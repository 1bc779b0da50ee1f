"""What the first run on the chip settled, held from the CPU side: the
smoke refuses a host with no chip, and every entry point shares one
compile cache whose place the environment — and nothing else — can move.
None of this builds a model."""

import os
import re
import subprocess
import sys
import time

from nnstreamer_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_host_with_no_chip():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""                     # no result line
    assert "platform 'cpu'" in proc.stderr and "not a tpu" in proc.stderr
    # it stopped at the device check: a model build alone takes longer
    assert "no model was built" in proc.stderr
    assert time.monotonic() - t0 < 60


def test_chip_smoke_checks_the_device_before_any_phase():
    src = open(os.path.join(ROOT, "chip_smoke.py")).read()
    main = src[src.index("def main()"):]
    first = main.index("phase_device()")
    assert all(first < main.index(other) for other in
               ("phase_kernels()", "phase_stream()", "phase_llm(",
                "phase_mesh()"))
    # one mode: no arguments, no environment switches
    assert not re.search(r"argparse|sys\.argv|os\.environ|getenv", src)


def test_chip_smoke_ends_on_the_verdict_line(monkeypatch, tmp_path,
                                            capsys):
    """The driver parses the LAST stdout line: exactly ``ok`` and
    ``device`` {platform, kind, count}; the detail goes on the line
    before it.  Phases are stubbed — this pins the shape, not the chip."""
    import json

    import jax

    sys.path.insert(0, ROOT)
    import chip_smoke as cs

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    phase = {"ok": True, "setup_s": 1.0, "run_s": 0.5}
    monkeypatch.setattr(cs, "enable_compile_cache", lambda: str(tmp_path))
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda fn: None)
    monkeypatch.setattr(cs, "phase_device", lambda: {
        "device": dict(device), "versions": {}, "native": "libnnstw.so"})
    for name in ("phase_kernels", "phase_stream", "phase_mesh"):
        monkeypatch.setattr(cs, name, lambda: dict(phase))
    monkeypatch.setattr(cs, "phase_llm", lambda sid, **kw: dict(
        phase, streams=[[1, 2, 3]]))
    assert cs.main() == 0
    detail, verdict = map(json.loads,
                          capsys.readouterr().out.splitlines()[-2:])
    assert verdict == {"ok": True, "device": device}
    assert type(verdict["device"]["count"]) is int
    assert set(detail["phases"]) == {"kernels", "stream", "llm_dense",
                                     "llm_paged", "llm_hybrid",
                                     "llm_latent", "mesh"}
    assert detail["setup_s_total"] == 7.0


def test_cache_dir_is_the_environments_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append(name))
    assert platform.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; no directory is set in code
    assert "jax_compilation_cache_dir" not in calls


def test_cache_dir_is_fixed_inside_the_checkout_otherwise(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(ROOT, ".jax_cache")
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    assert platform.enable_compile_cache() == fixed
    assert calls["jax_compilation_cache_dir"] == fixed
    # git ignores it, so a fresh checkout starts with none
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def _sources():
    for top in ("nnstreamer_tpu", "tools", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(ROOT, top)):
            for name in files:
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)
    for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        yield os.path.join(ROOT, name)


def test_nothing_else_places_a_compile_cache():
    """tensor_llm, the filter backends, launch.py, the bench child and
    the smoke all go through utils/platform.py; none names a directory
    or a threshold of its own."""
    own = os.path.join(ROOT, "nnstreamer_tpu", "utils", "platform.py")
    setters = [p for p in _sources() if p != own and re.search(
        r"compilation_cache|persistent_cache",
        open(p, encoding="utf-8").read())]
    assert setters == []
    for rel in ("nnstreamer_tpu/llm/element.py",
                "nnstreamer_tpu/filter/backends/_jitexec.py",
                "nnstreamer_tpu/launch.py", "bench.py", "chip_smoke.py"):
        assert "enable_compile_cache()" in open(
            os.path.join(ROOT, rel), encoding="utf-8").read(), rel


def test_device_label_names_platform_kind_and_count():
    label = platform.device_label()
    assert label["platform"] == "cpu" and label["device_count"] == 8
    assert set(label) == {"platform", "device_kind", "device_count"}
