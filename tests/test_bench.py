"""bench.py contract: one bounded child per configuration, and a row is a
measurement or a failure — a failing child fails the row AND the exit
code, and the measurement path refuses a host with no chip."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402


class _Proc:
    def __init__(self, returncode, stdout):
        self.returncode, self.stdout = returncode, stdout


def _fake_run(monkeypatch, returncode=0, stdout="", seen=None):
    def run(cmd, env=None, **kw):
        if seen is not None:
            seen.update(cmd=cmd, env=env, kw=kw)
        return _Proc(returncode, stdout)

    monkeypatch.setattr(bench.subprocess, "run", run)


def test_row_is_the_childs_last_line(monkeypatch):
    _fake_run(monkeypatch, 0,
              "a warning on stdout\n"
              '{"metric": "m", "value": 3.0, "unit": "fps"}\n')
    row = bench.run_config("mobilenet", cpu=False, deadline=1)
    assert row == {"metric": "m", "value": 3.0, "unit": "fps"}


def test_failing_child_fails_the_row_even_after_printing_one(monkeypatch):
    _fake_run(monkeypatch, 1, '{"metric": "m", "value": 3.0}\n')
    row = bench.run_config("lm", cpu=False, deadline=1)
    assert row["error"] == "child exited 1" and row["value"] is None
    assert row["metric"] == bench.CONFIG_METRICS["lm"]
    assert row["unit"] == "decode_tok_s"


def test_child_without_a_row_fails(monkeypatch):
    for out in ("", "Terminated\n", '{"not_a_result": 1}\n'):
        _fake_run(monkeypatch, 0, out)
        row = bench.run_config("mobilenet", cpu=False, deadline=1)
        assert "error" in row and row["value"] is None, out


def test_deadline_overrun_fails_the_row(monkeypatch):
    def run(cmd, **kw):
        raise subprocess.TimeoutExpired(cmd, kw["timeout"])

    monkeypatch.setattr(bench.subprocess, "run", run)
    row = bench.run_config("mobilenet", cpu=True, deadline=7)
    assert "7s deadline" in row["error"]
    assert row["metric"].endswith("_cpu")


def test_cpu_is_explicit_in_env_and_argv(monkeypatch):
    seen = {}
    _fake_run(monkeypatch, 0, '{"metric": "m", "value": 1.0}\n', seen)
    bench.run_config("mobilenet", cpu=True, deadline=5, stream_batch=64)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["NNS_TPU_BENCH_BATCH"] == "64"
    assert seen["cmd"][-1] == "--cpu" and "--_child" in seen["cmd"]
    assert seen["kw"]["timeout"] == 5
    seen.clear()
    bench.run_config("mobilenet", cpu=False, deadline=5)
    assert "--cpu" not in seen["cmd"]


def test_main_exit_code_follows_the_rows(monkeypatch, capsys):
    rows = iter([{"metric": "a", "value": 1.0},
                 {"metric": "b", "value": None, "error": "boom"}])
    monkeypatch.setattr(bench, "run_config",
                        lambda *a, **k: next(rows))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--sweep-batch", "8,16"])
    assert bench.main() == 1
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [r["stream_batch"] for r in out] == [8, 16]
    monkeypatch.setattr(bench, "run_config",
                        lambda *a, **k: {"metric": "a", "value": 1.0})
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    assert bench.main() == 0


def test_no_chip_and_no_cpu_flag_exits_nonzero():
    """The real thing, end to end: on this CPU-only host the child finds
    no TPU, builds nothing, and the parent's exit code says so."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--config", "lm"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 1
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["error"] == "child exited 1" and row["value"] is None
    assert "platform 'cpu'" in proc.stderr and "not a tpu" in proc.stderr


def test_batched_roofline_frac_over_one_carries_note():
    """A measured fps above the computed ceiling flags the ceiling as
    conservative (XLA cost-analysis bytes overcount on attention-heavy
    graphs) instead of silently publishing frac>1."""
    # vit-shaped: memory-bound, measured ABOVE the bytes-implied ceiling
    f = bench._batched_roofline_fields(
        bfps=6769.43, bflops=9.313e9, bbytes=138e6,
        peak=197e12, bw=819e9)
    assert f["batched_roofline_frac"] > 1
    assert "conservative" in f["batched_roofline_note"]
    assert f["batched_roofline_bound"] == "memory"
    # an under-ceiling row carries no note
    f2 = bench._batched_roofline_fields(
        bfps=1000.0, bflops=9.313e9, bbytes=138e6,
        peak=197e12, bw=819e9)
    assert f2["batched_roofline_frac"] < 1
    assert "batched_roofline_note" not in f2
