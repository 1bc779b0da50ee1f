"""The benchmark takes a new configuration, cell, traffic mix and
per-layer metric as new files and appended entries alone.

A copy of the real benchmark gets what a new configuration brings:
a configuration of a family of its own (its file, cost functions and
plain reference), a traffic mix, a reader, and the entries that name
them appended to ``BENCHMARK.json``, the cell among the family-blind
readers' cells.  Then the accepted cells' pinned tests run on the copy
and pass.  The control removes one accepted cell from one reader's list
on a second copy, and the same tests must fail: the pins still guard
what every accepted cell reports.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: every test of the three files that reads BENCHMARK.json and runs no
#: model and no harness window; one added there later belongs
#: here too
PINNED = [
    "test_bm_manifest.py::"
    "test_manifest_validates_and_every_name_resolves[BENCHMARK.json]",
    "test_bm_manifest.py::"
    "test_command_is_the_contracts_and_run_seconds_fits_a_full_check",
    "test_bm_manifest.py::test_every_layer_is_a_row_of_perf_md_section_3",
    "test_bm_manifest.py::test_every_per_layer_metric_names_its_cells",
    "test_bm_manifest.py::test_token_configuration_says_what_it_is",
    "test_bm_manifest.py::"
    "test_saturating_mix_has_a_client_per_slot_and_lengths_fit_the_cache",
    "test_bm_manifest.py::test_validator_refuses",
    "test_bm_manifest.py::test_validator_takes_a_depth_cut_by_its_source_key",
    "test_bm_manifest.py::test_unknown_cell_and_missing_file_are_named",
    "test_bm_dsv3.py::test_benchmark_json_validates_with_the_new_cell",
    "test_bm_dsv3.py::test_configuration_keeps_every_published_width",
    "test_bm_dsv3.py::test_memory_plan_is_the_arithmetic_of_the_shapes",
    "test_bm_dsv3.py::"
    "test_saturating_mix_has_a_client_per_slot_and_lengths_fit",
    "test_bm_dsv3.py::"
    "test_cell_reports_the_family_blind_metrics_and_its_own",
    "test_bm_dsv3.py::test_parameter_count_is_the_programs_tree",
    "test_bm_dsv3.py::test_readers_on_a_hand_worked_reduction",
    "test_bm_dsv3.py::test_readers_find_nothing_in_another_familys_step",
    "test_bm_phi4flash.py::test_configuration_keeps_every_published_width",
    "test_bm_phi4flash.py::test_memory_plan_is_the_arithmetic_of_the_shapes",
    "test_bm_phi4flash.py::"
    "test_saturating_mix_has_a_client_per_slot_and_lengths_fit",
    "test_bm_phi4flash.py::"
    "test_cell_reports_the_family_blind_metrics_and_its_own",
    "test_bm_phi4flash.py::test_parameter_count_is_the_programs_tree",
    "test_bm_phi4flash.py::test_readers_on_a_hand_worked_reduction",
    "test_bm_phi4flash.py::"
    "test_readers_find_nothing_in_another_familys_step",
]

FAMILY = "room_probe"
CELL = "room_probe.saturate"
MIX = "room_probe_saturate"
READER = "room_probe_tokens"
#: the lists a cell of a family with routed experts or recurrent state
#: joins: its end-to-end metric, the metrics every cell reports, and
#: the family-blind readers
JOINS = ["tok_s", "compiles_in_window", "decode_step_ms",
         "routed_experts_roofline", "moe_ms_per_step", "ssm_ms_per_step"]

COST = '''
"""Operations and bytes of the family ``room_probe``: those of
``streamformer_lm``, under a name of its own."""

from benchmarks.cost.streamformer_lm import *  # noqa: F401,F403
'''

REFERENCE = '''
"""Plain reference of the family ``room_probe``: that of
``streamformer_lm``, under a name of its own."""

from benchmarks.reference.streamformer_lm import *  # noqa: F401,F403
'''

NEW_READER = '''
"""Engine: tokens the engine emitted per admitted session."""


def read(run):
    sessions = run.counters["sessions"]
    return run.counters["tokens"] / sessions if sessions else None
'''


def _copy_of_the_benchmark(dest):
    """``BENCHMARK.json``, ``PERF.md``, ``benchmarks/`` and
    ``tests/benchmarks/`` under ``dest``, in their own layout."""
    ignore = shutil.ignore_patterns("__pycache__", "bench_out")
    for rel in ("benchmarks", os.path.join("tests", "benchmarks")):
        shutil.copytree(os.path.join(ROOT, rel), dest / rel, ignore=ignore)
    for rel in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(os.path.join(ROOT, rel), dest / rel)


def _add_a_cell(dest):
    """What a new configuration brings: new files, appended entries."""
    bench = dest / "benchmarks"
    config = json.loads((bench / "configs" / "sflm_gpt2m.json").read_text())
    config["family"] = FAMILY
    new_files = {
        bench / "configs" / f"{FAMILY}.json": json.dumps(config, indent=1),
        bench / "cost" / f"{FAMILY}.py": COST,
        bench / "reference" / f"{FAMILY}.py": REFERENCE,
        bench / "traffic" / f"{MIX}.json":
            (bench / "traffic" / "decode_saturate.json").read_text(),
        bench / "layer_metrics" / f"{READER}.py": NEW_READER,
    }
    for path, text in new_files.items():
        assert not path.exists(), path
        path.write_text(text)
    doc = json.loads((dest / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": FAMILY,
        "source": "https://huggingface.co/openai-community/gpt2-medium",
        "file": f"benchmarks/configs/{FAMILY}.json",
        "reduced": ["num_hidden_layers"],
        "why": "a family the benchmark did not have, cut in depth by the "
               "source's own key"})
    doc["workloads"].append({
        "name": CELL, "config": FAMILY, "traffic": MIX, "chips": 1,
        "why": "closed loop, a client a slot: the new family's decode "
               "step"})
    for kind in ("end_to_end", "per_layer"):
        for entry in doc[kind]:
            if entry["name"] in JOINS:
                entry["workloads"].append(CELL)
    doc["per_layer"].append({
        "name": READER, "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine", "moves": "tok_s",
        "workloads": [CELL]})
    return doc


def _run_pinned(dest):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    # the copy's benchmark and tests (cwd first on the path); the
    # program comes from the repository
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    here = os.path.join("tests", "benchmarks")
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rf", "-p",
         "no:cacheprovider", "-p", "no:randomly",
         *(os.path.join(here, t) for t in PINNED)],
        cwd=str(dest), env=env, capture_output=True, text=True,
        timeout=240)


@pytest.mark.parametrize("case", ["appended", "accepted_cell_dropped"])
def test_a_new_cell_arrives_by_additions_alone(tmp_path, case):
    _copy_of_the_benchmark(tmp_path)
    doc = _add_a_cell(tmp_path)
    if case == "accepted_cell_dropped":
        entry = next(m for m in doc["per_layer"]
                     if m["name"] == "ssm_ms_per_step")
        entry["workloads"].remove("phi4flash.reason_saturate")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    # only BENCHMARK.json changed among the files that were there
    for rel in ("PERF.md", os.path.join("tests", "benchmarks",
                                        "test_bm_dsv3.py")):
        with open(os.path.join(ROOT, rel), "rb") as fh:
            assert (tmp_path / rel).read_bytes() == fh.read()

    proc = _run_pinned(tmp_path)
    out = proc.stdout[-4000:] + proc.stderr[-2000:]
    summary = proc.stdout.strip().splitlines()[-1]
    assert "error" not in summary, out
    if case == "appended":
        assert proc.returncode == 0, out
        assert re.search(r"\b\d+ passed\b", summary), out
    else:
        failed = set(re.findall(r"^FAILED (\S+)", proc.stdout, re.M))
        assert proc.returncode == 1, out
        # the cell's own set, and the count the other family's file
        # keeps for it
        assert failed == {
            f"tests/benchmarks/{name}::"
            "test_cell_reports_the_family_blind_metrics_and_its_own"
            for name in ("test_bm_phi4flash.py", "test_bm_dsv3.py")}, out
