"""A later PR adds a cell, a configuration of a new model family (its
sizes, its cost functions, its plain reference), a traffic mix and a
per-layer metric as new files and manifest entries, and edits no file
that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NEW_MIX = {
    "kind": "token", "loop": "open", "qos": "gold", "processes": 1,
    "drain_s": 10.0,
    "arrivals": {"process": "poisson", "rate_per_s": 4.0,
                 "fixed_count": True},
    "prompt_len": {"dist": "uniform_int", "min": 3, "max": 6},
    "max_new": {"dist": "fixed", "value": 3},
    "sharing": {"prefix_len": 2, "groups": 1},
    "stop_token": -1}

NEW_READER = '''
"""Engine: tokens the engine emitted per admitted session."""


def read(run):
    sessions = run.counters["sessions"]
    return run.counters["tokens"] / sessions if sessions else None
'''


# a new family: the harness finds ``cost/<family>.py`` and
# ``reference/<family>.py`` by the name the configuration's file gives.
# (tensor_llm serves one architecture, so this family's reference is the
# same arithmetic under its own name, and says that it judged.)
NEW_REFERENCE = '''
"""Plain reference of the family ``toy_other``."""

import sys

from benchmarks.reference import streamformer_lm


def served_tokens_near_top(params, model, prompt, served, slack):
    print("judged by reference/toy_other.py", file=sys.stderr)
    return streamformer_lm.served_tokens_near_top(params, model, prompt,
                                                  served, slack)
'''

NEW_COST = '''
"""Operations and bytes of the family ``toy_other``."""


def kv_bytes_per_position(model):
    return 1000


def decode_step_cost(model, lanes, attended):
    return 7 * lanes, 1000 * attended
'''


def digest(root):
    out = {}
    for base, _, files in os.walk(root):
        if "__pycache__" in base or "bench_out" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_a_mix_and_a_metric_arrive_as_files_alone(tmp_path):
    ignore = shutil.ignore_patterns("__pycache__", "bench_out")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks", ignore=ignore)
    toy = tmp_path / "tests" / "benchmarks" / "toy"
    shutil.copytree(os.path.join(ROOT, "tests", "benchmarks", "toy"), toy,
                    ignore=ignore)
    before = digest(tmp_path)

    # the later PR: a directory of its own, three new files, new entries
    new = tmp_path / "benchmarks_r99"
    for folder in ("traffic", "layer_metrics", "configs", "cost",
                   "reference"):
        (new / folder).mkdir(parents=True)
    (new / "traffic" / "toy_shared.json").write_text(json.dumps(NEW_MIX))
    (new / "layer_metrics" / "tokens_per_session.py").write_text(
        NEW_READER)
    config = json.loads((toy / "configs" / "toy_lm.json").read_text())
    config["family"] = "toy_other"
    (new / "configs" / "toy_other.json").write_text(json.dumps(config))
    (new / "cost" / "toy_other.py").write_text(NEW_COST)
    (new / "reference" / "toy_other.py").write_text(NEW_REFERENCE)
    doc = json.loads((toy / "manifest.json").read_text())
    doc["paths"].append("benchmarks_r99")
    doc["configs"].append({
        "name": "toy_other", "source": "https://example.org/toy_other",
        "file": "benchmarks_r99/configs/toy_other.json", "reduced": [],
        "why": "a configuration of a family the benchmark did not have"})
    doc["workloads"].append({
        "name": "toy_other.shared", "config": "toy_other",
        "traffic": "toy_shared", "chips": 1,
        "why": "requests sharing a 2-token prefix: a new mix from "
               "parameters the generator already reads"})
    doc["end_to_end"][1]["workloads"].append("toy_other.shared")
    doc["per_layer"].append({
        "name": "tokens_per_session", "unit": "tokens",
        "better": "higher", "source": "program_counter",
        "layer": "engine", "moves": "ttft_p95_ms",
        "workloads": ["toy_other.shared"]})
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(doc))

    code = ("import sys; from benchmarks import run; "
            "run.device_or_exit = lambda chips: {'platform': 'cpu', "
            "'kind': 'TPU v5 lite', 'count': 1}; "
            "sys.exit(run.main(['--workload', 'toy_other.shared', "
            "'--seed', '5', '--seconds', '1', '--trace', '1']))")
    # the copy's own harness runs (cwd first on the path); the program
    # comes from the repository
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0      # a traced run with no device op ...
    assert "no operation ran on a device" in proc.stderr   # ... is refused

    code = code.replace("'--trace', '1'", "'--trace', '0'")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path),
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    detail, also, last = (json.loads(x) for x in lines[-3:])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == 4                  # 4 /s x 1 s
    assert set(last["metrics"]) == {"ttft_p95_ms", "setup_s"}
    # the new reader ran, through the manifest, on the new cell
    assert also["also"]["tokens_per_session"]["value"] == 3.0
    assert detail["outcomes"] == {"done": 4}
    # the new family's reference judged the streams, and its cost
    # functions priced the cache the traffic wrote (1000 B a position)
    assert "judged by reference/toy_other.py" in proc.stderr
    assert detail["checks"]["sampled"] >= 2
    assert detail["memory"]["kv_bytes_per_position"] == 1000
    # nothing that was there changed
    after = digest(tmp_path)
    assert {k: after[k] for k in before} == before
