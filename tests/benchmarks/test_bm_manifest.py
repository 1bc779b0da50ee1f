"""The manifests validate, every name they mention resolves to a file,
and the validator refuses what the benchmark's contract refuses."""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import manifest as mf  # noqa: E402

MANIFESTS = ["BENCHMARK.json", "stream_manifest",
             "tests/benchmarks/toy/manifest.json"]


def load(rel):
    return mf.Manifest(os.path.join(ROOT, rel), root=ROOT)


@pytest.mark.parametrize("rel", MANIFESTS)
def test_manifest_validates_and_every_name_resolves(rel, request):
    # the stream tier's cells come without bounds (conftest.py)
    m = (request.getfixturevalue(rel) if rel == "stream_manifest"
         else load(rel))
    assert len(open(m.path, "rb").read()) <= 64 * 1024
    for cell in m.doc["workloads"]:
        cfg = m.config(cell["config"])
        mix = m.traffic(cell["traffic"])
        driver = mix.get("driver") or cfg["driver"]
        assert hasattr(m.module("drivers", driver), "Driver")
        for kind, folder in (("end_to_end", "e2e_metrics"),
                             ("per_layer", "layer_metrics")):
            reported = m.metrics(cell["name"], kind)
            assert reported, (cell["name"], kind)
            for entry in reported:
                assert callable(m.module(folder, entry["name"]).read)
        family = cfg["family"]
        assert os.path.isfile(m.find("reference", family + ".py"))
        assert os.path.isfile(m.find("cost", family + ".py"))
    assert "TPU v5 lite" in json.load(open(m.find("peaks.json")))


def test_command_is_the_contracts_and_run_seconds_fits_a_full_check():
    doc = load("BENCHMARK.json").doc
    assert doc["command"] == ["python3", "benchmarks/run.py"]
    assert doc["paths"] == ["benchmarks", "tests/benchmarks"]
    # the driver's budget with the full 24 cells (builder's contract)
    runs = 2 + 14 * 24
    assert (runs * (doc["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)


def test_every_layer_is_a_row_of_perf_md_section_3():
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as fh:
        rows = {line.split("|")[1].strip().strip("`")
                for line in fh if line.startswith("| `")}
    layers = {m["layer"] for m in load("BENCHMARK.json").doc["per_layer"]}
    assert layers and layers <= rows, layers - rows


def test_token_configuration_says_what_it_is():
    cfg = load("BENCHMARK.json").config("sflm_gpt2m")
    assert "NOT a public model" in cfg["statement"]
    assert cfg["reduced"] == [] and cfg["assumed"] and cfg["departures"]
    model, borrowed = cfg["model"], cfg["borrowed"]
    # GPT-2 medium's published widths and depth, under the grammar's names
    assert (model["dim"], model["heads"], model["layers"], model["max_seq"],
            model["vocab"], model["mlp"]) == (
        borrowed["n_embd"], borrowed["n_head"], borrowed["n_layer"],
        borrowed["n_positions"], borrowed["vocab_size"],
        borrowed["n_inner"])
    assert model["head_dim"] * model["heads"] == model["dim"]
    plan, el = cfg["memory_plan"], cfg["element"]
    pool = ((el["slots"] + 1) * model["layers"] * model["max_seq"]
            * model["heads"] * model["head_dim"] * 2 * 2)
    assert plan["kv_pool_bytes"] == pool
    assert el["slots"] == el["batch"] and el["max-new-tokens"] == 256


def test_saturating_mix_has_a_client_per_slot_and_lengths_fit_the_cache():
    m = load("BENCHMARK.json")
    cfg = m.config("sflm_gpt2m")
    sat, chat = m.traffic("decode_saturate"), m.traffic("steady_short")
    assert sat["clients"] == cfg["element"]["slots"]
    assert sat["loop"] == "closed" and chat["loop"] == "open"
    for mix in (sat, chat):
        longest = mix["prompt_len"]["max"] + mix["max_new"].get(
            "max", mix["max_new"].get("value"))
        assert longest <= cfg["model"]["max_seq"]
        assert mix["max_new"].get("max", mix["max_new"].get("value")) \
            <= cfg["element"]["max-new-tokens"]
    assert isinstance(chat["arrivals"]["rate_per_s"], float)


def _bad(change):
    doc = copy.deepcopy(load("BENCHMARK.json").doc)
    change(doc)
    return doc


def _the(doc, kind, name):
    """The entry of ``doc[kind]`` called ``name``: by name, so an entry
    appended later moves no breach onto another entry."""
    return next(e for e in doc[kind] if e["name"] == name)


CELL, CONFIG = "gpt2m.decode_saturate", "sflm_gpt2m"
BREACHES = {
    "extra key": lambda d: d.update(notes="x"),
    "bound over a tenth":
        lambda d: _the(d, "end_to_end", "tok_s").update(bound=0.2),
    "bound under a hundredth":
        lambda d: _the(d, "end_to_end", "tok_s").update(bound=0.001),
    "run_seconds too long": lambda d: d.update(run_seconds=52),
    "reduced names a width":
        lambda d: _the(d, "configs", CONFIG).update(reduced=["head_dim"]),
    "reduced names the hidden size":
        lambda d: _the(d, "configs", CONFIG).update(
            reduced=["hidden_size"]),
    "reduced names the intermediate size":
        lambda d: _the(d, "configs", CONFIG).update(
            reduced=["intermediate_size"]),
    "reduced names a width beside the depth":
        lambda d: _the(d, "configs", CONFIG).update(
            reduced=["num_hidden_layers", "kv_lora_rank"]),
    "unknown moves": lambda d: _the(
        d, "per_layer", "decode_step_ms").update(moves="fps"),
    "layer metric in a cell the manifest has not":
        lambda d: _the(d, "per_layer", "decode_step_ms").update(
            workloads=["gpt2m.nowhere"]),
    "layer metric where its end-to-end metric is not":
        lambda d: _the(d, "per_layer", "decode_step_ms").update(
            workloads=["gpt2m.steady_short"]),
    "one cell": lambda d: d.update(workloads=[_the(d, "workloads", CELL)]),
    "no setup_s": lambda d: d["end_to_end"].remove(
        _the(d, "end_to_end", "setup_s")),
    "pair twice": lambda d: d["workloads"].append(
        dict(_the(d, "workloads", CELL), name="again")),
    "name not plain":
        lambda d: _the(d, "workloads", CELL).update(name="a b"),
    "why too long":
        lambda d: _the(d, "workloads", CELL).update(why="y" * 201),
    "config file outside paths":
        lambda d: _the(d, "configs", CONFIG).update(file="bench.py"),
    "command leaves the repo":
        lambda d: d.update(command=["python3", "../x.py"]),
    "end-to-end from a program counter":
        lambda d: _the(d, "end_to_end", "tok_s").update(
            source="program_counter"),
    "two cells on four chips":
        lambda d: [w.update(chips=4) for w in d["workloads"]],
    "config no cell uses": lambda d: d["configs"].append(
        dict(_the(d, "configs", CONFIG), name="idle",
             file="benchmarks/configs/mnv2_224.json")),
    "roofline not in percent": lambda d: _the(
        d, "per_layer", "decode_step_roofline").update(unit="share"),
    "layer with a space": lambda d: _the(
        d, "per_layer", "compiles_in_window").update(layer="Jit exec"),
    "layer too long": lambda d: _the(
        d, "per_layer", "compiles_in_window").update(layer="l" * 65),
}

#: a key that counts layers is a depth, whatever width its name also
#: mentions: a cut of it names the source's own key
DEPTH_CUTS = {
    "the depth by its source key": ["num_hidden_layers"],
    "the depth beside the vocabulary":
        ["num_hidden_layers", "vocab_size"],
    "a count of prediction layers": ["num_nextn_predict_layers"],
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_validator_refuses(breach):
    with pytest.raises(mf.ManifestError):
        mf.validate(_bad(BREACHES[breach]))


@pytest.mark.parametrize("cut", sorted(DEPTH_CUTS))
def test_validator_takes_a_depth_cut_by_its_source_key(cut):
    mf.validate(_bad(lambda d: _the(d, "configs", CONFIG).update(
        reduced=DEPTH_CUTS[cut])))


def test_every_per_layer_metric_names_its_cells():
    """A metric with no ``workloads`` list is owed by every cell that
    reports what it moves, cells appended later too: the benchmark's check
    refuses a new cell while such a metric stands."""
    doc = load("BENCHMARK.json").doc
    cells = {w["name"] for w in doc["workloads"]}
    for entry in doc["per_layer"]:
        where = entry.get("workloads")
        assert isinstance(where, list) and where, entry["name"]
        assert set(where) <= cells, entry["name"]


def test_unknown_cell_and_missing_file_are_named():
    m = load("BENCHMARK.json")
    with pytest.raises(mf.ManifestError, match="gpt2m.decode_saturate"):
        m.cell("nope")
    with pytest.raises(mf.ManifestError, match="traffic/nope.json"):
        m.traffic("nope")
