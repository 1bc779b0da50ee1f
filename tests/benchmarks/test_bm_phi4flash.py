"""The ``phi4flash`` family in the benchmark: its configuration at the
published widths, its cost functions worked by hand at a tiny size and
against the program's own parameter tree, its cell's traffic, its three
per-layer readers on a hand-made reduction, and a toy cell of the family
through the harness's own ``main`` on the CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.cost import phi4flash as cost  # noqa: E402
from benchmarks.cost.roofline import least_seconds  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.record import Run  # noqa: E402

TOY = os.path.join(ROOT, "tests", "benchmarks", "toy",
                   "manifest_phi4flash.json")
FIXTURE = os.path.join(ROOT, "tests", "benchmarks", "fixtures",
                       "hybrid_program.json")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "phi4flash.reason_saturate"
#: the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 10240, "layer_norm_eps": 1e-05,
             "max_position_embeddings": 262144, "mb_per_layer": 2,
             "model_type": "phi4flash", "num_attention_heads": 40,
             "num_hidden_layers": 32, "num_key_value_heads": 20,
             "resid_pdrop": 0, "sliding_window": 512,
             "tie_word_embeddings": True, "mlp_bias": False,
             "lm_head_bias": False, "vocab_size": 200064}
#: 8 layers: 3 Mamba, 2 window, 1 full, 1 memory unit, 1 cross
TINY = {"arch": "sambay_lm", "vocab": 50, "dim": 16, "heads": 4,
        "kv_heads": 2, "head_dim": 4, "mlp": 32, "layers": 8, "window": 8,
        "d_state": 4, "d_conv": 4, "expand": 2, "dt_rank": 1,
        "max_seq": 64, "dtype": "bfloat16"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(ROOT, "BENCHMARK.json"))


# -- the configuration and its cell --------------------------------------
def test_configuration_keeps_every_published_width(manifest):
    cfg = manifest.config("phi4_mini_flash")
    entry = next(c for c in manifest.doc["configs"]
                 if c["name"] == "phi4_mini_flash")
    assert entry["reduced"] == cfg["reduced"] == ["max_position_embeddings"]
    for key, value in PUBLISHED.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    model = cfg["model"]
    assert cfg["max_position_embeddings"] == model["max_seq"] == 16384
    assert (model["dim"], model["heads"], model["kv_heads"], model["mlp"],
            model["layers"], model["vocab"], model["window"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], cfg["vocab_size"], cfg["sliding_window"])
    assert model["head_dim"] * model["heads"] == model["dim"]
    assert model["dt_rank"] * 16 == model["dim"]
    assert model["dtype"] == "bfloat16" and model["arch"] == "sambay_lm"
    assert cfg["assumed"] and cfg["departures"] and cfg["weights"]
    assert cfg["source"] == entry["source"]
    assert set(cfg["reference"]) >= {"sampled_streams", "judged_tokens_min",
                                     "token_slack", "min_share", "why"}
    # no routed expert here: far tighter than sflm_gpt2m's share
    assert cfg["reference"]["min_share"] > manifest.config(
        "sflm_gpt2m")["reference"]["min_share"]


def test_memory_plan_is_the_arithmetic_of_the_shapes(manifest):
    cfg = manifest.config("phi4_mini_flash")
    model, el, plan = cfg["model"], cfg["element"], cfg["memory_plan"]
    s = el["slots"] + 1
    assert plan["state_bytes"]["kv"] == s * model["max_seq"] \
        * cost.kv_bytes_per_position(model)
    fixed = cost.fixed_state_bytes(model)
    assert plan["state_bytes"]["ring"] == s * fixed["ring"]
    assert plan["state_bytes"]["conv"] + plan["state_bytes"]["ssm"] \
        == s * fixed["mamba"]
    assert plan["pool_bytes"] == sum(plan["state_bytes"].values())
    # matrices in bfloat16; the float32 vectors and A_log are the rest
    assert 0 <= plan["weights_bytes"] - 2 * cost.total_params(model) \
        < 0.001 * plan["weights_bytes"]
    resident = plan["weights_bytes"] + plan["pool_bytes"]
    assert 0.25 * 16e9 < 0.65 * 16e9 < resident < 0.75 * 16e9
    assert el["slots"] == el["batch"] == 32 and el["page-size"] == 0


def test_saturating_mix_has_a_client_per_slot_and_lengths_fit(manifest):
    cfg = manifest.config("phi4_mini_flash")
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4_mini_flash", "reason_saturate", 1)
    mix = manifest.traffic("reason_saturate")
    assert mix["loop"] == "closed" and mix["qos"] == "gold"
    assert mix["clients"] == cfg["element"]["slots"]
    assert (mix["prompt_len"]["min"], mix["prompt_len"]["max"]) == (
        1024, 8192)
    new = mix["max_new"]["value"]
    assert mix["prompt_len"]["max"] + new <= cfg["model"]["max_seq"]
    assert new == cfg["element"]["max-new-tokens"] == 6144
    # the ramp is the clients' own deadline for a first token
    assert mix["ramp_s"] == 30.0 and mix["stop_token"] == -1


def test_cell_reports_the_family_blind_metrics_and_its_own(manifest):
    e2e = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    layer = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert layer == {
        "compiles_in_window", "decode_step_ms", "lanes_per_step",
        "decode_step_roofline", "step_mfu", "device_idle_share",
        "decode_thread_off_device_share", "step_gap_ms",
        "step_gap_engine_ms", "hybrid_state_device_share",
        "shared_kv_attn_roofline", "ssm_ms_per_step"}
    for name in ("hybrid_state_device_share", "shared_kv_attn_roofline",
                 "ssm_ms_per_step"):
        entry = next(m for m in manifest.doc["per_layer"]
                     if m["name"] == name)
        # family-blind: another family's cell may report it too
        assert CELL in entry["workloads"] and entry["moves"] == "tok_s"


# -- the cost functions --------------------------------------------------
def test_cost_by_hand_at_a_tiny_size():
    m = TINY
    assert cost.layer_counts(m) == {"mamba": 3, "window": 2, "full": 1,
                                    "gmu": 1, "cross": 1}
    per = cost.mixer_params(m)
    # dim 16, d_inner 32: in 16*64, out 32*16, x 32*(1+8), dt 1*32,
    # conv 4*32, A 32*4
    assert per["mamba"] == 1024 + 512 + 288 + 32 + 128 + 128 == 2112
    # q 16, k 8, v 8 wide: qkv 16*32, out 16*16
    assert per["window"] == per["full"] == 512 + 256
    assert per["gmu"] == 2 * 16 * 32 and per["cross"] == 2 * 16 * 16
    assert per["mlp"] == 3 * 16 * 32
    total = (8 * 1536 + 3 * 2112 + 3 * 768 + 1024 + 512 + 50 * 16)
    assert cost.total_params(m) == total == 23264
    # K and V rows of 8 bfloat16, once
    assert cost.kv_bytes_per_position(m) == 2 * 8 * 2 == 32
    assert cost.fixed_state_bytes(m) == {
        "ring": 2 * 8 * 32, "mamba": 3 * (32 * 4 * 4 + 3 * 32 * 2)}
    # 2 lanes attending 30 positions in all: two readers of the rows;
    # a query head against a position: 2 * 4 * 4 * 3 = 96 operations
    assert cost.shared_kv_cost(m, 2, 30) == (2 * 30 * 96, 2 * 30 * 32)
    flops, nbytes = cost.decode_step_cost(m, 2, 30)
    ring_rows = 2 * 8                   # mean position 15 >= window 8
    assert flops == (2 * 2 * total + 2 * 30 * 96 + 2 * ring_rows * 96
                     + 3 * 2 * 6 * 32 * 4)
    assert nbytes == (2 * total + 2 * 30 * 32 + 2 * 32
                      + 2 * (ring_rows + 2) * 32
                      + 2 * 2 * 3 * (512 + 192) + 2 * 50 * 4)
    # short lanes hold fewer ring rows than the window
    assert cost._window_rows(m, 4, 12) == 4 * 3
    # a prefill of 10 positions: layers 0-5 everywhere, 6-7 and the head
    # once; 55 causal pairs, a window's 8 + 8 + sum(1..8) = 52 pairs a
    # window layer, the cross layer's 10
    flops, nbytes = cost.prefill_cost(m, 10)
    front = 2 * (6 * 1536 + 3 * 2112 + 2 * 768 + 768)
    once = 2 * (2 * 1536 + 1024 + 512 + 50 * 16)
    assert flops == (10 * front + once + 96 * (55 + 2 * 52 + 10)
                     + 3 * 10 * 6 * 32 * 4)
    assert nbytes == (2 * total + 10 * 32 + 2 * 8 * 32
                      + 3 * (512 + 192) + 50 * 4)


def test_parameter_count_is_the_programs_tree(manifest):
    """3 852 M: the cost functions' matrices against every leaf the
    program would draw (shapes only), at the published widths."""
    import jax

    from nnstreamer_tpu.models import sambay_lm as sm

    model = manifest.config("phi4_mini_flash")["model"]
    cfg = sm.config_from_custom({k: str(v) for k, v in model.items()
                                 if k != "arch"})
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: sm.init_params(cfg, 0)))
    tree = sum(x.size for x in leaves)
    assert round(tree / 1e6) == 3853 and cost.total_params(model) \
        == 3851980800
    vectors = sum(x.size for x in leaves if x.ndim == 1)
    assert tree - vectors == cost.total_params(model)
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert nbytes == manifest.config("phi4_mini_flash")[
        "memory_plan"]["weights_bytes"]
    assert cost.kv_bytes_per_position(model) == 5120
    # half the step's least bytes are the hybrid's own state at the
    # cell's contexts (~5.8 k positions a lane)
    _, nbytes = cost.decode_step_cost(model, 32, 32 * 5800)
    state = nbytes - 2 * cost.total_params(model) - 32 * 200064 * 4
    assert 0.45 < state / nbytes < 0.6


# -- the three readers ---------------------------------------------------
def hybrid_run(manifest, program=None, cost_module=cost):
    run = Run(cell=manifest.cell(CELL),
              config=manifest.config("phi4_mini_flash"),
              traffic=manifest.traffic("reason_saturate"), seed=1,
              seconds=50.0, peaks=PEAKS, cost=cost_module)
    if program is not None:
        run.trace = {"path": FIXTURE, "program": program,
                     "counters": {"samples": [(1.0, 32, 32, 32 * 6000),
                                              (1.1, 32, 32, 32 * 6200)]}}
    return run


@pytest.fixture()
def program():
    with open(FIXTURE, encoding="utf-8") as fh:
        got = json.load(fh)
    got.pop("_comment")
    return got


def read(manifest, run, name):
    return manifest.module("layer_metrics", name).read(run)


def test_readers_on_a_hand_worked_reduction(manifest, program):
    run = hybrid_run(manifest, program)
    # rows 16 + 0.8, rings and recurrent rows 6 + 2 ms of 160 busy; the
    # 8 ms of unscoped operations are printed, not added
    assert read(manifest, run, "hybrid_state_device_share") == \
        pytest.approx(100 * (0.016 + 0.0008 + 0.006 + 0.002) / 0.16)
    beside = run.trace["hybrid_state"]
    assert beside["unscoped:jit__step_ms_per_step"] == pytest.approx(2.0)
    assert beside["unscoped:jit__step_share_of_busy"] == pytest.approx(5.0)
    assert beside["ms_per_step"]["sflm.kv_read"] == pytest.approx(4.0)
    # (8 + 4 + 6 + 2) ms over 4 steps
    assert read(manifest, run, "ssm_ms_per_step") == pytest.approx(5.0)
    # 8 readings of 32 x 6100 positions of 5 120 B at 819 GB/s, against
    # (56 + 8 + 16) ms over 4 steps
    flops, nbytes = cost.shared_kv_cost(run.config["model"], 32,
                                        32 * 6100)
    assert nbytes == 8 * 32 * 6100 * 5120
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "bytes"
    assert read(manifest, run, "shared_kv_attn_roofline") == \
        pytest.approx(100 * least * 4 / 0.08)
    assert run.trace["shared_kv"]["least_ms_per_step"] == \
        pytest.approx(least * 1e3)
    assert 0 < read(manifest, run, "shared_kv_attn_roofline") < 100


@pytest.mark.parametrize("name", ["hybrid_state_device_share",
                                  "shared_kv_attn_roofline",
                                  "ssm_ms_per_step"])
def test_readers_find_nothing_in_another_familys_step(manifest, program,
                                                      name):
    """No trace; a step that names none of the family's scopes (the
    parent's program, ``streamformer_lm``); a family whose cost
    functions price no shared cache: no metric, and nothing raises."""
    assert read(manifest, hybrid_run(manifest), name) is None
    program["device_by_scope"] = {"sflm.attn": 0.05, "sflm.kv_read": 0.03,
                                  "sflm.kv_write": 0.01, "sflm.mlp": 0.07}
    assert read(manifest, hybrid_run(manifest, program), name) is None
    program.pop("steps")
    assert read(manifest, hybrid_run(manifest, program), name) is None
    if name == "shared_kv_attn_roofline":
        from benchmarks.cost import streamformer_lm

        with open(FIXTURE, encoding="utf-8") as fh:
            whole = json.load(fh)
        run = hybrid_run(manifest, whole, cost_module=streamformer_lm)
        assert read(manifest, run, name) is None


# -- a toy cell of the family through the harness ------------------------
def rehearse(monkeypatch, capsys, cell, seconds):
    monkeypatch.setattr(harness, "device_or_exit", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    assert harness.main(["--manifest", TOY, "--workload", cell,
                         "--seed", "2600100061", "--seconds", seconds,
                         "--trace", "0"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[0]), json.loads(lines[-1])


def test_toy_manifest_validates_and_resolves():
    m = Manifest(TOY, root=ROOT)
    for cell in m.doc["workloads"]:
        cfg = m.config(cell["config"])
        assert hasattr(m.module("drivers", cfg["driver"]), "Driver")
        for kind, folder in (("end_to_end", "e2e_metrics"),
                             ("per_layer", "layer_metrics")):
            for entry in m.metrics(cell["name"], kind):
                assert callable(m.module(folder, entry["name"]).read)
        assert os.path.isfile(m.find("reference", cfg["family"] + ".py"))
        assert os.path.isfile(m.find("cost", cfg["family"] + ".py"))


def test_toy_cell_of_the_family_closed_loop(monkeypatch, capsys):
    """The whole command on the second family, a large seed: the
    driver's own check against the family's reference on the served
    weights says ``correct``."""
    detail, last = rehearse(monkeypatch, capsys, "toy_phi4flash.closed",
                            "1.5")
    assert last["correct"] is True, detail["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 4
    assert set(last["metrics"]) == {"tok_s", "setup_s"}
    checks = detail["checks"]
    # float32 toy against the float32 reference: every sampled token is
    # the reference's own argmax
    assert checks["sampled"] == 2 and checks["exact"] == checks["tokens"]
    assert checks["compiles_in_window"] == 0
    memory = detail["memory"]
    # a position costs ONE layer's K and V row (2 x 16 float32), the
    # pool is the three kinds of state over 4 slots and the scratch one
    assert memory["kv_bytes_per_position"] == 128
    assert memory["kv_pool_bytes"] == 5 * (
        64 * 128 + 2 * 8 * 128 + 3 * (4 * 64 * 4 + 3 * 64 * 4))


def test_toy_cell_of_the_family_open_loop(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_phi4flash.open", "2")
    assert last["correct"] is True, detail["checks"]
    assert last["attempted"] == 12 and last["failed"] == 0
    assert detail["outcomes"] == {"done": 12}
