"""The ``dsv3`` family in the benchmark: its configuration at the
published widths with the cut stated, its cost functions worked by hand
at a tiny size and against the program's own parameter tree, its cell's
traffic, its three per-layer readers on a hand-made reduction, and a toy
cell of the family through the harness's own ``main`` on the CPU."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.cost import dsv3 as cost  # noqa: E402
from benchmarks.cost.roofline import least_seconds  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.record import Run  # noqa: E402

TOY = os.path.join(ROOT, "tests", "benchmarks", "toy", "manifest_dsv3.json")
FIXTURE = os.path.join(ROOT, "tests", "benchmarks", "fixtures",
                       "latent_program.json")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "gigachat31.latent_saturate"
CONFIG = "gigachat31_702b_a36b"
#: the catalog row's config (model-configs guide, architectures.jsonl)
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 64,
    "num_nextn_predict_layers": 1, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256, "ep_size": 1,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 192,
    "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 8,
    "topk_group": 4, "num_experts_per_tok": 8, "moe_layer_freq": 1,
    "first_k_dense_replace": 3, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "num_key_value_heads": 64,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "rope_type": "yarn"},
    "attention_bias": False, "tie_word_embeddings": False,
    "model_type": "deepseek_v3"}
#: the cut, by the source's keys: what runs here
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "n_routed_experts": 16, "vocab_size": 16032,
       "max_position_embeddings": 8192, "num_nextn_predict_layers": 0}
#: 1 dense + 2 expert layers, 2 of 8 experts held
TINY = {"arch": "dsv3_lm", "vocab": 50, "dim": 16, "heads": 2,
        "q_lora_rank": 8, "kv_lora_rank": 4, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 2, "v_head_dim": 6, "mlp": 32,
        "expert_mlp": 8, "experts": 8, "experts_held": 2,
        "expert_rank": 0, "n_group": 2, "topk_group": 1,
        "experts_per_tok": 2, "shared_experts": 1, "dense_layers": 1,
        "layers": 3, "max_seq": 64, "dtype": "bfloat16"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest(os.path.join(ROOT, "BENCHMARK.json"))


# -- the configuration and its cell --------------------------------------
def test_benchmark_json_validates_with_the_new_cell(manifest):
    assert [w["name"] for w in manifest.doc["workloads"]][-1] == CELL
    assert manifest.doc["configs"][-1]["name"] == CONFIG
    assert manifest.doc["run_seconds"] == 50
    # four cells, none on four chips, the full check inside its limit
    cells = len(manifest.doc["workloads"])
    assert cells == 4 and all(w["chips"] == 1
                              for w in manifest.doc["workloads"])
    assert (2 + 14 * cells) * (50 + 60) + 2 * 90 * cells + 1200 < 43200


def test_configuration_keeps_every_published_width(manifest):
    cfg = manifest.config(CONFIG)
    entry = next(c for c in manifest.doc["configs"] if c["name"] == CONFIG)
    # the file names the cut by the source's keys, letter for letter;
    # BENCHMARK.json spells the depth ``layers`` (manifest.py's _WIDTH
    # matches 'hidden'), which the file also carries
    assert cfg["reduced"] == list(CUT)
    assert entry["reduced"] == ["layers"] + list(CUT)[1:]
    assert "_WIDTH" in cfg["reduced_why"]
    for key, value in PUBLISHED.items():
        if key not in entry["reduced"]:
            # num_hidden_layers among them: a top-level key that differs
            # from the source must be listed in BENCHMARK.json
            assert cfg[key] == value, key
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    model = cfg["model"]
    run = {"num_hidden_layers": cfg["layers"],
           **{k: cfg[k] for k in list(CUT)[1:]}}
    assert run == CUT
    assert (model["layers"], model["dense_layers"], model["experts_held"],
            model["vocab"], model["max_seq"]) == (5, 1, 16, 16032, 8192)
    # the floors: a whole period and four expert layers, at least 8
    # experts, an eighth of the vocabulary
    assert model["layers"] - model["dense_layers"] >= 4
    assert model["experts_held"] >= 8
    assert model["vocab"] * 8 == PUBLISHED["vocab_size"]
    # every width as published
    assert (model["dim"], model["mlp"], model["expert_mlp"],
            model["q_lora_rank"], model["kv_lora_rank"],
            model["qk_nope_head_dim"], model["qk_rope_head_dim"],
            model["v_head_dim"], model["heads"], model["experts"],
            model["n_group"], model["topk_group"],
            model["experts_per_tok"], model["shared_experts"],
            model["routed_scaling_factor"]) == (
        7168, 18432, 2048, 1536, 512, 128, 64, 192, 64, 256, 8, 4, 8, 1,
        2.5)
    rope = PUBLISHED["rope_scaling"]
    assert (model["rope_theta"], model["rope_factor"],
            model["rope_original_max"], model["beta_fast"],
            model["beta_slow"], model["mscale"],
            model["mscale_all_dim"]) == (
        100000, rope["factor"], rope["original_max_position_embeddings"],
        rope["beta_fast"], rope["beta_slow"], rope["mscale"],
        rope["mscale_all_dim"])
    assert model["dtype"] == "bfloat16" and model["arch"] == "dsv3_lm"
    assert model["experts"] // model["experts_held"] \
        == cfg["deployment"]["chips_sharing_a_layer"] == 16
    assert cfg["assumed"] and cfg["departures"] and cfg["weights"]
    assert cfg["source"] == entry["source"]
    assert set(cfg["reference"]) >= {"sampled_streams", "judged_tokens_min",
                                     "token_slack", "min_share", "why"}
    # the grammar takes the file's model as it stands
    from nnstreamer_tpu.llm.family import family_of_custom

    family, rest = family_of_custom({k: str(v) for k, v in model.items()})
    got = family.config_from_custom(rest)
    assert got.row == 576 and got.chunk == 512 and got.expert_layers == 4


def test_memory_plan_is_the_arithmetic_of_the_shapes(manifest):
    cfg = manifest.config(CONFIG)
    model, el, plan = cfg["model"], cfg["element"], cfg["memory_plan"]
    s = el["slots"] + 1
    # held 640 wide (576 filled to the chip's tile): what the program
    # reserves; the cost functions price the 576 a position needs
    assert plan["state_bytes"]["latent"] == (
        s * model["max_seq"] * model["layers"] * 640 * 2)
    assert cost.kv_bytes_per_position(model) == 5760
    assert plan["pool_bytes"] == sum(plan["state_bytes"].values())
    # matrices in bfloat16; the float32 vectors are the rest
    assert 0 <= plan["weights_bytes"] - 2 * cost.held_params(model) \
        < 0.001 * plan["weights_bytes"]
    resident = plan["weights_bytes"] + plan["pool_bytes"]
    assert 0.25 * 16e9 < 0.7 * 16e9 < resident < 0.8 * 16e9
    assert el["slots"] == el["batch"] == 64 and el["page-size"] == 0


def test_saturating_mix_has_a_client_per_slot_and_lengths_fit(manifest):
    cfg = manifest.config(CONFIG)
    cell = manifest.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "latent_saturate", 1)
    mix = manifest.traffic("latent_saturate")
    assert mix["loop"] == "closed" and mix["qos"] == "gold"
    assert mix["clients"] == cfg["element"]["slots"] == 64
    assert (mix["prompt_len"]["dist"], mix["prompt_len"]["min"],
            mix["prompt_len"]["max"]) == ("uniform_int", 1024, 3072)
    new = mix["max_new"]["value"]
    assert mix["prompt_len"]["max"] + new == cfg["model"]["max_seq"]
    assert new == cfg["element"]["max-new-tokens"] == 5120
    # the ramp is the clients' own deadline for a first token
    assert mix["ramp_s"] == 30.0 and mix["stop_token"] == -1
    assert mix["trace_slice_s"] == 3.0 and 2 <= mix["processes"] <= 4
    # no stream ends inside ramp + window while a step takes over this
    assert (30.0 + 50.0) / new * 1e3 == pytest.approx(15.625)


def test_cell_reports_the_family_blind_metrics_and_its_own(manifest):
    e2e = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert e2e == {"tok_s", "setup_s"}
    layer = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert layer == {
        "compiles_in_window", "decode_step_ms", "lanes_per_step",
        "decode_step_roofline", "step_mfu", "device_idle_share",
        "decode_thread_off_device_share", "step_gap_ms",
        "step_gap_engine_ms", "kv_copy_device_share",
        "latent_attn_roofline", "routed_experts_roofline",
        "moe_ms_per_step"}
    for name in ("latent_attn_roofline", "routed_experts_roofline",
                 "moe_ms_per_step"):
        entry = next(m for m in manifest.doc["per_layer"]
                     if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "tok_s"
        assert entry["layer"] == "model_math"
        assert callable(manifest.module("layer_metrics", name).read)
    # the accepted cells report what they reported
    for cell, n in (("gpt2m.decode_saturate", 11),
                    ("phi4flash.reason_saturate", 12),
                    ("gpt2m.steady_short", 7)):
        assert len(manifest.metrics(cell, "per_layer")) == n


# -- the cost functions --------------------------------------------------
def test_cost_by_hand_at_a_tiny_size():
    m = TINY
    p = cost.layer_params(m)
    # dim 16, 2 heads of 4 + 2 query/key dims and 6 value dims
    assert (p["w_qa"], p["w_qb"], p["w_kva"], p["w_kvb_k"], p["w_kvb_v"],
            p["w_o"]) == (16 * 8, 8 * 2 * 6, 16 * 6, 4 * 2 * 4, 4 * 2 * 6,
                          2 * 6 * 16)
    attn = 128 + 96 + 96 + 32 + 48 + 192
    assert cost.attention_params(m) == attn == 592
    assert (p["dense_mlp"], p["router"], p["one_expert"], p["shared"]) == (
        3 * 16 * 32, 16 * 8, 3 * 16 * 8, 3 * 16 * 8)
    beside = attn + 384 + 128
    assert cost.layer_beside_experts(m) == beside
    held = (attn + 1536) + 2 * (beside + 2 * 384) + 2 * 50 * 16
    assert cost.held_params(m) == held
    # a row of 4 latents + 2 rotated dims, bfloat16, three layers
    assert cost.kv_bytes_per_position(m) == 3 * 6 * 2 == 36
    # a token chooses an expert with probability 2/8
    assert cost.experts_reached(m, 3) == pytest.approx(2 * (1 - 0.75 ** 3))
    assert cost.pairs_here(m, 3) == 3 * 2 * 2 / 8 == 1.5
    flops, nbytes = cost.routed_experts_cost(m, 3)
    assert flops == 2 * 1.5 * 2 * 384
    assert nbytes == pytest.approx(
        2 * (cost.experts_reached(m, 3) * 384 * 2 + 1.5 * 16 * 6))
    # 3 lanes attending 20 positions in all: a head against a row is
    # 2 * 6 for the score and 2 * 4 for the sum
    assert cost._absorbed_flops_per_position(m) == 2 * (12 + 8)
    flops, nbytes = cost.latent_attn_cost(m, 3, 20)
    assert flops == 3 * (20 * 40 + 3 * 2 * (48 + 192))
    assert nbytes == 20 * 36 + 3 * (48 + 192) * 2
    token = 3 * attn + 1536 + 2 * (384 + 128) + 50 * 16
    flops, nbytes = cost.decode_step_cost(m, 3, 20)
    moe_f, moe_b = cost.routed_experts_cost(m, 3)
    assert flops == 3 * 2 * token + moe_f + 3 * 20 * 40
    assert nbytes == pytest.approx(
        token * 2 + moe_b + 3 * 16 * 2 + 20 * 36 + 3 * 36 + 3 * 50 * 4)
    # a prefill of 10 positions: 55 causal pairs of (6 + 6) dims a head
    flops, nbytes = cost.prefill_cost(m, 10)
    per_token = 3 * attn + 1536 + 2 * (384 + 128)
    assert flops == pytest.approx(
        10 * 2 * per_token + 2 * cost.pairs_here(m, 10) * 2 * 384
        + 3 * 55 * 2 * (2 * 6 + 2 * 6) + 2 * 50 * 16)
    assert nbytes == pytest.approx(
        (per_token + 800) * 2 + 2 * cost.experts_reached(m, 10) * 384 * 2
        + 10 * 16 * 2 + 10 * 36 + 50 * 4)


def test_parameter_count_is_the_programs_tree(manifest):
    """4 291 M held: the cost functions' matrices against every leaf the
    program would draw (shapes only), at the published widths."""
    import jax

    from nnstreamer_tpu.models import dsv3_lm as dm

    config = manifest.config(CONFIG)
    model = config["model"]
    cfg = dm.config_from_custom({k: str(v) for k, v in model.items()
                                 if k != "arch"})
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: dm.init_params(cfg, 0)))
    tree = sum(x.size for x in leaves)
    vectors = sum(x.size for x in leaves if x.ndim == 1)
    assert tree - vectors == cost.held_params(model) == 4291166208
    assert round(cost.held_params(model) / 1e6) == 4291
    assert round(cost.layer_beside_experts(model) / 1e5) == 1785
    assert cost.layer_params(model)["one_expert"] == 44040192
    assert round(cost.attention_params(model) / 1e5) == 1326
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    assert nbytes == config["memory_plan"]["weights_bytes"]
    assert cost.kv_bytes_per_position(model) == 5760
    state = jax.eval_shape(lambda: dm.init_state(cfg, 64))
    assert [s.size * s.dtype.itemsize for s in state] == list(
        config["memory_plan"]["state_bytes"].values())
    # 16 (1 - (31/32)^64) = 13.9 of the 16 held experts a layer a step
    assert cost.experts_reached(model, 64) == pytest.approx(13.9, abs=0.01)
    # a step's least bytes at 64 lanes and ~3.9 k attended positions:
    # about 9 GB, 11 ms, routed experts and latent rows 70 % of them
    flops, nbytes = cost.decode_step_cost(model, 64, 64 * 3900)
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "bytes" and 10.5e-3 < least < 11.5e-3
    _, moe = cost.routed_experts_cost(model, 64)
    rows = 64 * 3900 * 5760
    assert 0.65 < (moe + rows) / nbytes < 0.75
    # an expert is bound by its bytes at 2 tokens and at 32 alike
    assert 2 * 32 * 44040192 / (44040192 * 2) == 32 < 197e12 / 819e9


# -- the three readers ---------------------------------------------------
def latent_run(manifest, program=None, cost_module=cost):
    run = Run(cell=manifest.cell(CELL), config=manifest.config(CONFIG),
              traffic=manifest.traffic("latent_saturate"), seed=1,
              seconds=50.0, peaks=PEAKS, cost=cost_module)
    if program is not None:
        run.trace = {"path": FIXTURE, "program": program,
                     "counters": {"samples": [(1.0, 64, 64, 64 * 3800),
                                              (1.1, 64, 64, 64 * 4000)]}}
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
    run = latent_run(manifest, program)
    model = run.config["model"]
    # (2 + 32 + 4) ms over 4 steps
    assert read(manifest, run, "moe_ms_per_step") == pytest.approx(9.5)
    flops, nbytes = cost.routed_experts_cost(model, 64)
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "bytes"
    assert read(manifest, run, "routed_experts_roofline") == \
        pytest.approx(100 * least * 4 / 0.032)
    assert run.trace["routed_experts"]["spent_ms_per_step"] == \
        pytest.approx(8.0)
    flops, nbytes = cost.latent_attn_cost(model, 64, 64 * 3900)
    assert nbytes == 64 * 3900 * 5760 + 5 * (
        512 * 64 * 192 + 64 * 192 * 7168) * 2
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "bytes"
    # no sflm.kv_read in the step: the attention's scope alone
    assert read(manifest, run, "latent_attn_roofline") == \
        pytest.approx(100 * least * 4 / 0.036)
    assert run.trace["latent_attn"]["attended_mean"] == 64 * 3900
    for name in ("latent_attn_roofline", "routed_experts_roofline"):
        assert 0 < read(manifest, run, name) < 100
    # gathered rows count with the attention that reads them
    program["device_by_scope"]["sflm.kv_read"] = 0.012
    assert read(manifest, latent_run(manifest, program),
                "latent_attn_roofline") == pytest.approx(
        100 * least * 4 / 0.048)


@pytest.mark.parametrize("name", ["latent_attn_roofline",
                                  "routed_experts_roofline",
                                  "moe_ms_per_step"])
def test_readers_find_nothing_in_another_familys_step(manifest, program,
                                                      name):
    """No trace; a step that names none of the family's scopes (the
    parent's program); a family whose cost functions price no latent
    attention or routed experts: no metric, and nothing raises."""
    assert read(manifest, latent_run(manifest), name) is None
    program["device_by_scope"] = {"sflm.attn": 0.05, "sflm.kv_read": 0.03,
                                  "sflm.kv_write": 0.01, "sflm.mlp": 0.07}
    assert read(manifest, latent_run(manifest, program), name) is None
    with open(FIXTURE, encoding="utf-8") as fh:
        whole = json.load(fh)
    whole.pop("steps")
    assert read(manifest, latent_run(manifest, whole), name) is None
    if name != "moe_ms_per_step":
        from benchmarks.cost import streamformer_lm

        with open(FIXTURE, encoding="utf-8") as fh:
            whole = json.load(fh)
        run = latent_run(manifest, whole, cost_module=streamformer_lm)
        assert read(manifest, run, name) is None
        assert read(manifest, latent_run(manifest, whole,
                                         cost_module=None), name) is None


# -- a toy cell of the family through the harness ------------------------
def rehearse(monkeypatch, capsys, cell, seconds):
    monkeypatch.setattr(harness, "device_or_exit", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    assert harness.main(["--manifest", TOY, "--workload", cell,
                         "--seed", "3700100061", "--seconds", seconds,
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
    """The whole command on the third family, a large seed: the driver's
    own check against the family's reference on the served weights says
    ``correct``."""
    detail, last = rehearse(monkeypatch, capsys, "toy_dsv3.closed", "1.5")
    assert last["correct"] is True, detail["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 4
    assert set(last["metrics"]) == {"tok_s", "setup_s"}
    checks = detail["checks"]
    # float32 toy against the float32 reference: every sampled token is
    # the reference's own argmax
    assert checks["sampled"] == 2 and checks["exact"] == checks["tokens"]
    assert checks["compiles_in_window"] == 0
    memory = detail["memory"]
    # a position NEEDS 3 layers x (16 + 8) float32; the pool holds rows
    # 128 wide over 4 slots and the scratch one, and the counters
    assert memory["kv_bytes_per_position"] == 3 * 24 * 4
    assert memory["kv_pool_bytes"] == 5 * 64 * 3 * 128 * 4 + 2 * 4 * 4


def test_toy_cell_of_the_family_open_loop(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_dsv3.open", "2")
    assert last["correct"] is True, detail["checks"]
    assert last["attempted"] == 12 and last["failed"] == 0
    assert detail["outcomes"] == {"done": 12}
