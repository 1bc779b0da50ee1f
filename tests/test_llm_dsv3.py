"""The ``dsv3_lm`` family (multi-head latent attention over a cache of
latents, sigmoid-routed experts of which a chip holds a share, YaRN
rotary positions) against its plain reference,
``benchmarks/reference/dsv3.py``, at a small size on the CPU with every
mechanism kept: 16 experts in 4 groups of which the best 2 are kept, 4
experts a token, 4 ranks of 4 experts, 1 dense + 2 expert layers, 4
heads whose query/key and value dims differ, YaRN over a short original
context so that the blend is not trivial.  Logits are compared, never
sampled tokens."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import dsv3 as ref  # noqa: E402
from nnstreamer_tpu.llm.engine import DecodeEngine  # noqa: E402
from nnstreamer_tpu.llm.family import family_of_custom  # noqa: E402
from nnstreamer_tpu.llm.pool import KVCachePool  # noqa: E402
from nnstreamer_tpu.models import dsv3_lm as dm  # noqa: E402
from nnstreamer_tpu.ops import latent_decode  # noqa: E402

MODEL = {"arch": "dsv3_lm", "vocab": 257, "dim": 64, "heads": 4,
         "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
         "qk_rope_head_dim": 16, "v_head_dim": 12, "mlp": 96,
         "expert_mlp": 32, "experts": 16, "experts_held": 4,
         "expert_rank": 1, "n_group": 4, "topk_group": 2,
         "experts_per_tok": 4, "shared_experts": 1, "dense_layers": 1,
         "layers": 3, "routed_scaling_factor": 2.5, "rope_theta": 10000,
         "rope_factor": 4, "rope_original_max": 32, "beta_fast": 8,
         "beta_slow": 0.25, "mscale": 1, "mscale_all_dim": 1,
         "max_seq": 64, "chunk": 8, "dtype": "float32"}
CUSTOM = ",".join(f"{k}:{v}" for k, v in MODEL.items())
TOL = 2e-5
T = 44          # tokens of the test sequence: five chunks and a half


def _cfg(model=MODEL):
    family, rest = family_of_custom({k: str(v) for k, v in model.items()})
    assert family is dm.FAMILY
    return family.config_from_custom(rest)


def _params(cfg, seed=3):
    """Seeded weights with every norm moved off its identity, so a
    dropped norm weight shows (the selection bias is drawn non-zero)."""
    params = dm.init_params(cfg, seed)
    leaves, tree = jax.tree_util.tree_flatten(params)
    rng = np.random.default_rng(seed)
    leaves = [leaf + jnp.asarray(rng.normal(0, 0.05, leaf.shape),
                                 leaf.dtype) if leaf.ndim == 1 else leaf
              for leaf in leaves]
    return jax.tree_util.tree_unflatten(tree, leaves)


@pytest.fixture(scope="module")
def world():
    cfg = _cfg()
    params = _params(cfg)
    tokens = np.random.default_rng(11).integers(0, cfg.vocab, T).astype(
        np.int32)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "ref": ref.forward_logits(params, tokens, MODEL),
            "chunk": jax.jit(partial(dm.prefill_chunk, cfg=cfg))}


def _prefill(w, state, slot, prompt):
    c = w["cfg"].chunk
    n = -(-len(prompt) // c)
    for i in range(n):
        buf = np.zeros((c,), np.int32)
        real = min(c, len(prompt) - i * c)
        buf[:real] = prompt[i * c:i * c + real]
        logits, state = w["chunk"](
            w["params"], state, jnp.asarray(buf), jnp.int32(slot),
            jnp.int32(i * c), jnp.int32(real), jnp.bool_(i == n - 1))
    return np.asarray(logits), state


def _dirty(cfg, slots):
    """A pool no session has cleared: every row holds something."""
    rows, stats = dm.init_state(cfg, slots)
    return rows + 3, stats


def _path(kernels, monkeypatch):
    """Take one of the decode step's two paths, both on the CPU: XLA's
    forms, or the chip's — the decode attention over the pool where it
    lies and the megablox grouped products — in Pallas' interpret mode,
    the attention in blocks of 16 positions so that a slot of 64 is four
    of them."""
    monkeypatch.setattr(dm, "GROUPED_KERNEL", kernels)
    if kernels:
        from jax.experimental.pallas.ops.tpu import megablox

        monkeypatch.setattr(latent_decode, "BLOCK_T", 16)
        monkeypatch.setattr(dm, "latent_decode_attention", partial(
            latent_decode.latent_decode_attention, interpret=True))
        # ``_grouped`` imports the kernel where it calls it
        monkeypatch.setattr(megablox, "gmm",
                            partial(megablox.gmm, interpret=True))


# -- the model's functions against the reference -------------------------
def test_parameter_tree_and_state():
    cfg = _cfg(dict(MODEL, dtype="bfloat16"))
    params = dm.init_params(cfg, 5)
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == (jnp.float32 if leaf.ndim == 1
                              else jnp.bfloat16)
    dense, expert = params["layers"][0], params["layers"][1]
    assert "w_gate_up" in dense and "w_router" not in dense
    assert expert["we_gate_up"].shape == (4, 64, 64)     # HELD experts
    assert expert["we_down"].shape == (4, 32, 64)
    assert expert["w_router"].shape == (64, 16)           # ALL outputs
    # the selection bias is drawn, not zero
    assert float(jnp.abs(expert["e_bias"]).min()) > 0
    assert params["head"].shape == params["embed"].shape == (257, 64)
    assert params["head"] is not params["embed"]
    rows, stats = dm.init_state(cfg, 3)
    # 16 latents + 16 rotated key dims, held 128 wide; a scratch slot
    assert cfg.row == 32 and cfg.row_held == 128
    assert rows.shape == (3, 4, 64, 128) and rows.dtype == jnp.bfloat16
    assert stats.shape == (2, 4) and stats.dtype == jnp.float32
    assert dm.STATE_KINDS == ("latent", "route_stats")
    big = dm.config_from_custom({"kv_lora_rank": "512", "max_seq": "64",
                                 "qk_rope_head_dim": "64"})
    assert big.row == 576 and big.row_held == 640 and big.chunk == 64


def test_yarn_blend_is_not_trivial_and_equals_the_references():
    cfg = _cfg()
    got = dm.rope_inv_freq(cfg)
    plain = 10000.0 ** (-2.0 * np.arange(8) / 16)
    # the ramp runs over frequencies 0..3: 0, 1/3, 2/3, then 1
    ramp = np.array([0, 1 / 3, 2 / 3, 1, 1, 1, 1, 1])
    assert np.allclose(got, plain / 4 * ramp + plain * (1 - ramp))
    assert np.allclose(got, ref.yarn_inv_freq(MODEL))
    m = 0.1 * np.log(4.0) + 1.0
    assert dm.softmax_scale(cfg) == pytest.approx(24 ** -0.5 * m * m)
    assert ref.softmax_scale(MODEL) == pytest.approx(dm.softmax_scale(cfg))
    # the published numbers: dims 8 and 19 bound the ramp, m = 1.4159
    pub = dm.config_from_custom({
        "qk_rope_head_dim": "64", "qk_nope_head_dim": "128",
        "rope_theta": "100000", "rope_factor": "64",
        "rope_original_max": "4096", "max_seq": "64"})
    f = dm.rope_inv_freq(pub)
    plain = 100000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(f[:9], plain[:9]) and np.allclose(
        f[19:], plain[19:] / 64)
    assert plain[12] / 64 < f[12] < plain[12]
    assert dm.softmax_scale(pub) == pytest.approx(
        192 ** -0.5 * 1.4158883 ** 2, rel=1e-6)


def test_full_forward_equals_the_reference(world):
    got = np.asarray(dm.forward_logits(world["params"],
                                       jnp.asarray(world["tokens"]),
                                       world["cfg"]))
    assert np.abs(world["ref"]).max() > 0.1
    assert np.abs(got - world["ref"]).max() < TOL


@pytest.mark.parametrize("plen", [1, 7, 8, 9, 21, 24])
def test_chunked_prefill_equals_the_whole(world, plen):
    """The prompt's last position through the chunks (the plain path
    over the slot's rows, keys and values expanded) equals the
    reference's full forward — under, at and over a chunk boundary, from
    a pool nobody cleared."""
    logits, _ = _prefill(world, _dirty(world["cfg"], 2), 1,
                         world["tokens"][:plen])
    assert np.abs(logits - world["ref"][plen - 1]).max() < TOL


@pytest.mark.parametrize("plen, lanes, kernels", [
    (21, 1, False), (3, 4, False), (12, 8, False),   # XLA, rows gathered
    (21, 3, True), (12, 8, True)])          # the kernels, interpreted
def test_absorbed_decode_equals_plain_attention_at_every_position(
        world, plen, lanes, kernels, monkeypatch):
    """Prefill (plain path) then decode (ABSORBED path: queries carried
    into the latent space, scores and sum over the cached rows, values
    expanded after the sum) equals the reference, which expands keys and
    values at every position — the lane of interest among padding lanes,
    with the lanes' rows gathered and with the kernel walking the pool
    where it lies (the lane crosses three of its blocks' edges)."""
    cfg, tokens = world["cfg"], world["tokens"]
    slots = 3
    _, state = _prefill(world, _dirty(cfg, slots), 2, tokens[:plen])
    _path(kernels, monkeypatch)
    step = jax.jit(partial(dm.decode_step, cfg=cfg))
    for p in range(plen, T):
        tok = np.zeros((lanes,), np.int32)
        pos = np.zeros((lanes,), np.int32)
        sl = np.full((lanes,), slots, np.int32)       # the scratch slot
        tok[0], pos[0], sl[0] = tokens[p], p, 2
        logits, state = step(world["params"], state, jnp.asarray(tok),
                             jnp.asarray(pos), jnp.asarray(sl))
        assert np.abs(np.asarray(logits[0]) - world["ref"][p]).max() < TOL
    # the padding lanes are left out of the counters
    assert float(state[1][0, 0]) == T - plen


@pytest.mark.parametrize("kernels", [False, True])
def test_lanes_at_their_own_positions_do_not_mix(world, kernels,
                                                 monkeypatch):
    cfg = world["cfg"]
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, cfg.vocab, n + 1).astype(np.int32)
            for n in (13, 5, 20, 9)]
    state = _dirty(cfg, 4)
    for slot, seq in enumerate(seqs):
        _, state = _prefill(world, state, slot, seq[:-1])
    order = np.array([2, 0, 3, 1])
    _path(kernels, monkeypatch)
    logits, _ = dm.decode_step(
        world["params"], state,
        jnp.asarray([seqs[s][-1] for s in order], jnp.int32),
        jnp.asarray([len(seqs[s]) - 1 for s in order], jnp.int32),
        jnp.asarray(order, jnp.int32), cfg)
    for lane, s in enumerate(order):
        want = ref.forward_logits(world["params"], seqs[s], MODEL)[-1]
        assert np.abs(np.asarray(logits[lane]) - want).max() < TOL


def test_a_reused_slot_starts_clean(world):
    """A shorter prompt in a slot a longer one filled: the stale rows
    past its end are masked by position."""
    cfg, tokens = world["cfg"], world["tokens"]
    _, state = _prefill(world, dm.init_state(cfg, 1), 0, tokens[:40])
    logits, state = _prefill(world, state, 0, tokens[:6])
    assert np.abs(logits - world["ref"][5]).max() < TOL
    logits, _ = dm.decode_step(
        world["params"], state, jnp.asarray(tokens[6:7]),
        jnp.asarray([6], jnp.int32), jnp.asarray([0], jnp.int32), cfg)
    assert np.abs(np.asarray(logits[0]) - world["ref"][6]).max() < TOL


def _without_shared(params):
    return dict(params, layers=[
        dict(lyr, ws_down=jnp.zeros_like(lyr["ws_down"]))
        if "ws_down" in lyr else lyr for lyr in params["layers"]])


#: what a layer may not leave out: a patch of the family's module
BRANCHES = {
    "e_score_correction_bias": ("route", partial(dm.route, use_bias=False)),
    "the group limit": ("route", partial(dm.route, group_limit=False)),
    "the weights' normalisation": (
        "route", partial(dm.route, normalise=False)),
    "the routed scaling factor 2.5": (
        "route", partial(dm.route, scale=False)),
    "the YaRN blend": ("rope_inv_freq", lambda cfg: (
        cfg.rope_theta ** (-2.0 * np.arange(cfg.qk_rope_head_dim // 2)
                           / cfg.qk_rope_head_dim)
        / cfg.rope_factor).astype(np.float32)),
    "m squared in the scale": ("softmax_scale", lambda cfg: (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5),
}


@pytest.mark.parametrize("branch", [*BRANCHES, "the shared expert"])
def test_a_missing_part_fails_the_comparison(world, branch, monkeypatch):
    params = world["params"]
    if branch in BRANCHES:
        monkeypatch.setattr(dm, *BRANCHES[branch])
    else:
        params = _without_shared(params)
    got = np.asarray(dm.forward_logits(params, jnp.asarray(world["tokens"]),
                                       world["cfg"]))
    assert np.abs(got - world["ref"]).max() > 100 * TOL


@pytest.mark.parametrize("switch", ["use_bias", "group_limit", "normalise",
                                    "scale"])
def test_the_references_own_switches_move_its_routing(world, switch):
    """The reference's routing with one part dropped differs from the
    whole: the family's comparison above is against something that has
    the part."""
    cfg = world["cfg"]
    lyr = world["params"]["layers"][1]
    y = jnp.asarray(np.random.default_rng(8).normal(size=(40, cfg.dim)),
                    jnp.float32)
    whole = np.asarray(ref.route(y, lyr, MODEL))
    assert ((whole > 0).sum(-1) == 4).all()
    assert np.allclose(whole.sum(-1), 2.5, rtol=1e-5)
    less = np.asarray(ref.route(y, lyr, MODEL, **{switch: False}))
    assert np.abs(less - whole).max() > 1e-3
    # the family chooses the same experts with the same weights
    chosen, w = dm.route(y, lyr, cfg)
    dense = np.zeros_like(whole)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(w), axis=1)
    assert np.abs(dense - whole).max() < 1e-5
    # at most 2 of the 4 groups are touched
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(chosen))


# -- the shares of the expert layer --------------------------------------
@pytest.fixture(scope="module")
def whole_layer():
    """One expert layer with ALL 16 experts (the uncut model), inputs,
    and the uncut reference's routed and shared parts."""
    model = dict(MODEL, experts_held=16, expert_rank=0)
    cfg = _cfg(model)
    lyr = _params(cfg, 7)["layers"][1]
    y = jnp.asarray(np.random.default_rng(6).normal(size=(48, cfg.dim)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed = np.asarray(ref.routed(y, lyr, model))
        shared = np.asarray(ref.shared(
            y, {k: lyr[k] for k in ("ws_gate_up", "ws_down")}))
    return {"lyr": lyr, "y": y, "routed": routed, "shared": shared}


def _share(lyr, rank):
    return dict(lyr, we_gate_up=lyr["we_gate_up"][4 * rank:4 * rank + 4],
                we_down=lyr["we_down"][4 * rank:4 * rank + 4])


@pytest.mark.parametrize("rank", [0, 1, 2, 3])
def test_a_ranks_part_equals_the_references_for_that_rank(whole_layer,
                                                          rank):
    """The program's grouped products over its held experts alone
    against the reference's loop over the same share, masked."""
    model = dict(MODEL, expert_rank=rank)
    cfg = _cfg(model)
    lyr, y = _share(whole_layer["lyr"], rank), whole_layer["y"]
    chosen, w = dm.route(y, lyr, cfg)
    got, sizes = dm.routed_experts(y, chosen, w, lyr, cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed(y, lyr, model))
    assert np.abs(np.asarray(got) - want).max() < TOL
    local = np.asarray(chosen) - 4 * rank
    assert np.asarray(sizes).tolist() == [
        int((local == j).sum()) for j in range(4)]
    assert np.abs(want).max() > 1e-3


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    """Over all four ranks, the routed parts summed and the shared
    expert counted ONCE equal the uncut reference's layer."""
    y, total = whole_layer["y"], 0.0
    pairs = 0
    for rank in range(4):
        cfg = _cfg(dict(MODEL, expert_rank=rank))
        lyr = _share(whole_layer["lyr"], rank)
        chosen, w = dm.route(y, lyr, cfg)
        part, sizes = dm.routed_experts(y, chosen, w, lyr, cfg)
        total = total + np.asarray(part)
        pairs += int(np.asarray(sizes).sum())
    assert pairs == 48 * 4              # every chosen pair on some rank
    assert np.abs(total - whole_layer["routed"]).max() < TOL
    lyr = whole_layer["lyr"]
    shared = np.asarray(dm._swiglu(y, lyr["ws_gate_up"], lyr["ws_down"]))
    assert np.abs(shared - whole_layer["shared"]).max() < TOL
    # one rank alone is NOT the layer
    assert np.abs(np.asarray(part) - whole_layer["routed"]).max() > 1e-3


# -- the counters --------------------------------------------------------
def test_route_stats_count_what_a_numpy_recount_gives(world):
    """Many calls of one expert layer on random inputs of 8 lanes, two
    of them padding: the counters on the device against a recount of the
    chosen experts, and against what uniform routing would reach."""
    cfg = world["cfg"]
    lyr = world["params"]["layers"][2]
    stats = jnp.zeros((2, 4), jnp.float32)
    counted = jnp.asarray([True] * 6 + [False] * 2)
    rng = np.random.default_rng(21)
    ffn = jax.jit(lambda x, s: (None, dm.count_routes(
        s, dm._ffn(x, lyr, cfg)[1], counted, 2, cfg)))
    want = np.zeros(4)
    calls = 60
    for _ in range(calls):
        x = jnp.asarray(rng.normal(size=(8, cfg.dim)), jnp.float32)
        _, stats = ffn(x, stats)
        chosen, _ = dm.route(dm._rms(x, lyr["ln2"], cfg.eps), lyr, cfg)
        local = np.asarray(chosen)[:6] - 4 * cfg.expert_rank
        counts = np.bincount(local[(local >= 0) & (local < 4)],
                             minlength=4)
        want += [1, counts.sum(), (counts > 0).sum(), 0]
        want[3] = max(want[3], counts.max())
    got = np.asarray(stats)
    assert got[0].tolist() == [0, 0, 0, 0]        # the other layer's row
    assert got[1].tolist() == want.tolist()
    # uniform routing: 6 lanes x 4 of 16 experts, 4 held: 6 pairs a
    # call, 4 (1 - (3/4)^6) = 3.29 experts reached; random weights come
    # within sampling error of it (the bias and the groups tilt it)
    from benchmarks.cost import dsv3 as cost

    assert cost.pairs_here(MODEL, 6) == 6.0
    assert cost.experts_reached(MODEL, 6) == pytest.approx(3.288, abs=1e-3)
    assert abs(want[1] / calls - 6.0) < 1.5
    assert abs(want[2] / calls - cost.experts_reached(MODEL, 6)) < 0.6
    report = dm.state_counters(cfg, (None, stats))
    assert report["held_experts_reached_per_step"] == want[2] / calls


def test_bfloat16_weights_stay_near_the_float32_reference():
    """The serving dtype at the small size: the reference reads the same
    bfloat16 tree, so what differs is the arithmetic alone — and a top 4
    that flips on rounding, which is why agreement is a share (PERF.md
    section 6 (h))."""
    model = dict(MODEL, dtype="bfloat16")
    cfg = _cfg(model)
    params = _params(cfg)
    tokens = np.random.default_rng(2).integers(0, cfg.vocab, 40).astype(
        np.int32)
    want = ref.forward_logits(params, tokens, model)
    got = np.asarray(dm.forward_logits(params, jnp.asarray(tokens), cfg))
    close = np.abs(got - want).max(-1) < 0.05 * np.abs(want).max()
    assert close.mean() >= 0.9


# -- the grammar ---------------------------------------------------------
@pytest.mark.parametrize("custom, word", [
    ({"max_seq": "64", "window": "8"}, "unknown custom keys"),
    ({"dim": "64"}, "max_seq must be named"),
    ({"max_seq": "60", "chunk": "8"}, "multiple of chunk"),
    ({"max_seq": "64", "experts_held": "5"}, "must divide"),
    ({"max_seq": "64", "expert_rank": "4"}, "name one of the shares"),
    ({"max_seq": "64", "n_group": "3"}, "n_group divides"),
    ({"max_seq": "64", "topk_group": "1", "experts_per_tok": "8"},
     "exceeds the experts"),
    ({"max_seq": "64", "dense_layers": "3"}, "at least one expert layer"),
    ({"max_seq": "64", "qk_rope_head_dim": "7"}, "pairs by two"),
    ({"max_seq": "64", "heads": "0"}, "must be > 0")])
def test_grammar_refuses(custom, word):
    with pytest.raises(ValueError, match=word):
        dm.config_from_custom(custom)


def test_grammar_reads_floats_and_defaults_the_chunk():
    cfg = dm.config_from_custom({
        "max_seq": "8192", "routed_scaling_factor": "2.5",
        "rope_theta": "100000", "beta_slow": "0.25"})
    assert cfg.chunk == 512 and cfg.routed_scaling_factor == 2.5
    assert cfg.rope_theta == 100000.0 and cfg.beta_slow == 0.25
    assert dm.FAMILY.chunk_len(cfg) == 512 and not dm.FAMILY.paged


# -- the engine ----------------------------------------------------------
@pytest.fixture(scope="module")
def engine(world):
    cfg = world["cfg"]
    pool = KVCachePool(cfg, 3, family=dm.FAMILY)
    eng = DecodeEngine(world["params"], cfg, pool, capacity=2)
    eng.warmup()
    return eng


def test_engine_serves_the_family_through_one_prefill_executable(
        world, engine):
    """Warm-up compiles the lane shapes and ONE prefill executable; a
    prompt of any length then compiles nothing, and prefill + steps give
    the reference's greedy continuation.  The report carries the pool by
    kind and the routing counters."""
    eng, pool = engine, engine.pool
    assert eng.chunk_len == world["cfg"].chunk and not eng.paged
    warm = eng.compiles
    assert warm == 3                      # lanes 1, 2 and the one chunk
    # warm-up ran on the scratch slot alone: nothing counted
    assert eng.report()["state_counters"]["by_expert_layer"] == [
        [0.0] * 4, [0.0] * 4]
    tokens = world["tokens"]
    sess = pool.acquire("a")
    first = eng.prefill(sess, tokens[:21])
    assert first == int(world["ref"][20].argmax()) and sess.pos == 21
    assert eng.prefill_chunks_total == 3
    other = pool.acquire("b")
    other.next_token = eng.prefill(other, tokens[:5])
    sess.next_token = int(tokens[21])
    for p in range(21, 26):
        out = eng.step([sess, other])
        assert out[0] == int(world["ref"][p].argmax())
        sess.next_token = int(tokens[p + 1])   # teacher-forced
        other.next_token = out[1]
    assert eng.compiles == warm
    report = eng.report()
    assert report["prefill_chunks"] == 4
    by_kind = report["cache_bytes_by_kind"]
    assert by_kind == {"latent": 3 * 4 * 64 * 128 * 4,
                       "route_stats": 2 * 4 * 4}
    assert sum(by_kind.values()) == report["cache_bytes"] \
        == pool.cache_bytes()
    counters = report["state_counters"]
    assert counters["columns"] == list(dm.ROUTE_STATS)
    assert [row[0] for row in counters["by_expert_layer"]] == [5.0, 5.0]
    # 2 lanes x 4 of 16 experts, 4 held here: about 2 pairs a step
    assert 0 <= counters["pairs_per_step"] <= 8
    pool.release("a")
    pool.release("b")


def test_prefill_by_steps_equals_the_chunks(world):
    cfg = world["cfg"]
    pool = KVCachePool(cfg, 1, family=dm.FAMILY)
    pool.arrays = _dirty(cfg, 1)
    eng = DecodeEngine(world["params"], cfg, pool, capacity=1,
                       prefill_mode="step")
    sess = pool.acquire("a")
    assert eng.prefill(sess, world["tokens"][:13]) == int(
        world["ref"][12].argmax())


# -- the element ---------------------------------------------------------
def _launch(extra=""):
    from nnstreamer_tpu import parse_launch

    return parse_launch(
        "appsrc name=src caps=other/tensors,format=static,num_tensors=1,"
        "dimensions=67,types=int32,framerate=0/1 ! "
        f"tensor_llm name=llm custom={CUSTOM} seed=3 slots=2 batch=2 "
        f"max-new-tokens=12 {extra} ! tensor_sink name=out")


@pytest.mark.parametrize("extra, named", [
    ("page-size=8", "page-size=8"),
    ("prefill-chunk=16", "prefill-chunk=16"),
    ("prefix-cache=1", "prefix-cache=1")])
def test_element_refuses_what_the_family_cannot_serve(extra, named):
    p = _launch(extra)
    found = [f for f in p.get("llm").static_check()
             if f[1] == "llm-family-not-paged"]
    assert found and named in found[0][2]
    assert "arch:dsv3_lm" in found[0][2] and "latents" in found[0][2]
    with pytest.raises(Exception, match="cannot serve"):
        p.play()
    p.stop()


def test_element_serves_the_family_from_the_launch_line():
    """``custom=arch:dsv3_lm,...`` through the element's own start,
    warm-up, admission and decode thread: the stream is the reference's
    greedy continuation of the prompt, and the pool's bytes are on the
    gauges by kind."""
    import time

    from nnstreamer_tpu.obs.metrics import REGISTRY
    from nnstreamer_tpu.tensor.buffer import TensorBuffer

    cfg = _cfg()
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, 19).astype(np.int32)
    p = _launch()
    assert not [f for f in p.get("llm").static_check() if f[0] == "error"]
    got = []
    p.get("out").connect("new-data", lambda buf: got.append(
        int(np.asarray(buf.np(0)).reshape(-1)[0])))
    p.play()
    try:
        llm = p.get("llm")
        assert llm.family is dm.FAMILY
        assert llm.engine.chunk_len == 8
        kinds = {g.labels.get("kind"): g.sample()
                 for g in REGISTRY._snapshot()
                 if g.name == "nns_llm_state_bytes"
                 and g.labels.get("element") == "llm"}
        frame = np.zeros((67,), np.int32)
        frame[:3] = (len(prompt), 10, -1)
        frame[3:3 + len(prompt)] = prompt
        p.get("src").push_buffer(TensorBuffer(tensors=[frame]))
        deadline = time.monotonic() + 60
        while len(got) < 10 and time.monotonic() < deadline:
            time.sleep(0.02)
        params = llm.engine.params
        by_kind = llm.pool.bytes_by_kind()
    finally:
        p.stop()
    assert len(got) == 10
    seq = np.concatenate([prompt, np.asarray(got[:-1], np.int32)])
    want = ref.forward_logits(params, seq, MODEL)[len(prompt) - 1:]
    assert got == [int(r.argmax()) for r in want]
    assert set(by_kind) == {"latent", "route_stats"}
    assert kinds == {k: float(v) for k, v in by_kind.items()}
    # read after the loop has stopped: the report's, never the loop's
    counters = llm.final_report["state_counters"]
    assert counters["by_expert_layer"][0][0] >= 9


# -- one decode step in flight (tests/llm_ahead.py holds the scenario) ----
@pytest.fixture(scope="module")
def ahead_requests():
    import llm_ahead

    family, cfg, params = llm_ahead.world(CUSTOM, 3)
    assert family is dm.FAMILY
    eng = DecodeEngine(params, cfg, KVCachePool(cfg, 1, family=family),
                       capacity=1)
    out = llm_ahead.requests_for(cfg, 3 + 40, eng)
    (prompt, max_new, _), want = out[0]
    seq = np.concatenate([prompt, np.asarray(want[:-1], np.int32)])
    logits = ref.forward_logits(params, seq, MODEL)[len(prompt) - 1:]
    assert want == [int(r.argmax()) for r in logits]
    assert len(out[4][0][0]) + out[4][0][1] == cfg.max_seq
    return out


@pytest.mark.parametrize("props, every_lane", [
    ("slots=6 batch=6", True),          # every stream a lane
    ("slots=4 batch=2", False),         # round-robin pick
    ("slots=2 batch=2", True)])         # slots and rows reused
def test_element_one_step_ahead_serves_the_synchronous_streams(
        ahead_requests, props, every_lane):
    import llm_ahead

    got, report = llm_ahead.serve(CUSTOM, 3, props, 3 + 40, ahead_requests)
    llm_ahead.check(got, report, ahead_requests, every_lane=every_lane)


def test_element_drains_with_a_step_in_flight(ahead_requests):
    import llm_ahead

    got, report = llm_ahead.serve(CUSTOM, 3, "slots=6 batch=4", 3 + 40,
                                  ahead_requests, drain=True)
    assert report["live_after_drain"] == 0
    llm_ahead.check(got, report, ahead_requests, every_lane=False)
