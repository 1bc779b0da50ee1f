"""The ``sambay_lm`` family (Mamba, windowed and full differential
attention, gated memory units, cross-attention over one shared cache)
against its plain reference, ``benchmarks/reference/phi4flash.py``, at a
small size on the CPU: the whole-sequence forward, chunked prefill and
decode through the three kinds of pooled state, the engine and the
element that serve it, and ``streamformer_lm`` through the same seam.
Logits are compared, never sampled tokens."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import phi4flash as ref  # noqa: E402
from nnstreamer_tpu.llm.engine import DecodeEngine  # noqa: E402
from nnstreamer_tpu.llm.family import family_of_custom, get_family  # noqa: E402
from nnstreamer_tpu.llm.pool import KVCachePool  # noqa: E402
from nnstreamer_tpu.models import sambay_lm as sm  # noqa: E402
from nnstreamer_tpu.ops import shared_kv_decode  # noqa: E402

#: every kind of layer and the 4/5/6 hand-over; window 8, chunk 8
MODEL = {"arch": "sambay_lm", "vocab": 257, "dim": 64, "heads": 8,
         "kv_heads": 4, "head_dim": 8, "mlp": 128, "layers": 8,
         "window": 8, "d_state": 4, "d_conv": 4, "expand": 2,
         "dt_rank": 4, "max_seq": 64, "dtype": "float32"}
CUSTOM = ",".join(f"{k}:{v}" for k, v in MODEL.items())
TOL = 2e-5
T = 44          # tokens of the test sequence: 5 windows and a half


def _cfg(model=MODEL):
    family, rest = family_of_custom({k: str(v) for k, v in model.items()})
    assert family is sm.FAMILY
    return family.config_from_custom(rest)


def _params(cfg, seed=3):
    """Seeded weights with every bias, norm and lambda moved off its
    identity, so a dropped bias or norm weight shows."""
    params = sm.init_params(cfg, seed)
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
            "chunk": jax.jit(partial(sm.prefill_chunk, cfg=cfg)),
            "step": jax.jit(partial(sm.decode_step, cfg=cfg))}


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
    return tuple(a + 3 for a in sm.init_state(cfg, slots))


# -- the model's functions against the reference -------------------------
def test_parameter_tree_and_layer_kinds():
    cfg = _cfg()
    kinds = [sm.layer_kind(i, cfg) for i in range(cfg.layers)]
    assert kinds == ["mamba", "swa", "mamba", "swa", "mamba", "full",
                     "gmu", "cross"]
    assert kinds == [ref.kind_of(i, cfg.layers).replace("window", "swa")
                     for i in range(cfg.layers)]
    big = sm.config_from_custom({"layers": "32", "max_seq": "64"})
    kinds = [sm.layer_kind(i, big) for i in range(32)]
    assert kinds[:17] == ["mamba", "swa"] * 8 + ["mamba"]
    assert kinds[17] == "full" and kinds[18:] == ["gmu", "cross"] * 7
    state = sm.init_state(cfg, 3)
    assert [a.shape for a in state] == [
        (1, 4, 64, 32), (1, 4, 64, 32), (2, 4, 8, 32), (2, 4, 8, 32),
        (3, 4, 3, 128), (3, 4, 4, 128)]
    assert state[5].dtype == jnp.float32
    assert len(sm.STATE_KINDS) == len(state)


def test_full_forward_equals_the_reference(world):
    got = np.asarray(sm.forward_logits(world["params"],
                                       jnp.asarray(world["tokens"]),
                                       world["cfg"]))
    assert np.abs(world["ref"]).max() > 0.1
    assert np.abs(got - world["ref"]).max() < TOL


@pytest.mark.parametrize("plen", [1, 7, 8, 9, 21, 24])
def test_chunked_prefill_skips_the_cross_decoder_and_agrees(world, plen):
    """The prompt's last position through the chunks (layers past the
    full one only there) equals the reference's full forward, which
    computes every layer everywhere — under, at and over a chunk
    boundary, from a pool nobody cleared."""
    logits, _ = _prefill(world, _dirty(world["cfg"], 2), 1,
                         world["tokens"][:plen])
    assert np.abs(logits - world["ref"][plen - 1]).max() < TOL


@pytest.mark.parametrize("plen, lanes", [(21, 1), (3, 4), (12, 8)])
def test_prefill_then_decode_equals_the_reference_at_every_position(
        world, plen, lanes):
    """Chunked prefill, then one token at a time through the three
    pools to the sequence's end: prompt and stream both pass the
    window's wrap (8) and a chunk boundary; the lane of interest sits
    among padding lanes (scratch slot, position 0)."""
    cfg, tokens = world["cfg"], world["tokens"]
    slot = 2
    _, state = _prefill(world, _dirty(cfg, 3), slot, tokens[:plen])
    for p in range(plen, T):
        toks = np.zeros((lanes,), np.int32)
        pos = np.zeros((lanes,), np.int32)
        slots = np.full((lanes,), 3, np.int32)
        lane = (p * 3) % lanes
        toks[lane], pos[lane], slots[lane] = tokens[p], p, slot
        logits, state = world["step"](
            world["params"], state, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(slots))
        assert np.abs(np.asarray(logits)[lane]
                      - world["ref"][p]).max() < TOL, p


def test_lanes_at_their_own_positions_do_not_mix(world):
    """Three sessions of different lengths decode together; each lane
    equals the reference of its own sequence."""
    cfg = world["cfg"]
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, cfg.vocab, n).astype(np.int32)
            for n in (30, 19, 11)]
    plens = [17, 3, 9]
    refs = [ref.forward_logits(world["params"], s, MODEL) for s in seqs]
    state = _dirty(cfg, 3)
    for slot, (s, n) in enumerate(zip(seqs, plens)):
        _, state = _prefill(world, state, slot, s[:n])
    at = list(plens)
    for _ in range(10):
        live = [i for i in range(3) if at[i] < len(seqs[i])]
        lanes = live + [3] * (4 - len(live))
        toks = [seqs[i][at[i]] if i < 3 else 0 for i in lanes]
        pos = [at[i] if i < 3 else 0 for i in lanes]
        logits, state = world["step"](
            world["params"], state, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(lanes, jnp.int32))
        for lane, i in enumerate(live):
            assert np.abs(np.asarray(logits)[lane]
                          - refs[i][at[i]]).max() < TOL
            at[i] += 1


def test_a_reused_slot_starts_clean(world):
    """A slot a longer session left (its rows, ring and recurrent state
    all written) serves the next session as a fresh pool does: prefill,
    and decode from position 0 without a prefill."""
    cfg, tokens = world["cfg"], world["tokens"]
    fresh = sm.init_state(cfg, 1)
    _, used = _prefill(world, sm.init_state(cfg, 1), 0, tokens[:40])
    short = tokens[5:16]
    a, sa = _prefill(world, fresh, 0, short)
    b, sb = _prefill(world, used, 0, short)
    assert np.array_equal(a, b)
    one = lambda v: jnp.asarray([v], jnp.int32)   # noqa: E731
    for p in range(3):
        la, sa = world["step"](world["params"], sa, one(tokens[p]), one(p),
                               one(0))
        lb, sb = world["step"](world["params"], sb, one(tokens[p]), one(p),
                               one(0))
        assert np.array_equal(np.asarray(la), np.asarray(lb))
        assert np.abs(np.asarray(la)[0] - world["ref"][p]).max() < TOL


def _decode_path(kernel, cfg, monkeypatch):
    """The decode step with one of its two readings of the cache, both
    on the CPU: XLA's over the gathered rows, or the chip's kernel
    (``ops/shared_kv_decode.py``) in Pallas' interpret mode, in blocks
    of 16 positions so that a slot of 64 is four of them."""
    monkeypatch.setattr(sm, "SHARED_KV_KERNEL", kernel)
    if kernel:
        monkeypatch.setattr(shared_kv_decode, "BLOCK_T", 16)
        monkeypatch.setattr(sm, "shared_kv_decode_attention", partial(
            shared_kv_decode.shared_kv_decode_attention, interpret=True))
    return jax.jit(partial(sm.decode_step, cfg=cfg))


def test_the_kernel_serves_the_tokens_the_gathered_form_serves(
        world, monkeypatch):
    """Three sessions at positions in different blocks of their slots,
    one of them in a slot a longer session left (its rows past the new
    position stale), and a padding lane, decode six greedy tokens each:
    through the kernel the same tokens, logits and pools as through
    XLA's gathered form."""
    cfg, params = world["cfg"], world["params"]
    rng = np.random.default_rng(8)
    state = _dirty(cfg, 3)
    _, state = _prefill(world, state, 1, rng.integers(0, cfg.vocab, 40))
    slots = np.array([2, 3, 0, 1], np.int32)     # the last lane: padding
    first, pos = [], []
    for slot, n in ((2, 17), (0, 30), (1, 5)):
        logits, state = _prefill(world, state, slot,
                                 rng.integers(0, cfg.vocab, n))
        first.append(int(logits.argmax()))
        pos.append(n)
    lanes = [0, 3, 1, 2]                          # lane -> session order

    def serve(kernel):
        step = _decode_path(kernel, cfg, monkeypatch)
        st, toks = state, np.array(first + [0], np.int32)[lanes]
        at = np.array(pos + [0], np.int32)[lanes]
        out = []
        for _ in range(6):
            logits, st = step(params, st, jnp.asarray(toks),
                              jnp.asarray(at), jnp.asarray(slots))
            out.append(np.asarray(logits))
            toks = out[-1].argmax(-1).astype(np.int32)
            at = np.where(slots < 3, at + 1, 0).astype(np.int32)
        return np.stack(out), st

    want, want_state = serve(False)
    got, got_state = serve(True)
    real = slots < 3
    assert np.array_equal(got.argmax(-1)[:, real], want.argmax(-1)[:, real])
    assert np.abs(got - want).max() < TOL
    for a, b in zip(got_state, want_state):
        assert np.array_equal(np.asarray(a), np.asarray(b))


BRANCHES = {
    "lambda a2": ("_lambda", lambda lyr, i: 0.0),
    "the memory unit's gate": (
        "_gmu", lambda y, m, lyr: sm._mm(m, lyr["w_2"])),
}


@pytest.mark.parametrize("branch", [*BRANCHES, "the scan's D term"])
def test_a_missing_branch_fails_the_comparison(world, branch, monkeypatch):
    params = world["params"]
    if branch in BRANCHES:
        monkeypatch.setattr(sm, *BRANCHES[branch])
    else:
        params = dict(params, layers=[
            dict(lyr, D=jnp.zeros_like(lyr["D"])) if "D" in lyr else lyr
            for lyr in params["layers"]])
    got = np.asarray(sm.forward_logits(params, jnp.asarray(world["tokens"]),
                                       world["cfg"]))
    assert np.abs(got - world["ref"]).max() > 100 * TOL


def test_bfloat16_weights_stay_near_the_float32_reference():
    """The serving dtype at the small size: the reference reads the same
    bfloat16 tree, so what differs is the arithmetic alone."""
    model = dict(MODEL, dtype="bfloat16")
    cfg = _cfg(model)
    params = _params(cfg)
    assert params["embed"].dtype == jnp.bfloat16
    assert params["layers"][0]["A_log"].dtype == jnp.float32
    tokens = np.random.default_rng(2).integers(0, cfg.vocab, 20).astype(
        np.int32)
    want = ref.forward_logits(params, tokens, model)
    got = np.asarray(sm.forward_logits(params, jnp.asarray(tokens), cfg))
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()


# -- the grammar ---------------------------------------------------------
@pytest.mark.parametrize("custom, word", [
    ({"layers": "6", "max_seq": "64"}, "multiple of 4"),
    ({"heads": "3", "max_seq": "64"}, "pair"),
    ({"window": "8", "max_seq": "60"}, "multiple of window"),
    ({"experts": "2", "max_seq": "64"}, "routes no experts"),
    ({"rope": "1", "max_seq": "64"}, "unknown custom keys"),
])
def test_grammar_refuses(custom, word):
    with pytest.raises(ValueError, match=word):
        sm.config_from_custom(custom)


def test_a_tensor_llm_without_the_seam_refuses_the_launch_line_at_once():
    """The benchmark's ``model`` says ``experts:0``.  The family takes
    that; ``streamformer_lm``'s grammar — all a ``tensor_llm`` from
    before the ``arch:`` key reads, ignoring the keys it does not know —
    refuses it before any weight is drawn, where it would otherwise
    draw 6.9 G float32 parameters on the host."""
    import json

    from nnstreamer_tpu.models.streamformer_lm import config_from_custom

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi4_mini_flash.json")) as fh:
        model = {k: str(v) for k, v in json.load(fh)["model"].items()}
    assert model["experts"] == "0"
    family, own = family_of_custom(model)
    assert family.config_from_custom(own).layers == 32
    with pytest.raises(ValueError, match="must all be >= 1"):
        config_from_custom(model)


def test_unknown_arch_is_named():
    with pytest.raises(ValueError, match="unknown arch 'mamba9'"):
        get_family("mamba9")
    assert get_family().name == "streamformer_lm"


# -- the engine ----------------------------------------------------------
@pytest.fixture(scope="module")
def engine(world):
    cfg = world["cfg"]
    pool = KVCachePool(cfg, 3, family=sm.FAMILY)
    eng = DecodeEngine(world["params"], cfg, pool, capacity=2)
    eng.warmup()
    return eng


def test_engine_serves_the_family_through_one_prefill_executable(
        world, engine):
    """Warm-up compiles the lane shapes and ONE prefill executable; a
    prompt of any length then compiles nothing, and prefill + steps give
    the reference's greedy continuation."""
    eng, pool = engine, engine.pool
    assert eng.chunk_len == world["cfg"].chunk and not eng.paged
    warm = eng.compiles
    assert warm == 3                      # lanes 1, 2 and the one chunk
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
    assert set(by_kind) == {"kv", "ring", "conv", "ssm"}
    assert sum(by_kind.values()) == report["cache_bytes"] \
        == pool.cache_bytes()
    # one layer's rows, K and V, float32 here: 4 slots x 64 x 32 x 4 B
    assert by_kind["kv"] == 2 * 4 * 64 * 32 * 4
    pool.release("a")
    pool.release("b")


def test_prefill_by_steps_equals_the_chunks(world):
    """``prefill=step`` (the prompt through the decode step) on a pool
    another session wrote: position 0 starts the recurrent rows clean."""
    cfg = world["cfg"]
    pool = KVCachePool(cfg, 1, family=sm.FAMILY)
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
    assert found and named in found[0][2] and "recurrent" in found[0][2]
    with pytest.raises(Exception, match="cannot serve"):
        p.play()
    p.stop()


def test_element_names_an_unknown_arch():
    from nnstreamer_tpu import parse_launch

    p = parse_launch(
        "appsrc name=src caps=other/tensors,format=static,num_tensors=1,"
        "dimensions=67,types=int32,framerate=0/1 ! "
        "tensor_llm name=llm custom=arch:mamba9,max_seq:64 ! "
        "tensor_sink name=out")
    assert any(f[1] == "llm-unknown-arch" and "mamba9" in f[2]
               for f in p.get("llm").static_check())


def test_element_serves_the_family_from_the_launch_line():
    """``custom=arch:sambay_lm,...`` through the element's own start,
    warm-up, admission and decode thread: the stream is the reference's
    greedy continuation of the prompt, and the pool's bytes are on the
    gauges by kind."""
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
        assert llm.family is sm.FAMILY
        assert llm.engine.chunk_len == 8
        kinds = {g.labels.get("kind"): g.sample()
                 for g in REGISTRY._snapshot()
                 if g.name == "nns_llm_state_bytes"
                 and g.labels.get("element") == "llm"}
        frame = np.zeros((67,), np.int32)
        frame[:3] = (len(prompt), 10, -1)
        frame[3:3 + len(prompt)] = prompt
        p.get("src").push_buffer(TensorBuffer(tensors=[frame]))
        import time
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
    assert set(by_kind) == {"kv", "ring", "conv", "ssm"}
    assert kinds == {k: float(v) for k, v in by_kind.items()}


# -- one decode step in flight (tests/llm_ahead.py holds the scenario) ----
@pytest.fixture(scope="module")
def ahead_requests():
    import llm_ahead

    family, cfg, params = llm_ahead.world(CUSTOM, 3)
    assert family is sm.FAMILY
    eng = DecodeEngine(params, cfg, KVCachePool(cfg, 1, family=family),
                       capacity=1)
    out = llm_ahead.requests_for(cfg, 3 + 40, eng)
    # the synchronous path is the reference's greedy continuation
    (prompt, max_new, _), want = out[0]
    seq = np.concatenate([prompt, np.asarray(want[:-1], np.int32)])
    logits = ref.forward_logits(params, seq, MODEL)[len(prompt) - 1:]
    assert want == [int(r.argmax()) for r in logits]
    # prompts of one to five chunks of 8; the fifth request fills its
    # slot (rows, rings and recurrent rows) to max_seq exactly
    assert sorted(-(-len(r[0][0]) // 8) for r in out) == [1, 1, 1, 2, 2, 5]
    assert len(out[4][0][0]) + out[4][0][1] == cfg.max_seq
    return out


@pytest.mark.parametrize("props, every_lane", [
    ("slots=6 batch=6", True),          # every stream a lane
    ("slots=4 batch=2", False),         # round-robin pick
    ("slots=2 batch=2", True)])         # slots, rings and rows reused
def test_element_one_step_ahead_serves_the_synchronous_streams(
        ahead_requests, props, every_lane):
    """The element dispatches step k before it has read step k-1; a
    stream that ends by its stop token rides one more step, which writes
    one more row, ring entry and recurrent state into a slot the next
    prompt's first chunk then starts clean."""
    import llm_ahead

    got, report = llm_ahead.serve(CUSTOM, 3, props, 3 + 40, ahead_requests)
    llm_ahead.check(got, report, ahead_requests, every_lane=every_lane)
    assert report["prefill_chunks"] == 12


def test_element_drains_with_a_step_in_flight(ahead_requests):
    import llm_ahead

    got, report = llm_ahead.serve(CUSTOM, 3, "slots=6 batch=4", 3 + 40,
                                  ahead_requests, drain=True)
    assert report["live_after_drain"] == 0
    llm_ahead.check(got, report, ahead_requests, every_lane=False)


# -- streamformer_lm through the same seam -------------------------------
def test_streamformer_through_the_seam_is_bit_equal():
    """The default family's engine (state as one donated tuple, the
    prefill's install moved into the family) gives, to the bit, the
    pools the model's own functions give when called as the engine used
    to call them, and samples the greedy token of their logits: prefill,
    then steps at one lane and at a padded bucket."""
    from nnstreamer_tpu.models import streamformer_lm as sf
    from nnstreamer_tpu.parallel.train_step import init_params

    family, rest = family_of_custom(
        {"vocab": "61", "dim": "32", "heads": "4", "head_dim": "8",
         "mlp": "64", "layers": "2", "experts": "2", "max_seq": "64",
         "dtype": "bfloat16"})
    assert family is sf.FAMILY and family.paged
    cfg = family.config_from_custom(rest)
    params = init_params(cfg, 5)
    pool = KVCachePool(cfg, 3)
    assert pool.family is family and pool.k is pool.arrays[0]
    eng = DecodeEngine(params, cfg, pool, capacity=4)
    prompt = np.random.default_rng(1).integers(0, 61, 11).astype(np.int32)

    # as the engine called them before the seam
    k = v = jnp.zeros(pool.k.shape, cfg.dtype)
    buf = np.zeros((16,), np.int32)
    buf[:11] = prompt
    logits, ks, vs = jax.jit(partial(sf.prefill_kv, cfg=cfg, flash=None))(
        params, jnp.asarray(buf))
    run = (cfg.layers, 1, 16, -1)
    k = jax.lax.dynamic_update_slice(k, ks.reshape(run), (0, 1, 0, 0))
    v = jax.lax.dynamic_update_slice(v, vs.reshape(run), (0, 1, 0, 0))
    want_first = np.asarray(logits[10])

    sess = pool.acquire("a")
    sess.slot = 1
    fn = eng._prefill_fn(16)
    first, sampled, pool.arrays = fn(
        eng.params, pool.arrays, jnp.zeros((4,), jnp.int32),
        jnp.asarray(buf), jnp.int32(1), jnp.int32(11))
    assert int(first) == int(np.argmax(want_first))
    assert np.asarray(sampled).tolist() == [0, int(first), 0, 0]
    assert np.array_equal(np.asarray(pool.k, np.float32),
                          np.asarray(k, np.float32))
    step = jax.jit(partial(sf.decode_step_pooled, cfg=cfg))
    for lanes in (1, 4):
        toks = jnp.asarray([7] + [0] * (lanes - 1), jnp.int32)
        pos = jnp.asarray([11] + [0] * (lanes - 1), jnp.int32)
        slots = jnp.asarray([1] + [3] * (lanes - 1), jnp.int32)
        want, k2, v2 = step(params, k, v, toks, pos, slots)
        # the lanes' tokens come from the rows their slots name
        got, sampled, state = eng._step_fn(lanes)(
            eng.params, pool.arrays, jnp.asarray([0, 7, 0, 0], jnp.int32),
            pos, slots)
        pool.arrays = state
        assert got.dtype == jnp.int32 and np.array_equal(
            np.asarray(got), np.argmax(np.asarray(want), -1))
        assert int(sampled[1]) == int(got[0])
        assert np.array_equal(np.asarray(state[1], np.float32),
                              np.asarray(v2, np.float32))
        k, v = k2, v2
