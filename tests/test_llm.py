"""LLM serving tier (nnstreamer_tpu/llm): session-keyed KV-cache pool +
continuous-batching decode plane.

The consistency contract, end-to-end: token-by-token decode THROUGH the
``tensor_llm`` element — sessions joining and leaving a shared decode
bucket — reproduces the full-sequence ``forward_logits`` math at every
position (pinned against the compiled ``generate()`` scan, which the
streamformer suite pins against ``forward_logits``).  Plus the serving
invariants: slot admission sheds explicitly (T_SHED with retry-after,
never unbounded memory), per-client token order is exact, mid-stream
disconnect reclaims the slot with zero leaked pooled slabs, and the
decode thread's prefill/decode wall-time attribution is 100 % conserved
by construction.
"""

import gc
import os
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.analysis.verify import verify_pipeline
from nnstreamer_tpu.llm.client import (TokenStreamClient,
                                       TokenTimeoutError, encode_request)
from nnstreamer_tpu.llm.engine import (DecodeEngine, PhaseClock,
                                       quantize_pages, quantize_prompt)
from nnstreamer_tpu.llm.paged import PagedKVCachePool, chain_hashes
from nnstreamer_tpu.llm.pool import KVCachePool, dense_pool_shape
from nnstreamer_tpu.models.streamformer_lm import (_slot_rows,
                                                   config_from_custom,
                                                   decode_step,
                                                   decode_step_paged,
                                                   decode_step_pooled,
                                                   forward_logits,
                                                   generate, init_cache,
                                                   prefill_kv)
from nnstreamer_tpu.parallel.train_step import (StreamFormerConfig,
                                                init_params)
from nnstreamer_tpu.query.overload import ShedError
from nnstreamer_tpu.query.server import get_server, shutdown_server
from nnstreamer_tpu.tensor.buffer import TensorBuffer, default_pool


def _cfg(**kw):
    base = dict(vocab=61, dim=32, heads=4, head_dim=8, mlp=64, layers=2,
                experts=2, max_seq=48, dtype=jnp.float32)
    base.update(kw)
    return StreamFormerConfig(**base)


CUSTOM = ("vocab:61,dim:32,heads:4,head_dim:8,mlp:64,layers:2,"
          "max_seq:48,dtype:float32")
REQ_CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=24,"
            "types=int32,framerate=0/1")


def wait_until(cond, timeout=15.0, step=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


# ---------------------------------------------------------------------------
# core math
# ---------------------------------------------------------------------------

class TestPooledDecode:
    def test_lanes_equal_solo_decode_steps(self):
        """Lane i of one pooled step == a solo decode_step on slot i's
        cache — the batched serving tier's correctness spine."""
        cfg = _cfg()
        params = init_params(cfg, 1)
        shape = dense_pool_shape(cfg, 3)
        kp = jnp.zeros(shape, cfg.dtype)
        vp = jnp.zeros(shape, cfg.dtype)
        toks = jnp.asarray([5, 17, 42], jnp.int32)
        logits, kp, vp = decode_step_pooled(
            params, kp, vp, toks, jnp.zeros(3, jnp.int32),
            jnp.arange(3, dtype=jnp.int32), cfg)
        for i, t in enumerate([5, 17, 42]):
            solo, _ = decode_step(params, init_cache(cfg),
                                  jnp.int32(t), cfg)
            np.testing.assert_allclose(np.asarray(logits[i]),
                                       np.asarray(solo),
                                       atol=1e-4, rtol=1e-4)

    def test_padding_lane_cannot_touch_live_slots(self):
        """Padding lanes write the SCRATCH slot only: a partial bucket's
        pad rows must never corrupt a resident session's cache."""
        cfg = _cfg()
        params = init_params(cfg, 2)
        shape = dense_pool_shape(cfg, 2)
        kp = jnp.ones(shape, cfg.dtype)
        vp = jnp.ones(shape, cfg.dtype)
        # lane 0 live (slot 0), lane 1 = padding pointed at scratch (2)
        _, kp2, _ = decode_step_pooled(
            params, kp, vp, jnp.asarray([3, 0], jnp.int32),
            jnp.asarray([0, 0], jnp.int32),
            jnp.asarray([0, 2], jnp.int32), cfg)
        # slot 1 (untouched live slot) is bit-identical
        np.testing.assert_array_equal(np.asarray(kp2[:, 1]),
                                      np.asarray(kp[:, 1]))

    def test_teacher_forced_pooled_decode_matches_full_forward(self):
        """The consistency contract at the math layer: stepping a fixed
        token sequence through the pooled cache reproduces
        forward_logits at EVERY position."""
        cfg = _cfg()
        params = init_params(cfg, 3)
        toks = np.random.default_rng(0).integers(0, 61, 14)
        full = np.asarray(forward_logits(
            params, jnp.asarray(toks, jnp.int32), cfg, flash=False))
        shape = dense_pool_shape(cfg, 1)
        kp = jnp.zeros(shape, cfg.dtype)
        vp = jnp.zeros(shape, cfg.dtype)
        for i, t in enumerate(toks):
            logits, kp, vp = decode_step_pooled(
                params, kp, vp, jnp.asarray([t], jnp.int32),
                jnp.asarray([i], jnp.int32),
                jnp.asarray([0], jnp.int32), cfg)
            np.testing.assert_allclose(np.asarray(logits[0]), full[i],
                                       atol=1e-4, rtol=1e-4)

    def test_prefill_kv_matches_decode_scan(self):
        """prefill_kv's logits == forward_logits; its K/V == what a
        decode_step scan over the prompt would have cached."""
        cfg = _cfg()
        params = init_params(cfg, 4)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 61, 11), jnp.int32)
        full = forward_logits(params, toks, cfg, flash=False)
        logits, ks, vs = prefill_kv(params, toks, cfg, flash=False)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   atol=1e-4, rtol=1e-4)
        cache = init_cache(cfg)
        for t in toks:
            _, cache = decode_step(params, cache, t, cfg)
        np.testing.assert_allclose(np.asarray(ks),
                                   np.asarray(cache["k"][:, :11]),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(vs),
                                   np.asarray(cache["v"][:, :11]),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("max_seq", [48, 64, 256, 512, 1000])
    def test_slot_rows_is_the_plain_gather(self, max_seq):
        """The step's read (blocks of up to 256 positions through a
        flat view of the pool) gives ``pool[layer][slots]`` to the bit:
        one block a row, several, and a ``max_seq`` that 256 does not
        divide; a slot named twice, the scratch slot, every layer."""
        cfg = _cfg(layers=3, max_seq=max_seq)
        shape = dense_pool_shape(cfg, 4)
        pool = jnp.asarray(
            np.random.default_rng(max_seq).standard_normal(shape),
            jnp.bfloat16)
        slots = jnp.asarray([3, 0, 4, 3, 1], jnp.int32)
        for li in range(cfg.layers):
            rows = _slot_rows(pool, li, slots)
            assert rows.shape == (5,) + shape[2:]
            np.testing.assert_array_equal(
                np.asarray(rows.astype(jnp.float32)),
                np.asarray(pool[li][slots].astype(jnp.float32)))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_step_writes_exactly_its_rows(self, dtype):
        """A step's scatter lands in the ``(layer, slot, pos)`` row of
        each lane, for every layer, in the live slots and the scratch
        one; every other byte of both pools is bit-identical, and the
        rows hold what a solo decode_step on that slot would cache."""
        cfg = _cfg(layers=3, dtype=dtype)
        params = init_params(cfg, 6)
        shape = dense_pool_shape(cfg, 3)
        rng = np.random.default_rng(6)
        kp = jnp.asarray(rng.standard_normal(shape), dtype)
        vp = jnp.asarray(rng.standard_normal(shape), dtype)
        toks, slots, pos = [5, 17, 0], [2, 0, 3], [5, 0, 7]  # 3 = scratch
        _, kp2, vp2 = decode_step_pooled(
            params, kp, vp, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32),
            cfg)
        assert kp2.shape == vp2.shape == shape and kp2.dtype == dtype
        written = np.zeros(shape[:3], bool)
        written[:, slots, pos] = True
        for before, after in ((kp, kp2), (vp, vp2)):
            before = np.asarray(before.astype(jnp.float32))
            after = np.asarray(after.astype(jnp.float32))
            np.testing.assert_array_equal(after[~written],
                                          before[~written])
            assert (after[written] != before[written]).any(axis=-1).all()
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        for tok, slot, p in zip(toks, slots, pos):
            solo = {"k": kp[:, slot], "v": vp[:, slot]}
            solo = {n: a.reshape(cfg.layers, cfg.max_seq, cfg.heads,
                                 cfg.head_dim) for n, a in solo.items()}
            _, cache = decode_step(params, dict(solo, pos=jnp.int32(p)),
                                   jnp.int32(tok), cfg)
            for name, pool in (("k", kp2), ("v", vp2)):
                np.testing.assert_allclose(
                    np.asarray(pool[:, slot, p].astype(jnp.float32)),
                    np.asarray(cache[name][:, p].astype(jnp.float32)
                               ).reshape(cfg.layers, -1),
                    atol=tol, rtol=tol)

    def test_engine_prefill_then_pooled_steps_match_decode_scan(self):
        """test_prefill_kv_matches_decode_scan's contract through the
        engine: ``_prefill`` installs the run into its slot's rows (and
        no other slot's), and pooled steps from there on, each fed the
        continuation's token (``next_token``), sample the token a
        decode_step scan over prompt + continuation gives."""
        cfg = _cfg(layers=3)
        params = init_params(cfg, 4)
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, 61, 11).astype(np.int32)
        cont = rng.integers(0, 61, 5)
        pool = KVCachePool(cfg, 3)
        assert pool.k.shape == pool.v.shape == dense_pool_shape(cfg, 3)
        eng = DecodeEngine(params, cfg, pool, capacity=2,
                           prefill_mode="naive")
        pool.acquire("other")
        sess = pool.acquire("a")
        first = eng.prefill(sess, prompt)
        cache, scan = init_cache(cfg), []
        for t in list(prompt) + list(cont):
            logits, cache = decode_step(params, cache, jnp.int32(t), cfg)
            scan.append(np.asarray(logits))
        assert first == int(np.argmax(scan[10]))
        for name, arr in (("k", pool.k), ("v", pool.v)):
            arr = np.asarray(arr)
            np.testing.assert_allclose(
                arr[:, sess.slot, :11],
                np.asarray(cache[name][:, :11]).reshape(cfg.layers, 11,
                                                        -1),
                atol=1e-4, rtol=1e-4)
            others = np.delete(arr, sess.slot, axis=1)
            assert not others.any()      # the install touched one slot
        for i, t in enumerate(cont):
            assert sess.pos == 11 + i
            sess.next_token = int(t)
            assert eng.step([sess]) == [int(np.argmax(scan[11 + i]))]
            assert sess.next_token is None       # consumed by the step

    @staticmethod
    def _written_pools(cfg, slots, seed, scale=1.0):
        """Both dense pools of ``slots`` sessions (+ scratch), every
        position written with seeded values rounded to ``cfg.dtype``."""
        rng = np.random.default_rng(seed)
        shape = dense_pool_shape(cfg, slots)
        return tuple(jnp.asarray(scale * rng.standard_normal(shape),
                                 cfg.dtype) for _ in range(2))

    def test_bfloat16_rows_match_the_float32_solo_step(self):
        """The row-form attention with bfloat16 operands (``q`` and
        ``p`` rounded for the MXU, float32 accumulation) against the
        float32 solo ``decode_step`` on the same weights and the same
        cached values: three lanes at positions on both sides of a
        256-position block edge of ``_slot_rows``, plus a padding lane
        on the scratch slot."""
        base = dict(max_seq=512, layers=3)
        cfg, cfg32 = _cfg(dtype=jnp.bfloat16, **base), _cfg(**base)
        params = init_params(cfg32, 30)
        kp, vp = self._written_pools(cfg, 3, 30)
        toks, slots, pos = [5, 17, 42, 0], [2, 0, 1, 3], [255, 256, 300, 0]
        logits, _, _ = decode_step_pooled(
            params, kp, vp, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(slots, jnp.int32),
            cfg)
        assert logits.dtype == jnp.float32
        for lane in range(3):
            cache = {n: a[:, slots[lane]].astype(jnp.float32).reshape(
                cfg.layers, cfg.max_seq, cfg.heads, cfg.head_dim)
                for n, a in (("k", kp), ("v", vp))}
            solo, _ = decode_step(
                params, dict(cache, pos=jnp.int32(pos[lane])),
                jnp.int32(toks[lane]), cfg32)
            np.testing.assert_allclose(np.asarray(logits[lane]),
                                       np.asarray(solo),
                                       atol=5e-2, rtol=5e-2)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dead_sessions_rows_beyond_pos_are_masked(self, dtype):
        """A slot whose rows beyond ``pos`` still hold a longer dead
        session's values, large and finite, gives the logits of a fresh
        slot: the mask holds through the products (a masked position's
        weight is an exact zero, whatever its key and value)."""
        cfg = _cfg(dtype=dtype, max_seq=64)
        params = init_params(_cfg(max_seq=64), 31)
        live, _ = self._written_pools(cfg, 1, 31)
        dead, _ = self._written_pools(cfg, 1, 32, scale=3e4)
        upto = jnp.arange(cfg.max_seq)[None, None, :, None] <= 9
        args = (jnp.asarray([7], jnp.int32), jnp.asarray([9], jnp.int32),
                jnp.asarray([0], jnp.int32), cfg)
        fresh, _, _ = decode_step_pooled(
            params, jnp.where(upto, live, 0), jnp.where(upto, live, 0),
            *args)
        reused, _, _ = decode_step_pooled(
            params, jnp.where(upto, live, dead),
            jnp.where(upto, live, dead), *args)
        assert np.isfinite(np.asarray(reused)).all()
        np.testing.assert_array_equal(np.asarray(reused),
                                      np.asarray(fresh))

    def test_paged_step_is_the_pooled_steps_mathematics(self):
        """One helper, one mathematics: the same three sessions in a
        paged arena (pages out of order, a scratch-padded table) and in
        the dense pool give the same bfloat16 step far inside the
        bfloat16 tolerance; only the masked tail's length (``W * page``
        against ``max_seq``) differs."""
        cfg = _cfg(dtype=jnp.bfloat16, max_seq=64, layers=3)
        params = init_params(_cfg(max_seq=64, layers=3), 33)
        kp, vp = self._written_pools(cfg, 3, 33)
        ps, w = 8, 4                           # 32 of 64 positions paged
        tables = np.asarray([[5, 2, 9, 12], [0, 7, 12, 12], [11, 3, 1, 6]])
        arena = (13, cfg.layers, ps, cfg.heads, cfg.head_dim)  # 12: scratch
        pages = []
        for dense in (kp, vp):
            a = np.zeros(arena, np.float32)
            rows = np.asarray(dense.astype(jnp.float32))
            for slot in range(3):
                for j, page in enumerate(tables[slot]):
                    if page != 12:
                        a[page] = rows[:, slot, j * ps:(j + 1) * ps].reshape(
                            arena[1:])
            pages.append(jnp.asarray(a, cfg.dtype))
        toks = jnp.asarray([5, 17, 42], jnp.int32)
        pos = jnp.asarray([20, 9, 31], jnp.int32)
        dense, _, _ = decode_step_pooled(
            params, kp, vp, toks, pos, jnp.arange(3, dtype=jnp.int32), cfg)
        paged, kpg, _ = decode_step_paged(
            params, *pages, toks, pos, jnp.asarray(tables, jnp.int32), cfg,
            ps)
        assert kpg.shape == arena
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   atol=1e-3, rtol=1e-3)

    def test_pooled_logits_and_pools_are_the_parents_values(self):
        """Same values in, same values out: a seeded 3-slot, 14-token
        run's logits and both pools, against what the parent of the
        attention's row form (PR 29; to the bit what PR 24's ``(S, L, T,
        H, Dh)`` pool gave) computed on the CPU.  The greedy tokens are
        the parent's; the values agree to a float32 sum's order (the
        scores are summed over a row's ``H * Dh`` columns, all but a
        head's own ``Dh`` of them exact zeros)."""
        cfg = _cfg()
        params = init_params(cfg, 26)
        toks = np.random.default_rng(26).integers(0, 61, (14, 3))
        shape = dense_pool_shape(cfg, 3)
        kp, vp = jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)
        out = []
        for i in range(14):
            logits, kp, vp = decode_step_pooled(
                params, kp, vp, jnp.asarray(toks[i], jnp.int32),
                jnp.full((3,), i, jnp.int32),
                jnp.asarray([2, 0, 1], jnp.int32), cfg)
            out.append(np.asarray(logits))
        out = np.stack(out)
        assert out.argmax(-1).tolist() == PARENT_ARGMAX
        with np.load(PARENT_VALUES) as parent:
            np.testing.assert_allclose(out, parent["logits"],
                                       atol=1e-5, rtol=1e-5)
            for name, pool in (("k", kp), ("v", vp)):
                pool = np.asarray(pool)
                np.testing.assert_allclose(pool[:, :, :14], parent[name],
                                           atol=1e-5, rtol=1e-5)
                assert not pool[:, :, 14:].any()


#: recorded from commit 8ed891c (PR 24) before the pool's layout changed:
#: argmax per (step, lane)
PARENT_ARGMAX = [
    [3, 57, 22], [52, 45, 56], [26, 47, 47], [19, 19, 49], [42, 42, 42],
    [8, 42, 14], [59, 20, 23], [52, 56, 56], [0, 50, 60], [6, 20, 43],
    [40, 60, 55], [38, 42, 34], [17, 58, 14], [17, 49, 21]]
#: recorded from commit 8310dbe (PR 29), whose SHA-256 of each array was
#: PR 24's: the (14, 3, 61) float32 logits and the written positions
#: ``[:, :, :14]`` of the final K and V pools (the rest are zeros)
PARENT_VALUES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "test_llm_parent_values.npz")


class TestCustomGrammar:
    def test_width_alias_and_max_seq(self):
        cfg = config_from_custom({"width": "64", "layers": "3",
                                  "heads": "2", "head_dim": "8",
                                  "max_seq": "128"})
        assert (cfg.dim, cfg.layers, cfg.heads, cfg.max_seq) \
            == (64, 3, 2, 128)

    def test_conflicting_aliases_rejected(self):
        with pytest.raises(ValueError, match="alias"):
            config_from_custom({"dim": "64", "width": "128"})

    def test_max_seq_must_hold_window(self):
        with pytest.raises(ValueError, match="max_seq"):
            config_from_custom({"seq": "128", "max_seq": "64"})

    def test_sizes_validated(self):
        with pytest.raises(ValueError, match=">= 1"):
            config_from_custom({"layers": "0"})

    def test_quantize_prompt_bounded(self):
        assert quantize_prompt(1, 1024) == 8
        assert quantize_prompt(8, 1024) == 8
        assert quantize_prompt(9, 1024) == 16
        assert quantize_prompt(900, 1024) == 1024
        assert quantize_prompt(40, 48) == 48   # capped at max_seq


# ---------------------------------------------------------------------------
# pool
# ---------------------------------------------------------------------------

class TestKVCachePool:
    def _pool(self, slots=4, clock=None):
        return KVCachePool(_cfg(), slots, clock=clock)

    def test_acquire_release_cycle(self):
        pool = self._pool(2)
        a = pool.acquire("a")
        b = pool.acquire("b")
        assert {a.slot, b.slot} == {0, 1}
        assert pool.live == 2 and pool.occupancy == 1.0
        assert pool.admit("gold") is not None   # hard boundary
        pool.release("a")
        assert pool.admit("gold") is None
        c = pool.acquire("c")
        assert c.slot == a.slot                  # slot recycled

    def test_duplicate_key_rejected(self):
        pool = self._pool(2)
        pool.acquire("a")
        with pytest.raises(ValueError, match="already live"):
            pool.acquire("a")

    def test_qos_watermarks_shed_bronze_before_full(self):
        """Bronze sessions shed at 80 % slot occupancy (hysteretic),
        gold only at the hard no-free-slot boundary."""
        pool = self._pool(10)
        for i in range(8):
            pool.acquire(i)
        assert pool.admit("bronze") is not None   # armed at 0.8
        assert pool.admit("gold") is None
        # hysteresis: dropping just under the arm point stays armed
        pool.release(7)
        assert pool.admit("bronze") is not None
        for i in range(7):
            pool.release(i)
        assert pool.admit("bronze") is None       # disarmed at half

    def test_no_slot_hint_passthrough(self):
        pool = self._pool(1)
        pool.acquire("a", qos="gold")
        assert pool.admit("gold", no_slot_retry_s=1.5) \
            == pytest.approx(1.5)

    def test_aged_keys_injected_clock(self):
        now = [100.0]
        pool = self._pool(4, clock=lambda: now[0])
        pool.acquire("old")
        now[0] = 103.0
        pool.acquire("young")
        assert pool.aged_keys(5.0) == []
        now[0] = 106.0
        assert pool.aged_keys(5.0) == ["old"]
        assert pool.aged_keys(0.0) == []          # disabled

    def test_cache_bytes_constant(self):
        pool = self._pool(3)
        before = pool.cache_bytes()
        for i in range(3):
            pool.acquire(i)
        assert pool.cache_bytes() == before
        cfg = pool.cfg
        want = (4 * cfg.layers * cfg.max_seq * cfg.heads * cfg.head_dim
                * np.dtype(np.float32).itemsize * 2)
        assert before == want

    def test_lru_key(self):
        now = [0.0]
        pool = self._pool(3, clock=lambda: now[0])
        pool.acquire("a")
        now[0] = 1.0
        pool.acquire("b")
        now[0] = 2.0
        pool.touch("a")
        assert pool.lru_key() == "b"


class TestPhaseClock:
    def test_conservation_identity(self):
        ms = 1_000_000                     # ns per ms
        now = [0]
        clk = PhaseClock(clock_ns=lambda: now[0])
        now[0] = 10 * ms
        clk.enter("admit")
        now[0] = 30 * ms
        prev = clk.enter("prefill")
        assert prev == "admit"
        now[0] = 70 * ms
        clk.enter(prev)
        now[0] = 100 * ms
        rep = clk.report()
        assert rep["conserved_pct"] == 100.0
        s = rep["states_s"]
        assert s["idle"] == pytest.approx(0.010)
        assert s["admit"] == pytest.approx(0.020 + 0.030)
        assert s["prefill"] == pytest.approx(0.040)


class _FakePhases:
    """A hand-cranked PhaseClock stand-in: tests set the totals dict
    directly, so blame folds are checked against exact integers."""

    def __init__(self, **totals):
        self.totals = dict(totals)

    def totals_ns(self):
        return dict(self.totals)


class _FakeSess:
    def __init__(self, key="k", qos="gold"):
        self.key = key
        self.qos = qos
        self.extra = {}
        self.obs = None


class TestTokenObs:
    """Token-level observability (ISSUE 20): TTFT/ITL math under an
    injected clock, blame conservation against the PhaseClock identity,
    shed/evict exclusion from the histograms, and the monotone blame
    counter mirror."""

    def _fixture(self, phases=None):
        from nnstreamer_tpu.llm.tokenobs import TokenObs
        from nnstreamer_tpu.obs.metrics import MetricsRegistry

        now = [0]
        reg = MetricsRegistry()
        tobs = TokenObs(phases if phases is not None else _FakePhases(),
                        clock_ns=lambda: now[0], registry=reg,
                        labels={"element": "t", "pipeline": "t"})
        return now, reg, tobs

    def _hist_state(self, reg, family):
        snap = reg.snapshot_state(prefix="nns_llm_")
        return {k: v for k, v in snap.items()
                if k.partition("{")[0] == family
                and v["kind"] == "histogram"}

    def test_ttft_and_itl_from_injected_clock(self):
        """TTFT is admit -> FIRST emitted token (chunk interleave
        included: two chunks happen in between and change nothing);
        every later token observes the inter-token gap."""
        from nnstreamer_tpu.llm.tokenobs import ITL_US, TTFT_US

        now, reg, tobs = self._fixture()
        s = _FakeSess()
        now[0] = 1_000
        tobs.on_admit(s)
        tobs.on_chunk(s)
        tobs.on_chunk(s)
        now[0] = 2_501_000                      # +2.5 ms to first token
        tobs.on_token(s)
        now[0] = 2_601_000                      # +100 us gap
        tobs.on_token(s)
        now[0] = 2_801_000                      # +200 us gap
        tobs.on_token(s)
        (ttft,) = self._hist_state(reg, TTFT_US).values()
        assert ttft["count"] == 1
        assert ttft["total"] == pytest.approx(2_500.0)    # us
        (itl,) = self._hist_state(reg, ITL_US).values()
        assert itl["count"] == 2
        assert itl["total"] == pytest.approx(300.0)
        assert s.obs.tokens == 3 and s.obs.chunks == 2

    def test_blame_conserves_phaseclock_wall_time(self):
        """A session's accumulated blame sums EXACTLY to its
        admit->terminal window: the snapshots partition the decode
        thread's wall time, so conservation is integer arithmetic."""
        ms = 1_000_000
        now = [0]
        clk = PhaseClock(clock_ns=lambda: now[0])
        _, _, tobs = self._fixture(phases=clk)
        tobs._clock_ns = lambda: now[0]
        s = _FakeSess(qos="silver")
        now[0] = 10 * ms
        tobs.on_admit(s)
        clk.enter("prefill")
        now[0] = 30 * ms
        clk.enter("decode")
        now[0] = 50 * ms
        tobs.on_token(s)                        # first token
        clk.enter("llm-prefill-chunk")          # another session's chunk
        now[0] = 70 * ms
        clk.enter("decode")
        now[0] = 90 * ms
        tobs.on_token(s)
        clk.enter("idle")
        now[0] = 100 * ms
        tobs.on_terminal(s, "stop")
        rec = tobs.records()[-1]
        assert rec["cause"] == "stop" and rec["tokens"] == 2
        assert rec["ttft_us"] == pytest.approx(40_000.0)
        blame = rec["blame_ns"]
        # both prefill phases fold to the steal cause; the partition
        # covers the 90 ms admit->terminal window to the nanosecond
        assert blame["prefill-chunk-steal"] == 40 * ms
        assert blame["decode-compute"] == 40 * ms
        assert blame["idle"] == 10 * ms
        assert sum(blame.values()) == 90 * ms
        assert rec["blame_conserved_pct"] == 100.0

    def test_shed_evict_excluded_from_histograms(self):
        """Refused streams and token-less evictions land in the
        terminal-cause counters ONLY: a fast refusal must not flatter
        p50, a reaped zombie must not poison p99."""
        from nnstreamer_tpu.llm.tokenobs import (ITL_US, TERMINAL_TOTAL,
                                                 TTFT_US)

        now, reg, tobs = self._fixture()
        tobs.on_refused("silver", "shed")
        tobs.on_refused("silver", "shed")
        tobs.on_refused("gold", "reject")
        s = _FakeSess()
        now[0] = 1_000
        tobs.on_admit(s)
        now[0] = 9_000_000
        tobs.on_terminal(s, "evict")            # reaped before a token
        assert not self._hist_state(reg, TTFT_US)
        assert not self._hist_state(reg, ITL_US)
        snap = reg.snapshot_state(prefix="nns_llm_")
        causes = {}
        for key, st in snap.items():
            if key.partition("{")[0] == TERMINAL_TOTAL:
                cause = key.partition('cause="')[2].partition('"')[0]
                causes[cause] = causes.get(cause, 0) + st["value"]
        assert causes == {"shed": 2, "reject": 1, "evict": 1}
        assert s.obs is None                    # record closed exactly once
        assert tobs.records()[-1]["cause"] == "evict"

    def test_sync_blame_counters_monotone_no_double_publish(self):
        from nnstreamer_tpu.llm.tokenobs import BLAME_NS_TOTAL

        phases = _FakePhases(decode=100, prefill=50)
        _, reg, tobs = self._fixture(phases=phases)

        def _blame(reg):
            out = {}
            for key, st in reg.snapshot_state(
                    prefix="nns_llm_").items():
                if key.partition("{")[0] == BLAME_NS_TOTAL:
                    cause = key.partition(
                        'cause="')[2].partition('"')[0]
                    out[cause] = st["value"]
            return out

        tobs.sync_blame_counters()
        assert _blame(reg) == {"decode-compute": 100,
                               "prefill-chunk-steal": 50}
        tobs.sync_blame_counters()              # idempotent: no growth
        assert _blame(reg)["decode-compute"] == 100
        phases.totals["decode"] = 175
        phases.totals["llm-prefill-chunk"] = 25
        tobs.sync_blame_counters()
        assert _blame(reg) == {"decode-compute": 175,
                               "prefill-chunk-steal": 75}

    def test_cold_engine_first_dispatch_charged_to_compile(self):
        """A fresh (un-warmed) engine's first decode step compiles; the
        PhaseClock charges that wall time to ``compile``, not
        ``decode`` — blame must name the cold start, not smear it over
        decode-compute."""
        cfg = _cfg()
        params = init_params(cfg, 1)
        pool = KVCachePool(cfg, 2)
        eng = DecodeEngine(params, cfg, pool, capacity=2)
        s = pool.acquire("a")
        s.max_new, s.next_token = 2, 5
        eng.step([s])
        tot = eng.phases.totals_ns()
        assert tot.get("compile", 0) > 0
        # the compiled dispatch dominates the warm part of the step
        assert tot["compile"] > tot["decode"]
        pool.release("a")

    def test_chrome_events_session_lanes(self):
        now, _, tobs = self._fixture()
        s = _FakeSess(key="sess-1")
        now[0] = 1_000_000
        tobs.on_admit(s)
        now[0] = 3_000_000
        tobs.on_token(s)
        now[0] = 5_000_000
        tobs.on_token(s)
        now[0] = 6_000_000
        tobs.on_terminal(s, "max_new")
        events = tobs.chrome_events(pid=9)
        meta = [e for e in events if e["ph"] == "M"]
        spans = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in meta} == {"process_name",
                                             "thread_name"}
        assert [e["name"] for e in spans] == ["ttft", "decode"]
        ttft, decode = spans
        assert ttft["dur"] == pytest.approx(2_000.0)      # us
        assert decode["dur"] == pytest.approx(3_000.0)
        assert decode["args"]["cause"] == "max_new"
        assert decode["args"]["tokens"] == 2
        # metadata sorts ahead of spans (the chrome_trace merge key)
        assert events[:len(meta)] == meta


class TestEngine:
    def test_bounded_executables_across_fills(self):
        """Sequences joining/leaving between steps never recompile:
        after warmup, every fill level hits a warm padded executable."""
        import jax

        from nnstreamer_tpu.models.registry import host_init

        cfg = _cfg()
        # host-built weights, as tensor_llm builds them: the engine
        # places them — and the pool — on the pool's device, committed
        params = host_init(lambda: init_params(cfg, 5))
        pool = KVCachePool(cfg, 8)
        eng = DecodeEngine(params, cfg, pool, capacity=8)
        device = next(iter(pool.k.devices()))
        for leaf in jax.tree_util.tree_leaves(eng.params) + [pool.k,
                                                             pool.v]:
            assert leaf.committed and leaf.devices() == {device}
        eng.warmup()
        compiled = eng.compiles
        built = []   # XLA's own count, not the engine's bookkeeping
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **kw: built.append(kw.get("fun_name"))
            if event == "/jax/core/compile/backend_compile_duration"
            else None)
        sessions = [pool.acquire(i) for i in range(5)]
        for s in sessions:
            s.max_new = 4
            s.next_token = s.key + 1
        for fill in (5, 3, 1, 4, 2):
            eng.step(sessions[:fill])
        assert eng.compiles == compiled
        assert built == [], built
        assert eng.steps_total == 5

    def test_retry_after_hint_tracks_soonest_finisher(self):
        cfg = _cfg()
        params = init_params(cfg, 5)
        pool = KVCachePool(cfg, 2)
        eng = DecodeEngine(params, cfg, pool, capacity=2)
        a = pool.acquire("a")
        a.max_new, a.emitted = 10, 8
        b = pool.acquire("b")
        b.max_new, b.emitted = 30, 0
        eng.ewma_step_s = 0.1
        assert eng.retry_after_hint() == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# element: the consistency contract END TO END
# ---------------------------------------------------------------------------

def build_local(extra_props="", custom=CUSTOM, caps=REQ_CAPS):
    p = parse_launch(
        f"appsrc name=src caps={caps} ! "
        f"tensor_llm name=llm custom={custom} seed=0 {extra_props} ! "
        "tensor_sink name=out")
    by_key = {}
    order = []

    def on_data(b):
        key = b.extra.get("tag")
        tok = int(np.asarray(b.tensors[0]).reshape(-1)[0])
        by_key.setdefault(key, []).append((b.pts, tok, b.extra))
        order.append(key)
    p.get("out").connect("new-data", on_data)
    return p, by_key, order


class TestElementLocal:
    def test_sessions_share_bucket_and_match_generate(self):
        """THE contract: sessions joining/leaving a shared decode
        bucket token-by-token THROUGH the element reproduce the
        compiled generate() scan (itself pinned against forward_logits
        at every position)."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 61, 4 + 2 * i).astype(np.int32)
                   for i in range(3)]
        lens = [7, 4, 9]   # heterogeneous: sessions LEAVE at different
        #                    steps while others continue
        refs = [generate(params, cfg, pr, n).tolist()
                for pr, n in zip(prompts, lens)]
        p, by_key, _ = build_local("slots=4 batch=4")
        p.play()
        for i, (pr, n) in enumerate(zip(prompts, lens)):
            buf = TensorBuffer(tensors=[encode_request(
                pr, max_new=n, frame_len=24)])
            buf.extra["tag"] = i
            p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=180)
        p.stop()
        for i in range(3):
            toks = [t for _, t, _ in by_key[i]]
            pts = [q for q, _, _ in by_key[i]]
            assert pts == list(range(lens[i]))      # exact order
            assert toks == refs[i], (i, toks, refs[i])
            # streaming markers: every frame but the last carries
            # nns_more
            mores = [bool(e.get("nns_more")) for _, _, e in by_key[i]]
            assert mores == [True] * (lens[i] - 1) + [False]

    def test_stop_token_ends_stream_early(self):
        """The stream ends AT the first stop-token frame (delivered,
        then the slot releases)."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        prompt = np.asarray([3, 1, 4], np.int32)
        ref = generate(params, cfg, prompt, 12).tolist()
        stop = ref[4]   # a token generate() emits mid-stream
        want = ref[:5]
        p, by_key, _ = build_local("slots=2 batch=2")
        p.play()
        buf = TensorBuffer(tensors=[encode_request(
            prompt, max_new=12, stop_token=stop, frame_len=24)])
        buf.extra["tag"] = 0
        p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=120)
        p.stop()
        assert [t for _, t, _ in by_key[0]] == want

    def test_overlength_prompt_refused_terminally(self):
        """prompt + max_new > max_seq can never succeed: one terminal
        stop-token frame, no shed, no session."""
        p, by_key, _ = build_local("slots=2 batch=2")
        p.play()
        buf = TensorBuffer(tensors=[encode_request(
            np.arange(20, dtype=np.int32), max_new=40, stop_token=9,
            frame_len=24)])
        buf.extra["tag"] = 0
        p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=60)
        llm = p.get("llm")
        assert llm.rejected_total == 1
        assert llm.sessions_total == 0
        p.stop()
        assert [t for _, t, _ in by_key[0]] == [9]

    def test_standalone_slot_shed_is_tagged(self):
        """No server table: a slot shed still yields an explicit,
        final, tagged answer (never a silent drop)."""
        p, by_key, _ = build_local("slots=1 batch=1")
        p.play()
        for i in range(2):
            buf = TensorBuffer(tensors=[encode_request(
                np.asarray([1, 2], np.int32), max_new=25,
                stop_token=-1, frame_len=24)])
            buf.extra["tag"] = i
            p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=120)
        llm = p.get("llm")
        p.stop()
        assert llm.shed_total == 1
        shed_frames = [e for frames in by_key.values()
                       for _, _, e in frames if "nns_llm_shed" in e]
        assert len(shed_frames) == 1
        # the admitted session still streamed fully
        full = [k for k, frames in by_key.items() if len(frames) == 25]
        assert len(full) == 1

    def test_phase_attribution_conserved(self):
        p, by_key, _ = build_local("slots=2 batch=2")
        p.play()
        buf = TensorBuffer(tensors=[encode_request(
            np.asarray([5, 6, 7], np.int32), max_new=8, frame_len=24)])
        buf.extra["tag"] = 0
        p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=60)
        report = p.get("llm").engine.report()
        p.stop()
        phases = report["phases"]
        assert phases["conserved_pct"] == pytest.approx(100.0, abs=0.1)
        assert phases["states_s"]["prefill"] > 0
        assert phases["states_s"]["decode"] > 0
        assert report["tokens"] == 8


# ---------------------------------------------------------------------------
# element over the query wire
# ---------------------------------------------------------------------------

SID = 4510

#: long-cache sizing for the tests that need a stream still RUNNING
#: while something else happens (sheds, disconnects): hundreds of
#: decode steps of wall-clock window
CUSTOM_LONG = ("vocab:61,dim:32,heads:4,head_dim:8,mlp:64,layers:2,"
               "max_seq:2048,dtype:float32")


def build_server(extra="slots=4 batch=4", sid=SID, src_extra="",
                 custom=CUSTOM):
    p = parse_launch(
        f"tensor_query_serversrc name=qsrc id={sid} port=0 {src_extra} "
        f"caps={REQ_CAPS} ! "
        f"tensor_llm name=llm custom={custom} seed=0 {extra} id={sid} ! "
        f"tensor_query_serversink id={sid}")
    p.play()
    return p, p.get("qsrc").bound_port


class TestElementWire:
    def teardown_method(self):
        shutdown_server(SID)

    def test_multi_client_streams_exact_order_and_content(self):
        """Concurrent clients with heterogeneous prompt/output lengths:
        every stream arrives complete, in exact order (pts 0,1,2,… —
        TokenStreamClient raises on any violation), token-identical to
        the reference scan."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        p, port = build_server()
        rng = np.random.default_rng(3)
        jobs = [(rng.integers(0, 61, 3 + i).astype(np.int32), 4 + 2 * i)
                for i in range(4)]
        refs = [generate(params, cfg, pr, n).tolist() for pr, n in jobs]
        results = {}

        def run(i):
            cli = TokenStreamClient("127.0.0.1", port,
                                    timeout=60.0).connect()
            try:
                pr, n = jobs[i]
                results[i] = cli.generate(pr, n, frame_len=24)
            except Exception as exc:  # noqa: BLE001 — asserted below
                results[i] = repr(exc)
            finally:
                cli.close()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        srv = get_server(SID)
        assert wait_until(lambda: srv._inflight == 0, timeout=10)
        p.stop()
        for i in range(4):
            assert results[i] == refs[i], (i, results[i])
        gc.collect()
        assert default_pool().stats["pending"] == 0

    def test_slot_exhaustion_sheds_explicitly(self):
        """slots=1: a second concurrent stream gets an explicit T_SHED
        with a retry-after — never queued as unbounded memory."""
        p, port = build_server("slots=1 batch=1 max-new-tokens=1500",
                               sid=SID, custom=CUSTOM_LONG)
        a = TokenStreamClient("127.0.0.1", port, timeout=60.0).connect()
        b = TokenStreamClient("127.0.0.1", port, timeout=20.0).connect()
        stream = a.stream(np.asarray([1, 2, 3], np.int32), 1200,
                          frame_len=24)
        next(stream)                      # session A is resident
        llm = p.get("llm")
        assert wait_until(lambda: llm.pool.live == 1, timeout=10)
        with pytest.raises(ShedError) as err:
            b.generate(np.asarray([4], np.int32), 5, frame_len=24)
        assert err.value.retry_after_s > 0
        assert llm.shed_total >= 1
        a.close()                          # disconnect mid-stream
        b.close()
        assert wait_until(lambda: llm.pool.live == 0, timeout=15)
        assert llm.evicted_total >= 1      # slot reclaimed
        srv = get_server(SID)
        assert wait_until(lambda: srv._inflight == 0, timeout=10)
        p.stop()
        gc.collect()
        assert default_pool().stats["pending"] == 0

    def test_disconnect_mid_stream_reclaims_slot_no_leaks(self):
        """A client vanishing mid-stream: its session evicts, the slot
        frees for the next session, peers are unaffected, ZERO pooled
        slabs leak."""
        cfg = _cfg(max_seq=2048)
        params = init_params(cfg, 0)
        p, port = build_server("slots=2 batch=2 max-new-tokens=1500",
                               custom=CUSTOM_LONG)
        llm = p.get("llm")
        doomed = TokenStreamClient("127.0.0.1", port,
                                   timeout=60.0).connect()
        stream = doomed.stream(np.asarray([9, 9], np.int32), 1200,
                               frame_len=24)
        for _ in range(3):
            next(stream)
        doomed.close()                     # vanish mid-stream
        assert wait_until(lambda: llm.pool.live == 0, timeout=15)
        assert llm.evicted_total == 1
        # the pool is whole again: a fresh session serves correctly
        pr = np.asarray([2, 4, 6], np.int32)
        ref = generate(params, cfg, pr, 6).tolist()
        survivor = TokenStreamClient("127.0.0.1", port,
                                     timeout=60.0).connect()
        assert survivor.generate(pr, 6, frame_len=24) == ref
        survivor.close()
        srv = get_server(SID)
        assert wait_until(lambda: srv._inflight == 0, timeout=10)
        p.stop()
        shutdown_server(SID)
        gc.collect()
        assert default_pool().stats["pending"] == 0

    def test_duplicate_wire_seq_cannot_error_the_pipeline(self):
        """A client REUSING a wire seq while its first stream is
        resident (hostile or buggy — query_seq is client-controlled)
        must not collide session keys and error the pipeline every
        other client shares (code-review finding: pool.acquire's
        duplicate-key ValueError reached the decode loop's
        post_error)."""
        import socket as _socket

        from nnstreamer_tpu.query.protocol import (T_DATA,
                                                   send_tensors)

        cfg = _cfg(max_seq=2048)
        params = init_params(cfg, 0)
        p, port = build_server("slots=4 batch=4 max-new-tokens=1500",
                               custom=CUSTOM_LONG)
        sock = _socket.create_connection(("127.0.0.1", port),
                                         timeout=10)
        req = encode_request(np.asarray([1, 2], np.int32), 1200,
                             frame_len=24)
        # two requests, SAME seq, pipelined on one connection
        send_tensors(sock, T_DATA, TensorBuffer(tensors=[req]), seq=7)
        send_tensors(sock, T_DATA, TensorBuffer(tensors=[req]), seq=7)
        llm = p.get("llm")
        assert wait_until(lambda: llm.pool.live == 2, timeout=15)
        assert p._error is None if hasattr(p, "_error") else True
        # an unrelated client still serves correctly end to end
        ref = generate(params, cfg, np.asarray([3, 4], np.int32),
                       5).tolist()
        cli = TokenStreamClient("127.0.0.1", port,
                                timeout=60.0).connect()
        assert cli.generate(np.asarray([3, 4], np.int32), 5,
                            frame_len=24) == ref
        cli.close()
        sock.close()
        assert wait_until(lambda: llm.pool.live == 0, timeout=15)
        p.stop()

    def test_overcap_request_ends_with_terminal_marker(self):
        """A request asking MORE than the server's max-new-tokens cap
        is truncated — and the stream says so: cap tokens plus one
        explicit terminal marker frame, never a silent clamp the
        client (counting toward ITS ask) would wait out as a timeout
        (code-review finding)."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        p, port = build_server("slots=2 batch=2 max-new-tokens=6")
        pr = np.asarray([4, 5], np.int32)
        ref = generate(params, cfg, pr, 6).tolist()
        cli = TokenStreamClient("127.0.0.1", port, timeout=30.0).connect()
        t0 = time.monotonic()
        toks = cli.generate(pr, 30, frame_len=24)   # asks 30, cap 6
        assert time.monotonic() - t0 < 15.0
        assert toks == ref + [-1]       # 6 real tokens + the marker
        cli.close()
        p.stop()

    def test_refusal_is_terminal_without_stop_token(self):
        """An over-length request from a client with NO stop token set
        must end the stream immediately (negative tokens are
        unconditionally terminal), not hang until the per-token
        timeout (code-review finding)."""
        p, port = build_server("slots=2 batch=2")
        cli = TokenStreamClient("127.0.0.1", port, timeout=60.0).connect()
        t0 = time.monotonic()
        toks = cli.generate(np.arange(20, dtype=np.int32), 40,
                            stop_token=-1, frame_len=24)
        assert toks == [-1]                 # one terminal marker frame
        assert time.monotonic() - t0 < 10.0
        cli.close()
        p.stop()

    def test_drain_finishes_streams_and_sheds_new(self):
        """Pipeline.drain: resident streams complete, a late request
        sheds with a drain-sized retry-after."""
        p, port = build_server("slots=2 batch=2")
        cli = TokenStreamClient("127.0.0.1", port, timeout=60.0).connect()
        stream = cli.stream(np.asarray([1, 2], np.int32), 30,
                            frame_len=24)
        got = [next(stream)]
        done = threading.Event()

        def _drain():
            p.drain(deadline=30.0)
            done.set()

        threading.Thread(target=_drain, daemon=True).start()
        llm = p.get("llm")
        assert wait_until(lambda: llm.pool.admission.draining,
                          timeout=10)
        got.extend(stream)                 # the stream COMPLETES
        assert len(got) == 30
        assert done.wait(timeout=30)
        cli.close()
        p.stop()


# ---------------------------------------------------------------------------
# verifier rules
# ---------------------------------------------------------------------------

class TestVerifyRules:
    def _findings(self, llm_props, custom=CUSTOM):
        p = parse_launch(
            f"appsrc name=src caps={REQ_CAPS} ! "
            f"tensor_llm name=llm custom={custom} {llm_props} ! "
            "fakesink")
        return verify_pipeline(p)

    def _rules(self, findings):
        return {f.rule for f in findings}

    def test_slots_lt_batch_is_named_error(self):
        fs = self._findings("slots=2 batch=8")
        hit = [f for f in fs if f.rule == "llm-slots-lt-batch"]
        assert hit and hit[0].severity == "error"
        assert "llm" in hit[0].path

    def test_no_max_seq_is_named_error(self):
        fs = self._findings(
            "slots=4 batch=2",
            custom="vocab:61,dim:32,heads:4,head_dim:8,layers:2")
        hit = [f for f in fs if f.rule == "llm-no-max-seq"]
        assert hit and hit[0].severity == "error"

    def test_prefill_step_warns_decode_without_prefill(self):
        fs = self._findings("slots=4 batch=2 prefill=step")
        hit = [f for f in fs if f.rule == "llm-decode-without-prefill"]
        assert hit and hit[0].severity == "warning"

    def test_clean_config_has_no_llm_findings(self):
        fs = self._findings("slots=4 batch=2")
        assert not [f for f in fs if f.rule.startswith("llm-")]

    def test_preflight_rejects_bad_config_at_play(self):
        from nnstreamer_tpu.pipeline.graph import VerifyError

        p = parse_launch(
            f"appsrc name=src caps={REQ_CAPS} ! "
            f"tensor_llm name=llm custom={CUSTOM} slots=2 batch=8 ! "
            "fakesink")
        with pytest.raises(VerifyError, match="llm-slots-lt-batch"):
            p.play()


# ---------------------------------------------------------------------------
# pinned perf_diff gate on the committed acceptance artifact
# ---------------------------------------------------------------------------

class TestPerfDiffPinned:
    """The committed SOAK_llm_r15.json rows pin the perf_diff gate: an
    eroded continuous-batching win FAILS and the attribution delta
    names the regressed stage (the test_xbatch.py discipline)."""

    def _load(self):
        import importlib.util
        import json
        import os

        root = os.path.join(os.path.dirname(__file__), "..")
        spec = importlib.util.spec_from_file_location(
            "perf_diff", os.path.join(root, "tools", "perf_diff.py"))
        pd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pd)
        with open(os.path.join(root, "SOAK_llm_r15.json"),
                  encoding="utf-8") as fh:
            rows = json.load(fh)["rows"]
        return pd, rows

    def test_committed_rows_self_pass(self):
        pd, rows = self._load()
        verdict = pd.diff([rows, rows], rows, margin_pct=10.0)
        assert verdict["pass"], verdict

    def test_eroded_win_regresses_and_names_stage(self):
        import copy

        pd, rows = self._load()
        eroded = copy.deepcopy(rows)
        for row in eroded:
            if row["metric"] == "soak_llm_tokens_per_s":
                row["value"] *= 0.4          # batching win collapsed
                states = row.setdefault("attribution", {}).setdefault(
                    "states", {})
                # e.g. a donation regression: per-step pool copies land
                # as decode share while tokens/s falls
                states["decode"] = states.get("decode", 0.0) + 25.0
        verdict = pd.diff([rows, rows], eroded, margin_pct=10.0)
        assert not verdict["pass"]
        reg = [r for r in verdict["regressions"]
               if r["metric"] == "soak_llm_tokens_per_s"]
        assert reg, verdict["regressions"]
        blame = reg[0].get("attribution")
        assert blame and blame["regressed_stage"] == "decode"

    def test_committed_artifact_gates_hold(self):
        """The committed artifact itself must BE a pass with every
        acceptance box checked — committing a FAIL (or a gutted
        verdict) turns tier-1 red here."""
        import json
        import os

        root = os.path.join(os.path.dirname(__file__), "..")
        with open(os.path.join(root, "SOAK_llm_r15.json"),
                  encoding="utf-8") as fh:
            v = json.load(fh)
        assert v["pass"] and v["verdict"] == "PASS"
        checks = v["llm"]["checks"]
        for name in ("zero_errors", "exact_order", "sheds_explicit",
                     "cache_bounded", "batched_2x_solo",
                     "consistency_under_batching",
                     "attribution_conserved", "disconnects_reclaimed"):
            assert checks.get(name) is True, (name, checks)
        assert v["llm"]["speedup_vs_solo"] >= 2.0
        assert v["attribution"]["conserved_pct"] == 100.0

# ---------------------------------------------------------------------------
# paged KV cache (block tables + prefix reuse + chunked prefill)
# ---------------------------------------------------------------------------

class TestQuantizePages:
    def test_pow2_widths_bounded(self):
        assert [quantize_pages(n, 12) for n in (1, 2, 3, 4, 5, 8, 9, 12)] \
            == [1, 2, 4, 4, 8, 8, 12, 12]
        # the warm set over a 12-page table is {1, 2, 4, 8, 12}: five
        # executables cover EVERY session length
        assert {quantize_pages(n, 12) for n in range(1, 13)} \
            == {1, 2, 4, 8, 12}


class TestChainHashes:
    def test_chain_extends_not_commutes(self):
        """h_j commits to the WHOLE prefix, not page j alone: two
        prompts sharing page 1's bytes but not page 0's must not
        collide (a positional hash would cross-link their caches)."""
        a = chain_hashes(np.arange(8, dtype=np.int32), 4)
        b = chain_hashes(np.concatenate([np.arange(4, 8),
                                         np.arange(4, 8)]).astype(np.int32),
                         4)
        assert len(a) == len(b) == 2
        assert a[1] != b[1]          # same page-1 tokens, different chain

    def test_partial_tail_page_never_hashed(self):
        assert len(chain_hashes(np.arange(7, dtype=np.int32), 4)) == 1


class TestPagedPool:
    def _pool(self, pages=59, slots=8, ps=4, clock=None, **kw):
        return PagedKVCachePool(_cfg(), pages, ps, slots=slots,
                                clock=clock, **kw)

    def test_arena_bytes_match_dense_at_element_sizing(self):
        """(slots+1)*table_max - 1 pages + scratch == the dense pool's
        (slots+1) full-length lanes, byte for byte — the residency
        claim is apples to apples."""
        cfg = _cfg()
        dense = KVCachePool(cfg, 8)
        paged = self._pool(pages=(8 + 1) * 12 - 1, slots=8)
        assert paged.cache_bytes() == dense.cache_bytes()

    def test_prefix_hit_pins_and_cow_isolates(self):
        # 10 tokens = 2 full pages + a 2-token tail (the tail keeps the
        # exact-length cap out of the way: cap (10-1)//4 = 2 pages)
        pool = self._pool()
        prompt = (np.arange(10) % 61).astype(np.int32)
        a = pool.acquire("a", prompt=prompt, max_new=4)
        pool.grow(a, 10)
        pool.note_prefill(a, 10)
        shared = list(a.table[:2])
        pool.release("a")
        assert pool.stats()["reclaimable"] == 2   # registered, refs 0
        b = pool.acquire("b", prompt=prompt, max_new=4)
        assert pool.prefix_hits == 1
        assert b.shared_tokens == 8 and b.prefill_pos == 8
        assert b.table[:2] == shared              # the SAME pages
        pool.grow(b, 10)                          # b's private tail page
        assert pool._page_hash[b.table[2]] is None  # unhashed: COW land
        pool.release("b")
        assert pool.free_pages == pool.pages
        assert pool.check_leaks() == []

    def test_hit_capped_below_full_prompt(self):
        """An exact-length hit must leave >= 1 suffix token to compute
        (the model needs a forward pass to emit token 0)."""
        pool = self._pool()
        prompt = (np.arange(8) % 61).astype(np.int32)
        a = pool.acquire("a", prompt=prompt, max_new=2)
        pool.grow(a, 8)
        pool.note_prefill(a, 8)
        pool.release("a")
        b = pool.acquire("b", prompt=prompt, max_new=2)
        assert b.shared_tokens == 4               # cap (8-1)//4 = 1 page
        pool.release("b")

    def test_admission_is_commitment_based(self):
        """admit() reasons about worst-case PAGES net of the prefix
        hit, not slots: a request whose private remainder cannot fit
        sheds BEFORE acquire, so grow() can never fail mid-stream."""
        pool = self._pool(pages=7, slots=8)
        big = np.arange(20, dtype=np.int32) % 61
        assert pool.admit("gold", prompt=big, max_new=9) is not None
        assert pool.admit("gold", prompt=big, max_new=8) is None
        sess = pool.acquire("a", prompt=big, max_new=8)
        pool.grow(sess, 28)                       # the full commitment
        assert pool.admit("gold", prompt=np.arange(4, dtype=np.int32),
                          max_new=1) is not None  # arena exhausted
        pool.release("a")
        assert pool.check_leaks() == []

    def test_reclaim_is_lru_and_reset_frees(self):
        pool = self._pool(pages=8, slots=4)
        for i, key in enumerate(("a", "b")):
            prompt = ((np.arange(8) + 10 * i) % 61).astype(np.int32)
            s = pool.acquire(key, prompt=prompt, max_new=4)
            pool.grow(s, 8)
            pool.note_prefill(s, 8)
            pool.release(key)
        assert pool.stats()["reclaimable"] == 4
        assert pool.free_pages == 8
        # allocation pressure past the free list (4 free pages, c needs
        # 5) reclaims a registered page, LRU chain first
        c = pool.acquire("c", prompt=np.full(12, 7, np.int32), max_new=8)
        pool.grow(c, 20)
        assert pool.pages_reclaimed >= 1
        pool.release("c")
        assert pool.reset_prefix_cache() > 0
        assert pool.stats()["reclaimable"] == 0
        assert pool.free_pages == 8

    def test_fragmentation_churn_property(self):
        """Satellite 3: randomized join/leave churn — short chats,
        shared prefixes, mid-prefill abandons, cache resets — must end
        with every page back (free_pages == pages) and zero refcount /
        reservation leaks, under an injected clock (no wall-time
        dependence).  The mid-churn conservation identity holds too:
        free + reclaimable + uniquely-held == pages at every audit."""
        t = {"now": 0.0}
        pool = self._pool(clock=lambda: t["now"])
        rng = np.random.default_rng(1234)
        live = {}
        for step in range(400):
            t["now"] += 0.01
            roll = rng.random()
            if live and (len(live) >= pool.slots or roll < 0.40):
                key = list(live)[int(rng.integers(0, len(live)))]
                live.pop(key)
                pool.release(key)
            elif roll < 0.45:
                pool.reset_prefix_cache()
            else:
                plen = int(rng.integers(1, 20))
                max_new = int(rng.integers(1, 12))
                if rng.random() < 0.5:   # shared-prompt family: hits
                    prompt = (np.arange(plen) % 61).astype(np.int32)
                else:
                    prompt = rng.integers(0, 61, plen).astype(np.int32)
                if pool.admit("silver", prompt=prompt,
                              max_new=max_new) is not None:
                    continue
                key = f"s{step}"
                sess = pool.acquire(key, prompt=prompt, max_new=max_new)
                live[key] = sess
                # drive the engine's paged life cycle to a random depth:
                # abandon mid-prefill, after prefill, or mid-decode
                upto = int(rng.integers(sess.prefill_pos, plen + 1))
                pool.grow(sess, upto)
                pool.note_prefill(sess, upto)
                if upto == plen and rng.random() < 0.7:
                    pool.grow(sess, plen + int(rng.integers(0, max_new)))
            if step % 25 == 0:
                held = {pg for s in live.values() for pg in s.table}
                stats = pool.stats()
                assert stats["free"] + stats["reclaimable"] \
                    + len(held) == pool.pages, (step, stats)
        for key in list(live):
            pool.release(key)
        assert pool.free_pages == pool.pages
        assert pool.check_leaks() == []
        assert pool.stats()["reserved"] == 0


PAGED = "slots=4 batch=4 page-size=4"


class TestPagedElementLocal:
    def _refs(self, params, cfg, prompts, lens):
        from nnstreamer_tpu.models.streamformer_lm import generate
        return [generate(params, cfg, pr, n).tolist()
                for pr, n in zip(prompts, lens)]

    def _run(self, props, prompts, lens, sequential=False):
        p, by_key, _ = build_local(props)
        p.play()
        for i, (pr, n) in enumerate(zip(prompts, lens)):
            buf = TensorBuffer(tensors=[encode_request(
                pr, max_new=n, frame_len=24)])
            buf.extra["tag"] = i
            p.get("src").push_buffer(buf)
            if sequential:
                assert wait_until(
                    lambda i=i, n=n: len(by_key.get(i, [])) >= n,
                    timeout=120)
        p.get("src").end_of_stream()
        p.wait(timeout=180)
        llm = p.get("llm")
        eng, pool = llm.engine, llm.pool
        snap = {                 # stop() drops engine+pool: snapshot
            "paged": eng.paged, "chunk": eng.chunk,
            "compiles": eng.compiles, "report": eng.report(),
            "cache_bytes": pool.cache_bytes(),
            "prefix_hits": getattr(pool, "prefix_hits", 0),
            "prefix_tokens_reused": getattr(pool,
                                            "prefix_tokens_reused", 0),
            "free_pages": getattr(pool, "free_pages", None),
            "pages": getattr(pool, "pages", None),
            "leaks": (pool.check_leaks()
                      if hasattr(pool, "check_leaks") else []),
        }
        p.stop()
        return by_key, snap

    def test_paged_whole_prefill_matches_generate(self):
        """THE paged contract: block-table decode through the element
        is token-byte-identical to the compiled generate() scan."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 61, 4 + 2 * i).astype(np.int32)
                   for i in range(3)]
        lens = [7, 4, 9]
        refs = self._refs(params, cfg, prompts, lens)
        by_key, snap = self._run(PAGED + " prefill-chunk=0",
                                 prompts, lens)
        assert snap["paged"]
        for i in range(3):
            toks = [t for _, t, _ in by_key[i]]
            pts = [q for q, _, _ in by_key[i]]
            assert pts == list(range(lens[i]))
            assert toks == refs[i], (i, toks, refs[i])

    def test_paged_chunked_prefill_matches_generate(self):
        """Chunked prefill (bounded chunks interleaved with decode
        steps) lands on the SAME tokens: prompts longer than the chunk
        force multi-chunk prefills while earlier sessions decode."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, 61, n).astype(np.int32)
                   for n in (13, 5, 17)]
        lens = [6, 8, 5]
        refs = self._refs(params, cfg, prompts, lens)
        by_key, snap = self._run(PAGED + " prefill-chunk=4",
                                 prompts, lens)
        assert snap["chunk"] == 4
        assert snap["report"]["prefill_chunks"] >= 2
        for i in range(3):
            assert [t for _, t, _ in by_key[i]] == refs[i]

    def test_prefix_hit_reuses_pages_and_isolates_tails(self):
        """Two sessions sharing an 8-token system prompt, run back to
        back: the second admits onto the first's registered pages (hit
        counted, 8 tokens never re-prefilled) and BOTH streams still
        match their own generate() — copy-on-write isolation."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        pre = (np.arange(8) % 61).astype(np.int32)
        prompts = [np.concatenate([pre, np.asarray(t, np.int32)])
                   for t in ([3, 9], [44, 1])]
        lens = [6, 6]
        refs = self._refs(params, cfg, prompts, lens)
        by_key, snap = self._run(PAGED, prompts, lens,
                                 sequential=True)
        assert snap["prefix_hits"] >= 1
        assert snap["prefix_tokens_reused"] >= 8
        for i in range(2):
            assert [t for _, t, _ in by_key[i]] == refs[i]
        assert snap["leaks"] == []
        assert snap["free_pages"] == snap["pages"]

    def test_dense_mode_unchanged_and_bytes_equal(self):
        """page-size=0 still runs the dense pool, and the default
        paged arena sizes to the SAME device bytes."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        prompts = [np.asarray([5, 6, 7], np.int32)]
        refs = self._refs(params, cfg, prompts, [5])
        by_key, snap_d = self._run("slots=4 batch=4 page-size=0",
                                   prompts, [5])
        assert not snap_d["paged"]
        assert [t for _, t, _ in by_key[0]] == refs[0]
        by_key2, snap_p = self._run(PAGED, prompts, [5])
        assert [t for _, t, _ in by_key2[0]] == refs[0]
        assert snap_p["cache_bytes"] == snap_d["cache_bytes"]

    def test_zero_steady_state_compiles_after_warmup(self):
        """The pow2 width/row grid warmed at start() covers the whole
        serving mix: a heterogeneous session stream adds ZERO compiles
        (the bounded-executables contract, paged edition)."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        p, by_key, _ = build_local(PAGED + " prefill-chunk=4")
        p.play()
        warm = p.get("llm").engine.compiles
        rng = np.random.default_rng(3)
        # 4 sessions for 4 slots: nothing sheds, every stream completes
        for i, (plen, n) in enumerate([(3, 5), (14, 7),
                                       (19, 6), (1, 8)]):
            buf = TensorBuffer(tensors=[encode_request(
                rng.integers(0, 61, plen).astype(np.int32),
                max_new=n, frame_len=24)])
            buf.extra["tag"] = i
            p.get("src").push_buffer(buf)
        p.get("src").end_of_stream()
        p.wait(timeout=180)
        compiles = p.get("llm").engine.compiles
        p.stop()
        assert sum(len(v) for v in by_key.values()) == 5 + 7 + 6 + 8
        assert compiles == warm, (warm, compiles)


# ---------------------------------------------------------------------------
# one decode step in flight (tests/llm_ahead.py holds the scenario)
# ---------------------------------------------------------------------------

class TestOneStepInFlight:
    """The element dispatches step k before it has read step k-1: the
    streams it serves are the synchronous path's and ``generate()``'s,
    to the token, whatever the pool and however the lanes are picked."""

    @pytest.fixture(scope="class")
    def requests(self):
        import llm_ahead

        family, cfg, params = llm_ahead.world(CUSTOM, 0)
        eng = DecodeEngine(params, cfg, KVCachePool(cfg, 1, family=family),
                           capacity=1)
        out = llm_ahead.requests_for(cfg, 24, eng)
        for (prompt, max_new, stop), want in out:
            ref = generate(params, cfg, prompt, max_new).tolist()
            assert want == (ref if stop < 0
                            else ref[:ref.index(stop) + 1])
        # the fifth ends on its slot's last row but one: prompt +
        # max_new == max_seq, the most admission grants
        prompt, max_new, _ = out[4][0]
        assert len(prompt) + max_new == cfg.max_seq == 48
        return out

    @pytest.mark.parametrize("props", [
        "slots=6 batch=6",                       # every stream a lane
        "slots=4 batch=2",                       # round-robin pick
        "slots=2 batch=2",                       # slots reused
        "slots=4 batch=4 page-size=16 prefill-chunk=0",
        "slots=2 batch=2 page-size=16 prefill-chunk=0",
        "slots=4 batch=2 page-size=4 prefill-chunk=4",   # interleaved
    ])
    def test_streams_are_the_synchronous_paths(self, requests, props):
        import llm_ahead

        got, report = llm_ahead.serve(CUSTOM, 0, props, 24, requests)
        llm_ahead.check(got, report, requests,
                        every_lane="batch=2" not in props
                        or "slots=2" in props)
        if "page-size" in props:
            assert report["paged"]["live"] == 0
            assert report["paged"]["free"] + report["paged"][
                "reclaimable"] == report["paged"]["pages"]

    def test_drain_with_a_step_in_flight_finishes_every_stream(
            self, requests):
        import llm_ahead

        got, report = llm_ahead.serve(CUSTOM, 0, "slots=6 batch=4", 24,
                                      requests, drain=True)
        assert report["live_after_drain"] == 0
        llm_ahead.check(got, report, requests, every_lane=False)

    def test_step_refuses_to_run_beside_a_step_in_flight(self):
        cfg = _cfg()
        pool = KVCachePool(cfg, 2)
        eng = DecodeEngine(init_params(cfg, 1), cfg, pool, capacity=2)
        a, b = pool.acquire("a"), pool.acquire("b")
        for s in (a, b):
            s.max_new = 8
            eng.prefill(s, np.asarray([3, 1, 4], np.int32))
        eng.dispatch([a, b])
        assert eng.in_flight == 1 and (a.pos, a.in_flight) == (4, 1)
        with pytest.raises(RuntimeError, match="in flight"):
            eng.step([a])
        eng.dispatch([a])                      # one ahead of the other
        with pytest.raises(RuntimeError, match="two steps in flight"):
            eng.dispatch([a])
        pool.release("b")                      # ends while its lane runs
        first = eng.collect()
        assert [s.key for s, _ in first] == ["a"]
        assert eng.lanes_discarded == 1 and b.in_flight == 0
        (sess, tok), = eng.collect()
        assert sess is a and a.in_flight == 0 and eng.in_flight == 0
        assert eng.steps_total == 2 and eng.steps_ahead == 1
        assert eng.step_tokens == 2


# ---------------------------------------------------------------------------
# per-token inactivity timeout (client)
# ---------------------------------------------------------------------------

class TestTokenTimeout:
    def test_stalled_stream_raises_named_error_and_drains(self):
        """A server that accepts the request then never replies: the
        stream raises TokenTimeoutError (not a bare socket timeout) at
        the per-token deadline, carrying how many tokens arrived — and
        the reply queue's leased slabs are drained, not leaked."""
        import socket

        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]
        held = []

        def accept_and_stall():
            conn, _ = srv.accept()
            held.append(conn)          # read nothing, send nothing

        t = threading.Thread(target=accept_and_stall, daemon=True)
        t.start()
        gc.collect()
        pending0 = default_pool().stats["pending"]
        cli = TokenStreamClient("127.0.0.1", port, timeout=30.0,
                                token_timeout=0.3).connect()
        t0 = time.monotonic()
        with pytest.raises(TokenTimeoutError) as ei:
            cli.generate(np.asarray([1, 2, 3], np.int32), 8,
                         frame_len=24)
        took = time.monotonic() - t0
        assert took < 5.0                      # the PER-TOKEN deadline,
        #                                        not the 30 s transport
        assert ei.value.got == 0
        assert ei.value.timeout_s == pytest.approx(0.3)
        assert isinstance(ei.value, TimeoutError)
        cli.close()
        for c in held:
            c.close()
        srv.close()
        gc.collect()
        assert default_pool().stats["pending"] == pending0

    def test_stream_override_beats_constructor_default(self):
        import socket

        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]
        held = []
        threading.Thread(target=lambda: held.append(srv.accept()),
                         daemon=True).start()
        cli = TokenStreamClient("127.0.0.1", port, timeout=30.0,
                                token_timeout=20.0).connect()
        with pytest.raises(TokenTimeoutError) as ei:
            list(cli.stream(np.asarray([1], np.int32), 4, frame_len=24,
                            token_timeout=0.2))
        assert ei.value.timeout_s == pytest.approx(0.2)
        cli.close()
        for c, _ in held:
            c.close()
        srv.close()

    def test_healthy_stream_unaffected(self):
        """A generous per-token budget never fires on a live server."""
        cfg = _cfg()
        params = init_params(cfg, 0)
        prompt = np.asarray([5, 6], np.int32)
        ref = generate(params, cfg, prompt, 6).tolist()
        p, port = build_server("slots=2 batch=2", sid=SID + 70)
        cli = TokenStreamClient("127.0.0.1", port, timeout=60.0,
                                token_timeout=30.0).connect()
        assert cli.generate(prompt, 6, frame_len=24) == ref
        cli.close()
        p.stop()
        shutdown_server(SID + 70)


class TestVerifyRulesPaged:
    def _findings(self, llm_props, custom=CUSTOM):
        p = parse_launch(
            f"appsrc name=src caps={REQ_CAPS} ! "
            f"tensor_llm name=llm custom={custom} {llm_props} ! "
            "fakesink")
        return verify_pipeline(p)

    def test_page_size_must_tile_max_seq(self):
        fs = self._findings("slots=4 batch=2 page-size=5")
        hit = [f for f in fs if f.rule == "llm-page-size"]
        assert hit and hit[0].severity == "error"
        assert "tile" in hit[0].message

    def test_negative_page_size_is_named_error(self):
        fs = self._findings("slots=4 batch=2 page-size=-1")
        assert [f for f in fs if f.rule == "llm-page-size"]

    def test_prefix_without_pages_is_named_error(self):
        fs = self._findings("slots=4 batch=2 page-size=0 prefix-cache=1")
        hit = [f for f in fs if f.rule == "llm-prefix-without-pages"]
        assert hit and hit[0].severity == "error"

    def test_chunk_without_pages_is_named_error(self):
        fs = self._findings("slots=4 batch=2 page-size=0 "
                            "prefill-chunk=8")
        assert [f for f in fs
                if f.rule == "llm-prefix-without-pages"]

    def test_clean_paged_config_has_no_findings(self):
        fs = self._findings("slots=4 batch=2 page-size=4 "
                            "prefill-chunk=8 prefix-cache=1")
        assert not [f for f in fs if f.rule.startswith("llm-")]


# ---------------------------------------------------------------------------
# perf_diff: renamed/vanished metrics FAIL by name
# ---------------------------------------------------------------------------

def _load_perf_diff():
    import importlib.util
    import os

    root = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "perf_diff", os.path.join(root, "tools", "perf_diff.py"))
    pd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pd)
    return pd


class TestPerfDiffMissingMetric:
    def _row(self, metric, value, unit="tokens_per_s"):
        return {"metric": metric, "value": value, "unit": unit,
                "status": "live"}

    def test_renamed_metric_fails_and_names_suspect(self):
        """Satellite: a candidate whose stage renamed its metric key
        must FAIL with the old name AND point at the likely new key —
        not silently skip the band it was gated by."""
        pd = _load_perf_diff()
        base = [self._row("soak_llm_tokens_per_s", 100.0)]
        cand = [self._row("soak_llm_tok_s", 99.0)]
        verdict = pd.diff([base, base], cand)
        assert not verdict["pass"]
        missing = [r for r in verdict["regressions"]
                   if r["verdict"] == "MISSING"]
        assert missing and missing[0]["metric"] == "soak_llm_tokens_per_s"
        assert missing[0]["rename_suspects"] == ["soak_llm_tok_s"]
        assert "soak_llm_tok_s" in missing[0]["reason"]

    def test_single_baseline_sample_still_fails_missing(self):
        """Even ONE baseline run measuring the metric arms the check:
        a single-sample metric can never regress by value (no band),
        but vanishing entirely is a gate failure regardless."""
        pd = _load_perf_diff()
        a = [self._row("hotpath_llmpaged_tok_s", 50.0),
             self._row("other", 1.0)]
        b = [self._row("other", 1.0)]
        cand = [self._row("other", 1.0)]
        verdict = pd.diff([a, b], cand)
        assert not verdict["pass"]
        missing = [r for r in verdict["regressions"]
                   if r["verdict"] == "MISSING"]
        assert missing[0]["metric"] == "hotpath_llmpaged_tok_s"
        assert "1 baseline run(s)" in missing[0]["reason"]
        assert "rename_suspects" not in missing[0]

    def test_present_metric_still_passes(self):
        pd = _load_perf_diff()
        base = [self._row("a", 100.0)]
        verdict = pd.diff([base, base], [self._row("a", 101.0)])
        assert verdict["pass"]


class TestPerfDiffTokenLatencyDirection:
    """Satellite (ISSUE 20): ``ttft``/``itl``/``latency`` metric-name
    tokens pin lower-is-better regardless of how a row spelled its
    unit — an inflated first-token latency must read as REGRESSION."""

    def _row(self, metric, value, unit=""):
        return {"metric": metric, "value": value, "unit": unit,
                "status": "live"}

    @pytest.mark.parametrize("metric", [
        "soak_llm_paged_ttft_p99",       # bare unit: name token only
        "soak_llm_itl_p99",
        "client_latency_mean",
    ])
    def test_inflated_token_latency_regresses(self, metric):
        pd = _load_perf_diff()
        base = [self._row(metric, 100_000.0)]
        verdict = pd.diff([base, base],
                          [self._row(metric, 1_000_000.0)])
        assert not verdict["pass"]
        assert [r for r in verdict["regressions"]
                if r["metric"] == metric]

    def test_reduced_ttft_is_an_improvement(self):
        pd = _load_perf_diff()
        base = [self._row("soak_llm_ttft_p99", 100_000.0)]
        verdict = pd.diff([base, base],
                          [self._row("soak_llm_ttft_p99", 50_000.0)])
        assert verdict["pass"]


# ---------------------------------------------------------------------------
# pinned perf_diff gate on the committed paged acceptance artifact
# ---------------------------------------------------------------------------

class TestPerfDiffPinnedPaged:
    """The committed SOAK_llm_paged_r17.json rows pin the paged-serving
    acceptance: an eroded residency win or a ballooned prefill share
    FAILS tier-1 here, and the attribution delta names the regressed
    stage (the SOAK_llm_r15.json discipline, paged edition)."""

    def _load(self):
        import json
        import os

        pd = _load_perf_diff()
        root = os.path.join(os.path.dirname(__file__), "..")
        with open(os.path.join(root, "SOAK_llm_paged_r17.json"),
                  encoding="utf-8") as fh:
            doc = json.load(fh)
        return pd, doc

    def test_committed_rows_self_pass(self):
        pd, doc = self._load()
        rows = doc["rows"]
        verdict = pd.diff([rows, rows], rows, margin_pct=10.0)
        assert verdict["pass"], verdict

    def test_eroded_residency_regresses(self):
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        eroded = copy.deepcopy(rows)
        for row in eroded:
            if row["metric"] == "soak_llm_paged_residency_ratio":
                row["value"] *= 0.4      # paging win collapsed to dense
        verdict = pd.diff([rows, rows], eroded, margin_pct=10.0)
        assert not verdict["pass"]
        assert [r for r in verdict["regressions"]
                if r["metric"] == "soak_llm_paged_residency_ratio"]

    def test_eroded_throughput_names_chunk_stage(self):
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        eroded = copy.deepcopy(rows)
        for row in eroded:
            if row["metric"] == "soak_llm_paged_tokens_per_s":
                row["value"] *= 0.4
                states = row.setdefault("attribution", {}).setdefault(
                    "states", {})
                # e.g. unbounded chunks stalling decode: chunk share
                # balloons while tokens/s falls
                states["llm-prefill-chunk"] = states.get(
                    "llm-prefill-chunk", 0.0) + 30.0
        verdict = pd.diff([rows, rows], eroded, margin_pct=10.0)
        assert not verdict["pass"]
        reg = [r for r in verdict["regressions"]
               if r["metric"] == "soak_llm_paged_tokens_per_s"]
        assert reg, verdict["regressions"]
        blame = reg[0].get("attribution")
        assert blame \
            and blame["regressed_stage"] == "llm-prefill-chunk"

    def test_renamed_row_fails_missing_with_suspect(self):
        """The satellite wired to the artifact: dropping/renaming a
        pinned row key fails by NAME (never a silent skip)."""
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        renamed = copy.deepcopy(rows)
        for row in renamed:
            if row["metric"] == "soak_llm_paged_prefix_hits_warm":
                row["metric"] = "soak_llm_paged_hits"
        verdict = pd.diff([rows, rows], renamed, margin_pct=10.0)
        assert not verdict["pass"]
        missing = [r for r in verdict["regressions"]
                   if r["verdict"] == "MISSING"]
        assert missing[0]["metric"] == "soak_llm_paged_prefix_hits_warm"
        assert "soak_llm_paged_hits" in missing[0]["rename_suspects"]

    def test_committed_artifact_gates_hold(self):
        """The committed artifact must BE a pass with every paged
        acceptance box checked — committing a FAIL (or gutting a
        check) turns tier-1 red here."""
        _, doc = self._load()
        assert doc["pass"] and doc["verdict"] == "PASS"
        checks = doc["llm_paged"]["checks"]
        for name in ("zero_errors", "exact_order",
                     "arena_bytes_equal_dense", "arena_bytes_fixed",
                     "residency_2x_dense", "replay_identical_to_dense",
                     "prefix_hits_warm", "prefill_share_drops_warm",
                     "chunk_share_present", "zero_steady_compiles",
                     "zero_page_leaks", "slabs_settled",
                     "attribution_conserved"):
            assert checks.get(name) is True, (name, checks)
        lp = doc["llm_paged"]
        assert lp["residency_ratio_vs_dense"] >= 2.0
        assert lp["arena_bytes"] == lp["dense_arena_bytes"]
        assert lp["prefix_hits_warm"] > 0
        assert lp["steady_state_compiles"] == 0


# ---------------------------------------------------------------------------
# pinned perf_diff gate on the committed token-observability artifact
# ---------------------------------------------------------------------------

class TestPerfDiffPinnedObs:
    """The committed SOAK_llm_obs_r20.json pins the token-latency
    acceptance (ISSUE 20): inflated TTFT/ITL FAILS tier-1 here (the
    lower-is-better name tokens), the blame-conservation and
    warm-vs-cold evidence must BE in the artifact, and the ttft/itl
    SLO objectives must have passed."""

    def _load(self):
        import json
        import os

        pd = _load_perf_diff()
        root = os.path.join(os.path.dirname(__file__), "..")
        with open(os.path.join(root, "SOAK_llm_obs_r20.json"),
                  encoding="utf-8") as fh:
            doc = json.load(fh)
        return pd, doc

    def test_committed_rows_self_pass(self):
        pd, doc = self._load()
        rows = doc["rows"]
        verdict = pd.diff([rows, rows], rows, margin_pct=10.0)
        assert verdict["pass"], verdict

    def test_inflated_ttft_regresses(self):
        """A candidate whose first tokens got 3x slower must FAIL even
        though the row's raw value got BIGGER — direction is pinned by
        the ``ttft`` name token + ``us`` unit."""
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        inflated = copy.deepcopy(rows)
        for row in inflated:
            if row["metric"] == "soak_llm_paged_ttft_p99_us":
                row["value"] *= 3.0
        verdict = pd.diff([rows, rows], inflated, margin_pct=10.0)
        assert not verdict["pass"]
        assert [r for r in verdict["regressions"]
                if r["metric"] == "soak_llm_paged_ttft_p99_us"]

    def test_inflated_itl_regresses(self):
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        inflated = copy.deepcopy(rows)
        for row in inflated:
            if row["metric"] == "soak_llm_paged_itl_p99_us":
                row["value"] *= 5.0
        verdict = pd.diff([rows, rows], inflated, margin_pct=10.0)
        assert not verdict["pass"]
        assert [r for r in verdict["regressions"]
                if r["metric"] == "soak_llm_paged_itl_p99_us"]

    def test_committed_artifact_gates_hold(self):
        """The artifact must BE a pass with the token-latency boxes
        checked: per-class distributions with sheds excluded, blame
        conservation at 100 %, warm-prefix TTFT decisively below cold
        IN THE SAME RUN, and the ttft/itl SLO verdict green."""
        _, doc = self._load()
        assert doc["pass"] and doc["verdict"] == "PASS"
        checks = doc["llm_paged"]["checks"]
        for name in ("token_slo_pass", "session_blame_conserved",
                     "ttft_warm_below_cold", "zero_errors",
                     "exact_order", "zero_steady_compiles",
                     "attribution_conserved"):
            assert checks.get(name) is True, (name, checks)
        tl = doc["token_latency"]
        # per-class distributions present, sheds in the cause counters
        # only (they can never reach the histograms by construction)
        assert tl["ttft_us"] and tl["itl_us"]
        assert tl["terminal_causes"].get("shed", 0) > 0
        assert tl["sessions_recorded"] > 0
        # blame shares fold the PhaseClock partition: they sum to 100 %
        # of the decode thread's windowed wall time
        assert sum(tl["blame_shares_pct"].values()) \
            == pytest.approx(100.0, abs=0.1)
        cons = tl["session_blame_conserved_pct"]
        assert abs(cons["mean"] - 100.0) < 1.0
        assert cons["n"] > 0
        # the warm-prefix win, measured inside ONE run: warm-phase
        # median TTFT well under the cold phase's
        assert 0.0 < tl["ttft_warm_vs_cold_p50"] <= 0.9
        slo = doc["slo"]
        assert slo["pass"] and slo["verdict"] == "PASS"
        assert {o["name"] for o in slo["objectives"]} \
            >= {"ttft", "itl"}

    def test_renamed_ttft_row_fails_missing(self):
        import copy

        pd, doc = self._load()
        rows = doc["rows"]
        renamed = copy.deepcopy(rows)
        for row in renamed:
            if row["metric"] == "soak_llm_paged_ttft_p99_us":
                row["metric"] = "soak_llm_paged_first_tok_p99_us"
        verdict = pd.diff([rows, rows], renamed, margin_pct=10.0)
        assert not verdict["pass"]
        missing = [r for r in verdict["regressions"]
                   if r["verdict"] == "MISSING"]
        assert missing[0]["metric"] == "soak_llm_paged_ttft_p99_us"
