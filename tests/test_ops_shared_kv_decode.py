"""``ops/shared_kv_decode.py`` in Pallas' interpret mode on the CPU
against ``sambay_lm._diff_attn_rows`` over GATHERED rows, at small
shapes: a lane reads its own slot's K and V rows up to its own position
and nothing else, and the differential read-out over the kernel's result
equals XLA's."""

import functools
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from nnstreamer_tpu.models import sambay_lm as sm  # noqa: E402
from nnstreamer_tpu.ops import shared_kv_decode  # noqa: E402
from nnstreamer_tpu.ops.shared_kv_decode import (  # noqa: E402
    shared_kv_decode_attention)

SLOTS, T, BLOCK = 4, 64, 16
#: 8 query heads in pairs over 4 key heads of 32: rows 128 wide
CFG = sm.SambaYConfig(heads=8, kv_heads=4, head_dim=32, max_seq=T,
                      dtype=jnp.float32)
FULL = CFG.yoco + 1


@functools.cache
def _lyr():
    """The full layer's weights: its lambdas and sub-norm."""
    return sm.init_params(CFG, 7)["layers"][FULL]


def _world(seed, dtype=jnp.float32):
    """Both pools ``(1, SLOTS + 1, T, row)`` and the queries of 8
    lanes."""
    rng = np.random.default_rng(seed)
    kpool, vpool = (jnp.asarray(rng.normal(0, 1, (1, SLOTS + 1, T,
                                                  CFG.kv_row)), dtype)
                    for _ in range(2))
    q = jnp.asarray(rng.normal(0, 1, (8, CFG.heads * CFG.head_dim)),
                    jnp.float32)
    return kpool, vpool, q


def _both(q, kpool, vpool, slots, pos, cfg=CFG, block_t=BLOCK):
    """``(kernel, XLA over the gathered rows)`` for ``len(slots)``
    lanes, each through the differential read-out."""
    slots, pos = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    q = q[:len(slots)]
    wide = shared_kv_decode_attention(
        sm._query_rows(q, cfg), kpool, vpool, slots, pos,
        1.0 / math.sqrt(cfg.head_dim), block_t=block_t, interpret=True)
    assert wide.shape == (len(slots), cfg.heads, cfg.kv_row)
    assert wide.dtype == jnp.float32
    got = sm._pair_out(wide, _lyr(), FULL, cfg)
    valid = jnp.arange(T)[None, :] <= pos[:, None]
    want = sm._diff_attn_rows(q, jnp.nan_to_num(kpool[0][slots]),
                              jnp.nan_to_num(vpool[0][slots]), valid, _lyr(),
                              FULL, cfg)
    return np.asarray(got), np.asarray(want), np.asarray(wide)


@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, 2 * BLOCK + 5, T - 1])
def test_a_lane_at_a_blocks_edge(pos):
    """Position 0 (one row), the last row of a block, the first of the
    next, mid-slot, and the slot's last row — among lanes elsewhere."""
    kpool, vpool, q = _world(pos)
    got, want, wide = _both(q, kpool, vpool, [1, 3, 0], [T - 1, pos, 7])
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 1e-4
    # one row: every head's weighted sum is that V row
    if pos == 0:
        assert np.allclose(wide[1], np.asarray(vpool[0, 3, 0])[None],
                           atol=1e-6)


@pytest.mark.parametrize("slots, pos", [
    # two padding lanes on the scratch slot at position 0
    ([2, SLOTS, 0, SLOTS], [33, 0, 16, 0]),
    # slots out of order, one named twice, the pool passed whole
    ([3, 0, 2, 1, 3, 0], [63, 11, 40, 5, 20, 47]),
])
def test_lanes_read_their_own_slot_of_the_whole_pool(slots, pos):
    kpool, vpool, q = _world(sum(pos))
    got, want, _ = _both(q, kpool, vpool, slots, pos)
    assert np.abs(got - want).max() < 1e-4
    # each lane read its own rows: results differ where the rows do (two
    # lanes at position 0 of one slot both return its first V row)
    assert len({np.round(g, 3).tobytes() for g in got}) \
        == len(set(zip(slots, pos)))


@pytest.mark.parametrize("pos", [0, 5, BLOCK - 1, BLOCK, T - 2])
def test_rows_past_a_lanes_position_never_reach_its_result(pos):
    """A reused slot whose K and V rows beyond ``pos`` hold NaN: a
    skipped block is not read, and a masked row of the last block weighs
    nothing."""
    kpool, vpool, q = _world(pos)
    kpool = kpool.at[:, 2, pos + 1:].set(jnp.nan)
    vpool = vpool.at[:, 2, pos + 1:].set(jnp.nan)
    got, want, wide = _both(q, kpool, vpool, [2, 1], [pos, 20])
    assert np.isfinite(wide).all()
    assert np.abs(got - want).max() < 1e-4


def test_the_rows_of_other_slots_are_not_read():
    kpool, vpool, q = _world(9)
    alone = [np.full(p.shape, np.nan, np.float32) for p in (kpool, vpool)]
    for a, p in zip(alone, (kpool, vpool)):
        a[0, 3] = np.asarray(p[0, 3])
    got, want, wide = _both(q, *map(jnp.asarray, alone), [3], [40])
    assert np.isfinite(wide).all()
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("block_t", [8, 32, T, 4 * T])
def test_any_block_that_divides_the_slot(block_t):
    kpool, vpool, q = _world(block_t)
    got, want, _ = _both(q, kpool, vpool, [3, 0, 1], [T - 1, 11, 32],
                         block_t=block_t)
    assert np.abs(got - want).max() < 1e-4


@pytest.mark.parametrize("most, walked", [(48, 16), (24, 8), (4096, 64)])
def test_the_default_block_is_a_divisor_of_the_slot(most, walked,
                                                    monkeypatch):
    """A slot that ``BLOCK_T`` does not divide is walked in the largest
    blocks that divide both."""
    assert math.gcd(T, most) == walked
    monkeypatch.setattr(shared_kv_decode, "BLOCK_T", most)
    kpool, vpool, q = _world(most)
    got, want, _ = _both(q, kpool, vpool, [0, 2], [T - 1, 17], block_t=None)
    assert np.abs(got - want).max() < 1e-4


def test_bfloat16_rows_as_the_step_casts_them():
    """bf16 rows and queries, float32 scores, softmax and sum, ``p`` in
    bf16 into the second product: the kernel's online form rounds ``p``
    against the running maximum where XLA's rounds it normalised."""
    cfg = sm.SambaYConfig(heads=8, kv_heads=4, head_dim=32, max_seq=T,
                          dtype=jnp.bfloat16)
    kpool, vpool, q = _world(4, jnp.bfloat16)
    got, want, _ = _both(q, kpool, vpool, [0, 1, 2, 3], [63, 31, 32, 1],
                         cfg=cfg)
    assert np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 5e-2


def test_shapes_that_do_not_fit_are_refused():
    kpool, vpool, q = _world(0)
    qm = sm._query_rows(q, CFG)
    lanes = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError, match="block_t"):
        shared_kv_decode_attention(qm, kpool, vpool, lanes, lanes, 1.0,
                                   block_t=24, interpret=True)
    with pytest.raises(ValueError, match="queries"):
        shared_kv_decode_attention(qm[..., :64], kpool, vpool, lanes, lanes,
                                   1.0, interpret=True)
    with pytest.raises(ValueError, match="pools"):
        shared_kv_decode_attention(qm, kpool, vpool[:, :2], lanes, lanes,
                                   1.0, interpret=True)
