"""``ops/latent_decode.py`` in Pallas' interpret mode on the CPU against
``dsv3_lm._attn_absorbed`` over GATHERED rows, at tiny shapes: a lane
reads its own slot's rows up to its own position and nothing else."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from nnstreamer_tpu.models import dsv3_lm as dm  # noqa: E402
from nnstreamer_tpu.ops.latent_decode import (  # noqa: E402
    latent_decode_attention)

LAYERS, SLOTS, T, HEADS, BLOCK = 3, 4, 64, 4, 16
#: rows 48 wide, HELD 128 wide: the filling is zeros
CFG = dm.DSV3Config(kv_lora_rank=32, qk_rope_head_dim=16, heads=HEADS,
                    max_seq=T, dtype=jnp.float32)


def _world(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    pool = rng.normal(0, 1, (LAYERS, SLOTS + 1, T, CFG.row_held))
    pool[..., CFG.row:] = 0
    q = rng.normal(0, 1, (8, HEADS, CFG.row_held))
    q[..., CFG.row:] = 0
    return jnp.asarray(pool, dtype), jnp.asarray(q, dtype)


def _both(q, pool, layer, slots, pos, cfg=CFG, block_t=BLOCK):
    """``(kernel, XLA over the gathered rows)`` for ``len(slots)``
    lanes."""
    slots, pos = jnp.asarray(slots, jnp.int32), jnp.asarray(pos, jnp.int32)
    q = q[:len(slots)]
    got = latent_decode_attention(q, pool, layer, slots, pos,
                                  dm.softmax_scale(cfg), block_t=block_t,
                                  interpret=True)
    rows = jnp.nan_to_num(pool[layer][slots])
    want = dm._attn_absorbed(q, rows, pos, cfg)
    assert got.shape == want.shape == (len(slots), HEADS, cfg.row_held)
    assert got.dtype == jnp.float32
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("pos", [0, BLOCK - 1, BLOCK, 2 * BLOCK + 5, T - 1])
@pytest.mark.parametrize("layer", [0, 2])
def test_a_lane_at_a_blocks_edge(pos, layer):
    """Position 0 (one row), the last row of a block, the first of the
    next, mid-block, and the slot's last row — among lanes elsewhere."""
    pool, q = _world(pos + layer)
    got, want = _both(q, pool, layer, [1, 3, 0], [T - 1, pos, 7])
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 1e-5
    # one row: the weighted sum is that row
    if pos == 0:
        assert np.allclose(got[1], np.asarray(pool[layer, 3, 0])[None],
                           atol=1e-6)


def test_two_lanes_on_the_scratch_slot_and_lanes_in_any_order():
    """Padding lanes share the scratch slot at position 0; real lanes
    name their slots in any order, and a slot twice."""
    pool, q = _world(5)
    slots = [2, SLOTS, 0, SLOTS, 2, 3]
    got, want = _both(q, pool, 1, slots, [33, 0, 16, 0, 9, 63])
    assert np.abs(got - want).max() < 1e-5
    assert not np.allclose(got[0], got[4], atol=1e-3)


@pytest.mark.parametrize("pos", [0, 5, BLOCK - 1, BLOCK, T - 2])
def test_rows_past_a_lanes_position_never_reach_its_result(pos):
    """A slot whose rows beyond ``pos`` hold NaN (a slot is reused
    without being cleared): a skipped block is not read, and a masked
    row of the last block weighs nothing."""
    pool, q = _world(pos)
    pool = pool.at[:, 2, pos + 1:].set(jnp.nan)
    got, want = _both(q, pool, 0, [2, 1], [pos, 20])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5


def test_the_rows_of_other_layers_and_slots_are_not_read():
    pool, q = _world(9)
    alone = np.full(pool.shape, np.nan, np.float32)
    alone[1, 3] = np.asarray(pool[1, 3])
    got, want = _both(q, jnp.asarray(alone), 1, [3], [40])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-5


def test_the_filling_of_a_held_row_stays_zero():
    """``row_held`` wider than the used row: zeros in, zeros out."""
    pool, q = _world(2)
    got, _ = _both(q, pool, 2, [0, 1], [50, 3])
    assert CFG.row < CFG.row_held
    assert np.abs(got[..., CFG.row:]).max() == 0
    assert np.abs(got[..., :CFG.row]).max() > 0.1


def test_bfloat16_rows_as_the_step_casts_them():
    """bf16 rows and queries, float32 scores, softmax and sum, ``p`` in
    bf16 into the second product: the kernel's online form rounds ``p``
    against the running maximum where XLA's rounds it normalised, both
    to 8 bits."""
    cfg = dm.DSV3Config(kv_lora_rank=32, qk_rope_head_dim=16, heads=HEADS,
                        max_seq=T, dtype=jnp.bfloat16)
    pool, q = _world(4, jnp.bfloat16)
    got, want = _both(q, pool, 1, [0, 1, 2, 3], [63, 31, 32, 1], cfg=cfg)
    assert np.abs(got - want).max() < 2e-2
    assert np.abs(want).max() > 0.5


@pytest.mark.parametrize("block_t", [8, 32, T, 4 * T])
def test_any_block_that_divides_the_slot(block_t):
    pool, q = _world(block_t)
    got, want = _both(q, pool, 0, [3, 0, 1], [T - 1, 11, 32],
                      block_t=block_t)
    assert np.abs(got - want).max() < 1e-5


def test_the_default_block_is_a_divisor_of_the_slot(monkeypatch):
    """A slot that ``BLOCK_T`` does not divide (1 536 under 1 024) is
    walked in the largest blocks that divide both: here 64 under 48,
    blocks of 16."""
    from nnstreamer_tpu.ops import latent_decode

    monkeypatch.setattr(latent_decode, "BLOCK_T", 48)
    pool, q = _world(7)
    got, want = _both(q, pool, 1, [0, 2], [T - 1, 17], block_t=None)
    assert np.abs(got - want).max() < 1e-5


def test_shapes_that_do_not_fit_are_refused():
    pool, q = _world(0)
    with pytest.raises(ValueError, match="block_t"):
        latent_decode_attention(q, pool, 0, jnp.zeros(8, jnp.int32),
                                jnp.zeros(8, jnp.int32), 1.0, block_t=24,
                                interpret=True)
    with pytest.raises(ValueError, match="queries"):
        latent_decode_attention(q[..., :64], pool, 0,
                                jnp.zeros(8, jnp.int32),
                                jnp.zeros(8, jnp.int32), 1.0,
                                interpret=True)
