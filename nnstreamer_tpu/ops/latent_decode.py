"""Pallas decode attention over a pool of LATENT rows: one query row a
head a lane, each lane over its own slot's rows up to its own position.

A decode step of multi-head latent attention (``models/dsv3_lm.py``)
scores a lane's ``H`` absorbed queries against the rows its slot holds
and sums the SAME rows under the softmax's weights: keys and values are
one array.  This kernel does that for every lane of a step in one call,
reading the pool where it lies:

- **grid** ``(lanes, max_seq // block_t)``: a lane, then its blocks of
  ``block_t`` positions in order (``arbitrary``: the online softmax's
  maximum, sum and accumulator live in VMEM scratch across them);
- **scalar prefetch** ``slots`` and ``pos``: the rows' index map is
  ``(layer, slots[b], min(t, pos[b] // block_t), 0)``.  The slot is the
  gather; a block past the lane's last one names the block already
  resident, so the pipeline issues no copy for it, and ``pl.when``
  keeps its compute out.  A lane reads ``pos // block_t + 1`` blocks,
  once, whatever the pool reserves;
- **per block** ``s = q · rowsᵀ`` ``(H, block_t)`` float32 on the MXU,
  positions past ``pos`` masked in the lane's LAST block alone (the
  blocks before it are whole), ``p`` cast to the rows' dtype for ``p ·
  rows``; scores, softmax and weighted sum never leave VMEM.  The last
  grid step of a lane divides and writes ``(H, row)`` float32.

Rows past a lane's position never reach its result, whatever they hold
(a slot is reused without being cleared): skipped blocks are not read,
and in the last block the rows themselves are zeroed past ``pos`` (a
weight of 0 on a NaN is a NaN).

A kernel for another cache that is read in place (separate K and V, a
window, more than one reading of a layer's rows) would keep this grid,
prefetch and index map and change the block's arithmetic.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a grid step takes (a block of 1 024 x 640 bfloat16 is 1.3
#: MB, 2.6 MB double-buffered).  One value, chosen on the chip: 64 lanes
#: of 64 heads at ~5 000 positions each take 0.794 ms a layer, against
#: 0.897 at 512 (twice the grid steps, ~0.35 us each, read or skipped)
#: and 0.862 at 2 048 (more masked rows in a lane's last block); the
#: transposed order, scores ``(block_t, H)``, 1.03 (chip run, PR 38)
BLOCK_T = 1024
#: lanes of the scratch that holds a row's running maximum and sum
_STAT = 128


def _kernel(slots_ref, pos_ref, q_ref, rows_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale: float, block_t: int):
    del slots_ref                          # the index maps' alone
    t = pl.program_id(1)
    last = pos_ref[pl.program_id(0)]       # the lane's own position
    start = t * block_t

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(masked: bool):
        q, rows = q_ref[...], rows_ref[...]
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (H, block_t)
        if masked:
            at = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at <= last, s, -jnp.inf)
            row_at = start + jax.lax.broadcasted_iota(
                jnp.int32, (block_t, 1), 0)
            rows = jnp.where(row_at <= last, rows, jnp.zeros_like(rows))
        m_was = m_ref[...]                                  # (H, _STAT)
        m_now = jnp.maximum(m_was, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_was - m_now)
        p = jnp.exp(s - m_now[:, :1])
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_now
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(rows.dtype), rows, preferred_element_type=jnp.float32)

    # position ``start`` is attended in both: every lane attends its
    # block 0, so the sum is never empty
    pl.when(start + block_t - 1 <= last)(lambda: attend(False))
    pl.when((start <= last) & (last < start + block_t - 1))(
        lambda: attend(True))

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...][:, :1]


def latent_decode_attention(q_rows, pool, layer: int, slots, pos,
                            scale: float, *, block_t: int | None = None,
                            interpret: bool = False):
    """The softmax-weighted sum of each lane's cached rows: ``q_rows (B,
    H, row)`` in the pool's dtype, ``pool (layers, slots + 1, max_seq,
    row)`` taken WHOLE (a slice of it feeding a custom call is a copy),
    ``layer`` static, ``slots (B,)`` and ``pos (B,)`` int32: lane ``b``
    attends positions ``0 .. pos[b]`` of ``pool[layer, slots[b]]`` with
    ``softmax(scale * q · rowsᵀ)``.  Returns ``(B, H, row)`` float32.
    Two lanes may name one slot (padding lanes share the scratch slot).
    A ``block_t`` that is given divides ``max_seq`` (the smaller of the
    two is taken); where none is, the largest divisor of ``max_seq``
    that divides :data:`BLOCK_T` (a slot of 1 536 is walked in blocks
    of 512)."""
    lanes, heads, row = q_rows.shape
    max_seq = pool.shape[2]
    block_t = (math.gcd(max_seq, BLOCK_T) if block_t is None
               else min(block_t, max_seq))
    if max_seq % block_t or pool.shape[3] != row:
        raise ValueError(f"latent_decode_attention: pool {pool.shape} "
                         f"against queries {q_rows.shape}, block_t "
                         f"{block_t}")
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), block_t=block_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, max_seq // block_t),
            in_specs=[
                pl.BlockSpec((None, heads, row),
                             lambda b, t, slots, pos: (b, 0, 0)),
                pl.BlockSpec(
                    (None, None, block_t, row),
                    lambda b, t, slots, pos: (
                        layer, slots[b],
                        jnp.minimum(t, pos[b] // block_t), 0)),
            ],
            out_specs=pl.BlockSpec((None, heads, row),
                                   lambda b, t, slots, pos: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, _STAT), jnp.float32),
                            pltpu.VMEM((heads, _STAT), jnp.float32),
                            pltpu.VMEM((heads, row), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((lanes, heads, row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_decode_attention",
        interpret=interpret,
    )(slots.astype(jnp.int32), pos.astype(jnp.int32), q_rows, pool)
