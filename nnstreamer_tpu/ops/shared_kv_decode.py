"""Pallas decode attention over pools of KEY and VALUE rows held apart:
one query row a head a lane, each lane over its own slot's rows up to
its own position.

``models/sambay_lm.py`` caches ONE layer's keys and values (the full
attention layer's), read by that layer and by every cross-attention
layer after it: eight readings a decode step.  This kernel makes one
reading for every lane of a step in one call, from the two pools where
they lie.  Grid, scalar prefetch and index map are those of
``ops/latent_decode.py``:

- **grid** ``(lanes, max_seq // block_t)``: a lane, then its blocks of
  ``block_t`` positions in order (``arbitrary``: the online softmax's
  maximum, sum and accumulator live in VMEM scratch across them);
- **scalar prefetch** ``slots`` and ``pos``: both pools' index map is
  ``(0, slots[b], min(t, pos[b] // block_t), 0)``.  The slot is the
  gather; a block past the lane's last one names the block already
  resident, so the pipeline issues no copy for it, and ``pl.when`` keeps
  its compute out.  A lane reads ``pos // block_t + 1`` blocks of K and
  of V, once, whatever the pools reserve;
- **per block** ``s = q · Kᵀ`` ``(H, block_t)`` float32 on the MXU,
  positions past ``pos`` masked in the lane's LAST block alone, ``p``
  cast to V's dtype for ``p · V`` ``(H, row)``; scores, softmax and
  weighted sum never leave VMEM.  The last grid step of a lane divides
  and writes ``(H, row)`` float32.

Rows past a lane's position never reach its result, whatever they hold
(a slot is reused without being cleared): skipped blocks are not read;
in the last block a masked key's score is replaced by ``-inf`` whatever
it was, and the V rows there are zeroed (a weight of 0 on a NaN is a
NaN).

The query is the caller's: a row the width of a K row a head, so a head
that reads only some columns of the row (``sambay_lm``'s one-hot
expansion of each query head into its key head's columns) and whatever
it keeps of the result are decided outside.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a grid step takes (a block of 512 x 1 280 bfloat16 is 1.3 MB
#: of K and as much of V, 5.2 MB double-buffered).  One value, chosen on
#: the chip: the eight readings of 32 lanes at 6 795 attended positions
#: each take 13.53 ms at 512, against 13.93 at 1 024 (14.11 with the
#: scoped VMEM limit raised) and 15.25 at 2 048, which needs it raised
#: (more masked rows in a lane's last block); 11.28 / 11.37 / 12.52 at
#: 5 431; XLA's gathered form 37.3 at both (TPU v5e)
BLOCK_T = 512
#: lanes of the scratch that holds a row's running maximum and sum
_STAT = 128


def _kernel(slots_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
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
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (H, block_t)
        if masked:
            at = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(at <= last, s, -jnp.inf)
            row_at = start + jax.lax.broadcasted_iota(
                jnp.int32, (block_t, 1), 0)
            v = jnp.where(row_at <= last, v, jnp.zeros_like(v))
        m_was = m_ref[...]                                  # (H, _STAT)
        m_now = jnp.maximum(m_was, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_was - m_now)
        p = jnp.exp(s - m_now[:, :1])
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_now
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # position ``start`` is attended in both: every lane attends its
    # block 0, so the sum is never empty
    pl.when(start + block_t - 1 <= last)(lambda: attend(False))
    pl.when((start <= last) & (last < start + block_t - 1))(
        lambda: attend(True))

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] / l_ref[...][:, :1]


def shared_kv_decode_attention(q_rows, kpool, vpool, slots, pos,
                               scale: float, *, block_t: int | None = None,
                               interpret: bool = False):
    """The softmax-weighted sum of each lane's cached V rows under its
    scores against the K rows: ``q_rows (B, H, row)`` in the pools'
    dtype, ``kpool`` and ``vpool`` ``(1, slots + 1, max_seq, row)``
    taken WHOLE (a slice of a pool feeding a custom call is a copy),
    ``slots (B,)`` and ``pos (B,)`` int32: lane ``b`` attends positions
    ``0 .. pos[b]`` of slot ``slots[b]`` with ``softmax(scale * q ·
    Kᵀ) · V``.  Returns ``(B, H, row)`` float32.  Two lanes may name one
    slot (padding lanes share the scratch slot).  A ``block_t`` that is
    given divides ``max_seq`` (the smaller of the two is taken); where
    none is, the largest divisor of ``max_seq`` that divides
    :data:`BLOCK_T`."""
    lanes, heads, row = q_rows.shape
    max_seq = kpool.shape[2]
    block_t = (math.gcd(max_seq, BLOCK_T) if block_t is None
               else min(block_t, max_seq))
    if (max_seq % block_t or kpool.shape != vpool.shape
            or kpool.shape[0] != 1 or kpool.shape[3] != row):
        raise ValueError(f"shared_kv_decode_attention: pools {kpool.shape} "
                         f"and {vpool.shape} against queries "
                         f"{q_rows.shape}, block_t {block_t}")
    rows = pl.BlockSpec((None, None, block_t, row),
                        lambda b, t, slots, pos: (
                            0, slots[b], jnp.minimum(t, pos[b] // block_t),
                            0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=float(scale), block_t=block_t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(lanes, max_seq // block_t),
            in_specs=[
                pl.BlockSpec((None, heads, row),
                             lambda b, t, slots, pos: (b, 0, 0)),
                rows, rows,
            ],
            out_specs=pl.BlockSpec((None, heads, row),
                                   lambda b, t, slots, pos: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, _STAT), jnp.float32),
                            pltpu.VMEM((heads, _STAT), jnp.float32),
                            pltpu.VMEM((heads, row), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((lanes, heads, row), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="shared_kv_decode_attention",
        interpret=interpret,
    )(slots.astype(jnp.int32), pos.astype(jnp.int32), q_rows, kpool, vpool)
