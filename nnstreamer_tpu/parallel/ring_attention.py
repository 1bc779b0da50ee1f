"""Ring attention: exact attention over sequence shards on a mesh axis.

Net-new, first-class long-context capability (absent from the reference —
SURVEY.md §5 "Long-context / sequence parallelism: Absent"): each device
holds a sequence block; K/V blocks rotate around the ring via
``jax.lax.ppermute`` while a streaming softmax accumulates exact
attention, and the K/V transfer for step i+1 overlaps with compute for
step i (XLA schedules the ppermute async on ICI).  On TPU each block
runs the Pallas flash kernel and blocks merge via their logsumexp, so
per-device memory is O(T_local·d) — no score matrix in HBM; the jnp
fallback path materializes one (T_local, T_local) block at a time.

Use inside ``jax.shard_map`` with a mesh axis carrying the sequence
dimension (``sp``), e.g. through
:func:`nnstreamer_tpu.parallel.train_step.make_train_step`.
"""

from __future__ import annotations


import jax

import jax.numpy as jnp


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str = "sp", causal: bool = False,
                   flash: "bool | None" = None) -> jnp.ndarray:
    """Exact multi-head attention over a ring of sequence shards.

    Args (per-device views inside shard_map):
      q, k, v: (T_local, n_heads, head_dim)
      axis_name: mesh axis carrying the sequence shards
      causal: apply causal masking using global positions
      flash: run each ring step's block attention as the Pallas
        streaming-softmax kernel (ops/flash_attention.py) and combine
        blocks via their logsumexp — per-device memory drops from
        O(T_local²) score matrices to O(T_local·d).  Default: on TPU
        only (numerics are oracle-tested identical; the CPU interpreter
        is slow).

    Returns: (T_local, n_heads, head_dim) attention output.
    """
    if flash is None:
        from ..ops.flash_attention import flash_is_default

        flash = flash_is_default()
    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_local, n_heads, head_dim = q.shape
    if flash:
        return _ring_flash(q, k, v, axis_name, causal, n, my_idx)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))

    q_pos = my_idx * t_local + jnp.arange(t_local)  # global query positions

    def block(carry, step):
        k_blk, v_blk, acc, row_max, row_sum = carry
        # source block index: the block we hold at `step` originated at
        # device (my_idx - step) mod n
        src = (my_idx - step) % n
        k_pos = src * t_local + jnp.arange(t_local)
        # scores: (heads, Tq, Tk) in f32 for stable softmax accumulation
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        if causal:
            mask = k_pos[None, None, :] > q_pos[None, :, None]
            s = jnp.where(mask, -jnp.inf, s)
        blk_max = jnp.max(s, axis=-1)                      # (h, Tq)
        new_max = jnp.maximum(row_max, blk_max)
        # guard fully-masked rows (all -inf)
        safe_max = jnp.where(jnp.isfinite(new_max), new_max, 0.0)
        p = jnp.exp(s - safe_max[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(row_max),
                                 row_max - safe_max, -jnp.inf))
        corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
        acc = acc * corr[..., None] + jnp.einsum(
            "hqk,khd->hqd", p, v_blk.astype(jnp.float32))
        row_sum = row_sum * corr + jnp.sum(p, axis=-1)
        # rotate K/V to the next device on the ring
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, acc, new_max, row_sum), None

    acc0 = jnp.zeros((n_heads, t_local, head_dim), jnp.float32)
    max0 = jnp.full((n_heads, t_local), -jnp.inf, jnp.float32)
    sum0 = jnp.zeros((n_heads, t_local), jnp.float32)
    (_, _, acc, _, row_sum), _ = jax.lax.scan(
        block, (k, v, acc0, max0, sum0), jnp.arange(n))
    out = acc / jnp.maximum(row_sum[..., None], 1e-20)
    return jnp.transpose(out, (1, 0, 2)).astype(q.dtype)  # (Tq, h, d)


def _ring_flash(q, k, v, axis_name: str, causal: bool, n, my_idx):
    """Ring steps through the Pallas flash kernel: each K/V block runs
    the VMEM-tiled streaming-softmax forward (with its logsumexp), and
    blocks combine through the standard lse merge — no (T_local,
    T_local) score matrix ever materializes in HBM.

    Causality decomposes per block relation (the offsets are traced, so
    they cannot enter the kernel as static args): a block from the
    ring's PAST is fully visible (causal=False), the DIAGONAL block is
    causal at equal offsets, a FUTURE block contributes nothing.
    """
    from ..ops.flash_attention import flash_attention

    t_local, n_heads, head_dim = q.shape

    def _full(q, kb, vb):
        return flash_attention(q, kb, vb, causal=False, return_lse=True)

    def _diag(q, kb, vb):
        return flash_attention(q, kb, vb, causal=True, return_lse=True)

    def _skip(q, kb, vb):
        return (jnp.zeros_like(q),
                jnp.full((n_heads, t_local), -jnp.inf, jnp.float32))

    def block(carry, step):
        k_blk, v_blk, acc, m, den = carry
        src = (my_idx - step) % n
        if causal:
            rel = jnp.where(src == my_idx, 1,
                            jnp.where(src < my_idx, 0, 2)).astype(jnp.int32)
            o_blk, lse = jax.lax.switch(rel, (_full, _diag, _skip),
                                        q, k_blk, v_blk)
        else:
            o_blk, lse = _full(q, k_blk, v_blk)
        new_m = jnp.maximum(m, lse)                        # (h, Tq)
        safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        w = jnp.where(jnp.isfinite(lse), jnp.exp(lse - safe), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        wq = jnp.transpose(w)[:, :, None]                  # (Tq, h, 1)
        corrq = jnp.transpose(corr)[:, :, None]
        acc = acc * corrq + o_blk.astype(jnp.float32) * wq
        den = den * corr + w
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, acc, new_m, den), None

    acc0 = jnp.zeros((t_local, n_heads, head_dim), jnp.float32)
    m0 = jnp.full((n_heads, t_local), -jnp.inf, jnp.float32)
    den0 = jnp.zeros((n_heads, t_local), jnp.float32)
    (_, _, acc, _, den), _ = jax.lax.scan(
        block, (k, v, acc0, m0, den0), jnp.arange(n))
    denq = jnp.maximum(jnp.transpose(den)[:, :, None], 1e-20)
    return (acc / denq).astype(q.dtype)


def local_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False) -> jnp.ndarray:
    """Single-device reference attention (same signature, no ring) — used
    by tests to validate ring_attention numerically."""
    t, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        pos = jnp.arange(t)
        s = jnp.where(pos[None, None, :] > pos[None, :, None], -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
