"""Ulysses-style all-to-all sequence parallelism: the second long-context
strategy next to :mod:`.ring_attention`.

Net-new, first-class capability (the reference has no sequence
parallelism, SURVEY.md §5).  Where ring attention keeps K/V rotating and
computes blockwise, Ulysses re-shards with two collectives:

1. all-to-all scatters the HEAD dimension and gathers the SEQUENCE
   dimension — each device then holds the FULL sequence for heads/n
   heads;
2. plain exact attention runs locally (no streaming softmax needed);
3. the inverse all-to-all restores sequence shards × all heads.

Trade-off vs ring: Ulysses moves Q, K, V and O once each through
all-to-all (4·T·H·D/n words per device, latency O(1) collectives — rides
ICI well) and needs heads % n == 0, while ring needs n ppermute rounds of
K/V but works for any head count and keeps peak memory at
O(T_local · T_local) scores.  Both are exact; pick per topology via
``StreamFormerConfig.seq_parallel``.
"""

from __future__ import annotations

import jax

import jax.numpy as jnp

from .ring_attention import local_attention


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str = "sp", causal: bool = False,
                      flash: "bool | None" = None) -> jnp.ndarray:
    """Exact attention over sequence shards via head↔sequence all-to-all.

    Args (per-device views inside shard_map):
      q, k, v: (T_local, n_heads, head_dim); n_heads must divide by the
      axis size.
      flash: run the local core as the Pallas streaming-softmax kernel
        (ops/flash_attention.py) — default: on TPU (this is the
        sequence-parallel training path: the kernel's O(T·d)
        forward AND backward residuals are the design, so the
        forward-speed crossover gate does not apply here).

    Returns: (T_local, n_heads, head_dim).
    """
    n = jax.lax.axis_size(axis_name)
    t_local, n_heads, _ = q.shape
    if n_heads % n:
        raise ValueError(
            f"ulysses: heads {n_heads} not divisible by |{axis_name}|={n}"
            " (use ring_attention for uneven head counts)")

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    # scatter heads, gather sequence: (T_local, H, D) -> (T_global, H/n, D)
    qg, kg, vg = (a2a(x, 1, 0) for x in (q, k, v))
    # the full sequence is local now, so plain causal attention is exact
    if flash is None:
        # NOT length-gated: ulysses is the sequence-parallel TRAINING
        # path, where the kernel's O(T*d) forward+backward residuals are
        # the point — naive autodiff saves (H, T, T) probability
        # residuals per layer, which OOMs long-context jobs that fit
        # with the kernel.  The speed crossover (flash_wins) is measured
        # on forward-only timings and does not cover the backward.
        from ..ops.flash_attention import flash_is_default

        flash = flash_is_default()
    if flash:
        from ..ops.flash_attention import flash_attention

        out = flash_attention(qg, kg, vg, causal=causal)
    else:
        out = local_attention(qg, kg, vg, causal=causal)
    # inverse: scatter sequence, gather heads
    return a2a(out, 0, 1)
