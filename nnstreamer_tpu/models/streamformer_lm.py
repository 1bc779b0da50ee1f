"""StreamFormer LM serving: full-sequence forward + KV-cache decoding.

The training side lives in parallel/train_step.py (sharded over
dp/sp/tp/ep).  This module is the single-device SERVING path for the same
parameter tree: a full-sequence forward for pipeline use (registry model
``streamformer_lm`` → ``tensor_filter framework=xla``), and an
incremental decode step with a static-shape KV cache for token streaming
— `lax`-friendly (fixed ``max_seq`` cache, position index, one
``dynamic_update_slice`` per layer), so the whole generate loop is ONE
compiled ``lax.scan``.

Consistency contract (tested): decoding token-by-token through the cache
reproduces the full-sequence forward's logits at every position, and the
full forward matches the training forward (shard_map on a 1-device mesh)
— params trained with make_train_step serve unchanged.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.train_step import (StreamFormerConfig, _ln,
                                   init_params)


def _moe_dense(y, lyr, cfg: StreamFormerConfig):
    """Top-1 routed MoE for serving: per-token expert selection with a
    dense einsum over ALL experts masked to the chosen one (E is small;
    no capacity cap at serving — every token runs its expert)."""
    gate = jnp.einsum("...d,de->...e", y.astype(jnp.float32),
                      lyr["gate"].astype(jnp.float32))
    probs = jax.nn.softmax(gate, axis=-1)
    choice = jnp.argmax(probs, axis=-1)                      # (...,)
    onehot = jax.nn.one_hot(choice, cfg.experts, dtype=y.dtype)
    scale = jnp.take_along_axis(probs, choice[..., None],
                                axis=-1)[..., 0].astype(y.dtype)
    h = jax.nn.gelu(jnp.einsum("...d,edf->...ef", y,
                               lyr["we1"].astype(y.dtype)))
    out = jnp.einsum("...ef,efd->...ed", h, lyr["we2"].astype(y.dtype))
    picked = jnp.einsum("...ed,...e->...d", out, onehot)
    return picked * scale[..., None]


def forward_logits(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: StreamFormerConfig,
                   flash: "bool | None" = None) -> jnp.ndarray:
    """Full-sequence forward: tokens (T,) int32 → logits (T, vocab).
    Same math as the training forward (single device, causal).

    ``flash``: run attention as the Pallas streaming-softmax kernel
    (ops/flash_attention.py) — the long-prompt prefill path never
    materializes (T, T) scores.  Default: length-gated on TPU
    (flash_wins): each prefill length takes whichever path the
    measured win table / crossover records say is faster there (the
    r5 capture routes 2k prefills to the kernel at 1.365×)."""
    t = tokens.shape[0]
    if flash is None:
        from ..ops.flash_attention import flash_wins

        flash = flash_wins(t)
    pos = jnp.arange(t)
    x = (params["embed"][tokens] + params["pos"][pos]).astype(cfg.dtype)
    for lyr in params["layers"]:
        y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
        qkv = jnp.einsum("td,dchn->tchn", y, lyr["wqkv"].astype(cfg.dtype))
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        if flash:
            from ..ops.flash_attention import flash_attention

            attn = flash_attention(q, k, v, causal=True)
        else:
            from ..parallel.ring_attention import local_attention

            attn = local_attention(q, k, v, causal=True)
        o = jnp.einsum("qhd,hdn->qn", attn.astype(cfg.dtype),
                       lyr["wo"].astype(cfg.dtype))
        x = x + o
        y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
        m = jnp.einsum("td,df->tf", y, lyr["w1"].astype(cfg.dtype))
        m = jnp.einsum("tf,fd->td", jax.nn.gelu(m),
                       lyr["w2"].astype(cfg.dtype))
        x = x + m + _moe_dense(y, lyr, cfg)
    x = _ln(x.astype(jnp.float32), params["ln_f"])
    return jnp.einsum("td,dv->tv", x, params["head"])


def config_from_custom(custom: Dict[str, Any],
                       default_seq: int = 64) -> StreamFormerConfig:
    """The ``custom=`` sizing grammar, shared by the registry builder and
    the LLM serving tier (``nnstreamer_tpu/llm/``) — the same
    parameterization discipline ``models/mlp.py`` established, so a soak
    server sizes a realistically heavy decoder from the launch line
    alone::

        custom=layers:8,width:512,heads:8,head_dim:64,max_seq:1024

    Keys: ``vocab`` ``dim``/``width`` (aliases) ``heads`` ``head_dim``
    ``mlp`` ``layers`` ``experts`` ``max_seq`` ``dtype`` (``seq`` — the
    registry filter's window length — and ``seed`` stay with their
    callers).  ``max_seq`` defaults to ``max(seq, 64)`` for the
    full-sequence filter's historical sizing; the decode tier sets it
    explicitly (its KV-cache memory is ``slots x layers x max_seq x
    heads x head_dim x 2``, the bound the cache pool enforces)."""
    if "dim" in custom and "width" in custom \
            and str(custom["dim"]) != str(custom["width"]):
        raise ValueError("streamformer_lm: custom dim and width are "
                         "aliases; give one")
    # ``seq`` is the full-sequence FILTER's window length; the decode
    # tier never sets it (its sequence axis is the cache), so the
    # window-fits-cache validation only applies when a caller names it
    seq = int(custom["seq"]) if "seq" in custom else int(default_seq)
    cfg = StreamFormerConfig(
        vocab=int(custom.get("vocab", 256)),
        dim=int(custom.get("dim", custom.get("width", 128))),
        heads=int(custom.get("heads", 8)),
        head_dim=int(custom.get("head_dim", 16)),
        mlp=int(custom.get("mlp", 512)),
        layers=int(custom.get("layers", 2)),
        experts=int(custom.get("experts", 2)),
        max_seq=int(custom.get("max_seq", max(seq, 64))),
        dtype=jnp.dtype(custom.get("dtype", "bfloat16")))
    if min(cfg.vocab, cfg.dim, cfg.heads, cfg.head_dim, cfg.mlp,
           cfg.layers, cfg.experts, cfg.max_seq) < 1:
        raise ValueError(
            "streamformer_lm: vocab/dim/heads/head_dim/mlp/layers/"
            "experts/max_seq must all be >= 1")
    if "seq" in custom and cfg.max_seq < seq:
        raise ValueError(
            f"streamformer_lm: max_seq={cfg.max_seq} < seq={seq}: the "
            "KV cache could not hold one full input window")
    return cfg


def init_cache(cfg: StreamFormerConfig) -> Dict[str, jnp.ndarray]:
    """Static-shape KV cache: (layers, max_seq, heads, head_dim)."""
    L = cfg.layers
    shape = (L, cfg.max_seq, cfg.heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def prefill_kv(params: Dict[str, Any], tokens: jnp.ndarray,
               cfg: StreamFormerConfig, flash: "bool | None" = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-prompt prefill for the KV-cache serving tier: one
    full-sequence forward (the :func:`forward_logits` math, length-gated
    onto the Pallas flash kernel so long prompts never materialize
    (T, T) scores) that ALSO returns every layer's keys/values —
    ``tokens (T,) int32 → (logits (T, vocab) f32, k (L, T, H, Dh),
    v (L, T, H, Dh))`` in ``cfg.dtype``.

    A prompt prefilled here and continued through
    :func:`decode_step` / :func:`decode_step_pooled` produces the same
    logits as scanning :func:`decode_step` over the whole prompt — at
    full-sequence GEMM arithmetic intensity instead of T GEMV steps
    (the consistency contract tests/test_llm.py pins)."""
    t = tokens.shape[0]
    if flash is None:
        from ..ops.flash_attention import flash_wins

        flash = flash_wins(t)
    with jax.named_scope("sflm.embed"):
        pos = jnp.arange(t)
        x = (params["embed"][tokens]
             + params["pos"][pos]).astype(cfg.dtype)
    ks, vs = [], []
    for lyr in params["layers"]:
        with jax.named_scope("sflm.qkv"):
            y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
            qkv = jnp.einsum("td,dchn->tchn", y,
                             lyr["wqkv"].astype(cfg.dtype))
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        ks.append(k)
        vs.append(v)
        with jax.named_scope("sflm.attn"):
            if flash:
                from ..ops.flash_attention import flash_attention

                attn = flash_attention(q, k, v, causal=True)
            else:
                from ..parallel.ring_attention import local_attention

                attn = local_attention(q, k, v, causal=True)
            o = jnp.einsum("qhd,hdn->qn", attn.astype(cfg.dtype),
                           lyr["wo"].astype(cfg.dtype))
            x = x + o
        with jax.named_scope("sflm.mlp"):
            y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
            m = jnp.einsum("td,df->tf", y, lyr["w1"].astype(cfg.dtype))
            m = jnp.einsum("tf,fd->td", jax.nn.gelu(m),
                           lyr["w2"].astype(cfg.dtype))
        with jax.named_scope("sflm.moe"):
            x = x + m + _moe_dense(y, lyr, cfg)
    with jax.named_scope("sflm.head"):
        x = _ln(x.astype(jnp.float32), params["ln_f"])
        logits = jnp.einsum("td,dv->tv", x, params["head"])
    # the cache write is the caller's (the engine's ``_prefill`` installs
    # the run into its slot under ``sflm.kv_write``); nothing is read back
    return logits, jnp.stack(ks), jnp.stack(vs)


def prefill_pooled(params: Dict[str, Any], k_pool: jnp.ndarray,
                   v_pool: jnp.ndarray, tokens: jnp.ndarray,
                   slot: jnp.ndarray, true_len: jnp.ndarray,
                   cfg: StreamFormerConfig, flash: "bool | None" = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """:func:`prefill_kv` into slot ``slot`` of the dense pool: the whole
    padded K/V run is installed as ``(L, 1, T, H * Dh)`` rows at ``(0,
    slot, 0, 0)`` of the layer-major pool.  Rows past ``true_len`` are
    garbage the decode mask never reads (valid = arange <= pos), so one
    static-shape update serves every real length under this quantized
    bucket.  Returns ``(logits of position true_len - 1, k_pool',
    v_pool')``."""
    logits, ks, vs = prefill_kv(params, tokens, cfg, flash=flash)
    with jax.named_scope("sflm.kv_write"):
        run = (cfg.layers, 1, tokens.shape[0], -1)
        k_pool = jax.lax.dynamic_update_slice(
            k_pool, ks.reshape(run), (0, slot, 0, 0))
        v_pool = jax.lax.dynamic_update_slice(
            v_pool, vs.reshape(run), (0, slot, 0, 0))
    last = jax.lax.dynamic_index_in_dim(
        logits, true_len - 1, axis=0, keepdims=False)
    return last, k_pool, v_pool


def _slot_rows(pool: jnp.ndarray, li: int, slots: jnp.ndarray
               ) -> jnp.ndarray:
    """``pool[li][slots]`` of a ``(L, S, max_seq, H * Dh)`` pool, read
    as blocks of 256 positions through a flat ``(L * S * n, 256, H *
    Dh)`` view of the whole pool (a reshape of leading dimensions: no
    copy).  The values are the plain gather's; the TPU compiler makes
    of this form ONE gather per call, which copies the ``B * n`` blocks
    it names straight out of the pool, where of ``pool[li][slots]`` it
    made a copy of the whole layer, a fusion cutting that into four
    quarters of 256 positions, four gathers and a pad joining them:
    45.2 -> 34.3 ms a step at 32 lanes, 16.2 -> 7.3 at 8, 10.3 -> 5.5
    at one (PERF.md section 6, PR 26).  Where ``max_seq`` is no multiple
    of 256 the blocks are the largest power of two that divides it, and
    where a block of a wider row would pass 512 KiB (256 positions of
    1 024 bfloat16 are just that) the blocks are halved until it does
    not: the compiler cuts a larger block's gather in two along the row
    and joins the halves in a pass of their own (described-chip compile
    of ``sambay_lm``'s 1 280-wide rows, PR 29)."""
    layers, s, t, row = pool.shape
    blk = math.gcd(t, 256)
    while blk > 8 and blk * row * pool.dtype.itemsize > 2 ** 19:
        blk //= 2
    n = t // blk
    first = (li * s + slots) * n                                # (B,)
    blocks = pool.reshape(layers * s * n, blk, row)[
        first[:, None] + jnp.arange(n)]                  # (B, n, blk, row)
    return blocks.reshape(slots.shape[0], t, row)


def _attend_rows(q: jnp.ndarray, k_rows: jnp.ndarray, v_rows: jnp.ndarray,
                 valid: jnp.ndarray, cfg: StreamFormerConfig) -> jnp.ndarray:
    """Attention of ONE query a lane over that lane's gathered rows, the
    rows read as they lie: ``q (B, H, Dh)``, ``k_rows``/``v_rows (B, T,
    H * Dh)`` in ``cfg.dtype`` (what :func:`_slot_rows` hands over),
    ``valid (B, T)``; returns ``(B, H, Dh)`` float32 before ``wo``.

    Each query head is laid into its own ``Dh`` columns of a row
    (zeros elsewhere), so the scores are one ``(H, H * Dh) x (T, H *
    Dh)^T`` product a lane and the read-out one ``(H, T) x (T, H *
    Dh)`` product, of which a head keeps its own columns: operands in
    ``cfg.dtype``, accumulation in float32, scale, mask and softmax in
    float32.  The rows are never reshaped to heads, converted or
    transposed: of ``einsum("bhd,bthd->bht")`` over a float32 image the
    TPU compiler made a ``copy f32[32,1024,1024]`` a pool and layer,
    48 a step, and two multiply-and-reduce passes on the vector unit
    (22.2 ms of a 32-lane step's 32.6 busy at ``sflm_gpt2m``'s widths;
    PERF.md section 6, PR 30).  ``sambay_lm._diff_attn_rows`` is the
    same form for differential pairs over grouped key heads."""
    b, h, hd = q.shape
    own = jnp.eye(h, dtype=cfg.dtype)
    qm = jnp.einsum("bhd,hk->bhkd", q.astype(cfg.dtype),
                    own).reshape(b, h, h * hd)
    s = jnp.einsum("bhr,btr->bht", qm, k_rows,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), axis=-1)
    wide = jnp.einsum("bht,btr->bhr", p.astype(cfg.dtype), v_rows,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bhkd,hk->bhd", wide.reshape(b, h, h, hd),
                      own.astype(jnp.float32))


def decode_step_pooled(params: Dict[str, Any], k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, tokens: jnp.ndarray,
                       pos: jnp.ndarray, slots: jnp.ndarray,
                       cfg: StreamFormerConfig
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One continuous-batching decode step over a SLOT-POOLED cache:
    ``B`` resident sequences — each at its own position, each owning one
    cache slot — advance together through one batched invoke.

    - ``k_pool``/``v_pool``: ``(L, S, max_seq, H * Dh)`` — the whole
      session pool's cache (``llm/pool.dense_pool_shape``), ``S`` static
      slots (the llm/ tier's bounded memory: nothing here ever allocates
      per-sequence).  Layer-major and lane-dense: the layer is a
      STATIC leading index, so a step's write and read
      (:func:`_slot_rows`) touch its lanes' rows of one layer and never
      walk the others, and a row is ``H * Dh`` wide, so the compiler
      keeps the pool in its resting layout;
    - ``tokens``/``pos``/``slots``: ``(B,) int32`` — this step's token,
      position and cache-slot id per lane.  Padding lanes (partial
      buckets) point at a caller-reserved scratch slot, so their
      scatter writes can never touch a live session;
    - returns ``(logits (B, vocab) f32, k_pool', v_pool')``.

    Same math as :func:`decode_step` (scatter the new K/V at
    ``(layer, slot, pos)``, attend the single query against the slot's
    prefix, positions beyond ``pos`` masked) — lane *i* of this step
    equals a solo :func:`decode_step` on slot *i*'s cache, which is the
    correctness spine the batched serving tier rests on.  The attention
    (:func:`_attend_rows`) takes the gathered rows as they lie, ``(B,
    max_seq, H * Dh)`` in ``cfg.dtype``: both products on the MXU with
    float32 accumulation, no float32 or transposed image of a lane's
    cache (with ``dtype: float32`` nothing is rounded).  The batched
    shape is the point: B GEMV-shaped single-token steps become one
    GEMM-shaped step (the PR 9 padded-bucket economics, applied to the
    decode loop), and ONE executable per padded B serves every fill."""
    with jax.named_scope("sflm.embed"):
        x = (params["embed"][tokens]
             + params["pos"][pos]).astype(cfg.dtype)
    valid = jnp.arange(cfg.max_seq)[None, :] <= pos[:, None]   # (B, T)
    b, row = tokens.shape[0], cfg.heads * cfg.head_dim
    for li, lyr in enumerate(params["layers"]):
        with jax.named_scope("sflm.qkv"):
            y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
            qkv = jnp.einsum("bd,dchn->bchn", y,
                             lyr["wqkv"].astype(cfg.dtype))
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # (B, H, Dh)
        with jax.named_scope("sflm.kv_write"):
            k_pool = k_pool.at[li, slots, pos].set(k.reshape(b, row))
            v_pool = v_pool.at[li, slots, pos].set(v.reshape(b, row))
        with jax.named_scope("sflm.kv_read"):
            # the barrier ends the attention's say over layouts at the
            # gathered copy, (B, max_seq, H * Dh) a pool: the einsum
            # over a float32 image of the rows, without it, had the
            # blocks converted and re-laid out as they came (PERF.md
            # section 6, PR 26).  _attend_rows asks for no other
            # layout, and the barrier now costs: it holds K's and V's
            # rows (134 MB at 32 lanes) both before the scores start,
            # 13.9 ms a 32-lane step dispatched back to back against
            # 9.7 without it, 4.2 either way at one lane (chip run,
            # PR 30; PERF.md section 7)
            kcur, vcur = jax.lax.optimization_barrier(
                (_slot_rows(k_pool, li, slots),
                 _slot_rows(v_pool, li, slots)))
        with jax.named_scope("sflm.attn"):
            attn = _attend_rows(q, kcur, vcur, valid, cfg)
            o = jnp.einsum("bhd,hdn->bn", attn.astype(cfg.dtype),
                           lyr["wo"].astype(cfg.dtype))
            x = x + o
        with jax.named_scope("sflm.mlp"):
            y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
            m = jnp.einsum("bd,df->bf", y, lyr["w1"].astype(cfg.dtype))
            m = jnp.einsum("bf,fd->bd", jax.nn.gelu(m),
                           lyr["w2"].astype(cfg.dtype))
        with jax.named_scope("sflm.moe"):
            x = x + m + _moe_dense(y, lyr, cfg)
    with jax.named_scope("sflm.head"):
        x = _ln(x.astype(jnp.float32), params["ln_f"])
        logits = jnp.einsum("bd,dv->bv", x, params["head"])
    return logits, k_pool, v_pool


def decode_step_paged(params: Dict[str, Any], k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, tokens: jnp.ndarray,
                      pos: jnp.ndarray, tables: jnp.ndarray,
                      cfg: StreamFormerConfig, page_size: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One continuous-batching decode step over a BLOCK-PAGED cache:
    the vLLM/PagedAttention layout, where a session's cache is a chain
    of fixed-size pages named by a block table instead of one dense
    ``max_seq`` lane.

    - ``k_pages``/``v_pages``: ``(P, L, page_size, H, Dh)`` — ONE fixed
      arena shared by every session; a page belongs to whichever block
      table names it.  The last page is the caller's scratch page;
    - ``tokens``/``pos``: ``(B,) int32`` per lane, as in
      :func:`decode_step_pooled`;
    - ``tables``: ``(B, W) int32`` — each lane's block table, pages in
      sequence order (page ``j`` holds positions ``[j*page_size,
      (j+1)*page_size)``).  ``W`` must satisfy ``W*page_size > max(pos)``
      (the caller pow2-quantizes it so the executable set stays
      bounded); entries past a lane's allocated pages — and every entry
      of a padding lane — point at the scratch page;
    - returns ``(logits (B, vocab) f32, k_pages', v_pages')``.

    Per layer: scatter-append the new K/V into the TAIL page
    (``tables[b, pos//page_size]`` at offset ``pos % page_size``),
    gather the lane's pages back as one ``(W*page_size,)`` run of ``H *
    Dh``-wide rows and attend through the dense step's
    :func:`_attend_rows` under the same causal-prefix mask — lane
    *i* equals a solo :func:`decode_step` on the same history, the
    correctness spine the paged pool rests on.  The arena is donated by
    the engine exactly like the dense pool (the in-place-update
    discipline: without donation the WHOLE arena copies per step)."""
    ps = int(page_size)
    b, w = tables.shape
    span = w * ps
    with jax.named_scope("sflm.embed"):
        x = (params["embed"][tokens]
             + params["pos"][pos]).astype(cfg.dtype)
    valid = jnp.arange(span)[None, :] <= pos[:, None]      # (B, W*ps)
    # tail-page coordinates for this step's scatter-append
    wpage = jnp.take_along_axis(tables, (pos // ps)[:, None],
                                axis=1)[:, 0]              # (B,)
    woff = pos % ps
    for li, lyr in enumerate(params["layers"]):
        with jax.named_scope("sflm.qkv"):
            y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
            qkv = jnp.einsum("bd,dchn->bchn", y,
                             lyr["wqkv"].astype(cfg.dtype))
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # (B, H, Dh)
        li_ix = jnp.full_like(wpage, li)
        with jax.named_scope("sflm.kv_write"):
            k_pages = k_pages.at[wpage, li_ix, woff].set(k)
            v_pages = v_pages.at[wpage, li_ix, woff].set(v)
        with jax.named_scope("sflm.kv_read"):
            # the page gather, viewed as rows (B, W*ps, H * Dh)
            kcur = k_pages[tables, li].reshape(b, span, -1)
            vcur = v_pages[tables, li].reshape(b, span, -1)
        with jax.named_scope("sflm.attn"):
            attn = _attend_rows(q, kcur, vcur, valid, cfg)
            o = jnp.einsum("bhd,hdn->bn", attn.astype(cfg.dtype),
                           lyr["wo"].astype(cfg.dtype))
            x = x + o
        with jax.named_scope("sflm.mlp"):
            y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
            m = jnp.einsum("bd,df->bf", y, lyr["w1"].astype(cfg.dtype))
            m = jnp.einsum("bf,fd->bd", jax.nn.gelu(m),
                           lyr["w2"].astype(cfg.dtype))
        with jax.named_scope("sflm.moe"):
            x = x + m + _moe_dense(y, lyr, cfg)
    with jax.named_scope("sflm.head"):
        x = _ln(x.astype(jnp.float32), params["ln_f"])
        logits = jnp.einsum("bd,dv->bv", x, params["head"])
    return logits, k_pages, v_pages


def prefill_chunk_paged(params: Dict[str, Any], k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, tokens: jnp.ndarray,
                        table: jnp.ndarray, start: jnp.ndarray,
                        true_len: jnp.ndarray, cfg: StreamFormerConfig,
                        page_size: int, scratch: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                   jnp.ndarray]:
    """One bounded prefill CHUNK for a paged session: process ``C``
    prompt tokens starting at absolute position ``start``, writing
    their K/V into the session's pages and attending over everything
    the pages already hold (a cached/shared prefix, earlier chunks) plus
    the chunk itself — causally, so chaining chunks reproduces the
    full-prompt prefill's math.

    - ``tokens (C,) int32``: the chunk, zero-padded past ``true_len``;
    - ``table (W,) int32``: the session's block table, scratch-padded;
      ``W*page_size >= start + C`` (caller-quantized);
    - ``start ()`` / ``true_len ()`` int32: chunk origin and real
      length — traced operands, so ONE ``(C, W)`` executable serves
      every chunk of every prompt at every prefix-hit offset;
    - ``scratch``: the arena's scratch page id (static) — padding
      queries' writes land there;
    - returns ``(last_logits (vocab,), k_pages', v_pages')`` where
      ``last_logits`` is position ``start + true_len - 1``'s row — the
      final chunk's caller argmaxes it into the session's first token.

    This one function is BOTH levers pages buy: chunked prefill (the
    engine interleaves these between decode steps so a long prompt
    cannot stall resident streams) and prefix-cache suffix completion
    (a prefix hit starts the chunk walk at the shared-page boundary
    instead of position 0)."""
    ps = int(page_size)
    c = tokens.shape[0]
    w = table.shape[0]
    span = w * ps
    qpos = start + jnp.arange(c)                           # (C,) absolute
    qvalid = jnp.arange(c) < true_len
    with jax.named_scope("sflm.embed"):
        x = (params["embed"][tokens]
             + params["pos"][qpos]).astype(cfg.dtype)
    # key position t is visible to chunk query i iff t <= start + i
    kvalid = jnp.arange(span)[None, :] <= qpos[:, None]    # (C, W*ps)
    wpage = jnp.where(qvalid, table[qpos // ps], scratch)  # (C,)
    woff = qpos % ps
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    for li, lyr in enumerate(params["layers"]):
        with jax.named_scope("sflm.qkv"):
            y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
            qkv = jnp.einsum("td,dchn->tchn", y,
                             lyr["wqkv"].astype(cfg.dtype))
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]      # (C, H, Dh)
        li_ix = jnp.full_like(wpage, li)
        with jax.named_scope("sflm.kv_write"):
            k_pages = k_pages.at[wpage, li_ix, woff].set(k)
            v_pages = v_pages.at[wpage, li_ix, woff].set(v)
        with jax.named_scope("sflm.kv_read"):
            kcur = k_pages[table, li].reshape(
                span, cfg.heads, cfg.head_dim)
            vcur = v_pages[table, li].reshape(
                span, cfg.heads, cfg.head_dim)
        with jax.named_scope("sflm.attn"):
            s = jnp.einsum("chd,thd->cht", q.astype(jnp.float32),
                           kcur.astype(jnp.float32)) * scale
            s = jnp.where(kvalid[:, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            attn = jnp.einsum("cht,thd->chd", p,
                              vcur.astype(jnp.float32))
            o = jnp.einsum("chd,hdn->cn", attn.astype(cfg.dtype),
                           lyr["wo"].astype(cfg.dtype))
            x = x + o
        with jax.named_scope("sflm.mlp"):
            y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
            m = jnp.einsum("td,df->tf", y, lyr["w1"].astype(cfg.dtype))
            m = jnp.einsum("tf,fd->td", jax.nn.gelu(m),
                           lyr["w2"].astype(cfg.dtype))
        with jax.named_scope("sflm.moe"):
            x = x + m + _moe_dense(y, lyr, cfg)
    with jax.named_scope("sflm.head"):
        x = _ln(x.astype(jnp.float32), params["ln_f"])
        logits = jnp.einsum("td,dv->tv", x, params["head"])
        last = jax.lax.dynamic_index_in_dim(logits, true_len - 1,
                                            axis=0, keepdims=False)
    return last, k_pages, v_pages


def decode_step(params: Dict[str, Any], cache: Dict[str, jnp.ndarray],
                token: jnp.ndarray, cfg: StreamFormerConfig
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One incremental step: token () int32 → (logits (vocab,), cache').

    Attention runs the single query against the cache prefix; positions
    beyond ``cache['pos']`` are masked, so the cache array's static
    ``max_seq`` shape never leaks into the math."""
    pos = cache["pos"]
    x = (params["embed"][token] + params["pos"][pos]).astype(cfg.dtype)
    new_k = cache["k"]
    new_v = cache["v"]
    valid = jnp.arange(cfg.max_seq) <= pos                 # causal prefix
    for li, lyr in enumerate(params["layers"]):
        y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
        qkv = jnp.einsum("d,dchn->chn", y, lyr["wqkv"].astype(cfg.dtype))
        q, k, v = qkv[0], qkv[1], qkv[2]                   # (H, Dh)
        new_k = jax.lax.dynamic_update_slice(
            new_k, k[None, None], (li, pos, 0, 0))
        new_v = jax.lax.dynamic_update_slice(
            new_v, v[None, None], (li, pos, 0, 0))
        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
        s = jnp.einsum("hd,thd->ht", q.astype(jnp.float32),
                       new_k[li].astype(jnp.float32)) * scale
        s = jnp.where(valid[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("ht,thd->hd", p,
                          new_v[li].astype(jnp.float32))
        o = jnp.einsum("hd,hdn->n", attn.astype(cfg.dtype),
                       lyr["wo"].astype(cfg.dtype))
        x = x + o
        y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
        m = jnp.einsum("d,df->f", y, lyr["w1"].astype(cfg.dtype))
        m = jnp.einsum("f,fd->d", jax.nn.gelu(m),
                       lyr["w2"].astype(cfg.dtype))
        x = x + m + _moe_dense(y, lyr, cfg)
    x = _ln(x.astype(jnp.float32), params["ln_f"])
    logits = jnp.einsum("d,dv->v", x, params["head"])
    return logits, {"k": new_k, "v": new_v, "pos": pos + 1}


#: compiled generate programs keyed by (cfg fields, lengths, temperature)
_RUN_CACHE: Dict[tuple, Any] = {}


def _compiled_run(cfg: StreamFormerConfig, n_prompt: int, n_tokens: int,
                  temperature: float):
    key = (tuple(sorted(vars(cfg).items(), key=lambda kv: kv[0],
                        )).__repr__(), n_prompt, n_tokens, temperature)
    fn = _RUN_CACHE.get(key)
    if fn is not None:
        return fn

    @jax.jit
    def run(params, prompt_toks, rng_key):
        cache = init_cache(cfg)

        def prefill(carry, tok):
            cache = carry
            logits, cache = decode_step(params, cache, tok, cfg)
            return cache, logits

        cache, logits_seq = jax.lax.scan(prefill, cache, prompt_toks)
        last_logits = logits_seq[-1]

        def step(carry, _):
            cache, logits, rng_key = carry
            if temperature > 0:
                rng_key, sub = jax.random.split(rng_key)
                tok = jax.random.categorical(sub, logits / temperature)
            else:
                tok = jnp.argmax(logits)
            tok = tok.astype(jnp.int32)
            new_logits, cache = decode_step(params, cache, tok, cfg)
            return (cache, new_logits, rng_key), tok

        _, toks = jax.lax.scan(step, (cache, last_logits, rng_key),
                               None, length=n_tokens)
        return toks

    _RUN_CACHE[key] = run
    return run


def generate(params: Dict[str, Any], cfg: StreamFormerConfig,
             prompt: np.ndarray, n_tokens: int,
             temperature: float = 0.0, seed: int = 0) -> np.ndarray:
    """Greedy (temperature 0) or sampled continuation, fully device-side
    (prefill scan + decode scan); compiled programs are cached per
    (config, lengths, temperature) so repeat calls skip XLA."""
    prompt_arr = jnp.asarray(prompt, jnp.int32)
    total = prompt_arr.shape[0] + n_tokens
    if total > cfg.max_seq:
        raise ValueError(
            f"prompt ({prompt_arr.shape[0]}) + n_tokens ({n_tokens}) = "
            f"{total} exceeds max_seq={cfg.max_seq}: the KV cache would "
            "clamp positions and silently corrupt the continuation")
    run = _compiled_run(cfg, prompt_arr.shape[0], n_tokens, temperature)
    return np.asarray(run(params, prompt_arr, jax.random.PRNGKey(seed)))


def _build_registry_model(custom_props):
    """``framework=xla model=streamformer_lm``: full-sequence next-token
    logits as a pipeline filter — tokens in (T,) int32, logits out
    (T, vocab) float32."""
    from .registry import Model, host_init
    from ..tensor.info import TensorInfo, TensorsInfo
    from ..tensor.types import TensorType

    seed = int(custom_props.get("seed", 0))
    seq = int(custom_props.get("seq", 64))
    # one sizing grammar for every streamformer_lm consumer (registry
    # filter here, the llm/ decode tier, soak servers): layers/width/
    # heads/head_dim/max_seq all launch-line parameterizable
    cfg = config_from_custom(custom_props)
    params = host_init(lambda: init_params(cfg, seed))

    def forward(params, tokens):
        return (forward_logits(params, tokens, cfg).astype(jnp.float32),)

    in_info = TensorsInfo([TensorInfo(TensorType.INT32, (seq,))])
    out_info = TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                       (cfg.vocab, seq))])
    return Model(name="streamformer_lm", forward=forward, params=params,
                 in_info=in_info, out_info=out_info)


# -- the seam tensor_llm takes a family through (llm/family.py) ----------
class _Family:
    """``arch:streamformer_lm`` (the default): keys and values by
    position for every layer, in a dense slot pool or a paged arena."""

    name = "streamformer_lm"
    paged = True
    state_kinds = ("kv", "kv")
    config_from_custom = staticmethod(config_from_custom)

    @staticmethod
    def init_params(cfg, seed):
        from .registry import host_init

        return host_init(lambda: init_params(cfg, int(seed)))

    @staticmethod
    def init_state(cfg, slots):
        from ..llm.pool import dense_pool_shape

        shape = dense_pool_shape(cfg, slots)
        return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)

    @staticmethod
    def chunk_len(cfg) -> int:
        return 0

    @staticmethod
    def decode_step(params, state, tokens, pos, slots, cfg):
        logits, k, v = decode_step_pooled(params, *state, tokens, pos,
                                          slots, cfg)
        return logits, (k, v)

    @staticmethod
    def prefill(params, state, tokens, slot, true_len, cfg, flash):
        last, k, v = prefill_pooled(params, *state, tokens, slot,
                                    true_len, cfg, flash=flash)
        return last, (k, v)

    @staticmethod
    def decode_step_paged(params, state, tokens, pos, tables, cfg,
                          page_size):
        logits, k, v = decode_step_paged(params, *state, tokens, pos,
                                         tables, cfg, page_size)
        return logits, (k, v)

    @staticmethod
    def prefill_chunk_paged(params, state, tokens, table, start, true_len,
                            cfg, page_size, scratch):
        last, k, v = prefill_chunk_paged(params, *state, tokens, table,
                                         start, true_len, cfg, page_size,
                                         scratch)
        return last, (k, v)


FAMILY = _Family()


def _register():
    from .registry import register_model

    register_model("streamformer_lm")(_build_registry_model)


_register()
