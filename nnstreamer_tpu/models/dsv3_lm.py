"""DeepSeek-V3-shaped serving (the ``dsv3_lm`` family of ``tensor_llm``):
multi-head latent attention over a cache of LATENTS, sigmoid-routed
experts of which this chip holds a share and computes only the chosen,
YaRN rotary positions (``model_type: deepseek_v3``;
GigaChat3.1-702B-A36B is this family at ``dim:7168,heads:64``).

Every layer is ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``; the
first ``dense_layers`` have a SwiGLU of width ``mlp``, the others
``experts`` routed experts of width ``expert_mlp`` plus
``shared_experts`` shared ones; final RMSNorm, untied head.  The
equations are in ``benchmarks/reference/dsv3.py``, the plain reference
this file is tested against.

**Attention.**  A position leaves ONE row a layer in the cache:
``kv_lora_rank`` normalised latents and ``qk_rope_head_dim`` rotated key
dims, shared by every head (``init_state``: ``(layers, slots + 1,
max_seq, row_held)``, kind ``latent``; ``row_held`` is their 576 filled
with zeros to 640, a multiple of the chip's tile).  Prefill
takes the PLAIN path: keys and values of every cached position are
expanded from the latents (``w_kvb``).  Decode takes the ABSORBED path:
a head's no-position query is carried into the latent space (``q_lat =
q_nope W_kvb,k^T``), scores and the weighted sum run over the cached
rows as they lie (one ``(heads, row) x (T, row)^T`` product a lane and
one ``(heads, T) x (T, row)`` product back), and the values are
expanded after the sum.  On a TPU that is one kernel a layer
(``ops/latent_decode.py``): it walks each lane's slot in the pool block
by block up to the lane's own position, once, and keeps scores and
softmax in VMEM; elsewhere the lanes' rows are gathered and the same
arithmetic runs as XLA's.  The two must agree; the tests hold them to
it.

**Experts.**  The layer is told which experts it holds
(``experts_held`` of them, the ``expert_rank``-th share of
``experts``): it routes over ALL of them as published (sigmoid scores,
a selection bias, ``n_group`` groups of which the best ``topk_group``
are kept, ``experts_per_tok`` chosen, their weights normalised and
scaled), gathers the token-expert pairs that fall on its own experts
into rows sorted by expert, and computes two grouped matrix products
over those rows alone (:func:`_grouped`): nothing for an expert it does
not hold, nothing for a held expert no token chose, and never every
token by every held expert.  What the absent experts would have added
is left out, here and in the reference alike; no code stands in for the
other chips.  ``route_stats`` (``float32 [expert layers, 4]``, the last
array of the state, kind ``route_stats``) counts on the chip, decode
steps only, with no host read: steps, token-expert pairs routed here,
held experts reached, the largest count at one expert.

Matrices are ``cfg.dtype`` (bfloat16 as published); norms, the
selection bias, the router's scores, the softmax and the residual
stream are float32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.latent_decode import latent_decode_attention
from .sambay_lm import _draw, _key, _mm
from .streamformer_lm import _slot_rows

#: names of the arrays of :func:`init_state`, in order
STATE_KINDS = ("latent", "route_stats")
#: columns of ``route_stats``
ROUTE_STATS = ("steps", "pairs_routed_here", "held_experts_reached",
               "largest_count_at_one_expert")
#: query positions a prefill chunk scores at a time
_QBLOCK = 256
#: whether the step's two kernels run — the megablox grouped products
#: of the routed experts and the decode attention over the pool
#: (``ops/latent_decode.py``) — or XLA's forms of both: None = where the
#: default backend is a TPU (a test that compiles for a described chip
#: sets it)
GROUPED_KERNEL = None
#: rows of a block of the grouped kernel
_GROUPED_ROWS = 64
_FLOATS = ("routed_scaling_factor", "rope_theta", "rope_factor",
           "beta_fast", "beta_slow", "mscale", "mscale_all_dim")


@dataclasses.dataclass(frozen=True)
class DSV3Config:
    vocab: int = 251              # rows of embedding and head held here
    dim: int = 64
    heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 8
    qk_rope_head_dim: int = 8
    v_head_dim: int = 12
    mlp: int = 128                # SwiGLU width of a dense layer
    expert_mlp: int = 32          # SwiGLU width of one expert
    experts: int = 16             # the router's outputs
    experts_held: int = 4         # of which this chip holds these many,
    expert_rank: int = 0          # the expert_rank-th share
    n_group: int = 4
    topk_group: int = 2
    experts_per_tok: int = 4
    shared_experts: int = 1
    dense_layers: int = 1
    layers: int = 3
    routed_scaling_factor: float = 2.5
    rope_theta: float = 10000.0
    rope_factor: float = 4.0      # YaRN: 1 = plain rotary positions
    rope_original_max: int = 16
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    max_seq: int = 64
    chunk: int = 16               # positions one prefill executable takes
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6

    @property
    def row(self) -> int:
        """Values a cached position holds a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_held(self) -> int:
        """The width a row is HELD at: ``row`` filled with zeros to a
        multiple of 128.  Of a pool 576 wide the TPU compiler made ten
        copies of the whole pool a step (``fusion.remat_compressed`` /
        ``_uncompressed`` around every layer's scatter and gather: the
        two want different tilings of a row that is no multiple of a
        tile); of one 640 wide, none (described-chip compile, PR 37)."""
        return -(-self.row // 128) * 128

    @property
    def expert_layers(self) -> int:
        return self.layers - self.dense_layers


def config_from_custom(custom: Dict[str, Any]) -> DSV3Config:
    """The family's ``custom=`` grammar (``arch:dsv3_lm`` selects it)::

        custom=arch:dsv3_lm,vocab:16032,dim:7168,heads:64,
               q_lora_rank:1536,kv_lora_rank:512,qk_nope_head_dim:128,
               qk_rope_head_dim:64,v_head_dim:192,mlp:18432,
               expert_mlp:2048,experts:256,experts_held:16,expert_rank:0,
               n_group:8,topk_group:4,experts_per_tok:8,shared_experts:1,
               dense_layers:1,layers:5,routed_scaling_factor:2.5,
               rope_theta:100000,rope_factor:64,rope_original_max:4096,
               beta_fast:32,beta_slow:1,mscale:1,mscale_all_dim:1,
               max_seq:8192,chunk:512,dtype:bfloat16

    ``max_seq`` must be named and be a multiple of ``chunk`` (a prompt
    is prefilled in chunks that never straddle the cache's end);
    ``chunk`` defaults to the largest power of two up to 512 that
    divides it."""
    known = {f.name for f in dataclasses.fields(DSV3Config)} - {"eps"}
    extra = set(custom) - known - {"arch"}
    if extra:
        raise ValueError(f"dsv3_lm: unknown custom keys {sorted(extra)} "
                         f"(known: {sorted(known)})")
    if "max_seq" not in custom:
        raise ValueError("dsv3_lm: max_seq must be named")
    kw: Dict[str, Any] = {}
    for k, v in custom.items():
        if k in _FLOATS:
            kw[k] = float(v)
        elif k in known and k != "dtype":
            kw[k] = int(v)
    kw.setdefault("chunk", math.gcd(kw["max_seq"], 512))
    cfg = DSV3Config(dtype=jnp.dtype(custom.get("dtype", "bfloat16")),
                     **kw)
    sizes = {k: getattr(cfg, k) for k in known - {"dtype", "expert_rank"}}
    if min(sizes.values()) <= 0:
        raise ValueError("dsv3_lm: every size must be > 0, got "
                         f"{ {k: v for k, v in sizes.items() if v <= 0} }")
    if not 0 <= cfg.dense_layers < cfg.layers:
        raise ValueError("dsv3_lm: dense_layers leading layers of "
                         "`layers`, at least one expert layer after them")
    if cfg.experts % cfg.n_group or cfg.topk_group > cfg.n_group \
            or cfg.experts // cfg.n_group < 2:
        raise ValueError("dsv3_lm: n_group divides experts into groups "
                         "of at least 2, of which topk_group are kept")
    if cfg.experts_per_tok > cfg.topk_group * (cfg.experts // cfg.n_group):
        raise ValueError("dsv3_lm: experts_per_tok exceeds the experts "
                         "of the kept groups")
    if cfg.experts % cfg.experts_held or not \
            0 <= cfg.expert_rank < cfg.experts // cfg.experts_held:
        raise ValueError(
            f"dsv3_lm: experts_held={cfg.experts_held} must divide "
            f"experts={cfg.experts}, and expert_rank={cfg.expert_rank} "
            "name one of the shares")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("dsv3_lm: qk_rope_head_dim pairs by two")
    if cfg.max_seq % cfg.chunk:
        raise ValueError(f"dsv3_lm: max_seq={cfg.max_seq} must be a "
                         f"multiple of chunk={cfg.chunk}")
    return cfg


# -- parameters ----------------------------------------------------------
def init_params(cfg: DSV3Config, seed: int = 0) -> Dict[str, Any]:
    """Seeded random weights, drawn on the device leaf by leaf: matrices
    N(0, 0.02) in ``cfg.dtype`` (the router N(0, 1 / dim), so its
    scores spread alike at any width), no bias anywhere; norms at
    identity and
    the selection bias ``e_score_correction_bias`` N(0, 0.01), both
    float32 (a trained bias balances the load and is not zero: a layer
    that leaves it out must fail the tests; at 0.1 it UNbalanced it, a
    group's best two scores lying within 0.03 of each other's: a 64-lane
    step reached 6-7 of 16 held experts where uniform routing reaches
    13.9, chip run, PR 37).  Only the held experts'
    matrices exist: ``(experts_held, dim, 2 * expert_mlp)`` and
    ``(experts_held, expert_mlp, dim)``."""
    d, h, dt = cfg.dim, cfg.heads, cfg.dtype
    f, fs = cfg.expert_mlp, cfg.shared_experts * cfg.expert_mlp
    keys = iter(jax.random.split(_key(seed), 16 * cfg.layers + 2))

    def mat(*shape):
        return _draw(next(keys), shape, 0.02, dt)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    layers = []
    for i in range(cfg.layers):
        lyr = {"ln1": ones(d), "ln2": ones(d),
               "w_qa": mat(d, cfg.q_lora_rank),
               "q_norm": ones(cfg.q_lora_rank),
               "w_qb": mat(cfg.q_lora_rank, h * (
                   cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
               "w_kva": mat(d, cfg.row), "kv_norm": ones(cfg.kv_lora_rank),
               "w_kvb": mat(cfg.kv_lora_rank, h * (
                   cfg.qk_nope_head_dim + cfg.v_head_dim)),
               "w_o": mat(h * cfg.v_head_dim, d)}
        if i < cfg.dense_layers:
            lyr.update(w_gate_up=mat(d, 2 * cfg.mlp),
                       w_down=mat(cfg.mlp, d))
        else:
            lyr.update(
                # scores of unit spread at any width
                w_router=_draw(next(keys), (d, cfg.experts), d ** -0.5, dt),
                e_bias=_draw(next(keys), (cfg.experts,), 0.01, jnp.float32),
                we_gate_up=mat(cfg.experts_held, d, 2 * f),
                we_down=mat(cfg.experts_held, f, d),
                ws_gate_up=mat(d, 2 * fs), ws_down=mat(fs, d))
        layers.append(lyr)
    return {"embed": mat(cfg.vocab, d), "layers": layers,
            "ln_f": ones(d), "head": mat(cfg.vocab, d)}


def init_state(cfg: DSV3Config, slots: int) -> Tuple[jnp.ndarray, ...]:
    """The pool's arrays (:data:`STATE_KINDS` names them): the latent
    rows, index ``slots`` of the slot dimension being the scratch slot,
    and the routing counters."""
    return (jnp.zeros((cfg.layers, int(slots) + 1, cfg.max_seq,
                       cfg.row_held), cfg.dtype),
            jnp.zeros((cfg.expert_layers, len(ROUTE_STATS)), jnp.float32))


def state_counters(cfg: DSV3Config, state) -> Dict[str, Any]:
    """``route_stats`` read to the host (on request: a report, never the
    loop): one list an expert layer, and over all of them the pairs and
    the held experts reached a counted step."""
    stats = np.asarray(state[-1], np.float64)
    out = {"columns": list(ROUTE_STATS), "by_expert_layer": stats.tolist()}
    steps = stats[:, 0].sum()
    if steps:
        out["pairs_per_step"] = float(stats[:, 1].sum() / steps)
        out["held_experts_reached_per_step"] = float(
            stats[:, 2].sum() / steps)
    return out


# -- shared arithmetic ---------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * w


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: DSV3Config) -> np.ndarray:
    """YaRN's blend of the rotary frequencies, ``qk_rope_head_dim / 2``
    of them: below the correction range the plain frequency, above it
    the frequency divided by ``rope_factor``, a linear ramp between."""
    d = cfg.qk_rope_head_dim
    i = np.arange(d // 2, dtype=np.float64)
    plain = cfg.rope_theta ** (-2.0 * i / d)
    if cfg.rope_factor <= 1:
        return plain.astype(np.float32)

    def correction(rotations: float) -> float:
        return (d * math.log(cfg.rope_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction(cfg.beta_fast)), 0)
    high = min(math.ceil(correction(cfg.beta_slow)), d - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (plain / cfg.rope_factor * ramp
            + plain * (1.0 - ramp)).astype(np.float32)


def softmax_scale(cfg: DSV3Config) -> float:
    m = _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, pos, cfg):
    """Rotate-half over the last axis (``qk_rope_head_dim``) of ``x
    (..., T, [heads,] d)`` at positions ``pos (T,)``, float32."""
    half = cfg.qk_rope_head_dim // 2
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        rope_inv_freq(cfg))[None, :]
    table = _yarn_mscale(cfg.rope_factor, cfg.mscale) \
        / _yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    cos, sin = jnp.cos(angle) * table, jnp.sin(angle) * table
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _queries(y, lyr, pos, cfg):
    """``(q_nope (T, H, nope), q_pe (T, H, rope))`` of normed ``y (T,
    dim)`` at positions ``pos``, float32, the rotary part rotated."""
    t, h, n = y.shape[0], cfg.heads, cfg.qk_nope_head_dim
    c_q = _rms(_mm(y, lyr["w_qa"]), lyr["q_norm"], cfg.eps)
    q = _mm(c_q, lyr["w_qb"]).reshape(t, h, n + cfg.qk_rope_head_dim)
    return q[..., :n], _rope(q[..., n:], pos, cfg)


def _latent_rows(y, lyr, pos, cfg):
    """What positions ``pos`` leave in the cache: ``[RMSNorm(c_kv) |
    RoPE(k_pe) | zeros]``, ``(T, row_held)`` in ``cfg.dtype``."""
    ckv = _mm(y, lyr["w_kva"])
    r = cfg.kv_lora_rank
    return jnp.concatenate(
        [_rms(ckv[:, :r], lyr["kv_norm"], cfg.eps),
         _rope(ckv[:, r:], pos, cfg),
         jnp.zeros((y.shape[0], cfg.row_held - cfg.row), jnp.float32)],
        -1).astype(cfg.dtype)


def _w_kvb(lyr, cfg):
    """``w_kvb`` by head: the key part ``(rank, H, nope)`` and the value
    part ``(rank, H, v)``."""
    w = lyr["w_kvb"].reshape(cfg.kv_lora_rank, cfg.heads,
                             cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def _expand(rows, lyr, cfg):
    """Keys and values EXPANDED from cached rows ``(Tk, row_held)``, by
    head: ``(k (H, Tk, nope + rope), v (H, Tk, v))`` in ``cfg.dtype``,
    the one rotated key of a position laid beside every head's own."""
    dt, r = cfg.dtype, cfg.kv_lora_rank
    wk, wv = _w_kvb(lyr, cfg)
    k_nope = jnp.einsum("tc,chn->htn", rows[:, :r], wk,
                        preferred_element_type=jnp.float32).astype(dt)
    k_pe = jnp.broadcast_to(rows[None, :, r:cfg.row],
                            k_nope.shape[:2] + (cfg.qk_rope_head_dim,))
    v = jnp.einsum("tc,chv->htv", rows[:, :r], wv,
                   preferred_element_type=jnp.float32).astype(dt)
    return jnp.concatenate([k_nope, k_pe.astype(dt)], -1), v


def _attn_plain(q_nope, q_pe, keys, mask, cfg):
    """The plain path: ``Tq`` queries of one sequence over the
    :func:`_expand`-ed keys and values of ``Tk`` cached positions,
    ``mask (Tq, Tk)``; one batched product a head each way.  Returns
    ``(Tq, H * v)`` before ``w_o``."""
    dt = cfg.dtype
    k, v = keys
    q = jnp.concatenate([q_nope, q_pe], -1).astype(dt).transpose(1, 0, 2)
    s = jnp.einsum("hqd,htd->hqt", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(mask[None], s * softmax_scale(cfg), -jnp.inf)
    # the row's maximum behind a barrier: of ``jax.nn.softmax`` here the
    # TPU compiler made a ``reduce-window`` 16 383 wide at every one of
    # the 8 192 positions of a row (the maximum, computed where it is
    # subtracted): 546 ms a chunk of 512 over 8 192 positions, where
    # this form takes 62 (chip runs, PR 37)
    top = jax.lax.optimization_barrier(s.max(axis=-1, keepdims=True))
    e = jnp.exp(s - top)
    p = e / e.sum(axis=-1, keepdims=True)
    o = jnp.einsum("hqt,htv->qhv", p.astype(dt), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[0], -1)


def _absorb(q_nope, q_pe, lyr, cfg):
    """A lane's queries carried into the latent space and laid beside
    their rotary part (and zeros over the rows' filling): ``(B, H,
    row_held)`` in ``cfg.dtype``, one row a head to score against the
    cached rows as they lie."""
    wk, _ = _w_kvb(lyr, cfg)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope.astype(cfg.dtype), wk,
                       preferred_element_type=jnp.float32)
    fill = jnp.zeros(q_pe.shape[:2] + (cfg.row_held - cfg.row,),
                     jnp.float32)
    return jnp.concatenate([q_lat, q_pe, fill], -1).astype(cfg.dtype)


def _kernels() -> bool:
    """Whether the step's kernels run (:data:`GROUPED_KERNEL`)."""
    if GROUPED_KERNEL is None:
        return jax.default_backend() == "tpu"
    return GROUPED_KERNEL


def _attn_absorbed(q_rows, rows, pos, cfg):
    """The absorbed path as XLA's: ONE query a lane over that lane's
    GATHERED rows (``(B, T, row_held)``) up to its position.  ``q_rows
    (B, H, row_held)``, ``pos (B,)``.  Returns the weighted sum of the
    rows themselves, ``(B, H, row_held)`` float32 — what
    :func:`~nnstreamer_tpu.ops.latent_decode.latent_decode_attention`
    returns from the pool where it lies."""
    valid = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    s = jnp.einsum("bhr,btr->bht", q_rows, rows,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(valid[:, None, :],
                                 s * softmax_scale(cfg), -jnp.inf), axis=-1)
    return jnp.einsum("bht,btr->bhr", p.astype(cfg.dtype), rows,
                      preferred_element_type=jnp.float32)


def _values(o_rows, lyr, cfg):
    """The heads' values EXPANDED from the weighted sums of latents
    ``o_rows (B, H, row_held)``: ``(B, H * v)`` before ``w_o``."""
    _, wv = _w_kvb(lyr, cfg)
    o = jnp.einsum("bhc,chv->bhv",
                   o_rows[..., :cfg.kv_lora_rank].astype(cfg.dtype), wv,
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[0], -1)


def _swiglu(y, w_gate_up, w_down):
    g, u = jnp.split(_mm(y, w_gate_up), 2, axis=-1)
    return _mm(u * jax.nn.silu(g), w_down)


# -- the expert layer ----------------------------------------------------
def route(y, lyr, cfg, use_bias=True, group_limit=True, normalise=True,
          scale=True):
    """The router over ALL ``experts`` for normed ``y (N, dim)``:
    ``(chosen (N, k) int32, weights (N, k) float32)``.  Scores in
    float32 at the highest matmul precision (a top-k that flips on
    rounding moves a logit more than bfloat16 does).  The keyword
    switches exist for the tests, which drop each part in turn."""
    n, e, g = y.shape[0], cfg.experts, cfg.n_group
    s = jax.nn.sigmoid(jnp.matmul(
        y, lyr["w_router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick = s + lyr["e_bias"] if use_bias else s
    if group_limit:
        by_group = pick.reshape(n, g, e // g)
        best2 = jax.lax.top_k(by_group, 2)[0].sum(-1)
        kept = jax.lax.top_k(best2, cfg.topk_group)[1]
        keep = jnp.zeros((n, g), bool).at[
            jnp.arange(n)[:, None], kept].set(True)
        pick = jnp.where(jnp.repeat(keep, e // g, axis=1), pick, -jnp.inf)
    chosen = jax.lax.top_k(pick, cfg.experts_per_tok)[1]
    w = jnp.take_along_axis(s, chosen, axis=1)
    if normalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if scale:
        w = w * cfg.routed_scaling_factor
    return chosen.astype(jnp.int32), w


def _tile(n: int, most: int) -> int:
    """The largest power of two up to ``most`` that divides ``n``, or
    ``n`` itself where none of 128 and more does."""
    t = most
    while t >= 128:
        if n % t == 0:
            return t
        t //= 2
    return n


def _grouped(xs, w, sizes):
    """``xs[i] @ w[g(i)]`` for rows sorted by group, ``sizes`` rows a
    group: a grouped matrix product.  Rows past the groups' total belong
    to none and are the caller's to zero.  On a TPU the megablox kernel
    (``GROUPED_KERNEL``), which walks only the blocks of rows a group
    holds and reads no matrix of a group without rows: the two products
    of a 64-lane step that reaches 14 of 16 experts take 1.71 ms against
    4.11 ms through ``jax.lax.ragged_dot`` and a least time of 1.51
    (chip run, PR 37).  Elsewhere ``ragged_dot``."""
    kernel = GROUPED_KERNEL
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if not kernel:
        return jax.lax.ragged_dot(xs.astype(w.dtype), w, sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, (_, k, n) = xs.shape[0], w.shape
    rows = -(-m // _GROUPED_ROWS) * _GROUPED_ROWS
    xs = jnp.pad(xs.astype(w.dtype), ((0, rows - m), (0, 0)))
    return gmm(xs, w, sizes, jnp.float32,
               (_GROUPED_ROWS, _tile(k, 1024), _tile(n, 2048)))[:m]


def routed_experts(y, chosen, weights, lyr, cfg):
    """This chip's part of the routed experts' sum for ``y (N, dim)``:
    the pairs ``(token, chosen expert)`` whose expert is held here,
    sorted by expert, through the two grouped products.  Returns ``(out
    (N, dim) float32, sizes (experts_held,) int32)``."""
    n, k, held = y.shape[0], cfg.experts_per_tok, cfg.experts_held
    local = chosen.reshape(-1) - cfg.expert_rank * held
    here = (local >= 0) & (local < held)
    order = jnp.argsort(jnp.where(here, local, held), stable=True)
    token = (jnp.arange(n * k, dtype=jnp.int32) // k)[order]
    sizes = jnp.zeros((held,), jnp.int32).at[
        jnp.where(here, local, held)].add(1, mode="drop")
    xs = y.astype(cfg.dtype)[token]
    g, u = jnp.split(_grouped(xs, lyr["we_gate_up"], sizes), 2, axis=-1)
    ys = _grouped(u * jax.nn.silu(g), lyr["we_down"], sizes)
    scale = jnp.where(here, weights.reshape(-1), 0.0)[order]
    ys = jnp.where(scale[:, None] > 0, ys * scale[:, None], 0.0)
    return jnp.zeros((n, cfg.dim), jnp.float32).at[token].add(ys), sizes


def _ffn(x, lyr, cfg: DSV3Config):
    """``(x + FFN(RMSNorm(x)), chosen)`` of one layer: ``chosen (N, k)``
    the experts each token chose, ``None`` of a dense layer (one whose
    tree holds no router)."""
    y = _rms(x, lyr["ln2"], cfg.eps)
    if lyr.get("w_router") is None:
        with jax.named_scope("sflm.mlp"):
            return x + _swiglu(y, lyr["w_gate_up"], lyr["w_down"]), None
    with jax.named_scope("sflm.route"):
        chosen, weights = route(y, lyr, cfg)
    with jax.named_scope("sflm.moe"):
        out, _ = routed_experts(y, chosen, weights, lyr, cfg)
    with jax.named_scope("sflm.shared_expert"):
        out = out + _swiglu(y, lyr["ws_gate_up"], lyr["ws_down"])
    return x + out, chosen


def count_routes(stats, chosen, counted, i: int, cfg: DSV3Config):
    """``route_stats`` with one call of expert layer ``i`` counted in:
    ``chosen (N, k)`` what its tokens chose, ``counted (N,)`` the tokens
    that are real lanes."""
    with jax.named_scope("sflm.route"):
        held = cfg.experts_held
        local = chosen - cfg.expert_rank * held
        mine = (local >= 0) & (local < held) & counted[:, None]
        real = jnp.zeros((held,), jnp.float32).at[
            jnp.where(mine, local, held)].add(1.0, mode="drop")
        j = i - cfg.dense_layers
        was = stats[j]
        return stats.at[j].set(jnp.stack([
            was[0] + counted.any(), was[1] + real.sum(),
            was[2] + (real > 0).sum(), jnp.maximum(was[3], real.max())]))


def _logits(x, params, cfg):
    with jax.named_scope("sflm.head"):
        y = _rms(x, params["ln_f"], cfg.eps).astype(cfg.dtype)
        return jnp.einsum("...d,vd->...v", y, params["head"],
                          preferred_element_type=jnp.float32)


# -- the whole sequence, nothing cached ----------------------------------
def forward_logits(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: DSV3Config) -> jnp.ndarray:
    """``tokens (T,) int32`` → float32 logits ``(T, vocab)`` by the
    plain path at every position.  What the serving functions are tested
    against at small sizes; not a serving path."""
    t = tokens.shape[0]
    at = jnp.arange(t)
    x = params["embed"][tokens].astype(jnp.float32)
    for lyr in params["layers"]:
        y = _rms(x, lyr["ln1"], cfg.eps)
        o = _attn_plain(*_queries(y, lyr, at, cfg),
                        _expand(_latent_rows(y, lyr, at, cfg), lyr, cfg),
                        at[None, :] <= at[:, None], cfg)
        x, _ = _ffn(x + _mm(o, lyr["w_o"]), lyr, cfg)
    return _logits(x, params, cfg)


# -- serving: one chunk of a prompt --------------------------------------
def prefill_chunk(params: Dict[str, Any], state: Tuple[jnp.ndarray, ...],
                  tokens: jnp.ndarray, slot: jnp.ndarray,
                  start: jnp.ndarray, true_len: jnp.ndarray,
                  last: jnp.ndarray, cfg: DSV3Config
                  ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Positions ``start .. start + true_len - 1`` of the prompt in
    ``slot``: ``tokens (chunk,)`` zero-padded, ``start`` a multiple of
    the chunk (traced, as ``slot``, ``true_len`` and ``last`` are: ONE
    executable serves every chunk of every prompt).  Every layer writes
    the chunk's latent rows and attends, by the plain path, over the
    slot's rows up to each query's own position (expanded over the
    shortest of a few fixed spans that holds the chunk's end).  Where
    ``last`` is set the head runs at the chunk's last real position,
    else the logits are zeros.  ``route_stats`` counts decode steps only.  Returns ``(logits
    (vocab,), state')``."""
    pool, stats = state
    c = cfg.chunk
    at = start + jnp.arange(c)
    with jax.named_scope("sflm.embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    blk = _QBLOCK if c % _QBLOCK == 0 else c
    spans = [c]
    while spans[-1] < cfg.max_seq:
        spans.append(min(2 * spans[-1], cfg.max_seq))
    for i, lyr in enumerate(params["layers"]):
        y = _rms(x, lyr["ln1"], cfg.eps)
        with jax.named_scope("sflm.mla_q"):
            q_nope, q_pe = _queries(y, lyr, at, cfg)
        with jax.named_scope("sflm.kv_write"):
            pool = jax.lax.dynamic_update_slice(
                pool, _latent_rows(y, lyr, at, cfg)[None, None],
                (i, slot, start, 0))
        with jax.named_scope("sflm.mla_attn"):
            def over(span, lyr=lyr, i=i, pool=pool, q_nope=q_nope,
                     q_pe=q_pe):
                """The chunk's queries over the slot's first ``span``
                positions, a block of queries at a time, the blocks
                unrolled (a loop's operations would lie inside its own
                event in a trace)."""
                with jax.named_scope("sflm.kv_read"):
                    rows = jax.lax.dynamic_slice(
                        pool, (i, slot, 0, 0),
                        (1, 1, span, cfg.row_held))[0, 0]
                keys = _expand(rows, lyr, cfg)
                seen = jnp.arange(span)
                return jnp.concatenate([
                    _attn_plain(q_nope[lo:lo + blk], q_pe[lo:lo + blk],
                                keys, seen[None, :] <= at[lo:lo + blk, None],
                                cfg)
                    for lo in range(0, c, blk)])

            # the keys of a chunk end where the chunk does: the shortest
            # of a few fixed spans that holds them, chosen on the chip
            # (a prompt of 1 024 expands 1 024 positions, not max_seq)
            o = jax.lax.switch(
                sum((start + c > span).astype(jnp.int32)
                    for span in spans[:-1]),
                [partial(over, span) for span in spans])
            x = x + _mm(o.reshape(c, -1), lyr["w_o"])
        x, _ = _ffn(x, lyr, cfg)

    def tail():
        return _logits(jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1),
                       params, cfg)[0]

    logits = jax.lax.cond(
        last, tail, lambda: jnp.zeros((cfg.vocab,), jnp.float32))
    return logits, (pool, stats)


# -- serving: one token a lane -------------------------------------------
def decode_step(params: Dict[str, Any], state: Tuple[jnp.ndarray, ...],
                tokens: jnp.ndarray, pos: jnp.ndarray, slots: jnp.ndarray,
                cfg: DSV3Config
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """One decode step over ``B`` lanes, each at its own position in its
    own slot (padding lanes: the scratch slot, position 0; they are left
    out of ``route_stats``).  Every layer writes its lane's latent row
    and attends by the absorbed path: on a TPU the kernel over the pool
    where it lies, each lane's slot up to its position (``slots`` is the
    gather); elsewhere the lanes' rows gathered (``_slot_rows``) and
    XLA's products over every reserved position.  Returns ``(logits (B,
    vocab) f32, state')``."""
    pool, stats = state
    real = slots < pool.shape[1] - 1
    kernel = _kernels()
    with jax.named_scope("sflm.embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    for i, lyr in enumerate(params["layers"]):
        y = _rms(x, lyr["ln1"], cfg.eps)
        with jax.named_scope("sflm.mla_q"):
            # a lane is a sequence of one position: rotate per lane
            q_nope, q_pe = _queries(y, lyr, pos, cfg)
            q_rows = _absorb(q_nope, q_pe, lyr, cfg)
        with jax.named_scope("sflm.kv_write"):
            pool = pool.at[i, slots, pos].set(
                _latent_rows(y, lyr, pos, cfg))
        with jax.named_scope("sflm.mla_attn"):
            if kernel:
                o_rows = latent_decode_attention(
                    q_rows, pool, i, slots, pos, softmax_scale(cfg))
            else:
                o_rows = _attn_absorbed(
                    q_rows, _slot_rows(pool, i, slots), pos, cfg)
            x = x + _mm(_values(o_rows, lyr, cfg), lyr["w_o"])
        x, chosen = _ffn(x, lyr, cfg)
        if chosen is not None:
            stats = count_routes(stats, chosen, real, i, cfg)
    return _logits(x, params, cfg), (pool, stats)


# -- the seam tensor_llm takes a family through (llm/family.py) ----------
class _Family:
    name = "dsv3_lm"
    #: no block-paged arena, interleaved prefill or prefix reuse yet: a
    #: page of latents is as nameable as a page of keys, but the paged
    #: functions are not written (ROADMAP Queue 2, M2)
    paged = False
    unpaged_why = ("its sessions keep rows of latents and rotated key "
                   "dims, for which no paged step or chunk is written yet "
                   "(a page's content hash would also have to vouch for "
                   "the positions its rows were rotated at)")
    state_kinds = STATE_KINDS
    config_from_custom = staticmethod(config_from_custom)
    init_params = staticmethod(init_params)
    init_state = staticmethod(init_state)
    decode_step = staticmethod(decode_step)
    prefill_chunk = staticmethod(prefill_chunk)
    state_counters = staticmethod(state_counters)

    @staticmethod
    def chunk_len(cfg) -> int:
        return cfg.chunk


FAMILY = _Family()
