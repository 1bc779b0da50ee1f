"""Plain float32 reference of the ``dsv3`` family (``model_type:
deepseek_v3``: multi-head latent attention, sigmoid-routed experts with
a shared expert, YaRN rotary positions; GigaChat3.1-702B-A36B).

Written from the equations below, independently of
``nnstreamer_tpu/models/dsv3_lm.py``: no cache, NO ABSORPTION (keys and
values are expanded from the latents at every position), no batching, no
kernels, every matrix product in float32 at ``highest`` precision (on a
TPU a float32 product otherwise runs in bfloat16 passes).  Every expert
of the SHARE is looped over and masked: each runs over every position
and a position keeps its result times its routing weight, zero where it
did not choose the expert.  It reads the parameter tree the program
serves from.  Positions go through in blocks (a block of queries against
every key) so that a stream of 8 192 positions of width 7 168 fits
beside the resident engine; the blocks change nothing of the arithmetic
but the order of independent rows.

``model`` is the ``model`` object of a configuration file (the
program's grammar).  Layer ``i`` of ``layers``::

    h = h + Attn(RMSNorm(h; ln1)) ;  h = h + FFN(RMSNorm(h; ln2))
    RMSNorm(x; w) = x / sqrt(mean(x^2) + 1e-6) * w
    SwiGLU(y; w_gate_up, w_down) = (u * silu(g)) @ w_down,  [g, u] = y @ w_gate_up

then ``logits = RMSNorm(h; ln_f) @ head^T`` (untied, no bias).

**Attention** (``H`` heads, ``n`` = ``qk_nope_head_dim``, ``r`` =
``qk_rope_head_dim``, ``v`` = ``v_head_dim``, ``c`` = ``kv_lora_rank``)::

    c_q           = RMSNorm(y @ w_qa; q_norm)
    [q_nope|q_pe] = c_q @ w_qb                       H x (n + r)
    [c_kv|k_pe]   = y @ w_kva                        c + r
    c_kv          = RMSNorm(c_kv; kv_norm)
    k_pe = RoPE(k_pe) (one for all heads),  q_pe = RoPE(q_pe)
    [k_nope|v]    = c_kv @ w_kvb                     H x (n + v)
    score = (q_nope . k_nope + q_pe . k_pe) * s,  causal, softmax
    out   = concat_h(softmax @ v) @ w_o
    s = (n + r)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1

**YaRN** on the ``r`` rotary dims, ``i = 0 .. r/2 - 1``::

    f_i = theta^(-2i / r),  g_i = f_i / factor
    dim(b) = r * ln(L / (2 pi b)) / (2 ln theta),  L = rope_original_max
    low = max(floor(dim(beta_fast)), 0),  high = min(ceil(dim(beta_slow)), r - 1)
    ramp_i = clip((i - low) / (high - low), 0, 1)
    inv_freq_i = g_i * ramp_i + f_i * (1 - ramp_i)
    RoPE(x)_t = [x1 cos - x2 sin | x2 cos + x1 sin],  [x1|x2] = x halved,
    angles t * inv_freq, both tables times
    (0.1 mscale ln(factor) + 1) / (0.1 mscale_all_dim ln(factor) + 1)

**Expert layer** (``E`` = ``experts`` router outputs, ``G`` =
``n_group``, ``k`` = ``experts_per_tok``)::

    s  = sigmoid(y @ w_router),   s' = s + e_bias          (selection only)
    a group's score = the sum of its two largest s'; keep the best topk_group
    chosen = the k largest s' among the kept groups' experts
    w = s[chosen] / (sum(s[chosen]) + 1e-20) * routed_scaling_factor
    FFN(y) = sum_{e chosen, e held here} w_e SwiGLU_e(y) + SwiGLU_shared(y)

The tree holds ``experts_held`` experts, the ``expert_rank``-th share:
held expert ``j`` is expert ``expert_rank * experts_held + j`` of the
router.  The other experts' part of the sum is left out, as the program
leaves it out (the configuration's ``deployment``).  The first
``dense_layers`` layers have ``FFN = SwiGLU`` of width ``mlp``.

Departures from the published model are the configuration file's
(``departures``); none is made here.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: positions a token-wise part takes at a time, and queries an attention
#: scores against every key at a time
TOKEN_BLOCK = 1024
QUERY_BLOCK = 128
#: vocabulary rows the head multiplies at a time
VOCAB_BLOCK = 16384
#: a stream is padded to a multiple of this, so few lengths compile
PAD_TO = 1024
EPS = 1e-6

f32 = lambda a: a.astype(jnp.float32)    # noqa: E731


def _rms(x, w):
    return x / jnp.sqrt((x ** 2).mean(axis=-1, keepdims=True) + EPS) \
        * f32(w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _swiglu(y, w_gate_up, w_down):
    gu = y @ f32(w_gate_up)
    half = gu.shape[-1] // 2
    return (gu[..., half:] * _silu(gu[..., :half])) @ f32(w_down)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model: Dict[str, Any], blend: bool = True) -> np.ndarray:
    """The ``r / 2`` rotary frequencies after YaRN's blend (``blend``
    off: every frequency divided by the factor, for the tests)."""
    r, theta = int(model["qk_rope_head_dim"]), float(model["rope_theta"])
    factor = float(model.get("rope_factor", 1.0))
    i = np.arange(r // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / r)
    if factor <= 1:
        return f
    g = f / factor
    if not blend:
        return g
    length = float(model["rope_original_max"])

    def dim(beta: float) -> float:
        return r * math.log(length / (2 * math.pi * beta)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(float(model.get("beta_fast", 32)))), 0)
    high = min(math.ceil(dim(float(model.get("beta_slow", 1)))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return g * ramp + f * (1.0 - ramp)


def rope_tables(model: Dict[str, Any], t: int, blend: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(cos, sin)``, each ``(t, r / 2)`` float32."""
    factor = float(model.get("rope_factor", 1.0))
    scale = _mscale(factor, float(model.get("mscale", 1.0))) \
        / _mscale(factor, float(model.get("mscale_all_dim", 1.0)))
    angle = np.arange(t, dtype=np.float64)[:, None] \
        * yarn_inv_freq(model, blend)[None, :]
    return (jnp.asarray(np.cos(angle) * scale, jnp.float32),
            jnp.asarray(np.sin(angle) * scale, jnp.float32))


def softmax_scale(model: Dict[str, Any], with_mscale: bool = True) -> float:
    m = _mscale(float(model.get("rope_factor", 1.0)),
                float(model.get("mscale_all_dim", 1.0)))
    s = (int(model["qk_nope_head_dim"])
         + int(model["qk_rope_head_dim"])) ** -0.5
    return s * m * m if with_mscale else s


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.jit
def _embed(table, tokens):
    return f32(table[tokens])


@partial(jax.jit, static_argnames=("heads", "nope"))
def _project(x, lyr, cos, sin, heads: int, nope: int):
    """Queries, expanded keys and values of a block of positions:
    ``(q (T, H, n + r), k (T, H, n + r), v (T, H, v))``."""
    y = _rms(x, lyr["ln1"])
    t = y.shape[0]
    q = (_rms(y @ f32(lyr["w_qa"]), lyr["q_norm"])
         @ f32(lyr["w_qb"])).reshape(t, heads, -1)
    q = jnp.concatenate([q[..., :nope], _rotate(
        q[..., nope:], cos[:, None, :], sin[:, None, :])], -1)
    ckv = y @ f32(lyr["w_kva"])
    rank = lyr["kv_norm"].shape[0]
    kv = (_rms(ckv[:, :rank], lyr["kv_norm"])
          @ f32(lyr["w_kvb"])).reshape(t, heads, -1)
    k_pe = _rotate(ckv[:, rank:], cos, sin)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, k_pe.shape[-1]))], -1)
    return q, k, kv[..., nope:]


@partial(jax.jit, static_argnames=("scale",))
def _attend(x, q, k, v, first, w_o, scale: float):
    """``x + Attn`` for a block of queries at positions ``first ..``
    against every key."""
    tq, tk = q.shape[0], k.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    mask = jnp.arange(tk)[None, :] <= (first + jnp.arange(tq))[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", p, v).reshape(tq, -1)
    return x + o @ f32(w_o)


@jax.jit
def _dense(x, lyr):
    return x + _swiglu(_rms(x, lyr["ln2"]), lyr["w_gate_up"],
                       lyr["w_down"])


@partial(jax.jit, static_argnames=(
    "groups", "keep", "k", "scaling", "use_bias", "group_limit",
    "normalise"))
def _route(y, w_router, e_bias, groups: int, keep: int, k: int,
           scaling: float, use_bias: bool, group_limit: bool,
           normalise: bool):
    t, e = y.shape[0], w_router.shape[1]
    s = 1.0 / (1.0 + jnp.exp(-(y @ f32(w_router))))
    pick = s + f32(e_bias) if use_bias else s
    if group_limit:
        by_group = pick.reshape(t, groups, e // groups)
        score = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)
        worst_kept = jnp.sort(score, axis=-1)[:, groups - keep][:, None]
        pick = jnp.where(jnp.repeat(score >= worst_kept, e // groups,
                                    axis=1), pick, -jnp.inf)
    chosen = pick >= jnp.sort(pick, axis=-1)[:, e - k][:, None]
    w = jnp.where(chosen, s, 0.0)
    if normalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scaling


def route(y, lyr, model: Dict[str, Any], use_bias: bool = True,
          group_limit: bool = True, normalise: bool = True,
          scale: bool = True):
    """Routing weights over ALL router outputs for normed ``y (T,
    dim)``: ``(T, E)`` float32, zero where an expert was not chosen
    (equal scores, which random weights do not produce, would choose
    both).  The switches drop one part each, for the tests."""
    return _route(
        y, lyr["w_router"], lyr["e_bias"], int(model["n_group"]),
        int(model["topk_group"]), int(model["experts_per_tok"]),
        float(model["routed_scaling_factor"]) if scale else 1.0,
        use_bias, group_limit, normalise)


@jax.jit
def _expert(y, w, w_gate_up, w_down):
    return w[:, None] * _swiglu(y, w_gate_up, w_down)


def routed(y, lyr, model: Dict[str, Any], **switches):
    """The held experts' part of the routed sum for normed ``y``: every
    expert of the share over every position, masked by its weight."""
    held, rank = int(model["experts_held"]), int(model["expert_rank"])
    w = route(y, lyr, model, **switches)
    out = jnp.zeros_like(y)
    for j in range(held):
        out = out + _expert(y, w[:, rank * held + j],
                            lyr["we_gate_up"][j], lyr["we_down"][j])
    return out


@jax.jit
def shared(y, lyr):
    """The shared expert's part, for normed ``y``: every chip computes
    it alike."""
    return _swiglu(y, lyr["ws_gate_up"], lyr["ws_down"])


_ln2 = jax.jit(lambda x, w: _rms(x, w))


def _experts(x, lyr, model):
    y = _ln2(x, lyr["ln2"])
    return x + routed(y, lyr, model) + shared(
        y, {"ws_gate_up": lyr["ws_gate_up"], "ws_down": lyr["ws_down"]})


def _blocks(n: int, size: int):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def hidden_states(params: Dict[str, Any], tokens,
                  model: Dict[str, Any]) -> jnp.ndarray:
    """``tokens (T,)`` → the residual stream after the last layer, ``(T,
    dim)`` float32, before the final norm."""
    heads, nope = int(model["heads"]), int(model["qk_nope_head_dim"])
    dense = int(model["dense_layers"])
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    cos, sin = rope_tables(model, t)
    scale = softmax_scale(model)
    x = _embed(params["embed"], tokens)
    for i, lyr in enumerate(params["layers"]):
        attn = {name: lyr[name] for name in (
            "ln1", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kvb")}
        parts = [_project(x[lo:hi], attn, cos[lo:hi], sin[lo:hi], heads,
                          nope) for lo, hi in _blocks(t, TOKEN_BLOCK)]
        q, k, v = (jnp.concatenate(p) for p in zip(*parts))
        x = jnp.concatenate([
            _attend(x[lo:hi], q[lo:hi], k, v, lo, lyr["w_o"], scale)
            for lo, hi in _blocks(t, QUERY_BLOCK)])
        del q, k, v, parts
        if i < dense:
            x = jnp.concatenate([_dense(x[lo:hi], lyr)
                                 for lo, hi in _blocks(t, TOKEN_BLOCK)])
        else:
            x = jnp.concatenate([_experts(x[lo:hi], lyr, model)
                                 for lo, hi in _blocks(t, TOKEN_BLOCK)])
    return x


@jax.jit
def _head(x, ln_f, table):
    return _rms(x, ln_f) @ f32(table).T


@jax.jit
def _judge(x, ln_f, table, served):
    """For each row: the reference logit of its served token, and the
    top logit and its index, the vocabulary taken in blocks."""
    y = _rms(x, ln_f)
    vocab = table.shape[0]
    size = min(VOCAB_BLOCK, vocab)
    got = jnp.sum(y * f32(table[served]), axis=-1)

    def block(carry, lo):
        top, arg = carry
        # the last block is moved back to end at the table's end: rows
        # seen twice change neither a maximum nor where it first is
        lo = jnp.minimum(lo, vocab - size)
        logits = y @ f32(jax.lax.dynamic_slice_in_dim(table, lo, size)).T
        here, where = logits.max(axis=-1), lo + logits.argmax(axis=-1)
        better = here > top
        return (jnp.where(better, here, top),
                jnp.where(better, where, arg)), None

    start = (jnp.full(y.shape[:1], -jnp.inf), jnp.zeros(y.shape[:1],
                                                        jnp.int32))
    (top, arg), _ = jax.lax.scan(
        block, start, jnp.arange(0, vocab, size, dtype=jnp.int32))
    return got, top, arg


def forward_logits(params: Dict[str, Any], tokens,
                   model: Dict[str, Any]) -> np.ndarray:
    """``tokens (T,) int32`` → float32 logits ``(T, vocab)`` (small sizes:
    the whole array is returned)."""
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens, model)
        return np.asarray(_head(x, params["ln_f"], params["head"]))


def judge_rows(params: Dict[str, Any], model: Dict[str, Any], seq,
               first: int, served) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """Feed ``seq`` (padded here to a multiple of :data:`PAD_TO`; causal,
    so the padding cannot reach back) and judge positions ``first ..
    first + len(served) - 1``: ``(logit of the served token, top logit,
    its index)`` per position."""
    seq = np.asarray(seq, np.int32)
    served = np.asarray(served, np.int32)
    buf = np.zeros((-(-len(seq) // PAD_TO) * PAD_TO,), np.int32)
    buf[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, buf, model)[first:first + len(served)]
        out = [_judge(x[lo:hi], params["ln_f"], params["head"],
                      jnp.asarray(served[lo:hi]))
               for lo, hi in _blocks(len(served), TOKEN_BLOCK)]
    got, top, arg = (np.concatenate([np.asarray(o[j]) for o in out])
                     for j in range(3))
    return got, top, arg


def rounded(params: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree with every matrix rounded to ``dtype`` and back: what a
    precision below the configuration's keeps of the weights.  The
    reference on such a tree, judged against the reference on the true
    one, is the second reading a tolerance is set between (PERF.md);
    the activations stay float32, so real arithmetic in ``dtype`` can
    only be worse.  Leaf by leaf, so the two trees are never whole side
    by side."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    for i, leaf in enumerate(leaves):
        if leaf.ndim >= 2:
            leaves[i] = leaf.astype(dtype).astype(leaf.dtype)
    return jax.tree_util.tree_unflatten(tree, leaves)


def served_tokens_near_top(params: Dict[str, Any], model: Dict[str, Any],
                           prompt: np.ndarray, served, slack: float
                           ) -> Dict[str, int]:
    """Teacher-force one served stream through the reference: feed the
    prompt and the served tokens and count the served tokens whose
    reference logit is within ``slack`` of their position's top logit.
    Every family's reference has this function; the caller judges the
    share (bfloat16 serving against float32: with random weights the top
    two logits of a position are often closer than bfloat16 resolves,
    and a routed expert that flips on rounding moves a logit further, so
    agreement is a share of positions and never token equality)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    got, top, arg = judge_rows(params, model, seq, len(prompt) - 1, served)
    if not (np.isfinite(got).all() and np.isfinite(top).all()):
        raise FloatingPointError("reference logits are not finite")
    return {"tokens": int(len(served)),
            "near_top": int((got >= top - slack).sum()),
            "exact": int((arg == served).sum())}
