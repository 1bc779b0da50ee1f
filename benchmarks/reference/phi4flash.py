"""Plain float32 reference of the ``phi4flash`` family
(Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder of
arXiv:2507.06607 with differential attention).

Written from the equations below, independently of
``nnstreamer_tpu/models/sambay_lm.py``: no cache, no batching, no
kernels, every matrix product in float32 at ``highest`` precision (on a
TPU a float32 product otherwise runs in bfloat16 passes).  It reads the
parameter tree the program serves from.  One jitted function per kind of
layer, called from Python; positions go through in blocks (a block of
queries against every key; the scan's state carried from block to block)
so that a 14 k-position stream fits beside the resident engine.  The
blocks change nothing of the arithmetic but the order of independent
rows.

``model`` is the ``model`` object of a configuration file.  With ``h =
layers / 2``, layer ``i`` is: Mamba (``i <= h``, even); differential
attention over the last ``window`` positions (``i < h``, odd); full
causal differential attention (``i = h + 1``), whose keys and values
the later layers share; a gated memory unit (``i > h + 1``, even);
differential cross-attention (``i > h + 1``, odd).  Every layer::

    x = x + Mixer(LN(x; ln1)) ;  x = x + MLP(LN(x; ln2))
    LN(x; w, b)  = (x - mean) / sqrt(var + 1e-5) * w + b
    MLP(y)       = (u * silu(g)) @ w_down,   [g, u] = y @ w_gate_up

then ``logits = LN(x; ln_f) @ embed^T`` (tied, no bias, no scale).  No
positional encoding of any kind.

**Mamba** (``d_inner = expand * dim``, ``N = d_state``, ``R = dt_rank``)::

    [x, z]   = y @ w_in                                (dim -> 2 d_inner)
    c_t      = silu(sum_j conv_w[j] * x_{t - d_conv + 1 + j} + conv_b)
    [d, B, C] = c @ w_x                                (d_inner -> R + 2N)
    D_t      = softplus(d_t @ w_dt + b_dt)             (R -> d_inner)
    A        = -exp(A_log)                             (d_inner, N)
    h_t      = exp(D_t[:, None] * A) * h_{t-1} + (D_t * c_t)[:, None] * B_t[None, :]
    m_t      = h_t @ C_t + D * c_t
    out_t    = (m_t * silu(z_t)) @ w_out

(``x`` before position 0 is zero, ``h_{-1} = 0``).  Layer ``h`` is this
same layer and also passes ``m_t`` on.  **Gated memory unit**: ``out_t =
(m_t * silu(y_t @ w_1)) @ w_2`` with layer ``h``'s ``m_t`` at the SAME
position.  **Differential attention**: ``[q, k, v] = y @ w_qkv + b_qkv``;
the heads pair by two, ``q -> (P, 2, hd)``, ``k, v -> (G, 2, hd)``, and
query pair ``p`` uses key/value pair ``p // (P / G)``; with ``V = [v1 ||
v2]``::

    a1 = softmax(q1 k1^T / sqrt(hd), mask) V,  a2 = softmax(q2 k2^T / sqrt(hd), mask) V
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,   lam0 = 0.8 - 0.6 exp(-0.3 i)
    o   = RMSNorm(a1 - lam a2; subln, eps 1e-5) * (1 - lam0)
    out = concat_p(o) @ w_o + b_o

``mask`` is causal; in a window layer position ``t`` sees ``t - window +
1 .. t``.  **Cross-attention**: ``q = y @ w_q + b_q`` only; ``k, v`` are
layer ``h + 1``'s, unchanged; then the same with this layer's own
``lam``, ``subln``, ``w_o``.

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
QUERY_BLOCK = 256
#: vocabulary rows the head multiplies at a time
VOCAB_BLOCK = 16384
#: a stream is padded to a multiple of this, so few lengths compile
PAD_TO = 2048

f32 = lambda a: a.astype(jnp.float32)    # noqa: E731


def kind_of(i: int, layers: int) -> str:
    h = layers // 2
    if i <= h:
        return "mamba" if i % 2 == 0 else "window"
    if i == h + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def _norm(x, p):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * f32(p["w"]) + f32(p["b"])


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


@jax.jit
def _embed(table, tokens):
    return f32(table[tokens])


@jax.jit
def _mlp(x, lyr):
    gu = _norm(x, lyr["ln2"]) @ f32(lyr["w_gate_up"])
    half = gu.shape[-1] // 2
    return x + (gu[:, half:] * _silu(gu[:, :half])) @ f32(lyr["w_down"])


@jax.jit
def _mamba(x, lyr, before, h):
    """One block of positions; ``before`` the ``d_conv - 1`` inputs that
    precede it, ``h`` the state it starts from.  Returns ``(x', m,
    before', h')``."""
    t = x.shape[0]
    taps, n = lyr["conv_w"].shape[0], lyr["A_log"].shape[1]
    r = lyr["w_dt"].shape[0]
    xz = _norm(x, lyr["ln1"]) @ f32(lyr["w_in"])
    d_inner = xz.shape[-1] // 2
    xin, z = xz[:, :d_inner], xz[:, d_inner:]
    seq = jnp.concatenate([before, xin])
    c = _silu(sum(seq[j:j + t] * f32(lyr["conv_w"])[j]
                  for j in range(taps)) + f32(lyr["conv_b"]))
    dbc = c @ f32(lyr["w_x"])
    delta = jax.nn.softplus(dbc[:, :r] @ f32(lyr["w_dt"])
                            + f32(lyr["b_dt"]))
    a = -jnp.exp(f32(lyr["A_log"]))

    def step(h, inp):
        d_t, c_t, b_t, c_out = inp
        h = jnp.exp(d_t[:, None] * a) * h \
            + (d_t * c_t)[:, None] * b_t[None, :]
        return h, h @ c_out

    h, m = jax.lax.scan(step, h, (delta, c, dbc[:, r:r + n],
                                  dbc[:, r + n:]))
    m = m + f32(lyr["D"]) * c
    out = (m * _silu(z)) @ f32(lyr["w_out"])
    return x + out, m, seq[t:], h


@jax.jit
def _gmu(x, m, lyr):
    gate = _silu(_norm(x, lyr["ln1"]) @ f32(lyr["w_1"]))
    return x + (m * gate) @ f32(lyr["w_2"])


@partial(jax.jit, static_argnames=("heads", "kv_heads"))
def _qkv(x, lyr, heads: int, kv_heads: int):
    qkv = _norm(x, lyr["ln1"]) @ f32(lyr["w_qkv"]) + f32(lyr["b_qkv"])
    hd = qkv.shape[-1] // (heads + 2 * kv_heads)
    return (qkv[:, :heads * hd], qkv[:, heads * hd:(heads + kv_heads) * hd],
            qkv[:, (heads + kv_heads) * hd:])


@jax.jit
def _q_only(x, lyr):
    return _norm(x, lyr["ln1"]) @ f32(lyr["w_q"]) + f32(lyr["b_q"])


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window"))
def _attend(x, q, k, v, first, lyr, lam0, heads: int, kv_heads: int,
            window: int):
    """Differential attention of a block of queries (positions ``first
    ..``) over every key; ``window`` 0 = causal over the whole context;
    ``lam0`` the layer's ``lambda_init`` (an operand, so one executable
    serves every layer of a kind).  Returns ``x + out``."""
    tq, tk = q.shape[0], k.shape[0]
    pairs, groups = heads // 2, kv_heads // 2
    hd = q.shape[-1] // heads
    q = q.reshape(tq, pairs, 2, hd)
    k = jnp.repeat(k.reshape(tk, groups, 2, hd), pairs // groups, axis=1)
    vv = jnp.repeat(v.reshape(tk, groups, 2 * hd), pairs // groups, axis=1)
    qpos = first + jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    mask = kpos <= qpos
    if window:
        mask = mask & (kpos > qpos - window)

    def softmax_v(half):
        s = jnp.einsum("qpd,kpd->pqk", q[:, :, half], k[:, :, half]) \
            / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("pqk,kpe->qpe", p, vv)

    lam = (jnp.exp(jnp.sum(f32(lyr["lq1"]) * f32(lyr["lk1"])))
           - jnp.exp(jnp.sum(f32(lyr["lq2"]) * f32(lyr["lk2"]))) + lam0)
    d = softmax_v(0) - lam * softmax_v(1)
    o = d / jnp.sqrt((d ** 2).mean(axis=-1, keepdims=True) + 1e-5) \
        * f32(lyr["subln"]) * (1.0 - lam0)
    return x + o.reshape(tq, pairs * 2 * hd) @ f32(lyr["w_o"]) \
        + f32(lyr["b_o"])


def _blocks(n: int, size: int):
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def hidden_states(params: Dict[str, Any], tokens,
                  model: Dict[str, Any]) -> jnp.ndarray:
    """``tokens (T,)`` → the residual stream after the last layer, ``(T,
    dim)`` float32, before the final norm."""
    layers, window = model["layers"], model["window"]
    heads, kv_heads = model["heads"], model["kv_heads"]
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    x = _embed(params["embed"], tokens)
    m = k_full = v_full = None
    for i, lyr in enumerate(params["layers"]):
        kind = kind_of(i, layers)
        if kind == "mamba":
            d_inner, n = lyr["A_log"].shape
            before = jnp.zeros((lyr["conv_w"].shape[0] - 1, d_inner),
                               jnp.float32)
            h = jnp.zeros((d_inner, n), jnp.float32)
            xs, ms = [], []
            for lo, hi in _blocks(t, TOKEN_BLOCK):
                xb, mb, before, h = _mamba(x[lo:hi], lyr, before, h)
                xs.append(xb)
                ms.append(mb)
            x = jnp.concatenate(xs)
            if i == layers // 2:
                m = jnp.concatenate(ms)
        elif kind == "gmu":
            x = jnp.concatenate([_gmu(x[lo:hi], m[lo:hi], lyr)
                                 for lo, hi in _blocks(t, TOKEN_BLOCK)])
        else:
            if kind == "cross":
                q = jnp.concatenate([_q_only(x[lo:hi], lyr)
                                     for lo, hi in _blocks(t, TOKEN_BLOCK)])
                k, v = k_full, v_full
            else:
                parts = [_qkv(x[lo:hi], lyr, heads, kv_heads)
                         for lo, hi in _blocks(t, TOKEN_BLOCK)]
                q, k, v = (jnp.concatenate(p) for p in zip(*parts))
                if kind == "full":
                    k_full, v_full = k, v
            # the leaves every kind of attention layer has: one
            # executable then serves full and cross layers alike
            own = {name: lyr[name] for name in (
                "lq1", "lk1", "lq2", "lk2", "subln", "w_o", "b_o")}
            x = jnp.concatenate([
                _attend(x[lo:hi], q[lo:hi], k, v, lo, own,
                        0.8 - 0.6 * math.exp(-0.3 * i), heads, kv_heads,
                        window if kind == "window" else 0)
                for lo, hi in _blocks(t, QUERY_BLOCK)])
        x = jnp.concatenate([_mlp(x[lo:hi], lyr)
                             for lo, hi in _blocks(t, TOKEN_BLOCK)])
    return x


@jax.jit
def _head(x, ln_f, table):
    return _norm(x, ln_f) @ f32(table).T


@jax.jit
def _judge(x, ln_f, table, served):
    """For each row: the reference logit of its served token, and the
    top logit and its index, the vocabulary taken in blocks (a whole
    ``(rows, vocab)`` float32 array is 1.6 GB at 2 000 rows)."""
    y = _norm(x, ln_f)
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
        return np.asarray(_head(x, params["ln_f"], params["embed"]))


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
        out = [_judge(x[lo:hi], params["ln_f"], params["embed"],
                      jnp.asarray(served[lo:hi]))
               for lo, hi in _blocks(len(served), QUERY_BLOCK)]
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
    two logits of a position are often closer than bfloat16 resolves, so
    agreement is a share of positions and never token equality)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    got, top, arg = judge_rows(params, model, seq, len(prompt) - 1, served)
    if not (np.isfinite(got).all() and np.isfinite(top).all()):
        raise FloatingPointError("reference logits are not finite")
    return {"tokens": int(len(served)),
            "near_top": int((got >= top - slack).sum()),
            "exact": int((arg == served).sum())}
