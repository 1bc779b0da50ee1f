"""Plain float32 reference of the ``streamformer_lm`` architecture.

Written from the architecture's description, independently of
``nnstreamer_tpu/models/streamformer_lm.py``: no cache, no batching, no
kernels, every matrix product at ``highest`` precision (on a TPU a
float32 product otherwise runs in bfloat16 passes).  It reads the same
parameter tree the program serves from.

Per layer, on a sequence ``x`` of shape ``(T, dim)``::

    y  = LN(x) * ln1                       LayerNorm, eps 1e-5, scale only
    q, k, v = y @ wqkv                     (dim, 3, heads, head_dim), no bias
    a  = softmax(q k^T / sqrt(head_dim), causal) v
    x  = x + a @ wo
    y  = LN(x) * ln2
    m  = gelu(y @ w1) @ w2                 dense MLP (tanh GELU)
    e* = argmax softmax(y @ gate)          top-1 routed expert
    r  = softmax(y @ gate)[e*] * (gelu(y @ we1[e*]) @ we2[e*])
    x  = x + m + r

with learned absolute positions added to the token embedding, a final
scale-only LayerNorm and an untied head.  One jitted function per part,
called layer by layer from Python, so the reference compiles one layer
once whatever the depth.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, scale):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@jax.jit
def _embed(embed, pos, tokens):
    return (embed[tokens] + pos[:tokens.shape[0]]).astype(jnp.float32)


@partial(jax.jit, static_argnames=("head_dim",))
def _layer(x, lyr, head_dim: int):
    f32 = lambda a: a.astype(jnp.float32)    # noqa: E731
    t = x.shape[0]
    y = _layer_norm(x, f32(lyr["ln1"]))
    qkv = jnp.einsum("td,dchn->tchn", y, f32(lyr["wqkv"]))
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = jnp.einsum("qhn,khn->hqk", q, k) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khn->qhn", jax.nn.softmax(scores, axis=-1), v)
    x = x + jnp.einsum("qhn,hnd->qd", attn, f32(lyr["wo"]))
    y = _layer_norm(x, f32(lyr["ln2"]))
    dense = _gelu(y @ f32(lyr["w1"])) @ f32(lyr["w2"])
    probs = jax.nn.softmax(y @ f32(lyr["gate"]), axis=-1)
    choice = probs.argmax(axis=-1)
    routed = jnp.zeros_like(x)
    for e in range(lyr["we1"].shape[0]):
        out = _gelu(y @ f32(lyr["we1"][e])) @ f32(lyr["we2"][e])
        routed = routed + jnp.where((choice == e)[:, None],
                                    out * probs[:, e:e + 1], 0.0)
    return x + dense + routed


@jax.jit
def _head(x, ln_f, head):
    return _layer_norm(x, ln_f.astype(jnp.float32)) @ head.astype(
        jnp.float32)


def forward_logits(params: Dict[str, Any], tokens, head_dim: int
                   ) -> np.ndarray:
    """``tokens (T,) int32`` → float32 logits ``(T, vocab)``."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], params["pos"],
                   jnp.asarray(tokens, jnp.int32))
        for lyr in params["layers"]:
            x = _layer(x, lyr, head_dim=head_dim)
        return np.asarray(_head(x, params["ln_f"], params["head"]))


def served_tokens_near_top(params: Dict[str, Any], model: Dict[str, Any],
                           prompt: np.ndarray, served, slack: float
                           ) -> Dict[str, int]:
    """Teacher-force one served stream through the reference: feed the
    prompt and the served tokens, padded to the configuration's
    ``max_seq`` (one shape to compile), and count the served tokens
    whose reference logit is within ``slack`` of their position's top
    logit.  ``model`` is the ``model`` object of a configuration file;
    every family's reference has this function.

    With random weights the top logit changes on rounding and top-1
    routing flips at about 1 % of positions in bfloat16 (PERF.md,
    finding 6 of PR 21), so agreement is a share of positions, judged
    by the caller, and never token equality."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    buf = np.zeros((max(model["max_seq"], len(seq)),), np.int32)
    buf[:len(seq)] = seq          # causal: the padding cannot reach back
    rows = forward_logits(params, buf, model["head_dim"])[
        len(prompt) - 1:len(seq)]
    if not np.isfinite(rows).all():
        raise FloatingPointError("reference logits are not finite")
    got = rows[np.arange(len(served)), served]
    return {"tokens": int(len(served)),
            "near_top": int((got >= rows.max(axis=1) - slack).sum()),
            "exact": int((rows.argmax(axis=1) == served).sum())}
