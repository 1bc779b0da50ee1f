"""SambaY decoder-hybrid-decoder serving (the ``sambay_lm`` family of
``tensor_llm``): Mamba, windowed and full differential attention, and a
cross-decoder whose gated memory units and cross-attention layers keep
no state of their own (arXiv:2507.06607; Phi-4-mini-flash-reasoning is
this family at ``dim:2560,layers:32``).

Layers by index ``i``, with ``h = layers // 2`` (``layers % 4 == 0``)::

    i < h, even     Mamba
    i < h, odd      differential attention over the last ``window``
                    positions
    i == h          Mamba, which also hands its scan output m_t (before
                    the gate) to the memory units
    i == h + 1      differential attention, full and causal; its keys
                    and values are THE cache
    i > h + 1, even gated memory unit on layer h's m_t (token-wise)
    i > h + 1, odd  differential CROSS-attention: own queries, layer
                    h + 1's keys and values

Every layer is ``x += Mixer(LN1(x)); x += MLP(LN2(x))`` (LayerNorm with
scale and bias, SwiGLU), no positional encoding, tied embedding and
head.  The equations are in ``benchmarks/reference/phi4flash.py``, the
plain reference this file is tested against.

What a session keeps (the three kinds of state :func:`init_state` lays
side by side, one row per slot): layer h + 1's keys and values by
position (``kv``: ``(1, slots + 1, max_seq, kv_heads * head_dim)``, the
dense pool's own layout, which the decode step reads where it lies on a
TPU, ``ops/shared_kv_decode.py``, and through ``_slot_rows`` elsewhere);
a ring of ``window`` positions for each windowed layer, written at
``pos mod window`` (``ring``); the convolution tail and the float32 SSM
state of each Mamba layer (``conv``, ``ssm``: fixed rows, nothing by position).
The SSM state is held ``(d_state, d_inner)``: the wide axis on the
lanes.  A slot a longer session left behind starts clean without being
cleared: stale keys are masked by position as in every pool, and the
recurrent rows, which no position masks, read as zero wherever a
session is at position 0.

Serving functions: :func:`prefill_chunk` (one fixed chunk of a prompt:
layers up to h + 1 over the chunk, carrying conv/SSM state and the
ring and appending the cache's rows; the cross-decoder only at the
position whose logits are returned, on the last chunk) and
:func:`decode_step` (one token for each of ``B`` lanes; free of loops,
so every device operation carries its own scope).  Weights are
``cfg.dtype`` (bfloat16 as published), the residual stream, the norms,
the softmaxes and the scan are float32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.shared_kv_decode import shared_kv_decode_attention
from .streamformer_lm import _slot_rows

#: names of the arrays of :func:`init_state`, in order
STATE_KINDS = ("kv", "kv", "ring", "ring", "conv", "ssm")
#: query positions a prefill chunk's full attention scores at a time
_QBLOCK = 64
#: whether the decode step reads layer h + 1's rows through the kernel of
#: ``ops/shared_kv_decode.py`` or XLA's gathered form: None = where the
#: default backend is a TPU (a test that compiles for a described chip
#: sets it)
SHARED_KV_KERNEL = None


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab: int = 257
    dim: int = 64
    heads: int = 4            # query heads; paired by two
    kv_heads: int = 2         # key/value heads; paired by two
    head_dim: int = 16
    mlp: int = 128            # SwiGLU intermediate width
    layers: int = 8
    window: int = 8           # positions a windowed layer sees, itself included
    d_state: int = 4
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 4
    max_seq: int = 64
    dtype: Any = jnp.bfloat16
    eps: float = 1e-5

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def kv_row(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def yoco(self) -> int:
        """Index of the Mamba layer that hands ``m_t`` on; the full
        attention layer is the next."""
        return self.layers // 2

    @property
    def chunk(self) -> int:
        """Positions one prefill executable takes: the window, so a
        whole chunk IS the next ring."""
        return self.window


def layer_kind(i: int, cfg: SambaYConfig) -> str:
    h = cfg.yoco
    if i <= h:
        return "mamba" if i % 2 == 0 else "swa"
    if i == h + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def config_from_custom(custom: Dict[str, Any]) -> SambaYConfig:
    """The family's ``custom=`` grammar (``arch:sambay_lm`` selects it)::

        custom=arch:sambay_lm,vocab:200064,dim:2560,heads:40,kv_heads:20,
               head_dim:64,mlp:10240,layers:32,window:512,d_state:16,
               d_conv:4,expand:2,dt_rank:160,max_seq:16384,dtype:bfloat16

    ``dt_rank`` defaults to ``dim / 16``; ``max_seq`` must be named and
    be a multiple of ``window`` (a prompt is prefilled in chunks of
    ``window`` positions that never straddle the cache's end).
    ``experts:0`` may be stated and nothing else of it: the family
    routes no experts (a launch line that says so is refused at once by
    a ``tensor_llm`` that knows no ``arch:`` and would build
    ``streamformer_lm`` at these sizes, whose ``experts`` is >= 1)."""
    known = {f.name for f in dataclasses.fields(SambaYConfig)} - {"eps"}
    custom = dict(custom)
    if int(custom.pop("experts", 0)) != 0:
        raise ValueError("sambay_lm: the family routes no experts "
                         "(experts must be 0 or left out)")
    extra = set(custom) - known - {"arch"}
    if extra:
        raise ValueError(f"sambay_lm: unknown custom keys {sorted(extra)} "
                         f"(known: {sorted(known)})")
    kw = {k: int(v) for k, v in custom.items()
          if k in known and k != "dtype"}
    if "dim" in kw:
        kw.setdefault("dt_rank", max(1, kw["dim"] // 16))
    cfg = SambaYConfig(dtype=jnp.dtype(custom.get("dtype", "bfloat16")),
                       **kw)
    sizes = [getattr(cfg, k) for k in sorted(known - {"dtype"})]
    if min(sizes) < 1:
        raise ValueError("sambay_lm: every size must be >= 1")
    if cfg.layers % 4 or cfg.layers < 8:
        raise ValueError(f"sambay_lm: layers={cfg.layers} must be a "
                         "multiple of 4 and at least 8 (a Mamba/window "
                         "pair, the hand-over pair, a memory/cross pair)")
    if cfg.heads % 2 or cfg.kv_heads % 2 or cfg.heads % cfg.kv_heads:
        raise ValueError("sambay_lm: heads and kv_heads pair by two, and "
                         "kv_heads divides heads")
    if cfg.d_conv < 2:
        raise ValueError("sambay_lm: d_conv must be >= 2")
    if cfg.max_seq % cfg.window:
        raise ValueError(f"sambay_lm: max_seq={cfg.max_seq} must be a "
                         f"multiple of window={cfg.window}")
    return cfg


# -- parameters ----------------------------------------------------------
def _key(seed: int):
    # --seed is any whole number up to a little over 2**31
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _draw(key, shape, std: float, dtype):
    """One leaf, drawn where the default device is (on the chip: a host
    draw of 3.85 G normals costs minutes and 15 GB of host memory)."""
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_params(cfg: SambaYConfig, seed: int = 0) -> Dict[str, Any]:
    """Seeded random weights: matrices N(0, 0.02) in ``cfg.dtype``;
    ``A_log = log(1..d_state)``, ``D = 1``, ``b_dt = softplus^-1(U(1e-3,
    1e-1))`` (Mamba's own initialisation, so the state neither dies nor
    blows up); the lambda vectors N(0, 0.1); norms at identity.  Vectors
    and ``A_log`` are float32."""
    d, di, f = cfg.dim, cfg.d_inner, cfg.mlp
    qw, kw = cfg.heads * cfg.head_dim, cfg.kv_row
    n, r, dt = cfg.d_state, cfg.dt_rank, cfg.dtype
    keys = iter(jax.random.split(_key(seed), 16 * cfg.layers + 1))

    def mat(*shape):
        return _draw(next(keys), shape, 0.02, dt)

    def norm():
        return {"w": jnp.ones((d,), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}

    def diff():
        out = {name: _draw(next(keys), (cfg.head_dim,), 0.1, jnp.float32)
               for name in ("lq1", "lk1", "lq2", "lk2")}
        out.update(subln=jnp.ones((2 * cfg.head_dim,), jnp.float32),
                   w_o=mat(qw, d), b_o=jnp.zeros((d,), jnp.float32))
        return out

    layers = []
    for i in range(cfg.layers):
        kind = layer_kind(i, cfg)
        lyr = {"ln1": norm(), "ln2": norm(),
               "w_gate_up": mat(d, 2 * f), "w_down": mat(f, d)}
        if kind == "mamba":
            u = jax.random.uniform(next(keys), (di,), jnp.float32,
                                   1e-3, 1e-1)
            lyr.update(
                w_in=mat(d, 2 * di), conv_w=mat(cfg.d_conv, di),
                conv_b=jnp.zeros((di,), jnp.float32),
                w_x=mat(di, r + 2 * n), w_dt=mat(r, di),
                b_dt=u + jnp.log(-jnp.expm1(-u)),
                A_log=jnp.tile(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                    (di, 1)),
                D=jnp.ones((di,), jnp.float32), w_out=mat(di, d))
        elif kind in ("swa", "full"):
            lyr.update(w_qkv=mat(d, qw + 2 * kw),
                       b_qkv=jnp.zeros((qw + 2 * kw,), jnp.float32),
                       **diff())
        elif kind == "gmu":
            lyr.update(w_1=mat(d, di), w_2=mat(di, d))
        else:
            lyr.update(w_q=mat(d, qw), b_q=jnp.zeros((qw,), jnp.float32),
                       **diff())
        layers.append(lyr)
    return {"embed": mat(cfg.vocab, d), "layers": layers, "ln_f": norm()}


def init_state(cfg: SambaYConfig, slots: int) -> Tuple[jnp.ndarray, ...]:
    """The pool's arrays (:data:`STATE_KINDS` names them), index
    ``slots`` of the slot dimension being the scratch slot."""
    s = int(slots) + 1
    h = cfg.yoco
    n_swa, n_mamba = h // 2, h // 2 + 1
    kv = (1, s, cfg.max_seq, cfg.kv_row)
    ring = (n_swa, s, cfg.window, cfg.kv_row)
    return (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype),
            jnp.zeros(ring, cfg.dtype), jnp.zeros(ring, cfg.dtype),
            jnp.zeros((n_mamba, s, cfg.d_conv - 1, cfg.d_inner),
                      cfg.dtype),
            jnp.zeros((n_mamba, s, cfg.d_state, cfg.d_inner),
                      jnp.float32))


# -- shared arithmetic ---------------------------------------------------
def _mm(x, w):
    """``x @ w`` with both in the weights' dtype, summed in float32."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _ln(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


def _mlp(x, lyr, cfg):
    with jax.named_scope("sflm.mlp"):
        gu = _mm(_ln(x, lyr["ln2"], cfg.eps), lyr["w_gate_up"])
        g, u = jnp.split(gu, 2, axis=-1)
        return x + _mm(u * jax.nn.silu(g), lyr["w_down"])


def _lambda(lyr, i: int):
    return (jnp.exp(jnp.dot(lyr["lq1"], lyr["lk1"]))
            - jnp.exp(jnp.dot(lyr["lq2"], lyr["lk2"])) + lambda_init(i))


def _sub_norm(a1, a2, lyr, i: int, cfg):
    """``RMSNorm(a1 - lambda a2) * (1 - lambda_init)`` over the last
    axis (``2 * head_dim`` wide)."""
    diff = a1 - _lambda(lyr, i) * a2
    ms = jnp.square(diff).mean(-1, keepdims=True)
    return (diff * jax.lax.rsqrt(ms + cfg.eps) * lyr["subln"]
            * (1.0 - lambda_init(i)))


def _diff_attn_seq(q, k, v, mask, lyr, i: int, cfg):
    """Differential attention of ``Tq`` queries over ``Tk`` keys of one
    sequence: ``q (Tq, heads * hd)``, ``k``/``v (Tk, kv_heads * hd)``,
    ``mask (Tq, Tk)``.  Query pair ``p`` uses key/value pair ``p //
    (heads / kv_heads)``; returns ``(Tq, heads * hd)`` before ``w_o``."""
    hd, g = cfg.head_dim, cfg.kv_heads // 2
    rep = cfg.heads // cfg.kv_heads
    tq, tk = q.shape[0], k.shape[0]
    q5 = q.reshape(tq, g, rep, 2, hd).astype(cfg.dtype)
    k4 = k.reshape(tk, g, 2, hd).astype(cfg.dtype)
    v3 = v.reshape(tk, g, 2 * hd).astype(cfg.dtype)
    s = jnp.einsum("qgrsd,kgsd->grsqk", q5, k4,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = jnp.einsum("grsqk,kge->qgrse", p.astype(cfg.dtype), v3,
                   preferred_element_type=jnp.float32)
    o = _sub_norm(a[..., 0, :], a[..., 1, :], lyr, i, cfg)
    return o.reshape(tq, cfg.heads * hd)


def _head_maps(cfg):
    """For each query head: its key head, and its value pair, one-hot."""
    rep = cfg.heads // cfg.kv_heads
    head = jnp.arange(cfg.heads)
    pair = head // 2 // rep
    key_head = 2 * pair + head % 2
    return (jax.nn.one_hot(key_head, cfg.kv_heads, dtype=cfg.dtype),
            jax.nn.one_hot(pair, cfg.kv_heads // 2, dtype=jnp.float32))


def _query_rows(q, cfg):
    """``q (B, heads * hd)`` with each query head laid into the columns
    of its key head: ``(B, heads, kv_heads * hd)`` in ``cfg.dtype``, so
    a lane's scores are one ``(heads, row) x (T, row)^T`` product."""
    b = q.shape[0]
    to_key, _ = _head_maps(cfg)
    q3 = q.reshape(b, cfg.heads, cfg.head_dim).astype(cfg.dtype)
    return jnp.einsum("bhd,hk->bhkd", q3, to_key).reshape(
        b, cfg.heads, cfg.kv_row)


def _pair_out(wide, lyr, i: int, cfg):
    """The read-out ``wide (B, heads, kv_heads * hd)`` float32 (every
    head's weighted sum of whole V rows), of which a head keeps the
    columns of its value pair, through the pairs' difference and sub-norm:
    ``(B, heads * hd)`` before ``w_o``."""
    hd, b = cfg.head_dim, wide.shape[0]
    _, to_pair = _head_maps(cfg)
    a = jnp.einsum("bhge,hg->bhe",
                   wide.reshape(b, cfg.heads, cfg.kv_heads // 2, 2 * hd),
                   to_pair).reshape(b, cfg.heads // 2, 2, 2 * hd)
    o = _sub_norm(a[:, :, 0], a[:, :, 1], lyr, i, cfg)
    return o.reshape(b, cfg.heads * hd)


def _diff_attn_rows(q, k_rows, v_rows, valid, lyr, i: int, cfg):
    """Differential attention of ONE query a lane over that lane's
    cached rows, the rows left as they lie (``(B, T, kv_heads * hd)``,
    lane-dense): the scores are one ``(heads, row) x (T, row)^T``
    product a lane (:func:`_query_rows`) and the read-out one ``(heads,
    T) x (T, row)`` product (:func:`_pair_out`).  ``q (B, heads * hd)``,
    ``valid (B, T)``; returns ``(B, heads * hd)`` before ``w_o``."""
    s = jnp.einsum("bhr,btr->bht", _query_rows(q, cfg), k_rows,
                   preferred_element_type=jnp.float32) / math.sqrt(
                       cfg.head_dim)
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, -jnp.inf), axis=-1)
    wide = jnp.einsum("bht,btr->bhr", p.astype(cfg.dtype), v_rows,
                      preferred_element_type=jnp.float32)
    return _pair_out(wide, lyr, i, cfg)


def _split_qkv(y, lyr, cfg):
    qw = cfg.heads * cfg.head_dim
    qkv = _mm(y, lyr["w_qkv"]) + lyr["b_qkv"]
    return (qkv[..., :qw], qkv[..., qw:qw + cfg.kv_row].astype(cfg.dtype),
            qkv[..., qw + cfg.kv_row:].astype(cfg.dtype))


def _ssm_inputs(xc, lyr, cfg):
    """``(delta, B, C)`` of the selective scan from the convolved
    ``xc (..., d_inner)``, in float32."""
    r, n = cfg.dt_rank, cfg.d_state
    dbc = _mm(xc, lyr["w_x"])
    delta = jax.nn.softplus(_mm(dbc[..., :r], lyr["w_dt"]) + lyr["b_dt"])
    return delta, dbc[..., r:r + n], dbc[..., r + n:]


def _mamba_seq(y, lyr, conv0, h0, n_valid, cfg):
    """The Mamba mixer over ``T`` positions of one sequence, from the
    state a previous chunk left: ``y (T, dim)`` normed, ``conv0 (d_conv
    - 1, d_inner)`` the inputs before the first position, ``h0 (d_state,
    d_inner)`` float32.  Positions from ``n_valid`` on are padding: they
    leave the state as it was.  Returns ``(out (T, dim), m (T, d_inner),
    conv', h')``."""
    t, di, kc = y.shape[0], cfg.d_inner, cfg.d_conv
    xz = _mm(y, lyr["w_in"])
    x, z = xz[:, :di], xz[:, di:]
    xpad = jnp.concatenate([conv0.astype(jnp.float32), x])
    w = lyr["conv_w"].astype(jnp.float32)
    xc = jax.nn.silu(sum(xpad[j:j + t] * w[j] for j in range(kc))
                     + lyr["conv_b"])
    delta, bm, cm = _ssm_inputs(xc, lyr, cfg)
    delta = jnp.where(jnp.arange(t)[:, None] < n_valid, delta, 0.0)
    a = -jnp.exp(lyr["A_log"]).T                           # (N, Di)

    def step(h, inp):
        d_t, x_t, b_t, c_t = inp
        h = jnp.exp(d_t[None, :] * a) * h \
            + (d_t * x_t)[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0)

    # four positions an iteration: 0.43 ms a 512-position chunk on a
    # v5e against 1.78 one by one and 0.54 at sixteen (chip run, PR 29)
    h1, hc = jax.lax.scan(step, h0, (delta, xc, bm, cm),
                          unroll=min(4, t))
    m = hc + lyr["D"] * xc
    conv1 = jax.lax.dynamic_slice_in_dim(xpad, n_valid, kc - 1)
    return (_mm(m * jax.nn.silu(z), lyr["w_out"]), m,
            conv1.astype(conv0.dtype), h1)


def _mamba_step(y, lyr, conv0, h0, cfg):
    """The same mixer for one position of each of ``B`` lanes: ``y (B,
    dim)``, ``conv0 (B, d_conv - 1, d_inner)``, ``h0 (B, d_state,
    d_inner)``."""
    di = cfg.d_inner
    xz = _mm(y, lyr["w_in"])
    x, z = xz[:, :di], xz[:, di:]
    taps = jnp.concatenate([conv0.astype(jnp.float32), x[:, None]], axis=1)
    xc = jax.nn.silu(jnp.einsum("bkd,kd->bd", taps,
                                lyr["conv_w"].astype(jnp.float32))
                     + lyr["conv_b"])
    delta, bm, cm = _ssm_inputs(xc, lyr, cfg)
    a = -jnp.exp(lyr["A_log"]).T
    h1 = jnp.exp(delta[:, None, :] * a) * h0 \
        + (delta * xc)[:, None, :] * bm[:, :, None]
    m = (h1 * cm[:, :, None]).sum(1) + lyr["D"] * xc
    return (_mm(m * jax.nn.silu(z), lyr["w_out"]), m,
            taps[:, 1:].astype(conv0.dtype), h1)


def _rows(pool, j: int, slots):
    """``pool[j][slots]`` as ONE gather off a flat view of the leading
    two dimensions (no copy of layer ``j`` first)."""
    return pool.reshape((-1,) + pool.shape[2:])[j * pool.shape[1] + slots]


def _gmu(y, m, lyr):
    return _mm(m * jax.nn.silu(_mm(y, lyr["w_1"])), lyr["w_2"])


def _logits(x, params, cfg):
    with jax.named_scope("sflm.head"):
        y = _ln(x, params["ln_f"], cfg.eps).astype(cfg.dtype)
        return jnp.einsum("...d,vd->...v", y, params["embed"],
                          preferred_element_type=jnp.float32)


# -- the whole sequence, every layer everywhere --------------------------
def forward_logits(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: SambaYConfig) -> jnp.ndarray:
    """``tokens (T,) int32`` → float32 logits ``(T, vocab)``: every
    layer at every position, nothing cached.  What the serving functions
    are tested against at small sizes; not a serving path."""
    t = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)
    at = jnp.arange(t)
    causal = at[None, :] <= at[:, None]
    near = causal & (at[None, :] > at[:, None] - cfg.window)
    zero_conv = jnp.zeros((cfg.d_conv - 1, cfg.d_inner), cfg.dtype)
    zero_h = jnp.zeros((cfg.d_state, cfg.d_inner), jnp.float32)
    m = k17 = v17 = None
    for i in range(cfg.layers):
        lyr = params["layers"][i]
        kind = layer_kind(i, cfg)
        y = _ln(x, lyr["ln1"], cfg.eps)
        if kind == "mamba":
            out, mi, _, _ = _mamba_seq(y, lyr, zero_conv, zero_h, t, cfg)
            if i == cfg.yoco:
                m = mi
        elif kind == "gmu":
            out = _gmu(y, m, lyr)
        else:
            if kind == "cross":
                q, k, v = _mm(y, lyr["w_q"]) + lyr["b_q"], k17, v17
            else:
                q, k, v = _split_qkv(y, lyr, cfg)
                if kind == "full":
                    k17, v17 = k, v
            o = _diff_attn_seq(q, k, v, near if kind == "swa" else causal,
                               lyr, i, cfg)
            out = _mm(o, lyr["w_o"]) + lyr["b_o"]
        x = _mlp(x + out, lyr, cfg)
    return _logits(x, params, cfg)


# -- serving: one chunk of a prompt --------------------------------------
def _ring_after(ring, rows, start, end, cfg):
    """The ring once positions ``start .. end - 1`` (``rows[0 ..]``)
    are in it: row ``j`` holds the latest position ``p < end`` with ``p
    mod window == j``, the chunk's if ``p >= start``, else what it
    held."""
    w = cfg.window
    j = jnp.arange(w)
    p = (end - 1) - jnp.mod(end - 1 - j, w)
    take = jnp.clip(p - start, 0, rows.shape[0] - 1)
    return jnp.where((p >= start)[:, None], rows[take], ring)


def _cross_decoder(x, m, attend, params, cfg):
    """Layers ``h + 2 ..`` and the head for one position of each of
    ``B`` lanes: ``x (B, dim)``, ``m (B, d_inner)`` layer h's scan
    output there, ``attend(q, lyr, i)`` the caller's reading of the
    cache's rows (differential attention, ``(B, heads * hd)`` before
    ``w_o``)."""
    for i in range(cfg.yoco + 2, cfg.layers):
        lyr = params["layers"][i]
        if layer_kind(i, cfg) == "gmu":
            with jax.named_scope("sflm.gmu"):
                x = x + _gmu(_ln(x, lyr["ln1"], cfg.eps), m, lyr)
        else:
            with jax.named_scope("sflm.qkv"):
                q = _mm(_ln(x, lyr["ln1"], cfg.eps), lyr["w_q"]) \
                    + lyr["b_q"]
            with jax.named_scope("sflm.cross_attn"):
                o = attend(q, lyr, i)
                x = x + _mm(o, lyr["w_o"]) + lyr["b_o"]
        x = _mlp(x, lyr, cfg)
    return _logits(x, params, cfg)


def prefill_chunk(params: Dict[str, Any], state: Tuple[jnp.ndarray, ...],
                  tokens: jnp.ndarray, slot: jnp.ndarray,
                  start: jnp.ndarray, true_len: jnp.ndarray,
                  last: jnp.ndarray, cfg: SambaYConfig
                  ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Positions ``start .. start + true_len - 1`` of the prompt in
    ``slot``: ``tokens (chunk,)`` zero-padded, ``start`` a multiple of
    the chunk (traced, as ``slot``, ``true_len`` and ``last`` are: ONE
    executable serves every chunk of every prompt).  Layers up to ``h +
    1`` run over the chunk from the state the slot holds (clean at
    ``start == 0``, whatever the slot held) and leave theirs behind;
    where ``last`` is set the cross-decoder and the head run at the
    chunk's last real position, else the logits are zeros.  Returns
    ``(logits (vocab,), state')``."""
    kpool, vpool, rk, rv, conv, ssm = state
    c, w, h = cfg.chunk, cfg.window, cfg.yoco
    end = start + true_len
    at = start + jnp.arange(c)                       # absolute positions
    fresh = start == 0
    with jax.named_scope("sflm.embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    # a windowed layer's keys: the ring (positions start - w .. start -
    # 1, in order, as start is a multiple of w), then the chunk's own
    kpos = jnp.concatenate([start - w + jnp.arange(w), at])
    near = ((kpos[None, :] <= at[:, None])
            & (kpos[None, :] > at[:, None] - w) & (kpos[None, :] >= 0))
    m = None
    for i in range(h + 2):
        lyr = params["layers"][i]
        kind, j = layer_kind(i, cfg), i // 2
        if kind == "mamba":
            with jax.named_scope("sflm.state_read"):
                conv0 = jnp.where(fresh, 0, conv[j, slot])
                h0 = jnp.where(fresh, 0.0, ssm[j, slot])
            with jax.named_scope("sflm.ssm"):
                out, mi, conv1, h1 = _mamba_seq(
                    _ln(x, lyr["ln1"], cfg.eps), lyr, conv0, h0,
                    true_len, cfg)
                x = x + out
            with jax.named_scope("sflm.state_write"):
                conv = jax.lax.dynamic_update_slice(
                    conv, conv1[None, None], (j, slot, 0, 0))
                ssm = jax.lax.dynamic_update_slice(
                    ssm, h1[None, None], (j, slot, 0, 0))
            if i == h:
                m = mi
        else:
            with jax.named_scope("sflm.qkv"):
                q, k, v = _split_qkv(_ln(x, lyr["ln1"], cfg.eps), lyr, cfg)
            if kind == "swa":
                with jax.named_scope("sflm.state_read"):
                    rk_j, rv_j = rk[j, slot], rv[j, slot]
                with jax.named_scope("sflm.swa_attn"):
                    o = _diff_attn_seq(
                        q, jnp.concatenate([rk_j, k]),
                        jnp.concatenate([rv_j, v]), near, lyr, i, cfg)
                    x = x + _mm(o, lyr["w_o"]) + lyr["b_o"]
                with jax.named_scope("sflm.state_write"):
                    rk = jax.lax.dynamic_update_slice(
                        rk, _ring_after(rk_j, k, start, end, cfg)[
                            None, None], (j, slot, 0, 0))
                    rv = jax.lax.dynamic_update_slice(
                        rv, _ring_after(rv_j, v, start, end, cfg)[
                            None, None], (j, slot, 0, 0))
            else:
                with jax.named_scope("sflm.kv_write"):
                    kpool = jax.lax.dynamic_update_slice(
                        kpool, k[None, None], (0, slot, start, 0))
                    vpool = jax.lax.dynamic_update_slice(
                        vpool, v[None, None], (0, slot, start, 0))
                with jax.named_scope("sflm.kv_read"):
                    k_all, v_all = kpool[0, slot], vpool[0, slot]
                with jax.named_scope("sflm.full_attn"):
                    seen = jnp.arange(cfg.max_seq)

                    def block(args):
                        qb, pb = args
                        return _diff_attn_seq(
                            qb, k_all, v_all, seen[None, :] <= pb[:, None],
                            lyr, i, cfg)

                    # scores of _QBLOCK queries against every
                    # reserved position at a time (a chunk that is no
                    # multiple of it goes whole)
                    blk = _QBLOCK if c % _QBLOCK == 0 else c
                    o = jax.lax.map(block, (q.reshape(c // blk, blk, -1),
                                            at.reshape(c // blk, blk)))
                    x = x + _mm(o.reshape(c, -1), lyr["w_o"]) + lyr["b_o"]
        x = _mlp(x, lyr, cfg)

    def tail():
        one = lambda a: jax.lax.dynamic_slice_in_dim(   # noqa: E731
            a, true_len - 1, 1)
        valid = jnp.arange(cfg.max_seq)[None, :] < end
        return _cross_decoder(
            one(x), one(m), lambda q, lyr, i: _diff_attn_rows(
                q, k_all[None], v_all[None], valid, lyr, i, cfg),
            params, cfg)[0]

    logits = jax.lax.cond(
        last, tail, lambda: jnp.zeros((cfg.vocab,), jnp.float32))
    return logits, (kpool, vpool, rk, rv, conv, ssm)


# -- serving: one token a lane -------------------------------------------
def _shared_rows(kpool, vpool, slots, pos, cfg):
    """The decode step's reading of layer h + 1's rows, ``attend(q, lyr,
    i)`` as :func:`_cross_decoder` takes it, for the full layer and every
    cross-attention layer.  On a TPU (:data:`SHARED_KV_KERNEL`) the
    kernel over both pools where they lie, each lane's slot up to its
    position (``slots`` is the gather), once a reading; elsewhere the
    lanes' rows gathered once (``_slot_rows``, every reserved position)
    and XLA's products over them."""
    kernel = SHARED_KV_KERNEL
    if kernel is None:
        kernel = jax.default_backend() == "tpu"
    if kernel:
        def attend(q, lyr, i):
            wide = shared_kv_decode_attention(
                _query_rows(q, cfg), kpool, vpool, slots, pos,
                1.0 / math.sqrt(cfg.head_dim))
            return _pair_out(wide, lyr, i, cfg)
        return attend
    with jax.named_scope("sflm.kv_read"):
        # the barrier keeps the attention's say over layouts out of the
        # gather (streamformer_lm's decode_step_pooled; PERF.md section 6)
        k_rows, v_rows = jax.lax.optimization_barrier(
            (_slot_rows(kpool, 0, slots), _slot_rows(vpool, 0, slots)))
    seen = jnp.arange(cfg.max_seq)[None, :] <= pos[:, None]
    return lambda q, lyr, i: _diff_attn_rows(q, k_rows, v_rows, seen, lyr,
                                             i, cfg)


def decode_step(params: Dict[str, Any], state: Tuple[jnp.ndarray, ...],
                tokens: jnp.ndarray, pos: jnp.ndarray, slots: jnp.ndarray,
                cfg: SambaYConfig
                ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """One decode step over ``B`` lanes, each at its own position in its
    own slot (padding lanes: the scratch slot, position 0).  Layer ``h +
    1``'s rows are written once and read by the full layer and by every
    cross-attention layer (:func:`_shared_rows`: on a TPU each reading
    walks each lane's rows up to its position where they lie).  Returns
    ``(logits (B, vocab) f32, state')``."""
    kpool, vpool, rk, rv, conv, ssm = state
    w, h = cfg.window, cfg.yoco
    with jax.named_scope("sflm.embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    fresh = (pos == 0)[:, None, None]
    at = pos % w
    in_ring = ((jnp.arange(w)[None, :] <= pos[:, None])
               | (pos[:, None] >= w))
    m = attend = None
    for i in range(h + 2):
        lyr = params["layers"][i]
        kind, j = layer_kind(i, cfg), i // 2
        if kind == "mamba":
            with jax.named_scope("sflm.state_read"):
                conv0 = jnp.where(fresh, 0, _rows(conv, j, slots))
                h0 = jnp.where(fresh, 0.0, _rows(ssm, j, slots))
            with jax.named_scope("sflm.ssm"):
                out, mi, conv1, h1 = _mamba_step(
                    _ln(x, lyr["ln1"], cfg.eps), lyr, conv0, h0, cfg)
                x = x + out
            with jax.named_scope("sflm.state_write"):
                conv = conv.at[j, slots].set(conv1)
                ssm = ssm.at[j, slots].set(h1)
            if i == h:
                m = mi
        else:
            with jax.named_scope("sflm.qkv"):
                q, k, v = _split_qkv(_ln(x, lyr["ln1"], cfg.eps), lyr, cfg)
            if kind == "swa":
                with jax.named_scope("sflm.state_write"):
                    rk = rk.at[j, slots, at].set(k)
                    rv = rv.at[j, slots, at].set(v)
                with jax.named_scope("sflm.state_read"):
                    rows = jax.lax.optimization_barrier(
                        (_slot_rows(rk, j, slots),
                         _slot_rows(rv, j, slots)))
                with jax.named_scope("sflm.swa_attn"):
                    o = _diff_attn_rows(q, *rows, in_ring, lyr, i, cfg)
                    x = x + _mm(o, lyr["w_o"]) + lyr["b_o"]
            else:
                with jax.named_scope("sflm.kv_write"):
                    kpool = kpool.at[0, slots, pos].set(k)
                    vpool = vpool.at[0, slots, pos].set(v)
                attend = _shared_rows(kpool, vpool, slots, pos, cfg)
                with jax.named_scope("sflm.full_attn"):
                    o = attend(q, lyr, i)
                    x = x + _mm(o, lyr["w_o"]) + lyr["b_o"]
        x = _mlp(x, lyr, cfg)
    logits = _cross_decoder(x, m, attend, params, cfg)
    return logits, (kpool, vpool, rk, rv, conv, ssm)


# -- the seam tensor_llm takes a family through (llm/family.py) ----------
class _Family:
    name = "sambay_lm"
    #: no block-paged arena, no interleaved prefill, no prefix reuse:
    #: a page of keys says nothing of the recurrent state beside it
    paged = False
    unpaged_why = ("its sessions keep recurrent rows and rings beside one "
                   "layer's keys, which no page table names and no page's "
                   "content hash vouches for (prefix reuse over recurrent "
                   "state needs snapshots)")
    state_kinds = STATE_KINDS
    config_from_custom = staticmethod(config_from_custom)
    init_params = staticmethod(init_params)
    init_state = staticmethod(init_state)
    decode_step = staticmethod(decode_step)
    prefill_chunk = staticmethod(prefill_chunk)

    @staticmethod
    def chunk_len(cfg) -> int:
        return cfg.chunk


FAMILY = _Family()
