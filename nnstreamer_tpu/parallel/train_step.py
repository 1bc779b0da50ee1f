"""StreamFormer: a sharded transformer LM + train step over a 4-axis mesh.

Net-new TPU scale story (the reference's trainer is single-device
on-device training, gsttensor_trainer.c; its only distribution is stream
offload).  This module is the framework's distributed training core and the
target of the driver's multi-chip dryrun:

- **dp**: batch sharded, gradients psum'd
- **sp**: sequence sharded, attention runs as ring attention (exact) with
  K/V rotating on ICI
- **tp**: attention heads + MLP hidden megatron-sharded, activations psum'd
- **ep**: MoE experts sharded, switch-style top-1 ROUTED: each token runs
  exactly one expert (capacity-capped), with the Switch load-balance aux
  loss — compute scales with tokens, not with experts

Everything is a single ``jax.shard_map``-ped, jitted step: params enter
device-resident with per-leaf PartitionSpecs, the step never leaves the
device, and gradients are psum'd only over the axes each param is
replicated on.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention


@dataclasses.dataclass
class StreamFormerConfig:
    vocab: int = 256
    dim: int = 128
    heads: int = 8
    head_dim: int = 16
    mlp: int = 512
    layers: int = 2
    experts: int = 2          # MoE experts (sharded over ep)
    capacity_factor: float = 1.25  # per-expert token capacity multiplier
    aux_coef: float = 0.01    # Switch load-balance aux loss weight
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16
    lr: float = 1e-3
    #: long-context strategy over the sp axis: "ring" (K/V ppermute ring,
    #: any head count) or "ulysses" (head<->seq all-to-all, heads % sp == 0)
    seq_parallel: str = "ring"


def _param_specs(cfg: StreamFormerConfig) -> Dict[str, Any]:
    """PartitionSpec per parameter leaf.  tp shards heads/hidden; ep shards
    experts; everything is replicated over dp and sp."""
    layer = {
        "ln1": P(), "ln2": P(),
        "wqkv": P(None, None, "tp", None),   # (D, 3, H, Dh)
        "wo": P("tp", None, None),           # (H, Dh, D)
        "w1": P(None, "tp"),                 # (D, F)
        "w2": P("tp", None),                 # (F, D)
        "gate": P(),                         # (D, E)
        "we1": P("ep", None, None),          # (E_local, D, F)
        "we2": P("ep", None, None),          # (E_local, F, D)
    }
    return {
        "embed": P(),                        # (V, D)
        "pos": P(),                          # (max_seq, D)
        "head": P(),                         # (D, V) replicated (small V)
        "ln_f": P(),
        "layers": [dict(layer) for _ in range(cfg.layers)],
    }


def init_params(cfg: StreamFormerConfig, seed: int = 0) -> Dict[str, Any]:
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 8 * cfg.layers + 4)
    it = iter(ks)

    def norm(key, shape, scale=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * scale)

    d, h, hd, f, e = cfg.dim, cfg.heads, cfg.head_dim, cfg.mlp, cfg.experts
    params: Dict[str, Any] = {
        "embed": norm(next(it), (cfg.vocab, d)),
        "pos": norm(next(it), (cfg.max_seq, d)),
        "head": norm(next(it), (d, cfg.vocab)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": [],
    }
    for _ in range(cfg.layers):
        params["layers"].append({
            "ln1": jnp.ones((d,), jnp.float32),
            "ln2": jnp.ones((d,), jnp.float32),
            "wqkv": norm(next(it), (d, 3, h, hd)),
            "wo": norm(next(it), (h, hd, d)),
            "w1": norm(next(it), (d, f)),
            "w2": norm(next(it), (f, d)),
            "gate": norm(next(it), (d, e)),
            "we1": norm(next(it), (e, d, f)),
            "we2": norm(next(it), (e, f, d)),
        })
    return params


def _moe_switch(y, lyr, cfg: StreamFormerConfig):
    """Switch-Transformer top-1 routed MoE over the ep axis.

    Activations are replicated over ep (data rides dp/sp), so routing needs
    NO all-to-all: every ep shard sees all local tokens, gathers only those
    routed to ITS experts into a dense (E_local, capacity, D) block — a
    static-shaped, MXU-friendly batched matmul — and the psum over ep
    scatters expert outputs back to the token stream.  Tokens over an
    expert's capacity are dropped (residual passes them through), the
    standard Switch capacity-factor contract.

    Returns (moe_out (B,T,D), aux) where aux is the Switch load-balance
    loss E * Σ_e f_e·P_e computed over the GLOBAL (dp,sp) token set.
    """
    b, t, d = y.shape
    n = b * t
    e = cfg.experts
    tokens = y.reshape(n, d)
    logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32),
                        lyr["gate"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)          # (N, E) f32
    exp_idx = jnp.argmax(probs, axis=-1)             # (N,)
    gate_val = jnp.max(probs, axis=-1)               # (N,)
    onehot = jax.nn.one_hot(exp_idx, e, dtype=jnp.float32)
    cap = max(1, int(np.ceil(n / e * cfg.capacity_factor)))  # static
    pos = jnp.cumsum(onehot, axis=0) * onehot        # 1-based slot / expert
    disp = onehot * (pos <= cap)                     # capacity-capped
    pos0 = jnp.clip(pos - 1, 0, cap - 1).astype(jnp.int32)
    e_local = lyr["we1"].shape[0]
    ep_idx = jax.lax.axis_index("ep")
    disp_l = jax.lax.dynamic_slice_in_dim(disp, ep_idx * e_local,
                                          e_local, axis=1)
    pos_l = jax.lax.dynamic_slice_in_dim(pos0, ep_idx * e_local,
                                         e_local, axis=1)
    # (N, E_local, C): token→(expert, capacity-slot) dispatch tensor
    slot = (jax.nn.one_hot(pos_l, cap, dtype=cfg.dtype)
            * disp_l[..., None].astype(cfg.dtype))
    xe = jnp.einsum("nec,nd->ecd", slot, tokens.astype(cfg.dtype))
    he = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe,
                                lyr["we1"].astype(cfg.dtype)))
    oe = jnp.einsum("ecf,efd->ecd", he, lyr["we2"].astype(cfg.dtype))
    out = (jnp.einsum("nec,ecd->nd", slot, oe)
           * gate_val.astype(cfg.dtype)[:, None])
    out = jax.lax.psum(out, "ep")
    # load-balance aux (Switch eq. 4): fraction routed × mean router prob,
    # over the global token set so every device agrees on the value
    f_sum = jax.lax.psum(jnp.sum(onehot, axis=0), ("dp", "sp"))
    p_sum = jax.lax.psum(jnp.sum(probs, axis=0), ("dp", "sp"))
    n_tot = jax.lax.psum(jnp.float32(n), ("dp", "sp"))
    aux = e * jnp.sum((f_sum / n_tot) * (p_sum / n_tot))
    return out.reshape(b, t, d), aux


def _ln(x, scale):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale


def _forward_local(params, tokens, cfg: StreamFormerConfig):
    """Per-device forward inside shard_map.

    tokens: (B_local, T_local) int32.  Heads and MLP hidden are the local
    tp shard; sequence is the local sp shard (ring attention crosses sp);
    experts are the local ep shard (psum over ep combines).
    """
    sp_idx = jax.lax.axis_index("sp")
    b, t = tokens.shape
    pos = sp_idx * t + jnp.arange(t)
    x = params["embed"][tokens] + params["pos"][pos][None]
    x = x.astype(cfg.dtype)
    aux = jnp.float32(0)
    for lyr in params["layers"]:
        # -- attention (tp shards heads, sp ring over sequence) -------------
        y = _ln(x.astype(jnp.float32), lyr["ln1"]).astype(cfg.dtype)
        qkv = jnp.einsum("btd,dchn->btchn", y,
                         lyr["wqkv"].astype(cfg.dtype))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cfg.seq_parallel == "ulysses":
            from .ulysses import ulysses_attention

            seq_attn = ulysses_attention
        elif cfg.seq_parallel == "ring":
            seq_attn = ring_attention
        else:
            raise ValueError(
                f"seq_parallel={cfg.seq_parallel!r}: ring | ulysses")
        attn = jax.vmap(
            lambda qq, kk, vv: seq_attn(qq, kk, vv, "sp",
                                        causal=True))(q, k, v)
        o = jnp.einsum("bthn,hnd->btd", attn, lyr["wo"].astype(cfg.dtype))
        o = jax.lax.psum(o, "tp")  # combine head shards
        x = x + o
        # -- dense MLP (megatron tp) ---------------------------------------
        y = _ln(x.astype(jnp.float32), lyr["ln2"]).astype(cfg.dtype)
        hcore = jax.nn.gelu(jnp.einsum("btd,df->btf", y,
                                       lyr["w1"].astype(cfg.dtype)))
        m = jnp.einsum("btf,fd->btd", hcore, lyr["w2"].astype(cfg.dtype))
        m = jax.lax.psum(m, "tp")
        # -- MoE (switch-routed top-1, experts sharded over ep) ------------
        moe, aux_l = _moe_switch(y, lyr, cfg)
        aux = aux + aux_l
        x = x + m + moe
    x = _ln(x.astype(jnp.float32), params["ln_f"])
    logits = jnp.einsum("btd,dv->btv", x, params["head"])
    return logits, aux / max(1, len(params["layers"]))


def _loss_local(params, tokens, labels, cfg):
    logits, aux = _forward_local(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    # global mean over (dp, sp)-sharded tokens
    s = jax.lax.psum(jnp.sum(nll), ("dp", "sp"))
    n = jax.lax.psum(nll.size, ("dp", "sp"))
    return s / n + cfg.aux_coef * aux


def make_train_step(mesh: Mesh, cfg: Optional[StreamFormerConfig] = None,
                    seed: int = 0):
    """Build (jitted_step, sharded_params, sharded_opt_state, specs).

    The returned step is ``step(params, opt, tokens, labels) -> (params,
    opt, loss)`` jitted over the mesh; tokens/labels are (B, T) int32
    sharded (dp, sp).
    """
    cfg = cfg or StreamFormerConfig()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if cfg.experts % axis_sizes.get("ep", 1):
        raise ValueError("experts must divide ep axis size")
    specs = _param_specs(cfg)
    params = init_params(cfg, seed)

    # Adam state mirrors param sharding
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}
    opt_specs = {"m": specs, "v": specs, "step": P()}

    def local_step(params, opt, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: _loss_local(p, tokens, labels, cfg))(params)
        # psum gradients over every axis the param is REPLICATED on
        def sync(g, spec):
            used = {ax for part in spec if part
                    for ax in ((part,) if isinstance(part, str) else part)}
            axes = tuple(a for a in ("dp", "sp", "tp", "ep")
                         if a not in used)
            return jax.lax.psum(g, axes) if axes else g
        grads = jax.tree.map(sync, grads, specs,
                             is_leaf=lambda x: isinstance(x, jnp.ndarray))
        # Adam
        step = opt["step"] + 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g,
                         opt["m"], grads)
        v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g,
                         opt["v"], grads)
        t_f = step.astype(jnp.float32)
        corr = jnp.sqrt(1 - b2 ** t_f) / (1 - b1 ** t_f)
        params = jax.tree.map(
            lambda p, mm, vv: p - cfg.lr * corr * mm /
            (jnp.sqrt(vv) + eps), params, m, v)
        return params, {"m": m, "v": v, "step": step}, loss

    shard_step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, opt_specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, opt_specs, P()),
        check_vma=False)
    jitted = jax.jit(shard_step, donate_argnums=(0, 1))

    def place(tree, spec_tree):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, spec_tree,
            is_leaf=lambda x: isinstance(x, (jnp.ndarray, np.ndarray)))

    params = place(params, specs)
    opt = place(opt, opt_specs)
    return jitted, params, opt, specs


def make_data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp", "sp"))
