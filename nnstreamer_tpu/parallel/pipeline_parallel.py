"""Pipeline parallelism (pp axis): GPipe-scheduled stage sharding.

Completes the framework's parallelism set (dp/sp/tp/ep in train_step.py,
pp here).  The reference has no training-scale story at all
(gsttensor_trainer.c is single-device); this is TPU-native design:

- **stage sharding**: transformer layers are STACKED on a leading axis and
  sharded over the mesh's ``pp`` axis — each pp rank owns ``L/pp``
  consecutive layers, embed/head are replicated (their grads are nonzero
  only on the ranks that use them; the pp psum recovers the global grad).
- **GPipe fill-drain schedule**: the batch splits into M microbatches; a
  ``lax.scan`` over ``M + pp - 1`` ticks keeps every rank busy once the
  pipe fills.  At each tick every rank applies its stage to the activation
  it received and hands the result to the next rank via
  ``jax.lax.ppermute`` — one hop over ICI per tick.
- **backward for free**: the whole schedule (scan + ppermute chain) is
  differentiated by jax; the transposed program runs the reversed
  schedule with reversed permutes, so 1F1B-style comm emerges from
  autodiff rather than hand-written send/recv.  (The reference's NCCL
  analogue would be explicit isend/irecv pairs.)
- composes with **dp** (batch), **sp** (ring attention over sequence) and
  **tp** (megatron heads/hidden) on the same mesh.

The stage math is the dense StreamFormer layer (attention + MLP; MoE stays
with the ep axis in train_step.py — pp×ep on one mesh needs more devices
than the 8-way CI mesh can host).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .ring_attention import ring_attention
from .train_step import StreamFormerConfig, _ln


def stacked_param_specs() -> Dict[str, Any]:
    """PartitionSpec per leaf: layer stacks shard over pp (leading axis),
    tp shards heads/hidden within each stage."""
    return {
        "embed": P(), "pos": P(), "head": P(), "ln_f": P(),
        "ln1": P("pp", None),
        "ln2": P("pp", None),
        "wqkv": P("pp", None, None, "tp", None),
        "wo": P("pp", "tp", None, None),
        "w1": P("pp", None, "tp"),
        "w2": P("pp", "tp", None),
    }


def init_stacked_params(cfg: StreamFormerConfig, seed: int = 0
                        ) -> Dict[str, Any]:
    k = jax.random.PRNGKey(seed)
    ks = jax.random.split(k, 8)
    d, h, hd, f, L = cfg.dim, cfg.heads, cfg.head_dim, cfg.mlp, cfg.layers

    def norm(key, shape, scale=0.02):
        return jax.random.normal(key, shape, jnp.float32) * scale

    return {
        "embed": norm(ks[0], (cfg.vocab, d)),
        "pos": norm(ks[1], (cfg.max_seq, d)),
        "head": norm(ks[2], (d, cfg.vocab)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "ln1": jnp.ones((L, d), jnp.float32),
        "ln2": jnp.ones((L, d), jnp.float32),
        "wqkv": norm(ks[3], (L, d, 3, h, hd)),
        "wo": norm(ks[4], (L, h, hd, d)),
        "w1": norm(ks[5], (L, d, f)),
        "w2": norm(ks[6], (L, f, d)),
    }


def _stage_forward(params, x, cfg: StreamFormerConfig):
    """Apply this rank's local layer stack to activations (mb, T_local, D).
    Leading stack axis is the LOCAL pp shard (static size L/pp)."""
    n_local = params["ln1"].shape[0]
    for i in range(n_local):
        y = _ln(x.astype(jnp.float32), params["ln1"][i]).astype(cfg.dtype)
        qkv = jnp.einsum("btd,dchn->btchn", y,
                         params["wqkv"][i].astype(cfg.dtype))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = jax.vmap(
            lambda qq, kk, vv: ring_attention(qq, kk, vv, "sp",
                                              causal=True))(q, k, v)
        o = jnp.einsum("bthn,hnd->btd", attn,
                       params["wo"][i].astype(cfg.dtype))
        o = jax.lax.psum(o, "tp")
        x = x + o
        y = _ln(x.astype(jnp.float32), params["ln2"][i]).astype(cfg.dtype)
        hcore = jax.nn.gelu(jnp.einsum("btd,df->btf", y,
                                       params["w1"][i].astype(cfg.dtype)))
        m = jnp.einsum("btf,fd->btd", hcore,
                       params["w2"][i].astype(cfg.dtype))
        x = x + jax.lax.psum(m, "tp")
    return x


def _pp_loss_local(params, tokens, labels, cfg: StreamFormerConfig,
                   n_stages: int, microbatches: int):
    """GPipe fill-drain loss inside shard_map.

    tokens: (B_local, T_local) int32, B_local = microbatches * mb.
    Returns the global mean NLL (psum over dp/sp/pp)."""
    r = jax.lax.axis_index("pp")
    sp_idx = jax.lax.axis_index("sp")
    B, T = tokens.shape
    mb = B // microbatches
    toks = tokens.reshape(microbatches, mb, T)
    labs = labels.reshape(microbatches, mb, T)
    pos = sp_idx * T + jnp.arange(T)

    def embed(tb):
        return (params["embed"][tb] + params["pos"][pos][None]
                ).astype(cfg.dtype)

    n_ticks = microbatches + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, s):
        x_out, nll_sum, tok_count = carry
        # hand the previous tick's output to the next stage
        x_in = jax.lax.ppermute(x_out, "pp", perm)
        # rank 0 ingests microbatch s (when one remains)
        mb_in = jnp.clip(s, 0, microbatches - 1)
        fresh = embed(toks[mb_in])
        x_in = jnp.where((r == 0) & (s < microbatches), fresh, x_in)
        x_next = _stage_forward(params, x_in, cfg)
        # last rank emits microbatch s-(P-1)'s loss (when valid)
        mb_out = jnp.clip(s - (n_stages - 1), 0, microbatches - 1)
        emit = (r == n_stages - 1) & (s >= n_stages - 1)
        xf = _ln(x_next.astype(jnp.float32), params["ln_f"])
        logits = jnp.einsum("btd,dv->btv", xf, params["head"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, labs[mb_out][..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + jnp.where(emit, jnp.sum(nll), 0.0)
        tok_count = tok_count + jnp.where(emit, nll.size, 0)
        return (x_next, nll_sum, tok_count), None

    x0 = jnp.zeros((mb, T, cfg.dim), cfg.dtype)
    (_, nll_sum, tok_count), _ = jax.lax.scan(
        tick, (x0, jnp.float32(0), jnp.int32(0)), jnp.arange(n_ticks))
    s = jax.lax.psum(nll_sum, ("dp", "sp", "pp"))
    n = jax.lax.psum(tok_count, ("dp", "sp", "pp"))
    return s / n.astype(jnp.float32)


def make_pp_train_step(mesh: Mesh, cfg: Optional[StreamFormerConfig] = None,
                       microbatches: Optional[int] = None, seed: int = 0
                       ) -> Tuple[Any, Dict, Dict, Dict]:
    """Build (jitted_step, sharded_params, sharded_opt, specs) for a mesh
    with a ``pp`` axis (plus any of dp/sp/tp)."""
    cfg = cfg or StreamFormerConfig()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    missing = {"dp", "sp", "tp", "pp"} - set(mesh.axis_names)
    if missing:
        raise ValueError(f"pp mesh must name axes dp/sp/tp/pp (size-1 "
                         f"axes are fine); missing {sorted(missing)}")
    n_stages = sizes.get("pp", 1)
    if cfg.layers % n_stages:
        raise ValueError(f"pp={n_stages} must divide layers={cfg.layers} "
                         "(each stage holds layers/pp consecutive layers)")
    M = microbatches or max(2, n_stages)
    specs = stacked_param_specs()
    params = init_stacked_params(cfg, seed)
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}
    opt_specs = {"m": specs, "v": specs, "step": P()}
    mesh_axes = ("dp", "sp", "tp", "pp")

    def local_step(params, opt, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: _pp_loss_local(p, tokens, labels, cfg, n_stages, M)
        )(params)

        def sync(g, spec):
            used = {ax for part in spec if part
                    for ax in ((part,) if isinstance(part, str) else part)}
            axes = tuple(a for a in mesh_axes if a not in used)
            return jax.lax.psum(g, axes) if axes else g

        grads = jax.tree.map(sync, grads, specs,
                             is_leaf=lambda x: isinstance(x, jnp.ndarray))
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

    data_spec = P("dp", "sp")
    shard_step = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, opt_specs, data_spec, data_spec),
        out_specs=(specs, opt_specs, P()),
        check_vma=False)
    jitted = jax.jit(shard_step, donate_argnums=(0, 1))

    def place(tree, spec_tree):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, spec_tree,
            is_leaf=lambda x: isinstance(x, (jnp.ndarray, np.ndarray)))

    return jitted, place(params, specs), place(opt, opt_specs), specs
