#!/usr/bin/env python3
"""First-run proof on the chip: drive the two serving paths this system
sells, through their normal entry points, on one TPU — and fail if any
of it is wrong.

    python3 chip_smoke.py        (on the chip; no arguments, one mode)

Phases, each a plain function that raises on the first wrong answer:

0. device   — JAX must report a TPU (else exit non-zero before any model
              is built); the native wire library builds and loads; the
              peaks table knows this chip's ``device_kind``.
1. kernels  — the Pallas flash-attention kernels, compiled by Mosaic
              (never interpreted), against naive attention at the shapes
              the package routes to them.
2. stream   — ``parse_launch`` of the flagship MobileNetV2 line, 256
              frames at batch 32; results in order, params on the chip,
              logits against float32 on the host CPU device.
3. llm      — ``tensor_query_serversrc ! tensor_llm !
              tensor_query_serversink`` at the bench LM's full width,
              four concurrent ``TokenStreamClient``s, dense pool then
              paged pool (chunked prefill + prefix cache); then its
              second family (``arch:sambay_lm``) and its third
              (``arch:dsv3_lm``), each at a small size.
4. mesh     — only where four chips are visible: the flagship filter
              with ``custom=mesh:dp=4``.

One process holds the chip; nothing here starts another.  Stdout ends
with two JSON lines: the detail (versions, native library, per-phase
``ok``/``setup_s``/``run_s``, compile cache) and then, last, the verdict
the driver parses — exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``, the device as
JAX reports it.  A failed phase raises, so a failed run prints neither.
``setup_s`` (build + compile + warm-up) and ``run_s`` are smoke timings
of THIS script — they are not fps or tok/s and are recorded nowhere as
such.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from nnstreamer_tpu.utils.platform import (device_label,  # noqa: E402
                                           enable_compile_cache)

#: the bench LM (bench.py bench_lm) at full width: 38.8 M parameters
LM_CUSTOM = ("vocab:8192,dim:512,heads:8,head_dim:64,mlp:2048,layers:4,"
             "experts:2,max_seq:2048,dtype:bfloat16")

#: the second family (arch:sambay_lm: Mamba, window and full differential
#: attention, memory units, cross-attention on one cache) at a small
#: size, every kind of layer: a bring-up fault shows in seconds, not
#: after the 7.7 GB warm-up of benchmarks/configs/phi4_mini_flash.json
HYBRID_CUSTOM = ("arch:sambay_lm,vocab:8192,dim:512,heads:8,kv_heads:4,"
                 "head_dim:64,mlp:2048,layers:8,window:128,d_state:16,"
                 "d_conv:4,expand:2,dt_rank:32,max_seq:2048,"
                 "dtype:bfloat16")

#: the third family (arch:dsv3_lm: latent attention over a cache of
#: latents, 32 sigmoid-routed experts of which this chip holds 8 and
#: computes only the chosen, a shared expert, YaRN) at a small size: the
#: grouped product's kernel, the absorbed decode and the 3-chunk prefill
#: show on a fresh machine before the 8.6 GB warm-up of
#: benchmarks/configs/gigachat31_702b_a36b.json
LATENT_CUSTOM = ("arch:dsv3_lm,vocab:8192,dim:512,heads:8,q_lora_rank:192,"
                 "kv_lora_rank:128,qk_nope_head_dim:64,qk_rope_head_dim:64,"
                 "v_head_dim:96,mlp:1024,expert_mlp:256,experts:32,"
                 "experts_held:8,expert_rank:0,n_group:4,topk_group:2,"
                 "experts_per_tok:4,dense_layers:1,layers:3,"
                 "rope_theta:100000,rope_factor:16,rope_original_max:256,"
                 "max_seq:2048,chunk:512,dtype:bfloat16")

#: flash kernel vs naive float32 attention, bf16 inputs: max abs error of
#: the forward output (values are O(1)), and max error of a gradient
#: relative to the oracle gradient's range
KERNEL_FWD_TOL = 2e-2
KERNEL_GRAD_RTOL = 5e-2
#: MobileNetV2 bf16 on the chip vs float32 on the host CPU device: max
#: abs logit error relative to the float32 logits' range
STREAM_LOGIT_RTOL = 5e-2
#: LM logits (std 0.45 at these random weights) bf16 on the chip vs
#: float32 on the host CPU device: max abs error per position, and the
#: slack within which a served token must sit under the top reference
#: logit of its position.  Top-1 expert routing is discrete — a position
#: whose gate margin is inside bf16 rounding takes the other expert and
#: its logits move by O(1) (1.2 % of positions, bf16 vs float32 on a
#: CPU) — so each tolerance holds for a stated SHARE of positions.
LM_LOGIT_TOL = 6e-2
LM_TOKEN_SLACK = 0.25
LM_MIN_SHARE = 0.9

_compiled = []   # fun_name of every XLA executable built, in order


def _on_jax_event(event, duration, fun_name="", **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiled.append(fun_name)


def _is_tpu(device) -> bool:
    return device.platform == "tpu"


def _on_tpu(arr) -> bool:
    return all(_is_tpu(d) for d in arr.devices())


def _all_on_tpu(tree) -> bool:
    import jax

    return all(_on_tpu(x) for x in jax.tree_util.tree_leaves(tree))


# -- phase 0 ------------------------------------------------------------------

def phase_device() -> dict:
    import jax
    import jaxlib

    from nnstreamer_tpu import native
    from nnstreamer_tpu.obs.attrib import device_peaks

    label = device_label()
    if label["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {label['platform']!r} "
                 f"({label['device_count']} x {label['device_kind']}), "
                 "not a tpu; no model was built")
    if not native.available():
        sys.exit("chip_smoke: native/libnnstw.so did not build or load "
                 "(make -C native)")
    device_peaks(jax.devices()[0])   # LookupError: kind not in the table
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:   # a label, not a phase
        libtpu = "unknown"
    return {"device": {"platform": label["platform"],
                       "kind": label["device_kind"],
                       "count": label["device_count"]},
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu,
                         "python": sys.version.split()[0]},
            "native": "libnnstw.so"}


# -- phase 1 ------------------------------------------------------------------

def _mosaic(fn, *args) -> bool:
    """Whether ``fn`` lowers to a Mosaic custom call for these operands
    (an interpreted pallas_call lowers to plain HLO instead)."""
    return "tpu_custom_call" in fn.lower(*args).as_text()


def phase_kernels(t: int = 2048, h: int = 8, d: int = 64,
                  odd_t: int = 197) -> dict:
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.flash_attention import (flash_attention,
                                                    flash_is_default)
    from nnstreamer_tpu.parallel.ring_attention import local_attention

    t0 = time.monotonic()
    assert flash_is_default(), "package default would interpret the kernel"
    rng = np.random.default_rng(0)

    def qkv(n):
        return [jnp.asarray(rng.standard_normal((n, h, d)), jnp.bfloat16)
                for _ in range(3)]

    def naive(q, k, v, causal):
        with jax.default_matmul_precision("highest"):
            return local_attention(*(x.astype(jnp.float32)
                                     for x in (q, k, v)), causal=causal)

    def lse_naive(q, k, causal):
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) / np.sqrt(d)
        if causal:
            pos = jnp.arange(q.shape[0])
            s = jnp.where(pos[None, None, :] > pos[None, :, None],
                          -jnp.inf, s)
        return jax.nn.logsumexp(s, axis=-1)

    out = {}
    # forward, the LM prefill shape (causal) and the odd-length pad path
    for name, n, causal in (("fwd_causal", t, True),
                            ("fwd_odd", odd_t, False)):
        q, k, v = qkv(n)
        fn = jax.jit(lambda q, k, v, c=causal: flash_attention(
            q, k, v, causal=c))
        assert _mosaic(fn, q, k, v), f"{name}: no Mosaic call"
        err = float(jnp.max(jnp.abs(fn(q, k, v).astype(jnp.float32)
                                    - naive(q, k, v, causal))))
        assert err < KERNEL_FWD_TOL, (name, err)
        out[name] = {"T": n, "max_abs_err": round(err, 5)}
    # forward with lse, non-causal: the ring-attention block
    q, k, v = qkv(t)
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                 return_lse=True))
    assert _mosaic(fn, q, k, v), "fwd_lse: no Mosaic call"
    o, lse = fn(q, k, v)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - naive(q, k, v, False))))
    lse_err = float(jnp.max(jnp.abs(lse - lse_naive(q, k, False))))
    assert err < KERNEL_FWD_TOL and lse_err < KERNEL_FWD_TOL, (err,
                                                               lse_err)
    out["fwd_lse"] = {"T": t, "max_abs_err": round(err, 5),
                      "lse_max_abs_err": round(lse_err, 5)}
    # the dq / dkv backward pair at the prefill shape
    q, k, v = qkv(t)
    g_flash = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True).astype(jnp.float32) ** 2), (0, 1, 2)))
    assert _mosaic(g_flash, q, k, v), "bwd: no Mosaic call"
    g_naive = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        naive(q, k, v, True) ** 2), (0, 1, 2)))
    gf, gn = g_flash(q, k, v), g_naive(q, k, v)
    ref = max(float(jnp.max(jnp.abs(b))) for b in gn)
    rel = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(gf, gn)) / ref
    assert rel < KERNEL_GRAD_RTOL, ("bwd", rel)
    out["bwd_causal"] = {"T": t, "max_rel_grad_err": round(rel, 5)}
    return {"ok": True, "setup_s": round(time.monotonic() - t0, 2),
            "run_s": 0.0, "checks": out}


# -- phase 2 ------------------------------------------------------------------

def _source(n_frames: int) -> str:
    return (f"videotestsrc num-buffers={n_frames} pattern=random "
            "cache-frames=64 ! "
            "video/x-raw,format=RGB,width=224,height=224,framerate=120/1 ! "
            "tensor_converter ! ")


def _flagship_line(n_frames: int, batch: int, custom: str) -> str:
    return (_source(n_frames) +
            "tensor_filter framework=xla model=mobilenet_v2 "
            f"custom={custom} batch={batch} name=f ! "
            f"queue max-size-buffers={2 * batch} ! "
            "tensor_decoder mode=image_labeling ! tensor_sink name=out")


def _run_flagship(n_frames: int, batch: int, custom: str):
    """Run the flagship line to EOS.  Returns (pipeline — stopped by the
    caller —, label index per frame, setup_s to the first result,
    run_s for the rest)."""
    from nnstreamer_tpu import parse_launch

    t0 = time.monotonic()
    stamps = []
    p = parse_launch(_flagship_line(n_frames, batch, custom))
    p.get("out").connect("new-data",
                         lambda buf: stamps.append(time.monotonic()))
    p.play()
    p.wait(timeout=900)
    results = p.get("out").results
    assert len(results) == n_frames, (len(results), n_frames)
    step = results[1].pts - results[0].pts
    assert [r.pts for r in results] == [i * step for i in
                                        range(n_frames)], "out of order"
    index = [int(r.extra["index"]) for r in results]
    return p, index, stamps[0] - t0, stamps[-1] - stamps[0]


def _source_frames(n: int):
    """The first ``n`` frames the flagship line's source emits."""
    from nnstreamer_tpu import parse_launch

    p = parse_launch(_source(n) + "tensor_sink name=out")
    p.run(timeout=120)
    return [np.array(r.np(0)).reshape(224, 224, 3)
            for r in p.get("out").results]


def phase_stream(n_frames: int = 256, batch: int = 32,
                 n_check: int = 4) -> dict:
    import jax

    from nnstreamer_tpu.models.registry import get_model

    p, index, setup_s, run_s = _run_flagship(n_frames, batch, "seed:0")
    try:
        fw = p.get("f").fw
        assert _is_tpu(fw._device), fw._device
        assert _all_on_tpu(fw._params_dev), "a parameter leaf is off-chip"
        frames = _source_frames(n_check)
        chip = jax.jit(fw._model.forward)
        got = np.stack([np.asarray(chip(fw._params_dev, f)[0], np.float32)
                        for f in frames])
    finally:
        p.stop()
    assert np.isfinite(got).all() and got.shape == (n_check, 1001)
    # the same model and weights in float32 on the host CPU device
    ref_model = get_model("mobilenet_v2", {"seed": "0",
                                           "dtype": "float32"})
    with jax.default_device(jax.devices("cpu")[0]):
        host = jax.jit(ref_model.forward)
        want = np.stack([np.asarray(host(ref_model.params, f)[0])
                         for f in frames])
    span = float(want.max() - want.min())
    rel = float(np.abs(got - want).max()) / span
    assert rel < STREAM_LOGIT_RTOL, ("logits vs float32", rel)
    # what the pipeline itself answered for those frames is a top logit
    # of the float32 reference (within the same tolerance)
    for i in range(n_check):
        assert 0 <= index[i] < 1001
        assert want[i, index[i]] >= want[i].max() - STREAM_LOGIT_RTOL * span
    return {"ok": True, "setup_s": round(setup_s, 2),
            "run_s": round(run_s, 2), "frames": n_frames, "batch": batch,
            "logit_rel_err_vs_f32_cpu": round(rel, 5)}


# -- phase 3 ------------------------------------------------------------------

def _serve(port: int, jobs, frame_len: int):
    """One TokenStreamClient per job, all concurrent.  Returns the token
    list per job; a client error fails the phase."""
    from nnstreamer_tpu.llm.client import TokenStreamClient

    results, errors = {}, {}

    def run(i):
        cli = TokenStreamClient("127.0.0.1", port, timeout=300.0)
        try:
            cli.connect()
            prompt, max_new = jobs[i]
            results[i] = cli.generate(prompt, max_new,
                                      frame_len=frame_len)
        except Exception as exc:  # noqa: BLE001 — re-raised below
            errors[i] = exc
        finally:
            cli.close()

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(jobs))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
    assert not any(th.is_alive() for th in threads), "a client hung"
    if errors:
        raise next(iter(errors.values()))
    return [results[i] for i in range(len(jobs))]


def _check_streams(params, cfg, jobs, streams, forward_logits) -> dict:
    """Every stream is exactly its granted length of in-vocabulary
    tokens, and — teacher-forced through the family's
    ``forward_logits`` on the same weights — at least LM_MIN_SHARE of
    the served tokens sit within LM_TOKEN_SLACK of the top logit of
    their position."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.llm.engine import quantize_prompt

    fwd = jax.jit(lambda p, t: forward_logits(p, t, cfg))
    exact = near = total = 0
    for (prompt, max_new), toks in zip(jobs, streams):
        assert len(toks) == max_new, (len(toks), max_new)
        assert all(0 <= t < cfg.vocab for t in toks), toks
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        buf = np.zeros((quantize_prompt(len(seq), cfg.max_seq),),
                       np.int32)
        buf[:len(seq)] = seq
        logits = np.asarray(fwd(params, jnp.asarray(buf)))
        rows = logits[len(prompt) - 1:len(seq)]
        assert np.isfinite(rows).all()
        served = rows[np.arange(max_new), toks]
        near += int((served >= rows.max(axis=1) - LM_TOKEN_SLACK).sum())
        exact += int((rows.argmax(axis=1) == np.asarray(toks)).sum())
        total += max_new
    assert near >= LM_MIN_SHARE * total, (
        "served tokens are not top logits of their positions", near,
        total)
    return {"tokens_served": total, "tokens_near_top_logit": near,
            "tokens_exact_argmax": exact}


def _logits_vs_f32_cpu(params, cfg, t: int) -> dict:
    """``forward_logits`` at length ``t``: the serving dtype on the
    default device against float32 on the host CPU device — at least
    LM_MIN_SHARE of the positions within LM_LOGIT_TOL."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.streamformer_lm import forward_logits

    toks = np.random.default_rng(1).integers(
        0, cfg.vocab, (t,)).astype(np.int32)
    got = np.asarray(jax.jit(lambda p, x: forward_logits(p, x, cfg))(
        params, jnp.asarray(toks)))
    cfg32 = dataclasses.replace(cfg, dtype=jnp.dtype("float32"))
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(jax.jit(lambda p, x: forward_logits(
            p, x, cfg32, flash=False))(jax.device_get(params), toks))
    assert got.shape == want.shape == (t, cfg.vocab)
    assert np.isfinite(got).all()
    err = np.abs(got - want).max(axis=1)
    share = float((err < LM_LOGIT_TOL).mean())
    assert share >= LM_MIN_SHARE, ("logits vs float32", share)
    return {"logit_err_median_vs_f32_cpu": round(float(np.median(err)), 5),
            "logit_positions_within_tol": round(share, 4)}


def phase_llm(sid: int, custom: str = LM_CUSTOM, slots: int = 8,
              batch: int = 8, prompt_lens=(16, 200, 1100, 180),
              max_new: int = 32, page_size: int = 0,
              shared_prefix: int = 0, mosaic_bucket: int = 0,
              logits_t: int = 0) -> dict:
    """Serve ``prompt_lens`` concurrently over the query wire.  Sizes
    are arguments so a toy rehearsal can call this; ``mosaic_bucket``
    names the dense prefill bucket that must hold the Mosaic call and
    ``logits_t`` the length of the float32-CPU logits comparison (0 =
    neither applies at this size)."""
    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.filter.framework import FilterProperties
    from nnstreamer_tpu.llm.element import REQ_HEADER
    from nnstreamer_tpu.llm.family import FAMILIES, family_of_custom
    from nnstreamer_tpu.query.server import peek_server, shutdown_server

    import importlib

    import jax.numpy as jnp

    family, own = family_of_custom(FilterProperties.parse_custom(custom))
    cfg = family.config_from_custom(own)
    forward_logits = importlib.import_module(
        FAMILIES[family.name]).forward_logits
    frame_len = REQ_HEADER + cfg.max_seq
    paged = f" page-size={page_size}" if page_size else ""
    t0 = time.monotonic()
    p = parse_launch(
        f"tensor_query_serversrc name=qsrc id={sid} port=0 "
        "caps=other/tensors,format=static,num_tensors=1,"
        f"dimensions={frame_len},types=int32,framerate=0/1 ! "
        f"tensor_llm name=llm custom={custom} seed=0 slots={slots} "
        f"batch={batch} max-new-tokens={max_new}{paged} id={sid} ! "
        f"tensor_query_serversink id={sid}")
    p.play()                       # tensor_llm.start() runs the warm-up
    setup_s = time.monotonic() - t0
    try:
        llm = p.get("llm")
        eng, pool = llm.engine, llm.pool
        assert _all_on_tpu(eng.params), "a parameter leaf is off-chip"
        assert all(_on_tpu(a) for a in pool.arrays), "pool is off-chip"
        out = {"ok": True, "setup_s": round(setup_s, 2),
               "warm_executables": eng.compiles}
        if mosaic_bucket:
            assert _mosaic(eng._prefill_jit[mosaic_bucket], eng.params,
                           pool.arrays, eng._sampled,
                           jnp.zeros((mosaic_bucket,), jnp.int32),
                           jnp.int32(0), jnp.int32(1)), (
                f"{mosaic_bucket}-bucket prefill holds no Mosaic call")
            out["mosaic_prefill_bucket"] = mosaic_bucket
        rng = np.random.default_rng(7)
        jobs = [(rng.integers(0, cfg.vocab, n).astype(np.int32), max_new)
                for n in prompt_lens]
        warm = (eng.compiles, len(_compiled))
        t1 = time.monotonic()
        port = p.get("qsrc").bound_port
        streams = _serve(port, jobs, frame_len)
        if shared_prefix:
            # two requests sharing a prefix, one after the other: the
            # second finds the first's full prompt pages registered
            head = rng.integers(0, cfg.vocab,
                                shared_prefix).astype(np.int32)
            for tail in (20, 30):
                job = (np.concatenate([head, rng.integers(
                    0, cfg.vocab, tail).astype(np.int32)]), max_new)
                jobs.append(job)
                streams += _serve(port, [job], frame_len)
        out["run_s"] = round(time.monotonic() - t1, 2)
        assert (eng.compiles, len(_compiled)) == warm, (
            "compiled after warm-up", eng.compiles - warm[0],
            _compiled[warm[1]:])
        # the decode thread releases a session just after pushing its
        # last frame, so the client can get here first
        deadline = time.monotonic() + 10.0
        while pool.live and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.live == 0, "a session outlived its stream"
        if page_size:
            assert pool.prefix_hits >= 1, "shared prefix missed"
            assert pool.free_pages == pool.pages, "pages leaked"
            out.update(prefix_hits=pool.prefix_hits,
                       prefix_tokens_reused=pool.prefix_tokens_reused,
                       prefill_chunks=eng.prefill_chunks_total)
        # every stream's in-flight unit closed: each ended on a
        # terminal frame
        assert peek_server(sid).drain(10.0), "a stream has no terminal"
        out.update(sessions=llm.sessions_total, streams=streams,
                   **_check_streams(eng.params, cfg, jobs, streams,
                                    forward_logits))
        if logits_t:
            out.update(_logits_vs_f32_cpu(eng.params, cfg, logits_t))
    finally:
        p.stop()
        shutdown_server(sid)
    return out


# -- phase 4 ------------------------------------------------------------------

def phase_mesh(dp: int = 4, n_frames: int = 128, batch: int = 32) -> dict:
    """Guard that ``custom=mesh:dp=N`` really spans N chips and nothing
    lands everything on device 0 unnoticed: the flagship line runs with
    it, its batched output is sharded over all N devices, and the
    sharded executable's logits equal the single-device one's."""
    import jax

    from nnstreamer_tpu.filter.single import FilterSingle

    n = len(jax.devices())
    if n < dp:
        return {"skipped": f"{n} device"}
    mesh = f"seed:0,mesh:dp={dp}"
    p, _, setup_s, run_s = _run_flagship(n_frames, batch, mesh)
    p.stop()
    frames = [[f] for f in _source_frames(batch)]
    with FilterSingle(framework="xla", model="mobilenet_v2",
                      custom=mesh) as sharded:
        handle = sharded.fw.invoke_batched(frames, bucket=batch)
        spanned = len(handle._outs[0].sharding.device_set)
        got = np.stack([np.asarray(o[0], np.float32)
                        for o in handle.wait()])
    assert spanned == dp, f"batched output spans {spanned} device(s)"
    with FilterSingle(framework="xla", model="mobilenet_v2",
                      custom="seed:0") as single:
        want = np.stack([np.asarray(o[0], np.float32) for o in
                         single.fw.invoke_batched(frames,
                                                  bucket=batch).wait()])
    # same math, same dtype; an 8-row shard and a 32-row batch may tile
    # differently, so equal within the bf16 tolerance, not bitwise
    rel = float(np.abs(got - want).max()) / float(want.max() - want.min())
    assert rel < STREAM_LOGIT_RTOL, ("sharded vs single device", rel)
    return {"ok": True, "setup_s": round(setup_s, 2),
            "run_s": round(run_s, 2), "dp": dp, "frames": n_frames,
            "devices_spanned": spanned,
            "logit_rel_err_vs_single_device": round(rel, 6)}


# -- driver -------------------------------------------------------------------

def main() -> int:
    import jax

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
    summary = phase_device()
    phases = {"kernels": phase_kernels(), "stream": phase_stream()}
    dense = phase_llm(4621, mosaic_bucket=2048, logits_t=256)
    paged = phase_llm(4622, page_size=16, shared_prefix=64)
    hybrid = phase_llm(4623, custom=HYBRID_CUSTOM)
    hybrid.pop("streams")
    latent = phase_llm(4624, custom=LATENT_CUSTOM)
    latent.pop("streams")
    # the two prefill paths round differently in bf16: reported, not
    # asserted
    pairs = [(a, b) for da, pa in zip(dense.pop("streams"),
                                      paged.pop("streams"))
             for a, b in zip(da, pa)]
    paged["tokens_equal_dense"] = (
        f"{sum(a == b for a, b in pairs)}/{len(pairs)}")
    phases.update(llm_dense=dense, llm_paged=paged, llm_hybrid=hybrid,
                  llm_latent=latent, mesh=phase_mesh())
    summary.update(
        phases=phases,
        setup_s_total=round(sum(ph.get("setup_s", 0.0)
                                for ph in phases.values()), 2),
        executables_built=len(_compiled),
        cache={"dir": cache, "entries": len(os.listdir(cache))
               if os.path.isdir(cache) else 0},
        note="setup_s/run_s are smoke timings, not fps or tok/s")
    print(json.dumps(summary), flush=True)
    # the verdict line: these keys and no others (the driver's contract)
    print(json.dumps({"ok": True, "device": summary["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
