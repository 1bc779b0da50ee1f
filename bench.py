#!/usr/bin/env python
"""Benchmarks for the BASELINE.md configs on the chip.

The default invocation benches the flagship MobileNetV2 224x224
image-labeling pipeline (BASELINE config 1) and prints ONE JSON line:
  {"metric": ..., "value": fps, "unit": "fps", "vs_baseline": fps/30,
   "platform": "tpu", "device_kind": ..., "device_count": ..., ...}

A chip belongs to one process, so each configuration is measured in ONE
child process, one after another, and this parent never touches JAX.  A
row is a measurement or a failure, never both: a child that exits
non-zero, prints no row or overruns its deadline yields an ``error`` row
and a non-zero exit code for the whole run.  The measurement path fails
when JAX finds no TPU; ``--cpu`` says so explicitly and renames every
metric ``..._cpu`` (a CPU number is a correctness smoke, not a speed).

Extra measurements per model config: p50 single-invoke latency, model FLOPs
(XLA cost analysis), streaming MFU, and a vmap-batched invoke mode
(batched_fps / batched_mfu) showing MXU utilization past the
one-frame-per-dispatch streaming bound.

Usage:
  python bench.py                      # flagship (config 1), TPU
  python bench.py --config resident    # flagship w/ HBM-resident frames
  python bench.py --config ssd         # SSD-MobileNetV2 + bounding_boxes
  python bench.py --config deeplab     # DeepLabV3 + image_segment
  python bench.py --config posenet     # PoseNet + pose_estimation
  python bench.py --config edge        # distributed edge_sink -> edge_src
  python bench.py --config lm          # StreamFormer LM prefill + decode
  python bench.py --all                # every config, one JSON line each
  python bench.py --cpu                # correctness smoke on the host CPU
Env: NNS_TPU_BENCH_DEADLINE (s per config, default 480),
     NNS_TPU_BENCH_FRAMES (default 1920).
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

import numpy as np  # noqa: E402

#: streaming micro-batch for tensor_filter (1 = per-frame dispatch);
#: coalesces frames into one device invoke, double-buffered (round-3 path)
STREAM_BATCH = int(os.environ.get("NNS_TPU_BENCH_BATCH", "32"))
#: dispatched-batch queue depth (tensor_filter inflight=): 1 keeps the
#: historical double-buffering.  The device-resident config deepens it
#: on TPU (run_child): with no per-frame upload its throughput is bound
#: by dispatch pipelining, so it overlaps K dispatches
INFLIGHT = int(os.environ.get("NNS_TPU_BENCH_INFLIGHT", "1"))
#: dispatch-queue depth the device-resident TPU config runs by default
RESIDENT_INFLIGHT = 8
N_FRAMES = int(os.environ.get("NNS_TPU_BENCH_FRAMES",
                              str(max(1920, 30 * STREAM_BATCH))
                              if STREAM_BATCH > 1 else "150"))
BASELINE_FPS = 30.0  # north-star target (BASELINE.json)
BATCH = 64           # vmap-batched invoke mode
# per-chip bf16 peak FLOP/s and HBM bandwidth for MFU/roofline: the ONE
# source is obs/attrib.py — the live nns_mfu gauge and these BENCH rows
# compute MFU from the same table and the same lookup, so the two
# surfaces cannot drift apart.
from nnstreamer_tpu.obs.attrib import device_peaks  # noqa: E402

CONFIG_METRICS = {
    "mobilenet": "mobilenet_v2_224_image_labeling_e2e_fps",
    "resident": "mobilenet_v2_224_device_resident_e2e_fps",
    "ssd": "ssd_mobilenet_v2_300_bounding_boxes_e2e_fps",
    "deeplab": "deeplab_v3_257_image_segment_e2e_fps",
    "posenet": "posenet_257_pose_estimation_e2e_fps",
    "edge": "mobilenet_v2_edge_distributed_e2e_fps",
    "vit": "vit_s16_224_image_labeling_e2e_fps",
    "lm": "streamformer_lm_serving",
}

#: configs whose pipeline honors NNS_TPU_BENCH_NO_PUSHDOWN (the
#: _model_pipeline decoder toggle) — only these may carry the
#: _host_decode metric suffix; edge/lm pipelines ignore the env var
PUSHDOWN_CONFIGS = frozenset(
    {"mobilenet", "resident", "ssd", "deeplab", "posenet", "vit"})


def _no_pushdown() -> bool:
    """The ONE reading of NNS_TPU_BENCH_NO_PUSHDOWN (metric naming and
    pipeline construction must never diverge)."""
    from nnstreamer_tpu.utils.conf import parse_bool

    return parse_bool(os.environ.get("NNS_TPU_BENCH_NO_PUSHDOWN", ""))


def _pd_suffix(config: str) -> str:
    return ("_host_decode"
            if _no_pushdown() and config in PUSHDOWN_CONFIGS else "")


# ---------------------------------------------------------------------------
# child: the actual measurement (runs under a parent-enforced deadline)
# ---------------------------------------------------------------------------

def _measure(pipeline, sink_name: str, timeout: float = 1200,
             feeders=()):
    """Run a pipeline (plus optional feeder pipelines), return
    steady-state fps from sink timestamps."""
    stamps = []
    pipeline.get(sink_name).connect(
        "new-data", lambda buf: stamps.append(time.monotonic()))
    pipeline.play()
    for f in feeders:
        f.play()
    for f in feeders:
        f.wait(timeout=timeout)
    pipeline.wait(timeout=timeout)
    n = len(stamps)
    if n < 2:
        raise SystemExit("benchmark produced no frames")
    # skip pipeline ramp: with micro-batching the first batches carry the
    # dispatch-queue fill ((1 + inflight depth) batches), so skip at
    # least that many batches' worth
    required = max(10, (1 + _effective_inflight()) * STREAM_BATCH)
    skip = min(required, n // 3)
    if skip < required:
        # ramp frames leak into the average, understating fps — scale
        # NNS_TPU_BENCH_FRAMES with a deepened queue (run_child does
        # this for the resident config; env-forced depths must too)
        print(f"bench: warning: {required - skip} dispatch-queue ramp "
              f"frames inside the measured window (frames={n} too few "
              f"for inflight={_effective_inflight()} at "
              f"batch={STREAM_BATCH})", file=sys.stderr)
    span = stamps[-1] - stamps[skip]
    return ((n - 1 - skip) / span if span > 0 else 0.0), n


def _model_pipeline(model: str, size: int, decoder: str, dtype_prop: str,
                    decoder_opts: str = "", src_cache: str = "cache-frames",
                    n_frames: int = 0) -> str:
    from nnstreamer_tpu import parse_launch

    return parse_launch(
        f"videotestsrc num-buffers={n_frames or N_FRAMES} pattern=random "
        f"{src_cache}=64 ! "
        f"video/x-raw,format=RGB,width={size},height={size},"
        "framerate=120/1 ! "
        "tensor_converter ! "
        f"tensor_filter framework=xla model={model}"
        f" custom=seed:0{dtype_prop} batch={STREAM_BATCH} "
        f"inflight={INFLIGHT} name=f ! "
        # queue = thread boundary: decoding a pushed batch overlaps the
        # dispatch + async d2h of the queued batches (depth = inflight)
        f"queue max-size-buffers={max(8, (1 + INFLIGHT) * STREAM_BATCH)} ! "
        f"tensor_decoder mode={decoder} {decoder_opts}"
        # NNS_TPU_BENCH_NO_PUSHDOWN=1: host decode path, so the capture
        # loop can measure the device-fused decode tail's fps DELTA
        f"{' pushdown=false' if _no_pushdown() else ''} ! "
        "tensor_sink name=out")


def _invoke_p50(fw, size: int) -> float:
    import jax

    frame = np.random.default_rng(0).integers(
        0, 255, (size, size, 3), dtype=np.uint8)
    lats = []
    for _ in range(30):
        t0 = time.monotonic()
        jax.block_until_ready(fw.invoke([frame]))
        lats.append((time.monotonic() - t0) * 1000)
    lats.sort()
    return lats[len(lats) // 2]


def _model_cost(model):
    """Per-frame (flops, bytes_accessed) from XLA cost analysis."""
    import jax

    zeros = [np.zeros(i.np_shape, i.np_dtype) for i in model.in_info]
    cost = jax.jit(model.forward).lower(
        model.params, *zeros).compile().cost_analysis()
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)))


def _peaks(device):
    """(peak FLOP/s, peak bytes/s) of ``device``; (0, 0) on the host CPU
    (the ``--cpu`` smoke makes no utilization claim).  Any other device
    the peaks table does not know is an error (obs/attrib.py)."""
    return (0.0, 0.0) if device.platform == "cpu" else device_peaks(device)


def _batched_profile(model, device, size: int, batch: int = BATCH):
    """(fps, flops_per_frame, bytes_per_frame) of the vmap-batched
    executable — ONE XLA compile serves both the timing and the cost
    analysis.  The throughput is the MXU-utilization number the
    one-frame-per-dispatch streaming path can't show; the batch-amortized
    bytes (params read from HBM once per batch) are what decide the
    batched roofline position."""
    import jax

    batched = jax.vmap(model.forward, in_axes=(None, 0))
    params = jax.device_put(model.params, device)
    frames = np.random.default_rng(0).integers(
        0, 255, (batch, size, size, 3), dtype=np.uint8)
    frames = jax.device_put(frames, device)
    compiled = jax.jit(batched).lower(params, frames).compile()
    jax.block_until_ready(compiled(params, frames))  # warm
    # pick reps so the timed window is ~2s: a handful of reps is mostly
    # dispatch latency and understates the executable
    t0 = time.monotonic()
    jax.block_until_ready(compiled(params, frames))
    once = max(time.monotonic() - t0, 1e-4)
    reps = int(min(max(2.0 / once, 5), 50))
    t0 = time.monotonic()
    for _ in range(reps):
        out = compiled(params, frames)
    jax.block_until_ready(out)
    fps = reps * batch / (time.monotonic() - t0)
    cost = compiled.cost_analysis()
    return (fps, float(cost.get("flops", 0.0)) / batch,
            float(cost.get("bytes accessed", 0.0)) / batch)


def _effective_inflight(pipeline=None) -> int:
    """Depth the element actually runs — a row must never describe a
    configuration that wasn't run.  Reads the started element's own
    clamped depth when a pipeline is at hand; the fallback mirrors the
    element's rule (inflight>1 needs micro-batching, floor 1)."""
    if pipeline is not None:
        f = pipeline.get("f")
        depth = getattr(f, "_inflight_depth", None)
        if depth is not None:
            return int(depth)
    return max(1, INFLIGHT) if STREAM_BATCH > 1 else 1


def _trace_breakdown(model_name, size, decoder, dtype_prop,
                     decoder_opts, src_cache) -> "tuple[dict, dict]":
    """Per-element proctime/interlatency breakdown plus the wait-state
    attribution summary, from ONE short traced pass — a separate run so
    the headline fps numbers stay untraced (fused plans with zero
    tracer references).  Attached to BENCH rows as ``trace`` and
    ``attribution``, so artifacts carry where the time went (and which
    STATE ate it — the rows a batching PR must shrink), not just the
    end-to-end fps."""
    from nnstreamer_tpu.obs.profile import Profiler, compact_blame

    p = _model_pipeline(model_name, size, decoder, dtype_prop,
                        decoder_opts, src_cache,
                        n_frames=max(30, min(N_FRAMES, 120)))
    prof = Profiler(p, register_gauges=False)
    tracer = p.tracer
    try:
        p.run(timeout=300)
        report = prof.report(metrics_report={}, top_n=5)
    finally:
        prof.close()
        p.stop()
    keep = ("buffers", "proctime_avg_us", "proctime_p50_us",
            "proctime_p95_us", "proctime_p99_us", "fps",
            "interlatency_avg_us", "interlatency_p99_us")
    trace = {el: {k: v for k, v in row.items() if k in keep}
             for el, row in tracer.report().items()}
    return trace, compact_blame(report["blame"])


def bench_model(name: str, model_name: str, size: int, decoder: str,
                dtype_prop: str, decoder_opts: str = "",
                src_cache: str = "cache-frames") -> dict:
    p = _model_pipeline(model_name, size, decoder, dtype_prop, decoder_opts,
                        src_cache)
    try:
        fps1, n = _measure(p, "out")
    finally:
        p.stop()
    # stability pass: a second full pipeline run (fresh elements, warm
    # XLA compile cache); both runs are recorded and the SLOWER one is
    # the headline value
    p = _model_pipeline(model_name, size, decoder, dtype_prop, decoder_opts,
                        src_cache)
    try:
        fps2, _ = _measure(p, "out")
        fps = min(fps1, fps2)
        fw = p.get("f").fw
        p50 = _invoke_p50(fw, size)
        out = {"metric": name, "value": round(fps, 2), "unit": "fps",
               "vs_baseline": round(fps / BASELINE_FPS, 3),
               "fps_run1": round(fps1, 2), "fps_run2": round(fps2, 2),
               "p50_invoke_ms": round(p50, 3), "frames": n,
               "stream_batch": STREAM_BATCH,
               "inflight": _effective_inflight(p)}
        from nnstreamer_tpu.utils.conf import parse_bool

        if parse_bool(os.environ.get("NNS_TPU_BENCH_TRACE", "1")):
            out["trace"], out["attribution"] = _trace_breakdown(
                model_name, size, decoder, dtype_prop, decoder_opts,
                src_cache)
        model = fw._model
        device = fw._device
        peak, bw = _peaks(device)
        flops, bytes_acc = _model_cost(model)
        bfps, bflops, bbytes = _batched_profile(model, device, size)
        bfps_big = 0.0
        if device.platform != "cpu":
            # a second point for the batch-tuning curve (TPU only —
            # batch-256 convs take minutes on host CPU)
            bfps_big, _, _ = _batched_profile(model, device, size,
                                              batch=256)
    finally:
        p.stop()
    if flops:
        out["gflops_per_frame"] = round(flops / 1e9, 3)
        if peak:
            out["mfu_stream"] = round(fps * flops / peak, 6)
            out["mfu_batched"] = round(bfps * flops / peak, 6)
        if bytes_acc and peak and bw:
            # roofline: per-frame arithmetic intensity vs the machine
            # balance decides the bound; the implied fps ceiling is the
            # binding resource's rate (single frame, no batching)
            intensity = flops / bytes_acc
            balance = peak / bw
            out["bytes_per_frame"] = round(bytes_acc)
            out["arith_intensity"] = round(intensity, 2)
            out["roofline_bound"] = ("memory" if intensity < balance
                                     else "compute")
            out["roofline_fps"] = round(min(peak / flops,
                                            bw / bytes_acc), 1)
    out["batched_fps"] = round(bfps, 2)
    out["batch"] = BATCH
    if bflops and bbytes and peak and bw:
        out.update(_batched_roofline_fields(bfps, bflops, bbytes,
                                            peak, bw))
    if bfps_big:
        out["batched_fps_256"] = round(bfps_big, 2)
        if flops and peak:
            out["mfu_batched_256"] = round(bfps_big * flops / peak, 6)
    return out


def _batched_roofline_fields(bfps, bflops, bbytes, peak, bw) -> dict:
    """Roofline position of the BATCHED executable: params are read once
    per batch, so intensity is far above the single-frame number — this
    is the ceiling mfu_batched is honestly measured against.  A
    measured fraction ABOVE 1 means XLA's "bytes accessed"
    estimate overcounted the real HBM traffic (it sums post-fusion
    operand/output bytes; attention-heavy graphs like vit keep more of
    that in VMEM than the model assumes) — such rows carry a note
    marking the ceiling conservative rather than silently publishing
    frac>1."""
    bint = bflops / bbytes
    ceiling = min(peak / bflops, bw / bbytes)
    fields = {
        "batched_arith_intensity": round(bint, 2),
        "batched_roofline_bound": ("memory" if bint < peak / bw
                                   else "compute"),
        "batched_roofline_fps": round(ceiling, 1),
        "batched_roofline_frac": round(bfps / ceiling, 4),
    }
    if fields["batched_roofline_frac"] > 1:
        fields["batched_roofline_note"] = (
            "frac>1: cost-analysis bytes overcount (ceiling "
            "conservative)")
    return fields


def _edge_pass(dtype_prop: str):
    """One full dual-pipeline edge pass (fresh broker + both pipelines)."""
    from nnstreamer_tpu import parse_launch
    from nnstreamer_tpu.query.edge import get_broker

    broker = get_broker()
    try:
        recv = parse_launch(
            f"edge_src port={broker.port} topic=bench "
            f"num-buffers={N_FRAMES} ! "
            "tensor_filter framework=xla model=mobilenet_v2"
            f" custom=seed:0{dtype_prop} batch={STREAM_BATCH} name=f ! "
            f"queue max-size-buffers={max(8, 2 * STREAM_BATCH)} ! "
            "tensor_decoder mode=image_labeling ! tensor_sink name=out")
        send = parse_launch(
            f"videotestsrc num-buffers={N_FRAMES} pattern=random "
            "cache-frames=64 ! "
            "video/x-raw,format=RGB,width=224,height=224,framerate=120/1 ! "
            "tensor_converter ! "
            f"edge_sink port={broker.port} topic=bench")
        try:
            return _measure(recv, "out", feeders=(send,))
        finally:
            send.stop()
            recv.stop()
    finally:
        broker.close()


def bench_edge(dtype_prop: str) -> dict:
    """BASELINE config 5: distributed pipeline over the edge transport
    (sender and receiver as two pipelines through the TCP broker — the
    localhost twin of the reference's 2-host query/edge tests).  Two
    full passes, headline = the slower (same stability policy as every
    other config; this row was single-pass through round 4's first
    capture)."""
    from nnstreamer_tpu import parse_launch

    fps1, n1 = _edge_pass(dtype_prop)
    fps2, n2 = _edge_pass(dtype_prop)
    fps, n = min((fps1, n1), (fps2, n2))  # frames from the headline run
    out = {"metric": "mobilenet_v2_edge_distributed_e2e_fps",
           "value": round(fps, 2), "unit": "fps",
           "vs_baseline": round(fps / BASELINE_FPS, 3), "frames": n,
           "fps_run1": round(fps1, 2), "fps_run2": round(fps2, 2)}
    # supplementary: the same dual-pipeline config over the net-new
    # shared-memory ring (query/shm.py) — what co-located pipelines get
    # when they skip the socket path.  Headline stays the TCP number
    # (that's the reference-parity transport).
    ring = f"nns-bench-{os.getpid()}"
    # prefetch=1: drain the ring from a reader thread (the SAME
    # decoupling the TCP row gets from edge_src's broker-reader +
    # unbounded fifo) so the producer pipeline front-loads its work and
    # stops contending with the consumer's compute — without it the
    # bounded ring keeps both pipelines interleaved for the whole window
    # and the comparison measures GIL contention, not the transport
    recv = parse_launch(
        f"tensor_shm_src path={ring} timeout=60 prefetch=1 "
        f"num-buffers={N_FRAMES} ! "
        "tensor_filter framework=xla model=mobilenet_v2"
        f" custom=seed:0{dtype_prop} batch={STREAM_BATCH} name=f ! "
        f"queue max-size-buffers={max(8, 2 * STREAM_BATCH)} ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out")
    send = parse_launch(
        f"videotestsrc num-buffers={N_FRAMES} pattern=random "
        "cache-frames=64 ! "
        "video/x-raw,format=RGB,width=224,height=224,framerate=120/1 ! "
        "tensor_converter ! "
        # push timeout must ride out the consumer's one-time model
        # compile (the ring fills long before the filter's first drain
        # on a cold cache); 256 KiB slots fit the 147 KiB frame without
        # the default 1 MiB over-allocation
        f"tensor_shm_sink path={ring} slots=64 slot-bytes=262144 "
        "timeout=300")
    try:
        fps_shm, _ = _measure(recv, "out", feeders=(send,))
        out["fps_shm_transport"] = round(fps_shm, 2)
    finally:
        send.stop()
        recv.stop()
    return out


def bench_lm() -> dict:
    """LM serving (net-new axis, no reference analogue): prefill tokens/sec
    + MFU on the full-sequence forward (attention path chosen by the
    length gate — naive below the measured flash crossover), and
    KV-cache decode tokens/sec through the compiled generate scan at a
    stated cache size.  Both measurements run twice; headline is the
    SLOWER decode run (same stability policy as the vision configs).
    Every phase propagates its exceptions: a row with a missing phase
    is a failed row."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.llm.engine import DecodeEngine
    from nnstreamer_tpu.llm.paged import PagedKVCachePool
    from nnstreamer_tpu.llm.pool import KVCachePool
    from nnstreamer_tpu.models.streamformer_lm import (decode_step,
                                                       forward_logits,
                                                       generate,
                                                       init_cache)
    from nnstreamer_tpu.ops.flash_attention import flash_wins as _flash_wins
    from nnstreamer_tpu.parallel.train_step import (StreamFormerConfig,
                                                    init_params)

    device = jax.devices()[0]
    # the lengths scale with the platform; the attn_path LABEL keys on
    # the same flash_wins gate forward_logits consults, so the row
    # reports the kernel that actually served the prefill
    on_tpu = device.platform == "tpu"
    prefill_t = int(os.environ.get("NNS_TPU_BENCH_LM_PREFILL",
                                   "2048" if on_tpu else "256"))
    decode_n = int(os.environ.get("NNS_TPU_BENCH_LM_DECODE",
                                  "256" if on_tpu else "48"))
    prompt_len = 64
    cfg = StreamFormerConfig(vocab=8192, dim=512, heads=8, head_dim=64,
                             mlp=2048, layers=4, experts=2,
                             max_seq=max(prefill_t,
                                         prompt_len + decode_n),
                             dtype=jnp.bfloat16)
    # built on the default device; the serving engines below place what
    # they are given themselves (llm/engine.py), exactly as tensor_llm's
    # engine does — bench and element run the same arrays
    params = init_params(cfg, 0)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (prefill_t,)), jnp.int32)
    fwd = jax.jit(lambda p, t: forward_logits(p, t, cfg))

    def _prefill_tok_s() -> float:
        reps = 3
        t0 = time.monotonic()
        for _ in range(reps):
            out = fwd(params, toks)
        jax.block_until_ready(out)
        return prefill_t * reps / (time.monotonic() - t0)

    jax.block_until_ready(fwd(params, toks))      # compile
    pre1, pre2 = _prefill_tok_s(), _prefill_tok_s()

    prompt = np.asarray(rng.integers(0, cfg.vocab, (prompt_len,)), np.int32)
    generate(params, cfg, prompt, decode_n)       # compile

    def _decode_tok_s() -> float:
        # every scan step (prompt prefill + continuation) is one
        # decode_step through the KV cache, so all of them count
        t0 = time.monotonic()
        generate(params, cfg, prompt, decode_n)
        return (prompt_len + decode_n) / (time.monotonic() - t0)

    dec1, dec2 = _decode_tok_s(), _decode_tok_s()

    # multi-stream serving: N independent KV caches advance through ONE
    # vmapped decode step with greedy feedback — the aggregate tok/s a
    # batch-serving deployment gets from the chip (single-stream decode
    # is dispatch-bound; this is the compute-bound point)
    n_streams = 8
    steps = 128 if on_tpu else 24
    caches = jax.vmap(lambda _: init_cache(cfg))(jnp.arange(n_streams))
    stoks = jnp.asarray(rng.integers(0, cfg.vocab, n_streams), jnp.int32)

    @jax.jit
    def vstep(caches, stoks):
        logits, caches = jax.vmap(
            lambda c, t: decode_step(params, c, t, cfg))(caches, stoks)
        return caches, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    caches, stoks = vstep(caches, stoks)            # compile + warm
    jax.block_until_ready(stoks)
    t0 = time.monotonic()
    for _ in range(steps):
        caches, stoks = vstep(caches, stoks)
    jax.block_until_ready(stoks)
    stream_tok_s = steps * n_streams / (time.monotonic() - t0)
    out = {"metric": CONFIG_METRICS["lm"], "value": round(min(dec1, dec2), 2),
           "unit": "decode_tok_s", "vs_baseline": None,
           "note": "net-new axis: reference has no LM serving path",
           "decode_tok_s_run1": round(dec1, 2),
           "decode_tok_s_run2": round(dec2, 2),
           "prefill_tok_s": round(min(pre1, pre2), 1),
           "prefill_tok_s_run1": round(pre1, 1),
           "prefill_tok_s_run2": round(pre2, 1),
           "prefill_len": prefill_t, "decode_len": decode_n,
           "kv_cache_tokens": cfg.max_seq,
           "params_m": round(n_params / 1e6, 2),
           # the path the length gate ACTUALLY selects for this prefill
           # length (flash=None callers route through flash_wins) — a
           # row must never describe a kernel that didn't run
           "attn_path": ("pallas_flash" if _flash_wins(prefill_t)
                         else "naive"),
           "decode_streams": n_streams,
           "decode_tok_s_multistream": round(stream_tok_s, 1)}

    # continuous-batching SERVING tier (nnstreamer_tpu/llm): the
    # slot-pooled decode step the tensor_llm element dispatches —
    # unlike the vmap-over-full-caches multistream point above, this is
    # the shape that serves (sessions at HETEROGENEOUS positions in one
    # shared cache pool, join/leave quantized onto warm padded
    # executables).  Bucket tok/s vs the same engine stepped one
    # session at a time = the win the SOAK_llm acceptance gates live.
    pool = KVCachePool(cfg, n_streams)
    eng = DecodeEngine(params, cfg, pool, capacity=n_streams)
    sessions = [pool.acquire(i) for i in range(n_streams)]
    for i, s in enumerate(sessions):
        s.max_new, s.next_token = 1 << 30, i + 1
    eng.step(sessions)                    # compile bucket shape
    eng.step(sessions[:1])                # compile solo lane
    t0 = time.monotonic()
    for _ in range(steps):
        eng.step(sessions)
    pooled = steps * n_streams / (time.monotonic() - t0)
    t0 = time.monotonic()
    for _ in range(steps):
        eng.step(sessions[:1])
    pooled_solo = steps / (time.monotonic() - t0)
    out["llm_serve_tok_s"] = round(pooled, 1)
    out["llm_serve_solo_tok_s"] = round(pooled_solo, 1)
    out["llm_serve_bucket"] = n_streams
    out["llm_serve_vs_solo"] = round(pooled / max(1e-9, pooled_solo), 2)

    # block-paged serving tier (ISSUE 17): the same bucket decoding
    # from the page arena instead of dense slots — the rate must hold
    # (the hotpath llmpaged gate pins within-10%) while memory scales
    # with use, not max_seq
    ps = 16 if cfg.max_seq % 16 == 0 \
        and cfg.max_seq >= 32 + steps + 8 else 0
    if ps:
        pages = (n_streams + 1) * (cfg.max_seq // ps) - 1
        ppool = PagedKVCachePool(cfg, pages, ps, slots=n_streams)
        peng = DecodeEngine(params, cfg, ppool, capacity=n_streams)
        # a 2-page prompt starts every lane at position 32, so the
        # pow2 table width holds at 4 through position 64 — the
        # whole timed window runs one warm executable (no
        # mid-measurement width crossing), like the dense point
        two_pages = np.asarray(
            rng.integers(0, cfg.vocab, (2 * ps,)), np.int32)
        psess = []
        for i in range(n_streams):
            s = ppool.acquire(i, prompt=two_pages, max_new=steps + 8)
            s.max_new = 1 << 30
            s.next_token = peng.prefill(s, two_pages)
            psess.append(s)
        peng.step(psess)                  # compile bucket shape
        t0 = time.monotonic()
        for _ in range(steps):
            peng.step(psess)
        paged_rate = steps * n_streams / (time.monotonic() - t0)
        out["llm_serve_paged_tok_s"] = round(paged_rate, 1)
        out["llm_serve_paged_vs_dense"] = round(
            paged_rate / max(1e-9, pooled), 3)
        out["llm_serve_page_size"] = ps

    # flop count from the naive-math lowering: the flash kernel
    # computes the same matmuls (plus O(T) rescales), and XLA's
    # cost model can't see inside a pallas_call
    flops = float(jax.jit(lambda p, t: forward_logits(
        p, t, cfg, flash=False)).lower(params, toks).compile()
        .cost_analysis().get("flops", 0.0))
    peak, _ = _peaks(device)
    if flops:
        out["gflops_prefill"] = round(flops / 1e9, 2)
        if peak:
            out["prefill_mfu"] = round(
                min(pre1, pre2) / prefill_t * flops / peak, 6)
    return out


def _ssd_priors_file(n_anchors: int) -> str:
    """Synthetic box priors (cy cx h w rows x n_anchors) for the
    mobilenet-ssd decode scheme."""
    rng = np.random.default_rng(0)
    cy = rng.random(n_anchors)
    cx = rng.random(n_anchors)
    hw = np.full(n_anchors, 0.2)
    f = tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False)
    for row in (cy, cx, hw, hw):
        f.write(" ".join(f"{v:.6f}" for v in row) + "\n")
    f.close()
    return f.name


def run_child(config: str, cpu: bool) -> dict:
    from nnstreamer_tpu.utils.platform import (device_label,
                                               enable_compile_cache)

    enable_compile_cache()
    label = device_label()
    on_tpu = label["platform"] == "tpu"
    if not on_tpu and not cpu:
        raise SystemExit(
            f"bench: JAX found platform {label['platform']!r} "
            f"({label['device_count']} x {label['device_kind']}), not a "
            "tpu; a speed is measured on the chip (pass --cpu for a "
            "correctness smoke on the host, renamed ..._cpu)")
    dtype_prop = "" if on_tpu else ",dtype:float32"
    # metric hygiene: the host-decode (pushdown-off) delta variant names
    # itself — a row must never describe a configuration that wasn't run
    pd_suffix = _pd_suffix(config)
    global N_FRAMES, STREAM_BATCH, INFLIGHT
    if on_tpu and "NNS_TPU_BENCH_BATCH" not in os.environ:
        # the micro-batch the last on-chip sweep chose (before PR 1, on
        # another JAX); the 1920-frame default still spans 15 batches
        STREAM_BATCH = 128
    if (on_tpu and config == "resident" and STREAM_BATCH > 1
            and "NNS_TPU_BENCH_INFLIGHT" not in os.environ):
        # device-resident pays no per-frame upload, so its ceiling is
        # dispatch pipelining; frames scale so >=2/3 of the stream is
        # measured AFTER the queue-fill ramp the skip window discards
        INFLIGHT = RESIDENT_INFLIGHT
        if "NNS_TPU_BENCH_FRAMES" not in os.environ:
            N_FRAMES = max(N_FRAMES, 30 * STREAM_BATCH)
    if not on_tpu and "NNS_TPU_BENCH_FRAMES" not in os.environ:
        # host-CPU convs are ~100x slower; keep the smoke run inside the
        # deadline (the TPU frame count stays the measured default)
        N_FRAMES = 200

    # which segment-compiler lowering tier served this row (NNS_FUSE /
    # --fuse): interpret | python | xla — rows must name the dispatch
    # configuration they measured, like stream_batch already does
    from nnstreamer_tpu.pipeline.schedule import resolve_tier

    lowering = resolve_tier(None)

    if config == "mobilenet":
        result = bench_model(CONFIG_METRICS[config] + pd_suffix, "mobilenet_v2", 224,
                             "image_labeling", dtype_prop)
    elif config == "resident":
        # device-resident streaming: frames are staged to HBM once by the
        # source and cycle as handles, so this measures the pipeline
        # machinery + dispatch + device compute with no per-frame upload
        result = bench_model(CONFIG_METRICS[config] + pd_suffix, "mobilenet_v2", 224,
                             "image_labeling", dtype_prop,
                             src_cache="device-cache")
    elif config == "ssd":
        from nnstreamer_tpu.models.registry import get_model

        n_anchors = get_model(
            "ssd_mobilenet_v2", {"seed": "0"}).out_info[0].np_shape[0]
        priors = _ssd_priors_file(n_anchors)
        result = bench_model(
            CONFIG_METRICS[config] + pd_suffix, "ssd_mobilenet_v2", 300,
            "bounding_boxes", dtype_prop,
            f"option1=mobilenet-ssd option3={priors} "
            "option4=300:300 option5=300:300")
    elif config == "deeplab":
        result = bench_model(CONFIG_METRICS[config] + pd_suffix, "deeplab_v3", 257,
                             "image_segment", dtype_prop)
    elif config == "posenet":
        result = bench_model(
            CONFIG_METRICS[config] + pd_suffix, "posenet", 257, "pose_estimation",
            dtype_prop, "option1=257:257 option2=257:257")
    elif config == "vit":
        # attention-family vision config: ViT-S/16 whose encoder runs the
        # Pallas flash kernel on TPU (models/vit.py).  CPU smoke shrinks
        # the tower the way the lm config shrinks its lengths — an f32
        # 12-deep ViT at 224 is ~2 s/frame on this host.
        props = "" if on_tpu else ",depth:2,dim:192,heads:3"
        # metric-name hygiene: a shrunk smoke must not carry the
        # full-size model's metric name (notes don't survive
        # spreadsheet copy-paste) — the CPU smoke renames itself
        metric = (CONFIG_METRICS[config] + pd_suffix if on_tpu
                  else ("vit_depth2_dim192_224_image_labeling_smoke"
                        "_e2e_fps" + pd_suffix))
        result = bench_model(metric, "vit", 224,
                             "image_labeling", dtype_prop + props)
        if not on_tpu:
            result["note"] = "CPU smoke uses depth:2,dim:192"
    elif config == "lm":
        result = bench_lm()
    else:
        result = bench_edge(dtype_prop)
    if not on_tpu:
        result["metric"] += "_cpu"
    result.update(label)
    result["lowering"] = lowering
    return result


# ---------------------------------------------------------------------------
# parent: one bounded child per configuration; never touches JAX itself
# ---------------------------------------------------------------------------

def _failure_row(config: str, error: str, cpu: bool) -> dict:
    """The row of a configuration that produced no measurement."""
    metric = (CONFIG_METRICS[config] + _pd_suffix(config)
              + ("_cpu" if cpu else ""))
    return {"metric": metric, "value": None,
            "unit": "decode_tok_s" if config == "lm" else "fps",
            "error": error}


def run_config(config: str, cpu: bool, deadline: float,
               stream_batch: int = 0) -> dict:
    """Measure one configuration in one child process (the only process
    that touches JAX while it runs, so it owns the chip).  The child's
    stderr passes through.  A non-zero exit, a missing row or an overrun
    of ``deadline`` seconds gives an ``error`` row."""
    env = dict(os.environ)
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    if stream_batch:
        env["NNS_TPU_BENCH_BATCH"] = str(stream_batch)
    cmd = [sys.executable, os.path.abspath(__file__),
           "--_child", "--config", config] + (["--cpu"] if cpu else [])
    try:
        proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                              text=True, timeout=deadline)
    except subprocess.TimeoutExpired:
        return _failure_row(config, f"killed at the {deadline:.0f}s "
                                    "deadline", cpu)
    if proc.returncode != 0:
        return _failure_row(config, f"child exited {proc.returncode}",
                            cpu)
    lines = proc.stdout.strip().splitlines()
    try:
        row = json.loads(lines[-1])
        row["metric"]
    except (IndexError, ValueError, KeyError, TypeError):
        return _failure_row(config, "child printed no result row", cpu)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mobilenet",
                    choices=tuple(CONFIG_METRICS))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="correctness smoke on the host CPU "
                         "(JAX_PLATFORMS=cpu); metrics are renamed "
                         "..._cpu")
    ap.add_argument("--deadline", type=float, default=float(
        os.environ.get("NNS_TPU_BENCH_DEADLINE", "480")),
        help="hard per-config wall-clock limit (seconds)")
    ap.add_argument("--sweep-batch", default=None,
                    help="comma list of stream micro-batch sizes; benches "
                         "--config once per size (batch-tuning mode)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="disable the fused-segment scheduler (sets "
                         "NNS_FUSE=0, inherited by child runs): measures "
                         "the interpreted-dispatch baseline so the "
                         "scheduler's delta is attributable")
    ap.add_argument("--fuse", default=None,
                    choices=["interpret", "python", "xla"],
                    help="segment-compiler lowering tier (sets NNS_FUSE, "
                         "inherited by child runs); rows carry it as "
                         "'lowering' so fuse-python vs fuse-xla captures "
                         "stay distinguishable")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.no_fuse:
        os.environ["NNS_FUSE"] = "0"
    if args.fuse is not None:
        os.environ["NNS_FUSE"] = {"interpret": "0", "python": "1",
                                  "xla": "xla"}[args.fuse]

    if args._child:
        print(json.dumps(run_child(args.config, args.cpu)), flush=True)
        return 0

    runs = [(config, 0) for config in
            (tuple(CONFIG_METRICS) if args.all else (args.config,))]
    if args.sweep_batch:
        try:
            sizes = [int(v) for v in args.sweep_batch.split(",") if v]
        except ValueError:
            ap.error("--sweep-batch must be a comma list of integers")
        if not sizes or any(b < 1 for b in sizes):
            ap.error("--sweep-batch sizes must be >= 1")
        runs = [(args.config, b) for b in sizes]

    failed = False
    for config, stream_batch in runs:
        row = run_config(config, args.cpu, args.deadline, stream_batch)
        if stream_batch:
            row["stream_batch"] = stream_batch
        failed = failed or "error" in row
        print(json.dumps(row), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
