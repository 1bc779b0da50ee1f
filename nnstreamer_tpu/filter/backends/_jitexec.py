"""Shared jit-execution engine for device backends.

Any backend whose model is (pure jittable ``forward(params, *inputs)``,
params pytree) gets the identical hot-path discipline the XLA backend
pioneered — params resident in HBM, one compiled executable, async
dispatch, micro-batched invoke via vmap — by mixing this in and calling
:meth:`_setup_exec` at open.  Used by the xla, tensorflow-lite,
tensorflow, and pytorch backends; the TPU analogue of the reference
sharing ``tensor_filter_common`` invoke plumbing across subplugins.
"""

from __future__ import annotations

import time
from typing import Any, List

import numpy as np

from ...analysis import compileledger
from ...pipeline.tracing import annotate, annotation_active
from ...tensor.buffer import BatchView, is_device_array
from ..framework import Accelerator, FilterError, start_output_transfers


def _wrap_compute_dtype(forward_fn, params, dtype, example_inputs=None):
    """Cast f32 param leaves to ``dtype`` and wrap the forward so float
    inputs enter in ``dtype`` and every float output leaves in its
    ORIGINAL dtype (external tensor meta unchanged — including native
    f16/bf16 outputs, recovered via a traced eval_shape of the unwrapped
    forward when example inputs are available)."""
    import jax
    import jax.numpy as jnp

    out_dtypes = None
    if example_inputs is not None:
        try:
            shapes = jax.eval_shape(forward_fn, params, *example_inputs)
            out_dtypes = [jnp.dtype(o.dtype) for o in shapes]
        except Exception:
            out_dtypes = None

    def _cast_param(a):
        arr = np.asarray(a)
        return arr.astype(np.dtype(dtype)) if arr.dtype == np.float32 \
            else a

    params = jax.tree_util.tree_map(_cast_param, params)

    def _restore(o, want):
        if (want is not None and hasattr(o, "dtype") and o.dtype != want
                and jnp.issubdtype(o.dtype, jnp.floating)
                and jnp.issubdtype(want, jnp.floating)):
            return o.astype(want)
        if (want is None and hasattr(o, "dtype")
                and jnp.issubdtype(o.dtype, jnp.floating)
                and jnp.dtype(o.dtype) == jnp.dtype(dtype)):
            # no trace available: at least undo the compute-dtype leak
            return o.astype(jnp.float32)
        return o

    def wrapped(p, *xs):
        xs = [jnp.asarray(x) for x in xs]
        xs = [x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
              else x for x in xs]
        outs = forward_fn(p, *xs)
        wants = out_dtypes or [None] * len(outs)
        return [_restore(o, w) for o, w in zip(outs, wants)]

    return wrapped, params


class BatchHandle:
    """An in-flight batched invoke: batched device outputs + frame count.

    ``wait()`` materializes each batched output on host ONCE (the async
    copies were started at dispatch) and hands back zero-copy numpy views
    per frame.  ``views()`` instead hands back device-resident
    :class:`BatchView` handles — nothing crosses to host; a downstream
    batched filter consumes the underlying arrays directly (cascade mode).
    """

    def __init__(self, outs, n: int) -> None:
        self._outs = outs
        self._n = n

    def wait(self) -> List[List[np.ndarray]]:
        mats = [np.asarray(o) for o in self._outs]
        return [[m[i] for m in mats] for i in range(self._n)]

    def views(self) -> List[List[BatchView]]:
        caches = [{} for _ in self._outs]
        return [[BatchView(o, i, c) for o, c in zip(self._outs, caches)]
                for i in range(self._n)]


class _FlushHandle:
    """Tiny-tail twin of :class:`BatchHandle`: per-frame device outputs
    (the unbatched executable), same wait()/views() contract (per-frame
    device arrays are already valid device-resident payloads)."""

    def __init__(self, per_frame_outs) -> None:
        self._outs = per_frame_outs

    def wait(self) -> List[List[np.ndarray]]:
        return [[np.asarray(o) for o in frame] for frame in self._outs]

    def views(self):
        return [list(frame) for frame in self._outs]


class CastingHandle:
    """Wraps a :class:`BatchHandle`, applying per-output host dtype casts
    at wait() (declared-int64 outputs come back int32 when jax x64 is
    off).  ``views()`` falls back to host materialization — a cast that
    jax cannot represent has no device-resident form."""

    def __init__(self, inner: BatchHandle, casts) -> None:
        self._inner = inner
        self._casts = casts

    def wait(self) -> List[List[np.ndarray]]:
        return [[o if c is None else np.asarray(o).astype(c)
                 for o, c in zip(frame, self._casts)]
                for frame in self._inner.wait()]

    def views(self):
        return self.wait()


class JitExecMixin:
    """Execution engine over ``self._forward_fn`` / ``self._params_dev`` /
    ``self._device`` (set by :meth:`_setup_exec`)."""

    SUPPORTS_BATCHING = True
    #: concurrent jax dispatch on one jitted executable is supported (the
    #: default_device context and trace caches are thread-local/locked),
    #: so tensor_filter workers share ONE instance: executables compile
    #: once and params live in HBM once
    THREADSAFE_INVOKE = True

    def _setup_exec(self, forward_fn, params, device, warmup_inputs=None,
                    compute_dtype=None, mesh=None):
        """Compile + stage: params → HBM, jit the forward, optional warm-up
        invoke so frame 1 is steady state.  Returns the warm-up outputs
        (callers probe output meta from them — no second device trip).

        ``compute_dtype`` (e.g. bf16): float32 param leaves are cast
        BEFORE staging (half the HBM weight traffic) and the forward is
        wrapped to run float math in that dtype, casting float outputs
        back to their original precision — the generic MXU-native mode
        for lowered-graph backends (the tflite backend does this inside
        its lowering instead, where it also owns requantization).

        ``mesh`` (from ``custom=mesh:dp=N`` via :meth:`_resolve_mesh`):
        dp-shard the BATCHED serving executable over a ``("dp",)`` device
        mesh — params replicated, the stream micro-batch split along
        axis 0, XLA placing per-device compute (the TPU-native superset
        of the reference's among-device offload,
        tensor_query_client.c:656-743: instead of shipping sub-pipelines
        to other devices over TCP, the ONE serving executable spans the
        mesh).  The unbatched executable (p50 probe, tiny-tail flush)
        stays single-device on ``device`` with its own param copy — a
        1-frame dispatch has nothing to shard."""
        import jax

        if compute_dtype is not None:
            forward_fn, params = _wrap_compute_dtype(
                forward_fn, params, compute_dtype,
                example_inputs=warmup_inputs)
        self._device = device
        self._forward_fn = forward_fn
        self._params_dev = jax.device_put(params, device)
        self._jitted = jax.jit(forward_fn)
        self._vjit = None
        self._mesh = mesh
        self._nns_sig_seen = None   # compile-ledger signature mirror
        # wait-state attribution (obs/attrib.py): the first dispatch of
        # a cold executable is device-compile, not device-invoke — the
        # warm-up below (when inputs are given) pays it outside the
        # stream, so frame 1 annotates as a plain invoke
        self._annot_cold = True
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._params_mesh = jax.device_put(
                params, NamedSharding(mesh, PartitionSpec()))
        else:
            self._params_mesh = None
        if warmup_inputs is None:
            return None
        outs = self._invoke_device(warmup_inputs)
        jax.block_until_ready(outs)
        self._annot_cold = False
        return outs

    @staticmethod
    def _resolve_mesh(props, device):
        """``custom=mesh:dp=N``: a data-parallel serving mesh of N devices
        of this backend's platform.  None when the prop is absent or
        N == 1; FilterError on bad syntax or too few devices."""
        import jax
        from jax.sharding import Mesh

        spec = str(getattr(props, "custom_properties", {}).get(
            "mesh", "")).strip()
        if not spec:
            return None
        if not spec.startswith("dp="):
            raise FilterError(
                f"mesh spec {spec!r} not understood (expected mesh:dp=N; "
                "tp/pp serving shardings are model-parallel training "
                "territory — see parallel/)")
        try:
            dp = int(spec[3:])
        except ValueError:
            raise FilterError(f"mesh:dp={spec[3:]!r} is not an integer")
        if dp < 1:
            raise FilterError(f"mesh:dp={dp} must be >= 1")
        if dp == 1:
            return None
        devs = [d for d in jax.devices() if d.platform == device.platform]
        if len(devs) < dp:
            raise FilterError(
                f"mesh:dp={dp} but only {len(devs)} {device.platform} "
                "device(s) visible")
        return Mesh(np.array(devs[:dp]), ("dp",))

    @staticmethod
    def _resolve_compute(props, device):
        """``custom=compute:{auto,float32,bfloat16}`` for lowered-graph
        backends: auto = bfloat16 on TPU, float32 elsewhere."""
        import jax.numpy as jnp

        choice = str(getattr(props, "custom_properties", {}).get(
            "compute", "auto")).lower()
        if choice in ("float32", "fp32", "f32"):
            return None
        if choice in ("bfloat16", "bf16"):
            return jnp.bfloat16
        if choice != "auto":
            raise FilterError(
                f"unknown compute dtype {choice!r} "
                "(auto | float32 | bfloat16)")
        return jnp.bfloat16 if device.platform == "tpu" else None

    def _teardown_exec(self) -> None:
        self._jitted = None
        self._vjit = None
        self._forward_fn = None
        self._params_dev = None
        self._params_mesh = None
        self._mesh = None
        self._postprocess_fn = None

    @staticmethod
    def _pick_device(accelerators):
        """The device this backend serves on — every jit-exec backend's
        first touch of JAX at open, so the shared compile cache is
        switched on here, before the model build's first compile."""
        import jax

        from ...utils.platform import enable_compile_cache

        enable_compile_cache()
        want = accelerators[0] if accelerators else Accelerator.AUTO
        if want is Accelerator.CPU:
            return jax.devices("cpu")[0]
        if want is Accelerator.TPU:
            tpus = [d for d in jax.devices() if d.platform != "cpu"]
            if not tpus:
                raise FilterError("accelerator=true:tpu but no TPU device")
            return tpus[0]
        # AUTO/DEFAULT: first device (TPU when present)
        return jax.devices()[0]

    # -- hot path ------------------------------------------------------------
    def _ensure_device(self, x):
        """Re-commit a device array pinned to a DIFFERENT device onto this
        backend's device (no-op in the common case; a jitted call rejects
        mixed-device arguments, e.g. ``videotestsrc device-cache`` staging
        to the TPU while the filter runs ``accelerator=true:cpu``).  Moves
        are memoized by handle identity — sources cycle a small fixed set
        of cached frames, so a pinning mismatch costs one copy per distinct
        handle, not one per frame — and warned about once: a cross-device
        hop per distinct frame defeats the device-resident fast path."""
        if is_device_array(x):
            devs = getattr(x, "devices", None)
            # a mismatch is EITHER a different device OR a multi-device
            # (mesh-sharded) array feeding a single-device executable —
            # e.g. a mesh:dp cascade into a plain filter; device_put
            # gathers/reshards both cases
            if devs is not None and set(devs()) != {self._device}:
                cache = getattr(self, "_xdev_cache", None)
                if cache is None:
                    cache = self._xdev_cache = {}
                    from ...utils.log import ml_logw

                    ml_logw(
                        "input pinned to %s but filter runs on %s: "
                        "re-committing (device-resident fast path degraded "
                        "to cross-device copies)", devs(), self._device)
                hit = cache.get(id(x))
                if hit is not None and hit[0]() is x:  # id-reuse guard
                    return hit[1]
                import weakref

                import jax

                moved = jax.device_put(x, self._device)
                if len(cache) < 1024:   # bound: sources cycle small sets
                    key = id(x)
                    ref = weakref.ref(x, lambda _, k=key: cache.pop(k, None))
                    cache[key] = (ref, moved)
                return moved
        return x

    def _ledger_note(self, site: str, arrays) -> None:
        """Sentinel-on only: mirror jax's per-executable signature
        cache so each NOVEL dispatch signature reaches the compile
        ledger (jax compiles exactly when the signature is new — this
        set tracks the same key, per executable generation).  The hot
        key is raw ``(shape, dtype)`` pairs; the field-named ledger
        signature is built only on a miss, so a warm dispatch pays one
        genexp + one set probe."""
        seen = getattr(self, "_nns_sig_seen", None)
        if seen is None:
            seen = self._nns_sig_seen = set()
        key = (site,) + tuple((getattr(a, "shape", None),
                               getattr(a, "dtype", None))
                              for a in arrays)
        if key in seen:
            return
        seen.add(key)
        compileledger.record(site, tuple(
            (f"arg[{i}]", (tuple(getattr(a, "shape", ())),
                           str(getattr(a, "dtype", type(a).__name__))))
            for i, a in enumerate(arrays)))

    def _invoke_device(self, inputs: List[Any]):
        import jax

        inputs = [x.device_slice() if isinstance(x, BatchView) else x
                  for x in inputs]
        inputs = [self._ensure_device(x) for x in inputs]
        if compileledger.ENABLED:
            self._ledger_note("filter.jitexec.invoke", inputs)
        with jax.default_device(self._device):
            return self._jitted(self._params_dev, *inputs)

    def invoke(self, inputs: List[Any],
               emit_device: bool = False) -> List[Any]:
        t0 = time.monotonic_ns()
        outs = self._invoke_device(inputs)
        if not emit_device:
            start_output_transfers(outs)
        t1 = time.monotonic_ns()
        self.stats.record(t1 - t0)
        if annotation_active():
            annotate("device-compile" if self._annot_cold
                     else "device-invoke", t0, t1)
        self._annot_cold = False
        return list(outs)

    def invoke_batched(self, frames, bucket: int, emit_device: bool = False):
        """One h2d stage + one dispatch + one d2h stream for up to
        ``bucket`` frames: the per-dispatch RTT is paid once per batch
        instead of once per frame.  Short batches are padded by repeating
        the last frame (sliced away in wait()), so exactly one executable
        shape ever compiles — EXCEPT tiny flush tails (EOS /
        renegotiation drains, ≤ bucket/8 frames), which dispatch
        per-frame through the already-compiled unbatched executable:
        a 1-frame flush at bucket=64 would otherwise burn 64× the FLOPs.

        ``emit_device=True`` (cascade mode): outputs stay in HBM and the
        returned handle's ``views()`` hands out :class:`BatchView`
        payloads instead of host arrays — no d2h copies are started."""
        n = len(frames)
        if 8 * n <= bucket:
            t0 = time.monotonic_ns()
            outs = [self._invoke_device(list(f)) for f in frames]
            if not emit_device:
                for o in outs:
                    start_output_transfers(o)
            t1 = time.monotonic_ns()
            self.stats.record(t1 - t0)
            if annotation_active():
                annotate("device-invoke", t0, t1)
            return _FlushHandle(outs)
        stacked = [self._stage_batch([f[k] for f in frames], bucket)
                   for k in range(len(frames[0]))]
        cold = self._vjit is None
        t0 = time.monotonic_ns()
        outs = self._dispatch_batched(stacked, emit_device=emit_device)
        t1 = time.monotonic_ns()
        self.stats.record(t1 - t0)
        if annotation_active():
            annotate("device-compile" if cold else "device-invoke", t0, t1)
        return BatchHandle(list(outs), n)

    @staticmethod
    def pad_rows(n: int, capacity: int = 0) -> int:
        """Quantized pad target for an ``n``-row partial bucket: next
        power of two up to 8, then multiples of 8, capped at
        ``capacity`` — waste <= 7 rows above 8 (pow2 all the way up
        would charge a 33-row fill a 64-row tile) and the executable
        count stays bounded at ``4 + capacity/8``."""
        cap = max(int(capacity), n, 1)
        if n <= 8:
            bucket = 1
            while bucket < n:
                bucket <<= 1
        else:
            bucket = (n + 7) & ~7
        return min(bucket, cap)

    def warmup_stacked(self, capacity: int) -> None:
        """Pre-compile EVERY padded-bucket executable shape a
        ``capacity``-sized cross-stream bucket can dispatch
        (:meth:`pad_rows` quantization).  Called once, off the steady
        state (tensor_filter does it on the first bucket it sees):
        without this, each pad shape's first live bucket stalls the
        serving thread for a full XLA compile — seconds-long latency
        spikes landing mid-soak, exactly the tail a latency SLO
        notices."""
        import jax

        in_info, _ = self.get_model_info()
        shapes = sorted({self.pad_rows(n, capacity)
                         for n in range(1, max(1, int(capacity)) + 1)})
        for rows in shapes:
            zeros = [np.zeros((rows,) + i.np_shape, i.np_dtype)
                     for i in in_info]
            jax.block_until_ready(self._dispatch_batched(zeros))

    def invoke_stacked(self, stacked: List[Any], n: int,
                       capacity: int = 0,
                       emit_device: bool = False) -> List[Any]:
        """Cross-stream batched invoke over PRE-STACKED ``(n, …)``
        inputs (the query serving plane's bucket, query/server.py): pad
        axis 0 up to the next power of two (capped at ``capacity``) so
        a BOUNDED set of at most ``log2(capacity)+1`` vmapped
        executables serves every partial fill — a fill-dependent
        dispatch shape would JIT-compile once per distinct fill (up to
        ``capacity`` compiles, each multi-second on a real chip) and a
        fill-sized cache would thrash on bursty traffic, while padding
        straight to ``capacity`` would charge a quarter-full bucket the
        whole tile's FLOPs.  Power-of-two padding bounds the waste at
        <2x the live rows and each shape is warm after its first use.
        Padding repeats the last live row (the same policy
        :meth:`_stage_batch` applies) and is sliced away by the caller
        (rows past ``n`` are never replied — tensor/buffer.py
        XBatchMeta).

        Returns the PADDED stacked outputs as device handles with async
        d2h transfers started (``emit_device=False``): the split point
        materializes each output once per bucket and hands out zero-copy
        row views, so the whole bucket pays one sync."""
        import jax.numpy as jnp

        bucket = self.pad_rows(n, capacity)
        padded = []
        for arr in stacked:
            arr = arr.device_slice() if isinstance(arr, BatchView) else arr
            rows = int(arr.shape[0])
            if rows < bucket:
                if is_device_array(arr):
                    arr = self._ensure_device(arr)
                    pad = arr[-1:]
                    arr = jnp.concatenate(
                        [arr, jnp.broadcast_to(
                            pad, (bucket - rows,) + tuple(pad.shape[1:]))],
                        axis=0)
                else:
                    arr = np.asarray(arr)
                    arr = np.concatenate(
                        [arr, np.broadcast_to(
                            arr[-1:],
                            (bucket - rows,) + arr.shape[1:])], axis=0)
            padded.append(arr)
        cold = self._vjit is None
        t0 = time.monotonic_ns()
        outs = self._dispatch_batched(padded, emit_device=emit_device)
        t1 = time.monotonic_ns()
        self.stats.record(t1 - t0)
        if annotation_active():
            annotate("device-compile" if cold else "device-invoke", t0, t1)
        return list(outs)

    def _stage_batch(self, arrs, bucket: int):
        """One input's frames → one ``(bucket, …)`` batch array.

        Cascade fast path: contiguous :class:`BatchView` runs over shared
        underlying arrays are re-joined with at most one device op per run
        (zero when one upstream batch maps 1:1) — an A→B filter cascade at
        equal batch sizes moves NO tensor bytes and dispatches NO per-frame
        ops between the two executables.  Device arrays stack on device;
        host arrays stack on host (the h2d rides the dispatch)."""
        n = len(arrs)
        if not all(map(is_device_array, arrs)):
            arrs = [np.asarray(a) for a in arrs]
            if n < bucket:
                arrs = arrs + [arrs[-1]] * (bucket - n)
            return np.stack(arrs)
        import jax.numpy as jnp

        if all(isinstance(a, BatchView) for a in arrs):
            # group consecutive rows of the same underlying batch
            segs, i = [], 0
            while i < n:
                v, j = arrs[i], i + 1
                while (j < n and arrs[j].batch is v.batch
                       and arrs[j].index == arrs[j - 1].index + 1):
                    j += 1
                segs.append((v.batch, v.index, arrs[j - 1].index + 1))
                i = j
            b0, lo, _hi = segs[0]
            if len(segs) == 1 and lo == 0 and b0.shape[0] == bucket:
                # 1:1 with the upstream batch (padding rows included —
                # upstream pads by repeating its last frame, exactly this
                # stage's own padding policy): feed it straight through.
                # In mesh mode a sharded upstream batch stays sharded —
                # _dispatch_batched's device_put onto the batch sharding
                # is a no-op for a same-mesh cascade (true zero-copy).
                if getattr(self, "_mesh", None) is not None:
                    return b0
                return self._ensure_device(b0)
            # mixed segments: normalize every part onto this executable's
            # device BEFORE concatenating — jnp ops reject operands
            # committed to different device sets (a dp-sharded cascade
            # row next to a single-device flush-tail row)
            parts = [self._ensure_device(b[lo:hi]) for b, lo, hi in segs]
            if n < bucket:
                pad = parts[-1][-1:]
                parts.append(jnp.broadcast_to(
                    pad, (bucket - n,) + tuple(pad.shape[1:])))
            return jnp.concatenate(parts, axis=0)
        # plain device arrays (device source / flush-tail outputs):
        # stack ON DEVICE -- one tiny dispatch instead of a d2h sync +
        # full h2d re-upload (per-element ensure: see mixed-segment note)
        arrs = [self._ensure_device(
                    a.device_slice() if isinstance(a, BatchView) else a)
                for a in arrs]
        if n < bucket:
            arrs = arrs + [arrs[-1]] * (bucket - n)
        return jnp.stack(arrs)

    def _dispatch_batched(self, stacked, emit_device: bool = False):
        import jax

        if compileledger.ENABLED:
            self._ledger_note("filter.jitexec.vmap", stacked)
        mesh = getattr(self, "_mesh", None)
        n_in = len(stacked)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            dp = mesh.devices.size
            bucket = stacked[0].shape[0]
            if bucket % dp:
                raise FilterError(
                    f"stream batch {bucket} not divisible by mesh dp={dp} "
                    "(set tensor_filter batch= to a multiple)")
            bs = NamedSharding(mesh, P("dp"))
            if self._vjit is None:
                ps = NamedSharding(mesh, P())
                self._vjit = jax.jit(
                    jax.vmap(self._forward_fn,
                             in_axes=(None,) + (0,) * n_in),
                    in_shardings=(ps,) + (bs,) * n_in,
                    out_shardings=bs)
            # committed single-device arrays (device sources, cascades)
            # must be resharded onto the mesh explicitly — jit treats a
            # committed-mismatch as an error, device_put reshards
            stacked = [jax.device_put(s, bs) if is_device_array(s) else s
                       for s in stacked]
            outs = self._vjit(self._params_mesh, *stacked)
        else:
            if self._vjit is None:
                self._vjit = jax.jit(jax.vmap(self._forward_fn,
                                              in_axes=(None,) + (0,) * n_in))
            with jax.default_device(self._device):
                outs = self._vjit(self._params_dev, *stacked)
        if not emit_device:
            start_output_transfers(outs)
        return outs

    def warmup_batched(self, bucket: int) -> None:
        """Pre-compile BOTH batching executables — the bucket-wide vmap
        and the unbatched one the tiny-tail flush rides — outside the
        statistics (compile time would dominate the last-10 latency
        average) and outside the EOS drain (a compile stall there can
        blow pipeline wait timeouts)."""
        import jax

        in_info, _ = self.get_model_info()
        zeros = [np.zeros((bucket,) + i.np_shape, i.np_dtype)
                 for i in in_info]
        jax.block_until_ready(self._dispatch_batched(zeros))
        ones = [np.zeros(i.np_shape, i.np_dtype) for i in in_info]
        jax.block_until_ready(self._invoke_device(ones))
        self._annot_cold = False

    def set_postprocess(self, fn) -> bool:
        """Compose a decoder-pushed reduction into the jitted forward: one
        fused executable, so the reduced (small) outputs are what get the
        async d2h copies — the big intermediate never crosses the wire."""
        import jax

        base_fwd = self._forward_fn

        def fused(params, *xs):
            return tuple(fn(list(base_fwd(params, *xs))))

        self._forward_fn = fused
        self._jitted = jax.jit(fused)
        self._vjit = None  # rebuild the batched executable around the fusion
        self._nns_sig_seen = None   # new executables: signatures reset
        self._annot_cold = True   # next dispatch re-compiles
        self._nns_cost_cache = None   # fused graph has a new cost model
        # marker for the element's post-reload re-apply: a backend that
        # still carries the fusion must NOT be fused again (set_postprocess
        # composes over _forward_fn — a second application would reduce
        # the already-reduced outputs)
        self._postprocess_fn = fn
        return True

    def has_postprocess(self) -> bool:
        return getattr(self, "_postprocess_fn", None) is not None
