"""Serving path: one in-process stream, source to sink.

    videotestsrc ! tensor_converter ! tensor_filter ! queue !
    tensor_decoder mode=image_labeling ! tensor_sink

The line is ``chip_smoke._flagship_line``'s with the configuration's
filter properties.  ``videotestsrc`` does not pace (``framerate`` only
sets ``pts``), so the loop is closed by back-pressure and the window
counts what reaches the sink.  The filter warms every pad shape of its
bucket on the first bucket it sees; the window opens once
``warm_frames`` results have arrived.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.record import Run, raise_if_failed


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.model = ctx.config["model"]
        self.pipeline = None
        self.stamps: List[tuple] = []     # (arrival, pts, label index)

    def _source(self, mix: Dict[str, Any], frames: int) -> str:
        src = " ".join(f"{k}={v}" for k, v in mix["source"].items()
                       if k != "framerate")
        side = self.model["input_size"]
        return (f"videotestsrc num-buffers={frames} seed={self.ctx.seed} "
                f"{src} ! video/x-raw,format=RGB,width={side},"
                f"height={side},framerate={mix['source']['framerate']} ! "
                "tensor_converter ! ")

    def _custom(self) -> str:
        sizes = ",".join(f"{k}:{v}" for k, v in self.model.items())
        return f"seed:{self.ctx.seed},{sizes}"

    def open(self) -> None:
        from nnstreamer_tpu import parse_launch

        t = time.monotonic()
        mix = self.ctx.traffic
        props = " ".join(f"{k}={v}"
                         for k, v in self.ctx.config["element"].items())
        self.pipeline = parse_launch(
            self._source(mix, -1) +
            f"tensor_filter {props} custom={self._custom()} name=f ! "
            f"queue max-size-buffers={mix['queue_buffers']} ! "
            "tensor_decoder mode=image_labeling ! "
            "tensor_sink name=out collect=false")
        if self.ctx.traced:
            self.tracer = self.pipeline.enable_tracing()
        stamps = self.stamps
        self.pipeline.get("out").connect(
            "new-data", lambda buf: stamps.append(
                (time.monotonic(), buf.pts, int(buf.extra["index"]))))
        self.pipeline.play()
        warm = int(mix["warm_frames"])
        deadline = time.monotonic() + 1100.0
        while len(stamps) < warm:
            raise_if_failed(self.pipeline)
            if time.monotonic() > deadline:
                raise TimeoutError(f"{len(stamps)} of {warm} warm-up "
                                   "frames reached the sink")
            time.sleep(0.01)
        self.filter = self.pipeline.get("f")
        self.ctx.setup["build_and_warm_s"] = time.monotonic() - t

    def _snapshot(self) -> Dict[str, Any]:
        snap = {"t": time.monotonic(), "frames": len(self.stamps),
                "dispatches": self.filter.fw.stats.total_invokes}
        if self.ctx.traced:
            snap["elements"] = self.tracer.report()
        return snap

    def _delta(self, a: Dict[str, Any], b: Dict[str, Any]
               ) -> Dict[str, Any]:
        out = {"frames": b["frames"] - a["frames"],
               "dispatches": b["dispatches"] - a["dispatches"],
               "batch": int(self.ctx.config["element"]["batch"]),
               "filter": "f", "shed": 0}
        if "elements" in a:
            out["element_proctime_ms"] = {
                name: row["proctime_ms"]
                - a["elements"].get(name, {}).get("proctime_ms", 0.0)
                for name, row in b["elements"].items()}
        return out

    def window(self, mix: Dict[str, Any], seed: int, seconds: float,
               traced: bool) -> Run:
        ctx = self.ctx
        run = ctx.new_run(mix, seed, seconds)
        run.t0 = time.monotonic()
        run.t1 = run.t0 + seconds
        first = self._snapshot()
        if traced:
            from benchmarks.tracing import trace_middle

            run.trace = trace_middle(run, ctx.trace_dir, self._snapshot,
                                     self._delta)
        time.sleep(max(0.0, run.t1 - time.monotonic()))
        last = self._snapshot()
        raise_if_failed(self.pipeline)
        run.counters = self._delta(first, last)
        run.counters["compiles"] = ctx.compiles.between(run.t0, run.t1)
        run.requests = [{"id": i, "due": t, "sent": t, "done": t,
                         "ok": True, "outcome": "done", "pts": pts,
                         "label": label}
                        for i, (t, pts, label) in enumerate(self.stamps)]
        run.missed_ms = seconds * 1e3
        return run

    def _source_frames(self, n: int) -> List[np.ndarray]:
        """The first ``n`` frames the line's source emits."""
        from nnstreamer_tpu import parse_launch

        p = parse_launch(self._source(self.ctx.traffic, n)
                         + "tensor_sink name=out")
        p.run(timeout=120)
        side = self.model["input_size"]
        return [np.array(r.np(0)).reshape(side, side, 3)
                for r in p.get("out").results]

    def check(self, run: Run) -> Dict[str, Any]:
        """Results arrive in source order with none missing; the served
        label of the first frames is a top logit of the plain float32
        reference on the served weights, and the served dtype's logits
        stay within the configuration's share of the reference's range;
        nothing compiled inside the window."""
        import jax

        reference = self.ctx.family_module("reference")

        ref = self.ctx.config["reference"]
        pts = [r["pts"] for r in run.requests]
        step = pts[1] - pts[0]
        in_order = pts == [pts[0] + i * step for i in range(len(pts))]
        fw = self.filter.fw
        frames = self._source_frames(int(ref["sampled_frames"]))
        serve = jax.jit(fw._model.forward)
        worst, labels_ok = 0.0, True
        for i, frame in enumerate(frames):
            want = reference.forward_logits(fw._params_dev, frame)
            got = np.asarray(serve(fw._params_dev, frame)[0], np.float32)
            span = float(want.max() - want.min())
            worst = max(worst, float(np.abs(got - want).max()) / span)
            label = run.requests[i]["label"]
            labels_ok &= bool(0 <= label < want.shape[0] and want[label]
                              >= want.max() - ref["logit_rtol"] * span)
        out = {"frames": len(pts), "in_order": in_order,
               "logit_rel_err": worst, "labels_ok": labels_ok,
               "compiles_in_window": len(run.counters["compiles"])}
        out["correct"] = bool(in_order and labels_ok
                              and worst < ref["logit_rtol"]
                              and not run.counters["compiles"])
        return out

    def close(self) -> None:
        if self.pipeline is not None:
            self.pipeline.stop()
            self.pipeline = None
