"""Serving path: many cameras, one accelerator, over the query wire.

    tensor_query_serversrc batch=N ! tensor_filter ! tensor_query_serversink

in this process (it holds the chip); each camera is a connection of a
child process that sends a frame every ``1/fps`` seconds whatever the
server does, and decodes the logits it gets back (the source system's
offload pattern).  The cross-stream batcher coalesces the cameras'
frames; the filter warms every pad shape of that bucket on the first
bucket it sees, so set-up pushes one through before the clients start.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import traffic as gen
from benchmarks.children import Children
from benchmarks.record import Run, raise_if_failed

SERVER_ID = 4723
LEAD_S = 0.5
#: frames of each of the first cameras whose logits are kept and checked
KEEP_FRAME = 2
WARM_CONNECTIONS = 8


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.model = ctx.config["model"]
        self.pipeline = None

    def open(self) -> None:
        from nnstreamer_tpu import parse_launch

        t = time.monotonic()
        mix = self.ctx.traffic
        side = self.model["input_size"]
        element = dict(self.ctx.config["element"])
        # the bucket is the server's here: a solo stream's batcher is
        # bypassed, so the filter's own batch/inflight do not apply
        for key in ("batch", "inflight"):
            element.pop(key, None)
        props = " ".join(f"{k}={v}" for k, v in element.items())
        server = " ".join(f"{k}={v}" for k, v in mix["server"].items())
        sizes = ",".join(f"{k}:{v}" for k, v in self.model.items())
        self.pipeline = parse_launch(
            f"tensor_query_serversrc name=qsrc id={SERVER_ID} port=0 "
            f"{server} caps=other/tensors,format=static,num_tensors=1,"
            f"dimensions=3:{side}:{side},types=uint8,framerate=0/1 ! "
            f"tensor_filter {props} custom=seed:{self.ctx.seed},{sizes} "
            f"name=f ! tensor_query_serversink id={SERVER_ID}")
        self.pipeline.play()
        self.filter = self.pipeline.get("f")
        self.port = self.pipeline.get("qsrc").bound_port
        self.ctx.setup["build_s"] = time.monotonic() - t
        t = time.monotonic()
        self._warm(side)
        self.ctx.setup["warm_s"] = time.monotonic() - t

    def _warm(self, side: int) -> None:
        """Connections of this process send frames together until a
        shared bucket has gone through: the filter compiles every pad
        shape of the bucket on the first one it sees.  A lone frame is
        served solo and warms only the unbatched executable, and two
        synchronous clients take turns and never share a bucket (the
        first chip run of PR 22 waited out its deadline that way), so
        there are eight: while one frame is served the rest queue."""
        import threading

        from nnstreamer_tpu.query.client import QueryConnection
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        frame = np.zeros((side, side, 3), np.uint8)
        errors: List[BaseException] = []

        def send() -> None:
            conn = QueryConnection("127.0.0.1", self.port, timeout=900.0)
            try:
                conn.connect()
                deadline = time.monotonic() + 900.0
                while not self.filter._xb_invokes:
                    raise_if_failed(self.pipeline)
                    if time.monotonic() > deadline:
                        raise TimeoutError("no shared bucket formed")
                    conn.query(TensorBuffer(tensors=[frame]))
            except (ConnectionError, OSError, TimeoutError) as exc:
                errors.append(exc)
            finally:
                conn.close()

        threads = [threading.Thread(target=send) for _ in range(WARM_CONNECTIONS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]

    def _plans(self, mix: Dict[str, Any], seed: int, seconds: float
               ) -> List[Dict[str, Any]]:
        phases = gen.camera_phases(mix, seed)
        cams = [{"id": i, "phase": p} for i, p in enumerate(phases)]
        keep = [[c["id"], KEEP_FRAME] for c in cams[:int(
            self.ctx.config["reference"]["sampled_frames"])]]
        n = int(mix.get("processes", 4))
        base = {"kind": "cameras", "host": "127.0.0.1", "port": self.port,
                "seed": seed, "seconds": seconds, "fps": float(mix["fps"]),
                "ramp_s": float(mix["ramp_s"]), "timeout_s": 10.0,
                "frame_pool": int(mix["frame_pool"]),
                "frame_shape": list(mix["frame_shape"]),
                "keep_logits": keep}
        return [dict(base, cameras=cams[i::n]) for i in range(n)]

    def _snapshot(self) -> Dict[str, Any]:
        from nnstreamer_tpu.query.server import peek_server

        f = self.filter
        shed = peek_server(SERVER_ID).counters()["shed"]
        return {"t": time.monotonic(), "xb_invokes": f._xb_invokes,
                "xb_frames": f._xb_frames, "shed": sum(shed.values()),
                "dispatches": f.fw.stats.total_invokes}

    def _delta(self, a: Dict[str, Any], b: Dict[str, Any]
               ) -> Dict[str, Any]:
        out = {k: b[k] - a[k] for k in a if k != "t"}
        out["batch"] = int(self.ctx.traffic["server"]["batch"])
        out["frames"] = out["xb_frames"]
        return out

    def window(self, mix: Dict[str, Any], seed: int, seconds: float,
               traced: bool) -> Run:
        ctx = self.ctx
        run = ctx.new_run(mix, seed, seconds)
        t = time.monotonic()
        children = Children(self._plans(mix, seed, seconds), ctx.root)
        try:
            children.wait_ready(timeout=120.0)
            run.t0 = time.monotonic() + LEAD_S + float(mix["ramp_s"])
            run.t1 = run.t0 + seconds
            children.go(run.t0)
            ctx.setup["clients_and_ramp_s"] = run.t0 - t
            time.sleep(max(0.0, run.t0 - time.monotonic()))
            first = self._snapshot()
            if traced:
                from benchmarks.tracing import trace_middle

                run.trace = trace_middle(run, ctx.trace_dir,
                                         self._snapshot, self._delta)
            time.sleep(max(0.0, run.t1 - time.monotonic()))
            last = self._snapshot()
            cams = children.collect(run.t1 + 60.0)
        finally:
            children.stop()
        raise_if_failed(self.pipeline)
        run.counters = self._delta(first, last)
        run.counters["compiles"] = ctx.compiles.between(run.t0, run.t1)
        run.counters["kept_logits"] = {
            (c["cam"], int(k)): v for c in cams
            for k, v in c["logits"].items()}
        run.requests = [
            {"id": (c["cam"], k), "due": due, "sent": sent, "done": done,
             "ok": ok, "outcome": "done" if ok else "failed",
             "label": label}
            for c in cams
            for k, (due, sent, done, ok, label) in enumerate(zip(
                c["due"], c["sent"], c["done"], c["ok"], c["label"]))]
        run.counters["client_outcomes"] = {
            k: sum(c["outcome"].get(k, 0) for c in cams)
            for c in cams for k in c["outcome"]}
        run.missed_ms = (seconds + 10.0) * 1e3
        return run

    def check(self, run: Run) -> Dict[str, Any]:
        """Every frame was answered or is counted failed (a reply is
        matched to its request by sequence number, so order per client
        is exact or the frame fails); the kept logits stay within the
        configuration's share of the plain float32 reference's range,
        on the served weights; nothing compiled inside the window."""
        reference = self.ctx.family_module("reference")

        ref = self.ctx.config["reference"]
        fw = self.filter.fw
        worst = 0.0
        kept = run.counters["kept_logits"]
        for (cam, k), logits in kept.items():
            frames = gen.camera_frames(run.seed, cam,
                                       int(run.traffic["frame_pool"]),
                                       run.traffic["frame_shape"])
            want = reference.forward_logits(fw._params_dev,
                                            frames[k % len(frames)])
            got = np.asarray(logits, np.float32)
            span = float(want.max() - want.min())
            worst = max(worst, float(np.abs(got - want).max()) / span)
        answered = sum(1 for r in run.requests if r["ok"])
        out = {"frames": len(run.requests), "answered": answered,
               "checked": len(kept), "logit_rel_err": worst,
               "client_outcomes": run.counters["client_outcomes"],
               "compiles_in_window": len(run.counters["compiles"])}
        out["correct"] = bool(kept and answered
                              and worst < ref["logit_rtol"]
                              and not run.counters["compiles"])
        return out

    def close(self) -> None:
        from nnstreamer_tpu.query.server import shutdown_server

        if self.pipeline is not None:
            self.pipeline.stop()
            shutdown_server(SERVER_ID)
            self.pipeline = None
