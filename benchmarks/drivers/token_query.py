"""Serving path: token streams over the query wire.

    tensor_query_serversrc ! tensor_llm ! tensor_query_serversink

in this process (it holds the chip), ``TokenStreamClient``s in child
processes.  The element is built from the configuration file's ``model``
(its ``custom=`` grammar) and ``element`` (its properties) and warms
itself in ``start``; nothing else is warmed.  The program contributes
the pipeline, the engine's counters and its ``PhaseClock``; every time
is the clients' or this file's own.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import traffic as gen
from benchmarks.children import Children
from benchmarks.record import Run, raise_if_failed

#: server table id of the benchmark's pipeline
SERVER_ID = 4722
#: seconds between the children being ready and the window opening
LEAD_S = 0.5
SAMPLE_S = 0.1
#: tokens a stream cut at the run's end must have served to be judged
MIN_JUDGED = 8


class Driver:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.model = ctx.config["model"]
        self.pipeline = None

    # -- set-up ---------------------------------------------------------
    def open(self) -> None:
        """Build and start the pipeline; ``tensor_llm.start`` builds the
        weights from the seed and warms every shape it serves."""
        from nnstreamer_tpu import native, parse_launch
        from nnstreamer_tpu.llm.element import REQ_HEADER

        t = time.monotonic()
        # the wire's CRC builds ``native/libnnstw.so`` on first use (a
        # ``make`` of about a second in a fresh checkout): here, in
        # set-up, and not under the window's first requests, which at
        # 64 requests/s queue behind it and are shed
        native.available()
        self.frame_len = REQ_HEADER + self.model["max_seq"]
        custom = ",".join(f"{k}:{v}" for k, v in self.model.items())
        props = " ".join(f"{k}={v}"
                         for k, v in self.ctx.config["element"].items())
        self.pipeline = parse_launch(
            f"tensor_query_serversrc name=qsrc id={SERVER_ID} port=0 "
            "caps=other/tensors,format=static,num_tensors=1,"
            f"dimensions={self.frame_len},types=int32,framerate=0/1 ! "
            f"tensor_llm name=llm custom={custom} seed={self.ctx.seed} "
            f"{props} id={SERVER_ID} ! "
            f"tensor_query_serversink id={SERVER_ID}")
        self.pipeline.play()
        self.llm = self.pipeline.get("llm")
        self.engine, self.pool = self.llm.engine, self.llm.pool
        self.port = self.pipeline.get("qsrc").bound_port
        self.ctx.setup["build_and_warm_s"] = time.monotonic() - t

    # -- one window -----------------------------------------------------
    def _plans(self, mix: Dict[str, Any], seed: int, seconds: float
               ) -> List[Dict[str, Any]]:
        base = {"host": "127.0.0.1", "port": self.port, "seed": seed,
                "vocab": self.model["vocab"], "frame_len": self.frame_len,
                "seconds": seconds, "timeout_s": 30.0,
                "token_timeout_s": 30.0, "qos": mix.get("qos")}
        n = int(mix.get("processes", 2))
        if mix["loop"] == "open":
            requests = gen.open_token_requests(mix, seed, seconds)
            return [dict(base, kind="token_open",
                         drain_s=float(mix["drain_s"]),
                         requests=requests[i::n]) for i in range(n)]
        clients = list(range(int(mix["clients"])))
        return [dict(base, kind="token_closed", traffic=mix,
                     ramp_s=float(mix["ramp_s"]), clients=clients[i::n])
                for i in range(n)]

    def _snapshot(self) -> Dict[str, Any]:
        eng, llm = self.engine, self.llm
        return {"t": time.monotonic(), "steps": eng.steps_total,
                "step_tokens": eng.step_tokens, "tokens": eng.tokens_total,
                "prefills": eng.prefills_total,
                "phase_ns": eng.phases.totals_ns(),
                "shed": llm.shed_total, "rejected": llm.rejected_total,
                "evicted": llm.evicted_total,
                "sessions": llm.sessions_total}

    @staticmethod
    def _delta(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        out = {k: b[k] - a[k] for k in a if k != "phase_ns"}
        out["phase_ns"] = {p: b["phase_ns"][p] - a["phase_ns"][p]
                           for p in a["phase_ns"]}
        return out

    def _sample(self, stop: threading.Event, samples: List[tuple]) -> None:
        """Every 100 ms: live sessions, decodable lanes, and the cached
        positions those lanes attend (what a step's keys and values
        cost)."""
        while not stop.wait(SAMPLE_S):
            sessions = self.pool.sessions()
            lanes = [s for s in sessions
                     if not getattr(s, "prefilling", False)]
            samples.append((time.monotonic(), len(sessions), len(lanes),
                            sum(s.pos + 1 for s in lanes)))

    def window(self, mix: Dict[str, Any], seed: int, seconds: float,
               traced: bool) -> Run:
        ctx = self.ctx
        run = ctx.new_run(mix, seed, seconds)
        t = time.monotonic()
        children = Children(self._plans(mix, seed, seconds), ctx.root)
        try:
            children.wait_ready()
            ramp = float(mix.get("ramp_s", 0.0))
            run.t0 = time.monotonic() + LEAD_S + ramp
            run.t1 = run.t0 + seconds
            children.go(run.t0)
            before = self._snapshot()
            ctx.setup["clients_and_ramp_s"] = run.t0 - t
            samples: List[tuple] = []
            stop = threading.Event()
            sampler = threading.Thread(target=self._sample,
                                       args=(stop, samples), daemon=True)
            time.sleep(max(0.0, run.t0 - time.monotonic()))
            first = self._snapshot()
            sampler.start()
            if traced:
                run.trace = self._trace_slice(run, samples)
            time.sleep(max(0.0, run.t1 - time.monotonic()))
            last = self._snapshot()
            stop.set()
            sampler.join()
            drain = float(mix.get("drain_s", 0.0))
            run.requests = children.collect(run.t1 + drain + 45.0)
        finally:
            children.stop()
        raise_if_failed(self.pipeline)
        run.missed_ms = (seconds + float(mix.get("drain_s", 0.0))) * 1e3
        run.counters = self._delta(first, last)
        run.counters["samples"] = samples
        run.counters["slots"] = self.pool.slots
        run.counters["compiles"] = ctx.compiles.between(run.t0, run.t1)
        run.notes.update(self._notes(run, self._delta(before, first)))
        return run

    def _notes(self, run: Run, ramp: Dict[str, Any]) -> Dict[str, Any]:
        """Beside ``memory_peak_bytes``: the bytes of the pool this
        traffic WROTE at its fullest sample (positions cached times the
        family's bytes per position), so a pool that is reserved and
        never written does not pass for a deployment's memory; and what
        a prefill of the ramp took."""
        import jax

        notes: Dict[str, Any] = {}
        samples = run.counters["samples"]
        if samples and run.cost is not None:
            each = run.cost.kv_bytes_per_position(self.model)
            weights = sum(x.nbytes for x in
                          jax.tree_util.tree_leaves(self.engine.params))
            pool = self.pool.cache_bytes()
            written = max(s[3] for s in samples) * each
            notes["memory"] = {
                "weights_bytes": weights, "kv_pool_bytes": pool,
                "kv_bytes_per_position": each,
                "kv_written_bytes_peak": written,
                "kv_written_share_of_pool": written / pool,
                "weights_plus_kv_written_bytes": weights + written}
        if ramp["prefills"]:
            notes["before_window"] = {
                "prefills": ramp["prefills"],
                "prefill_ms_each": ramp["phase_ns"]["prefill"] / 1e6
                / ramp["prefills"]}
        return notes

    def _trace_slice(self, run: Run, samples: List[tuple]
                     ) -> Dict[str, Any]:
        from benchmarks.tracing import trace_middle

        out = trace_middle(run, self.ctx.trace_dir, self._snapshot,
                           self._delta)
        lo, hi = out["slice"]
        out["counters"]["samples"] = [s for s in samples
                                      if lo <= s[0] <= hi]
        return out

    # -- correctness ----------------------------------------------------
    def check(self, run: Run) -> Dict[str, Any]:
        """Every finished stream has exactly its granted length of
        in-vocabulary tokens; a seeded sample, teacher-forced through
        the plain float32 reference on the served weights, has its
        tokens near the top reference logit; nothing compiled inside
        the window."""
        reference = self.ctx.family_module("reference")
        ref = self.ctx.config["reference"]
        vocab = self.model["vocab"]
        done = [r for r in run.requests if r["outcome"] == "done"]
        cut = [r for r in run.requests if r["outcome"] == "cut"]
        wrong_length = sum(1 for r in done
                           if len(r["tokens"]) != r["max_new"])
        outside_vocab = sum(1 for r in done + cut for t in r["tokens"]
                            if not 0 <= t < vocab)
        lengths_ok, in_vocab = not wrong_length, not outside_vocab
        # a stream cut at the run's end is judged on what it had served
        # (at 1.3 s a step no 256-token stream ends inside a window)
        judged = done + [r for r in cut if len(r["tokens"]) >= MIN_JUDGED]
        # a seeded sample: at least ``sampled_streams`` streams, and on
        # until ``judged_tokens_min`` tokens are judged (streams of two
        # tokens would otherwise rest the verdict on a dozen tokens, and
        # the ~2 % that sit outside the slack would fail a run now and
        # then)
        rng = np.random.default_rng(run.seed)
        totals = {"tokens": 0, "near_top": 0, "exact": 0}
        plans = {p["id"]: p for p in self._requests_of(run)}
        sampled = 0
        for i in rng.permutation(len(judged)):
            if sampled >= int(ref["sampled_streams"]) and totals[
                    "tokens"] >= int(ref.get("judged_tokens_min", 0)):
                break
            sampled += 1
            rec = judged[int(i)]
            prompt = gen.prompt_tokens(plans[rec["id"]], vocab)
            got = reference.served_tokens_near_top(
                self.engine.params, self.model, prompt, rec["tokens"],
                float(ref["token_slack"]))
            for k in totals:
                totals[k] += got[k]
        share = totals["near_top"] / max(1, totals["tokens"])
        out = {"streams_done": len(done), "streams_cut": len(cut),
               "lengths_ok": lengths_ok, "in_vocab": in_vocab,
               "sampled": sampled, **totals, "near_top_share": share,
               "compiles_in_window": len(run.counters["compiles"])}
        out["correct"] = bool(sampled and lengths_ok and in_vocab
                              and share >= float(ref["min_share"])
                              and not run.counters["compiles"])
        # what ``correct`` compared, each beside its limit
        out["compared"] = {
            "near_top_share": {"value": share,
                               "limit": f">= {float(ref['min_share'])}"},
            "sampled_streams": {"value": sampled, "limit": ">= 1"},
            "streams_of_another_length": {"value": wrong_length,
                                          "limit": "== 0"},
            "tokens_outside_vocab": {"value": outside_vocab,
                                     "limit": "== 0"},
            "compiles_in_window": {
                "value": len(run.counters["compiles"]), "limit": "== 0"}}
        return out

    def _requests_of(self, run: Run) -> List[Dict[str, Any]]:
        """The plan of every request the run's records name."""
        mix = run.traffic
        if mix["loop"] == "open":
            return gen.open_token_requests(mix, run.seed, run.seconds)
        clients = int(mix["clients"])
        return [gen.closed_token_request(mix, run.seed, r["id"] % clients,
                                         r["id"] // clients)
                for r in run.requests]

    # -- teardown -------------------------------------------------------
    def close(self) -> None:
        from nnstreamer_tpu.query.server import shutdown_server

        if self.pipeline is not None:
            self.pipeline.stop()
            shutdown_server(SERVER_ID)
            self.pipeline = None
