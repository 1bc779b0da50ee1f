"""SLO harness tests: arrival schedules, burn-rate windows, flight
recorder, verdict schema, chaos stages, readiness states, and a
loopback mini-soak — all tier-1-fast on CPU.

The burn-rate tests drive the evaluator with an injected clock and a
private metrics registry (seeded counter/histogram fixtures), so window
math is asserted deterministically, minutes of simulated soak in
milliseconds of test time.
"""

import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu.obs.metrics import REGISTRY, MetricsRegistry
from nnstreamer_tpu.pipeline import Pipeline
from nnstreamer_tpu.query import (QueryConnection, TensorQueryServerSink,
                                  TensorQueryServerSrc, shutdown_server)
from nnstreamer_tpu.slo import (Evaluator, FlightRecorder, LoadGenerator,
                                Objective, SLOMonitor, SLOSpec, demo_spec)
from nnstreamer_tpu.slo.loadgen import (SERVICE_US, constant_schedule,
                                        poisson_schedule)
from nnstreamer_tpu.slo.spec import ERRORS_TOTAL, LATENCY_US, REQUESTS_TOTAL
from nnstreamer_tpu.tensor import TensorBuffer
from nnstreamer_tpu.testing.faults import (ChaosProxy, ChaosSchedule,
                                           ChaosStage)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def tcaps():
    return ("other/tensors,format=static,num_tensors=1,dimensions=4,"
            "types=float32,framerate=0/1")


def serving_pipeline(server_id):
    """Loopback server: serversrc -> transform(x2) -> serversink."""
    from nnstreamer_tpu.elements import TensorTransform

    p = Pipeline(f"server-{server_id}")
    src = TensorQueryServerSrc("qsrc", id=server_id, port=0, caps=tcaps())
    t = TensorTransform("t", mode="arithmetic", option="mul:2")
    sink = TensorQueryServerSink("qsink", id=server_id)
    p.add(src, t, sink)
    p.link(src, t, sink)
    p.play()
    return p, src.bound_port


# ==========================================================================
# arrival schedules (open-loop substrate)
# ==========================================================================

class TestArrivalSchedules:
    def test_poisson_statistics(self):
        import random

        sched = poisson_schedule(200.0, 50.0, random.Random(42))
        n = len(sched)
        # count ~ Poisson(10000): 5 sigma = 500
        assert abs(n - 10000) < 500, n
        assert sched == sorted(sched)
        assert 0 <= sched[0] and sched[-1] < 50.0
        gaps = np.diff(sched)
        assert abs(gaps.mean() - 1 / 200.0) / (1 / 200.0) < 0.05
        # exponential inter-arrivals: coefficient of variation ~ 1
        # (a constant-rate schedule would have cv ~ 0)
        assert 0.9 < gaps.std() / gaps.mean() < 1.1

    def test_poisson_seeded_determinism(self):
        import random

        a = poisson_schedule(50.0, 5.0, random.Random(7))
        b = poisson_schedule(50.0, 5.0, random.Random(7))
        c = poisson_schedule(50.0, 5.0, random.Random(8))
        assert a == b
        assert a != c

    def test_constant_spacing_and_phase(self):
        sched = constant_schedule(10.0, 1.0)
        assert len(sched) == 10
        np.testing.assert_allclose(np.diff(sched), 0.1)
        shifted = constant_schedule(10.0, 1.0, phase=0.03)
        assert shifted[0] == pytest.approx(0.03)


# ==========================================================================
# burn-rate window math (seeded fixtures, injected clock)
# ==========================================================================

def _err_spec(**kw):
    kw.setdefault("window_fast_s", 60.0)
    kw.setdefault("window_slow_s", 600.0)
    kw.setdefault("burn_threshold", 2.0)
    return SLOSpec(name="t", objectives=(
        Objective("err", "error_rate", target=0.99),), **kw)


class TestBurnRateWindows:
    def _minute(self, req, err, n_req, n_err):
        req.inc(n_req)
        err.inc(n_err)

    def _fixture(self, spec=None):
        reg = MetricsRegistry()
        ev = Evaluator(spec or _err_spec(), registry=reg)
        req = reg.counter(REQUESTS_TOTAL, **{"class": "default"})
        err = reg.counter(ERRORS_TOTAL, **{"class": "default"})
        return reg, ev, req, err

    def test_no_traffic_no_breach(self):
        _, ev, _, _ = self._fixture()
        for t in (0, 60, 120):
            e = ev.tick(now=float(t))
        assert not e["breached"]
        assert ev.verdict()["pass"]

    def test_fast_spike_alone_does_not_breach(self):
        """One bad minute (burn 10 in the fast window) inside an
        otherwise healthy run: the slow window never crosses, so no
        breach — the false-positive suppression the multi-window
        design exists for."""
        _, ev, req, err = self._fixture()
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(10):                     # 10 healthy minutes
            t += 60
            self._minute(req, err, 100, 0)
            ev.tick(now=t)
        t += 60                                 # the spike
        self._minute(req, err, 100, 10)
        spike = ev.tick(now=t)
        o = spike["objectives"][0]
        assert o["fast"]["burn_rate"] > 2.0     # fast window IS alight
        assert o["slow"]["burn_rate"] <= 2.0    # slow window is not
        assert not o["breached"]
        for _ in range(5):                      # recovery
            t += 60
            self._minute(req, err, 100, 0)
            ev.tick(now=t)
        v = ev.verdict()
        assert v["pass"] and v["verdict"] == "PASS" and not v["breaches"]

    def test_sustained_burn_breaches_once(self):
        _, ev, req, err = self._fixture()
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(10):
            t += 60
            self._minute(req, err, 100, 0)
            ev.tick(now=t)
        breach_seen = None
        for i in range(6):                      # sustained 10% errors
            t += 60
            self._minute(req, err, 100, 10)
            e = ev.tick(now=t)
            if e["breached"] and breach_seen is None:
                breach_seen = i
        assert breach_seen is not None
        v = ev.verdict()
        assert not v["pass"] and v["verdict"] == "FAIL"
        # onset latching: one sustained episode = ONE breach event
        assert len(v["breaches"]) == 1
        ev_fast = v["breaches"][0]["evidence"]["fast"]
        ev_slow = v["breaches"][0]["evidence"]["slow"]
        assert ev_fast["burn_rate"] > 2.0 and ev_slow["burn_rate"] > 2.0

    def test_startup_blip_unarmed_no_breach(self):
        """Before the slow window outspans the fast one, both cover
        the same data and the multi-window suppression cannot work —
        a startup blip (thundering-herd dial) must NOT breach on the
        first tick; the same sustained burn later must."""
        _, ev, req, err = self._fixture()
        ev.tick(now=0.0)
        self._minute(req, err, 100, 50)     # terrible first minute
        e = ev.tick(now=60.0)
        assert not e["armed"]
        assert not e["breached"]            # identical windows: unarmed
        t = 60.0
        for _ in range(10):                 # clean recovery
            t += 60
            self._minute(req, err, 100, 0)
            e = ev.tick(now=t)
        assert e["armed"]
        assert ev.verdict()["pass"]
        for _ in range(6):                  # NOW a sustained burn
            t += 60
            self._minute(req, err, 100, 50)
            ev.tick(now=t)
        assert not ev.verdict()["pass"]     # armed alerts still fire

    def test_recovery_rearms_breach_onset(self):
        _, ev, req, err = self._fixture(_err_spec(window_fast_s=60.0,
                                                  window_slow_s=120.0))
        ev.tick(now=0.0)
        t = 0.0

        def phase(minutes, bad):
            nonlocal t
            for _ in range(minutes):
                t += 60
                self._minute(req, err, 100, bad)
                ev.tick(now=t)

        phase(3, 0)
        phase(3, 50)      # first episode
        phase(6, 0)       # full recovery (both windows drain)
        phase(3, 50)      # second episode
        assert len(ev.verdict()["breaches"]) == 2

    def test_latency_objective_windowed_p99(self):
        reg = MetricsRegistry()
        spec = SLOSpec(name="lat", objectives=(
            Objective("p99", "latency", target=0.9,
                      threshold_us=100_000.0),),
            window_fast_s=60.0, window_slow_s=600.0)
        ev = Evaluator(spec, registry=reg)
        hist = reg.histogram(LATENCY_US, **{"class": "default"})
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(10):                     # healthy: 1 ms latencies
            t += 60
            for _ in range(100):
                hist.observe(1_000.0)
            ev.tick(now=t)
        e = None
        for _ in range(5):                      # degraded: 60% at 1 s
            t += 60
            for _ in range(40):
                hist.observe(1_000.0)
            for _ in range(60):
                hist.observe(1_000_000.0)
            e = ev.tick(now=t)
        o = e["objectives"][0]
        assert o["breached"]
        # windowed p99 evidence rides along and shows the slow tail
        assert o["fast"]["p99_us"] > 100_000.0
        assert not ev.verdict()["pass"]

    def test_availability_kind_counts_counters(self):
        reg = MetricsRegistry()
        spec = SLOSpec(name="av", objectives=(
            Objective("avail", "availability", target=0.9),),
            window_fast_s=10.0, window_slow_s=20.0)
        ev = Evaluator(spec, registry=reg)
        req = reg.counter(REQUESTS_TOTAL, **{"class": "a"})
        err = reg.counter(ERRORS_TOTAL, **{"class": "a"})
        ev.tick(now=0.0)
        req.inc(10)
        ev.tick(now=10.0)
        assert ev.verdict()["pass"]
        for t in (20.0, 30.0, 40.0):
            req.inc(10)
            err.inc(10)         # nothing answered at all
            e = ev.tick(now=t)
        assert e["objectives"][0]["breached"]

    def test_request_class_restriction(self):
        reg = MetricsRegistry()
        spec = SLOSpec(name="cls", objectives=(
            Objective("gold", "error_rate", target=0.9,
                      request_class="gold"),),
            window_fast_s=10.0, window_slow_s=20.0)
        ev = Evaluator(spec, registry=reg)
        for c in ("gold", "bulk"):
            reg.counter(REQUESTS_TOTAL, **{"class": c})
            reg.counter(ERRORS_TOTAL, **{"class": c})
        ev.tick(now=0.0)
        for t in (10.0, 20.0, 30.0):
            # bulk is on fire; gold is clean — the gold objective must
            # not see bulk's errors
            reg.counter(REQUESTS_TOTAL, **{"class": "bulk"}).inc(10)
            reg.counter(ERRORS_TOTAL, **{"class": "bulk"}).inc(10)
            reg.counter(REQUESTS_TOTAL, **{"class": "gold"}).inc(10)
            ev.tick(now=t)
        assert ev.verdict()["pass"]

    def test_metric_override_reads_element_histograms(self):
        """launch.py --slo on a plain (non-query) pipeline: a latency
        objective can gate the tracer's per-element histograms."""
        reg = MetricsRegistry()
        spec = SLOSpec(name="el", objectives=(
            Objective("sink_p99", "latency", target=0.9,
                      threshold_us=100.0,
                      metric="nns_element_proctime_us",
                      match='element="snk"'),),
            window_fast_s=10.0, window_slow_s=20.0)
        ev = Evaluator(spec, registry=reg)
        good = reg.histogram("nns_element_proctime_us", element="oth")
        bad = reg.histogram("nns_element_proctime_us", element="snk")
        ev.tick(now=0.0)
        for t in (10.0, 20.0, 30.0):
            for _ in range(10):
                good.observe(10.0)      # wrong element: ignored
                bad.observe(10_000.0)   # matched: all over threshold
            e = ev.tick(now=t)
        assert e["objectives"][0]["breached"]


class TestTokenLatencyObjectives:
    """ISSUE 20: the ``ttft``/``itl`` histogram-threshold kinds — spec
    validation, and burn-rate evaluation over the server-side
    ``nns_llm_*`` families via the ``metric`` override (the soak's
    token SLO gate, driven deterministically here)."""

    def test_spec_validation(self):
        for kind in ("ttft", "itl"):
            with pytest.raises(ValueError):
                Objective("t", kind, target=0.9)    # threshold required
        o = Objective("t", "ttft", target=0.9,
                      threshold_us=5_000_000.0,
                      metric="nns_llm_ttft_us")
        assert o.budget == pytest.approx(0.1)
        assert Objective.from_dict(o.to_dict()) == o

    def _fixture(self):
        reg = MetricsRegistry()
        spec = SLOSpec(
            name="tok", window_fast_s=60.0, window_slow_s=600.0,
            burn_threshold=2.0,
            objectives=(
                Objective("ttft", "ttft", target=0.90,
                          threshold_us=100_000.0,
                          metric="nns_llm_ttft_us"),
                Objective("itl", "itl", target=0.90,
                          threshold_us=50_000.0,
                          metric="nns_llm_itl_us"),
            ))
        ev = Evaluator(spec, registry=reg)
        ttft = reg.histogram("nns_llm_ttft_us", **{"class": "silver"})
        itl = reg.histogram("nns_llm_itl_us", **{"class": "silver"})
        return ev, ttft, itl

    def test_sustained_slow_first_tokens_breach_ttft_only(self):
        """First tokens going over budget breach the ``ttft``
        objective; healthy inter-token gaps keep ``itl`` green — the
        verdict names WHICH token contract broke."""
        ev, ttft, itl = self._fixture()
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(10):                 # healthy: 10 ms / 5 ms
            t += 60
            for _ in range(50):
                ttft.observe(10_000.0)
                itl.observe(5_000.0)
            ev.tick(now=t)
        assert ev.verdict()["pass"]
        for _ in range(6):                  # first tokens now take 1 s
            t += 60
            for _ in range(50):
                ttft.observe(1_000_000.0)
                itl.observe(5_000.0)
            ev.tick(now=t)
        v = ev.verdict()
        assert not v["pass"]
        assert [b for b in v["breaches"] if b["objective"] == "ttft"]
        assert not [b for b in v["breaches"]
                    if b["objective"] == "itl"]
        row = next(o for o in v["objectives"] if o["name"] == "ttft")
        assert row["final"]["fast"]["p99_us"] > 100_000.0

    def test_itl_breaches_on_sustained_stall(self):
        ev, ttft, itl = self._fixture()
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(10):
            t += 60
            for _ in range(50):
                ttft.observe(10_000.0)
                itl.observe(5_000.0)
            ev.tick(now=t)
        for _ in range(6):                  # decode plane stalling
            t += 60
            for _ in range(50):
                ttft.observe(10_000.0)
                itl.observe(400_000.0)
            ev.tick(now=t)
        v = ev.verdict()
        assert not v["pass"]
        assert [b for b in v["breaches"] if b["objective"] == "itl"]
        assert not [b for b in v["breaches"]
                    if b["objective"] == "ttft"]


# ==========================================================================
# verdict schema
# ==========================================================================

class TestVerdictSchema:
    def test_verdict_json_schema(self):
        _, ev, req, err = TestBurnRateWindows()._fixture()
        ev.tick(now=0.0)
        req.inc(50)
        ev.tick(now=30.0)
        v = ev.verdict()
        assert v["verdict"] in ("PASS", "FAIL")
        assert isinstance(v["pass"], bool)
        assert v["slo"] == "t"
        assert v["windows"] == {"fast_s": 60.0, "slow_s": 600.0}
        assert v["ticks"] == 2 and v["duration_s"] == pytest.approx(30.0)
        (obj,) = v["objectives"]
        for key in ("name", "kind", "target", "worst_burn_rate",
                    "breaches", "final"):
            assert key in obj, obj
        for win in ("fast", "slow"):
            for key in ("window_s", "total", "bad", "bad_fraction",
                        "burn_rate"):
                assert key in obj["final"][win]
        assert v["breaches"] == []
        json.dumps(v)               # machine-readable end to end

    def test_spec_json_round_trip(self, tmp_path):
        spec = demo_spec(60.0)
        path = str(tmp_path / "spec.json")
        spec.dump(path)
        assert SLOSpec.load(path).to_dict() == spec.to_dict()

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="window_fast_s"):
            _err_spec(window_fast_s=600.0, window_slow_s=60.0)
        with pytest.raises(ValueError, match="target"):
            Objective("x", "error_rate", target=1.5)
        with pytest.raises(ValueError, match="kind"):
            Objective("x", "nope", target=0.9)
        with pytest.raises(ValueError, match="threshold_us"):
            Objective("x", "latency", target=0.9)


# ==========================================================================
# flight recorder
# ==========================================================================

class TestFlightRecorder:
    def _breaching_evaluator(self, reg, recorder):
        spec = SLOSpec(name="fr", objectives=(
            Objective("err", "error_rate", target=0.9),),
            window_fast_s=10.0, window_slow_s=20.0)
        ev = Evaluator(spec, registry=reg,
                       on_breach=recorder.on_breach)
        ev.on_tick = recorder.record
        return ev

    def test_dump_on_breach_bundle(self, tmp_path):
        from nnstreamer_tpu.pipeline.tracing import Tracer

        reg = MetricsRegistry()
        tracer = Tracer(spans=True)
        tracer.enter("hot_element", None)
        tracer.exit()
        rec = FlightRecorder(str(tmp_path), tracer=tracer, registry=reg)
        ev = self._breaching_evaluator(reg, rec)
        req = reg.counter(REQUESTS_TOTAL, **{"class": "default"})
        err = reg.counter(ERRORS_TOTAL, **{"class": "default"})
        ev.tick(now=0.0)
        for t in (10.0, 20.0, 30.0):
            req.inc(10)
            err.inc(10)
            ev.tick(now=t)
        assert len(rec.dumps) == 1
        bundle = rec.dumps[0]
        names = sorted(os.listdir(bundle))
        assert names == ["breach.json", "manifest.json",
                         "metrics_final.json",
                         "metrics_timeline.jsonl", "trace.json"]
        trace = json.load(open(os.path.join(bundle, "trace.json")))
        spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert any(e["name"] == "hot_element" for e in spans)
        breach = json.load(open(os.path.join(bundle, "breach.json")))
        assert breach["event"]["objective"] == "err"
        assert breach["event"]["evidence"]["fast"]["burn_rate"] > 2.0
        manifest = json.load(open(os.path.join(bundle,
                                               "manifest.json")))
        assert manifest["recorded_ticks"] >= 1
        assert manifest["span_ring"]["capacity"] > 0
        timeline = [json.loads(ln) for ln in
                    open(os.path.join(bundle,
                                      "metrics_timeline.jsonl"))]
        assert timeline and "burn" in timeline[-1]

    def test_max_dumps_cap(self, tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(str(tmp_path), registry=reg, max_dumps=1)
        ev = self._breaching_evaluator(reg, rec)
        req = reg.counter(REQUESTS_TOTAL, **{"class": "default"})
        err = reg.counter(ERRORS_TOTAL, **{"class": "default"})
        ev.tick(now=0.0)
        t = 0.0
        for _ in range(3):      # breach / recover / breach again
            for _ in range(3):
                t += 10
                req.inc(10)
                err.inc(10)
                ev.tick(now=t)
            for _ in range(4):
                t += 10
                req.inc(10)
                ev.tick(now=t)
        assert len(ev.verdict()["breaches"]) >= 2
        assert len(rec.dumps) == 1      # capped; no disk fill

    def test_ring_is_bounded(self, tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(str(tmp_path), registry=reg, capacity=16)
        for _ in range(100):
            rec.record()
        bundle = rec.dump("manual")
        timeline = list(open(os.path.join(bundle,
                                          "metrics_timeline.jsonl")))
        assert len(timeline) == 16

    def _session_obs(self, reg):
        from nnstreamer_tpu.llm.tokenobs import TokenObs

        class _Phases:
            def totals_ns(self):
                return {"decode": 1_000}

        class _Sess:
            key, qos, extra, obs = "s0", "gold", {}, None

        now = [1_000_000]
        tobs = TokenObs(_Phases(), clock_ns=lambda: now[0],
                        registry=reg, labels={"element": "llm",
                                              "pipeline": "p"})
        s = _Sess()
        tobs.on_admit(s)
        now[0] = 3_000_000
        tobs.on_token(s)
        now[0] = 5_000_000
        tobs.on_terminal(s, "stop")
        return tobs

    def test_session_obs_bundle_grows_timeline_lanes(self, tmp_path):
        """ISSUE 20: with a TokenObs wired, bundles carry
        ``sessions.json`` (records + blame) and the trace gains the
        session lanes — merged into the tracer's export when one
        exists, standalone otherwise."""
        from nnstreamer_tpu.pipeline.tracing import Tracer

        reg = MetricsRegistry()
        tracer = Tracer(spans=True)
        tracer.enter("hot_element", None)
        tracer.exit()
        rec = FlightRecorder(str(tmp_path / "a"), tracer=tracer,
                             registry=reg,
                             session_obs=self._session_obs(reg))
        rec.record()
        bundle = rec.dump("manual")
        sessions = json.load(open(os.path.join(bundle,
                                               "sessions.json")))
        assert sessions["sessions"][0]["cause"] == "stop"
        assert sessions["sessions"][0]["ttft_us"] == 2_000.0
        assert sessions["blame"]["conserved_pct"] == 100.0
        trace = json.load(open(os.path.join(bundle, "trace.json")))
        names = {e["name"] for e in trace["traceEvents"]}
        assert "hot_element" in names          # tracer spans kept
        assert "ttft" in names                 # session lanes merged
        # metadata still sorts ahead of every span after the merge
        phases = [e["ph"] for e in trace["traceEvents"]]
        assert "M" not in phases[phases.index("X"):]

    def test_session_obs_without_tracer_still_writes_trace(self,
                                                           tmp_path):
        reg = MetricsRegistry()
        rec = FlightRecorder(str(tmp_path / "b"), registry=reg,
                             session_obs=self._session_obs(reg))
        rec.record()
        bundle = rec.dump("manual")
        trace = json.load(open(os.path.join(bundle, "trace.json")))
        assert any(e.get("name") == "decode"
                   for e in trace["traceEvents"])


# ==========================================================================
# chaos schedule
# ==========================================================================

class TestChaosSchedule:
    def test_parse_grammar(self):
        proxy = ChaosProxy(("127.0.0.1", 1))
        try:
            sched = ChaosSchedule.parse(
                proxy, "5:kill; 10:blackhole:3 ;12:delay:2:0.25")
            assert [s.fault for s in sched.stages] == \
                ["kill", "blackhole", "delay"]
            assert sched.stages[1].duration == 3.0
            assert sched.stages[2].value == 0.25
            with pytest.raises(ValueError, match="unknown fault"):
                ChaosSchedule.parse(proxy, "1:meteor")
            with pytest.raises(ValueError, match="at_s:fault"):
                ChaosSchedule.parse(proxy, "nope")
        finally:
            proxy.close()

    @pytest.mark.chaos
    def test_stages_apply_and_clear(self):
        proxy = ChaosProxy(("127.0.0.1", 1))
        sched = ChaosSchedule(proxy, [
            ChaosStage(0.05, "blackhole", duration=0.15),
            ChaosStage(0.10, "delay", duration=0.08, value=0.5),
            ChaosStage(0.12, "disconnect_once"),
        ])
        try:
            sched.start()
            deadline = time.monotonic() + 5
            while len(sched.log) < 5 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert [
                (e["action"], e["fault"]) for e in sched.log] == [
                ("apply", "blackhole"), ("apply", "delay"),
                ("apply", "disconnect_once"), ("clear", "delay"),
                ("clear", "blackhole")]
            assert proxy.blackhole is False and proxy.delay == 0.0
            assert proxy.disconnect_once is True    # one-shot stays armed
        finally:
            sched.stop()
            proxy.close()

    @pytest.mark.chaos
    def test_stop_mid_schedule_clears_toggles(self):
        proxy = ChaosProxy(("127.0.0.1", 1))
        sched = ChaosSchedule(proxy, [
            ChaosStage(0.02, "corrupt", duration=60.0),
            ChaosStage(30.0, "kill"),
        ])
        try:
            sched.start()
            deadline = time.monotonic() + 5
            while not proxy.corrupt and time.monotonic() < deadline:
                time.sleep(0.01)
            assert proxy.corrupt
            sched.stop()            # returns promptly, leaves it clean
            assert proxy.corrupt is False
        finally:
            proxy.close()


# ==========================================================================
# /healthz readiness states
# ==========================================================================

class TestHealthz:
    def test_health_report_aggregates_worst(self):
        from nnstreamer_tpu.obs.httpd import (health_report,
                                              register_health_source,
                                              unregister_health_source)

        t1 = register_health_source(lambda: "serving", label="a")
        t2 = register_health_source(lambda: "degraded", label="b")
        try:
            rep = health_report()
            assert rep["state"] == "degraded" and not rep["ready"]
            assert rep["sources"]["a"] == "serving"
        finally:
            unregister_health_source(t2)
        rep = health_report()
        assert rep["sources"].get("a") == "serving"
        unregister_health_source(t1)

    def test_pipeline_lifecycle_states(self):
        from nnstreamer_tpu.obs.httpd import health_report
        from nnstreamer_tpu.pipeline import AppSrc
        from nnstreamer_tpu.elements import TensorSink

        p = Pipeline("hz-pipe")
        src = AppSrc("src", caps=tcaps())
        sink = TensorSink("out")
        p.add(src, sink)
        p.link(src, sink)
        assert p.health_state() == "starting"
        src.push_buffer(TensorBuffer(
            tensors=[np.zeros(4, np.float32)]))
        src.end_of_stream()
        p.play()
        try:
            assert p.health_state() == "serving"
            assert health_report()["sources"][
                "pipeline:hz-pipe"] == "serving"
            p.wait(timeout=15)
        finally:
            p.stop()
        assert p.health_state() == "draining"
        assert "pipeline:hz-pipe" not in health_report()["sources"]

    def test_endpoint_serves_readiness_json(self):
        import urllib.error
        import urllib.request

        from nnstreamer_tpu.obs.httpd import (register_health_source,
                                              start_metrics_server,
                                              stop_metrics_server,
                                              unregister_health_source)

        server = start_metrics_server(0)
        token = None
        try:
            port = server.server_address[1]
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5).read()
            rep = json.loads(body)
            assert rep["ready"] is True and "state" in rep
            token = register_health_source(lambda: "degraded",
                                           label="t-deg")
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=5)
            assert exc.value.code == 503
            rep = json.loads(exc.value.read())
            assert rep["state"] == "degraded"
        finally:
            if token is not None:
                unregister_health_source(token)
            stop_metrics_server()

    def test_degraded_failover_connection(self):
        from nnstreamer_tpu.query.client import FailoverConnection

        conn = FailoverConnection([("127.0.0.1", 1)], timeout=0.2,
                                  max_retries=1)
        assert conn.degraded()      # never connected = degraded


# ==========================================================================
# query-layer loadgen hooks
# ==========================================================================

SERVER_ID = 94


@pytest.fixture
def loopback_server():
    p, port = serving_pipeline(SERVER_ID)
    yield p, port
    p.stop()
    shutdown_server(SERVER_ID)


class TestQueryHooks:
    def test_on_outcome_hook_with_class_tag(self, loopback_server):
        _, port = loopback_server
        conn = QueryConnection("127.0.0.1", port, timeout=5.0)
        outcomes = []
        conn.on_outcome = lambda c, lat, ok: outcomes.append(
            (c, lat, ok))
        conn.connect()
        try:
            buf = TensorBuffer(tensors=[np.ones(4, np.float32)])
            buf.extra["nns_class"] = "gold"
            out = conn.query(buf)
            np.testing.assert_array_equal(
                out.np(0), np.full(4, 2.0, np.float32))
            untagged = TensorBuffer(tensors=[np.ones(4, np.float32)])
            conn.query(untagged)
        finally:
            conn.close()
        assert [(c, ok) for c, _, ok in outcomes] == [
            ("gold", True), ("default", True)]
        assert all(lat > 0 for _, lat, _ in outcomes)

    def test_on_outcome_records_failures(self):
        proxy = ChaosProxy(("127.0.0.1", 1))    # dead upstream
        proxy.blackhole = True                  # accept, swallow bytes
        conn = QueryConnection("127.0.0.1", proxy.port, timeout=0.6,
                               max_retries=1)
        outcomes = []
        conn.on_outcome = lambda c, lat, ok: outcomes.append((c, ok))
        try:
            conn.connect()
            buf = TensorBuffer(tensors=[np.ones(4, np.float32)])
            with pytest.raises((TimeoutError, ConnectionError)):
                conn.query(buf)
        finally:
            conn.close()
            proxy.close()
        assert outcomes == [("default", False)]

    def test_server_connection_gauges(self, loopback_server):
        _, port = loopback_server
        conn = QueryConnection("127.0.0.1", port, timeout=5.0)
        conn.connect()
        try:
            deadline = time.monotonic() + 5
            key = f'nns_query_server_clients{{port="{port}"}}'
            while time.monotonic() < deadline:
                report = REGISTRY.report()
                if report.get(key, 0) >= 1:
                    break
                time.sleep(0.02)
            assert report[key] >= 1, report
            assert report[
                f'nns_query_server_accepted_total{{port="{port}"}}'] >= 1
        finally:
            conn.close()


# ==========================================================================
# loadgen accounting (review regressions)
# ==========================================================================

ACCT_ID = 97


class TestLoadGenAccounting:
    @pytest.mark.chaos
    def test_timeouts_burn_the_latency_budget(self):
        """Failed requests must land in the latency histogram at their
        elapsed (>= timeout) time: a stalled server's worst latencies
        must not vanish from a latency-only SLO."""
        proxy = ChaosProxy(("127.0.0.1", 1))
        proxy.blackhole = True          # accept, swallow every byte
        reg = MetricsRegistry()
        gen = LoadGenerator("127.0.0.1", proxy.port, clients=2,
                            rate_hz=3.0, duration_s=0.7, timeout=0.4,
                            seed=5, registry=reg)
        try:
            s = gen.run(warmup_s=0.1)
        finally:
            proxy.close()
        assert s["sent"] > 0 and s["errors"] == s["sent"]
        snap = reg.report()[f'{LATENCY_US}{{class="default"}}']
        assert snap["count"] == s["sent"]
        assert snap["min"] >= 300_000.0     # ~the 0.4 s timeout, in us

    def test_summary_quantiles_are_per_run(self):
        """Two generators sharing one registry (soak loops in one
        process): the second run's summary must not blend the first
        run's distribution."""
        proxy = ChaosProxy(("127.0.0.1", 1))
        proxy.blackhole = True
        reg = MetricsRegistry()
        slow = LoadGenerator("127.0.0.1", proxy.port, clients=2,
                             rate_hz=3.0, duration_s=0.6, timeout=0.4,
                             seed=5, registry=reg)
        s1 = slow.run(warmup_s=0.1)
        proxy.close()
        assert s1["latency_us"]["p50"] >= 300_000.0
        p, port = serving_pipeline(ACCT_ID)
        try:
            fast = LoadGenerator("127.0.0.1", port, clients=2,
                                 rate_hz=5.0, duration_s=0.8,
                                 timeout=3.0, seed=6, registry=reg)
            s2 = fast.run(warmup_s=0.2)
        finally:
            p.stop()
            shutdown_server(ACCT_ID)
        assert s2["errors"] == 0 and s2["sent"] > 0
        # loopback p50 is single-digit ms; blended with the first
        # run's 400 ms timeouts it would sit far above this bound
        assert s2["latency_us"]["p50"] < 100_000.0, (s1, s2)


# ==========================================================================
# end-to-end mini-soak (loopback, one injected disconnect, < 10 s)
# ==========================================================================

MINI_ID = 95


@pytest.mark.chaos
class TestMiniSoak:
    def test_mini_soak_with_disconnect(self):
        p, port = serving_pipeline(MINI_ID)
        proxy = ChaosProxy(("127.0.0.1", port))
        sched = ChaosSchedule(proxy,
                              [ChaosStage(0.8, "disconnect_once")])
        reg = MetricsRegistry()
        spec = demo_spec(duration_s=2.0)
        ev = Evaluator(spec, registry=reg)
        monitor = SLOMonitor(ev, tick_s=0.25)
        gen = LoadGenerator("127.0.0.1", proxy.port, clients=8,
                            rate_hz=4.0, duration_s=2.0,
                            schedule="poisson", seed=7, timeout=3.0,
                            registry=reg,
                            classes=(("interactive", 0.5),
                                     ("batch", 0.5)))
        try:
            monitor.start()
            sched.start()
            summary = gen.run(warmup_s=0.3)
        finally:
            monitor.stop(final_tick=True)
            sched.stop()
            proxy.close()
            p.stop()
            shutdown_server(MINI_ID)
        assert summary["peak_live_clients"] == 8
        assert summary["sent"] > 20
        assert summary["error_fraction"] < 0.25
        # both request classes saw traffic
        for cls in ("interactive", "batch"):
            key = f'{REQUESTS_TOTAL}{{class="{cls}"}}'
            assert reg.report().get(key, 0) > 0
        # the disconnect fired and the run still PASSES its SLO (the
        # client reconnects inside the request budget)
        assert [e["fault"] for e in sched.log] == ["disconnect_once"]
        v = ev.verdict()
        assert v["pass"], json.dumps(v, indent=2)
        assert v["ticks"] >= 4
        # both latency families populated: schedule-anchored (slo) and
        # service (query hook) histograms
        report = reg.report()
        assert any(k.startswith(LATENCY_US) for k in report)
        assert any(k.startswith(SERVICE_US) for k in report)


# ==========================================================================
# tier-1 soak smoke (perf-marked: the CI gate for ROADMAP item 5)
# ==========================================================================

SMOKE_ID = 96


@pytest.mark.perf
@pytest.mark.chaos
@pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="30 s multi-client loopback soak needs >=2 cores: clients "
           "and server serialize on one core, so the schedule-anchored "
           "latencies gate scheduler queueing, not the serving plane")
class TestSoakSmoke:
    def test_soak_smoke_chaos_no_false_positives_no_leaks(self):
        """30 s loopback soak (NNS_SOAK_SMOKE_S overrides) through a
        kill + a disconnect: gates on (1) a PASS verdict — the
        multi-window logic must not page on recoverable chaos, (2) zero
        PR 4 sanitizer findings (lock-order / aliasing) with the
        runtime sanitizer armed, (3) no slab leak in the shared pool."""
        import gc

        from nnstreamer_tpu.analysis import sanitizer
        from nnstreamer_tpu.tensor.buffer import default_pool

        duration = float(os.environ.get("NNS_SOAK_SMOKE_S", "30"))
        sanitizer.reset()
        sanitizer.enable(strict=False)
        try:
            p, port = serving_pipeline(SMOKE_ID)
            proxy = ChaosProxy(("127.0.0.1", port))
            sched = ChaosSchedule(proxy, [
                ChaosStage(duration * 0.35, "kill"),
                ChaosStage(duration * 0.60, "disconnect_once")])
            reg = MetricsRegistry()
            # CI-grade spec: same windows as the demo but budgets sized
            # for a GIL-shared loopback under a full pytest process —
            # the no-false-positive property must hold on a loaded CI
            # box, not just an idle one
            fast = max(2.0, duration / 6.0)
            spec = SLOSpec(
                name="soak-smoke", window_fast_s=fast,
                window_slow_s=fast * 10.0, burn_threshold=2.0,
                tick_s=max(0.25, fast / 10.0),
                objectives=(
                    Objective("availability", "availability",
                              target=0.95),
                    Objective("error_rate", "error_rate", target=0.90),
                    Objective("p99_latency", "latency", target=0.80,
                              threshold_us=500_000.0)))
            ev = Evaluator(spec, registry=reg)
            monitor = SLOMonitor(ev)
            gen = LoadGenerator("127.0.0.1", proxy.port, clients=32,
                                rate_hz=2.0, duration_s=duration,
                                schedule="poisson", seed=11,
                                timeout=2.0, registry=reg)
            try:
                monitor.start()
                sched.start()
                summary = gen.run()
            finally:
                monitor.stop(final_tick=True)
                sched.stop()
                proxy.close()
                p.stop()
                shutdown_server(SMOKE_ID)
            v = ev.verdict()
            # (1) zero false positives through recoverable chaos
            assert v["pass"], json.dumps(v, indent=2)
            assert summary["peak_live_clients"] == 32
            assert summary["sent"] > duration * 32 * 2.0 * 0.5
            assert [e["fault"] for e in sched.log] == \
                ["kill", "disconnect_once"]
            # (2) sanitizer: no lock-order inversions, no aliasing
            assert sanitizer.findings() == [], sanitizer.report()
            # (3) no leaked slabs: after teardown + collection the
            # shared pool has no stuck pending slabs from this soak
            gc.collect()
            assert default_pool().stats["pending"] <= 4, \
                default_pool().stats
        finally:
            sanitizer.disable()
            sanitizer.reset()


# ==========================================================================
# staged endpoint liveness check (tools/soak.py diagnose_endpoint)
# ==========================================================================

class TestEndpointDiagnosis:
    def test_live_query_server_all_stages_pass(self, loopback_server):
        import soak

        _, port = loopback_server
        d = soak.diagnose_endpoint("127.0.0.1", port, timeout=5.0)
        assert d["ok"] and d["stage_failed"] is None
        for stage in ("dns", "connect", "rtt", "throughput"):
            assert d["stages"][stage]["ok"], d
        assert d["stages"]["rtt"]["rtt_ms_p50"] > 0
        assert d["stages"]["throughput"]["MBps"] > 0

    def test_connect_failure_with_retries(self):
        import soak

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()                    # nothing listens here now
        t0 = time.monotonic()
        d = soak.diagnose_endpoint("127.0.0.1", port, timeout=0.5,
                                   retries=2, backoff=0.05)
        assert not d["ok"]
        assert d["stage_failed"] == "connect"
        assert d["attempts"] == 3
        assert d["stages"]["dns"]["ok"]
        assert time.monotonic() - t0 < 10

    def test_dns_failure(self):
        import soak

        d = soak.diagnose_endpoint(
            "no-such-host-xyz.invalid", 80, timeout=0.5)
        assert not d["ok"] and d["stage_failed"] == "dns"

    def test_tcp_but_not_query_server_fails_rtt(self):
        import soak

        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        accepted = []
        th = threading.Thread(
            target=lambda: accepted.append(lst.accept()),
            daemon=True)
        th.start()
        try:
            d = soak.diagnose_endpoint(
                "127.0.0.1", lst.getsockname()[1], timeout=0.5)
            assert not d["ok"] and d["stage_failed"] == "rtt"
            assert d["stages"]["connect"]["ok"]
        finally:
            lst.close()
            for conn, _ in accepted:
                conn.close()


# ==========================================================================
# overload protection (PR 7): flood chaos, QoS-tiered shed accounting,
# bounded serving plane
# ==========================================================================

class _Slow5ms:
    """Chain-path delay element factory is overkill for a test: a gated
    consumer on a RAW QueryServer gives a deterministic service time."""


def slow_serving_server(queue_depth=64, service_s=0.004):
    """Raw QueryServer + echo consumer with a fixed service time: the
    deterministic capacity (1/service_s rps) the overload tests drive
    past."""
    from nnstreamer_tpu.query.server import QueryServer

    srv = QueryServer(queue_depth=queue_depth)
    srv.set_caps_string(tcaps())

    def _run():
        import queue as _q
        while not srv._stop.is_set():
            try:
                buf = srv.incoming.get(timeout=0.1)
            except _q.Empty:
                continue
            # deterministic service time: Event.wait as the timer so a
            # close() mid-sleep returns promptly
            srv._stop.wait(service_s)
            out = TensorBuffer(
                tensors=[np.asarray(buf.tensors[0]) * 2], pts=buf.pts)
            out.extra.update(buf.extra)
            srv.reply(out)

    threading.Thread(target=_run, daemon=True,
                     name="slow-echo-consumer").start()
    return srv


class TestOverloadInvariants:
    def test_qos_assignment_largest_remainder(self):
        gen = LoadGenerator(
            "127.0.0.1", 1, clients=64, rate_hz=1.0, duration_s=1.0,
            classes=(("gold", 1.0), ("silver", 2.0), ("bronze", 5.0)),
            qos=True, registry=MetricsRegistry())
        assignment = gen._qos_assignment()
        from collections import Counter
        assert Counter(assignment) == {"gold": 8, "silver": 16,
                                       "bronze": 40}

    def test_flood_chaos_bounded_queue_no_silent_drops_no_leaks(self):
        """The flood fault against a bounded shedding server: incoming
        depth never exceeds the bound, every answer the flood saw was
        a reply or an explicit T_SHED, bronze shed on the server, and
        the slab pool reclaims everything (zero leaked slabs)."""
        import gc

        from nnstreamer_tpu.tensor.buffer import default_pool
        from nnstreamer_tpu.testing.faults import QueryFlood

        srv = slow_serving_server(queue_depth=16, service_s=0.003)
        flood = QueryFlood(("127.0.0.1", srv.port), conns=6).start()
        try:
            deadline = time.monotonic() + 8.0
            while time.monotonic() < deadline:
                if sum(srv.counters()["shed"].values()) >= 20:
                    break
                time.sleep(0.05)
            stats = flood.stop()
            assert stats["sent"] > 0
            # bounded: the hard queue bound held throughout
            assert srv.peak_depth <= srv.queue_depth
            # tiered: flood connections declared bronze, and bronze is
            # what shed
            counters = srv.counters()
            assert counters["shed"]["bronze"] >= 20
            assert counters["shed"]["gold"] == 0
            # no silent drops: everything the flood got back was a
            # REPLY or an explicit T_SHED, and the server's own
            # bookkeeping covers every frame it read
            assert stats["sheds"] > 0
            read = (sum(counters["admitted"].values())
                    + sum(counters["shed"].values()))
            assert read >= stats["replies"] + stats["sheds"]
        finally:
            flood.stop()
            srv.close()
        # zero leaked slabs: after the flood and teardown settle, no
        # slab stays parked with live external views.  Settle loop:
        # the consumer thread's last buffer local pins one slab until
        # the thread notices close() and exits.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            gc.collect()
            if default_pool().stats["pending"] == 0:
                break
            time.sleep(0.1)
        assert default_pool().stats["pending"] == 0

    def test_loadgen_qos_sheds_bronze_first_counters_match(self):
        """Open-loop QoS-mode loadgen at ~2x a slow server's capacity:
        bronze absorbs the shedding, gold is untouched, client-observed
        sheds equal the server's shed counters exactly, no errors, no
        breaker trips."""
        import gc

        from nnstreamer_tpu.query.resilience import STATS
        from nnstreamer_tpu.tensor.buffer import default_pool

        # 48 concurrent connections against a 5 ms server: up to 48
        # frames outstanding, so the queue crosses bronze's arm
        # watermark (64 * 0.45 = 28.8) but can never reach gold's
        # (57.6) — per-worker in-flight is 1, so depth <= clients
        srv = slow_serving_server(queue_depth=64, service_s=0.005)
        stats_before = STATS.snapshot()
        registry = MetricsRegistry()
        gen = LoadGenerator(
            "127.0.0.1", srv.port, clients=48, rate_hz=15.0,
            duration_s=1.5, schedule="constant", seed=7,
            timeout=10.0, registry=registry,
            classes=(("gold", 1.0), ("silver", 2.0), ("bronze", 5.0)),
            qos=True)
        try:
            summary = gen.run(warmup_s=0.3)
        finally:
            srv.close()
        assert summary["qos"] is True
        assert summary["errors"] == 0, summary
        # offered ~720 rps vs ~200 rps capacity: sheds happened
        assert summary["shed"] > 0, summary
        by_class = summary["shed_by_class"]
        # bronze sheds first; gold never reaches its 0.9 watermark
        assert by_class.get("bronze", 0) > 0
        assert by_class.get("gold", 0) == 0, summary
        assert by_class.get("bronze", 0) >= by_class.get("silver", 0)
        # client-observed sheds == server shed counters (every refusal
        # was an explicit T_SHED, none lost, none silent)
        srv_shed = {c: n for c, n in srv.counters()["shed"].items() if n}
        cli_shed = {c: n for c, n in by_class.items() if n}
        assert srv_shed == cli_shed
        # shed is not failure: zero breaker transitions
        delta = STATS.delta(stats_before)
        assert delta.get("breaker.open", 0) == 0
        # the registry's shed family carries the same per-class counts
        from nnstreamer_tpu.slo.loadgen import SHED_TOTAL
        for cls, n in cli_shed.items():
            assert registry.counter(SHED_TOTAL,
                                    **{"class": cls}).value == n
        # bounded pool: nothing leaked across the burst (settle loop —
        # the echo consumer's last buffer local pins one slab until
        # the thread notices close() and exits)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            gc.collect()
            if default_pool().stats["pending"] == 0:
                break
            time.sleep(0.1)
        assert default_pool().stats["pending"] == 0

    def test_shed_latency_excluded_from_admitted_histogram(self):
        """Shed requests must not contribute to the admitted-traffic
        latency distribution (a fast shed would flatter p99; a slow one
        would slander it)."""
        from nnstreamer_tpu.query.overload import AdmissionController
        from nnstreamer_tpu.query.server import QueryServer

        class _ShedAll:
            def decide(self, qos, depth, capacity):
                return 0.01

        srv = QueryServer(queue_depth=8,
                          admission=AdmissionController(policy=_ShedAll()))
        srv.set_caps_string(tcaps())
        registry = MetricsRegistry()
        gen = LoadGenerator(
            "127.0.0.1", srv.port, clients=2, rate_hz=20.0,
            duration_s=0.5, schedule="constant", seed=3,
            timeout=5.0, registry=registry,
            classes=(("bronze", 1.0),), qos=True)
        try:
            summary = gen.run(warmup_s=0.2)
        finally:
            srv.close()
        assert summary["shed"] == summary["sent"] > 0
        assert summary["errors"] == 0
        # the admitted-latency histogram saw NOTHING
        hist = registry.histogram(LATENCY_US, **{"class": "bronze"})
        assert hist.count == 0
