"""Utilization attribution profiler (obs/attrib.py + obs/profile.py).

The correctness spine is CONSERVATION: for every traced frame, the sum
of attributed state durations must equal end-to-end wall time within
clock-resolution tolerance — no unaccounted time, no double counting —
on the interpreted and fused executors, locally and across a query
round trip.  Plus: the attribution engine's interval math, the blame
report, the histogram windowed-quantile edge cases it cross-checks
against, the teardown-safe /metrics scrape, the device accounting
gauges, and the tools/perf_diff.py regression gate.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.models.registry import _MODELS, Model, register_model
from nnstreamer_tpu.obs import attrib
from nnstreamer_tpu.obs.profile import Profiler, attribution_block
from nnstreamer_tpu.obs.span import Span
from nnstreamer_tpu.pipeline.graph import AppSrc, Pipeline
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsInfo
from nnstreamer_tpu.tensor.types import TensorType

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")

CAPS4 = ("other/tensors,format=static,num_tensors=1,dimensions=4,"
         "types=float32,framerate=0/1")

#: conservation tolerance: attribution partitions integer-ns intervals
#: exactly; only rounding inside the engine could lose time, so 1 µs
#: per frame is generous
TOL_NS = 1_000


@pytest.fixture()
def tiny_model():
    import jax.numpy as jnp

    w = np.arange(32, dtype=np.float32).reshape(4, 8)

    def build(custom):
        def forward(params, x):
            return (jnp.asarray(x, jnp.float32) @ params,)

        return Model(name="tiny_attrib", forward=forward, params=w,
                     in_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                     (4,))]),
                     out_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                      (8,))]))

    register_model("tiny_attrib")(build)
    yield w
    _MODELS.pop("tiny_attrib", None)


def _assert_conserved(profiler, min_frames=1):
    attributed = profiler.attributed()
    assert len(attributed) >= min_frames
    for fr, states in attributed:
        e2e = fr.t1 - fr.t0
        total = sum(states.values())
        assert abs(total - e2e) <= TOL_NS, (
            f"frame {fr.seq}: attributed {total} ns != e2e {e2e} ns "
            f"({states})")
    return attributed


# ---------------------------------------------------------------------------
# the attribution engine (synthetic spans)
# ---------------------------------------------------------------------------

class TestEngine:
    def test_innermost_span_wins(self):
        spans = [
            Span("src:s", 1, 1000, 0, 0, 1),
            Span("outer", 1, 1000, 900, 0, 1),
            Span("state:device-invoke", 1, 1300, 200, 0, 1),
        ]
        [(fr, states)] = attrib.attribute_frames(
            spans, {"outer": "element-compute"})
        assert states["device-invoke"] == 200
        assert states["element-compute"] == 700
        assert sum(states.values()) == fr.t1 - fr.t0 == 900

    def test_gap_classification_and_source_pacing(self):
        spans = [
            Span("src:s", 1, 0, 0, 3, 1),
            Span("a", 1, 500, 100, 3, 1),       # 0..500 = source-pacing
            Span("b", 1, 900, 100, 3, 1),       # 600..900 = gap into b
        ]
        [(fr, states)] = attrib.attribute_frames(
            spans, {"a": "element-compute", "b": "element-compute"},
            transit={"b": "queue-wait"})
        assert states["source-pacing"] == 500
        assert states["queue-wait"] == 300
        assert states["element-compute"] == 200
        assert sum(states.values()) == fr.t1 - fr.t0 == 1000

    def test_span_before_birth_extends_window(self):
        """A serving pipeline's admission-wait starts at ARRIVAL,
        before the serversrc stamps birth: the frame window extends
        left so the wait is inside, not clipped away."""
        spans = [
            Span("state:admission-wait", 1, 100, 380, 5, 1),
            Span("src:qsrc", 1, 500, 0, 5, 1),
            Span("el", 1, 520, 80, 5, 1),
        ]
        [(fr, states)] = attrib.attribute_frames(spans)
        assert fr.t0 == 100
        assert states["admission-wait"] == 380
        assert sum(states.values()) == fr.t1 - fr.t0

    def test_remote_spans_carve_wire(self):
        local = [
            Span("src:s", 1, 0, 0, 0, 9),
            Span("qc", 1, 100, 1000, 0, 9),
        ]
        remote = [Span("st", 7, 400, 300, 0, 9)]
        [(fr, states)] = attrib.attribute_frames(
            local, {"qc": "wire"}, remote_spans=remote)
        assert states["element-compute"] == 300
        assert states["wire"] == 700
        assert sum(states.values()) == fr.t1 - fr.t0

    def test_blame_dominant_edges_and_top(self):
        mk = lambda seq, wire: [  # noqa: E731
            Span("src:s", 1, seq * 10_000, 0, seq, 1),
            Span("qc", 1, seq * 10_000 + 10, wire, seq, 1)]
        spans = [s for i in range(10) for s in mk(i, 5000)]
        spans += [Span("slowsink", 1, 10 * 10_000 + 10, 9000, 10, 1),
                  Span("src:s", 1, 10 * 10_000, 0, 10, 1)]
        report = attrib.blame(attrib.attribute_frames(
            spans, {"qc": "wire", "slowsink": "sink"}))
        assert report["frames"] == 11
        assert report["states"]["wire"]["dominant_frames"] == 10
        assert report["states"]["sink"]["dominant_frames"] == 1
        assert report["top"][0][0] == "wire"
        assert report["conservation"]["attributed_pct"] == pytest.approx(
            100.0, abs=0.1)

    def test_busy_fraction_unions_overlap(self):
        spans = [Span("e", 1, 0, 600, 0, 1),
                 Span("e", 2, 300, 600, 1, 1),   # overlaps: union 0..900
                 Span("other", 1, 0, 1000, 0, 1)]
        frac = attrib.busy_fraction(spans, "e", 1000, 1000)
        assert frac == pytest.approx(0.9, abs=0.01)

    def test_busy_fraction_counts_worker_invoke_spans(self):
        """A worker-mode filter's real work records under
        '<name>:invoke' on worker threads (chain() only covers the
        submit): occupancy must count it, or saturated async filters
        read idle."""
        spans = [Span("f", 1, 0, 10, 0, 1),           # submit: 10 ns
                 Span("f:invoke", 2, 100, 800, 0, 1)]  # the real work
        frac = attrib.busy_fraction(spans, "f", 1000, 1000)
        assert frac == pytest.approx(0.81, abs=0.01)

    def test_multi_source_seq_collision_dropped_loudly(self):
        """Two sources both stamp seq 0 under one tracer (mux graph):
        the colliding frame is EXCLUDED (reported via ambiguous), not
        silently blended into one corrupted window."""
        spans = [
            Span("src:a", 1, 0, 0, 0, 1),
            Span("ela", 1, 10, 100, 0, 1),
            Span("src:b", 2, 5000, 0, 0, 1),
            Span("elb", 2, 5010, 100, 0, 1),
            Span("src:a", 1, 10000, 0, 1, 1),   # seq 1: only source a
            Span("ela", 1, 10010, 100, 1, 1),
        ]
        ambiguous = []
        frames = attrib.group_frames(spans, ambiguous=ambiguous)
        assert [fr.seq for fr in frames] == [1]
        assert ambiguous == [0]

    def test_folded_stacks_paths_and_weights(self):
        spans = [
            Span("src:s", 1, 0, 0, 0, 1),
            Span("outer", 1, 0, 2_000_000, 0, 1),
            Span("state:serialize", 1, 500_000, 1_000_000, 0, 1),
        ]
        frames = attrib.group_frames(spans)
        folded = attrib.folded_stacks(frames,
                                      {"outer": "element-compute"})
        assert folded["outer;state:serialize"] == 1000
        assert folded["outer;element-compute"] == 1000


# ---------------------------------------------------------------------------
# conservation on real pipelines — the correctness spine
# ---------------------------------------------------------------------------

class TestConservation:
    PIPE = ("videotestsrc num-buffers=40 pattern=random ! "
            "video/x-raw,format=RGB,width=24,height=24 ! "
            "tensor_converter ! tensor_transform mode=arithmetic "
            "option=add:1 ! queue max-size-buffers=4 ! "
            "tensor_sink name=out")

    def _run(self, fuse):
        p = parse_launch(self.PIPE, Pipeline(fuse=fuse))
        prof = Profiler(p, register_gauges=False)
        try:
            p.run(timeout=60)
            attributed = _assert_conserved(prof, min_frames=40)
        finally:
            prof.close()
            p.stop()
        return p, attributed

    def test_interpreted_executor_conserves(self):
        self._run(fuse=False)

    def test_fused_executor_conserves_same_state_edges(self):
        def significant(attributed):
            report = attrib.blame(attributed)
            return {s for s, row in report["states"].items()
                    if row["pct"] >= 1.0}

        _, fused = self._run(fuse=True)
        _, interp = self._run(fuse=False)
        # the fused executor must emit the same state edges the
        # interpreted one does: the states that matter for this graph
        # (>=1% of e2e) surface under BOTH executors.  Two separately
        # timed runs cannot be compared state-set-equal — borderline
        # states (dispatch glue, the µs-scale sink) flip across the 1%
        # line on scheduler noise — so pin the core vocabulary instead.
        core = {"source-pacing", "element-compute", "queue-wait"}
        assert core <= significant(fused), significant(fused)
        assert core <= significant(interp), significant(interp)

    def test_cross_process_round_trip_conserves(self, tiny_model):
        from nnstreamer_tpu.elements.sink import TensorSink
        from nnstreamer_tpu.query.client import TensorQueryClient
        from nnstreamer_tpu.query.server import (TensorQueryServerSink,
                                                 TensorQueryServerSrc,
                                                 shutdown_server)

        sid = 811
        server = Pipeline("attrib-server")
        ssrc = TensorQueryServerSrc("qsrc", id=sid, port=0, caps=CAPS4)
        from nnstreamer_tpu.elements.filter_elem import TensorFilter

        f = TensorFilter("f", framework="xla", model="tiny_attrib")
        ssink = TensorQueryServerSink("qsink", id=sid)
        server.add(ssrc, f, ssink)
        server.link(ssrc, f, ssink)
        server_prof = Profiler(server, register_gauges=False)
        server.play()
        try:
            client = Pipeline("attrib-client")
            src = AppSrc("src", caps=CAPS4)
            qc = TensorQueryClient("qc", port=ssrc.bound_port,
                                   timeout=10.0)
            sink = TensorSink("out")
            client.add(src, qc, sink)
            client.link(src, qc, sink)
            n = 12
            for i in range(n):
                src.push_buffer(TensorBuffer(
                    tensors=[np.full(4, i, np.float32)], pts=i * 10))
            src.end_of_stream()
            prof = Profiler(client, register_gauges=False)
            client.play()
            try:
                client.wait(timeout=30)
            finally:
                client.stop()
            assert len(sink.results) == n
            attributed = _assert_conserved(prof, min_frames=n)
            states = {s for _, st in attributed for s in st}
            # the client's wire time was carved by the server's merged
            # timeline: server-side states visible from the client
            assert "wire" in states
            assert states & {"admission-wait", "element-compute",
                             "device-invoke", "device-compile"}, states
            # server-side attribution conserves too (admission-wait
            # spans extend the frame window left of the birth stamp)
            server_attr = _assert_conserved(server_prof, min_frames=1)
            server_states = {s for _, st in server_attr for s in st}
            assert "admission-wait" in server_states
            prof.close()
        finally:
            server_prof.close()
            server.stop()
            shutdown_server(sid)

    def test_device_invoke_annotated_per_frame(self, tiny_model):
        p = parse_launch(
            f"appsrc caps={CAPS4} name=in ! "
            "tensor_filter framework=xla model=tiny_attrib name=f ! "
            "tensor_sink name=out")
        prof = Profiler(p, register_gauges=False)
        src = p.get("in")
        for i in range(8):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        src.end_of_stream()
        p.play()
        p.wait(timeout=60)
        p.stop()
        attributed = _assert_conserved(prof, min_frames=8)
        with_device = [st for _, st in attributed
                       if "device-invoke" in st or "device-compile" in st]
        assert len(with_device) == len(attributed)
        prof.close()

    def test_batched_filter_names_queue_and_device_waits(self, tiny_model):
        """Micro-batched dispatch: every frame of a bucket gets a
        queue-wait (arrival → dispatch) and a device-invoke (the shared
        batch window) span — the coalescing wait must be NAMED, not a
        generic dispatch gap."""
        p = parse_launch(
            f"appsrc caps={CAPS4} name=in ! "
            "tensor_filter framework=xla model=tiny_attrib name=f "
            "batch=4 ! tensor_sink name=out")
        prof = Profiler(p, register_gauges=False)
        src = p.get("in")
        n = 16
        for i in range(n):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        src.end_of_stream()
        p.play()
        p.wait(timeout=60)
        p.stop()
        attributed = _assert_conserved(prof, min_frames=n)
        per_frame_states = [set(st) for _, st in attributed]
        assert all("device-invoke" in st or "device-compile" in st
                   for st in per_frame_states)
        assert sum("queue-wait" in st for st in per_frame_states) >= n - 4
        prof.close()

    def test_workers_reorder_and_invoke_spans_conserve(self, tiny_model):
        p = parse_launch(
            f"appsrc caps={CAPS4} name=in ! "
            "tensor_filter framework=xla model=tiny_attrib name=f "
            "workers=3 ! tensor_sink name=out")
        prof = Profiler(p, register_gauges=False)
        src = p.get("in")
        n = 24
        for i in range(n):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        src.end_of_stream()
        p.play()
        p.wait(timeout=60)
        p.stop()
        attributed = _assert_conserved(prof, min_frames=n)
        names = {name for fr, _ in attributed for name, _, _ in fr.spans}
        assert "f:invoke" in names
        prof.close()


# ---------------------------------------------------------------------------
# occupancy + device accounting gauges
# ---------------------------------------------------------------------------

class TestGauges:
    def test_occupancy_gauges_live_and_dropped_at_close(self):
        from nnstreamer_tpu.obs.metrics import REGISTRY

        p = parse_launch(
            "videotestsrc num-buffers=30 pattern=random ! "
            "video/x-raw,format=RGB,width=24,height=24 ! "
            "tensor_converter ! tensor_sink name=out")
        # tight window: the scrape happens right after the short run,
        # so busy/window stays above the report's 4-decimal rounding
        prof = Profiler(p, occupancy_window_s=0.5)
        p.run(timeout=60)
        report = REGISTRY.report()
        occ = {k: v for k, v in report.items()
               if k.startswith("nns_element_occupancy")}
        assert occ, report.keys()
        assert any(v > 0 for v in occ.values()), occ
        assert all(0.0 <= v <= 1.0 for v in occ.values()), occ
        prof.close()
        p.stop()
        assert not any(k.startswith("nns_element_occupancy")
                       for k in REGISTRY.report())

    def test_mfu_gauge_live_and_consistent_with_bench_math(
            self, tiny_model, monkeypatch):
        """nns_mfu = frame_rate x flops / peak — the BENCH mfu_stream
        formula over the same peak table (bench.py imports it from
        obs/attrib.py, so the two cannot drift).  The table knows no
        CPU, so the test lends it a row for this host's device."""
        import jax

        from nnstreamer_tpu.obs.metrics import REGISTRY

        monkeypatch.setitem(attrib.DEVICE_PEAKS,
                            jax.devices()[0].device_kind, (1e9, 1e9))
        p = parse_launch(
            f"appsrc caps={CAPS4} name=in ! "
            "tensor_filter framework=xla model=tiny_attrib name=f ! "
            "tensor_sink name=out")
        src = p.get("in")
        for i in range(20):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        src.end_of_stream()
        p.play()
        try:
            p.wait(timeout=60)
            f = p.get("f")
            flops, nbytes = attrib.estimate_jit_cost(f.fw)
            assert flops > 0   # 4x8 matmul has a cost model
            report = REGISTRY.report()
            mfu = [v for k, v in report.items()
                   if k.startswith("nns_mfu")]
            assert mfu, report.keys()
            # consistency: gauge == lifetime frame rate x flops / peak
            # (first scrape reads the lifetime rate by contract)
            rate = f.fw.stats.throughput
            expect = rate * flops / 1e9
            assert mfu[0] == pytest.approx(expect, rel=0.25), (
                mfu, rate, flops)
            assert any(k.startswith("nns_device_mem_bytes")
                       for k in report)
            # a device the table does not know: the gauge makes no claim
            monkeypatch.delitem(attrib.DEVICE_PEAKS,
                                jax.devices()[0].device_kind)
            assert [v for k, v in REGISTRY.report().items()
                    if k.startswith("nns_mfu")] == [0.0]
        finally:
            p.stop()
        assert not any(k.startswith("nns_mfu")
                       for k in REGISTRY.report())

    def test_device_peaks_keyed_by_what_the_chip_says(self, monkeypatch):
        class Dev:
            platform = "tpu"
            device_kind = "TPU v5 lite"   # a v5e, in its own words

        assert attrib.device_peaks(Dev()) == (197e12, 819e9)
        # the removed overrides are not read any more
        monkeypatch.setenv("NNS_PEAK_FLOPS", "42.0")
        monkeypatch.setenv("NNS_PEAK_BW", "42.0")
        assert attrib.device_peaks(Dev()) == (197e12, 819e9)

    def test_unknown_device_kind_is_an_error_not_a_default(self):
        class Dev:
            platform = "tpu"
            device_kind = "TPU v5e"       # not what any chip answered

        with pytest.raises(LookupError, match="TPU v5e"):
            attrib.device_peaks(Dev())
        import jax

        with pytest.raises(LookupError):   # this host's CPU: no claim
            attrib.device_peaks(jax.devices()[0])

    def test_bench_uses_the_same_peak_lookup(self):
        sys.path.insert(0, os.path.dirname(TOOLS))
        try:
            import bench

            assert bench.device_peaks is attrib.device_peaks
        finally:
            sys.path.remove(os.path.dirname(TOOLS))


# ---------------------------------------------------------------------------
# histogram windowed-quantile edge cases (satellite)
# ---------------------------------------------------------------------------

class TestHistogramEdges:
    def _counts(self, values):
        from nnstreamer_tpu.obs.metrics import Histogram

        h = Histogram("t", {})
        for v in values:
            h.observe(float(v))
        return h.state()[2]

    def test_empty_window_is_zero(self):
        from nnstreamer_tpu.obs.metrics import (count_over_threshold,
                                                quantile_from_counts)

        assert quantile_from_counts((), 0.99) == 0.0
        assert quantile_from_counts((0,) * 128, 0.5) == 0.0
        assert count_over_threshold((), 100.0) == 0

    def test_single_bucket_mass_answers_its_midpoint(self):
        from nnstreamer_tpu.obs.metrics import quantile_from_counts

        counts = self._counts([100.0] * 50)
        qs = {quantile_from_counts(counts, q)
              for q in (0.01, 0.5, 0.99)}
        assert len(qs) == 1           # one distinguishable value
        (v,) = qs
        assert v == pytest.approx(100.0, rel=0.12)

    def test_beyond_last_edge_reports_range_edge_not_extrapolation(self):
        from nnstreamer_tpu.obs.metrics import (_NBUCKETS, _SUB,
                                                quantile_from_counts)

        top_edge = 2.0 ** ((_NBUCKETS - 1) / _SUB)
        counts = self._counts([top_edge * 1000.0] * 10)
        v = quantile_from_counts(counts, 0.99)
        assert v == pytest.approx(top_edge)   # lower edge, no invention

    def test_threshold_edges(self):
        from nnstreamer_tpu.obs.metrics import (_NBUCKETS, _SUB,
                                                count_over_threshold)

        counts = self._counts([10.0] * 5 + [1000.0] * 3)
        assert count_over_threshold(counts, 0.5) == 8   # <=1: everything
        assert count_over_threshold(counts, 100.0) == 3
        beyond = 2.0 ** ((_NBUCKETS - 0.2) / _SUB)
        assert count_over_threshold(counts, beyond) == 0  # no claim

    @pytest.mark.parametrize("dist", ["lognormal", "bimodal", "heavy"])
    def test_windowed_quantiles_track_numpy(self, dist):
        from nnstreamer_tpu.obs.metrics import quantile_from_counts

        rng = np.random.default_rng(5)
        if dist == "lognormal":
            vals = np.exp(rng.normal(5, 1.5, 4000))
        elif dist == "bimodal":
            # adversarial: p50 sits exactly on the mode boundary —
            # numpy's default linear interpolation would invent a value
            # BETWEEN the modes; the empirical inverted CDF (what a
            # bucketed histogram estimates) picks the real mode
            vals = np.concatenate([rng.normal(50, 3, 2000),
                                   rng.normal(40000, 800, 2000)])
            vals = np.clip(vals, 1.0, None)
        else:
            vals = rng.pareto(1.5, 4000) * 100 + 1
        counts = self._counts(vals)
        for q in (0.5, 0.95, 0.99):
            got = quantile_from_counts(counts, q)
            want = float(np.quantile(vals, q, method="inverted_cdf"))
            # quarter-octave buckets: ~19% width, midpoint error ~9%;
            # allow 25% for mass straddling a boundary
            assert got == pytest.approx(want, rel=0.25), (dist, q)


# ---------------------------------------------------------------------------
# /metrics scrape vs teardown race (satellite)
# ---------------------------------------------------------------------------

class TestScrapeTeardownRace:
    def test_concurrent_scrape_survives_pipeline_stop(self):
        from nnstreamer_tpu.obs.httpd import (start_metrics_server,
                                              stop_metrics_server)

        server = start_metrics_server(0)
        port = server.server_address[1]
        stop_evt = threading.Event()
        statuses = []
        errors = []

        def _scraper():
            while not stop_evt.is_set():
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/metrics",
                            timeout=5) as resp:
                        statuses.append(resp.status)
                        resp.read()
                except urllib.error.HTTPError as exc:
                    statuses.append(exc.code)
                except Exception as exc:  # noqa: BLE001
                    errors.append(repr(exc))

        threads = [threading.Thread(target=_scraper, daemon=True)
                   for _ in range(3)]
        for t in threads:
            t.start()
        try:
            for _ in range(6):
                p = parse_launch(
                    "videotestsrc num-buffers=12 pattern=random ! "
                    "video/x-raw,format=RGB,width=16,height=16 ! "
                    "tensor_converter ! queue max-size-buffers=2 ! "
                    "tensor_sink name=out")
                p.play()
                # stop mid-flight: queue/filter gauges die under the
                # scrapers — dead providers must drop samples, never
                # 500 the scrape or kill the httpd thread
                time.sleep(0.02)
                p.stop()
        finally:
            stop_evt.set()
            for t in threads:
                t.join(timeout=10)
            stop_metrics_server()
        assert not errors, errors
        assert statuses and all(s == 200 for s in statuses), (
            set(statuses), len(statuses))


# ---------------------------------------------------------------------------
# tools/perf_diff.py (satellite: tier-1 smoke)
# ---------------------------------------------------------------------------

class TestPerfDiff:
    def _write(self, path, rows):
        with open(path, "w", encoding="utf-8") as fh:
            for r in rows:
                fh.write(json.dumps(r) + "\n")

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(TOOLS, "perf_diff.py"),
             *argv], capture_output=True, text=True, timeout=60)

    def _files(self, tmp_path, cand_rows):
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        c = tmp_path / "c.jsonl"
        self._write(a, [
            {"metric": "flagship_fps", "value": 100.0, "unit": "fps",
             "attribution": {"states": {"wire": 40.0, "queue-wait": 30.0,
                                        "device-invoke": 5.0}}},
            {"metric": "dispatch_ns", "value": 80.0, "unit": "ns"}])
        self._write(b, [
            {"metric": "flagship_fps", "value": 104.0, "unit": "fps",
             "attribution": {"states": {"wire": 42.0, "queue-wait": 28.0,
                                        "device-invoke": 5.0}}},
            {"metric": "dispatch_ns", "value": 85.0, "unit": "ns"}])
        self._write(c, cand_rows)
        return str(a), str(b), str(c)

    def test_injected_regression_names_the_stage(self, tmp_path):
        a, b, c = self._files(tmp_path, [
            {"metric": "flagship_fps", "value": 70.0, "unit": "fps",
             "attribution": {"states": {"wire": 38.0, "queue-wait": 52.0,
                                        "device-invoke": 5.0}}},
            {"metric": "dispatch_ns", "value": 83.0, "unit": "ns"}])
        r = self._run("--baseline", a, "--baseline", b,
                      "--candidate", c, "--json")
        assert r.returncode == 1, r.stdout + r.stderr
        verdict = json.loads(r.stdout)
        assert verdict["verdict"] == "REGRESSION"
        [reg] = verdict["regressions"]
        assert reg["metric"] == "flagship_fps"
        assert reg["attribution"]["regressed_stage"] == "queue-wait"
        assert reg["attribution"]["regressed_stage_delta_pct"] > 20

    def test_noise_band_jitter_passes(self, tmp_path):
        """Same arming philosophy as the PR 6 burn-rate evaluator: a
        wiggle inside the measured run-to-run noise band must NOT
        page."""
        a, b, c = self._files(tmp_path, [
            {"metric": "flagship_fps", "value": 97.0, "unit": "fps"},
            {"metric": "dispatch_ns", "value": 87.0, "unit": "ns"}])
        r = self._run("--baseline", a, "--baseline", b,
                      "--candidate", c, "--json")
        assert r.returncode == 0, r.stdout + r.stderr
        verdict = json.loads(r.stdout)
        assert verdict["verdict"] == "PASS"
        assert not verdict["regressions"]

    def test_lower_better_direction_and_dead_rows(self, tmp_path):
        a, b, c = self._files(tmp_path, [
            {"metric": "dispatch_ns", "value": 400.0, "unit": "ns"},
            {"metric": "flagship_fps", "value": 0.0, "unit": "fps",
             "status": "infra_dead"}])
        r = self._run("--baseline", a, "--baseline", b,
                      "--candidate", c, "--json")
        verdict = json.loads(r.stdout)
        assert r.returncode == 1
        by_verdict = {row["metric"]: row["verdict"]
                      for row in verdict["regressions"]}
        # ns: lower is better → judged a regression
        assert by_verdict["dispatch_ns"] == "REGRESSION"
        # the infra_dead fps row is NOT judged as a 0x value — but a
        # metric both baselines measured that produced no live
        # candidate sample cannot pass either: it surfaces as MISSING
        assert by_verdict["flagship_fps"] == "MISSING"
        assert all(row["metric"] != "flagship_fps" or
                   row["verdict"] == "MISSING"
                   for row in verdict["rows"])

    def test_unit_direction_matches_word_tokens_not_substrings(self):
        """Satellite fix (ISSUE 15): direction comes from the unit's
        word tokens.  The old raw-substring match made any unit
        CONTAINING the letters "ns" lower-is-better — "tokens_per_s"
        inverted the gate, so a collapsed token throughput PASSED and
        an improvement would have paged."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "perf_diff", os.path.join(TOOLS, "perf_diff.py"))
        pd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pd)
        assert pd.lower_is_better("ns")
        assert pd.lower_is_better("ns/decision")
        assert pd.lower_is_better("us/bucket")
        assert pd.lower_is_better("pct_vs_metrics_off")
        assert not pd.lower_is_better("tokens_per_s")
        assert not pd.lower_is_better("sessions_per_run")
        assert not pd.lower_is_better("fps")

    def test_compile_counters_lower_better_by_name(self, tmp_path):
        """Satellite (ISSUE 19): compile counts are costs — the ledger
        exports ``nns_jit_compiles_total`` unitless, so the metric NAME
        must carry the direction.  A compile-count increase is a
        REGRESSION, never read as throughput."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "perf_diff", os.path.join(TOOLS, "perf_diff.py"))
        pd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pd)
        assert pd.lower_is_better("", metric="nns_jit_compiles_total")
        assert pd.lower_is_better(
            "", metric='nns_jit_compiles_total{site="llm.engine.step"}')
        assert pd.lower_is_better("", metric="steady_compiles")
        assert pd.lower_is_better("count", metric="segment_recompiles")
        # names that merely contain "compile" letters elsewhere or are
        # throughput stay higher-is-better
        assert not pd.lower_is_better("", metric="tokens_total")
        assert not pd.lower_is_better("fps", metric="flagship_fps")
        # end-to-end: a compile-count rise REGRESSES through the gate
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        c = tmp_path / "c.jsonl"
        row = {"metric": "nns_jit_compiles_total", "value": 4, "unit": ""}
        self._write(a, [row])
        self._write(b, [dict(row, value=5)])
        self._write(c, [dict(row, value=40)])
        r = self._run("--baseline", str(a), "--baseline", str(b),
                      "--candidate", str(c), "--json")
        assert r.returncode == 1, r.stdout + r.stderr
        verdict = json.loads(r.stdout)
        [reg] = verdict["regressions"]
        assert reg["metric"] == "nns_jit_compiles_total"
        assert reg["direction"] == "lower_better"

    def test_progressive_reemits_last_row_wins(self, tmp_path):
        """bench.py re-emits the same metric row progressively enriched
        (core value first, attribution added later): the LAST line must
        win, so the stage naming fires and duplicates are not judged
        twice."""
        a = tmp_path / "a.jsonl"
        b = tmp_path / "b.jsonl"
        c = tmp_path / "c.jsonl"
        base = {"metric": "fps", "value": 100.0, "unit": "fps"}
        enriched = dict(base, attribution={
            "states": {"wire": 40.0, "queue-wait": 30.0}})
        self._write(a, [base, enriched])        # re-emit, enriched last
        self._write(b, [dict(enriched, value=102.0)])
        self._write(c, [
            {"metric": "fps", "value": 60.0, "unit": "fps"},
            {"metric": "fps", "value": 60.0, "unit": "fps",
             "attribution": {"states": {"wire": 30.0,
                                        "queue-wait": 55.0}}}])
        r = self._run("--baseline", str(a), "--baseline", str(b),
                      "--candidate", str(c), "--json")
        assert r.returncode == 1
        verdict = json.loads(r.stdout)
        assert len(verdict["regressions"]) == 1     # not per duplicate
        [reg] = verdict["regressions"]
        assert reg["attribution"]["regressed_stage"] == "queue-wait"

    def test_metric_missing_from_candidate_fails(self, tmp_path):
        """A metric both baselines measured that the candidate no
        longer emits must FAIL, not silently pass — a run that crashed
        before producing its rows is not a green run."""
        a, b, c = self._files(tmp_path, [
            {"metric": "flagship_fps", "value": 101.0, "unit": "fps"}])
        # candidate carries flagship_fps but NOT dispatch_ns
        r = self._run("--baseline", a, "--baseline", b,
                      "--candidate", c, "--json")
        assert r.returncode == 1
        verdict = json.loads(r.stdout)
        assert verdict["missing"] == 1
        assert any(row["verdict"] == "MISSING"
                   and row["metric"] == "dispatch_ns"
                   for row in verdict["regressions"])

    def test_needs_two_baselines(self, tmp_path):
        a, _, c = self._files(tmp_path, [
            {"metric": "flagship_fps", "value": 1.0, "unit": "fps"}])
        r = self._run("--baseline", a, "--candidate", c)
        assert r.returncode == 2


# ---------------------------------------------------------------------------
# surfaces
# ---------------------------------------------------------------------------

class TestSurfaces:
    def test_launch_profile_emits_artifacts(self, tmp_path):
        out = tmp_path / "prof"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.dirname(TOOLS))
        r = subprocess.run(
            [sys.executable, "-m", "nnstreamer_tpu.launch",
             "videotestsrc num-buffers=30 pattern=random ! "
             "video/x-raw,format=RGB,width=24,height=24 ! "
             "tensor_converter ! queue ! tensor_sink name=out",
             "--profile", "--profile-out", str(out), "--quiet"],
            capture_output=True, text=True, timeout=180, env=env,
            cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr
        assert "profile:" in r.stderr and "state" in r.stderr
        doc = json.loads((out / "profile.json").read_text())
        blame = doc["profile"]["blame"]
        assert blame["frames"] >= 30
        assert blame["conservation"]["attributed_pct"] >= 90.0
        assert (out / "trace.json").exists()
        folded = (out / "flame.folded").read_text().splitlines()
        assert folded and all(len(ln.rsplit(" ", 1)) == 2
                              for ln in folded)

    def test_flightrec_bundle_carries_blame(self, tmp_path):
        from nnstreamer_tpu.slo.flightrec import FlightRecorder

        p = parse_launch(
            "videotestsrc num-buffers=20 pattern=random ! "
            "video/x-raw,format=RGB,width=16,height=16 ! "
            "tensor_converter ! tensor_sink name=out")
        tracer = p.enable_tracing(spans=True)
        p.run(timeout=60)
        p.stop()
        rec = FlightRecorder(str(tmp_path / "fr"), tracer=tracer)
        rec.record()
        bundle = rec.dump("test")
        blame = json.loads(
            open(os.path.join(bundle, "blame.json")).read())
        assert blame["frames"] >= 20
        assert blame["attributed_pct"] >= 90.0

    def test_attribution_block_empty_without_spans(self):
        p = parse_launch(
            "videotestsrc num-buffers=3 ! "
            "video/x-raw,format=RGB,width=16,height=16 ! "
            "tensor_converter ! tensor_sink name=out")
        tracer = p.enable_tracing()   # counters only, no spans
        p.run(timeout=60)
        p.stop()
        assert attribution_block(tracer) == {}
        assert attribution_block(None) == {}
