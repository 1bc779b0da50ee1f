"""``run.py``: no CPU fall-back, the contract's last line, and every
metric reader on a run worked by hand."""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.record import Run  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_run_refuses_a_host_with_no_chip_before_building_anything():
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2m.decode_saturate", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""                         # no result line
    assert "platform 'cpu'" in proc.stderr
    assert "No CPU fall-back" in proc.stderr
    # an 809 M-parameter model alone takes longer than this to build
    assert time.monotonic() - t0 < 60


def test_run_needs_the_program_beside_it(tmp_path):
    """In a directory that holds only the manifest and the benchmark's
    own directories, the run fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.argv = ['run.py', '--workload', "
            "'gpt2m.steady_short', '--seed', '1', '--seconds', '1']; "
            "import runpy; from benchmarks import run; "
            "run.device_or_exit = lambda chips: {'platform': 'tpu', "
            "'kind': 'TPU v5 lite', 'count': 1}; sys.exit(run.main())")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=str(tmp_path),
        env={k: v for k, v in dict(os.environ, JAX_PLATFORMS="cpu").items()
             if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "nnstreamer_tpu" in proc.stderr


def token_run(trace=None):
    """A window of 10 s at t0 = 100 with four requests worked by hand."""
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cell = m.cell("gpt2m.steady_short")
    run = Run(cell=cell, config=m.config("sflm_gpt2m"),
              traffic=m.traffic("steady_short"), seed=1, seconds=10.0,
              t_start=40.0, t0=100.0, t1=110.0,
              peaks=PEAKS, missed_ms=30000.0,
              cost=m.module("cost", "streamformer_lm"))
    run.requests = [
        # due 101, first token at 101.2, then every 50 ms
        {"id": 0, "due": 101.0, "sent": 101.001, "ok": True,
         "outcome": "done", "stamps": [101.2, 101.25, 101.30],
         "tokens": [1, 2, 3]},
        # due 105, first token 0.4 s later, one gap of 100 ms
        {"id": 1, "due": 105.0, "sent": 105.0, "ok": True,
         "outcome": "done", "stamps": [105.4, 105.5], "tokens": [4, 5]},
        # shed: no token at all
        {"id": 2, "due": 107.0, "sent": 107.0, "ok": False,
         "outcome": "shed", "stamps": [], "tokens": []},
        # due before the window opened: its tokens inside still count,
        # its wait does not
        {"id": 3, "due": 99.0, "sent": 99.0, "ok": True, "outcome": "done",
         "stamps": [99.5, 100.5, 109.9, 110.5], "tokens": [6, 7, 8, 9]},
    ]
    run.counters = {
        "steps": 200, "step_tokens": 5000, "tokens": 5100, "prefills": 100,
        "shed": 1, "slots": 32, "compiles": [],
        "phase_ns": {"idle": 1e9, "admit": 0.5e9, "prefill": 1.5e9,
                     "llm-prefill-chunk": 0, "decode": 6e9, "egress": 1e9,
                     "compile": 0},
        "samples": [(100.1, 16, 16, 1600), (100.2, 24, 20, 2000)]}
    run.trace = trace
    return m, run


def read(m, run, folder, name):
    return m.module(folder, name).read(run)


def test_end_to_end_readers_on_a_hand_worked_run():
    m, run = token_run()
    assert read(m, run, "e2e_metrics", "setup_s") == 60.0
    # per stream, inside [100, 110): 2 gaps in 0.1 s, 1 gap in 0.1 s,
    # nothing, and 1 gap in 9.4 s (100.5 to 109.9)
    assert read(m, run, "e2e_metrics", "tok_s") == pytest.approx(
        20 + 10 + 1 / 9.4)
    # a closed loop's client carries its span over its streams
    run.requests[0]["client"] = run.requests[1]["client"] = 7
    assert read(m, run, "e2e_metrics", "tok_s") == pytest.approx(
        4 / (105.5 - 101.2) + 1 / 9.4)
    for r in run.requests[:2]:
        del r["client"]
    # due in the window: 200 ms, 400 ms and a miss -> p95 is the miss,
    # reported as the longest wait the run allows
    assert read(m, run, "e2e_metrics", "ttft_p95_ms") == 30000.0
    run.requests[2].update(ok=True, stamps=[107.3], tokens=[1])
    assert read(m, run, "e2e_metrics", "ttft_p95_ms") == pytest.approx(400)
    assert len(run.due_in_window()) == 3


def test_per_layer_readers_on_a_hand_worked_run():
    m, run = token_run()
    lm = "layer_metrics"
    assert read(m, run, lm, "compiles_in_window") == 0.0
    run.counters["compiles"] = [(104.0, "jit__step", 2.5)]
    assert read(m, run, lm, "compiles_in_window") == 1.0
    assert read(m, run, lm, "shed_share") == pytest.approx(25.0)
    assert read(m, run, lm, "lanes_per_step") == pytest.approx(25.0)
    assert read(m, run, lm, "decode_step_ms") == pytest.approx(30.0)
    # decode 6 + prefill 1.5 of 10 s run the device
    assert read(m, run, lm, "decode_thread_off_device_share") == \
        pytest.approx(25.0)
    assert read(m, run, lm, "prefill_stall_share") == pytest.approx(15.0)
    assert read(m, run, lm, "slot_occupancy") == pytest.approx(
        100 * 20 / 32)
    # gaps that END inside the window: 50, 50, 100 ms, and request 3's
    # 1000 and 9400 ms; its last gap ends after the window
    assert read(m, run, lm, "itl_p95_ms") == pytest.approx(9400.0)
    # no trace, no device metric
    assert read(m, run, lm, "device_idle_share") is None
    assert read(m, run, lm, "decode_step_roofline") is None


def test_device_readers_take_the_traced_slice():
    from benchmarks.cost import streamformer_lm as cost
    from benchmarks.cost.roofline import least_seconds

    # the slice cut 2.3 steps of 30 ms (the window's 6 s over 200 steps):
    # its step COUNT says 2 or 3, its decode phase time says 2.3
    trace = {"busy_s": 2.0, "window_s": 2.5, "idle_share": 0.2,
             "device_ops": [["fusion.1", 1.5]], "idle_gaps": [["x", 0.5]],
             "counters": {"steps": 3, "step_tokens": 75,
                          "phase_ns": {"decode": 2.3 * 30e6},
                          "samples": [(1.0, 32, 32, 3200),
                                      (1.1, 32, 32, 6400)]}}
    m, run = token_run(trace)
    assert read(m, run, "layer_metrics", "device_idle_share") == \
        pytest.approx(20.0)
    # lanes are the window's (5000 tokens over 200 steps)
    flops, nbytes = cost.decode_step_cost(run.config["model"], 25, 4800)
    least, _ = least_seconds(flops, nbytes, PEAKS)
    assert read(m, run, "layer_metrics", "decode_step_roofline") == \
        pytest.approx(100 * least * 2.3 / 2.0)
    assert trace["roofline_bound"] == "bytes"
    assert trace["least_ms_per_step"] == pytest.approx(least * 1e3)
    # the whole step's share of the peak counts steps in the trace
    # file (test_bm_spans.py): this trace names none
    assert read(m, run, "layer_metrics", "step_mfu") is None
    # a family with no cost functions has no roofline
    run.cost = None
    assert read(m, run, "layer_metrics", "decode_step_roofline") is None


def frames_run(m):
    run = Run(cell=m.cell("mnv2.query_cams"), config=m.config("mnv2_224"),
              traffic=m.traffic("query_cams"), seed=1, seconds=2.0,
              t0=10.0, t1=12.0, peaks=PEAKS,
              missed_ms=12000.0, cost=m.module("cost", "mobilenet_v2"))
    run.requests = [{"id": i, "due": 10.0 + 0.1 * i, "sent": 10.0 + 0.1 * i,
                     "done": 10.0 + 0.1 * i + 0.02, "ok": True,
                     "outcome": "done"} for i in range(20)]
    run.requests[7].update(ok=False, done=None, outcome="failed")
    run.counters = {"frames": 1000, "dispatches": 10, "batch": 128,
                    "xb_frames": 640, "xb_invokes": 20, "shed": 0,
                    "filter": "f", "compiles": [],
                    "element_proctime_ms": {"src": 30.0, "conv": 20.0,
                                            "f": 900.0, "out": 5.0}}
    return m, run


def test_stream_readers_on_a_hand_worked_run(stream_manifest):
    m, run = frames_run(stream_manifest)
    assert read(m, run, "e2e_metrics", "fps") == pytest.approx(19 / 2.0)
    # 19 frames answered in 20 ms, one missed: the 19th of 20 is 20 ms
    assert read(m, run, "e2e_metrics", "frame_p95_ms") == \
        pytest.approx(20.0)
    run.requests[3].update(ok=False, done=None)
    assert read(m, run, "e2e_metrics", "frame_p95_ms") == 12000.0
    lm = "layer_metrics"
    assert read(m, run, lm, "bucket_fill") == pytest.approx(
        100 * 1000 / 1280)
    assert read(m, run, lm, "xbatch_fill") == pytest.approx(25.0)
    # every element but the filter: 55 ms over 1000 frames
    assert read(m, run, lm, "elem_host_us_per_frame") == pytest.approx(55)
    assert read(m, run, lm, "forward_roofline") is None


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    m, run = token_run()
    got = harness.read_metrics(m, run, "per_layer")
    # steady_short's per-layer metrics; none of them needs the trace
    assert set(got) == {"compiles_in_window", "shed_share", "itl_p95_ms",
                        "prefill_stall_share", "slot_occupancy"}
    assert got["shed_share"] == {"value": 25.0, "unit": "%"}
    run.requests = []
    assert "shed_share" not in harness.read_metrics(m, run, "per_layer")
    assert set(harness.read_metrics(m, run, "end_to_end")) == {"setup_s"}


DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 13_000_000_000}


def test_last_line_has_exactly_the_contracts_keys():
    m, run = token_run()
    metrics = harness.read_metrics(m, run, "end_to_end")
    line = json.loads(json.dumps(harness.result_line(
        True, run, metrics, dict(DEVICE))))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["attempted"] == 4 and line["failed"] == 1
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"ttft_p95_ms", "setup_s"}
    for metric in line["metrics"].values():
        assert set(metric) == {"value", "unit"}
        assert isinstance(metric["value"], float)


def test_last_line_ends_in_what_was_compared_beside_its_limit():
    m, run = token_run()
    compared = {"near_top_share": {"value": 0.97, "limit": ">= 0.9"},
                "compiles_in_window": {"value": 0, "limit": "== 0"}}
    line = json.loads(json.dumps(harness.result_line(
        True, run, {}, dict(DEVICE), compared)))
    assert list(line)[-1] == "compared" and line["compared"] == compared
    # a driver whose check names none adds no key
    assert "compared" not in harness.result_line(True, run, {},
                                                 dict(DEVICE), {})


def test_traced_last_line_adds_busy_window_and_breakdown():
    trace = {"busy_s": 2.0, "window_s": 2.5, "idle_share": 0.2,
             "device_ops": [["fusion.1", 1.5]], "idle_gaps": [["x", 0.5]],
             "counters": {"steps": 0, "step_tokens": 0, "samples": []}}
    m, run = token_run(trace)
    line = harness.result_line(True, run, {}, dict(DEVICE))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert line["device"]["busy_s"] == 2.0
    assert line["device"]["window_s"] == 2.5
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("window_us", [(100, 400), (205, 255), (50, 190)])
def test_last_line_of_an_overrun_trace_keeps_busy_inside_the_window(
        overrun_trace, window_us):
    """The trace file is one the profiler overran on both sides of the
    ``bench.slice`` span (``fixtures/slice_overrun``), through the
    reducer and the readers to the line the driver parses: ``busy_s``
    above 0 and at most ``window_s``, the rule PR 35 was refused by."""
    from benchmarks import xplane

    lo, hi = window_us
    trace = xplane.reduce_trace(overrun_trace(lo, hi))
    trace["counters"] = {"steps": 0, "step_tokens": 0, "samples": []}
    m, run = token_run(trace)
    line = json.loads(json.dumps(harness.result_line(
        True, run, {}, dict(DEVICE))))
    device = line["device"]
    assert 0 < device["busy_s"] <= device["window_s"]
    assert device["window_s"] == pytest.approx((hi - lo) * 1e-6)
    idle = read(m, run, "layer_metrics", "device_idle_share")
    assert idle == pytest.approx(
        100 * (1 - device["busy_s"] / device["window_s"]))
    assert 0.0 <= idle < 100.0
    assert sum(s for _, s in line["breakdown"]["device_ops"]) >= \
        device["busy_s"]
    assert sum(s for _, s in line["breakdown"]["idle_gaps"]) == \
        pytest.approx(device["window_s"] - device["busy_s"])


def test_a_stream_cut_at_the_windows_end_is_not_a_failure():
    m, run = token_run()
    run.requests.append({"id": 9, "due": 109.0, "sent": 109.0, "ok": False,
                         "outcome": "cut", "stamps": [109.5],
                         "tokens": [1]})
    line = harness.result_line(True, run, {}, dict(DEVICE))
    assert line["attempted"] == 5 and line["failed"] == 1


def test_lateness_is_described_and_a_late_generator_says_so():
    m, run = token_run()
    run.notes["memory"] = {"kv_written_bytes_peak": 7}
    out = harness.describe(run)
    assert out["outcomes"] == {"done": 3, "shed": 1}
    # beside tok_s: the 7 token frames stamped inside [100, 110) over
    # the window's 10 s, and whatever the driver noted
    assert out["tokens_over_window_per_s"] == pytest.approx(0.7)
    assert out["memory"] == {"kv_written_bytes_peak": 7}
    assert out["generator_late_ms"]["max"] == pytest.approx(1.0)
    assert "GENERATOR_WAS_LATE" not in out
    for r in run.requests:              # 4 requests in 10 s: 2.5 s apart
        r["sent"] = r["due"] + 0.2      # late by 8 % of that
    assert "GENERATOR_WAS_LATE" in harness.describe(run)


def test_compile_log_keeps_the_events_that_ended_inside_the_window():
    log = harness.CompileLog()
    log._on("/jax/core/compile/jaxpr_trace_duration", 0.1, fun_name="f")
    log._on(harness.COMPILE_EVENT, 1.5, fun_name="jit__step")
    assert len(log.events) == 1 and log.events[0][1] == "jit__step"
    t = log.events[0][0]
    assert log.between(t - 1, t + 1) == log.events
    assert log.between(t + 1, t + 2) == []


def test_unknown_device_kind_has_no_peaks():
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert harness.load_peaks(m, "TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(LookupError, match="TPU v9"):
        harness.load_peaks(m, "TPU v9")
