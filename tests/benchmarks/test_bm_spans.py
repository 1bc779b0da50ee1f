"""The program's spans and scopes reduced from a trace
(``benchmarks/spans.py``) and the six readers on top, on a small trace
in the shape a TPU run of the token tier has
(``fixtures/steps_with_spans.xspace.textproto`` is its readable form,
with every number below worked out in its header; the test writes the
``.xplane.pb`` from it)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import spans, xplane  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.record import Run  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
US = 1e-6
READERS = ("step_gap_ms", "step_gap_engine_ms", "kv_copy_device_share",
           "model_math_ms_per_step", "pending_wait_ms",
           "prefill_device_ms")


def write_trace(tmp_path, name):
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, name), encoding="utf-8") as fh:
        raw = ProfileData.text_proto_to_serialized_xspace(fh.read())
    path = tmp_path / (name.split(".")[0] + ".xplane.pb")
    path.write_bytes(raw)
    return str(path)


@pytest.fixture()
def trace_path(tmp_path):
    return write_trace(tmp_path, "steps_with_spans.xspace.textproto")


@pytest.fixture()
def parsed(trace_path):
    (ops, modules), = spans.device_events(trace_path)
    return ops, modules, spans.program_spans(trace_path)


def test_program_spans_are_found_by_name_with_their_arguments(parsed):
    _, _, got = parsed
    assert len(got) == 25                    # PJRT's own event is not one
    assert all(s.name.startswith("llm.") for s in got)
    assert [s.start for s in got] == sorted(s.start for s in got)
    first = got[0]
    assert (first.name, first.stats) == ("llm.decode",
                                         {"step": 5, "lanes": 2})
    # timestamp_ns 1000 + offset, as ProfileData gives every event
    assert (first.start, first.end) == (1000 + 88e3, 1000 + 470e3)
    admits = [s.stats for s in got if s.name == "llm.admit"]
    assert admits == [{"waited_us": 700, "outcome": "admit"}, {},
                      {"waited_us": 300, "outcome": "admit"},
                      {"waited_us": 100, "outcome": "shed"}]


def test_device_events_read_the_metadatas_tf_op_from_the_bytes(
        trace_path, parsed):
    from jax.profiler import ProfileData

    ops, modules, _ = parsed
    assert [m.name for m in modules] == [
        "jit__prefill(222)", "jit__step(111)", "jit__step(111)",
        "jit__prefill(222)", "jit__step(111)"]
    assert ops[2].tf_op == \
        "jit(_step)/llm.engine.step/sflm.kv_read/gather:"
    assert ops[3].name.startswith("%fusion.2.remat_compressed")
    assert ops[3].tf_op == ""             # the compiler's own copy
    # names and times are ProfileData's, which shows no metadata stat
    plane, = [p for p in ProfileData.from_file(trace_path).planes
              if p.name.startswith(xplane.DEVICE_PLANE_PREFIX)]
    assert [(o.name, o.start, o.end) for o in ops] == \
        xplane.device_op_events(plane)
    for line in plane.lines:
        for ev in line.events:
            assert "tf_op" not in dict(ev.stats)


def test_whole_steps_run_from_the_first_step_start_to_the_last(parsed):
    _, _, got = parsed
    window, steps = spans.whole_steps(got)
    assert steps == 2
    assert window == (1000 + 90e3, 1000 + 890e3)
    # the step that began at 890 closed no llm.decode span: counting
    # those would leave one step and an interval of 400 us
    assert sum(1 for s in got
               if s.name == "llm.decode" and "step" in s.stats) == 2
    one = [s for s in got if s.start < 1000 + 400e3]
    assert spans.whole_steps(one) is None         # one start: no step
    assert spans.whole_steps([]) is None


def test_idle_time_is_partitioned_among_the_innermost_spans(parsed):
    ops, _, got = parsed
    window, _ = spans.whole_steps(got)
    parts = spans.idle_by_span(ops, got, window)
    want = {"llm.decode.operands": 12, "llm.decode.dispatch": 8,
            "llm.decode.wait": 45, "llm.decode.sample": 37,
            # the parent only where no child is open: innermost wins
            "llm.decode": 5, "llm.idle": 12, "llm.egress": 10,
            "llm.admit": 3, "llm.prefill": 2, "llm.prefill.dispatch": 1,
            "llm.prefill.wait": 3,
            # [888, 890): the running step's llm.decode never closed
            "no_program_span": 2}
    assert parts == pytest.approx({k: v * US for k, v in want.items()})
    # an exact partition: the parts sum to the idle time
    busy = xplane.busy_ns((max(o.start, window[0]), min(o.end, window[1]))
                          for o in ops)
    assert busy == pytest.approx(660e3)
    assert sum(parts.values()) == pytest.approx(
        (window[1] - window[0] - busy) / 1e9)
    assert sum(parts.values()) == pytest.approx(140 * US)


def test_pieces_tile_the_window_and_join_neighbours_of_one_name():
    S = spans.Span
    got = [S("llm.a", 0, 100, {}), S("llm.a.x", 10, 30, {}),
           S("llm.a.x", 30, 40, {}), S("llm.b", 120, 150, {})]
    assert spans.innermost_pieces(got, (5, 130)) == [
        ("llm.a", 5, 10), ("llm.a.x", 10, 40), ("llm.a", 40, 100),
        ("no_program_span", 100, 120), ("llm.b", 120, 130)]
    assert spans.innermost_pieces([], (0, 9)) == [
        ("no_program_span", 0, 9)]


def test_device_time_by_scope_and_by_program(parsed):
    ops, modules, got = parsed
    window, _ = spans.whole_steps(got)
    pairs = spans.attribute(ops, modules)
    by_scope, by_program = spans.device_by_scope(ops, pairs, window)
    assert by_scope == pytest.approx({
        "sflm.kv_read": 200 * US,
        # a fusion counts under the scope of its root
        "sflm.attn": 120 * US, "sflm.head": 80 * US,
        "sflm.mlp": 30 * US, "sflm.kv_write": 20 * US,
        # no tf_op: under the module that ran it, without its id
        "unscoped:jit__step": 200 * US, "unscoped:jit__prefill": 10 * US})
    assert sum(by_scope.values()) == pytest.approx(660 * US)
    # ... and an unscoped operation takes its module's program
    assert by_program == pytest.approx({"llm.engine.step": 600 * US,
                                        "llm.engine.prefill": 60 * US})
    assert pairs[0] == ("sflm.mlp", "llm.engine.prefill")
    assert pairs[1] == ("unscoped:no_module", None)     # copy.8
    assert pairs[3] == ("unscoped:jit__step", "llm.engine.step")
    assert spans.module_label("jit__step(12299521280512369579)") == \
        "jit__step"


def test_prefill_device_time_is_matched_to_the_spans_that_closed(parsed):
    ops, modules, got = parsed
    # the prefill at [10, 40) began before any llm.prefill span: the
    # slice cut it, so neither its time nor a span is counted
    pairs = spans.attribute(ops, modules)
    assert spans.prefill_device(ops, pairs, got) == (
        pytest.approx(60 * US), 1)
    assert spans.admit_waits(got) == {
        "admit": {"n": 2, "mean_waited_ms": 0.5},
        "shed": {"n": 1, "mean_waited_ms": 0.1}}


def run_with(trace, cell="gpt2m.decode_saturate"):
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    c = m.cell(cell)
    run = Run(cell=c, config=m.config(c["config"]),
              traffic=m.traffic(c["traffic"]), seed=1, seconds=10.0)
    run.trace = trace
    return m, run


def read(m, run, name):
    return m.module("layer_metrics", name).read(run)


@pytest.mark.parametrize("name, want", [
    ("step_gap_ms", 0.140 / 2),
    ("step_gap_engine_ms", (12 + 8 + 45 + 37 + 5) / 1e3 / 2),
    ("kv_copy_device_share", 100 * (200 + 20 + 200) / 660),
    ("model_math_ms_per_step", (120 + 80 + 30) / 1e3 / 2),
    ("pending_wait_ms", 0.5),
    ("prefill_device_ms", 0.060)])
def test_reader_on_the_hand_worked_trace(trace_path, name, want):
    m, run = run_with({"path": trace_path})
    assert read(m, run, name) == pytest.approx(want)
    # reduced once, kept for the line before the result
    kept = run.trace["program"]
    assert kept["steps"] == 2 and kept["idle_s"] == pytest.approx(140e-6)
    assert kept["decode_spans"] == {"first_step": 5, "last_step": 6,
                                    "lanes_mean": 2.0}
    assert spans.program(run) is kept
    assert list(kept["idle_by_span"])[:2] == ["llm.decode.wait",
                                              "llm.decode.sample"]


def test_step_mfu_counts_the_traces_whole_steps_over_their_interval(
        trace_path):
    """Two whole steps of two lanes span [90, 890) us of the trace: the
    steps, their lanes and the interval are the trace's; the positions
    attended are the pool's samples over the slice."""
    from benchmarks.cost import streamformer_lm as cost

    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    m, run = run_with({"path": trace_path, "counters": {"samples": [
        (1.0, 2, 2, 300), (1.1, 2, 2, 500), (1.2, 2, 0, 0)]}})
    run.peaks, run.cost = peaks, cost
    flops, _ = cost.decode_step_cost(run.config["model"], 2, 400)
    assert read(m, run, "step_mfu") == pytest.approx(
        100 * flops * 2 / (800e-6 * peaks["flops_per_s"]))
    # no lane decoding in any sample, or a family without cost functions
    run.trace["counters"]["samples"] = [(1.2, 2, 0, 0)]
    assert read(m, run, "step_mfu") is None
    run.cost = None
    assert read(m, run, "step_mfu") is None


@pytest.mark.parametrize("name", READERS)
def test_reader_without_a_trace_a_path_or_a_file_reads_nothing(
        tmp_path, name):
    for trace in (None, {"busy_s": 2.0, "window_s": 2.5},
                  {"path": str(tmp_path / "gone.xplane.pb")}):
        m, run = run_with(trace)
        assert read(m, run, name) is None
        assert "program" not in (run.trace or {})


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_trace_of_a_program_without_spans_reads_nothing(
        tmp_path, name):
    """The parent of the PR that added the spans and scopes: the same
    trace reduces to empty splits and no metric, and nothing raises."""
    m, run = run_with({"path": write_trace(
        tmp_path, "two_steps.xspace.textproto")})
    assert read(m, run, name) is None
    assert run.trace["program"] == {
        "spans": 0, "admits": {}, "prefills": {"n": 0, "device_s": 0.0}}


def test_the_new_metrics_are_declared_for_their_cells():
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    per_cell = {cell: {e["name"] for e in m.metrics(cell, "per_layer")}
                for cell in ("gpt2m.decode_saturate", "gpt2m.steady_short")}
    assert set(READERS[:4]) <= per_cell["gpt2m.decode_saturate"]
    assert set(READERS[4:]) <= per_cell["gpt2m.steady_short"]
    assert not set(READERS[:4]) & per_cell["gpt2m.steady_short"]
    assert not set(READERS[4:]) & per_cell["gpt2m.decode_saturate"]

