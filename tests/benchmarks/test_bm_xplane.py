"""The trace reduction, on intervals worked by hand and on small
traces in the shape a TPU run has (``fixtures/*.xspace.textproto`` are
their readable forms; the tests write the ``.xplane.pb`` from them):
each carries the ``bench.slice`` span that is the window: in
``two_steps`` and ``steps_with_spans`` it equals the device's first to
last event; in ``slice_overrun`` the profiler recorded operations on
both sides of it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import xplane  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
FIXTURE = os.path.join(FIXTURES, "two_steps.xspace.textproto")
US = 1e-6


def test_busy_time_is_the_union_not_the_sum():
    # [0,40) and [30,60) overlap by 10; [100,140) stands alone
    assert xplane.merge([(30, 60), (0, 40), (100, 140)]) == [
        (0, 60), (100, 140)]
    assert xplane.busy_ns([(30, 60), (0, 40), (100, 140)]) == 100
    # an interval inside another adds nothing; an empty one is dropped
    assert xplane.busy_ns([(0, 100), (10, 20), (50, 50)]) == 100
    assert xplane.busy_ns([]) == 0


def test_idle_gaps_longest_first_and_window_edges():
    gaps = xplane.idle_gaps([(10, 20), (15, 30), (80, 90)], (0, 100))
    assert gaps == [(30, 80), (0, 10), (90, 100)]
    assert xplane.idle_gaps([(0, 100)], (0, 100)) == []
    assert xplane.idle_gaps([], (5, 9)) == [(5, 9)]


def test_top_ops_sum_by_name_and_order():
    events = [("a", 0, 10), ("b", 0, 30), ("a", 50, 76), ("c", 0, 35)]
    top = xplane.top_ops(events, top=2)
    assert [n for n, _ in top] == ["a", "c"]
    assert [s for _, s in top] == pytest.approx([36e-9, 35e-9])
    assert [n for n, _ in xplane.top_ops(events)] == ["a", "c", "b"]
    # equal times: by name, so the order never depends on the input's
    assert xplane.top_ops([("z", 0, 5), ("y", 0, 5)]) == [
        ("y", 5e-9), ("z", 5e-9)]


def test_operations_of_one_kind_and_shape_share_a_label():
    # a v5e trace names an operation by its whole HLO line
    a = ("%fusion.3326 = (bf16[33,24,256,16,64]{4,3,2,1,0:T(8,128)(2,1)}, "
         "bf16[33,24,256,16,64]{4,3,2,1,0:T(8,128)(2,1)}) fusion(bf16[33,"
         "24,1024,16,64]{4,3,2,1,0:T(8,128)(2,1)} %fusion.265), kind=kLoop")
    b = a.replace("3326", "3340").replace("265", "251")
    assert xplane.op_label(a) == xplane.op_label(b) == \
        "fusion (bf16[33,24,256,16,64]"
    assert xplane.op_label(
        "%fusion.262.remat_uncompressed = bf16[33,24,1024,16,64]{2,4,3,"
        "1,0} copy(%x)") == "fusion.remat_uncompressed bf16[33,24,1024,16,64]"
    assert xplane.op_label("jit__step(123)") == "jit__step(123)"
    assert xplane.top_ops([(a, 0, 10), (b, 20, 30)]) == [
        ("fusion (bf16[33,24,256,16,64]", 20e-9)]


def test_a_gap_is_named_by_the_host_event_that_overlaps_it_most():
    host = xplane.HostActivity([("outer", 0, 1000), ("inner", 100, 200),
                                ("elsewhere", 500, 600)])
    # both cover the gap fully: the shorter, innermost one names it
    assert host.name_gap((120, 180)) == "inner"
    assert host.name_gap((150, 400)) == "outer"
    assert host.name_gap((2000, 3000)) == "no host event"
    assert xplane.HostActivity([]).name_gap((0, 1)) == "no host event"


@pytest.fixture()
def trace_file(tmp_path):
    from jax.profiler import ProfileData

    with open(FIXTURE, encoding="utf-8") as fh:
        raw = ProfileData.text_proto_to_serialized_xspace(fh.read())
    run = tmp_path / "plugins" / "profile" / "2026_09_26_00_00_00"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(raw)
    return str(tmp_path)


def test_reduction_of_the_recorded_fixture(trace_file):
    path = xplane.find_trace(trace_file)
    assert path.endswith("vm.xplane.pb")
    got = xplane.reduce_trace(path)
    # XLA Ops: union 120 us of a 310 us span; XLA Modules encloses them
    # and Async XLA Ops runs beside them: neither is counted
    assert got["devices"] == 1 and got["op_events"] == 5
    assert got["busy_s"] == pytest.approx(120e-6)
    assert got["window_s"] == pytest.approx(310e-6)
    assert got["idle_share"] == pytest.approx(1 - 120 / 310)
    names = [n for n, _ in got["device_ops"]]
    assert names == ["fusion.1", "copy.2", "convert.3", "custom-call.4"]
    assert got["device_ops"][0][1] == pytest.approx(80e-6)
    gaps = got["idle_gaps"]
    assert [n for n, _ in gaps] == ["argmax and egress",
                                    "TransferFromDevice"]
    assert [s for _, s in gaps] == pytest.approx([150e-6, 40e-6])


def test_a_trace_without_device_operations_is_refused(tmp_path):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "t" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5 } } '
        'event_metadata { key: 1 value { id: 1 name: "x" } } }')
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(raw)
    with pytest.raises(ValueError, match="no operation ran on a device"):
        xplane.reduce_trace(str(path))
    with pytest.raises(FileNotFoundError):
        xplane.find_trace(str(tmp_path / "nothing"))


# -- the window is a span in the trace ------------------------------------
#: window (us) -> busy us, device_ops (us), idle_gaps (us), by hand from
#: the fixture's header
WINDOWS = {
    # as recorded: operations straddle both edges and lie outside
    (100, 400): (130, [["fusion.1", 80], ["copy.2", 40], ["convert.3", 20]],
                 [["llm.egress", 120], ["np.asarray(jax.Array)", 50]]),
    # idle at both edges: [50,80) under no host event (the slice span
    # itself names nothing), [150,190) under the copy out
    (50, 190): (70, [["copy.2", 40], ["fusion.1", 40]],
                [["np.asarray(jax.Array)", 40], ["no host event", 30]]),
    # inside one operation that began before the span and ends after it
    (205, 255): (50, [["fusion.1", 50]], []),
}


@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_device_events_are_clipped_to_the_slice_span(overrun_trace, window):
    lo, hi = window
    busy, ops, gaps = WINDOWS[window]
    got = xplane.reduce_trace(overrun_trace(lo, hi))
    assert got["window_s"] == pytest.approx((hi - lo) * US, rel=1e-12)
    assert got["busy_s"] == pytest.approx(busy * US, rel=1e-12)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["idle_share"] == pytest.approx(1 - busy / (hi - lo))
    assert [n for n, _ in got["device_ops"]] == [n for n, _ in ops]
    assert [s for _, s in got["device_ops"]] == pytest.approx(
        [s * US for _, s in ops])
    assert [n for n, _ in got["idle_gaps"]] == [n for n, _ in gaps]
    assert [s for _, s in got["idle_gaps"]] == pytest.approx(
        [s * US for _, s in gaps])
    # busy and idle tile the window, edges included
    assert got["busy_s"] + sum(s for _, s in got["idle_gaps"]) == \
        pytest.approx(got["window_s"])


def test_a_device_busy_from_before_the_span_to_after_it_never_idles(
        overrun_trace):
    got = xplane.reduce_trace(overrun_trace(205, 255))
    assert got["busy_s"] == got["window_s"]          # exactly: no 1.00004
    assert got["idle_share"] == 0.0
    assert got["idle_gaps"] == []


def test_an_operation_wholly_outside_the_span_adds_nothing(overrun_trace):
    got = xplane.reduce_trace(overrun_trace())
    assert "fusion.0" not in [n for n, _ in got["device_ops"]]
    assert got["op_events"] == 4                     # of the 6 recorded
    # a window over all the profiler recorded holds it: 230 us busy,
    # 100 more than the slice's 130
    whole = xplane.reduce_trace(overrun_trace(0, 500, name="all.xplane.pb"))
    assert whole["busy_s"] == pytest.approx(230 * US)
    assert whole["op_events"] == 6
    assert dict(map(tuple, whole["device_ops"]))["fusion.0"] == \
        pytest.approx(60 * US)


def test_a_slice_in_which_nothing_ran_is_refused(overrun_trace):
    # the span opens as an operation ends and closes as one begins
    with pytest.raises(ValueError, match="inside the bench.slice span"):
        xplane.reduce_trace(overrun_trace(150, 200))


@pytest.mark.parametrize("fixture, busy_us, span_us, events", [
    ("two_steps", 120, 310, 5), ("steps_with_spans", 810, 990, 14)])
def test_the_older_fixtures_reduce_as_before_and_need_their_span(
        xplane_file, fixture, busy_us, span_us, events):
    with open(os.path.join(FIXTURES, fixture + ".xspace.textproto"),
              encoding="utf-8") as fh:
        text = fh.read()
    # their span is the device's first to last event, which was the
    # window before the span existed: the numbers stand
    got = xplane.reduce_trace(xplane_file(text))
    assert got["busy_s"] == pytest.approx(busy_us * US)
    assert got["window_s"] == pytest.approx(span_us * US)
    assert got["idle_share"] == pytest.approx(1 - busy_us / span_us)
    assert got["op_events"] == events
    # one definition of the window: a file without the span is refused
    assert text.count('name: "bench.slice"') == 1
    with pytest.raises(ValueError, match="no bench.slice span"):
        xplane.reduce_trace(xplane_file(
            text.replace('name: "bench.slice"', 'name: "some.other.span"'),
            "old.xplane.pb"))


def test_every_device_is_clipped_to_the_same_window(overrun_trace):
    # a second chip: [90,130) straddles the start, [300,350) inside,
    # [405,430) after the window: 30 + 50 = 80 us of the same 300
    second = """
planes {
  id: 3
  name: "/device:TPU:1"
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 40000000 }
    events { metadata_id: 1 offset_ps: 300000000 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 405000000 duration_ps: 25000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
}
"""
    got = xplane.reduce_trace(overrun_trace(more=second))
    assert got["devices"] == 2
    assert got["window_s"] == pytest.approx(300 * US)
    assert got["busy_s"] == pytest.approx((130 + 80) / 2 * US)
    # the breakdown is the fullest chip's
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(80 * US)]
    # a chip that ran nothing inside the window is not averaged in
    alone = xplane.reduce_trace(overrun_trace(205, 255, more=second,
                                              name="b.xplane.pb"))
    assert alone["devices"] == 1 and alone["idle_share"] == 0.0


def test_traced_slice_writes_the_span_and_the_reducer_finds_it(tmp_path):
    """On the CPU backend's own trace: ``ProfileData`` reads it, the
    span is on its ``/host:CPU`` plane, and as it holds no device plane
    the live reduction refuses it for that and not for the span."""
    import time

    import jax
    import jax.numpy as jnp

    from benchmarks import tracing

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the CPU backend's trace")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with pytest.raises(ValueError, match="no operation ran on a device"):
        with tracing.traced_slice(str(tmp_path)):
            t = time.monotonic()
            f(x).block_until_ready()
            time.sleep(0.05)
            held = time.monotonic() - t
    host, devices, planes = xplane.read_planes(
        xplane.find_trace(str(tmp_path)))
    assert devices == [] and xplane.HOST_PLANE in planes
    window = xplane.slice_window(host)
    assert window is not None
    # the span holds the body (and closes before stop_trace: it is
    # recorded at all), on the trace's clock, which counts from the
    # profiler's start
    assert (window[1] - window[0]) / 1e9 >= held
    assert 0 <= window[0] and (window[1] - window[0]) / 1e9 < held + 5.0
