"""The trace reduction, on intervals worked by hand and on a small
trace in the shape a TPU run has (``fixtures/two_steps.xspace.textproto``
is its readable form; the test writes the ``.xplane.pb`` from it)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import xplane  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "two_steps.xspace.textproto")


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
    assert got["device_span_s"] == pytest.approx(310e-6)
    assert got["idle_share"] == pytest.approx(1 - 120 / 310)
    names = [n for n, _ in got["device_ops"]]
    assert names == ["fusion.1", "copy.2", "convert.3", "custom-call.4"]
    assert got["device_ops"][0][1] == pytest.approx(80e-6)
    gaps = got["idle_gaps"]
    assert [n for n, _ in gaps] == ["argmax and egress",
                                    "TransferFromDevice"]
    assert [s for _, s in gaps] == pytest.approx([150e-6, 40e-6])
    # the host's own timing of the window, when given, is the base
    timed = xplane.reduce_trace(path, window_s=400e-6)
    assert timed["idle_share"] == pytest.approx(1 - 120 / 400)
    assert timed["busy_s"] == got["busy_s"]


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
