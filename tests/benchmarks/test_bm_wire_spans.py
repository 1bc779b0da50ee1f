"""The host's side of a request and of a step, reduced from a trace
(``benchmarks/wire_spans.py``), and the four readers on top, on the
hand-made trace of ``test_bm_spans.py`` with a request's wire spans, two
reply writes and two collections added to it (``with_wire``, worked out
below).  The same trace without them is what the parent of the program
that writes them leaves: every metric read from it before reads the
same with them."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import spans, wire_spans, xplane  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402
from benchmarks.record import Run  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
NEW = ("ingress_ms", "reply_write_us", "host_slack_ms",
       "gc_pause_ms_per_s")
CELLS = ("gpt2m.decode_saturate", "gpt2m.steady_short",
         "phi4flash.reason_saturate", "gigachat31.latent_saturate")


def _stats(**kw):
    ids = {"cid": 6, "seq": 7, "bytes": 8, "generation": 9,
           "collected": 10, "cause": 11}
    return " ".join(
        f'stats {{ metadata_id: {ids[k]} str_value: "{v}" }}'
        if isinstance(v, str)
        else f"stats {{ metadata_id: {ids[k]} int64_value: {v} }}"
        for k, v in kw.items())


def _event(meta, lo_us, hi_us, **kw):
    return (f"    events {{ metadata_id: {meta} "
            f"offset_ps: {int(lo_us * 1e6)} "
            f"duration_ps: {int((hi_us - lo_us) * 1e6)} {_stats(**kw)} }}")


#: (times in us from the host lines' timestamp; the slice is [10, 1000))
#: request (7, 1): handshake [20, 30), ingress [30, 34), the queue to
#:   100, chain [100, 116), pending to the admission at 816 (its
#:   waited_us is 700), the prefill, the first reply [885.5, 886.5)
#:   inside llm.egress [885, 887): ingress_ms = (116 - 30) / 1e3
#: request (8, 4): handshake [40, 50), ingress [50, 52), refused by the
#:   watermark at 51: no chain, not a row
#: request (9, 1): ingress [900, 903), chain [990, 1005) ends past the
#:   slice: not a row
#: replies inside the slice: 2 us at [472.5, 474.5) in the first
#:   llm.egress, 1 us: reply_write_us = 1.5
#: py.gc [300, 310) generation 2 inside llm.decode.wait, and [995,
#:   1005) generation 0, 5 us of it in the slice: 15 us of 990
#: llm.decode.wait inside the whole steps [90, 890): 340 + 305 us over
#:   2 steps, in the trace already: host_slack_ms = 0.3225
DECODE_LINE = [
    _event(17, 472.5, 474.5, cid=8, seq=5, bytes=4),
    _event(17, 885.5, 886.5, cid=7, seq=1, bytes=4),
    _event(19, 300, 310, generation=2, collected=17),
    _event(19, 995, 1005, generation=0, collected=0)]
CONNECTION_LINE = [
    _event(14, 20, 30, cid=7, seq=1), _event(15, 30, 34, cid=7, seq=1),
    _event(14, 40, 50, cid=8, seq=4), _event(15, 50, 52, cid=8, seq=4),
    _event(18, 51, 51, cid=8, seq=4, cause="watermark"),
    _event(15, 900, 903, cid=9, seq=1)]
SOURCE_LINE = [_event(16, 100, 116, cid=7, seq=1),
               _event(16, 990, 1005, cid=9, seq=1)]
NAMES = {14: "query.handshake", 15: "query.ingress", 16: "query.chain",
         17: "query.reply", 18: "query.refused", 19: "py.gc"}


def with_wire(text, gc=True):
    """``steps_with_spans``' text with the program's new spans."""
    admit = ('stats { metadata_id: 3 int64_value: 700 } '
             'stats { metadata_id: 4 str_value: "admit" }')
    assert text.count(admit) == 1
    text = text.replace(admit, admit + " " + _stats(cid=7, seq=1))
    last = text.index("\n", text.index("offset_ps: 1012000000"))
    decode = DECODE_LINE if gc else DECODE_LINE[:2]
    text = text[:last + 1] + "\n".join(decode) + text[last:]
    lines = "".join(
        f'  lines {{\n    id: {k} name: "python3" timestamp_ns: 1000\n'
        + "\n".join(events) + "\n  }\n"
        for k, events in ((9, CONNECTION_LINE), (10, SOURCE_LINE)))
    meta = "".join(f'  event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{n}" }} }}\n' for k, n in NAMES.items())
    stat = "".join(f'  stat_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{n}" }} }}\n' for n, k in (
                       ("cid", 6), ("seq", 7), ("bytes", 8),
                       ("generation", 9), ("collected", 10),
                       ("cause", 11)))
    anchor = '  event_metadata { key: 13 value { id: 13 name: "bench.slice" } }\n'
    assert text.count(anchor) == 1
    return text.replace(anchor, lines + anchor + meta + stat)


def write(tmp_path, name, text):
    from jax.profiler import ProfileData

    path = tmp_path / name
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def fixture_text(name):
    with open(os.path.join(FIXTURES, name), encoding="utf-8") as fh:
        return fh.read()


@pytest.fixture()
def parent_path(tmp_path):
    return write(tmp_path, "parent.xplane.pb",
                 fixture_text("steps_with_spans.xspace.textproto"))


@pytest.fixture()
def change_path(tmp_path):
    return write(tmp_path, "change.xplane.pb", with_wire(
        fixture_text("steps_with_spans.xspace.textproto")))


def run_with(trace, cell):
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    c = m.cell(cell)
    config = m.config(c["config"])
    run = Run(cell=c, config=config, traffic=m.traffic(c["traffic"]),
              seed=1, seconds=10.0)
    run.trace = trace
    return m, run


def read(m, run, name):
    return m.module("layer_metrics", name).read(run)


US = 1e-6


@pytest.mark.parametrize("name, cell, want", [
    ("ingress_ms", "gpt2m.steady_short", (116 - 30) / 1e3),
    ("reply_write_us", "gpt2m.decode_saturate", 1.5),
    ("host_slack_ms", "gigachat31.latent_saturate", (340 + 305) / 1e3 / 2),
    ("gc_pause_ms_per_s", "gpt2m.steady_short", 15 / 990 * 1e3)])
def test_reader_on_the_hand_worked_trace(change_path, name, cell, want):
    m, run = run_with({"path": change_path}, cell)
    assert read(m, run, name) == pytest.approx(want)
    # reduced once, kept for the line before the result
    kept = run.trace["wire"]
    assert wire_spans.wire(run) is kept
    assert kept["refused"] == {"watermark": 1}
    assert kept["replies"]["n"] == 2 and kept["requests"]["n"] == 1


def test_a_requests_life_is_split_from_dial_to_first_reply(change_path):
    got = wire_spans.reduce_wire(change_path)
    split = got["requests"]["first_token_split_ms"]
    assert split["n"] == 1
    assert split["median"] == split["p95"] == pytest.approx({
        "handshake": 10 * US * 1e3, "ingress": 86 * US * 1e3,
        "pending": 700 * US * 1e3, "admit_prefill": 69.5 * US * 1e3,
        "reply": 1 * US * 1e3, "total": 866.5 * US * 1e3})
    assert got["gc_spans"] == 2 and got["gc_s"] == pytest.approx(15 * US)
    assert got["slice_s"] == pytest.approx(990 * US)


def test_the_split_takes_the_median_and_the_95th_percentile_request():
    S = spans.Span
    got = []
    for cid in range(1, 21):                 # totals 1 .. 20 ms
        base = cid * 100e6
        got += [S("query.handshake", base, base + 1e5,
                  {"cid": cid, "seq": 1}),
                S("query.ingress", base + 1e5, base + 2e5,
                  {"cid": cid, "seq": 1}),
                S("query.chain", base + 3e5, base + 4e5,
                  {"cid": cid, "seq": 1}),
                S("llm.admit", base + 5e5, base + 6e5,
                  {"cid": cid, "seq": 1, "waited_us": 100}),
                S("query.reply", base + cid * 1e6 - 1e4, base + cid * 1e6,
                  {"cid": cid, "seq": 1})]
    rows = wire_spans.requests(got, (0, 1e12))
    split = wire_spans._split(rows)
    assert split["n"] == 20
    assert split["median"]["total"] == pytest.approx(10.0)
    assert split["p95"]["total"] == pytest.approx(19.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_where_its_spans_are_absent(tmp_path, name):
    """No trace, no file, a trace with no program span at all: nothing,
    and nothing raises."""
    cell = {"ingress_ms": "gpt2m.steady_short",
            "gc_pause_ms_per_s": "gpt2m.steady_short"}.get(
                name, "gpt2m.decode_saturate")
    bare = write(tmp_path, "bare.xplane.pb",
                 fixture_text("two_steps.xspace.textproto"))
    for trace in (None, {"busy_s": 2.0, "window_s": 2.5},
                  {"path": str(tmp_path / "gone.xplane.pb")},
                  {"path": bare}):
        m, run = run_with(trace, cell)
        assert read(m, run, name) is None


@pytest.mark.parametrize("name, want", [
    ("ingress_ms", None), ("reply_write_us", None),
    ("gc_pause_ms_per_s", None),
    # the decode thread's waits are the parent's spans too
    ("host_slack_ms", 0.3225)])
def test_the_parents_trace_reads_only_the_wait(parent_path, name, want):
    m, run = run_with({"path": parent_path}, "gpt2m.decode_saturate")
    got = read(m, run, name)
    assert got == (None if want is None else pytest.approx(want))


def test_a_slice_with_the_wire_and_no_collection_reads_zero(tmp_path):
    path = write(tmp_path, "nogc.xplane.pb", with_wire(
        fixture_text("steps_with_spans.xspace.textproto"), gc=False))
    m, run = run_with({"path": path}, "gpt2m.steady_short")
    assert read(m, run, "gc_pause_ms_per_s") == 0.0
    assert read(m, run, "ingress_ms") == pytest.approx(0.086)


def _full_run(path, cell):
    """A run as the driver leaves it: the reduced trace with the
    slice's counters, the run's counters and requests; the same on
    both sides of a comparison."""
    m, run = run_with(None, cell)
    with open(m.find("peaks.json"), encoding="utf-8") as fh:
        run.peaks = json.load(fh)["TPU v5 lite"]
    run.cost = m.module("cost", run.config["family"])
    run.t0, run.t1 = 0.0, 10.0
    phase_ns = dict.fromkeys(("idle", "admit", "prefill",
                              "llm-prefill-chunk", "decode", "egress",
                              "compile"), 1000)
    run.requests = [{"id": 0, "due": 1.0, "sent": 1.0, "ok": True,
                     "outcome": "done", "stamps": [1.1, 1.2, 1.35],
                     "tokens": [1, 2, 3]}]
    run.counters = {"steps": 2, "step_tokens": 4, "phase_ns": phase_ns,
                    "shed": 0, "samples": [(1.0, 2, 2, 300)],
                    "slots": 32, "compiles": []}
    run.trace = xplane.reduce_trace(path)
    run.trace["counters"] = dict(run.counters, samples=[
        (1.0, 2, 2, 300), (1.1, 2, 2, 500)])
    return m, run


@pytest.mark.parametrize("cell", CELLS)
def test_every_metric_there_was_reads_the_same_with_the_new_spans(
        parent_path, change_path, cell):
    m, parent = _full_run(parent_path, cell)
    _, change = _full_run(change_path, cell)
    names = [e["name"] for e in m.metrics(cell, "per_layer")
             if e["name"] not in NEW]
    assert len(names) >= 6
    got = {n: (read(m, parent, n), read(m, change, n)) for n in names}
    assert {n: a for n, (a, _) in got.items()} == \
        {n: b for n, (_, b) in got.items()}
    assert sum(1 for a, _ in got.values() if a is not None) >= 5
    for key in ("busy_s", "window_s", "idle_share", "device_ops"):
        assert parent.trace[key] == change.trace[key]


def test_the_program_reduction_is_unchanged_by_the_new_spans(
        parent_path, change_path):
    assert spans.reduce_program(change_path) == \
        spans.reduce_program(parent_path)
    # no new span is taken for the decode thread's
    assert not [s for s in spans.program_spans(change_path)
                if not s.name.startswith("llm.")]
    assert [s.name for s in spans.program_spans(change_path)] == \
        [s.name for s in spans.program_spans(parent_path)]


@pytest.mark.parametrize("name", NEW)
def test_each_reader_loads_by_name_as_the_harness_loads_one(name):
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert callable(m.module("layer_metrics", name).read)
