"""A request's host-side life on the device trace's clock, and every way
it is refused, counted by cause where it happens.

The wire's spans (``query.handshake``, ``query.ingress``,
``query.chain``, ``query.reply``, the instant ``query.refused``) and
Python's collections (``py.gc``) are ``TraceAnnotation``s written by
``nnstreamer_tpu/obs/devtrace.py``'s gated sites beside the decode
thread's ``llm.*`` phases; ``benchmarks/wire_spans.py`` reads them.

A profiler session is one per process, so every test that reads one
reads the round trip the module's fixture makes: the toy ``tensor_llm``
behind the query wire with ONE slot (a resident stream, a request shed
for want of a slot, a short request after the first client left), and a
bare server whose queue of one frame no pipeline drains (a full queue,
then a watermark).  CPU, Python tracer off, as ``benchmarks/tracing.py``
sets it."""

import gc
import glob
import os
import socket
import sys
import time

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.llm.client import TokenStreamClient, encode_request
from nnstreamer_tpu.llm.element import SHED_CAUSES
from nnstreamer_tpu.llm.paged import PagedKVCachePool
from nnstreamer_tpu.llm.pool import SlotPool
from nnstreamer_tpu.obs import devtrace
from nnstreamer_tpu.query.overload import (AdmissionController,
                                           TokenBucket,
                                           WatermarkShedPolicy)
from nnstreamer_tpu.query.protocol import (T_DATA, T_SHED, recv_msg,
                                           send_tensors)
from nnstreamer_tpu.query.server import (LOSSES, REFUSALS, QueryServer,
                                         get_server, shutdown_server)
from nnstreamer_tpu.tensor.buffer import TensorBuffer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import spans, wire_spans  # noqa: E402

SID = 4733
#: a cache long enough that the first stream is still resident when the
#: second request arrives (hundreds of steps to go)
CUSTOM = ("vocab:61,dim:32,heads:4,head_dim:8,mlp:64,layers:2,"
          "max_seq:2048,dtype:float32")
REQ_CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=24,"
            "types=int32,framerate=0/1")
NEVER = {"gold": 2.0, "silver": 2.0, "bronze": 2.0}
WIRE = ("query.handshake", "query.ingress", "query.chain", "query.reply")


class Counting:
    """``devtrace``'s annotation, counted: every span a site makes."""

    def __init__(self, real):
        self.real, self.names = real, []

    def __call__(self, name, **args):
        self.names.append(name)
        return self.real(name, **args)


def _pipeline(props=""):
    p = parse_launch(
        f"tensor_query_serversrc name=qsrc id={SID} port=0 "
        f"caps={REQ_CAPS} ! "
        f"tensor_llm name=llm custom={CUSTOM} seed=0 slots=1 batch=1 "
        f"max-new-tokens=1500 {props} id={SID} ! "
        f"tensor_query_serversink id={SID}")
    p.play()
    return p, p.get("qsrc").bound_port


def _client(port):
    return TokenStreamClient("127.0.0.1", port, timeout=60.0,
                             qos="gold").connect()


def _wait(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


def _data(sock, seq):
    send_tensors(sock, T_DATA, TensorBuffer(tensors=[encode_request(
        np.asarray([1, 2], np.int32), 1, frame_len=24)]), seq=seq)


def _bare_server_sheds():
    """A server whose queue holds one frame and that nothing drains:
    frame 1 waits in it, frame 2 finds it full (admission had let it
    pass), frame 3 meets the depth watermark.  Returns the server's
    counters and the sheds' seqs."""
    srv = QueryServer(queue_depth=1, admission=AdmissionController(
        policy=WatermarkShedPolicy(arm=NEVER)))
    sock = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
    try:
        _data(sock, 1)
        assert _wait(lambda: srv.incoming.qsize() == 1)
        _data(sock, 2)
        shed = [recv_msg(sock)]
        srv.admission.policy = WatermarkShedPolicy()
        _data(sock, 3)
        shed.append(recv_msg(sock))
        counters = srv.counters()
    finally:
        sock.close()
        srv.close()
    assert [m.type for m in shed] == [T_SHED, T_SHED]
    return counters, [m.seq for m in shed]


def _host_events(path):
    """Every ``/host:CPU`` event: (its line's index — a thread —, the
    line's name, name, start, end, stats)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                start = float(ev.start_ns)
                out.append((k, line.name, ev.name, start,
                            start + float(ev.duration_ns),
                            dict(ev.stats)))
    return sorted(out, key=lambda e: (e[3], -e[4]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    trace_dir = str(tmp_path_factory.mktemp("wire_trace"))
    p, port = _pipeline()
    llm = p.get("llm")
    counting = Counting(devtrace._annotation)
    devtrace._annotation = counting
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    got = {}
    try:
        a, b = _client(port), _client(port)
        stream = a.stream(np.asarray([1, 2, 3], np.int32), 1200,
                          frame_len=24)
        next(stream)                           # A holds the one slot
        assert _wait(lambda: llm.pool.live == 1)
        try:
            b.generate(np.asarray([4], np.int32), 5, frame_len=24)
        except ConnectionError as exc:          # ShedError
            got["shed"] = exc
        a.close()                               # A leaves mid-stream
        b.close()
        assert _wait(lambda: llm.pool.live == 0)
        c = _client(port)
        got["tokens"] = c.generate(np.asarray([5, 6, 7, 8, 9], np.int32),
                                   2, frame_len=24)
        c.close()
        got["bare"], got["bare_seqs"] = _bare_server_sheds()
        gc.collect()
        got["srv"] = get_server(SID).counters()
        got["report"] = llm.report()
    finally:
        jax.profiler.stop_trace()
        devtrace._annotation = counting.real
        p.stop()
        shutdown_server(SID)
    got["llm"] = llm
    got["spans_made"] = counting.names
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    got["path"] = path
    got["events"] = _host_events(path)
    return got


def _by_key(events, name):
    out = {}
    for e in events:
        if e[2] == name and "cid" in e[5] and "seq" in e[5]:
            out.setdefault((e[5]["cid"], e[5]["seq"]), e)
    return out


def test_a_request_is_handshake_ingress_chain_admit_reply_in_order(
        traced):
    """Each served request's pieces, joined on ``(cid, seq)``, follow
    one another and never overlap, from the dial to the first reply;
    the handshake hands over to the ingress at the header."""
    events = traced["events"]
    assert traced["tokens"] and len(traced["tokens"]) == 2
    table = {n: _by_key(events, n) for n in WIRE + ("llm.admit",)}
    served = set(table["query.reply"])
    assert len(served) == 2                    # A and C
    for key in served:
        hs, ing, chain, admit, reply = (table[n][key] for n in (
            "query.handshake", "query.ingress", "query.chain",
            "llm.admit", "query.reply"))
        assert hs[4] <= ing[3] <= ing[4] <= chain[3] <= chain[4] \
            <= admit[3] <= reply[3] <= reply[4], key
        # the header closes the dial and opens the ingress at once
        assert ing[3] - hs[4] < 5e6
        assert reply[5]["bytes"] == 4          # one [1, 1] int32 token
        assert admit[5]["outcome"] == "admit"


def test_no_llm_event_lies_on_any_thread_but_the_decode_thread(traced):
    events = traced["events"]
    lines = {e[0] for e in events if e[2].startswith("llm.")}
    assert len(lines) == 1
    decode, = lines
    assert any(e[0] == decode and e[2] == "query.reply" for e in events)
    # the wire's spans of a connection and of the serving source lie on
    # other threads, and no span the devtrace sites made is an llm. one
    for name in ("query.handshake", "query.ingress", "query.chain"):
        assert {e[0] for e in events if e[2] == name} - {decode}
        assert not {e[0] for e in events if e[2] == name} & {decode}
    assert not [n for n in traced["spans_made"] if n.startswith("llm.")]
    assert set(traced["spans_made"]) == {
        "query.handshake", "query.ingress", "query.chain", "query.reply",
        "query.refused", "py.gc"}


def test_a_shed_for_want_of_a_slot_is_counted_and_traced(traced):
    from nnstreamer_tpu.obs.metrics import REGISTRY

    assert traced["shed"].retry_after_s > 0
    llm = traced["llm"]
    assert llm.shed_total == 1
    assert traced["report"]["refused"] == dict(
        dict.fromkeys(SHED_CAUSES + ("reject",), 0), no_slot=1)
    assert llm.final_report["refused"]["no_slot"] == 1
    counter = REGISTRY.counter("nns_llm_shed_total", element="llm",
                               cause="no_slot",
                               pipeline=llm.pipeline.name or "")
    assert counter.value == 1
    events = traced["events"]
    shed = [e for e in events if e[2] == "llm.admit"
            and e[5].get("outcome") == "shed"]
    assert len(shed) == 1 and shed[0][5]["cause"] == "no_slot"
    key = (shed[0][5]["cid"], shed[0][5]["seq"])
    # the server sent the element's T_SHED: counted, and an instant on
    # the decode thread inside that admission
    assert traced["srv"]["refused"]["element"] == 1
    refused = [e for e in events if e[2] == "query.refused"
               and e[5]["cause"] == "element"]
    assert len(refused) == 1
    assert (refused[0][5]["cid"], refused[0][5]["seq"]) == key
    assert shed[0][3] <= refused[0][3] <= refused[0][4] <= shed[0][4]
    # ... and the request came over the wire like any other
    assert key in _by_key(events, "query.chain")


def test_a_full_queue_and_a_watermark_are_counted_and_traced(traced):
    bare = traced["bare"]
    assert bare["refused"] == dict(dict.fromkeys(REFUSALS, 0),
                                   queue_full=1, watermark=1)
    assert sum(bare["shed"].values()) == 2
    assert sum(bare["admitted"].values()) == 1
    causes = {e[5]["seq"]: e[5]["cause"] for e in traced["events"]
              if e[2] == "query.refused" and e[5]["cause"] != "element"}
    assert causes == dict(zip(traced["bare_seqs"],
                              ("queue_full", "watermark")))
    # a server refusal lies inside its frame's ingress
    ingress = [e for e in traced["events"] if e[2] == "query.ingress"
               and e[5]["seq"] in causes]
    assert len(ingress) == 3 - 1   # frame 1 was queued, not refused


def test_replies_to_a_client_that_left_are_counted_lost(traced):
    lost = traced["srv"]["lost"]
    assert set(lost) == set(LOSSES)
    # at least the eviction's terminal frame found A's connection gone;
    # a token written while A's socket closed may fail as os_error
    assert lost["client_gone"] >= 1
    assert lost["send_timeout"] == 0


def test_a_collection_is_a_span_and_is_counted(traced):
    collections = [e for e in traced["events"] if e[2] == "py.gc"]
    forced = [e for e in collections if e[5]["generation"] == 2]
    assert forced and all("collected" in e[5] for e in forced)
    report = traced["report"]["gc"]
    assert report["collections"][2] >= 1
    assert report["pause_ms"][2] > 0
    assert report["longest_ms"] > 0


def test_the_gc_hook_is_removed_at_stop(traced):
    llm = traced["llm"]
    assert not llm._gc.installed
    assert not any(getattr(cb, "__self__", None) is llm._gc
                   for cb in gc.callbacks)


def test_the_report_holds_the_stall_record(traced):
    stall = traced["llm"].final_report["stall"]
    assert stall["longest_iteration_ms"] > 0
    assert stall["longest_iteration_phase"] in (
        "admit", "prefill", "decode", "egress", "compile")
    assert stall["pending_peak"] >= 1
    assert stall["chain_blocked_ms"] >= 0 and stall["chain_blocks"] >= 0
    # the engine's own report is all there too
    assert traced["llm"].final_report["steps"] > 0


def test_the_readers_find_the_requests_of_the_cpu_trace(traced):
    got = wire_spans.reduce_wire(traced["path"])
    # the trace has no bench.slice: the session's whole length stands in
    events = traced["events"]
    window = (events[0][3], max(e[4] for e in events))
    rows = wire_spans.requests(wire_spans.host_spans(traced["path"]),
                               window)
    served = [r for r in rows if "total" in r]
    assert len(served) == 2
    for r in served:
        assert r["total"] >= r["handshake"] + r["ingress"] \
            + r["pending"] + r["admit_prefill"] + r["reply"] - 1e-6
        assert all(r[k] >= 0 for k in r)
    assert got["steps"] >= 1 and got["wait_s"] > 0
    assert got["gc_spans"] >= 1 and got["wire_spans"] >= 10


def _without(path, drop, out):
    """Write ``path``'s XSpace to ``out`` without the events whose
    metadata name ``drop`` accepts (field numbers as in
    ``benchmarks/spans.py`` ``device_events``)."""
    def varint(buf, i):
        value = shift = 0
        while True:
            byte = buf[i]
            i += 1
            value |= (byte & 0x7F) << shift
            if byte < 0x80:
                return value, i
            shift += 7

    def enc(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def fields(buf):
        i = 0
        while i < len(buf):
            start = i
            key, i = varint(buf, i)
            wire, value = key & 7, None
            if wire == 0:
                _, i = varint(buf, i)
            elif wire == 1:
                i += 8
            elif wire == 5:
                i += 4
            else:
                size, i = varint(buf, i)
                value = buf[i:i + size]
                i += size
            yield key >> 3, bytes(buf[start:i]), value

    def nested(number, body):
        return enc((number << 3) | 2) + enc(len(body)) + body

    def first_varint(buf):
        # field 1, a varint: one tag byte, then the value
        return next((varint(raw, 1)[0] for n, raw, _ in fields(buf)
                     if n == 1), 0)

    def plane(buf):
        names = {}
        for number, _, value in fields(buf):
            if number == 4:          # event_metadata: key 1, value 2
                meta = next(v for n, _, v in fields(value) if n == 2)
                names[first_varint(value)] = next(
                    (bytes(v).decode() for n, _, v in fields(meta)
                     if n == 2), "")
        body = bytearray()
        for number, raw, value in fields(buf):
            if number != 3:
                body += raw
                continue
            line = bytearray()
            for n, r, v in fields(value):
                if n == 4 and drop(names.get(first_varint(v), "")):
                    continue
                line += r
            body += nested(3, bytes(line))
        return bytes(body)

    with open(path, "rb") as fh:
        space = fh.read()
    kept = bytearray()
    for number, raw, value in fields(space):
        kept += nested(1, plane(value)) if number == 1 else raw
    with open(out, "wb") as fh:
        fh.write(bytes(kept))


def test_the_program_reads_the_same_without_the_new_spans(
        traced, tmp_path):
    """The parent's view of this run: the same trace without the wire's
    and the collections' spans reduces, in ``benchmarks/spans.py``, to
    exactly what the whole trace does."""
    parent = str(tmp_path / "parent.xplane.pb")
    _without(traced["path"],
             lambda name: name.startswith("query.") or name == "py.gc",
             parent)
    names = {e[2] for e in _host_events(parent)}
    assert not {n for n in names if n.startswith("query.")}
    assert "py.gc" not in names
    assert {n for n in names if n.startswith("llm.")} == {
        e[2] for e in traced["events"] if e[2].startswith("llm.")}
    assert spans.reduce_program(parent) == \
        spans.reduce_program(traced["path"])
    assert spans.program_spans(parent) == \
        spans.program_spans(traced["path"])
    # ... and the new readers find nothing there but the steps' waits
    got = wire_spans.reduce_wire(parent)
    assert got["wire_spans"] == got["gc_spans"] == 0
    assert got["wait_s"] == wire_spans.reduce_wire(traced["path"])[
        "wait_s"]


def test_with_no_session_the_wire_makes_no_span(monkeypatch):
    """The same round trip with no profiler session: no site of the
    wire, the serving source or the GC hook builds an annotation."""
    p, port = _pipeline()
    llm = p.get("llm")
    counting = Counting(devtrace._annotation)
    monkeypatch.setattr(devtrace, "_annotation", counting)
    try:
        assert devtrace.recording() is False
        c = _client(port)
        assert len(c.generate(np.asarray([3, 4], np.int32), 3,
                              frame_len=24)) == 3
        c.close()
        gc.collect()
        assert llm.report()["gc"]["collections"][2] >= 1
    finally:
        p.stop()
        shutdown_server(SID)
    assert counting.names == []


def test_recording_is_jaxs_own_answer_once_bound():
    from jax.profiler import TraceAnnotation

    devtrace.bind()
    assert devtrace.recording == TraceAnnotation.is_enabled
    assert devtrace.recording() is False
    assert devtrace._never() is False


def test_a_gc_watch_counts_two_clock_reads_a_collection():
    ticks = iter(range(0, 10 ** 9, 1000))
    reads = []

    def clock():
        reads.append(1)
        return next(ticks)

    watch = devtrace.GcWatch(clock)
    watch.install()
    try:
        assert watch.installed
        before = len(reads)
        gc.collect()
        assert len(reads) - before == 2
        gc.collect()
    finally:
        watch.remove()
    assert not watch.installed
    assert watch.collections[2] == 2 and watch.pause_ns[2] == 2000
    assert watch.report() == {"collections": [0, 0, 2],
                              "pause_ms": [0.0, 0.0, 0.002],
                              "longest_ms": 0.001}
    gc.collect()
    assert watch.collections[2] == 2


def test_admission_names_what_refused():
    ctl = AdmissionController(policy=WatermarkShedPolicy(),
                              bucket=TokenBucket(1.0, burst=1.0,
                                                 clock=lambda: 0.0))
    assert ctl.verdict("silver", 1, 10) == (None, "")
    assert ctl.verdict("silver", 1, 10) == (pytest.approx(1.0), "rate")
    hint, why = ctl.verdict("silver", 9, 10)
    assert hint > 0 and why == "policy"
    ctl.start_drain(5.0)
    assert ctl.verdict("gold", 0, 10)[1] == "drain"
    assert ctl.admit("gold", 0, 10) == pytest.approx(
        ctl.verdict("gold", 0, 10)[0], abs=0.01)


@pytest.mark.parametrize("qos, live, draining, want", [
    ("gold", 0, False, ""), ("gold", 2, False, "no_slot"),
    ("bronze", 2, False, "policy"), ("gold", 0, True, "drain")])
def test_a_slot_pool_names_what_refused(qos, live, draining, want):
    pool = SlotPool(2)
    for k in range(live):
        pool.acquire(k, qos=qos)
    if draining:
        pool.admission.start_drain(5.0)
    verdict, why = pool.admit_verdict(qos)
    assert why == want and (verdict is None) == (want == "")
    assert pool.admit(qos) == pytest.approx(verdict, abs=0.01)


def test_a_paged_pool_names_a_page_shortfall_apart_from_a_slot():
    from nnstreamer_tpu.parallel.train_step import StreamFormerConfig

    cfg = StreamFormerConfig(vocab=61, dim=32, heads=4, head_dim=8,
                             mlp=64, layers=2, max_seq=32)
    pool = PagedKVCachePool(cfg, pages=4, page_size=4, slots=2)
    prompt = np.arange(3, dtype=np.int32)
    assert pool.admit_verdict("gold", prompt=prompt, max_new=2) == (
        None, "")
    assert pool.admit_verdict("gold", prompt=np.arange(20),
                              max_new=8)[1] == "no_page"
    pool.acquire("a", qos="gold", prompt=prompt, max_new=2)
    pool.acquire("b", qos="gold", prompt=prompt, max_new=2)
    assert pool.admit_verdict("gold", prompt=prompt, max_new=2)[1] \
        == "no_slot"
