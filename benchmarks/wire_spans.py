"""The host's side of a request, and of a decode step, as the program
writes them into the profiler's trace beside the decode thread's
``llm.*`` phases (``nnstreamer_tpu/obs/devtrace.py``): the query wire's
``query.*`` spans, Python's collections (``py.gc``), and the wait of
the decode thread on the chip (``llm.decode.wait``), on the device's
clock.  Reduced from the same ``.xplane.pb`` as :mod:`benchmarks.spans`
reduces, in a pass of its own; nothing here changes what that module
reads or returns.

A request's life on the server, joined on its ``(cid, seq)``:

- ``query.handshake`` (the connection's thread): the thread's start,
  right after ``accept``, to the first message after the capability
  handshake — in a cell of a connection per request, the server's part
  of the dial;
- ``query.ingress`` (the same thread): the DATA header received → the
  frame in the server's queue (admission, tensor decode, the put);
- the queue: ``query.ingress``'s end → ``query.chain``'s start;
- ``query.chain`` (the serving source's streaming thread): out of the
  queue → the token element has it in its pending list (``chain()``'s
  back-pressure wait included);
- pending: ``query.chain``'s end → the first ``llm.admit`` piece with
  its ``cid`` and ``seq`` (the decode thread takes it);
- admit and prefill: that piece's start → its first ``query.reply``;
- ``query.reply`` (the decode thread, inside ``llm.egress``): the
  reply's write.

``query.refused`` is an instant, with ``cause``, where the server
answered ``T_SHED``.

Every reader of ``layer_metrics/`` that needs these goes through
:func:`wire`, which reduces the file once and keeps the result on
``run.trace["wire"]`` (printed on the line before the result).  A trace
written by a program without these spans (the parent of the PR that
added them) reduces to empty tables, and the readers return ``None``.
"""

from __future__ import annotations

import os
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import spans, stats, xplane
from benchmarks.spans import Span
from benchmarks.xplane import Interval

WIRE_PREFIX = "query."
HANDSHAKE = "query.handshake"
INGRESS = "query.ingress"
CHAIN = "query.chain"
REPLY = "query.reply"
REFUSED = "query.refused"
GC_SPAN = "py.gc"
WAIT_SPAN = "llm.decode.wait"

Key = Tuple[int, int]


def host_spans(path: str) -> List[Span]:
    """Every ``/host:CPU`` event named ``query.*``, ``py.gc``,
    ``llm.*`` or ``bench.slice``, with its stats, by start (the outer
    one first where two start together)."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith((WIRE_PREFIX, spans.PROGRAM_PREFIX)) \
                        or name in (GC_SPAN, xplane.SLICE_SPAN):
                    start = float(ev.start_ns)
                    out.append(Span(name, start,
                                    start + float(ev.duration_ns),
                                    dict(ev.stats)))
    out.sort(key=lambda s: (s.start, -s.end))
    return out


def _key(s: Span) -> Optional[Key]:
    cid, seq = s.stats.get("cid"), s.stats.get("seq")
    if cid is None or seq is None:
        return None
    return int(cid), int(seq)


def _inside(s: Span, window: Interval) -> bool:
    return window[0] <= s.start and s.end <= window[1]


def _clipped_s(found: Sequence[Span], window: Interval) -> float:
    return sum(max(0.0, min(s.end, window[1]) - max(s.start, window[0]))
               for s in found) / 1e9


def requests(got: Sequence[Span], window: Interval
             ) -> List[Dict[str, float]]:
    """One row a request whose ``query.ingress`` and ``query.chain``
    both lie inside ``window``, in ms: ``ingress`` (the header to the
    pending list: ingress, queue and chain), and where the trace holds
    them ``handshake``, ``pending``, ``admit_prefill``, ``reply`` and
    ``total`` (the dial's start to the first reply's end)."""
    first: Dict[str, Dict[Key, Span]] = defaultdict(dict)
    for s in got:
        if s.name in (HANDSHAKE, INGRESS, CHAIN, REPLY) or (
                s.name == spans.ADMIT_SPAN and "waited_us" in s.stats):
            key = _key(s)
            if key is not None:
                first[s.name].setdefault(key, s)
    rows = []
    for key, ing in first[INGRESS].items():
        chain = first[CHAIN].get(key)
        if chain is None or not (_inside(ing, window)
                                 and _inside(chain, window)):
            continue
        row = {"ingress": (chain.end - ing.start) / 1e6}
        hs = first[HANDSHAKE].get(key)
        admit = first[spans.ADMIT_SPAN].get(key)
        reply = first[REPLY].get(key)
        if hs is not None:
            row["handshake"] = (hs.end - hs.start) / 1e6
        if admit is not None:
            row["pending"] = (admit.start - chain.end) / 1e6
            if reply is not None:
                row["admit_prefill"] = (reply.start - admit.start) / 1e6
        if reply is not None:
            row["reply"] = (reply.end - reply.start) / 1e6
            if hs is not None:
                row["total"] = (reply.end - hs.start) / 1e6
        rows.append(row)
    return rows


def _split(rows: List[Dict[str, float]]) -> Dict[str, Any]:
    """The whole life of the median request and of the one at the 95th
    percentile, by its ``total``."""
    whole = sorted((r for r in rows if "total" in r),
                   key=lambda r: r["total"])
    if not whole:
        return {}
    p95 = stats.percentile([r["total"] for r in whole], 95)
    return {"n": len(whole), "median": whole[(len(whole) - 1) // 2],
            "p95": next(r for r in whole if r["total"] == p95)}


def reduce_wire(path: str) -> Dict[str, Any]:
    """Everything the four readers take, from one pass over ``path``."""
    got = host_spans(path)
    window = xplane.slice_window((s.name, s.start, s.end) for s in got)
    out: Dict[str, Any] = {
        "wire_spans": sum(1 for s in got if s.name.startswith(WIRE_PREFIX)),
        "gc_spans": sum(1 for s in got if s.name == GC_SPAN)}
    program = [s for s in got if s.name.startswith(spans.PROGRAM_PREFIX)]
    whole = spans.whole_steps(program)
    if whole is not None:
        steps_window, n = whole
        waits = [s for s in program if s.name == WAIT_SPAN]
        out["steps"] = n
        out["wait_s"] = _clipped_s(waits, steps_window)
    if window is None:
        return out
    out["slice_s"] = (window[1] - window[0]) / 1e9
    out["decode_thread_spans"] = sum(1 for s in program
                                     if _inside(s, window))
    out["gc_s"] = _clipped_s([s for s in got if s.name == GC_SPAN], window)
    replies = [s for s in got if s.name == REPLY and _inside(s, window)]
    if replies:
        out["replies"] = {"n": len(replies), "mean_us": sum(
            s.end - s.start for s in replies) / len(replies) / 1e3}
    refused: Dict[str, int] = defaultdict(int)
    for s in got:
        if s.name == REFUSED and _inside(s, window):
            refused[str(s.stats.get("cause", "none"))] += 1
    out["refused"] = dict(refused)
    rows = requests(got, window)
    if rows:
        parts = ("handshake", "ingress", "pending", "admit_prefill",
                 "reply")
        out["requests"] = {
            "n": len(rows),
            "mean_ingress_ms": sum(r["ingress"] for r in rows) / len(rows),
            "mean_ms": {p: sum(r[p] for r in rows if p in r)
                        / max(1, sum(1 for r in rows if p in r))
                        for p in parts},
            "first_token_split_ms": _split(rows)}
    return out


def wire(run) -> Optional[Dict[str, Any]]:
    """The reduction of ``run``'s trace, made once and kept under
    ``run.trace["wire"]``; ``None`` where the run has no trace, the
    trace names no file, or the file is gone."""
    trace = run.trace
    if not trace or not trace.get("path") \
            or not os.path.exists(trace["path"]):
        return None
    if "wire" not in trace:
        trace["wire"] = reduce_wire(trace["path"])
    return trace["wire"]
