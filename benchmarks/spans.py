"""The program's own names inside the profiler's trace: the decode
thread's phases (``llm.<state>`` spans and their ``llm.<state>.<part>``
children, written by ``nnstreamer_tpu/llm/engine.py``'s ``PhaseClock``
as ``TraceAnnotation``s) and the scopes of the step programs
(``llm.engine.*`` around ``sflm.*``, ``jax.named_scope``), reduced from
the same ``.xplane.pb`` as :mod:`benchmarks.xplane` reduces, on the
device's clock.

Two partitions, both exact.  The device's **idle** time inside a whole
number of decode steps is cut at span boundaries and each piece given to
the innermost program span open at that instant (``no_program_span``
where none is); the parts sum to the idle time, as ``PhaseClock`` sums
to the thread's.  The device's **operation** time in the same interval
is given to the ``sflm.*`` scope each operation ran under, and to the
``llm.engine.*`` program its XLA module is.

**Where an operation's scope comes from** (a v5e trace, looked at by
hand, PR 24): not from the event — an ``XLA Ops`` event's own stats are
its device offset and duration — and not from a ``Framework Name
Scope`` line, which the file does not hold (TensorBoard derives it).
It is the ``tf_op`` stat of the event's METADATA
(``jit(_step)/llm.engine.step/sflm.kv_read/gather:``), which
``jax.profiler.ProfileData`` does not show; :func:`device_events` reads
it from the file's own bytes with a protobuf wire reader of thirty
lines.  A fusion takes the scope of its root instruction, so work the
compiler fused into another scope's root counts there; that is
accepted.  The copies the compiler makes of its own accord
(``fusion.remat_compressed``, ``fusion.remat_uncompressed``, ``copy`` of
a donated argument) carry no ``tf_op`` at all: they go under
``unscoped:<module>``, the name of the enclosing ``XLA Modules`` event
without its program id (``unscoped:jit__step``).

Every reader of ``layer_metrics/`` goes through :func:`program`, which
reduces the file once and leaves the whole split on ``run.trace`` for
``run.py`` to print on the line before the result.  A trace written by
a program without these spans and scopes (the parent of the PR that
added them) reduces to empty splits, and the readers return ``None``.
"""

from __future__ import annotations

import bisect
import os
import re
from collections import defaultdict
from typing import (Any, Dict, Iterable, Iterator, List, NamedTuple,
                    Optional, Sequence, Tuple)

from benchmarks import xplane
from benchmarks.xplane import Interval

PROGRAM_PREFIX = "llm."
#: a decode step begins where its first child does (see whole_steps)
STEP_START_SPAN = "llm.decode.operands"
STEP_SPAN = "llm.decode"
ADMIT_SPAN = "llm.admit"
PREFILL_SPAN = "llm.prefill"
ENGINE_PREFIX = "llm.engine."
MODEL_PREFIX = "sflm."
PREFILL_PROGRAM = "llm.engine.prefill"
NO_SPAN = "no_program_span"
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
#: the XLA module of the engine's decode step, pooled or paged (both
#: closures are ``_step``): its unscoped operations are the layout
#: copies of the pool (see layer_metrics/kv_copy_device_share.py)
STEP_MODULE = "jit__step"

#: the model's scopes that move the pool, and those that are its math
KV_SCOPES = ("sflm.kv_write", "sflm.kv_read")
MATH_SCOPES = ("sflm.embed", "sflm.qkv", "sflm.attn", "sflm.mlp",
               "sflm.moe", "sflm.head")


class Span(NamedTuple):
    """One host span the program wrote."""
    name: str
    start: float          # ns
    end: float            # ns
    stats: Dict[str, Any]


class Op(NamedTuple):
    """One device event with the JAX name of its metadata."""
    name: str
    start: float          # ns
    end: float            # ns
    tf_op: str


# -- the file's own bytes ------------------------------------------------
def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one protobuf message: a varint as an
    int, a length-delimited field as a view of its bytes; fixed-width
    fields (a stat's double) are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} in an XSpace")


def _text(view: memoryview) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry: memoryview) -> Tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for number, got in _fields(entry):
        if number == 1:
            key = got
        elif number == 2:
            value = got
    return key, value


def device_events(path: str) -> List[Tuple[List[Op], List[Op]]]:
    """Per ``/device:TPU:*`` plane of the file, its operation events
    (the ``XLA Ops`` line) and its ``XLA Modules`` events, each with
    the ``tf_op`` stat of its event metadata (``""`` where it has none).
    Field numbers are ``xplane.proto``'s: ``XSpace.planes`` 1;
    ``XPlane`` name 2, lines 3, event_metadata 4, stat_metadata 5;
    ``XLine`` name 2, timestamp_ns 3, events 4; ``XEvent`` metadata_id
    1, offset_ps 2, duration_ps 3; ``XEventMetadata`` name 2, stats 5;
    ``XStat`` metadata_id 1, str_value 5, ref_value 7;
    ``XStatMetadata`` name 2.  Times come out as ``ProfileData`` gives
    them: the line's ``timestamp_ns`` plus the event's offset."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = []
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, metadata, stat_names = "", [], {}, {}
        for number, value in _fields(plane):
            if number == 2:
                name = _text(value)
            elif number == 3:
                lines.append(value)
            elif number == 4:
                key, entry = _map_entry(value)
                metadata[key] = entry
            elif number == 5:
                key, entry = _map_entry(value)
                stat_names[key] = next(
                    (_text(v) for n, v in _fields(entry) if n == 2), "")
        if not name.startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        named: Dict[int, Tuple[str, str]] = {}
        for key, entry in metadata.items():
            label, tf_op = "", ""
            for number, value in _fields(entry):
                if number == 2:
                    label = _text(value)
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in tf_op_ids:
                        tf_op = (_text(stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            named[key] = (label, tf_op)
        by_line: Dict[str, List[Op]] = {}
        for line in lines:
            line_name, t0, events = "", 0, []
            for number, value in _fields(line):
                if number == 2:
                    line_name = _text(value)
                elif number == 3:
                    t0 = value
                elif number == 4:
                    events.append(value)
            if line_name not in (xplane.OPS_LINE, MODULES_LINE):
                continue
            ops = by_line.setdefault(line_name, [])
            for event in events:
                got = dict(_fields(event))
                start = t0 + got.get(2, 0) / 1e3
                label, tf_op = named.get(got.get(1), ("", ""))
                ops.append(Op(label, start, start + got.get(3, 0) / 1e3,
                              tf_op))
        out.append((by_line.get(xplane.OPS_LINE, []),
                    by_line.get(MODULES_LINE, [])))
    return out


# -- the host's spans ----------------------------------------------------
def program_spans(path: str) -> List[Span]:
    """Every ``/host:CPU`` event whose name starts with ``llm.``, with
    its stats, by start (the outer one first where two start together).
    Found by name, on whatever line the decode thread's events land: it
    is called after the process (``python3``), not after the thread."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    start = float(ev.start_ns)
                    spans.append(Span(ev.name, start,
                                      start + float(ev.duration_ns),
                                      dict(ev.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    return spans


def whole_steps(spans: Iterable[Span]
                ) -> Optional[Tuple[Interval, int]]:
    """The interval from the first to the last decode step that BEGAN
    in the trace, and the steps in it (starts less one); ``None`` under
    one.  Whole steps, so a 3 s slice of 1.3 s steps does not swing by a
    fifth with where it cut.

    A step begins where its ``llm.decode.operands`` child does, the
    first thing ``DecodeEngine.step`` does inside ``llm.decode``.  Not
    at the ``llm.decode`` span that carries ``step``: the profiler
    records a span when it CLOSES, so the step running when the slice
    ends leaves no ``llm.decode`` span, only the children that closed —
    a 3 s slice of 1.27 s steps holds one or two ``llm.decode`` spans
    and two or three step starts (chip run, PR 24)."""
    starts = sorted(s.start for s in spans if s.name == STEP_START_SPAN)
    if len(starts) < 2:
        return None
    return (starts[0], starts[-1]), len(starts) - 1


def _clip(lo: float, hi: float, window: Interval) -> float:
    return max(0.0, min(hi, window[1]) - max(lo, window[0]))


def innermost_pieces(spans: Sequence[Span], window: Interval
                     ) -> List[Tuple[str, float, float]]:
    """``window`` cut at every span boundary inside it, each piece named
    by the innermost span open in it — the one that started last; the
    spans of one thread nest — or ``no_program_span``.  The pieces tile
    the window; neighbours of one name are joined."""
    lo, hi = window
    edges = sorted({lo, hi, *(t for s in spans for t in (s.start, s.end)
                              if lo < t < hi)})
    pieces: List[Tuple[str, float, float]] = []
    stack: List[Span] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(spans) and spans[i].start <= a:
            stack.append(spans[i])
            i += 1
        stack = [s for s in stack if s.end > a]
        name = max(stack, key=lambda s: (s.start, -s.end)).name \
            if stack else NO_SPAN
        if pieces and pieces[-1][0] == name:
            pieces[-1] = (name, pieces[-1][1], b)
        else:
            pieces.append((name, a, b))
    return pieces


def idle_by_span(ops: Iterable[Op], spans: Sequence[Span],
                 window: Interval) -> Dict[str, float]:
    """Seconds of ``window`` in which no operation ran on the device, by
    the innermost program span open at each instant.  An exact
    partition: the values sum to the idle time."""
    pieces = innermost_pieces(spans, window)
    starts = [p[1] for p in pieces]
    out: Dict[str, float] = defaultdict(float)
    for gap in xplane.idle_gaps(((o.start, o.end) for o in ops), window):
        i = max(0, bisect.bisect_right(starts, gap[0]) - 1)
        while i < len(pieces) and pieces[i][1] < gap[1]:
            name, lo, hi = pieces[i]
            out[name] += _clip(lo, hi, gap) / 1e9
            i += 1
    return dict(out)


def _scope(tf_op: str, prefix: str) -> Optional[str]:
    """The innermost component of a ``tf_op`` path that starts with
    ``prefix``."""
    return next((part for part in reversed(tf_op.split("/"))
                 if part.startswith(prefix)), None)


def module_label(name: str) -> str:
    """``jit__step(12299521280512369579)`` → ``jit__step``."""
    return re.sub(r"\(\d+\)$", "", name)


def attribute(ops: Sequence[Op], modules: Sequence[Op]
              ) -> List[Tuple[str, Optional[str]]]:
    """For each operation, ``(scope, program)``.  ``scope`` is the
    ``sflm.*`` part of its own ``tf_op``, or ``unscoped:<module>``.
    ``program`` is the ``llm.engine.*`` part of its own ``tf_op`` or,
    for an operation without one, the program the other operations of
    its ``XLA Modules`` event name; ``None`` outside the engine's
    programs."""
    modules = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in modules]

    def module_of(op: Op) -> int:
        k = bisect.bisect_right(starts, op.start) - 1
        return k if k >= 0 and modules[k].end > op.start else -1

    where = [module_of(op) for op in ops]
    program_of_module: Dict[int, str] = {}
    for op, k in zip(ops, where):
        if k not in program_of_module:
            program = _scope(op.tf_op, ENGINE_PREFIX)
            if program is not None:
                program_of_module[k] = program
    out = []
    for op, k in zip(ops, where):
        scope = _scope(op.tf_op, MODEL_PREFIX)
        if scope is None:
            label = module_label(modules[k].name) if k >= 0 \
                else "no_module"
            scope = f"{UNSCOPED}:{label}"
        program = _scope(op.tf_op, ENGINE_PREFIX)
        if program is None and k >= 0:
            program = program_of_module.get(k)
        out.append((scope, program))
    return out


def device_by_scope(ops: Sequence[Op],
                    pairs: Sequence[Tuple[str, Optional[str]]],
                    window: Interval
                    ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Device seconds inside ``window`` per scope (``sflm.*`` or
    ``unscoped:<module>``) and per ``llm.engine.*`` program; ``pairs``
    is :func:`attribute` of ``ops``."""
    by_scope: Dict[str, float] = defaultdict(float)
    by_program: Dict[str, float] = defaultdict(float)
    for op, (scope, program) in zip(ops, pairs):
        seconds = _clip(op.start, op.end, window) / 1e9
        if seconds > 0:
            by_scope[scope] += seconds
            if program is not None:
                by_program[program] += seconds
    return dict(by_scope), dict(by_program)


def prefill_device(ops: Sequence[Op],
                   pairs: Sequence[Tuple[str, Optional[str]]],
                   spans: Sequence[Span]) -> Tuple[float, int]:
    """Device seconds of ``llm.engine.prefill`` operations that began
    inside an ``llm.prefill`` span carrying ``padded`` (the dense
    prefill: dispatch and the wait for its logits, so all of its device
    work), and the number of those spans.  A prefill the slice's edge
    cut leaves no span, and its operations are left out with it.
    ``pairs`` is :func:`attribute` of ``ops``."""
    prefills = sorted((s.start, s.end) for s in spans
                      if s.name == PREFILL_SPAN and "padded" in s.stats)
    starts = [p[0] for p in prefills]
    seconds = 0.0
    for op, (_, program) in zip(ops, pairs):
        if program != PREFILL_PROGRAM:
            continue
        k = bisect.bisect_right(starts, op.start) - 1
        if k >= 0 and op.start < prefills[k][1]:
            seconds += (op.end - op.start) / 1e9
    return seconds, len(prefills)


def admit_waits(spans: Iterable[Span]) -> Dict[str, Dict[str, float]]:
    """Per admission verdict: how many ``llm.admit`` spans carry it and
    the mean of their ``waited_us``, in ms (``chain()`` → the decode
    thread taking the request)."""
    waits: Dict[str, List[float]] = defaultdict(list)
    for s in spans:
        if s.name == ADMIT_SPAN and "waited_us" in s.stats:
            waits[str(s.stats.get("outcome", "none"))].append(
                float(s.stats["waited_us"]) / 1e3)
    return {k: {"n": len(v), "mean_waited_ms": sum(v) / len(v)}
            for k, v in waits.items()}


def _ranked(table: Dict[str, float]) -> Dict[str, float]:
    return dict(sorted(table.items(), key=lambda kv: (-kv[1], kv[0])))


def reduce_program(path: str) -> Dict[str, Any]:
    """Everything the six readers take, from one pass over ``path``.
    The device is the one that ran most, as ``reduce_trace`` picks it."""
    spans = program_spans(path)
    ops, modules = max(
        device_events(path), default=([], []),
        key=lambda d: xplane.busy_ns((o.start, o.end) for o in d[0]))
    out: Dict[str, Any] = {"spans": len(spans),
                           "admits": admit_waits(spans)}
    pairs = attribute(ops, modules)
    seconds, prefills = prefill_device(ops, pairs, spans)
    out["prefills"] = {"n": prefills, "device_s": seconds}
    steps = [s.stats for s in spans
             if s.name == STEP_SPAN and "step" in s.stats]
    if steps:
        out["decode_spans"] = {
            "first_step": steps[0]["step"], "last_step": steps[-1]["step"],
            "lanes_mean": sum(s["lanes"] for s in steps) / len(steps)}
    whole = whole_steps(spans)
    if whole is None or not ops:
        return out
    window, n = whole
    busy = xplane.busy_ns(
        (max(o.start, window[0]), min(o.end, window[1])) for o in ops)
    by_scope, by_program = device_by_scope(ops, pairs, window)
    out.update({
        "steps": n, "interval_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy / 1e9,
        "idle_s": (window[1] - window[0] - busy) / 1e9,
        "idle_by_span": _ranked(idle_by_span(ops, spans, window)),
        "device_by_scope": _ranked(by_scope),
        "device_by_program": _ranked(by_program)})
    return out


def program(run) -> Optional[Dict[str, Any]]:
    """The reduction of ``run``'s trace, made once and kept under
    ``run.trace["program"]``; ``None`` where the run has no trace, the
    trace names no file, or the file is gone."""
    trace = run.trace
    if not trace or not trace.get("path") \
            or not os.path.exists(trace["path"]):
        return None
    if "program" not in trace:
        trace["program"] = reduce_program(trace["path"])
    return trace["program"]


def stepped(run) -> Optional[Dict[str, Any]]:
    """:func:`program` where the trace holds a whole decode step."""
    got = program(run)
    return got if got and got.get("steps") else None
