"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
benchmark reports: device busy seconds, idle share, the operations that
took most device time, and the longest idle gaps named by what the host
was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device is a
plane whose name starts with ``/device:TPU:``; its operations are the
events of its ``XLA Ops`` line (``XLA Modules`` and ``Steps`` enclose
them and would hide every gap between two operations of one program;
``Async XLA Ops`` are copies in flight beside them, during which the
core may be idle).  Plane and line names are a v5e trace's (chip run,
PR 22).
Busy time is the UNION of the operations' intervals, so operations that
overlap are not counted twice.  Host activity is every event of the
``/host:CPU`` plane; a gap takes the name of the host event that
overlaps it most, the shortest such event on a tie (the innermost
frame says most).

**The window** is the ``bench.slice`` span of the ``/host:CPU`` plane,
which ``benchmarks/tracing.py`` opens once the profiler runs and closes
before it stops it: a ``TraceAnnotation``, so on the clock the device's
operations are laid on.  Every device event is clipped to it before
anything is summed, so ``busy_s`` can never pass ``window_s``, whatever
the profiler recorded before the span opened or after it closed (a cell
whose chip never idles reads 1.0 and not 1.00004: PR 35 was refused over
that).  A file without the span is refused, as one in which no
operation ran on a device is: there is one definition of the window.
"""

from __future__ import annotations

import glob
import math
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]            # (start_ns, end_ns)
Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
#: the host span that bounds the traced slice (benchmarks/tracing.py)
SLICE_SPAN = "bench.slice"
#: device lines that enclose operations instead of being operations
_ENCLOSING_LINES = ("Steps", "XLA Modules", "XLA TraceMe",
                    "Async XLA Ops", "TC Overlay", "Framework Name Scope",
                    "Framework Ops", "Source code")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint intervals."""
    out: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def busy_ns(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``intervals`` (``fsum``: a hundred
    thousand operations that fill a window must not add up past it)."""
    return math.fsum(end - start for start, end in merge(intervals))


def idle_gaps(intervals: Iterable[Interval], window: Interval
              ) -> List[Interval]:
    """The parts of ``window`` no interval covers, longest first."""
    lo, hi = window
    gaps: List[Interval] = []
    cursor = lo
    for start, end in merge(intervals):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        gaps.append((cursor, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_label(name: str) -> str:
    """What an operation IS, without what tells two of its instances
    apart.  The trace names a TPU operation by its whole HLO line
    (``%fusion.3326 = (bf16[33,24,256,16,64]{4,3,...}, ...) fusion(...)``);
    the label keeps the kind without its instance numbers and the first
    result's shape (``fusion (bf16[33,24,256,16,64]``), so the 48 copies
    of one slicing fusion a step makes add up under one name."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    kind = re.sub(r"\.\d+", "", head.lstrip("%"))
    return f"{kind} {rest.split('{')[0].strip()}"[:120]


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """The part of each event that lies inside ``window``; an event
    wholly outside it is dropped."""
    lo, hi = window
    return [(name, max(start, lo), min(end, hi))
            for name, start, end in events
            if min(end, hi) > max(start, lo)]


def slice_window(host_events: Iterable[Event]) -> Optional[Interval]:
    """The ``bench.slice`` span among the host's events, or ``None``.
    A slice writes one; of several (two slices into one directory) the
    last is the one whose file this is."""
    found = [(start, end) for name, start, end in host_events
             if name == SLICE_SPAN and end > start]
    return max(found) if found else None


def top_ops(events: Iterable[Event], top: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds per operation label, largest first."""
    total: Dict[str, float] = defaultdict(float)
    for name, start, end in events:
        total[op_label(name)] += (end - start) / 1e9
    return sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:top]


class HostActivity:
    """Host events indexed for naming gaps (arrays, so a gap is named
    by one vector pass and not a Python loop over every event)."""

    def __init__(self, events: Sequence[Event]) -> None:
        self.names = [name for name, _, _ in events]
        self.starts = np.array([s for _, s, _ in events], np.float64)
        self.ends = np.array([e for _, _, e in events], np.float64)

    def name_gap(self, gap: Interval) -> str:
        """What the host was doing during ``gap``: the host event with
        the largest overlap, the shortest one on a tie."""
        if not self.names:
            return "no host event"
        overlap = (np.minimum(self.ends, gap[1])
                   - np.maximum(self.starts, gap[0]))
        best = float(overlap.max())
        if best <= 0:
            return "no host event"
        tied = np.flatnonzero(overlap == best)
        lengths = self.ends[tied] - self.starts[tied]
        return self.names[int(tied[int(lengths.argmin())])]


def top_gaps(gaps: Sequence[Interval], host_events: Sequence[Event],
             top: int = 10, named: int = 500) -> List[Tuple[str, float]]:
    """Idle seconds per host activity, largest first — many gaps under
    one host call add up to more than one long gap, and it is the sum a
    fix would win back.  Only the ``named`` longest gaps are attributed;
    the rest is reported as one row."""
    host = HostActivity(host_events)
    total: Dict[str, float] = defaultdict(float)
    ordered = sorted(gaps, key=lambda g: g[0] - g[1])
    for gap in ordered[:named]:
        total[host.name_gap(gap)] += (gap[1] - gap[0]) / 1e9
    rest = sum(g[1] - g[0] for g in ordered[named:]) / 1e9
    if rest > 0:
        total[f"gaps beyond the {named} longest"] += rest
    return sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:top]


def _line_events(line) -> List[Event]:
    return [(ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns))
            for ev in line.events]


def device_op_events(plane) -> List[Event]:
    """The operation events of one device plane."""
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    if not ops:
        ops = [ln for ln in lines if ln.name not in _ENCLOSING_LINES]
    return [ev for ln in ops for ev in _line_events(ln)]


def find_trace(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def read_planes(path: str) -> Tuple[List[Event], List[List[Event]],
                                    List[str]]:
    """Of one trace file: every event of the ``/host:CPU`` plane, the
    operation events of each device plane that has any, and the names
    of all its planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host_events: List[Event] = []
    per_device: List[List[Event]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            events = device_op_events(plane)
            if events:
                per_device.append(events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_events.extend(_line_events(line))
    return host_events, per_device, [p.name for p in data.planes]


def reduce_trace(path: str, top: int = 10) -> Dict[str, object]:
    """Reduce one trace file.  The window is the file's ``bench.slice``
    span and every device's events are clipped to it; a file without
    the span, or with no device operation inside it, is refused.
    ``busy_s`` is the mean over the devices that ran anything in the
    window, ``idle_share`` follows from it, ``device_ops`` and
    ``idle_gaps`` are the ``breakdown``, the fullest device's."""
    host_events, per_device, planes = read_planes(path)
    if not per_device:
        raise ValueError(f"{path}: no operation ran on a device "
                         f"(planes: {planes})")
    window = slice_window(host_events)
    if window is None:
        raise ValueError(f"{path}: no {SLICE_SPAN} span on the "
                         f"{HOST_PLANE} plane: the slice's window is "
                         "unknown")
    # the span covers every gap whole: it would name them all
    host_events = [ev for ev in host_events if ev[0] != SLICE_SPAN]
    per_device = [evs for evs in (clip(evs, window) for evs in per_device)
                  if evs]
    if not per_device:
        raise ValueError(f"{path}: no operation ran on a device inside "
                         f"the {SLICE_SPAN} span")
    busy = [busy_ns((s, e) for _, s, e in evs) / 1e9 for evs in per_device]
    window_s = (window[1] - window[0]) / 1e9
    busy_s = sum(busy) / len(busy)
    # the breakdown is the fullest device's: on one chip, the chip's
    fullest = max(range(len(busy)), key=busy.__getitem__)
    events = per_device[fullest]
    gaps = idle_gaps(((s, e) for _, s, e in events), window)
    return {
        "path": path,
        "devices": len(per_device),
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_events": sum(len(evs) for evs in per_device),
        "device_ops": [[n, s] for n, s in top_ops(events, top)],
        "idle_gaps": [[n, s] for n, s in top_gaps(gaps, host_events, top)],
    }
