"""Spans on the device trace's clock for the threads around the token
tier's decode thread: the query wire's connection threads, the serving
source's streaming thread, the reply writes, and Python's garbage
collections on whatever thread triggers them.

They are ``jax.profiler.TraceAnnotation``s, as the decode thread's
``PhaseClock`` phases are (``llm/engine.py``), so a profiler session
that records holds them on the clock the device's operations are laid
on; ``benchmarks/wire_spans.py`` reads them.  This is not a second span
store: with no session they record nothing and cost nothing, and the
``obs/span.py`` ring (``mono_ns``, ``launch.py --trace``) is another
thing.

**Gated, so that off costs nothing.**  Every site asks :func:`recording`
before it builds a span: until :func:`bind` has run it is a function
that answers ``False``; after, it is jaxlib's ``TraceMe.is_enabled``
(~25 ns; ~60 ns with the module lookup).  With no session a site reads
no clock, makes no object and takes no lock.  :func:`bind` imports
JAX's profiler, so a process that never imports JAX neither records
nor pays; the token element binds it at ``start`` and ``launch.py
--jax-trace`` before it opens a session.

**No name here starts with ``llm.``**: ``benchmarks/spans.py`` takes
every ``llm.*`` host event, on any thread, for the decode thread's
partition (one open at any instant), and a span of another thread under
that prefix would change every metric read from it.

A span opens and closes on one thread (one closed on another is lost),
and none is longer than the work it names."""

from __future__ import annotations

import contextlib
import gc
from typing import Any, Callable, Dict, Optional


def _never() -> bool:
    return False


#: ``True`` while a JAX profiler session records in this process: a
#: constant ``False`` until :func:`bind`, then ``TraceMe.is_enabled``
recording: Callable[[], bool] = _never
_annotation: Any = None
#: what a site enters when no session records: one shared, reusable
#: context that does nothing (no object is made per call)
NO_SPAN = contextlib.nullcontext()


def bind() -> None:
    """Make :func:`recording` JAX's own answer (imports the profiler)."""
    global recording, _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    recording = TraceAnnotation.is_enabled


def span(name: str, **args):
    """An annotation to enter with ``with``; ``args`` are its arguments.
    The caller has asked :func:`recording`."""
    return _annotation(name, **args)


def open_span(name: str, **args):
    """:func:`span`, entered: for a span whose end is found in another
    function than its start, on the same thread (:func:`close_span`)."""
    opened = _annotation(name, **args)
    opened.__enter__()
    return opened


def close_span(opened, **args) -> None:
    """End ``opened``; ``args`` are arguments learnt since it began."""
    if args:
        opened.set_metadata(**args)
    opened.__exit__(None, None, None)


def instant(name: str, **args) -> None:
    """A span of no length: an event placed on the trace's clock."""
    with _annotation(name, **args):
        pass


class GcWatch:
    """Python's garbage collections while installed (``gc.callbacks``):
    how many and how long by generation, and the longest; while a
    session records, a ``py.gc`` span (``generation``; ``collected`` at
    its end) around each, on the thread that collected.  Two clock reads
    a collection; nothing between collections.  A collection stops
    every thread of the process, so a pause inside the decode thread's
    step shows in the trace by name (the harness names an idle gap
    after the innermost host event open in it)."""

    def __init__(self, clock_ns: Callable[[], int]) -> None:
        self._clock_ns = clock_ns
        self.collections = [0, 0, 0]
        self.pause_ns = [0, 0, 0]
        self.longest_ns = 0
        self._t0 = 0
        self._span: Optional[Any] = None

    def install(self) -> None:
        gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        # a bound method compares equal to itself re-bound
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    @property
    def installed(self) -> bool:
        return self._on_gc in gc.callbacks

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if recording():
                self._span = open_span("py.gc",
                                       generation=info["generation"])
            self._t0 = self._clock_ns()
            return
        took = self._clock_ns() - self._t0
        gen = min(2, int(info["generation"]))
        self.collections[gen] += 1
        self.pause_ns[gen] += took
        if took > self.longest_ns:
            self.longest_ns = took
        opened, self._span = self._span, None
        if opened is not None:
            close_span(opened, collected=info["collected"])

    def report(self) -> Dict[str, Any]:
        return {"collections": list(self.collections),
                "pause_ms": [round(ns / 1e6, 3) for ns in self.pause_ns],
                "longest_ms": round(self.longest_ns / 1e6, 3)}
