"""What one measured window leaves behind: the record every metric
reader takes its number from.

A driver fills a :class:`Run`; ``e2e_metrics/<name>.py`` and
``layer_metrics/<name>.py`` each hold one ``read(run)`` that returns a
float, or ``None`` when the run has nothing for it (the metric is then
left out of the result line).  Times are ``time.monotonic()`` seconds —
``CLOCK_MONOTONIC`` is one clock for every process of the machine, so
the load generator's children stamp on it too.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


@dataclass
class Run:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    #: process start, and the measured window ``[t0, t1]``
    t_start: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    #: one record per request or frame, from the client's side:
    #: ``id``, ``due`` / ``sent`` (absolute), ``ok``, ``outcome``, and
    #: ``stamps`` (token arrivals) + ``tokens``, or ``done`` (a frame's
    #: reply)
    requests: List[Dict[str, Any]] = field(default_factory=list)
    #: what the program counted over the window (deltas), by name
    counters: Dict[str, Any] = field(default_factory=dict)
    #: seconds of each set-up phase, in order
    setup: Dict[str, float] = field(default_factory=dict)
    #: :func:`benchmarks.xplane.reduce_trace` of the traced slice, plus
    #: the program's counters over that slice under ``"counters"``
    trace: Optional[Dict[str, Any]] = None
    #: this chip's row of ``peaks.json``
    peaks: Dict[str, Any] = field(default_factory=dict)
    #: ``cost/<family>.py`` of the configuration's family (operations and
    #: bytes from shapes), or ``None`` where the family has none
    cost: Any = None
    #: what the driver wants printed beside the counts (bytes of cache the
    #: traffic wrote, the ramp's prefills): never a metric
    notes: Dict[str, Any] = field(default_factory=dict)
    #: the longest a request of this run can have waited, in ms — what a
    #: latency tail reads when it reaches into the failures
    missed_ms: float = 0.0

    def due_in_window(self) -> List[Dict[str, Any]]:
        """Requests due inside the window, whatever became of them."""
        return [r for r in self.requests if self.t0 <= r["due"] < self.t1]


def raise_if_failed(pipeline) -> None:
    """Raise the error an element posted on ``pipeline``, if any.  A
    serving pipeline never reaches EOS, so "not finished" is the normal
    answer and is swallowed; a posted error must end the run instead of
    being measured around."""
    try:
        pipeline.wait(timeout=0)
    except TimeoutError:
        pass
