"""The traced slice of a ``--trace 1`` run: the JAX profiler around a few
steady seconds in the middle of the window, reduced at once.

Only the process that holds the chip can trace it, so this runs in the
driver's process.  The Python tracer is off: it would record every frame
of every reader thread and slow the host it is measuring; the host's
own ``TraceMe`` events (dispatch, transfers) stay on and name the gaps.

The slice's window is a span in the trace itself (``bench.slice``,
:data:`benchmarks.xplane.SLICE_SPAN`): opened once ``start_trace`` has
returned, closed before ``stop_trace`` is called, since a span is
recorded when it closes.  The reducer clips the device's operations to
it, so the window and the busy time are read off one clock; no host
clock enters either.
"""

from __future__ import annotations

import contextlib
import shutil
import time
from typing import Any, Callable, Dict, Iterator

from benchmarks import xplane


class Slice:
    """Filled when the ``with`` block ends."""

    reduced: Dict[str, Any]


@contextlib.contextmanager
def traced_slice(trace_dir: str) -> Iterator[Slice]:
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False     # op names do not need it
    out = Slice()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.SLICE_SPAN):
            yield out
    finally:
        jax.profiler.stop_trace()
    out.reduced = xplane.reduce_trace(xplane.find_trace(trace_dir))


def trace_middle(run, trace_dir: str, snapshot: Callable[[], Dict[str, Any]],
                 delta: Callable[[Dict[str, Any], Dict[str, Any]],
                                 Dict[str, Any]]) -> Dict[str, Any]:
    """Trace a steady slice in the middle of ``run``'s window (the mix's
    ``trace_slice_s``, at most a third of the window) and reduce it.
    The driver's counters over the same slice ride along under
    ``"counters"``, its bounds under ``"slice"``.  Call it once the
    window has opened; it sleeps until the slice is due."""
    mix = run.traffic
    length = min(float(mix.get("trace_slice_s", 3.0)), run.seconds / 3.0)
    time.sleep(max(0.0, run.t0 + 0.4 * run.seconds - time.monotonic()))
    with traced_slice(trace_dir) as sl:
        a = snapshot()
        time.sleep(length)
        b = snapshot()
    out = sl.reduced
    out["counters"] = delta(a, b)
    out["slice"] = (a["t"], b["t"])
    return out
