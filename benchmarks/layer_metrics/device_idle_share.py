"""Device: share of the traced slice in which no operation ran on the
chip, in percent: 1 - ``busy_s`` / ``window_s``.  The window is the
``bench.slice`` span that ``benchmarks/tracing.py`` writes into the
trace, and busy time the union of the device's operation intervals
CLIPPED to that span (``benchmarks/xplane.py``): both on the trace's
clock, so the share lies in [0, 100] without a floor, and idle time at
the window's two edges counts."""


def read(run):
    if not run.trace:
        return None
    return 100.0 * run.trace["idle_share"]
