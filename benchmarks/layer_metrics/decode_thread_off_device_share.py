"""Token element: share of the decode thread's wall time spent outside
the calls that run the device (``PhaseClock``: everything but decode,
prefill and prefill chunks — admission, egress, idle), in percent.  Host
clock of the program's one serving thread; not a device number."""

DEVICE_PHASES = ("decode", "prefill", "llm-prefill-chunk")


def read(run):
    ns = run.counters["phase_ns"]
    total = sum(ns.values())
    if total <= 0:
        return None
    return 100.0 * (1.0 - sum(ns[p] for p in DEVICE_PHASES) / total)
