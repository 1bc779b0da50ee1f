"""Token element: share of the decode thread's wall time inside prefill
calls, in percent.  The thread is one: while it prefills, no resident
stream gets a token and no arrival is admitted."""


def read(run):
    ns = run.counters["phase_ns"]
    total = sum(ns.values())
    if total <= 0:
        return None
    return 100.0 * (ns["prefill"] + ns["llm-prefill-chunk"]) / total
