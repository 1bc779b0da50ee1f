"""Token element: 95th percentile of the gaps between consecutive token
frames of one request, as the clients stamped them, over the tokens
received inside the window."""

from benchmarks import stats


def read(run):
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r["stamps"], r["stamps"][1:])
            if run.t0 <= b < run.t1]
    return stats.percentile(gaps, 95) if gaps else None
