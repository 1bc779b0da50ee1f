"""95th percentile, over the requests DUE inside the window, of first
token received minus due time.  A request that was shed, refused, failed
or never answered missed: it sorts last, and a tail that reaches it reads
the longest any request of the run can have waited."""

from benchmarks import stats


def read(run):
    due = run.due_in_window()
    if not due:
        return None
    waits = [stats.latency_ms(r["due"], r["stamps"][0] if r["stamps"]
                              else None, bool(r["stamps"]))
             for r in due]
    return stats.finite_or(stats.percentile(waits, 95), run.missed_ms)
