"""95th percentile, over the frames DUE inside the window, of reply
received minus the time the frame was due to be sent.  A shed, failed or
unanswered frame missed."""

from benchmarks import stats


def read(run):
    due = run.due_in_window()
    if not due:
        return None
    waits = [stats.latency_ms(r["due"], r["done"], r["ok"]) for r in due]
    return stats.finite_or(stats.percentile(waits, 95), run.missed_ms)
