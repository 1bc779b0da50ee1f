"""Token element: milliseconds a decode step leaves the chip idle — the
device's idle time inside a whole number of decode steps of the traced
slice (``benchmarks/spans.py``: no operation of the ``XLA Ops`` line
running) over those steps.  Everything the one decode thread does
between two dispatches lands here: the logits' copy out, the host
argmax, 32 token frames pushed one by one, the lock, pruning,
admission, the operands.  The split by program span is printed on the
line before the result (``trace.program.idle_by_span``)."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None:
        return None
    return got["idle_s"] * 1e3 / got["steps"]
