"""Token element: milliseconds a decode step leaves the chip idle — the
device's idle time inside a whole number of decode steps of the traced
slice (``benchmarks/spans.py``: no operation of the ``XLA Ops`` line
running) over those steps.  Whatever the one decode thread does that
the step in flight does not hide lands here (PR 34: step k is queued
before step k-1's tokens are read and sent, so the egress, the lock,
pruning and the operands run beside the device): a synchronous
prefill's own gaps and the host after it, admission, and any iteration
of the host longer than the step.  The split by program span is printed
on the line before the result (``trace.program.idle_by_span``)."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None:
        return None
    return got["idle_s"] * 1e3 / got["steps"]
