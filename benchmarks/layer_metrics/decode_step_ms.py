"""Engine: ``PhaseClock`` decode time over decode steps.  A host clock.
Since PR 34 the loop keeps one step in flight and the token is sampled
on the chip, so the decode phase of an iteration is the operands and
dispatch of step k plus what is LEFT to wait of step k-1 (its ``B``
int32, read one step late) after the egress ran beside it.  It falls as
the host overlaps the device and says nothing of the step's own time;
that is ``device.busy_s`` over the steps, and the gap is
``step_gap_ms``."""


def read(run):
    steps = run.counters["steps"]
    if not steps:
        return None
    return run.counters["phase_ns"]["decode"] / 1e6 / steps
