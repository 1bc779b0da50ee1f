"""Engine: ``PhaseClock`` decode time over decode steps.  A host clock
that ends in the host's argmax over the step's logits, so it includes the
wait for the device and the copy of the logits."""


def read(run):
    steps = run.counters["steps"]
    if not steps:
        return None
    return run.counters["phase_ns"]["decode"] / 1e6 / steps
