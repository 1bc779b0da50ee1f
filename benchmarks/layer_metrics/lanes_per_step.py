"""Engine: tokens emitted by decode steps over decode steps — how full
the shared step ran."""


def read(run):
    steps = run.counters["steps"]
    return run.counters["step_tokens"] / steps if steps else None
