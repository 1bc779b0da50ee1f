"""Stream batcher: frames over (dispatches x the filter's ``batch``), in
percent — how full the solo stream's micro-batches ran."""


def read(run):
    c = run.counters
    if not c.get("dispatches"):
        return None
    return 100.0 * c["frames"] / (c["dispatches"] * c["batch"])
