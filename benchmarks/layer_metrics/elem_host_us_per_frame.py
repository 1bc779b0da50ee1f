"""Graph: the program tracer's per-element proctime, summed over every
element but the filter, per frame that reached the sink, in microseconds.
Host time the pipeline spends outside the model, on the streaming
threads.  Needs the program's tracer, so only a traced run has it."""


def read(run):
    c = run.counters
    per_element = c.get("element_proctime_ms")
    if not per_element or not c.get("frames"):
        return None
    outside = sum(ms for name, ms in per_element.items()
                  if name != c["filter"])
    return outside * 1e3 / c["frames"]
