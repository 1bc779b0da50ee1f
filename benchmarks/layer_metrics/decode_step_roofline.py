"""Model math: the least time the chip could take for the decode steps of
the traced slice over the time the device was busy in it, in percent.

The least time comes from ``cost/<family>.py`` (one routed expert, weights
once per step at the stated dtype, keys and values at the positions
actually attended) and this chip's row of ``peaks.json``.  The steps of
the slice are counted as its ``decode`` phase time over the window's mean
``decode`` time a step, so a slice that cuts a step counts the part it
holds (a whole number of steps in a slice of two or three swings by a
fifth).  Prefills inside the slice count as busy time and not as work, so
a slice with many of them reads low: this is a decode cell's metric."""

from benchmarks.cost.roofline import least_seconds


def read(run):
    if not run.trace or run.cost is None:
        return None
    c, whole = run.trace["counters"], run.counters
    samples = [s for s in c["samples"] if s[2]]
    if not whole["steps"] or not samples or run.trace["busy_s"] <= 0:
        return None
    step_ns = whole["phase_ns"]["decode"] / whole["steps"]
    steps = c["phase_ns"]["decode"] / step_ns
    lanes = whole["step_tokens"] / whole["steps"]
    attended = sum(s[3] for s in samples) / len(samples)
    flops, nbytes = run.cost.decode_step_cost(
        run.config["model"], max(1, round(lanes)), round(attended))
    least, bound = least_seconds(flops, nbytes, run.peaks)
    run.trace["roofline_bound"] = bound
    run.trace["least_ms_per_step"] = least * 1e3
    return 100.0 * least * steps / run.trace["busy_s"]
