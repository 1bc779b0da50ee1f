"""Model math: the least time the chip could take for the frames served in
the traced slice over the time the device was busy in it, in percent.  The
least time is ``cost/<family>.py``'s ``batch_cost`` (frames in, weights
once per dispatch, logits out) against this chip's row of
``peaks.json``."""

from benchmarks.cost.roofline import least_seconds


def read(run):
    if not run.trace or run.cost is None:
        return None
    c = run.trace["counters"]
    dispatches = c.get("dispatches") or c.get("xb_invokes")
    if not dispatches or not c["frames"] or run.trace["busy_s"] <= 0:
        return None
    per_dispatch = max(1, round(c["frames"] / dispatches))
    flops, nbytes = run.cost.batch_cost(run.config["model"], per_dispatch)
    least, bound = least_seconds(flops, nbytes, run.peaks)
    run.trace["roofline_bound"] = bound
    return 100.0 * least * dispatches / run.trace["busy_s"]
