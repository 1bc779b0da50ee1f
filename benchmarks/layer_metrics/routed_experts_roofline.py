"""Model math: the least time the chip could take for the routed
experts of the whole decode steps of the traced slice — the matrices of
the held experts a step's lanes are EXPECTED to reach under uniform
routing, once, and the products of the token-expert pairs that fall on
them (``cost/<family>.py`` ``routed_experts_cost`` and this chip's row
of ``peaks.json``) — over the device time under ``sflm.moe`` (the
gather of the pairs' rows, the grouped products, the scatter back) in
those steps, in percent.  Lanes are the slice's samples.  Nothing to
read where the family's cost functions price no routed experts or the
step names no ``sflm.moe`` scope."""

from benchmarks import spans
from benchmarks.cost.roofline import least_seconds

SCOPE = "sflm.moe"


def read(run):
    got = spans.stepped(run)
    cost = getattr(run.cost, "routed_experts_cost", None)
    if got is None or cost is None:
        return None
    spent = got["device_by_scope"].get(SCOPE, 0.0)
    samples = [s for s in run.trace["counters"]["samples"] if s[2]]
    if spent <= 0 or not samples:
        return None
    lanes = sum(s[2] for s in samples) / len(samples)
    flops, nbytes = cost(run.config["model"], max(1, round(lanes)))
    least, bound = least_seconds(flops, nbytes, run.peaks)
    run.trace["routed_experts"] = {
        "bound": bound, "least_ms_per_step": least * 1e3,
        "spent_ms_per_step": spent * 1e3 / got["steps"]}
    return 100.0 * least * got["steps"] / spent
