"""Model math: the least time the chip could take for the readings of
the ONE cached layer's rows in the whole decode steps of the traced
slice — by the full attention layer and by every cross-attention layer,
at the positions actually attended, with their products
(``cost/<family>.py`` ``shared_kv_cost`` and this chip's row of
``peaks.json``) — over the device time under ``sflm.full_attn``,
``sflm.cross_attn`` and ``sflm.kv_read`` in those steps, in percent.
Lanes and attended positions are the slice's samples, as
``decode_step_roofline`` takes them.  Nothing to read where the family's
cost functions price no shared cache or the step names no such scope."""

from benchmarks import spans
from benchmarks.cost.roofline import least_seconds

ATTN_SCOPES = ("sflm.full_attn", "sflm.cross_attn")


def read(run):
    got = spans.stepped(run)
    cost = getattr(run.cost, "shared_kv_cost", None)
    if got is None or cost is None:
        return None
    by_scope = got["device_by_scope"]
    if not any(name in by_scope for name in ATTN_SCOPES):
        return None
    samples = [s for s in run.trace["counters"]["samples"] if s[2]]
    if not samples:
        return None
    lanes = sum(s[2] for s in samples) / len(samples)
    attended = sum(s[3] for s in samples) / len(samples)
    flops, nbytes = cost(run.config["model"], max(1, round(lanes)),
                         round(attended))
    least, bound = least_seconds(flops, nbytes, run.peaks)
    spent = sum(by_scope.get(name, 0.0)
                for name in ATTN_SCOPES + ("sflm.kv_read",))
    if spent <= 0:
        return None
    run.trace["shared_kv"] = {
        "bound": bound, "least_ms_per_step": least * 1e3,
        "spent_ms_per_step": spent * 1e3 / got["steps"],
        "attended_mean": attended}
    return 100.0 * least * got["steps"] / spent
