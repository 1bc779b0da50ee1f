"""Model math: the least time the chip could take for what the whole
decode steps of the traced slice do over the cache of LATENTS — the
rows read once at the positions actually attended, the scores and the
weighted sum over them, the values' expansion and the output projection
(``cost/<family>.py`` ``latent_attn_cost`` and this chip's row of
``peaks.json``) — over the device time under ``sflm.mla_attn`` and
``sflm.kv_read`` in those steps, in percent.  Lanes and attended
positions are the slice's samples, as ``decode_step_roofline`` takes
them.  Nothing to read where the family's cost functions price no
latent attention or the step names no ``sflm.mla_attn`` scope."""

from benchmarks import spans
from benchmarks.cost.roofline import least_seconds

SCOPES = ("sflm.mla_attn", "sflm.kv_read")


def read(run):
    got = spans.stepped(run)
    cost = getattr(run.cost, "latent_attn_cost", None)
    if got is None or cost is None:
        return None
    by_scope = got["device_by_scope"]
    if SCOPES[0] not in by_scope:
        return None
    samples = [s for s in run.trace["counters"]["samples"] if s[2]]
    if not samples:
        return None
    lanes = sum(s[2] for s in samples) / len(samples)
    attended = sum(s[3] for s in samples) / len(samples)
    flops, nbytes = cost(run.config["model"], max(1, round(lanes)),
                         round(attended))
    least, bound = least_seconds(flops, nbytes, run.peaks)
    spent = sum(by_scope.get(name, 0.0) for name in SCOPES)
    if spent <= 0:
        return None
    run.trace["latent_attn"] = {
        "bound": bound, "least_ms_per_step": least * 1e3,
        "spent_ms_per_step": spent * 1e3 / got["steps"],
        "attended_mean": attended}
    return 100.0 * least * got["steps"] / spent
