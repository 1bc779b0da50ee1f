"""Model math: device milliseconds a decode step spends on its expert
layers — operations under ``sflm.route`` (the router's scores, the
group limit, the top k, the counters), ``sflm.moe`` (the routed
experts: gather, grouped products, scatter) and ``sflm.shared_expert``
inside the whole decode steps of the traced slice, over those steps.
Nothing to read where the step names no ``sflm.route`` scope (a family
whose experts are computed densely has ``sflm.moe`` alone)."""

from benchmarks import spans

SCOPES = ("sflm.route", "sflm.moe", "sflm.shared_expert")


def read(run):
    got = spans.stepped(run)
    if got is None or "sflm.route" not in got["device_by_scope"]:
        return None
    return sum(got["device_by_scope"].get(name, 0.0)
               for name in SCOPES) * 1e3 / got["steps"]
