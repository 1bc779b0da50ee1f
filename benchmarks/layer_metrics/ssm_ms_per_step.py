"""Model math: device milliseconds a decode step spends on the
recurrent half of a hybrid model — operations under ``sflm.ssm`` (a
Mamba mixer: its projections, the convolution, the state update and the
read-out), ``sflm.gmu`` (the gated memory units on its scan output) and
``sflm.state_read`` / ``sflm.state_write`` (the rows those and the
window layers keep) inside the whole decode steps of the traced slice,
over those steps.  Nothing to read where the step names no ``sflm.ssm``
scope."""

from benchmarks import spans

SCOPES = ("sflm.ssm", "sflm.gmu", "sflm.state_read", "sflm.state_write")


def read(run):
    got = spans.stepped(run)
    if got is None or "sflm.ssm" not in got["device_by_scope"]:
        return None
    return sum(got["device_by_scope"].get(name, 0.0)
               for name in SCOPES) * 1e3 / got["steps"]
