"""Model math: device milliseconds a decode step spends on the model
itself — operations under ``sflm.embed``, ``sflm.qkv``, ``sflm.attn``,
``sflm.mlp``, ``sflm.moe`` and ``sflm.head`` inside the whole decode
steps of the traced slice, over those steps.  What is left of a step
once the pool's copies are gone; the split by scope is printed on the
line before the result (``trace.program.device_by_scope``)."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None:
        return None
    by_scope = got["device_by_scope"]
    if not any(name in by_scope for name in spans.MATH_SCOPES):
        return None       # a program whose step names no scope
    math = sum(by_scope.get(name, 0.0) for name in spans.MATH_SCOPES)
    return math * 1e3 / got["steps"]
