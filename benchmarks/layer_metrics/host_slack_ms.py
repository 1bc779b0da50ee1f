"""Token element: how long the decode thread waits on the chip a step —
the time in ``llm.decode.wait`` pieces (``DecodeEngine.collect``
reading step k-1's tokens while step k is queued) over the whole
decode steps of the traced slice, the interval the step metrics use
(``benchmarks/spans.py`` ``whole_steps``), divided by those steps, in
ms (``benchmarks/wire_spans.py``).  The host's slack: it reaches 0 when
the host, not the chip, sets the period."""

from benchmarks import wire_spans


def read(run):
    got = wire_spans.wire(run)
    if got is None or not got.get("steps"):
        return None
    return got["wait_s"] * 1e3 / got["steps"]
