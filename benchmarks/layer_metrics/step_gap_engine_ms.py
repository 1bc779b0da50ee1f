"""Engine: the part of ``step_gap_ms`` that falls inside
``DecodeEngine.step`` — idle device time under the ``llm.decode`` span
and its children (``operands``: the lane arrays and their uploads;
``dispatch``; ``wait``: what is left to wait for step k-1's ``B`` int32
once step k is queued; ``sample``: the per-session bookkeeping of those
tokens, sampled on the chip since PR 34) over the whole steps of the
traced slice.  The rest of the gap — ``llm.egress``, ``llm.admit``,
``llm.idle`` (the loop itself), a prefill's own gaps — is the
element's."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None:
        return None
    inside = sum(s for name, s in got["idle_by_span"].items()
                 if name == spans.STEP_SPAN
                 or name.startswith(spans.STEP_SPAN + "."))
    return inside * 1e3 / got["steps"]
