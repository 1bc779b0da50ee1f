"""Query wire: what one reply frame's write costs the decode thread —
the mean length of the ``query.reply`` spans inside the traced slice
(``send_tensors`` in ``QueryServer._reply``, inside ``llm.egress``), in
microseconds (``benchmarks/wire_spans.py``).  A step's egress is its
lanes' frames written one by one: this times one."""

from benchmarks import wire_spans


def read(run):
    got = wire_spans.wire(run)
    if got is None or "replies" not in got:
        return None
    return got["replies"]["mean_us"]
