"""Query wire: how long a request takes from its DATA header to the
token element's pending list — for each request of the traced slice
whose ``query.ingress`` and ``query.chain`` spans both lie inside it,
joined on ``(cid, seq)``, the start of the one to the end of the other
(admission, tensor decode, the put, the wait in the server's queue, the
serving source's push and ``chain()``), the mean in ms
(``benchmarks/wire_spans.py``).  What the dial and the decode thread
add is printed beside it (``trace.wire.requests``)."""

from benchmarks import wire_spans


def read(run):
    got = wire_spans.wire(run)
    if got is None or "requests" not in got:
        return None
    return got["requests"]["mean_ingress_ms"]
