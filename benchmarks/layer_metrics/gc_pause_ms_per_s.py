"""Token element: Python's garbage collections, in ms a second — the
time inside ``py.gc`` spans (the token element's ``gc.callbacks`` hook,
around every collection on any thread while a session records) clipped
to the traced slice, over the slice's seconds
(``benchmarks/wire_spans.py``).  Reads 0 when the slice holds the
decode thread's spans and the program's wire or GC spans but no
collection; nothing where the program writes no such span (it has no
hook)."""

from benchmarks import wire_spans


def read(run):
    got = wire_spans.wire(run)
    if got is None or not got.get("slice_s") \
            or not got.get("decode_thread_spans") \
            or not (got["wire_spans"] or got["gc_spans"]):
        return None
    return got["gc_s"] * 1e3 / got["slice_s"]
