"""Engine: device milliseconds one dense prefill takes — the
operations of the ``llm.engine.prefill`` program that began inside an
``llm.prefill`` span carrying ``padded``, over those spans, in the
traced slice.  Beside the thread time a prefill takes
(``prefill_stall_share``): the difference is the host's — the prompt's
upload, the dispatch, the logits' copy out."""

from benchmarks import spans


def read(run):
    got = spans.program(run)
    if got is None or not got["prefills"]["n"] \
            or got["prefills"]["device_s"] <= 0:
        return None
    return got["prefills"]["device_s"] * 1e3 / got["prefills"]["n"]
