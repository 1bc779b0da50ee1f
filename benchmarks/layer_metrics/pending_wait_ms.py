"""Token element: how long a request sat in ``tensor_llm``'s pending
list before the one decode thread took it — the mean ``waited_us`` of
the traced slice's ``llm.admit`` spans whose verdict was ``admit``
(``chain()`` → admission; the wait for the running step included), in
ms.  A mean over the ~24 requests of a 3 s slice, no percentile; a
request requeued inside its admit-timeout is counted when it is
admitted."""

from benchmarks import spans


def read(run):
    got = spans.program(run)
    if got is None or "admit" not in got["admits"]:
        return None
    return got["admits"]["admit"]["mean_waited_ms"]
