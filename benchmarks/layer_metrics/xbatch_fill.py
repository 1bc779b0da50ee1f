"""Stream batcher, cross-stream: frames served through shared buckets over
(bucket dispatches x the server's ``batch``), in percent.  Frames that
went through alone (a bucket of one is served unbatched) are not in it."""


def read(run):
    c = run.counters
    if not c.get("xb_invokes"):
        return None
    return 100.0 * c["xb_frames"] / (c["xb_invokes"] * c["batch"])
