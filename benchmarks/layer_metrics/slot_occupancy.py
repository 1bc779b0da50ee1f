"""KV pools: mean of live sessions over slots, sampled every 100 ms, in
percent."""


def read(run):
    samples = run.counters["samples"]
    if not samples:
        return None
    live = sum(s[1] for s in samples) / len(samples)
    return 100.0 * live / run.counters["slots"]
