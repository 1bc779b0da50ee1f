"""Model math: the whole decode step's share of the chip's peak FLOP/s,
in percent, read off the trace: the operations the WHOLE decode steps of
the traced slice need (``cost/<family>.py``, from shapes: one routed
expert, the positions actually attended) over the length of the interval
those steps span and this chip's ``flops_per_s`` of ``peaks.json``.

Steps, their lanes and the interval are the trace's own
(``benchmarks/spans.py``: ``llm.decode.operands`` starts, the ``lanes``
of the ``llm.decode`` spans), on the clock the device's operations lie
on; idle time and prefills inside the interval count against it.  No
host clock enters.  It stands beside the rooflines
(``decode_step_roofline``: least time over BUSY time, bound by bytes;
``shared_kv_attn_roofline``: one group of operations): a change that
takes an operation off the path leaves its roofline silent, and this
share still says what the chip delivered.  Decode at 32 lanes is bound
by bytes, so it reads a few percent."""

from benchmarks import spans


def read(run):
    got = spans.stepped(run)
    if got is None or run.cost is None or "decode_spans" not in got:
        return None
    samples = [s for s in run.trace["counters"]["samples"] if s[2]]
    if not samples or got["interval_s"] <= 0:
        return None
    lanes = got["decode_spans"]["lanes_mean"]
    attended = sum(s[3] for s in samples) / len(samples)
    flops, _ = run.cost.decode_step_cost(
        run.config["model"], max(1, round(lanes)), round(attended))
    return (100.0 * flops * got["steps"]
            / (got["interval_s"] * run.peaks["flops_per_s"]))
