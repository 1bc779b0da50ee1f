#!/usr/bin/env python3
"""Find a cell's knee, once, on the chip: the highest offered load the
system sustains.  The cell's traffic file then fixes a rate at about
four fifths of it; no run of the benchmark ever searches.

    python3 benchmarks/sweep.py --workload <cell> --values 2,3,4,6 \\
        --seconds 25 --seeds 2 [--manifest <another manifest>]

One server, opened once; for every value (requests per second of an
open-loop token mix, cameras of a camera mix) and every seed, one window
of the cell's traffic with that one parameter replaced.  Each window
prints one JSON line: every metric of the cell, the outcomes, and the
latency of the window's first and second half (a backlog that grows
shows as a second half slower than the first).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run as harness            # noqa: E402
from benchmarks import stats                     # noqa: E402
from benchmarks.manifest import Manifest         # noqa: E402


def with_load(mix: dict, value: float) -> dict:
    """The mix with its offered load replaced by ``value``."""
    out = copy.deepcopy(mix)
    if "arrivals" in out:
        out["arrivals"]["rate_per_s"] = float(value)
    elif "cameras" in out:
        out["cameras"] = int(value)
    else:
        raise ValueError(f"mix {mix['name']!r} offers no load to sweep")
    return out


def halves(run) -> dict:
    """Median latency of the requests due in each half of the window."""
    mid = 0.5 * (run.t0 + run.t1)
    out = {}
    for name, lo, hi in (("first_half", run.t0, mid),
                         ("second_half", mid, run.t1)):
        waits = []
        for r in run.requests:
            if not lo <= r["due"] < hi:
                continue
            done = r["stamps"][0] if r.get("stamps") else r.get("done")
            ok = bool(r.get("stamps")) or bool(r.get("done") and r["ok"])
            waits.append(stats.latency_ms(r["due"], done, ok))
        if waits:
            out[name] = {"n": len(waits),
                         "p50_ms": stats.finite_or(stats.median(waits), -1),
                         "p95_ms": stats.finite_or(
                             stats.percentile(waits, 95), -1)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--values", required=True,
                    help="comma-separated loads to try, in order")
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--seed", type=int, default=1000)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args(argv)

    manifest = Manifest(args.manifest, root=ROOT)
    cell = manifest.cell(args.workload)
    device = harness.device_or_exit(cell["chips"])
    ctx = harness.Context(manifest, cell, args.seed, False,
                          harness.load_peaks(manifest, device["kind"]))
    ctx.compiles.listen()
    driver = ctx.driver()
    try:
        driver.open()
        for value in (float(v) for v in args.values.split(",")):
            mix = with_load(ctx.traffic, value)
            for k in range(args.seeds):
                run = driver.window(mix, args.seed + k, args.seconds, False)
                metrics = {
                    **harness.read_metrics(manifest, run, "end_to_end"),
                    **harness.read_metrics(manifest, run, "per_layer")}
                print(json.dumps({
                    "value": value, "seed": args.seed + k,
                    "metrics": {n: m["value"] for n, m in metrics.items()},
                    "halves": halves(run),
                    **harness.describe(run)}), flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
