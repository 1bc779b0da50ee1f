#!/usr/bin/env python
"""Noise-aware perf-regression diff over bench row files.

The BENCH/hotpath artifacts carry absolute numbers measured on machines
whose load and thermal state swing run to run — a naive
"candidate slower than baseline" comparison would page on noise (the
same arming philosophy as the PR 6 burn-rate evaluator: one fast window
alone must not page).  So the gate takes TWO prior runs to establish a
per-metric **noise band** first:

    band     = [min(a, b), max(a, b)] per metric
    tolerance = max(band width, --margin %% of the band center, an
                absolute floor for near-zero metrics)
    regression: candidate worse than the band's worst edge by more
                than the tolerance (direction from the metric's unit —
                fps/MB/s/acquires up is better, ns/us/ms/pct down)

A candidate inside (or better than) the band ± tolerance is PASS — a
jitter-sized wiggle can NEVER fail the gate, by construction.  A
genuine regression fails (exit 1) with the evidence, and when the rows
carry ``attribution`` blocks (bench.py / launch.py --profile emit
them), the verdict names **which wait state regressed**: the
attribution deltas are ranked and the biggest mover is the blame — "fps
-18% and queue-wait +21 points" is an actionable bisect hint, "fps
-18%" alone is not.

Input formats (auto-detected per file): JSON-lines of row objects
(bench.py / hotpath_bench stdout), a JSON array of rows, or a single
JSON object (one row, or ``{"rows": [...]}``).  Rows need ``metric``
and numeric ``value``; ``unit`` picks the direction; ``status`` rows
that are not ``live`` are skipped (an infra_dead 0 from tools/soak.py
is not a measurement), and so is a bench.py ``error`` row, whose value
is null.

Usage::

    python tools/perf_diff.py --baseline run1.jsonl --baseline run2.jsonl \
        --candidate run3.jsonl [--margin 10] [--json]

Exit 0 = PASS, 1 = regression, 2 = usage/data error.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Any, Dict, List, Optional

#: unit substrings where LOWER is better; everything else (fps, MB/s,
#: acquires/s, ok) treats higher as better
_LOWER_BETTER = ("ns", "us", "ms", "pct", "percent", "seconds", "bytes")
#: metric-NAME tokens that are lower-is-better regardless of unit: a
#: compile count is a cost (the bounded-executable discipline), and
#: the ledger exports it unitless — ``compiles``/``nns_jit_compiles``
#: rows must not be read as throughput.  ``ttft``/``itl``/``latency``
#: pin the token-latency direction even if a row ships a bare or
#: unconventional unit: an inflated first-token latency must read as
#: REGRESSION no matter how the artifact spelled its unit
_LOWER_BETTER_METRICS = ("compiles", "recompiles", "nns_jit_compiles",
                         "ttft", "itl", "latency")
#: absolute tolerance floor: metrics this close to zero are below the
#: resolution any scheduler can promise
_ABS_FLOOR = 1e-9


def load_rows(path: str) -> List[Dict[str, Any]]:
    """Rows from JSON-lines, a JSON array, or a single object."""
    with open(path, "r", encoding="utf-8") as fh:
        text = fh.read()
    rows: List[Any] = []
    try:
        doc = json.loads(text)
        if isinstance(doc, list):
            rows = doc
        elif isinstance(doc, dict):
            rows = doc.get("rows", [doc])
        else:
            raise ValueError(f"{path}: not rows")
    except json.JSONDecodeError:
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue   # interleaved log noise: skip
    out = []
    for row in rows:
        if not isinstance(row, dict) or "metric" not in row:
            continue
        if not isinstance(row.get("value"), (int, float)):
            continue
        if row.get("status", "live") != "live":
            continue   # a dead link is not a measurement
        out.append(row)
    return out


def lower_is_better(unit: str, metric: str = "") -> bool:
    """Direction from the unit's WORD tokens, not raw substrings: a
    bare ``in`` made every unit containing the letters "ns" (e.g.
    ``tokens_per_s``) silently lower-is-better — which would let a
    collapsed throughput metric PASS the gate (and page on an
    improvement).  ``p99_us``/``latency_ms``/``alloc_bytes`` still
    match on their token.  The metric NAME overrides a missing/neutral
    unit for compile counters: ``nns_jit_compiles_total`` /
    ``steady_compiles`` are costs (bounded-executable discipline) even
    though the ledger exports them unitless."""
    tokens = re.split(r"[^a-z]+", (unit or "").lower())
    if any(t in _LOWER_BETTER for t in tokens if t):
        return True
    mtokens = re.split(r"[^a-z]+", (metric or "").lower())
    return any(t in _LOWER_BETTER_METRICS for t in mtokens if t)


def _attribution_delta(base_rows: List[Dict[str, Any]],
                       cand: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Per-state percentage-point deltas, candidate vs mean of the
    baselines carrying an attribution block; the biggest adverse mover
    is the named blame."""
    cand_states = (cand.get("attribution") or {}).get("states")
    base_states: List[Dict[str, float]] = [
        (r.get("attribution") or {}).get("states") or {}
        for r in base_rows]
    base_states = [s for s in base_states if s]
    if not cand_states or not base_states:
        return None
    deltas = {}
    for state in set(cand_states) | {s for b in base_states for s in b}:
        base_mean = sum(b.get(state, 0.0) for b in base_states) \
            / len(base_states)
        deltas[state] = round(cand_states.get(state, 0.0) - base_mean, 2)
    worst = max(deltas.items(), key=lambda kv: kv[1])
    if worst[1] <= 0:
        # no state's share GREW: attribution cannot name a culprit for
        # this regression — better no hint than a confidently wrong one
        return None
    return {"state_deltas_pct": dict(
                sorted(deltas.items(), key=lambda kv: -abs(kv[1]))),
            "regressed_stage": worst[0],
            "regressed_stage_delta_pct": worst[1]}


def diff(baselines: List[List[Dict[str, Any]]],
         candidate: List[Dict[str, Any]],
         margin_pct: float = 10.0) -> Dict[str, Any]:
    """The comparator: returns the machine-readable verdict."""
    # one sample per metric per run, LAST wins: bench.py re-emits the
    # same metric row progressively enriched (the core number first,
    # trace/attribution added on later emits), so the last line is both
    # the headline value and the one carrying the attribution block
    by_metric: Dict[str, List[Dict[str, Any]]] = {}
    for rows in baselines:
        per_run: Dict[str, Dict[str, Any]] = {}
        for row in rows:
            per_run[row["metric"]] = row
        for m, row in per_run.items():
            by_metric.setdefault(m, []).append(row)
    cand_by_metric: Dict[str, Dict[str, Any]] = {}
    for row in candidate:
        cand_by_metric[row["metric"]] = row
    results = []
    regressions = []
    for cand in cand_by_metric.values():
        m = cand["metric"]
        base_rows = by_metric.get(m, [])
        if len(base_rows) < 2:
            results.append({"metric": m, "verdict": "SKIP",
                            "reason": f"{len(base_rows)} baseline "
                                      "sample(s); need 2 for a noise "
                                      "band"})
            continue
        vals = [float(r["value"]) for r in base_rows]
        lo, hi = min(vals), max(vals)
        center = (lo + hi) / 2.0
        tol = max(hi - lo, abs(center) * margin_pct / 100.0, _ABS_FLOOR)
        val = float(cand["value"])
        lower = lower_is_better(str(cand.get("unit")
                                    or base_rows[0].get("unit") or ""),
                                metric=m)
        if lower:
            regressed = val > hi + tol
            improved = val < lo - tol
        else:
            regressed = val < lo - tol
            improved = val > hi + tol
        row = {"metric": m, "value": val, "band": [lo, hi],
               "tolerance": round(tol, 6),
               "direction": "lower_better" if lower else "higher_better",
               "verdict": ("REGRESSION" if regressed
                           else "IMPROVED" if improved else "PASS")}
        if regressed:
            worst_edge = hi if lower else lo
            row["delta_pct"] = round(
                100.0 * (val - worst_edge) / max(abs(worst_edge),
                                                 _ABS_FLOOR), 2)
            attr = _attribution_delta(base_rows, cand)
            if attr:
                row["attribution"] = attr
            regressions.append(row)
        results.append(row)
    # a metric ANY baseline measured that the candidate no longer emits
    # is a failure, not a silent pass: a run that crashed before
    # producing its rows, a stage that stopped measuring, or a RENAMED
    # key (tokens_per_s -> tok_s evades every band it was gated by)
    # must not exit 0 — removing a measurement has to be acknowledged
    # by refreshing the baselines.  Candidate-only metrics are named as
    # rename suspects so the verdict points at the likely new key.
    cand_only = sorted(m for m in cand_by_metric if m not in by_metric)
    for m, base_rows in sorted(by_metric.items()):
        if m in cand_by_metric:
            continue
        n = len(base_rows)
        reason = (f"measured by {n} baseline run(s), absent from the "
                  "candidate")
        if cand_only:
            reason += (" — candidate-only metric(s) "
                       f"{', '.join(cand_only)} are rename suspects")
        row = {"metric": m, "verdict": "MISSING",
               "band": [min(float(r["value"]) for r in base_rows),
                        max(float(r["value"]) for r in base_rows)],
               "reason": reason}
        if cand_only:
            row["rename_suspects"] = list(cand_only)
        regressions.append(row)
        results.append(row)
    return {"metric": "perf_diff", "pass": not regressions,
            "verdict": "PASS" if not regressions else "REGRESSION",
            "margin_pct": margin_pct,
            "compared": len([r for r in results
                             if r["verdict"] not in ("SKIP", "MISSING")]),
            "skipped": len([r for r in results
                            if r["verdict"] == "SKIP"]),
            "missing": len([r for r in results
                            if r["verdict"] == "MISSING"]),
            "regressions": regressions, "rows": results}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--baseline", action="append", default=[],
                    metavar="FILE",
                    help="prior run's rows (give exactly two: they "
                         "establish the per-metric noise band)")
    ap.add_argument("--candidate", required=True, metavar="FILE",
                    help="the run under judgment")
    ap.add_argument("--margin", type=float, default=10.0, metavar="PCT",
                    help="minimum tolerance as %% of the band center "
                         "(default 10): the band may be accidentally "
                         "tight when two baseline runs happened to "
                         "agree")
    ap.add_argument("--json", action="store_true",
                    help="print the full verdict JSON (default: one "
                         "summary line + regression evidence)")
    args = ap.parse_args(argv)
    if len(args.baseline) < 2:
        print("perf_diff: need two --baseline files to establish the "
              "noise band", file=sys.stderr)
        return 2
    try:
        baselines = [load_rows(p) for p in args.baseline]
        candidate = load_rows(args.candidate)
    except OSError as exc:
        print(f"perf_diff: {exc}", file=sys.stderr)
        return 2
    if not candidate:
        print(f"perf_diff: no live rows in {args.candidate}",
              file=sys.stderr)
        return 2
    verdict = diff(baselines, candidate, margin_pct=args.margin)
    if args.json:
        print(json.dumps(verdict, indent=2))
    else:
        print(json.dumps({k: verdict[k] for k in
                          ("metric", "verdict", "pass", "compared",
                           "skipped")}))
        for reg in verdict["regressions"]:
            if reg["verdict"] == "MISSING":
                print(f"MISSING {reg['metric']}: {reg['reason']} "
                      f"(baseline band {reg['band']})", file=sys.stderr)
                continue
            blame = reg.get("attribution", {})
            stage = (f" — regressed stage: "
                     f"{blame['regressed_stage']} "
                     f"({blame['regressed_stage_delta_pct']:+.1f} pts)"
                     if blame else "")
            print(f"REGRESSION {reg['metric']}: {reg['value']} vs band "
                  f"{reg['band']} (tol {reg['tolerance']}, "
                  f"{reg.get('delta_pct', 0)}%){stage}",
                  file=sys.stderr)
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
