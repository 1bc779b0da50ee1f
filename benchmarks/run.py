#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip; the load generator's clients are child
processes that never import JAX.  The run fails, printing no result,
when JAX finds no TPU or fewer chips than the cell asks for — there is
no CPU fall-back (tests drive the harness's functions on toy
configurations of their own).  Set-up (imports, the server, its warm-up,
the clients) is timed as ``setup_s``; then the cell's traffic runs for
``--seconds``; then, outside the window, the outputs are checked.

The last line of stdout is the result the driver parses: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown`` — the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1`` — and last ``compared``: each
number ``correct`` was decided by, beside its limit (the same go out as
the last lines of stderr, which is what the driver's record keeps of a
run that was not correct).  Everything else a reader may want
(percentiles, lateness, the set-up split, the checks) is on the lines
before it.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()     # before the imports: set-up includes them

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402
from typing import Any, Dict, List, Optional   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import stats                      # noqa: E402
from benchmarks.manifest import Manifest, ManifestError   # noqa: E402
from benchmarks.record import Run                 # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    """Every executable XLA built or loaded in this process, stamped."""

    def __init__(self) -> None:
        self.events: List[tuple] = []

    def listen(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name="", **_) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.monotonic(), str(fun_name),
                                float(duration)))

    def between(self, t0: float, t1: float) -> List[tuple]:
        # an event is stamped at its end; one that ENDED inside the
        # window stalled it, wherever it began
        return [e for e in self.events if t0 <= e[0] <= t1]


class Context:
    """What a driver is given."""

    def __init__(self, manifest: Manifest, cell: Dict[str, Any],
                 seed: int, traced: bool, peaks: Dict[str, Any]) -> None:
        self.manifest = manifest
        self.root = manifest.root
        self.cell = cell
        self.config = manifest.config(cell["config"])
        self.traffic = manifest.traffic(cell["traffic"])
        self.seed = seed
        self.traced = traced
        self.peaks = peaks
        self.t_start = _T_START
        self.setup: Dict[str, float] = {}
        self.compiles = CompileLog()
        self.out_dir = os.path.join(self.root, "bench_out", cell["name"])
        self.trace_dir = os.path.join(self.out_dir, "trace")

    def new_run(self, mix: Dict[str, Any], seed: int, seconds: float
                ) -> Run:
        """An empty record of one window of this cell under ``mix``."""
        try:
            cost = self.family_module("cost")
        except ManifestError:
            cost = None       # a family no roofline metric is read for
        return Run(cell=self.cell, config=self.config, traffic=mix,
                   seed=seed, seconds=seconds, t_start=self.t_start,
                   setup=self.setup, peaks=self.peaks, cost=cost)

    def driver(self):
        name = self.traffic.get("driver") or self.config["driver"]
        return self.manifest.module("drivers", name).Driver(self)

    def family_module(self, kind: str):
        """``cost/<family>.py`` or ``reference/<family>.py`` of the
        configuration's ``family``: a new family brings both as files of
        its own, and no driver or reader names one."""
        return self.manifest.module(kind, self.config["family"])


def device_or_exit(chips: int) -> Dict[str, Any]:
    """The accelerator as JAX reports it; exit non-zero, with no result
    line, when it is no TPU or holds fewer chips than the cell needs."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < chips:
        sys.exit(f"benchmarks/run.py: JAX found {len(devices)} x "
                 f"{devices[0].device_kind!r} on platform {platform!r}; "
                 f"the cell needs {chips} TPU chip(s). No CPU fall-back; "
                 "nothing was built.")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def load_peaks(manifest: Manifest, kind: str) -> Dict[str, Any]:
    with open(manifest.find("peaks.json"), encoding="utf-8") as fh:
        table = json.load(fh)
    if kind not in table:
        raise LookupError(f"no peaks for device_kind {kind!r} in "
                          f"peaks.json (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def read_metrics(manifest: Manifest, run: Run, kind: str
                 ) -> Dict[str, Dict[str, Any]]:
    """Each metric of ``kind`` the cell reports, through its reader; a
    reader that finds nothing to read returns ``None`` and its metric is
    left out."""
    folder = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}
    out: Dict[str, Dict[str, Any]] = {}
    for entry in manifest.metrics(run.cell["name"], kind):
        value = manifest.module(folder[kind], entry["name"]).read(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def describe(run: Run) -> Dict[str, Any]:
    """What goes on the lines before the result: counts by outcome, the
    generator's lateness, the set-up split."""
    outcomes: Dict[str, int] = {}
    for r in run.requests:
        outcomes[r["outcome"]] = outcomes.get(r["outcome"], 0) + 1
    out: Dict[str, Any] = {"outcomes": outcomes, "setup": run.setup,
                           **run.notes}
    stamps = sum(1 for r in run.requests for s in r.get("stamps", ())
                 if run.t0 <= s < run.t1)
    if stamps:
        # beside ``tok_s`` (lanes over the mean gap): whole steps over
        # the window, so a client that stalled near an edge shows
        out["tokens_over_window_per_s"] = stamps / run.seconds
    open_loop = run.traffic.get("loop") == "open"
    sent = [r for r in run.requests if r.get("sent") is not None]
    if open_loop and sent:
        late = stats.lateness_ms([r["due"] for r in sent],
                                 [r["sent"] for r in sent])
        # a camera is due every 1/fps whatever the number of cameras;
        # a token mix is one source
        gap_ms = (1e3 / run.traffic["fps"] if "fps" in run.traffic
                  else run.seconds * 1e3 / max(1, len(sent)))
        out["generator_late_ms"] = {"median": stats.median(late),
                                    "max": max(late)}
        if stats.generator_was_late(late, gap_ms):
            out["GENERATOR_WAS_LATE"] = (
                "median lateness above 5 % of the mean inter-arrival "
                f"time ({gap_ms:.3f} ms): the offered load was not the "
                "cell's")
    return out


def result_line(correct: bool, run: Run, metrics: Dict[str, Any],
                device: Dict[str, Any],
                compared: Optional[Dict[str, Any]] = None
                ) -> Dict[str, Any]:
    """The contract's object: these keys, and ``compared`` (each number
    the check compared, with its limit) last where the driver's check
    gave any."""
    line = {"correct": bool(correct), "attempted": len(run.requests),
            "failed": sum(1 for r in run.requests if not r["ok"]
                          and r["outcome"] != "cut"),
            "metrics": metrics, "device": device}
    if run.trace:
        line["device"] = dict(device, busy_s=run.trace["busy_s"],
                              window_s=run.trace["window_s"])
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    if compared:
        line["compared"] = compared
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest of the same shape (the tests' "
                         "toy cells)")
    args = ap.parse_args(argv)

    manifest = Manifest(args.manifest, root=ROOT)
    cell = manifest.cell(args.workload)
    device = device_or_exit(cell["chips"])
    ctx = Context(manifest, cell, args.seed, bool(args.trace),
                  load_peaks(manifest, device["kind"]))
    ctx.compiles.listen()
    os.makedirs(ctx.out_dir, exist_ok=True)
    ctx.setup["imports_s"] = time.monotonic() - _T_START

    driver = ctx.driver()
    try:
        driver.open()
        run = driver.window(ctx.traffic, args.seed, args.seconds,
                            bool(args.trace))
        # the window's peak: a process's peak never falls again, so it
        # is read before the reference check allocates its own arrays
        device["memory_peak_bytes"] = memory_peak_bytes()
        checks = driver.check(run)
    finally:
        driver.close()
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(manifest, run, kind)
    print(json.dumps({"cell": cell["name"], "seed": args.seed,
                      "seconds": args.seconds, "checks": checks,
                      **describe(run)}), flush=True)
    # the other kind too, for a reader: never in the result line
    other = "end_to_end" if args.trace else "per_layer"
    print(json.dumps({"also": read_metrics(manifest, run, other),
                      "trace": {k: v for k, v in (run.trace or {}).items()
                                if k not in ("counters",)}}), flush=True)
    compared = checks.get("compared") or {}
    print(json.dumps(result_line(checks["correct"], run, metrics, device,
                                 compared)), flush=True)
    for name, got in compared.items():
        print(f"compared {name} {got['value']!r} limit {got['limit']}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
