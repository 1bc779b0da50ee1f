#!/usr/bin/env python
"""On-device proof for native-int8 tflite execution.

Runs the reference's real mobilenet_v2_1.0_224_quant.tflite on the TPU
in three modes — f32 emulation (compute:float32), native int8
(compute:int8), weight-only (compute:w8) — and reports agreement (quant
steps, top-1) plus p50 single-invoke latency and batch-64 throughput
for each.  Prints one red progress JSON line per completed mode (value
0 + "error": partial, so a killed run leaves its measured modes on
record) and a final all-modes line that supersedes them — consumers
take the LAST line; exit 0 iff the modes agree within tolerance on a
real TPU.

CPU twin: tests/test_tflite_quant_native.py (synthetic graphs — the full
model costs ~90s of XLA CPU int8-conv compile, so the real-model check
lives here in the TPU window where it is cheap).
"""

import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np  # noqa: E402

MODEL = ("/root/reference/tests/test_models/models/"
         "mobilenet_v2_1.0_224_quant.tflite")
TOL_STEPS = 4
BATCH = 64


def _perf_fields(perf):
    """p50/batched-fps row keys for the measured modes — shared by the
    partial-progress lines and the final row so the key names cannot
    drift apart ("float32" shortens to "f32" in keys)."""
    short = {"float32": "f32"}
    out = {}
    for m, (p50, bfps) in perf.items():
        k = short.get(m, m)
        out[f"p50_ms_{k}"] = round(p50, 3)
        out[f"batched_fps_{k}"] = round(bfps, 1)
    return out


def _bench(fw, x):
    import jax

    lats = []
    for _ in range(20):
        t0 = time.monotonic()
        out = fw.invoke([x[0]])
        jax.block_until_ready(out)
        lats.append((time.monotonic() - t0) * 1000)
    lats.sort()
    fw.warmup_batched(BATCH)
    frames = [[x[0]] for _ in range(BATCH)]
    t0 = time.monotonic()
    reps = 5
    for _ in range(reps):
        handle = fw.invoke_batched(frames, BATCH)
        handle.wait()
    bfps = reps * BATCH / (time.monotonic() - t0)
    return lats[len(lats) // 2], bfps


def main() -> int:
    import jax

    from nnstreamer_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    result = {"metric": "tflite_quant_native_tpu", "unit": "x_vs_emulation",
              "device": str(dev)}
    if dev.platform == "cpu":
        result.update(value=0, ok=False,
                      error="no TPU (CPU twin is the synthetic test)")
        print(json.dumps(result), flush=True)
        return 2
    if not os.path.isfile(MODEL):
        result.update(value=0, ok=False, error="reference model missing")
        print(json.dumps(result), flush=True)
        return 2

    from nnstreamer_tpu.filter.framework import (FilterProperties,
                                                 open_backend)

    x = np.random.default_rng(0).integers(
        0, 256, (1, 224, 224, 3), dtype=np.uint8)
    outs, perf = {}, {}
    # three serving modes for the same quant graph: f32 emulation,
    # native int8 on the MXU, weight-only (packed int8 weights,
    # bf16 math) — the round-4 window measured int8 slower than
    # emulation, so the artifact carries all three for the default call
    for mode in ("float32", "int8", "w8"):
        fw = open_backend(FilterProperties(
            framework="tensorflow-lite", model=MODEL,
            custom_properties={"compute": mode}))
        try:
            outs[mode] = np.asarray(fw.invoke([x[0]])[0], np.int32)
            perf[mode] = _bench(fw, x)
        finally:
            fw.close()
        # per-mode progress line: a window dying (or the step timeout
        # firing) mid-run must not discard the modes already measured —
        # the round-4 outage killed this tool at 15 min with all three
        # modes' work lost.  The line is red (value 0, error) so the
        # capture loop never installs it as the proof; the loop keeps
        # the last red output at $STAGE/int8.red for diagnosis, and the
        # final all-modes line below supersedes these (last-line-wins)
        print(json.dumps(dict(
            result, value=0, ok=False,
            error=f"partial: {len(perf)}/3 modes measured",
            modes_done=sorted(perf), **_perf_fields(perf))), flush=True)
    diff = np.abs(outs["float32"] - outs["int8"])
    diff_w8 = np.abs(outs["float32"] - outs["w8"])
    ok = (int(diff.max()) <= TOL_STEPS
          and outs["float32"].argmax() == outs["int8"].argmax()
          and int(diff_w8.max()) <= TOL_STEPS
          and outs["float32"].argmax() == outs["w8"].argmax())
    speedup = perf["float32"][1] and perf["int8"][1] / perf["float32"][1]
    # the data-derived default (utils/tuned.py consumes this via
    # --apply): among modes that AGREED with the f32 oracle, the one
    # with the best batched throughput serves compute:auto quant graphs
    candidates = {"float32": perf["float32"][1]}
    if int(diff.max()) <= TOL_STEPS and bool(
            outs["float32"].argmax() == outs["int8"].argmax()):
        candidates["int8"] = perf["int8"][1]
    if int(diff_w8.max()) <= TOL_STEPS and bool(
            outs["float32"].argmax() == outs["w8"].argmax()):
        candidates["w8"] = perf["w8"][1]
    recommended = max(candidates, key=candidates.get)
    result.update(
        value=round(float(speedup), 3), ok=bool(ok),
        max_qstep_diff=int(diff.max()),
        max_qstep_diff_w8=int(diff_w8.max()),
        top1_agree=bool(outs["float32"].argmax() == outs["int8"].argmax()),
        **_perf_fields(perf),
        w8_vs_f32=round(perf["w8"][1] / perf["float32"][1], 3)
        if perf["float32"][1] else 0, batch=BATCH,
        recommended_default=recommended)
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


def apply_from_artifact(path: str, tuned_path: str = None) -> int:
    """--apply <artifact.json>: rewrite utils/tuned.py's quant-auto
    default from a COMPLETED 3-mode capture, stamping provenance (file,
    per-mode fps, window link) so the shipped default is auditable.

    Gates on completion, not on global ok: ok=False means some mode
    disagreed with the f32 oracle — exactly when the recommendation
    (drawn only from AGREEING modes, f32 always in) matters most.
    No-op (exit 1) when the artifact is missing/red or lacks the
    recommendation."""
    from _tuned_apply import load_last_row, rewrite_tuned

    row = load_last_row(
        path, "tflite_quant_native_tpu",
        pred=lambda r: (r.get("recommended_default")
                        and r.get("batched_fps_f32", 0) > 0))
    if row is None:
        print(f"apply: no completed 3-mode row in {path}", file=sys.stderr)
        return 1
    mode = row["recommended_default"]
    if mode not in ("float32", "int8", "w8"):
        print(f"apply: bad mode {mode!r}", file=sys.stderr)
        return 1
    provenance = (
        f"measured: {os.path.basename(path)} — batched fps "
        f"f32={row.get('batched_fps_f32')} "
        f"int8={row.get('batched_fps_int8')} "
        f"w8={row.get('batched_fps_w8')} (batch {row.get('batch')}, "
        f"{row.get('device', '?')}); modes agreeing with the f32 "
        f"oracle only; applied by tflite_int8_tpu_bench --apply")
    if not rewrite_tuned(r'QUANT_AUTO_TPU = "[a-z0-9]+"',
                         f'QUANT_AUTO_TPU = "{mode}"',
                         "QUANT_AUTO_PROVENANCE", provenance,
                         tuned_path):
        return 1
    print(json.dumps({"applied": mode, "provenance": provenance}),
          flush=True)
    return 0


if __name__ == "__main__":
    if "--apply" in sys.argv[1:]:
        idx = sys.argv.index("--apply")
        if idx + 1 >= len(sys.argv):
            # no silent fallback to a (possibly stale prior-round)
            # artifact: the operand is the audit trail
            print("usage: tflite_int8_tpu_bench.py --apply "
                  "<BENCH_int8_r0N.json>", file=sys.stderr)
            sys.exit(2)
        sys.exit(apply_from_artifact(sys.argv[idx + 1]))
    sys.exit(main())
