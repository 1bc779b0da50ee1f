#!/usr/bin/env python
"""On-device proof for the Pallas flash-attention kernel.

All in-tree flash tests run the Pallas interpreter on CPU
(tests/test_flash_attention.py); tile/VMEM-limit bugs only manifest when
Mosaic compiles the kernel for a real chip.  This script runs the kernel
NON-interpreted on the TPU, checks it against the naive jnp oracle at bf16
tolerances, and times kernel vs naive at several sequence lengths.

Prints ONE JSON line:
  {"metric": "flash_attention_tpu_proof", "value": <speedup@max T>,
   "unit": "x_vs_naive", "ok": true, "checks": [...], "timings": [...]}

Exit code 0 iff every correctness check passed on a real TPU.
Refuses to run on CPU (the proof would be meaningless): emits an error
line and exits 2 so the capture loop records an .err, not a false green.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np  # noqa: E402

# bf16 has ~3 decimal digits; the kernel accumulates in f32 so the error
# vs an f32 oracle is dominated by the bf16 cast of inputs/outputs.
BF16_TOL = 2e-2
CHECK_SHAPES = [
    # (T, H, D, causal) — 2k/8k per VERDICT; 1023 exercises the
    # pad-to-block path (odd T must not collapse tiles to 1 row)
    (2048, 8, 64, True),
    (2048, 8, 64, False),
    (1023, 8, 64, True),
    (8192, 8, 64, True),
]
# 16k/32k are the lengths the kernel exists for: naive local_attention
# materializes the (T,T) score matrix per head (32k -> tens of GB),
# so an OOM there is the expected capability win, not a test failure.
TIME_SHAPES = [(2048, 8, 64), (8192, 8, 64), (16384, 8, 64),
               (32768, 8, 64)]


def _time(fn, *args, reps=10):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / reps * 1000  # ms


#: tile-tune sweep lengths: 8192 (the proof's gradcheck length) and
#: 16384 (where the (128,128) default measured a 0.795x LOSS to naive —
#: h*128*128 ~ 131k grid steps ~ 50 ms of pure Mosaic dispatch while
#: the matmuls cost ~3 ms; fewer, larger tiles are the cure, and the
#: per-length record lets 16k take them without disturbing lengths that
#: measured fine at the default)
TUNE_LENGTHS = (8192, 16384)

TILE_CANDIDATES = [(128, 128), (128, 256), (128, 512), (256, 256),
                   (256, 512), (512, 512), (512, 1024), (1024, 1024)]


def tune() -> int:
    """Sweep (block_q, block_k) causal at each TUNE_LENGTHS and print
    one JSON line ranking the tile shapes per length — run in a healthy
    TPU window to pick kernel defaults (the 128x128 default matches the
    MXU but bigger tiles cut grid-iteration overhead when VMEM allows).
    Each length's winner is gradcheck-validated at that length before
    --apply will ship it (the backward kernels' VMEM footprint is much
    bigger than the forward's)."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.ops.flash_attention import flash_attention
    from nnstreamer_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(json.dumps({"metric": "flash_tile_tune", "value": 0,
                          "error": "no TPU"}), flush=True)
        return 2
    rng = np.random.default_rng(0)
    h, d = 8, 64
    lengths = []
    for t in TUNE_LENGTHS:
        q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        rows = []
        for bq, bk in TILE_CANDIDATES:
            fn = jax.jit(functools.partial(
                flash_attention, causal=True, block_q=bq, block_k=bk,
                interpret=False))
            try:
                ms = _time(fn, q, k, v)
                rows.append({"block_q": bq, "block_k": bk,
                             "ms": round(ms, 3)})
            except Exception as exc:
                rows.append({"block_q": bq, "block_k": bk,
                             "error": repr(exc)[:200]})
        timed = [r for r in rows if "ms" in r]
        best = min(timed, key=lambda r: r["ms"]) if timed else {}
        # per-length speedup = default-tile ms / best ms (higher is
        # better).  A missing 128x128 baseline leaves default_ms null —
        # --apply refuses such rows (a provenance stamp must not claim
        # a baseline that was never measured).
        default_ms = next((r["ms"] for r in timed
                           if r["block_q"] == 128 and r["block_k"] == 128),
                          None)
        speedup = (default_ms / best["ms"]) if (best and default_ms) else 0
        # gradient-path validation at the winning tile AND length: the
        # tuned shape becomes the default for the custom_vjp path too,
        # whose dq/dk/dv kernels have a much bigger VMEM footprint than
        # the forward — a tile that only the forward can allocate must
        # not ship
        grad_ok = False
        if best:
            try:
                def loss(q, k, v):
                    return jnp.sum(flash_attention(
                        q, k, v, causal=True, block_q=best["block_q"],
                        block_k=best["block_k"], interpret=False) ** 2)

                g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
                jax.block_until_ready(g)
                grad_ok = all(bool(jnp.all(jnp.isfinite(
                    x.astype(jnp.float32)))) for x in g)
            except Exception as exc:
                best = dict(best, grad_error=repr(exc)[:200])
        lengths.append({"t": t, "rows": rows, "best": best,
                        "grad_ok": grad_ok, "default_ms": default_ms,
                        "speedup": round(speedup, 4)})
    first = lengths[0]
    # headline value = best per-length speedup (higher is better — the
    # capture loop's keep-best-score policy relies on that orientation);
    # top-level best/grad_ok/default_ms/rows mirror the first length for
    # artifact back-compat
    print(json.dumps({"metric": "flash_tile_tune",
                      "unit": "x_vs_128x128_tile",
                      "value": max(e["speedup"] for e in lengths),
                      "best": first["best"],
                      "grad_ok": first["grad_ok"],
                      "default_ms": first["default_ms"],
                      "rows": first["rows"], "lengths": lengths,
                      "device": str(dev)}), flush=True)
    return 0 if any(e["best"] for e in lengths) else 1


_NAIVE_INFEASIBLE_MARKERS = (
    # XLA/PJRT device-capacity signatures only — deliberately NOT loose
    # substrings like "allocat"/"exceeds", which also appear in
    # host failures ("Cannot allocate memory") and would defeat the
    # flake filter
    "RESOURCE_EXHAUSTED", "OUT_OF_MEMORY", "Out of memory",
    "out of memory", "OOM", "VMEM limit", "vmem limit",
    "HBM capacity", "hbm capacity")


def _naive_infeasible(err: str) -> bool:
    """True when a naive-path failure reads like a DEVICE capacity
    limit (the O(T^2) score matrix not fitting) rather than a transient
    failure of the host.  Only capacity failures count as kernel WINS —
    a flake during the naive run must not lower the persisted selection
    default."""
    return any(m in (err or "") for m in _NAIVE_INFEASIBLE_MARKERS)


_INFRA_TRANSIENT_MARKERS = (
    # RPC plumbing signatures — failures of the PATH to the device,
    # not of the kernel on it.  Deliberately
    # narrow, mirroring _NAIVE_INFEASIBLE_MARKERS: an unrecognized
    # kernel error stays durable evidence (naive must serve that
    # length) rather than being waved off as a flake.
    "ConnectionError", "ConnectionReset", "Connection reset",
    "ConnectionRefused", "Connection refused", "BrokenPipe",
    "Broken pipe", "timed out", "TimeoutError", "DEADLINE_EXCEEDED",
    "UNAVAILABLE", "Unavailable", "Socket closed", "EOFError",
    "HTTP error", "HTTP 5", "Remote disconnected", "RemoteDisconnected")


def _infra_transient(err: str) -> bool:
    """True when an error string reads like transient infra, not a
    deterministic device/kernel failure."""
    return any(m in (err or "") for m in _INFRA_TRANSIENT_MARKERS)


def _row_evidence(row):
    """Single classification of one timing row, shared by the
    crossover, the win table, and the provenance stamp (three consumers
    of one rule set must not drift): returns (verdict, label) where
    verdict is True (kernel wins: speedup > 1, or naive hit a DEVICE
    capacity wall while the kernel ran), False (kernel loses: measured
    slower, or the kernel itself failed deterministically — naive has
    to serve that length), or None (no evidence: EITHER side failed for
    reasons that read like transient infra — a flake during the kernel
    run must not enshrine a durable wins=False row via
    --apply-crossover any more than one during the naive run may
    enshrine a win)."""
    t = row.get("T")
    if row.get("error"):
        if _infra_transient(row.get("error", "")):
            return None, "%s:kernel-no-evidence" % t
        return False, "%s:kernel-error" % t
    if row.get("flash_only"):
        if _naive_infeasible(row.get("naive_error", "")):
            return True, "%s:naive-oom" % t
        return None, "%s:no-evidence" % t
    wins = row.get("speedup", 0) > 1.0
    return wins, "%s:%sx" % (t, row.get("speedup"))


def measured_crossover(timings):
    """Kernel-vs-naive crossover with SUFFIX-WIN semantics: the smallest
    measured T such that the kernel wins (speedup > 1, or the naive
    path hit a CAPACITY failure while the kernel ran) at that T AND at
    every longer measured T.  flash_min_t() is a threshold gate —
    deriving it from "first winning length" would route an interior
    LOSING length (e.g. a 16k row under un-tuned tiles) to the kernel
    just because 2k won.  Rows where the kernel itself errored break
    any win suffix; flash_only rows whose naive failure looks like
    transient infra (not capacity) are SKIPPED — no evidence either
    way — so they neither extend nor break the suffix, and the
    crossover must anchor on a definite win.  None when even the
    longest measured length loses."""
    crossover = None
    for row in reversed(timings):
        verdict, _ = _row_evidence(row)
        if verdict is None:
            continue
        if not verdict:
            break
        crossover = row["T"]
    return crossover


def measured_win_table(timings):
    """Per-length ((T, wins), ...) evidence rows for the FLASH_WIN_TABLE
    record — the non-monotonic complement to the suffix-win threshold.
    Classification is _row_evidence's; evidence-free rows contribute
    nothing."""
    rows = []
    for row in timings:
        verdict, _ = _row_evidence(row)
        if verdict is not None:
            rows.append((int(row["T"]), verdict))
    return tuple(sorted(rows))


def main() -> int:
    import jax

    from nnstreamer_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print(json.dumps({"metric": "flash_attention_tpu_proof",
                          "value": 0, "unit": "x_vs_naive", "ok": False,
                          "error": "no TPU (refusing interpreter proof)",
                          "device": str(dev)}), flush=True)
        return 2

    import jax.numpy as jnp

    from nnstreamer_tpu.ops.flash_attention import flash_attention
    from nnstreamer_tpu.parallel.ring_attention import local_attention

    rng = np.random.default_rng(0)
    checks = []
    ok = True
    for t, h, d, causal in CHECK_SHAPES:
        q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        flash = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=False))
        try:
            got = np.asarray(flash(q, k, v), np.float32)
            want = np.asarray(local_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=causal), np.float32)
            err = float(np.max(np.abs(got - want)))
            passed = bool(np.isfinite(err) and err < BF16_TOL)
        except Exception as exc:  # Mosaic compile/launch failure
            err, passed = float("nan"), False
            checks.append({"T": t, "H": h, "D": d, "causal": causal,
                           "ok": False, "error": repr(exc)[:300]})
            ok = False
            continue
        checks.append({"T": t, "H": h, "D": d, "causal": causal,
                       "max_abs_err": round(err, 5), "ok": passed})
        ok = ok and passed

    # streaming backward (FlashAttention-2 structure): gradcheck vs the
    # naive oracle, non-interpreted — Mosaic must compile all three
    # backward kernels for the real chip
    # 8192 hardware-verifies the O(T·d) claim at a length where it
    # matters: the naive backward materializes (T,T) probability tiles,
    # the streaming backward never does
    grad_checks = []
    for t, h, d in [(1024, 8, 64), (1023, 4, 64), (8192, 8, 64)]:
        q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           interpret=False) ** 2)

        def loss_naive(q, k, v):
            return jnp.sum(local_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True) ** 2)

        try:
            gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
            gn = jax.jit(jax.grad(loss_naive, argnums=(0, 1, 2)))(q, k, v)
            errs = [float(np.max(np.abs(np.asarray(a, np.float32)
                                        - np.asarray(b, np.float32))))
                    for a, b in zip(gf, gn)]
            # grads scale with T; compare relative to the oracle's range
            ref = max(float(np.max(np.abs(np.asarray(b, np.float32))))
                      for b in gn)
            rel = max(errs) / max(ref, 1e-6)
            passed = bool(np.isfinite(rel) and rel < 5e-2)
        except Exception as exc:
            grad_checks.append({"T": t, "ok": False,
                                "error": repr(exc)[:300]})
            ok = False
            continue
        grad_checks.append({"T": t, "H": h, "D": d,
                            "max_rel_grad_err": round(rel, 5),
                            "ok": passed})
        ok = ok and passed

    # correctness + grad checks are done: snapshot their verdict before
    # the timing loop — a kernel error while TIMING a length is evidence
    # (a loss at that length, recorded in the row) and fails the overall
    # `ok`, but must not impeach the math the checks proved, so the
    # appliers gate on `checks_ok`
    checks_ok = ok
    timings = []
    speedup = 0.0
    for t, h, d in TIME_SHAPES:
        q = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((t, h, d)), jnp.bfloat16)
        flash = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False))
        naive = jax.jit(lambda q, k, v: local_attention(
            q, k, v, causal=True))
        try:
            ms_flash = _time(flash, q, k, v)
        except Exception as exc:
            # the kernel itself must run at every length — that IS the proof
            timings.append({"T": t, "error": repr(exc)[:300]})
            ok = False
            continue
        try:
            ms_naive = _time(naive, q, k, v)
        except Exception as exc:
            # naive blowing up (OOM on the (T,T) scores) at long T is the
            # capability headroom the streaming kernel buys — record it as
            # a win, not a failure
            timings.append({"T": t, "flash_ms": round(ms_flash, 3),
                            "naive_ms": None,
                            "naive_error": repr(exc)[:200],
                            "flash_only": True})
            continue
        speedup = ms_naive / ms_flash if ms_flash else 0.0
        timings.append({"T": t, "flash_ms": round(ms_flash, 3),
                        "naive_ms": round(ms_naive, 3),
                        "speedup": round(speedup, 3)})

    crossover = measured_crossover(timings)
    print(json.dumps({"metric": "flash_attention_tpu_proof",
                      "value": round(speedup, 3), "unit": "x_vs_naive",
                      "ok": ok, "checks_ok": checks_ok,
                      "crossover_T": crossover,
                      "checks": checks,
                      "grad_checks": grad_checks, "timings": timings,
                      "device": str(dev)}), flush=True)
    return 0 if ok else 1


def _valid_tune_entry(e: dict) -> bool:
    """A tune entry ships only with (a) a measured 128x128 baseline —
    the provenance must never claim a comparison that didn't run — and
    (b) grad_ok: the tuned tile becomes the custom_vjp default too, so
    the backward kernels must have allocated at that shape (and length)
    on the real chip."""
    return bool(e.get("best", {}).get("ms") and e.get("default_ms")
                and e.get("grad_ok"))


def apply_tiles_from_artifact(path: str, tuned_path: str = None) -> int:
    """--tune --apply <artifact.json>: rewrite utils/tuned.py's tile
    records from a green tile-tune capture, provenance-stamped.  The
    per-length FLASH_TILES_BY_T record takes every valid length entry
    (see _valid_tune_entry); the legacy single FLASH_TILES record takes
    the first length's winner when valid (old single-length artifacts
    carry only that).  All records land in one atomic write.  Exit 1
    when no entry qualifies."""
    from _tuned_apply import load_last_row, rewrite_tuned_many

    def entries(r):
        # old artifacts have no "lengths": treat the top level as the
        # single (T=8192) entry
        return r.get("lengths") or [dict(r, t=8192)]

    row = load_last_row(
        path, "flash_tile_tune",
        pred=lambda r: any(_valid_tune_entry(e) for e in entries(r)))
    if row is None:
        print(f"apply: no tile-tune entry with a 128x128 baseline AND a "
              f"passing gradient check in {path}", file=sys.stderr)
        return 1
    valid = [e for e in entries(row) if _valid_tune_entry(e)]
    by_t = [(int(e["t"]), int(e["best"]["block_q"]),
             int(e["best"]["block_k"])) for e in valid]
    detail = ", ".join(
        f"T={e['t']}: {e['best']['block_q']}x{e['best']['block_k']} "
        f"{e['best']['ms']} ms vs 128x128 {e['default_ms']} ms"
        for e in valid)
    stamp = (f"measured: {os.path.basename(path)} — {detail} (causal, "
             f"{row.get('device', '?')}); backward kernels validated "
             "per tile+length (grad_ok); applied by flash_tpu_bench "
             "--tune --apply")
    by_t_src = "(%s,)" % ",".join("(%d,%d,%d)" % e for e in by_t)
    specs = [(r"FLASH_TILES_BY_T = \(.*\)",
              f"FLASH_TILES_BY_T = {by_t_src}",
              "FLASH_TILES_BY_T_PROVENANCE", stamp)]
    applied = {"applied_by_t": [list(e) for e in by_t]}
    first = entries(row)[0]
    if _valid_tune_entry(first):
        bq, bk = (int(first["best"]["block_q"]),
                  int(first["best"]["block_k"]))
        specs.append((r"FLASH_TILES = \(\d+, \d+\)",
                      f"FLASH_TILES = ({bq}, {bk})",
                      "FLASH_TILES_PROVENANCE", stamp))
        applied["applied"] = [bq, bk]
    if not rewrite_tuned_many(specs, tuned_path):
        return 1
    print(json.dumps(applied), flush=True)
    return 0


def apply_crossover_from_artifact(path: str, tuned_path: str = None) -> int:
    """--apply-crossover <proof.json>: rewrite utils/tuned.py's
    kernel-selection records from a green flash-proof capture,
    provenance-stamped.  Requires the row to be fully ok (every
    correctness and grad check passed — a selection default must not
    come from a run whose kernel mis-computed) and at least one timing
    row with evidence.  Always writes the per-length FLASH_WIN_TABLE
    (the hardware data is non-monotonic in T, which a threshold cannot
    express); additionally rewrites the FLASH_MIN_T threshold when the
    timings yield a non-null suffix-win crossover (recomputed here, NOT
    read from the stored crossover_T field, so artifacts written under
    older crossover semantics apply correctly; a null crossover means
    no unbroken win suffix, and the out-of-span fallback threshold
    stands).  Both records land in ONE atomic write (a partial rewrite
    would make the provenance lie).  The check gate is ``checks_ok``
    (correctness + grad checks) where the artifact carries it — a
    kernel error in a TIMING row is itself evidence (a loss at that
    length), not a reason to refuse the capture's other lengths; old
    artifacts without checks_ok fall back to the stricter ``ok``.
    Exit 1 when there is nothing applicable."""
    from _tuned_apply import load_last_row, rewrite_tuned_many

    row = load_last_row(
        path, "flash_attention_tpu_proof",
        pred=lambda r: (r.get("checks_ok", r.get("ok"))
                        and measured_win_table(r.get("timings", []))))
    if row is None:
        print(f"apply-crossover: no checks-ok proof row with timing "
              f"evidence in {path}", file=sys.stderr)
        return 1
    labels = [_row_evidence(r)[1] for r in row.get("timings", [])]
    evidence = "%s; %s" % (", ".join(labels), row.get("device", "?"))
    table = measured_win_table(row["timings"])
    table_src = "(%s,)" % ",".join("(%d,%s)" % tw for tw in table)
    specs = [(
        r"FLASH_WIN_TABLE = \(.*\)",
        f"FLASH_WIN_TABLE = {table_src}",
        "FLASH_WIN_TABLE_PROVENANCE",
        f"measured: {os.path.basename(path)} — {evidence}; applied "
        "by flash_tpu_bench --apply-crossover")]
    applied = {"applied_win_table": list(table)}
    crossover = measured_crossover(row["timings"])
    if crossover is not None:
        t = int(crossover)
        specs.append((
            r"FLASH_MIN_T = \d+", f"FLASH_MIN_T = {t}",
            "FLASH_MIN_T_PROVENANCE",
            f"measured: {os.path.basename(path)} — suffix-win crossover "
            f"at T={t} ({evidence}); applied by flash_tpu_bench "
            "--apply-crossover"))
        applied["applied_min_t"] = t
    if not rewrite_tuned_many(specs, tuned_path):
        return 1
    print(json.dumps(applied), flush=True)
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--apply-crossover" in argv:
        idx = argv.index("--apply-crossover")
        if idx + 1 >= len(argv):
            print("usage: flash_tpu_bench.py --apply-crossover "
                  "<BENCH_flash_r0N.json>", file=sys.stderr)
            sys.exit(2)
        sys.exit(apply_crossover_from_artifact(argv[idx + 1]))
    if "--apply" in argv and "--tune" not in argv:
        print("usage: flash_tpu_bench.py --tune --apply "
              "<BENCH_flashtune_r0N.json> (--apply applies TILE-TUNE "
              "data; bare --apply would silently run the full proof)",
              file=sys.stderr)
        sys.exit(2)
    if "--tune" in argv and "--apply" in argv:
        idx = argv.index("--apply")
        if idx + 1 >= len(argv):
            # no silent fallback to a (possibly stale prior-round)
            # artifact: the operand is the audit trail
            print("usage: flash_tpu_bench.py --tune --apply "
                  "<BENCH_flashtune_r0N.json>", file=sys.stderr)
            sys.exit(2)
        sys.exit(apply_tiles_from_artifact(argv[idx + 1]))
    sys.exit(tune() if "--tune" in argv else main())
