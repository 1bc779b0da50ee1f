"""Measurement-derived runtime defaults (data over theory).

The quant-graph ``compute:auto`` mode on TPU is a default DECIDED BY
HARDWARE DATA, not theory: in theory int8×int8→int32 on the MXU is 2×
the bf16 rate (v5e) with ¼ the f32 weight traffic, but the only
hardware capture so far (BENCH_int8_r04.json, degraded window) measured
native int8 at 0.65× the f32-emulation batched rate — with an
internally inconsistent per-invoke win, pointing at window drift.

``tools/tflite_int8_tpu_bench.py`` measures all three modes
(f32-emulation / native int8 / weight-only w8) on the real chip each
healthy capture window and emits a ``recommended_default``; running it
with ``--apply`` rewrites the record below from its green artifact, so
the shipped default always carries its own provenance.  The reference's
analogue decision — which delegate serves a quant graph — is hardcoded
per-vendor (tensor_filter_tensorflow_lite.cc:55-118); here it follows
the measurement.
"""

#: compute mode quant tflite graphs get under ``compute:auto`` on TPU:
#: "int8" (native MXU int8), "w8" (weight-only), or "float32"
#: (f32 emulation).
QUANT_AUTO_TPU = "int8"

#: where the current value came from (rewritten by
#: tools/tflite_int8_tpu_bench.py --apply)
QUANT_AUTO_PROVENANCE = (
    "theory default (MXU int8 2x bf16 rate, exact accumulation); the "
    "only capture, BENCH_int8_r04.json, measured 0.65x vs emulation "
    "batched in a DEGRADED window with an inconsistent per-invoke win "
    "- awaiting a healthy-window 3-mode capture (r5 loop armed)")

#: (block_q, block_k) the flash kernel defaults to for long sequences
#: on TPU, measured by tools/flash_tpu_bench.py --tune and applied with
#: --tune --apply.  Used only when both sequence lengths cover the tile
#: (short sequences keep the 128x128 MXU-shaped default so tiny inputs
#: don't pad up to a giant tile).
FLASH_TILES = (128, 128)

FLASH_TILES_PROVENANCE = (
    "default (MXU-shaped 128x128); no healthy-window tile-tune capture "
    "applied yet (r5 loop runs flash_tpu_bench --tune each window)")

#: Per-length measured tiles ``((T, block_q, block_k), ...)`` — the
#: tune step sweeps each length in its TUNE_LENGTHS (8192 and 16384:
#: the 16k grid-overhead loss is why long-T tiles differ) and each
#: row ships only with an on-chip gradcheck at its winning tile.
#: _default_tiles picks the largest measured length <= the sequence;
#: lengths below every row fall back to FLASH_TILES.  Applied with
#: ``flash_tpu_bench --tune --apply``.
FLASH_TILES_BY_T = ()

FLASH_TILES_BY_T_PROVENANCE = (
    "no healthy-window tile-tune capture applied yet (r5 loop runs "
    "flash_tpu_bench --tune each window)")

#: Sequence-length threshold above which full-attention callers
#: (``flash=None``) pick the Pallas flash kernel over naive XLA
#: attention (ops/flash_attention.py flash_wins).  Measured by the
#: timing rows of tools/flash_tpu_bench.py with SUFFIX-WIN semantics:
#: the smallest measured T such that the kernel wins (speedup > 1, or
#: naive fails to compile/OOMs) at that T *and every longer measured
#: T* — a threshold gate must not route an interior losing length to
#: the kernel just because some shorter length won.  Applied with
#: ``flash_tpu_bench --apply-crossover <proof.json>``.
FLASH_MIN_T = 16384

FLASH_MIN_T_PROVENANCE = (
    "r4 default: BENCH_flash_r04.json showed naive faster at every "
    "captured length (0.81x@2k, 0.95x@8k), kernel kept only for the "
    "O(T*d) memory regime; awaiting a healthy-window proof capture "
    "(r5 loop applies the measured crossover automatically)")

#: Measured per-length kernel-vs-naive outcomes, ``((T, wins), ...)``
#: sorted by T, from the same proof timings as FLASH_MIN_T.  The
#: hardware data is NOT monotonic in T (r5: win@2k, win@8k, loss@16k
#: under un-tuned long-T tiles), which a single threshold cannot
#: express — within the table's measured span ``flash_wins`` routes by
#: this evidence (exact hit: that row; between rows: the kernel only
#: when BOTH neighbors won); outside the span the FLASH_MIN_T
#: threshold gate still decides, preserving the memory-regime fallback
#: beyond the longest measurement.  Rows where the kernel itself
#: errored record ``wins=False``; naive-path failures that look like
#: transient infra (not device capacity) contribute no row.  Applied
#: with ``flash_tpu_bench --apply-crossover``.
FLASH_WIN_TABLE = ((2048,True),(8192,True),(16384,False),)

FLASH_WIN_TABLE_PROVENANCE = (
    "measured: BENCH_flash_r05.json (2026-08-01, before PR 1, another "
    "JAX; record deleted in PR 21) \u2014 2048:1.365x, 8192:1.011x, "
    "16384:0.795x, 32768:no-evidence; TPU v5 lite0; applied by "
    "flash_tpu_bench --apply-crossover"
)
