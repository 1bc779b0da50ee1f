"""Pallas flash-attention kernel vs the naive oracle (interpret mode on
CPU; the same kernel compiles for the MXU on TPU)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map

from nnstreamer_tpu.ops.flash_attention import flash_attention
from nnstreamer_tpu.parallel.ring_attention import local_attention


def _qkv(t, h, d, seed=0, dtype=jnp.float32, t_kv=None):
    rng = np.random.default_rng(seed)
    mk = lambda tt: jnp.asarray(rng.standard_normal((tt, h, d)), dtype)
    return mk(t), mk(t_kv or t), mk(t_kv or t)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,h,d", [(64, 4, 32), (48, 2, 16), (128, 8, 64)])
def test_matches_oracle(t, h, d, causal):
    q, k, v = _qkv(t, h, d)
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=16,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("t,t_kv", [(40, 40), (1023, 1023), (33, 65),
                                    (5, 7), (130, 1)])
@pytest.mark.parametrize("causal", [False, True])
def test_odd_lengths_pad_to_block_multiple(t, t_kv, causal):
    """A T that doesn't divide the tile is zero-padded up to a block
    multiple (padded K masked, padded Q sliced) — tiles never collapse
    to 1-row shapes.  1023 is the prime-adjacent case from the round-3
    advisor finding; (130, 1) exercises a single-K-row pad."""
    if causal and t != t_kv:
        pytest.skip("causal requires square self-attention here")
    q, k, v = _qkv(t, 2, 16, seed=1, t_kv=t_kv)
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    assert out.shape == (t, 2, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-5)


def test_padded_gradients_match_naive():
    # the vjp recompute path must agree at a padded length too
    q, k, v = _qkv(33, 2, 16, seed=7)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, interpret=True) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_bf16_inputs_accumulate_in_f32():
    q, k, v = _qkv(64, 4, 32, seed=2, dtype=jnp.bfloat16)
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_block_offsets_preserve_global_causality():
    """Blockwise use (ring-style): attending a PAST block is unmasked,
    a FUTURE block fully masked rows handled via running stats."""
    t, h, d = 32, 2, 16
    q, k, v = _qkv(t, h, d, seed=3, t_kv=t)
    # queries at global positions [t, 2t) attending K block 0: the whole
    # block is in the past, so this equals UNMASKED attention over it
    out_past = flash_attention(q, k, v, causal=True, q_offset=t, k_offset=0,
                               block_q=16, block_k=16, interpret=True)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    p = jax.nn.softmax(s, axis=-1)
    ref_past = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out_past), np.asarray(ref_past),
                               atol=2e-5, rtol=1e-5)


def test_ulysses_flash_path_matches_naive(jax_cpu_devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from nnstreamer_tpu.parallel.ulysses import ulysses_attention

    mesh = Mesh(np.array(jax_cpu_devices[:2]), ("sp",))
    t, h, d = 32, 4, 16
    q, k, v = _qkv(t, h, d, seed=4)

    def run(flash):
        fn = shard_map(
            lambda qq, kk, vv: ulysses_attention(qq, kk, vv, "sp",
                                                 causal=True, flash=flash),
            mesh=mesh, in_specs=(P("sp"), P("sp"), P("sp")),
            out_specs=P("sp"), check_vma=False)
        return np.asarray(jax.jit(fn)(q, k, v))

    np.testing.assert_allclose(run(True), run(False), atol=2e-5, rtol=1e-5)


def test_cross_length_noncausal_gradients():
    """Streaming backward at Tq != Tkv (both padded to block multiples)."""
    q, _, _ = _qkv(33, 2, 16, seed=8)
    _, k, v = _qkv(33, 2, 16, seed=9, t_kv=49)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32,
                                       interpret=True) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(local_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_block_offset_gradients_preserve_global_causality():
    """Blockwise (ring-style) training: grads through a past-block
    attention call match the unmasked oracle."""
    t, h, d = 32, 2, 16
    q, k, v = _qkv(t, h, d, seed=10)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, q_offset=t, k_offset=0,
                              block_q=16, block_k=16, interpret=True)
        return jnp.sum(out ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(local_attention(q, k, v) ** 2)   # fully unmasked

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_gradients_match_naive():
    """custom_vjp: flash forward + recompute backward == jax.grad of the
    naive oracle (training through ulysses/flash must work)."""
    t, h, d = 32, 2, 16
    q, k, v = _qkv(t, h, d, seed=5)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


class TestLengthGatedSelection:
    """flash_wins: kernel-vs-naive selection is gated on sequence length
    (hardware data: naive XLA attention beat the kernel at 2k and 8k;
    the kernel's O(T*d) memory makes it mandatory at long context)."""

    def test_below_crossover_prefers_naive_even_on_tpu(self, monkeypatch):
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        # pin the measured records: the live tuned.py values move with
        # each applied capture, the GATE semantics must not
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 16384)
        monkeypatch.setattr(tuned, "FLASH_WIN_TABLE", ())
        assert not fa.flash_wins(197)      # vit
        assert not fa.flash_wins(2048)     # lm prefill
        assert not fa.flash_wins(8192)
        assert fa.flash_wins(16384)
        assert fa.flash_wins(32768)

    def test_gate_follows_measured_tuned_record(self, monkeypatch):
        """flash_min_t() consults utils/tuned.py FLASH_MIN_T (the
        provenance-stamped record --apply-crossover rewrites), not a
        hardcoded constant."""
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 2048)
        monkeypatch.setattr(tuned, "FLASH_WIN_TABLE", ())
        assert fa.flash_wins(2048)
        assert not fa.flash_wins(2047)

    def test_off_tpu_never_selects_kernel(self, monkeypatch):
        from nnstreamer_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "flash_is_default", lambda: False)
        assert not fa.flash_wins(32768)

    def test_env_override_moves_crossover(self, monkeypatch):
        from nnstreamer_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setenv("NNS_TPU_FLASH_MIN_T", "1024")
        assert fa.flash_wins(2048)
        monkeypatch.setenv("NNS_TPU_FLASH_MIN_T", "65536")
        assert not fa.flash_wins(32768)

    def test_win_table_routes_nonmonotonic_lengths(self, monkeypatch):
        """The r5 hardware data is non-monotonic (win@2k/8k, loss@16k
        under un-tuned long-T tiles) — inside its measured span the
        per-length table decides: exact hits take their row, interior
        lengths take the kernel only when BOTH neighbors won."""
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 16384)
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE",
            ((2048, True), (8192, True), (16384, False)))
        assert fa.flash_wins(2048)       # exact measured win (lm@2k)
        assert fa.flash_wins(8192)
        assert not fa.flash_wins(16384)  # exact measured loss
        assert fa.flash_wins(4096)       # interior, both neighbors won
        assert not fa.flash_wins(12000)  # interior across the 16k loss

    def test_win_table_out_of_span_falls_back_to_threshold(
            self, monkeypatch):
        """Outside the table's measured span the FLASH_MIN_T threshold
        still decides — the memory-regime fallback (naive's O(T^2)
        score matrix) must survive beyond the longest measurement, and
        unmeasured short lengths must not inherit the 2k win."""
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 16384)
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE",
            ((2048, True), (8192, True), (16384, False)))
        assert not fa.flash_wins(197)    # below span: threshold says no
        assert fa.flash_wins(32768)      # above span: memory regime
        # an above-span length below the threshold stays naive
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE", ((1024, True), (2048, True)))
        assert not fa.flash_wins(4096)

    def test_trailing_loss_carries_above_span(self, monkeypatch):
        """ADVICE r5: lengths just above the table's last row inherit a
        trailing LOSS (16385..32767 must not route to the kernel that
        measured 0.795x at 16384) until the memory-regime bound, where
        naive's O(T^2) scores stop being feasible and the threshold
        gate takes back over."""
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 16384)
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE",
            ((2048, True), (8192, True), (16384, False)))
        assert not fa.flash_wins(16385)            # inherits the loss
        assert not fa.flash_wins(24576)
        assert not fa.flash_wins(fa.MEM_REGIME_MIN_T - 1)
        assert fa.flash_wins(fa.MEM_REGIME_MIN_T)  # naive infeasible
        # a trailing WIN still defers to the threshold (non-monotonic
        # hardware: 2k winning says nothing about 4k)
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE", ((1024, True), (2048, True)))
        assert not fa.flash_wins(4096)

    def test_env_override_beats_win_table(self, monkeypatch):
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        monkeypatch.setattr(
            tuned, "FLASH_WIN_TABLE", ((2048, False), (8192, False)))
        monkeypatch.setenv("NNS_TPU_FLASH_MIN_T", "1024")
        assert fa.flash_wins(2048)   # operator override wins over data

    def test_malformed_env_override_warns_and_falls_through(
            self, monkeypatch):
        import warnings

        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setenv("NNS_TPU_FLASH_MIN_T", "16k")
        monkeypatch.setattr(tuned, "FLASH_MIN_T", 4096)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            # malformed override is ignored; the measured record wins
            assert fa.flash_min_t() == 4096
        assert any("NNS_TPU_FLASH_MIN_T" in str(w.message) for w in caught)

    def test_ulysses_training_path_keeps_kernel(self, monkeypatch):
        """The seq-parallel training core must NOT be length-gated: the
        kernel's O(T*d) backward residuals are the design (naive
        autodiff saves (H, T, T) probabilities per layer)."""
        import inspect

        from nnstreamer_tpu.parallel import ulysses

        src = inspect.getsource(ulysses.ulysses_attention)
        assert "flash_is_default" in src and "flash_wins(" not in src

    def test_vit_attention_defaults_to_naive_below_crossover(
            self, monkeypatch):
        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        """A TPU-resident ViT (T=197) must take the naive path under the
        gate: the kernel would be selected only above the crossover."""
        import nnstreamer_tpu.ops.flash_attention as fa
        from nnstreamer_tpu.models import vit as vit_mod

        monkeypatch.setattr(fa, "flash_is_default", lambda: True)

        called = {"flash": False}
        real = fa.flash_attention

        def spy(*a, **kw):
            called["flash"] = True
            return real(*a, **kw, interpret=True)

        monkeypatch.setattr(fa, "flash_attention", spy)
        model = vit_mod.ViT(num_classes=10, depth=1, dim=64, heads=2,
                            patch=16, dtype=jnp.float32)
        x = np.zeros((32, 32, 3), np.float32)
        params = model.init(jax.random.PRNGKey(0), x)
        model.apply(params, x)
        assert not called["flash"], "vit below crossover selected kernel"

    def test_lm_prefill_defaults_to_naive_below_crossover(
            self, monkeypatch):
        monkeypatch.delenv("NNS_TPU_FLASH_MIN_T", raising=False)
        import nnstreamer_tpu.ops.flash_attention as fa
        from nnstreamer_tpu.models.streamformer_lm import forward_logits
        from nnstreamer_tpu.parallel.train_step import (StreamFormerConfig,
                                                        init_params)

        monkeypatch.setattr(fa, "flash_is_default", lambda: True)
        called = {"flash": False}
        real = fa.flash_attention

        def spy(*a, **kw):
            called["flash"] = True
            return real(*a, **kw, interpret=True)

        monkeypatch.setattr(fa, "flash_attention", spy)
        cfg = StreamFormerConfig(vocab=64, dim=32, heads=2, head_dim=16,
                                 mlp=64, layers=1, experts=1, max_seq=64,
                                 dtype=jnp.float32)
        params = init_params(cfg, 0)
        toks = jnp.zeros((16,), jnp.int32)
        forward_logits(params, toks, cfg)
        assert not called["flash"], "short prefill selected kernel"


class TestTunedTileDefaults:
    """Tile defaults follow measured tune data (utils/tuned.py
    FLASH_TILES) for long sequences; short inputs keep 128x128 so they
    don't pad up to a giant tuned tile."""

    def test_short_sequences_keep_mxu_default(self, monkeypatch):
        from nnstreamer_tpu.ops.flash_attention import _default_tiles
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setattr(tuned, "FLASH_TILES", (512, 1024))
        assert _default_tiles(197, 197, interpret=False) == (128, 128)

    def test_long_sequences_use_tuned(self, monkeypatch):
        from nnstreamer_tpu.ops.flash_attention import _default_tiles
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setattr(tuned, "FLASH_TILES", (256, 512))
        assert _default_tiles(8192, 8192, interpret=False) == (256, 512)

    def test_interpret_ignores_tuned(self, monkeypatch):
        from nnstreamer_tpu.ops.flash_attention import _default_tiles
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setattr(tuned, "FLASH_TILES", (512, 512))
        assert _default_tiles(8192, 8192, interpret=True) == (128, 128)

    def test_by_t_record_routes_per_length(self, monkeypatch):
        """The per-length tile record (the tune step's 8k AND 16k
        sweeps) takes precedence: the largest measured length <= the
        sequence wins; lengths below every row fall back to the legacy
        record / MXU default."""
        from nnstreamer_tpu.ops import flash_attention as fa
        from nnstreamer_tpu.utils import tuned

        monkeypatch.setattr(tuned, "FLASH_TILES", (128, 128))
        monkeypatch.setattr(tuned, "FLASH_TILES_BY_T",
                            ((8192, 256, 256), (16384, 256, 512)))
        assert fa._default_tiles(8192, 8192, interpret=False) \
            == (256, 256)
        assert fa._default_tiles(16384, 16384, interpret=False) \
            == (256, 512)
        # beyond the largest measured length: its tiles extend
        assert fa._default_tiles(32768, 32768, interpret=False) \
            == (256, 512)
        # between rows: the largest measured length below wins
        assert fa._default_tiles(12288, 12288, interpret=False) \
            == (256, 256)
        # below every row: legacy/MXU default (2k measured a WIN at
        # (128,128) — don't disturb it)
        assert fa._default_tiles(2048, 2048, interpret=False) \
            == (128, 128)
        # a q block too small for a row's tile falls down the list
        assert fa._default_tiles(64, 32768, interpret=False) \
            == (128, 128)
        # interpret has no tuned data
        assert fa._default_tiles(16384, 16384, interpret=True) \
            == (128, 128)

    def test_long_tiles_interpret_correctness_and_grad(self):
        """The asymmetric long-T tune candidate (256, 512) must be
        numerically correct through forward AND backward with MULTIPLE
        K blocks and a padded tail (interpret validates the tile
        plumbing; VMEM feasibility at depth is the on-chip tune
        gradcheck's job)."""
        t, h, d = 1088, 1, 32   # pads to 1536: 3 K blocks, masked tail
        q, k, v = _qkv(t, h, d, seed=77)
        bq, bk = 256, 512
        got = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=True)
        want = flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-5)

        def loss(fn_blocks, q, k, v):
            bq_, bk_ = fn_blocks
            return jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=bq_, block_k=bk_,
                interpret=True) ** 2)

        import functools
        g_long = jax.grad(functools.partial(loss, (bq, bk)),
                          argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(functools.partial(loss, (128, 128)),
                         argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_long, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=1e-4)

    def test_explicit_blocks_still_win(self):
        # callers passing block_q/block_k keep exact control (the tests
        # above all pass explicit tiles; spot-check the plumbing)
        q, k, v = _qkv(64, 2, 16, seed=12)
        a = flash_attention(q, k, v, block_q=16, block_k=16,
                            interpret=True)
        b = flash_attention(q, k, v, interpret=True)  # default tiles
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-5)

    def test_apply_rewrites_flash_tiles(self, tmp_path):
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import flash_tpu_bench as tool

        artifact = tmp_path / "tune.json"
        artifact.write_text(json.dumps({
            "metric": "flash_tile_tune", "value": 1.31,
            "best": {"block_q": 256, "block_k": 512, "ms": 4.2},
            "grad_ok": True,
            "default_ms": 5.5, "device": "TPU_0"}) + "\n")
        tuned_copy = tmp_path / "tuned.py"
        src = open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "nnstreamer_tpu", "utils",
            "tuned.py")).read()
        tuned_copy.write_text(src)
        rc = tool.apply_tiles_from_artifact(str(artifact),
                                            tuned_path=str(tuned_copy))
        assert rc == 0
        new = tuned_copy.read_text()
        assert "FLASH_TILES = (256, 512)" in new
        assert "tune.json" in new
        compile(new, "tuned.py", "exec")
        # idempotent re-apply
        assert tool.apply_tiles_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0

    def test_apply_multilength_tune_writes_by_t(self, tmp_path):
        """A two-length tune artifact ships a FLASH_TILES_BY_T row per
        valid length; the legacy FLASH_TILES record follows the first
        length's winner."""
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import flash_tpu_bench as tool

        artifact = tmp_path / "tune2.json"
        artifact.write_text(json.dumps({
            "metric": "flash_tile_tune", "value": 1.8,
            "best": {"block_q": 256, "block_k": 256, "ms": 10.0},
            "grad_ok": True, "default_ms": 15.0,
            "lengths": [
                {"t": 8192, "best": {"block_q": 256, "block_k": 256,
                                     "ms": 10.0},
                 "grad_ok": True, "default_ms": 15.0, "speedup": 1.5},
                {"t": 16384, "best": {"block_q": 256, "block_k": 512,
                                      "ms": 30.0},
                 "grad_ok": True, "default_ms": 54.0, "speedup": 1.8},
            ], "device": "TPU_0"}) + "\n")
        tuned_copy = tmp_path / "tuned.py"
        tuned_copy.write_text(open(os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "nnstreamer_tpu",
            "utils", "tuned.py")).read())
        assert tool.apply_tiles_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0
        new = tuned_copy.read_text()
        assert ("FLASH_TILES_BY_T = "
                "((8192,256,256),(16384,256,512),)") in new
        assert "FLASH_TILES = (256, 256)" in new
        assert "tune2.json" in new
        compile(new, "tuned.py", "exec")
        # idempotent re-apply
        assert tool.apply_tiles_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0

    def test_apply_multilength_skips_gradfailed_length(self, tmp_path):
        """A length whose winner failed its gradcheck must not ship —
        but it must not block the other length's valid row either."""
        import json
        import os
        import re
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import flash_tpu_bench as tool

        artifact = tmp_path / "tune3.json"
        artifact.write_text(json.dumps({
            "metric": "flash_tile_tune", "value": 1.8,
            "best": {"block_q": 512, "block_k": 1024, "ms": 9.0},
            "grad_ok": False, "default_ms": 15.0,
            "lengths": [
                {"t": 8192, "best": {"block_q": 512, "block_k": 1024,
                                     "ms": 9.0, "grad_error": "VMEM"},
                 "grad_ok": False, "default_ms": 15.0, "speedup": 1.7},
                {"t": 16384, "best": {"block_q": 256, "block_k": 512,
                                      "ms": 30.0},
                 "grad_ok": True, "default_ms": 54.0, "speedup": 1.8},
            ], "device": "TPU_0"}) + "\n")
        tuned_copy = tmp_path / "tuned.py"
        src = open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "nnstreamer_tpu", "utils",
            "tuned.py")).read()
        tuned_copy.write_text(src)
        tiles_line = re.search(r"FLASH_TILES = \(\d+, \d+\)",
                               src).group(0)
        assert tool.apply_tiles_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0
        new = tuned_copy.read_text()
        assert "FLASH_TILES_BY_T = ((16384,256,512),)" in new
        # first length invalid -> legacy record untouched
        assert tiles_line in new
        compile(new, "tuned.py", "exec")

    def test_apply_refuses_tune_without_baseline_or_gradcheck(
            self, tmp_path):
        import json
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import flash_tpu_bench as tool

        # missing 128x128 baseline
        a1 = tmp_path / "nobase.json"
        a1.write_text(json.dumps({
            "metric": "flash_tile_tune", "value": 1.0,
            "best": {"block_q": 512, "block_k": 512, "ms": 4.0},
            "grad_ok": True, "default_ms": None}) + "\n")
        safe = tmp_path / "tuned_copy.py"
        safe.write_text(open(os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "nnstreamer_tpu",
            "utils", "tuned.py")).read())
        assert tool.apply_tiles_from_artifact(
            str(a1), tuned_path=str(safe)) == 1
        # gradient check failed/absent: the tile must not become the
        # custom_vjp default
        a2 = tmp_path / "nograd.json"
        a2.write_text(json.dumps({
            "metric": "flash_tile_tune", "value": 1.2,
            "best": {"block_q": 1024, "block_k": 1024, "ms": 3.0},
            "grad_ok": False, "default_ms": 3.6}) + "\n")
        assert tool.apply_tiles_from_artifact(
            str(a2), tuned_path=str(safe)) == 1
        # the refusals really were refusals: record untouched
        assert safe.read_text() == open(os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "nnstreamer_tpu",
            "utils", "tuned.py")).read()


class TestMeasuredCrossover:
    """Suffix-win crossover semantics + the --apply-crossover path that
    turns a green proof capture into the FLASH_MIN_T tuned record."""

    def _tool(self):
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        import flash_tpu_bench as tool
        return tool

    def test_suffix_win_skips_interior_loss(self):
        # 2k wins but 16k loses: a threshold gate derived from "first
        # win" would route 16k to the slower kernel — suffix-win
        # reports the length where wins become unbroken (32k, naive
        # genuinely out of memory there)
        tool = self._tool()
        timings = [
            {"T": 2048, "speedup": 1.365},
            {"T": 8192, "speedup": 1.011},
            {"T": 16384, "speedup": 0.795},
            {"T": 32768, "flash_only": True,
             "naive_error": "RESOURCE_EXHAUSTED: ..."},
        ]
        assert tool.measured_crossover(timings) == 32768

    def test_unbroken_wins_reach_back(self):
        tool = self._tool()
        timings = [
            {"T": 2048, "speedup": 0.9},
            {"T": 8192, "speedup": 1.1},
            {"T": 16384, "speedup": 1.2},
            {"T": 32768, "flash_only": True,
             "naive_error": "out of memory allocating scores"},
        ]
        assert tool.measured_crossover(timings) == 8192

    def test_kernel_error_breaks_suffix(self):
        tool = self._tool()
        timings = [
            {"T": 8192, "speedup": 1.1},
            {"T": 16384, "error": "Mosaic..."},
            {"T": 32768, "flash_only": True,
             "naive_error": "RESOURCE_EXHAUSTED"},
        ]
        assert tool.measured_crossover(timings) == 32768

    def test_win_table_classification(self):
        """measured_win_table: speedup>1 or naive capacity failure →
        win; kernel error → loss (naive must serve that length); naive
        infra flake → no row."""
        tool = self._tool()
        timings = [
            {"T": 2048, "speedup": 1.365},
            {"T": 8192, "speedup": 1.011},
            {"T": 12288, "error": "Mosaic compile failure"},
            {"T": 16384, "speedup": 0.795},
            {"T": 24576, "flash_only": True,
             "naive_error": "HTTP 500: tpu_compile_helper"},
            {"T": 32768, "flash_only": True,
             "naive_error": "RESOURCE_EXHAUSTED"},
        ]
        assert tool.measured_win_table(timings) == (
            (2048, True), (8192, True), (12288, False),
            (16384, False), (32768, True))

    def test_all_losses_is_none(self):
        tool = self._tool()
        assert tool.measured_crossover(
            [{"T": 2048, "speedup": 0.8},
             {"T": 8192, "speedup": 0.95}]) is None

    def test_transient_naive_infra_error_is_not_a_win(self):
        # a naive failure that reads like an HTTP 500 from a compile
        # helper is a flake, not the O(T^2) capacity wall.  Such rows
        # are evidence-free: they
        # must neither extend the win suffix (here: 16k loses, so no
        # crossover) nor break it.
        tool = self._tool()
        timings = [
            {"T": 8192, "speedup": 1.011},
            {"T": 16384, "speedup": 0.795},
            {"T": 32768, "flash_only": True,
             "naive_error": "JaxRuntimeError('INTERNAL: http://...: "
                            "HTTP 500: tpu_compile_helper subprocess "
                            "exit code 1')"},
        ]
        assert tool.measured_crossover(timings) is None
        # ...and with the interior loss absent, the flake is skipped
        # but the definite wins below still anchor the crossover
        timings2 = [
            {"T": 8192, "speedup": 1.011},
            {"T": 16384, "speedup": 1.2},
            {"T": 32768, "flash_only": True,
             "naive_error": "HTTP 500: tpu_compile_helper"},
        ]
        assert tool.measured_crossover(timings2) == 8192

    def test_transient_kernel_infra_error_is_no_evidence(self):
        """Kernel-side failures get the SAME infra-vs-device triage as
        naive-side ones — a connection flake during the kernel run is
        evidence-free (no durable wins=False row, no broken
        suffix), while a real kernel failure stays a durable loss."""
        tool = self._tool()
        flake = {"T": 16384,
                 "error": "ConnectionError('connection reset by peer')"}
        assert tool._row_evidence(flake)[0] is None
        timings = [
            {"T": 2048, "speedup": 1.2},
            {"T": 8192, "speedup": 1.1},
            flake,
            {"T": 32768, "flash_only": True,
             "naive_error": "RESOURCE_EXHAUSTED"},
        ]
        # the flake neither breaks the win suffix nor lands in the table
        assert tool.measured_crossover(timings) == 2048
        assert tool.measured_win_table(timings) == (
            (2048, True), (8192, True), (32768, True))
        # a deterministic kernel failure is still a durable loss
        hard = {"T": 16384, "error": "Mosaic lowering failed: ..."}
        assert tool._row_evidence(hard)[0] is False
        assert tool.measured_crossover(
            [{"T": 8192, "speedup": 1.1}, hard,
             {"T": 32768, "flash_only": True,
              "naive_error": "RESOURCE_EXHAUSTED"}]) == 32768

    def _proof_row(self, **over):
        row = {"metric": "flash_attention_tpu_proof", "value": 1.0,
               "unit": "x_vs_naive", "ok": True, "crossover_T": 2048,
               "timings": [{"T": 2048, "speedup": 1.365},
                           {"T": 8192, "speedup": 1.011},
                           {"T": 32768, "flash_only": True,
                            "naive_error": "RESOURCE_EXHAUSTED"}],
               "device": "TPU_0"}
        row.update(over)
        return row

    def _tuned_copy(self, tmp_path):
        import os

        src = open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "nnstreamer_tpu", "utils",
            "tuned.py")).read()
        p = tmp_path / "tuned.py"
        p.write_text(src)
        return p

    def test_apply_crossover_rewrites_min_t(self, tmp_path):
        import json

        tool = self._tool()
        artifact = tmp_path / "proof.json"
        artifact.write_text(json.dumps(self._proof_row()) + "\n")
        tuned_copy = self._tuned_copy(tmp_path)
        assert tool.apply_crossover_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0
        new = tuned_copy.read_text()
        assert "FLASH_MIN_T = 2048" in new
        # the same apply writes the per-length evidence table
        assert ("FLASH_WIN_TABLE = "
                "((2048,True),(8192,True),(32768,True),)") in new
        assert "proof.json" in new
        compile(new, "tuned.py", "exec")
        # idempotent re-apply (the loop re-runs it every iteration)
        assert tool.apply_crossover_from_artifact(
            str(artifact), tuned_path=str(tuned_copy)) == 0

    def test_apply_gates_on_checks_ok_not_timing_survival(self, tmp_path):
        """A kernel error while TIMING a length fails the proof's
        overall `ok` but is itself evidence (a loss at that length);
        with the correctness/grad checks green (checks_ok), the apply
        must persist the capture's evidence — including the loss row —
        instead of refusing the whole window."""
        import json
        import re

        tool = self._tool()
        tuned_copy = self._tuned_copy(tmp_path)
        min_t_line = re.search(
            r"FLASH_MIN_T = \d+", tuned_copy.read_text()).group(0)
        a = tmp_path / "timingerr.json"
        a.write_text(json.dumps(self._proof_row(
            ok=False, checks_ok=True,
            timings=[{"T": 2048, "speedup": 1.2},
                     {"T": 16384, "error": "Mosaic compile failure"}]))
            + "\n")
        assert tool.apply_crossover_from_artifact(
            str(a), tuned_path=str(tuned_copy)) == 0
        new = tuned_copy.read_text()
        assert "FLASH_WIN_TABLE = ((2048,True),(16384,False),)" in new
        assert "16384:kernel-error" in new
        # the loss breaks the win suffix: threshold untouched
        assert min_t_line in new
        compile(new, "tuned.py", "exec")

    def test_apply_is_atomic_when_threshold_rewrite_fails(self, tmp_path):
        """Both records land in one write: if the FLASH_MIN_T rewrite
        cannot match (mangled record), the already-computed win table
        must NOT have been written either."""
        import json
        import re

        tool = self._tool()
        tuned_copy = self._tuned_copy(tmp_path)
        mangled = re.sub(r"FLASH_MIN_T = \d+", "FLASH_MIN_T = None",
                         tuned_copy.read_text())
        tuned_copy.write_text(mangled)
        a = tmp_path / "proof.json"
        a.write_text(json.dumps(self._proof_row()) + "\n")
        assert tool.apply_crossover_from_artifact(
            str(a), tuned_path=str(tuned_copy)) == 1
        assert tuned_copy.read_text() == mangled

    def test_apply_crossover_refuses_not_ok_keeps_threshold_on_null(
            self, tmp_path):
        import json
        import re

        tool = self._tool()
        tuned_copy = self._tuned_copy(tmp_path)
        before = tuned_copy.read_text()
        min_t_line = re.search(r"FLASH_MIN_T = \d+", before).group(0)
        # a run whose kernel mis-computed must not set any default
        a1 = tmp_path / "notok.json"
        a1.write_text(json.dumps(self._proof_row(ok=False)) + "\n")
        assert tool.apply_crossover_from_artifact(
            str(a1), tuned_path=str(tuned_copy)) == 1
        assert tuned_copy.read_text() == before
        # kernel lost at every measured length: no unbroken win suffix,
        # so the fallback THRESHOLD stands (crossover recomputed from
        # timings, not the stored field) — but the losses are still
        # evidence, and the win table pins those lengths to naive
        a2 = tmp_path / "nullx.json"
        a2.write_text(json.dumps(self._proof_row(
            crossover_T=2048,
            timings=[{"T": 2048, "speedup": 0.8},
                     {"T": 8192, "speedup": 0.9}])) + "\n")
        assert tool.apply_crossover_from_artifact(
            str(a2), tuned_path=str(tuned_copy)) == 0
        new = tuned_copy.read_text()
        assert min_t_line in new
        assert "FLASH_WIN_TABLE = ((2048,False),(8192,False),)" in new
        assert "nullx.json" in new
        compile(new, "tuned.py", "exec")
