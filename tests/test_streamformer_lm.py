"""StreamFormer LM serving: KV-cache consistency, training-forward parity,
generation, and the pipeline filter registration."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import shard_map

from nnstreamer_tpu.models.streamformer_lm import (decode_step,
                                                   forward_logits, generate,
                                                   init_cache)
from nnstreamer_tpu.parallel.train_step import (StreamFormerConfig,
                                                init_params)


def _cfg(**kw):
    base = dict(vocab=61, dim=32, heads=4, head_dim=8, mlp=64, layers=2,
                experts=2, max_seq=32, dtype=jnp.float32,
                capacity_factor=8.0)
    base.update(kw)
    return StreamFormerConfig(**base)


class TestKVCache:
    def test_incremental_matches_full_forward(self):
        """Teacher forcing: the logits the cache path emits at position i
        equal row i of the full-sequence forward."""
        cfg = _cfg()
        params = init_params(cfg, seed=1)
        toks = np.random.default_rng(0).integers(0, cfg.vocab, 16)
        toks = jnp.asarray(toks, jnp.int32)

        full = forward_logits(params, toks, cfg)

        cache = init_cache(cfg)
        rows = []
        for t in toks:
            logits, cache = decode_step(params, cache, t, cfg)
            rows.append(logits)
        inc = jnp.stack(rows)
        np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                                   atol=1e-4, rtol=1e-4)

    def test_cache_position_advances(self):
        cfg = _cfg()
        params = init_params(cfg, seed=0)
        cache = init_cache(cfg)
        _, cache = decode_step(params, cache, jnp.int32(3), cfg)
        _, cache = decode_step(params, cache, jnp.int32(4), cfg)
        assert int(cache["pos"]) == 2


class TestTrainingParity:
    def test_full_forward_matches_training_forward(self, jax_cpu_devices):
        """Serving forward == the sharded training forward on a 1-device
        mesh (same params, same math; capacity high so no MoE drops)."""
        from jax.sharding import Mesh, PartitionSpec as P

        from nnstreamer_tpu.parallel.train_step import _forward_local

        cfg = _cfg()
        params = init_params(cfg, seed=2)
        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab, (1, 16)),
            jnp.int32)

        mesh = Mesh(np.array(jax_cpu_devices[:1]).reshape(1, 1, 1, 1),
                    ("dp", "sp", "tp", "ep"))
        fn = shard_map(
            lambda p, t: _forward_local(p, t, cfg)[0],
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)
        train_logits = fn(params, toks)[0]
        serve_logits = forward_logits(params, toks[0], cfg)
        np.testing.assert_allclose(np.asarray(serve_logits),
                                   np.asarray(train_logits),
                                   atol=2e-3, rtol=2e-3)


class TestGenerate:
    def test_greedy_deterministic(self):
        cfg = _cfg()
        params = init_params(cfg, seed=3)
        prompt = np.array([1, 2, 3], np.int32)
        a = generate(params, cfg, prompt, 8)
        b = generate(params, cfg, prompt, 8)
        assert a.shape == (8,)
        np.testing.assert_array_equal(a, b)
        assert a.min() >= 0 and a.max() < cfg.vocab

    def test_overflow_guard(self):
        cfg = _cfg()
        params = init_params(cfg, seed=0)
        with pytest.raises(ValueError, match="max_seq"):
            generate(params, cfg, np.arange(30, dtype=np.int32), 10)

    def test_sampled_runs(self):
        cfg = _cfg()
        params = init_params(cfg, seed=3)
        out = generate(params, cfg, np.array([5], np.int32), 6,
                       temperature=1.0, seed=4)
        assert out.shape == (6,)

    def test_greedy_matches_step_loop(self):
        """generate()'s fused scan == hand-rolled decode_step loop."""
        cfg = _cfg()
        params = init_params(cfg, seed=5)
        prompt = np.array([7, 8], np.int32)
        fused = generate(params, cfg, prompt, 5)

        cache = init_cache(cfg)
        logits = None
        for t in prompt:
            logits, cache = decode_step(params, cache, jnp.int32(t), cfg)
        manual = []
        for _ in range(5):
            tok = jnp.argmax(logits).astype(jnp.int32)
            manual.append(int(tok))
            logits, cache = decode_step(params, cache, tok, cfg)
        np.testing.assert_array_equal(fused, np.array(manual))


class TestPipelineFilter:
    def test_streamformer_lm_as_tensor_filter(self):
        """Token stream through the pipeline: (T,) int32 frames in,
        (T, vocab) logits out — LM inference as a stream element."""
        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.tensor.buffer import TensorBuffer

        got = []
        caps = ("other/tensors,format=static,num_tensors=1,dimensions=16,"
                "types=int32,framerate=0/1")
        p = parse_launch(
            f"appsrc name=src caps={caps} ! "
            "tensor_filter framework=xla model=streamformer_lm "
            "custom=seq:16,vocab:61,dim:32,dtype:float32 ! "
            "tensor_sink name=out")
        p.get("out").connect("new-data", lambda b: got.append(
            np.asarray(b.tensors[0]).copy()))
        p.play()
        toks = np.random.default_rng(2).integers(0, 61, 16).astype(np.int32)
        p.get("src").push_buffer(TensorBuffer(tensors=[toks]))
        p.get("src").end_of_stream()
        p.wait(timeout=120)
        p.stop()
        assert len(got) == 1
        out = got[0]
        assert out.shape == (16, 61), out.shape
        # the filter's logits equal the module's forward on the same toks
        from nnstreamer_tpu.models.registry import get_model

        model = get_model("streamformer_lm",
                          {"seq": "16", "vocab": "61", "dim": "32",
                           "dtype": "float32", "seed": "0"})
        ref = np.asarray(model.forward(model.params,
                                       jnp.asarray(toks))[0])
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_forward_flash_matches_naive():
    """The Pallas-flash prefill path equals the naive attention path."""
    cfg = _cfg()
    params = init_params(cfg, seed=7)
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab, 16), jnp.int32)
    naive = forward_logits(params, toks, cfg, flash=False)
    flashed = forward_logits(params, toks, cfg, flash=True)
    np.testing.assert_allclose(np.asarray(flashed), np.asarray(naive),
                               atol=1e-3, rtol=1e-3)


def test_decode_step_vmaps_over_streams():
    """Serving N independent token streams = one vmap over (cache, token)
    with shared params — each lane advances its own KV cache."""
    cfg = _cfg()
    params = init_params(cfg, seed=9)
    n = 3
    caches = jax.vmap(lambda _: init_cache(cfg))(jnp.arange(n))
    toks = jnp.asarray([5, 17, 42], jnp.int32)

    step = jax.vmap(lambda c, t: decode_step(params, c, t, cfg))
    logits, caches = step(caches, toks)
    assert logits.shape == (n, cfg.vocab)
    assert caches["pos"].tolist() == [1, 1, 1]

    # lane i equals a solo decode of the same token
    solo, _ = decode_step(params, init_cache(cfg), jnp.int32(17), cfg)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(solo),
                               atol=1e-5, rtol=1e-5)
