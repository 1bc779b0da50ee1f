"""The plain float32 references agree with the program's forward passes
in float32 at small sizes, on the same seeded weights."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.reference import mobilenet_v2 as ref_mnv2  # noqa: E402
from benchmarks.reference import streamformer_lm as ref_lm  # noqa: E402

CUSTOM = ("vocab:61,dim:32,heads:4,head_dim:8,mlp:64,layers:3,experts:2,"
          "max_seq:64,dtype:float32")


@pytest.fixture(scope="module")
def lm():
    import jax

    from nnstreamer_tpu.filter.framework import FilterProperties
    from nnstreamer_tpu.models.streamformer_lm import config_from_custom
    from nnstreamer_tpu.parallel.train_step import init_params

    cfg = config_from_custom(FilterProperties.parse_custom(CUSTOM))
    # init's 0.02 scale makes every branch a whisper; at 20 x that the
    # attention, both experts and the gate all move the logits
    params = jax.tree_util.tree_map(
        lambda a: a * 20 if a.ndim > 1 else a, init_params(cfg, 3))
    return cfg, params


def test_lm_reference_equals_forward_logits_in_float32(lm):
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.streamformer_lm import forward_logits

    cfg, params = lm
    tokens = np.random.default_rng(0).integers(0, 61, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward_logits(params, jnp.asarray(tokens), cfg,
                                         flash=False))
    got = ref_lm.forward_logits(params, tokens, cfg.head_dim)
    assert got.shape == want.shape == (40, 61)
    assert np.abs(want).max() > 1.0               # the logits are not flat
    # float32 against float32: rounding order only
    assert np.abs(got - want).max() < 1e-4


def test_lm_reference_judges_a_served_stream(lm):
    from nnstreamer_tpu.models.streamformer_lm import generate

    cfg, params = lm
    prompt = np.random.default_rng(1).integers(0, 61, 9).astype(np.int32)
    served = generate(params, cfg, prompt, 12)     # the program, greedy
    model = {"head_dim": cfg.head_dim, "max_seq": 64}
    got = ref_lm.served_tokens_near_top(params, model, prompt, served,
                                        slack=1e-3)
    assert got == {"tokens": 12, "near_top": 12, "exact": 12}
    # a stream that did not come from these weights is told apart
    wrong = (np.asarray(served) + 1) % 61
    bad = ref_lm.served_tokens_near_top(params, model, prompt, wrong,
                                        slack=1e-3)
    assert bad["near_top"] < 6


def test_mobilenet_reference_equals_the_program_in_float32():
    import jax

    from nnstreamer_tpu.models.registry import get_model

    model = get_model("mobilenet_v2", {"seed": "3", "dtype": "float32",
                                       "input_size": "96"})
    frame = np.random.default_rng(0).integers(0, 256, (96, 96, 3),
                                              dtype=np.uint8)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.forward(model.params, frame)[0])
    got = ref_mnv2.forward_logits(model.params, frame)
    assert got.shape == want.shape == (1001,)
    span = float(want.max() - want.min())
    assert span > 0 and np.abs(got - want).max() < 1e-4 * span


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_one_precision_lower_reads_under_the_toys_limit(
        lm, seed):
    """The control of the token cells' comparison, at a size a test can
    hold: the same model with its weights rounded to the precision below
    the one the (float32) toy states, bfloat16, in the program's place.
    Its greedy streams, judged by the float32 reference, fall under the
    toy's limit (every token within 1e-3 of the top: share 1.0) on every
    seed, where the program's own streams read exactly 1.0.  At the
    cells' own sizes the control is float8-rounded weights under
    bfloat16 serving, read on the chip (PERF.md section 6, PR 29)."""
    import jax
    import jax.numpy as jnp

    from nnstreamer_tpu.models.streamformer_lm import generate

    cfg, params = lm
    lower = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    model = {"head_dim": cfg.head_dim, "max_seq": 64}
    share = {}
    for name, weights in (("program", params), ("control", lower)):
        total = {"tokens": 0, "near_top": 0}
        for k in range(4):
            prompt = np.random.default_rng([seed, k]).integers(
                0, 61, 9).astype(np.int32)
            got = ref_lm.served_tokens_near_top(
                params, model, prompt, generate(weights, cfg, prompt, 40),
                slack=1e-3)
            for key in total:
                total[key] += got[key]
        assert total["tokens"] == 160
        share[name] = total["near_top"] / total["tokens"]
    assert share["program"] == 1.0
    assert share["control"] < 1.0
