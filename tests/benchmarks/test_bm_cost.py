"""The shape functions against operations and bytes counted by hand."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.cost import mobilenet_v2 as mnv2  # noqa: E402
from benchmarks.cost import streamformer_lm as sflm  # noqa: E402
from benchmarks.cost.roofline import least_seconds  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
#: small enough to count on paper: d=4, h=2, hd=2, f=8, e=2, L=3, V=10
TINY = {"vocab": 10, "dim": 4, "heads": 2, "head_dim": 2, "mlp": 8,
        "layers": 3, "experts": 2, "max_seq": 16, "dtype": "bfloat16"}


def gpt2m():
    return Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(
        "sflm_gpt2m")["model"]


def test_gpt2m_parameter_count_by_hand_and_by_the_program():
    model = gpt2m()
    # per layer: qkv 3 x 1024^2, out 1024^2, two 1024x4096 MLP matrices,
    # the gate 1024x2, two experts of two such matrices, two norm scales
    per_layer = (3 * 1024 ** 2 + 1024 ** 2 + 2 * 1024 * 4096 + 1024 * 2
                 + 2 * 2 * 1024 * 4096 + 2 * 1024)
    total = (24 * per_layer + 2 * 50257 * 1024 + 1024 * 1024 + 1024)
    assert total == 808_717_312
    assert sflm.total_params(model) == total
    # the program's own tree, from shapes alone (nothing is allocated)
    import jax

    from nnstreamer_tpu.filter.framework import FilterProperties
    from nnstreamer_tpu.models.streamformer_lm import config_from_custom
    from nnstreamer_tpu.parallel.train_step import init_params

    custom = ",".join(f"{k}:{v}" for k, v in model.items())
    cfg = config_from_custom(FilterProperties.parse_custom(custom))
    leaves = jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(cfg, 0)))
    assert sum(x.size for x in leaves) == total
    plan = Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(
        "sflm_gpt2m")["memory_plan"]
    assert plan["weights_bytes"] == 4 * total        # held in float32


def test_tiny_decode_step_cost_by_hand():
    # one token, matrices only: per layer 2 x (qkv 48 + out 16 + mlp 64
    # + gate 8 + ONE expert 64) = 400; head 2 x 4 x 10 = 80
    token = 3 * 400 + 80
    lanes, attended = 2, 7
    flops, nbytes = sflm.decode_step_cost(TINY, lanes, attended)
    # attention: QK and PV, 2 x heads x head_dim each, per attended key
    assert flops == lanes * token + 3 * 4 * 2 * 2 * attended
    weights = (3 * (48 + 16 + 32 + 32 + 8 + 2 * 64 + 8) * 2  # bf16 layers
               + 4 * 10 * 4                                  # f32 head
               + 2 * lanes * 4 * 2)        # an embedding + a position row
    kv = 3 * 2 * 2 * 2 * (attended + lanes) * 2
    assert nbytes == weights + kv + lanes * 10 * 4
    # one lane can reach one expert only
    _, one = sflm.decode_step_cost(TINY, 1, 0)
    _, two = sflm.decode_step_cost(TINY, 2, 0)
    per_lane = 2 * 4 * 2 + 3 * 2 * 2 * 2 * 2 + 10 * 4
    assert two - one == 3 * 64 * 2 + per_lane


def test_tiny_prefill_cost_by_hand():
    t = 5
    flops, nbytes = sflm.prefill_cost(TINY, t)
    pairs = 15                                   # 1 + 2 + 3 + 4 + 5
    assert flops == t * 3 * 400 + 3 * 4 * 2 * 2 * pairs + 80
    weights = (3 * (48 + 16 + 32 + 32 + 8 + 2 * 64 + 8) * 2 + 4 * 10 * 4
               + 2 * t * 4 * 2)
    assert nbytes == weights + 3 * 2 * 2 * 2 * t * 2 + 10 * 4


def test_kv_bytes_per_position_by_hand():
    # keys and values, 3 layers x 2 heads x 2 wide, bf16
    assert sflm.kv_bytes_per_position(TINY) == 3 * 2 * 2 * 2 * 2
    # GPT-2 medium's widths: 24 x 2 x 16 x 64 x 2 B, and the pool of the
    # configuration's memory plan is 33 slots of 1024 such positions
    assert sflm.kv_bytes_per_position(gpt2m()) == 98_304
    plan = Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(
        "sflm_gpt2m")["memory_plan"]
    assert plan["kv_pool_bytes"] == 33 * 1024 * 98_304


def test_gpt2m_decode_step_is_bound_by_bytes():
    flops, nbytes = sflm.decode_step_cost(gpt2m(), 32, 32 * 150)
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "bytes"
    assert least == pytest.approx(nbytes / 819e9)
    # both experts, bf16: 705 M layer weights x 2 B, plus the f32 head
    assert 1.6e9 < nbytes < 2.4e9
    # a 1024-token prefill does 1024 tokens' products on the same
    # weights: bound by operations
    flops, nbytes = sflm.prefill_cost(gpt2m(), 1024)
    assert least_seconds(flops, nbytes, PEAKS)[1] == "flops"


MNV2 = {"input_size": 224, "num_classes": 1001, "dtype": "bfloat16"}


def test_mobilenet_layer_table_by_hand():
    layers = mnv2.conv_layers(MNV2)
    # 1 stem + (2 + 16 x 3) block convolutions + 1 head conv + classifier
    assert len(layers) == 1 + 2 + 16 * 3 + 1 + 1
    stem = layers[0]
    assert (stem["side"], stem["weights"]) == (112, 3 * 3 * 3 * 32)
    assert stem["macs"] == 112 * 112 * 864 == 10_838_016
    # first block, t = 1: a depthwise 3x3 on 32 channels, then 1x1 to 16
    assert layers[1]["weights"] == 9 * 32 and layers[1]["groups"] == 32
    assert layers[2]["macs"] == 112 * 112 * 32 * 16
    # second block: expand 16 -> 96 at 112, depthwise stride 2 -> 56
    assert layers[3]["macs"] == 112 * 112 * 16 * 96
    assert layers[4]["side"] == 56 and layers[4]["macs"] == 56 * 56 * 9 * 96
    assert layers[-2]["macs"] == 7 * 7 * 320 * 1280
    assert layers[-1]["macs"] == 1280 * 1001


def test_mobilenet_totals_match_the_paper():
    # Sandler et al. 2018, Table 4: 300 M multiply-adds, 3.4 M parameters
    assert mnv2.frame_macs(MNV2) == 300_775_552
    assert mnv2.weight_elements(MNV2) == 3_471_040
    flops, nbytes = mnv2.batch_cost(MNV2, 128)
    assert flops == 2 * 300_775_552 * 128
    assert nbytes == 128 * 150528 + 3_471_040 * 2 + 128 * 1001 * 4
    least, bound = least_seconds(flops, nbytes, PEAKS)
    assert bound == "flops" and least == pytest.approx(flops / 197e12)
