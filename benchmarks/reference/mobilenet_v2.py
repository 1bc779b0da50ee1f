"""Plain float32 reference of MobileNetV2 1.0 (Sandler et al. 2018,
arXiv:1801.04381, Table 2), independent of
``nnstreamer_tpu/models/mobilenet_v2.py``: ``lax`` convolutions at
``highest`` precision, inference-mode batch normalisation from the
stored statistics, ReLU6, no flax.  It reads the variable tree the
program serves from (``params`` + ``batch_stats``, flax's names).

    frame uint8 (H, W, 3)  ->  x = frame / 127.5 - 1
    conv 3x3 s2, 32                                   + BN + ReLU6
    17 inverted residuals (t, c, n, s of Table 2):
        1x1 expand to t*cin (absent when t = 1)       + BN + ReLU6
        3x3 depthwise, stride s on the first repeat   + BN + ReLU6
        1x1 project to c                              + BN
        + input, when the stride is 1 and the channels match
    conv 1x1, 1280                                    + BN + ReLU6
    global average pool, dense to the classes
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.cost.mobilenet_v2 import INVERTED_RESIDUALS

_BN_EPS = 1e-5                  # flax.linen.BatchNorm's default


def _conv(x, kernel, stride: int, groups: int = 1):
    return jax.lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=jax.lax.Precision.HIGHEST)


def _bn(x, params: Dict[str, Any], stats: Dict[str, Any]):
    inv = jax.lax.rsqrt(stats["var"].astype(jnp.float32) + _BN_EPS)
    return ((x - stats["mean"]) * inv * params["scale"] + params["bias"])


def _conv_bn(x, params, stats, stride: int = 1, groups: int = 1,
             relu6: bool = True):
    x = _bn(_conv(x, params["Conv_0"]["kernel"], stride, groups),
            params["BatchNorm_0"], stats["BatchNorm_0"])
    return jnp.clip(x, 0.0, 6.0) if relu6 else x


def forward_logits(variables: Dict[str, Any], frame) -> np.ndarray:
    """``frame`` uint8 ``(H, W, 3)`` → float32 logits ``(classes,)``."""
    params, stats = variables["params"], variables["batch_stats"]
    x = (jnp.asarray(frame, jnp.float32) / 127.5 - 1.0)[None]
    x = _conv_bn(x, params["_ConvBN_0"], stats["_ConvBN_0"], stride=2)
    block = 0
    for t, c, n, s in INVERTED_RESIDUALS:
        for i in range(n):
            name = f"_InvertedResidual_{block}"
            p, st = params[name], stats[name]
            stride = s if i == 0 else 1
            y, sub = x, 0
            if t != 1:
                y = _conv_bn(y, p["_ConvBN_0"], st["_ConvBN_0"])
                sub = 1
            y = _conv_bn(y, p[f"_ConvBN_{sub}"], st[f"_ConvBN_{sub}"],
                         stride=stride, groups=y.shape[-1])
            # the projection's conv and BN sit on the block itself
            y = _conv_bn(y, p, st, relu6=False)
            x = y + x if stride == 1 and x.shape[-1] == c else y
            block += 1
    x = _conv_bn(x, params["_ConvBN_1"], stats["_ConvBN_1"])
    x = x.mean(axis=(1, 2))
    dense = params["Dense_0"]
    logits = jnp.dot(x, dense["kernel"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST) + dense["bias"]
    return np.asarray(logits[0], np.float32)
