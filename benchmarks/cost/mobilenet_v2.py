"""Operations and bytes MobileNetV2 1.0 NEEDS for one batch of frames,
from the published layer table (Sandler et al. 2018, arXiv:1801.04381,
Table 2) — not from ``cost_analysis()``, whose bytes count every
intermediate XLA chose to write (PERF.md: the ViT row reached 1.14 of
its roofline that way).

Bytes are the least a batch can move: the frames in, the weights once,
the logits out.  Activations are not counted (a perfect schedule keeps
them on the chip), so the byte bound is a floor and the share it gives
is the harshest fair one.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

from benchmarks.cost.roofline import ITEMSIZE

#: (expansion t, output channels c, repeats n, stride s): Table 2
INVERTED_RESIDUALS = ((1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2),
                      (6, 64, 4, 2), (6, 96, 3, 1), (6, 160, 3, 2),
                      (6, 320, 1, 1))


def conv_layers(model: Dict[str, Any]) -> List[Dict[str, int]]:
    """Every convolution and the classifier, in order: output side,
    kernel size, input and output channels, groups, and from them the
    multiply-accumulates per frame and the weight elements."""
    side, layers = model["input_size"], []

    def conv(k: int, cin: int, cout: int, stride: int, groups: int = 1):
        nonlocal side
        side = -(-side // stride)                       # SAME padding
        weights = k * k * (cin // groups) * cout
        layers.append({"side": side, "k": k, "cin": cin, "cout": cout,
                       "groups": groups, "weights": weights,
                       "macs": side * side * weights})

    conv(3, 3, 32, 2)
    cin = 32
    for t, c, n, s in INVERTED_RESIDUALS:
        for i in range(n):
            hidden = cin * t
            if t != 1:
                conv(1, cin, hidden, 1)
            conv(3, hidden, hidden, s if i == 0 else 1, groups=hidden)
            conv(1, hidden, c, 1)
            cin = c
    conv(1, cin, 1280, 1)
    layers.append({"side": 1, "k": 1, "cin": 1280,
                   "cout": model["num_classes"], "groups": 1,
                   "weights": 1280 * model["num_classes"],
                   "macs": 1280 * model["num_classes"]})
    return layers


def frame_macs(model: Dict[str, Any]) -> int:
    return sum(layer["macs"] for layer in conv_layers(model))


def weight_elements(model: Dict[str, Any]) -> int:
    """Convolution and classifier weights (normalisation folds away)."""
    return sum(layer["weights"] for layer in conv_layers(model))


def batch_cost(model: Dict[str, Any], frames: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of one dispatch over ``frames`` frames."""
    side = model["input_size"]
    flops = 2 * frame_macs(model) * frames
    nbytes = (frames * side * side * 3                  # uint8 frames in
              + weight_elements(model) * ITEMSIZE[model["dtype"]]
              + frames * model["num_classes"] * 4)      # float32 logits
    return flops, nbytes
