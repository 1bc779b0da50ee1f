"""Operations and bytes the ``streamformer_lm`` architecture NEEDS for a
decode step or a prefill, from shapes alone.

These are the algorithm's counts, not the program's: one routed expert
per token (the program computes every expert), weights read once per
call at the dtype the configuration states, keys and values read at the
positions actually attended (the program gathers the whole padded slot).
The distance between these and the device's busy time is the roofline
share.  ``model`` is the ``model`` object of a configuration file.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.cost.roofline import ITEMSIZE


def layer_params(model: Dict[str, Any]) -> Dict[str, int]:
    """Weight elements of ONE layer, by part."""
    d, h, hd = model["dim"], model["heads"], model["head_dim"]
    f, e = model["mlp"], model["experts"]
    return {"wqkv": d * 3 * h * hd, "wo": h * hd * d, "w1": d * f,
            "w2": f * d, "gate": d * e, "one_expert": 2 * d * f,
            "norms": 2 * d}


def total_params(model: Dict[str, Any]) -> int:
    """Every weight element of the model (all experts)."""
    p = layer_params(model)
    per_layer = (p["wqkv"] + p["wo"] + p["w1"] + p["w2"] + p["gate"]
                 + model["experts"] * p["one_expert"] + p["norms"])
    d = model["dim"]
    return (model["layers"] * per_layer + model["vocab"] * d
            + model["max_seq"] * d + d * model["vocab"] + d)


def _token_flops(model: Dict[str, Any]) -> int:
    """Matrix-product operations for one token through every layer and
    the head, one routed expert, attention excluded."""
    p = layer_params(model)
    per_layer = 2 * (p["wqkv"] + p["wo"] + p["w1"] + p["w2"] + p["gate"]
                     + p["one_expert"])
    return model["layers"] * per_layer + 2 * model["dim"] * model["vocab"]


def _weight_bytes(model: Dict[str, Any], tokens: int) -> int:
    """Bytes of weights one call reads: every layer weight once, as many
    experts as its tokens can reach, the float32 head, one embedding and
    one position row per token."""
    p = layer_params(model)
    size = ITEMSIZE[model["dtype"]]
    experts = min(model["experts"], tokens)
    per_layer = (p["wqkv"] + p["wo"] + p["w1"] + p["w2"] + p["gate"]
                 + experts * p["one_expert"] + p["norms"])
    d = model["dim"]
    return (model["layers"] * per_layer * size
            + d * model["vocab"] * 4            # the head stays float32
            + 2 * tokens * d * size)


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Bytes of keys and values one cached position holds, over every
    layer, at the dtype the configuration states."""
    return (model["layers"] * 2 * model["heads"] * model["head_dim"]
            * ITEMSIZE[model["dtype"]])


def decode_step_cost(model: Dict[str, Any], lanes: int,
                     attended: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of one decode step over ``lanes``
    sequences that attend ``attended`` cached positions in total."""
    h, hd, layers = model["heads"], model["head_dim"], model["layers"]
    flops = lanes * _token_flops(model) + layers * 4 * h * hd * attended
    kv_read = kv_bytes_per_position(model) * attended
    kv_write = kv_bytes_per_position(model) * lanes
    logits = lanes * model["vocab"] * 4
    return flops, _weight_bytes(model, lanes) + kv_read + kv_write + logits


def prefill_cost(model: Dict[str, Any], tokens: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of one causal prefill of ``tokens``
    positions that answers with the last position's logits."""
    h, hd, layers = model["heads"], model["head_dim"], model["layers"]
    pairs = tokens * (tokens + 1) // 2          # causal (query, key) pairs
    p = layer_params(model)
    per_layer = 2 * (p["wqkv"] + p["wo"] + p["w1"] + p["w2"] + p["gate"]
                     + p["one_expert"])
    flops = (tokens * layers * per_layer + layers * 4 * h * hd * pairs
             + 2 * model["dim"] * model["vocab"])
    kv_write = kv_bytes_per_position(model) * tokens
    return flops, (_weight_bytes(model, tokens) + kv_write
                   + model["vocab"] * 4)
