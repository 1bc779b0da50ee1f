"""Operations and bytes the ``phi4flash`` family (the program's
``sambay_lm``: Mamba, windowed and full differential attention, gated
memory units and cross-attention over ONE shared cache) NEEDS for a
decode step or a prefill, from shapes alone.

The algorithm's counts, not the program's: weights read once per call at
the dtype the configuration states, the tied table counted once (as the
head; a token's embedding row is noise beside it); the full layer's rows
read once by each of its readers (itself and every cross-attention
layer) at the positions actually attended (the program gathers every
reserved position of a lane); a window layer's ``min(pos + 1, window)``
rows; the recurrent rows read and written once; the logits written.
``model`` is the ``model`` object of a configuration file.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.cost.roofline import ITEMSIZE


def layer_counts(model: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind ``layers`` gives (``h = layers / 2``:
    Mamba at the even indices up to ``h``, window attention at the odd
    ones below it, the full layer at ``h + 1``, then memory units and
    cross-attention in turn)."""
    h = model["layers"] // 2
    rest = model["layers"] - h - 2
    return {"mamba": h // 2 + 1, "window": h // 2, "full": 1,
            "gmu": rest // 2, "cross": rest // 2}


def mixer_params(model: Dict[str, Any]) -> Dict[str, int]:
    """Matrix elements of ONE mixer of each kind, and of the MLP every
    layer has (vectors — norms, biases, ``D``, the lambdas — are under
    0.01 % and left out; ``A_log`` is counted with the Mamba mixer)."""
    d, f = model["dim"], model["mlp"]
    qw = model["heads"] * model["head_dim"]
    kw = model["kv_heads"] * model["head_dim"]
    di, n = model["expand"] * d, model["d_state"]
    r, taps = model["dt_rank"], model["d_conv"]
    return {"mlp": 3 * d * f,
            "mamba": (d * 2 * di + di * d + di * (r + 2 * n) + r * di
                      + taps * di + di * n),
            "window": d * (qw + 2 * kw) + qw * d,
            "full": d * (qw + 2 * kw) + qw * d,
            "gmu": 2 * d * di,
            "cross": d * qw + qw * d}


def total_params(model: Dict[str, Any]) -> int:
    """Every matrix element of the model, the tied table once."""
    per, n = mixer_params(model), layer_counts(model)
    return (model["layers"] * per["mlp"]
            + sum(n[k] * per[k] for k in n)
            + model["vocab"] * model["dim"])


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Bytes one cached position holds: the full layer's key and value
    row, ONCE, whatever the depth (every cross-attention layer reads the
    same rows).  What a session holds whatever its length is
    :func:`fixed_state_bytes`."""
    return (2 * model["kv_heads"] * model["head_dim"]
            * ITEMSIZE[model["dtype"]])


def fixed_state_bytes(model: Dict[str, Any]) -> Dict[str, int]:
    """Bytes a session holds beside its positions: a ring of ``window``
    key and value rows a window layer, and a Mamba layer's convolution
    tail (the stated dtype) and float32 state."""
    n = layer_counts(model)
    size = ITEMSIZE[model["dtype"]]
    di = model["expand"] * model["dim"]
    return {"ring": n["window"] * model["window"]
            * kv_bytes_per_position(model),
            "mamba": n["mamba"] * (di * model["d_state"] * 4
                                   + (model["d_conv"] - 1) * di * size)}


def _attention_flops_per_position(model: Dict[str, Any]) -> int:
    """One query's two softmaxes against one cached position, every
    head: the scores over ``head_dim`` and the read-out of the paired
    ``2 * head_dim`` values."""
    return 2 * model["heads"] * model["head_dim"] * (1 + 2)


def shared_kv_cost(model: Dict[str, Any], lanes: int,
                   attended: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of the readings of the full layer's rows
    in one decode step — by the full layer and by each cross-attention
    layer, at the ``attended`` positions in total — and their
    products."""
    n = layer_counts(model)
    readers = n["full"] + n["cross"]
    return (readers * attended * _attention_flops_per_position(model),
            readers * attended * kv_bytes_per_position(model))


def _window_rows(model: Dict[str, Any], lanes: int, attended: int) -> int:
    """Rows the window layers' rings hold for these lanes: ``min(pos +
    1, window)`` a lane, the lanes taken at their mean position."""
    return lanes * min(attended // max(1, lanes), model["window"])


def decode_step_cost(model: Dict[str, Any], lanes: int,
                     attended: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of one decode step over ``lanes``
    sequences that attend ``attended`` cached positions in total."""
    n, size = layer_counts(model), ITEMSIZE[model["dtype"]]
    di = model["expand"] * model["dim"]
    ring_rows = _window_rows(model, lanes, attended)
    kv_flops, kv_read = shared_kv_cost(model, lanes, attended)
    flops = (lanes * 2 * total_params(model) + kv_flops
             + n["window"] * ring_rows
             * _attention_flops_per_position(model)
             + n["mamba"] * lanes * 6 * di * model["d_state"])
    each = kv_bytes_per_position(model)
    fixed = fixed_state_bytes(model)
    nbytes = (total_params(model) * size
              + kv_read + lanes * each                  # read, and written
              + n["window"] * (ring_rows + lanes) * each
              + 2 * lanes * fixed["mamba"]              # read and written
              + lanes * model["vocab"] * 4)
    return flops, nbytes


def prefill_cost(model: Dict[str, Any], tokens: int) -> Tuple[int, int]:
    """``(operations, bytes)`` of one prefill of ``tokens`` positions
    that answers with the last position's logits: the layers up to the
    full one over every position (causal pairs in the full layer, a
    window's worth in the window layers), the cross-decoder and the head
    at the last position alone, every weight once."""
    n, per = layer_counts(model), mixer_params(model)
    size = ITEMSIZE[model["dtype"]]
    di = model["expand"] * model["dim"]
    front = n["mamba"] + n["window"] + n["full"]
    per_token = 2 * (front * per["mlp"] + n["mamba"] * per["mamba"]
                     + n["window"] * per["window"] + per["full"])
    pairs = tokens * (tokens + 1) // 2
    near = sum(min(t + 1, model["window"]) for t in range(tokens))
    once = 2 * ((n["gmu"] + n["cross"]) * per["mlp"]
                + n["gmu"] * per["gmu"] + n["cross"] * per["cross"]
                + model["vocab"] * model["dim"])
    flops = (tokens * per_token + once
             + _attention_flops_per_position(model)
             * (pairs + n["window"] * near + n["cross"] * tokens)
             + n["mamba"] * tokens * 6 * di * model["d_state"])
    fixed = fixed_state_bytes(model)
    nbytes = (total_params(model) * size
              + tokens * kv_bytes_per_position(model)
              + fixed["ring"] + fixed["mamba"] + model["vocab"] * 4)
    return flops, nbytes
