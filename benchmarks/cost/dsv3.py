"""Operations and bytes the ``dsv3`` family (the program's ``dsv3_lm``:
multi-head latent attention over a cache of latents, routed experts of
which this chip holds a share, a shared expert) NEEDS for a decode step
or a prefill, from shapes alone.

The algorithm's counts, not the program's, and the same whatever
implements it: weights read once per call at the dtype the configuration
states; of the routed experts only those HELD here that the call's
tokens are EXPECTED to reach under uniform routing (``held * (1 - (1 -
k / E) ** tokens)``), and only the token-expert pairs that fall on them
(``tokens * k * held / E``); the latent rows read at the positions
actually attended (the program gathers every reserved position of a
lane) and written once; decode priced by the absorbed products (a query
carried into the latent space, scores and the weighted sum over the
rows, the values expanded after the sum), prefill by the plain ones
(keys and values expanded once a position); the logits written.
``model`` is the ``model`` object of a configuration file.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from benchmarks.cost.roofline import ITEMSIZE


def layer_params(model: Dict[str, Any]) -> Dict[str, int]:
    """Matrix elements of ONE layer, by part (vectors — norms, the
    selection bias — are under 0.01 % and left out)."""
    d, h = model["dim"], model["heads"]
    n, r, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    cq, ckv, f = (model["q_lora_rank"], model["kv_lora_rank"],
                  model["expert_mlp"])
    return {"w_qa": d * cq, "w_qb": cq * h * (n + r),
            "w_kva": d * (ckv + r), "w_kvb_k": ckv * h * n,
            "w_kvb_v": ckv * h * v, "w_o": h * v * d,
            "dense_mlp": 3 * d * model["mlp"],
            "router": d * model["experts"], "one_expert": 3 * d * f,
            "shared": 3 * d * f * model.get("shared_experts", 1)}


def attention_params(model: Dict[str, Any]) -> int:
    p = layer_params(model)
    return (p["w_qa"] + p["w_qb"] + p["w_kva"] + p["w_kvb_k"]
            + p["w_kvb_v"] + p["w_o"])


def layer_beside_experts(model: Dict[str, Any]) -> int:
    """An expert layer without its routed experts: attention, shared
    expert, router."""
    p = layer_params(model)
    return attention_params(model) + p["shared"] + p["router"]


def held_params(model: Dict[str, Any]) -> int:
    """Every matrix element this chip holds: the layers with the held
    experts alone, and its rows of the embedding and of the head."""
    p = layer_params(model)
    dense, layers = model["dense_layers"], model["layers"]
    return (dense * (attention_params(model) + p["dense_mlp"])
            + (layers - dense) * (layer_beside_experts(model)
                                  + model["experts_held"] * p["one_expert"])
            + 2 * model["vocab"] * model["dim"])


def kv_bytes_per_position(model: Dict[str, Any]) -> int:
    """Bytes one cached position holds, over every layer: a row of
    ``kv_lora_rank`` latents and ``qk_rope_head_dim`` rotated key dims
    a layer, whatever the number of heads."""
    return (model["layers"]
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            * ITEMSIZE[model["dtype"]])


def experts_reached(model: Dict[str, Any], tokens: int) -> float:
    """Held experts a call's tokens are expected to reach in ONE layer
    under uniform routing: a token chooses a given expert with
    probability ``k / E``."""
    miss = 1.0 - model["experts_per_tok"] / model["experts"]
    return model["experts_held"] * (1.0 - miss ** tokens)


def pairs_here(model: Dict[str, Any], tokens: int) -> float:
    """Token-expert pairs expected on this chip's experts in ONE
    layer."""
    return (tokens * model["experts_per_tok"] * model["experts_held"]
            / model["experts"])


def routed_experts_cost(model: Dict[str, Any], lanes: int
                        ) -> Tuple[float, float]:
    """``(operations, bytes)`` of the routed experts of one decode step
    over ``lanes`` tokens, every expert layer: the reached experts'
    matrices once, the pairs' rows gathered in and scattered out."""
    p, size = layer_params(model), ITEMSIZE[model["dtype"]]
    n = model["layers"] - model["dense_layers"]
    pairs = pairs_here(model, lanes)
    flops = n * pairs * 2 * p["one_expert"]
    nbytes = n * (experts_reached(model, lanes) * p["one_expert"] * size
                  + pairs * model["dim"] * (size + 4))
    return flops, nbytes


def _absorbed_flops_per_position(model: Dict[str, Any]) -> int:
    """One query of every head against one cached row: the score over
    the whole row, the weighted sum over its latents."""
    c, r = model["kv_lora_rank"], model["qk_rope_head_dim"]
    return model["heads"] * (2 * (c + r) + 2 * c)


def latent_attn_cost(model: Dict[str, Any], lanes: int, attended: int
                     ) -> Tuple[float, float]:
    """``(operations, bytes)`` of what a decode step does over the
    cached rows, every layer: the rows read once at the ``attended``
    positions in total, scores and weighted sum over them, then the
    values' expansion and the output projection of ``lanes`` queries
    with their matrices read once."""
    p, size = layer_params(model), ITEMSIZE[model["dtype"]]
    layers = model["layers"]
    after = p["w_kvb_v"] + p["w_o"]
    flops = layers * (attended * _absorbed_flops_per_position(model)
                      + lanes * 2 * after)
    nbytes = (attended * kv_bytes_per_position(model)
              + layers * after * size)
    return flops, nbytes


def decode_step_cost(model: Dict[str, Any], lanes: int,
                     attended: int) -> Tuple[float, float]:
    """``(operations, bytes)`` of one decode step over ``lanes``
    sequences that attend ``attended`` cached positions in total."""
    p, size = layer_params(model), ITEMSIZE[model["dtype"]]
    dense, layers = model["dense_layers"], model["layers"]
    d, vocab = model["dim"], model["vocab"]
    # what every token multiplies: attention (absorbed: the two halves
    # of w_kvb once each), its FFN beside the routed experts, the head
    token_params = (layers * attention_params(model)
                    + dense * p["dense_mlp"]
                    + (layers - dense) * (p["shared"] + p["router"])
                    + vocab * d)
    moe_flops, moe_bytes = routed_experts_cost(model, lanes)
    flops = (lanes * 2 * token_params + moe_flops
             + layers * attended * _absorbed_flops_per_position(model))
    each = kv_bytes_per_position(model)
    nbytes = (token_params * size + moe_bytes
              + lanes * d * size                       # embedding rows
              + attended * each + lanes * each         # read, written
              + lanes * vocab * 4)
    return flops, nbytes


def prefill_cost(model: Dict[str, Any], tokens: int
                 ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one prefill of ``tokens`` positions
    that answers with the last position's logits, by the plain path:
    keys and values expanded once a position, causal pairs of ``n + r``
    score and ``v`` value dims a head, every weight once."""
    p, size = layer_params(model), ITEMSIZE[model["dtype"]]
    dense, layers = model["dense_layers"], model["layers"]
    d, vocab, h = model["dim"], model["vocab"], model["heads"]
    n, r, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    per_token = (layers * attention_params(model)
                 + dense * p["dense_mlp"]
                 + (layers - dense) * (p["shared"] + p["router"]))
    pairs = tokens * (tokens + 1) // 2
    expert_layers = layers - dense
    flops = (tokens * 2 * per_token
             + expert_layers * pairs_here(model, tokens)
             * 2 * p["one_expert"]
             + layers * pairs * h * (2 * (n + r) + 2 * v)
             + 2 * vocab * d)
    nbytes = ((per_token + vocab * d) * size
              + expert_layers * experts_reached(model, tokens)
              * p["one_expert"] * size
              + tokens * d * size
              + tokens * kv_bytes_per_position(model) + vocab * 4)
    return flops, nbytes
