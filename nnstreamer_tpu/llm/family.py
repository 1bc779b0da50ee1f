"""The seam between ``tensor_llm`` and the model it serves.

``custom=arch:<name>,...`` names a FAMILY (default ``streamformer_lm``);
the element, the pools and the engine know a family only through what
this file lists, and import no model.  A family is the ``FAMILY`` object
of its model module:

``name``
    the ``arch:`` value
``paged``
    whether it serves the block-paged arena (``page-size > 0``), and with
    it interleaved prefill (``prefill-chunk``) and prefix reuse
    (``prefix-cache``); the element refuses those properties for a
    family that does not, and says why in the family's own words
    (``unpaged_why``: ``sambay_lm``, ``dsv3_lm``)
``state_kinds``
    one name per array of ``init_state`` (arrays of one kind share a
    name): what ``bytes_by_kind`` of a pool and the element's gauges
    report (``kv``; ``sambay_lm`` also ``ring``, ``conv``, ``ssm``;
    ``dsv3_lm`` ``latent`` and ``route_stats``, its routing counters,
    which ride in the state so that a step updates them with no host
    read)
``config_from_custom(custom) -> cfg``
    the family's grammar; raises ``ValueError`` on a key it does not know
``init_params(cfg, seed) -> params``
    seeded weights, wherever the family builds them
``init_state(cfg, slots) -> tuple of arrays``
    what the dense pool's slots hold, slot ``slots`` being the scratch
    slot; the engine passes the tuple, donated, to every function below
    and keeps what comes back
``chunk_len(cfg) -> int``
    0: a prompt is prefilled whole, padded to a power of two
    (``prefill``); > 0: in chunks of that many positions through ONE
    executable (``prefill_chunk``)
``decode_step(params, state, tokens, pos, slots, cfg) -> (logits, state)``
``prefill(params, state, tokens, slot, true_len, cfg, flash) -> (last, state)``
``prefill_chunk(params, state, tokens, slot, start, true_len, last, cfg)
-> (logits, state)``
``state_counters(cfg, state) -> dict`` (optional)
    counters the family keeps inside its state, read to the host: what
    ``DecodeEngine.report()`` adds under ``state_counters``, on request
    and never inside the loop
``decode_step_paged(params, state, tokens, pos, tables, cfg, page_size)``
``prefill_chunk_paged(params, state, tokens, table, start, true_len, cfg,
page_size, scratch)``
    a paged family only.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict

DEFAULT = "streamformer_lm"
#: ``arch:`` value -> the module whose ``FAMILY`` serves it
FAMILIES = {
    "streamformer_lm": "nnstreamer_tpu.models.streamformer_lm",
    "sambay_lm": "nnstreamer_tpu.models.sambay_lm",
    "dsv3_lm": "nnstreamer_tpu.models.dsv3_lm",
}


def get_family(name: str = DEFAULT):
    if name not in FAMILIES:
        raise ValueError(f"tensor_llm: unknown arch {name!r} "
                         f"(known: {sorted(FAMILIES)})")
    return importlib.import_module(FAMILIES[name]).FAMILY


def family_of_custom(custom: Dict[str, Any]):
    """The family a parsed ``custom=`` names, and the keys that are its
    own (``arch`` taken out)."""
    rest = dict(custom)
    return get_family(str(rest.pop("arch", DEFAULT))), rest
