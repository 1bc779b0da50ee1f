"""Model registry: named, jittable models the XLA filter backend serves.

The reference loads vendor model files (.tflite/.pb/.pt …) through per-SDK
subplugins (SURVEY.md §2.4).  TPU-native, a "model" is a pure JAX function +
params compiled by XLA; the registry replaces file-extension dispatch with
named model specs (file paths to orbax checkpoints also resolve here).

Sizing is a ``custom=`` grammar, not code: ``mlp``
(``custom=width:2048,depth:32``, models/mlp.py) and ``streamformer_lm``
(``custom=layers:8,width:512,max_seq:1024``,
models/streamformer_lm.config_from_custom — shared with the
``tensor_llm`` serving tier) both size from the launch line, so soak
and bench servers pick a realistically heavy model without edits.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..tensor.info import TensorsInfo


@dataclasses.dataclass
class Model:
    """A ready-to-serve model.

    ``forward(params, *inputs) -> tuple(outputs)`` must be jittable, operate
    on *unbatched* numpy-shaped arrays (one stream frame), and keep its
    FLOPs in MXU-friendly form (bf16 matmuls/convs).  ``in_info``/``out_info``
    use reference dim order (innermost first).
    """

    name: str
    forward: Callable[..., Tuple[Any, ...]]
    params: Any
    in_info: TensorsInfo
    out_info: TensorsInfo
    #: optional training step factory (loss, optimizer) for trainer parity
    make_train_step: Optional[Callable[..., Any]] = None


#: name -> build(custom_props: dict) -> Model
_MODELS: Dict[str, Callable[[Dict[str, str]], Model]] = {}


def register_model(name: str):
    def deco(build: Callable[[Dict[str, str]], Model]):
        _MODELS[name] = build
        return build
    return deco


def host_init(fn: Callable[[], Any]) -> Any:
    """Run a model-building computation (flax ``module.init`` etc.) on the
    host CPU device.

    Eager init on the default accelerator dispatches each of the model's
    hundreds of parameter/batch-norm ops separately, each paying its own
    tiny XLA compile plus a device round trip before the serving graph's
    single real compile even starts.  Params are moved to the serving
    device exactly once — at backend open (filter/backends/_jitexec.py
    ``_setup_exec``) or engine construction (llm/engine.py) — so nothing
    is lost by initializing on host.
    """
    import jax

    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:  # cpu platform masked out (e.g. JAX_PLATFORMS=tpu)
        return fn()
    with jax.default_device(cpu):
        return fn()


def save_checkpoint(model: Model, path: str) -> None:
    """Persist model params as an orbax checkpoint (the framework's model
    artifact format — the role of the reference's .tflite/.pb model files)."""
    import os

    import orbax.checkpoint as ocp

    ckpt = ocp.StandardCheckpointer()
    ckpt.save(os.path.abspath(path), model.params)
    ckpt.wait_until_finished()


def restore_params(template, path: str):
    """Restore params matching ``template``'s structure from orbax."""
    import os

    import orbax.checkpoint as ocp

    ckpt = ocp.StandardCheckpointer()
    return ckpt.restore(os.path.abspath(path), target=template)


def graft_params(dst, src):
    """Copy every ``src`` leaf into ``dst`` where the tree path AND shape
    match; returns ``(grafted, n_copied)``.

    The transfer-learning helper behind real-trunk validation: the zoo's
    SSD/posenet heads share the MobileNetV2 trunk by flax auto-naming
    (ConvBN_0, InvertedResidual_0.., incl. batch_stats), so grafting the
    real ImageNet weights under an untrained head takes one call —
    head layers differ in shape and keep their fresh init."""
    n = 0
    out = {}
    for k, v in dst.items():
        if k in src and isinstance(v, dict) and isinstance(src[k], dict):
            out[k], m = graft_params(v, src[k])
            n += m
        elif (k in src and hasattr(v, "shape")
                and getattr(src[k], "shape", None) == v.shape):
            out[k] = src[k]
            n += 1
        else:
            out[k] = v
    return out, n


def _ensure_loaded() -> None:
    from . import (mlp, mobilenet_v2, ssd, deeplab_v3,  # noqa: F401
                   posenet, streamformer_lm, vit)  # noqa: F401


def get_model(name: str, custom_props: Optional[Dict[str, str]] = None) -> Model:
    _ensure_loaded()
    if name not in _MODELS:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_MODELS)}")
    return _MODELS[name](custom_props or {})


def has_model(name: str) -> bool:
    try:
        _ensure_loaded()
    except Exception:  # pragma: no cover - import errors surface later
        return False
    return name in _MODELS


def list_models() -> List[str]:
    _ensure_loaded()
    return sorted(_MODELS)
