"""XLA/JAX filter backend — the native TPU execution path.

This is the framework's answer to the reference's accelerated backends
(tensor_filter_tensorrt.cc / tensor_filter_edgetpu.cc, SURVEY.md §2.4):
instead of building a TensorRT engine or delegating to libedgetpu, a model
from the registry is compiled to a single XLA executable and invoked on the
TPU (or CPU) device.

Hot-path discipline — the TPU analogue of the reference's zero-copy/
one-alloc rules (tensor_filter.c:631-894):

- params live in HBM permanently (device_put at open);
- the forward fn is jit-compiled once at open with a warm-up invoke, so
  steady state never recompiles;
- invoke() dispatches asynchronously and returns jax.Array handles WITHOUT
  a host sync — downstream materializes only when it actually needs bytes
  (decoder/sink), which keeps the device pipelined frame-to-frame;
- per-invoke dtype/shape validation against negotiated meta happens on the
  host before dispatch, as in the reference validate step.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from ...tensor.info import TensorsInfo
from ..framework import (Accelerator, FilterError, FilterFramework,
                         FilterProperties, FilterStatistics, register_filter)
from ._jitexec import JitExecMixin


@register_filter
class XLAFilter(JitExecMixin, FilterFramework):
    """``framework=xla``: serve a registry model via jit-compiled XLA."""

    NAME = "xla"
    SUPPORTED_ACCELERATORS = (Accelerator.TPU, Accelerator.CPU)
    SUPPORTS_BATCHING = True

    def __init__(self) -> None:
        super().__init__()
        self._model = None
        self._jitted = None
        self._vjit = None
        self._forward_fn = None
        self._params_dev = None
        self._device = None
        self.stats = FilterStatistics()

    # -- lifecycle -----------------------------------------------------------
    def open(self, props: FilterProperties) -> None:
        from ...models.registry import get_model

        model_name = str(props.model)
        self._device = self._pick_device(props.accelerators)
        custom = dict(props.custom_properties)
        if "dtype" not in custom and self._device.platform == "cpu":
            # bf16 is MXU-native on TPU but emulated (slow) on CPU hosts.
            custom["dtype"] = "float32"
        from ...models.registry import has_model

        if not has_model(model_name):
            from ...models.registry import list_models

            raise FilterError(f"xla: unknown model {model_name!r}; "
                              f"known: {list_models()}")
        self._model = get_model(model_name, custom)
        ckpt_path = custom.get("checkpoint")
        if ckpt_path:
            # restore pretrained params (orbax; the role of loading the
            # reference's .tflite/.pb weight files)
            from ...models.registry import restore_params

            self._model.params = restore_params(self._model.params,
                                                ckpt_path)
        # Warm-up compile at open so frame 1 is steady-state (the
        # reference's equivalent is engine build at open,
        # tensor_filter_tensorrt.cc:343).
        zeros = [np.zeros(i.np_shape, i.np_dtype)
                 for i in self._model.in_info]
        self._setup_exec(self._model.forward, self._model.params,
                         self._device, warmup_inputs=zeros,
                         mesh=self._resolve_mesh(props, self._device))
        super().open(props)

    def close(self) -> None:
        self._model = None
        self._teardown_exec()
        super().close()

    # -- model meta ----------------------------------------------------------
    def get_model_info(self) -> Tuple[TensorsInfo, TensorsInfo]:
        if self._model is None:
            raise FilterError("xla: not opened")
        return self._model.in_info, self._model.out_info

    # -- events --------------------------------------------------------------
    def handle_event(self, name: str, data: Optional[Dict[str, Any]] = None) -> None:
        if name == "reload_model":
            if data and "model" in data and \
                    str(data["model"]) != str(self.props.model):
                # a DIFFERENT model name changes the forward function,
                # not just the params — the jitted/vmapped executables
                # must be rebuilt, so take the generic close+open swap
                # (interface check + rollback).  The fast path below
                # would silently rebuild the OLD model: it merges data
                # into custom properties and re-gets props.model
                return super().handle_event(name, data)
            # Hot reload: rebuild params (e.g. new checkpoint path in data),
            # keep serving the old executable until the swap (reference
            # RELOAD_MODEL holds the old model,
            # nnstreamer_plugin_api_filter.h:377-383).
            import jax

            props = self.props
            if data:
                merged = dict(props.custom_properties)
                merged.update({k: str(v) for k, v in data.items()})
                props = FilterProperties(
                    framework=props.framework, model=props.model,
                    input_info=props.input_info, output_info=props.output_info,
                    accelerators=props.accelerators, custom_properties=merged,
                    shared_key=props.shared_key)
            from ...models.registry import get_model, restore_params

            new_model = get_model(str(props.model), props.custom_properties)
            ckpt = props.custom_properties.get("checkpoint")
            if ckpt:
                new_model.params = restore_params(new_model.params, ckpt)
            new_params = jax.device_put(new_model.params, self._device)
            self._model, self._params_dev = new_model, new_params
            self.props = props
            return
        super().handle_event(name, data)

    @classmethod
    def handles_model(cls, model: Any) -> bool:
        if not isinstance(model, str):
            return False
        from ...models.registry import has_model

        return has_model(model)
