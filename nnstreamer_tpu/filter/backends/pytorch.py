"""PyTorch (TorchScript) filter backend — compiled onto the TPU.

Parity with the reference pytorch subplugin
(ext/nnstreamer/tensor_filter/tensor_filter_pytorch.cc, SURVEY.md §2.4):
loads a TorchScript ``.pt`` file and serves it per buffer.  Like the
reference, the model file carries no input meta, so the caller must supply
``input_info`` (the element's ``input-dim``/``input-type`` properties);
output meta is discovered by probing the model with zeros at open — the
same contract as the reference's ``getModelInfo`` path.

Execution: the frozen TorchScript graph is **lowered to jax/lax**
(filter/torchscript.py) and served through the shared jit engine — params
in HBM, one XLA executable, async dispatch, micro-batching — exactly like
the tflite/pb backends.  The reference instead runs the libtorch
interpreter in-process with optional CUDA (``[pytorch] enable_use_gpu``,
nnstreamer.ini.in:28-30); a TPU host has no libtorch device backend, so
compilation IS the device path.

Graphs using ops outside the lowering table fall back to host-CPU eager
TorchScript execution (honest, logged) — unless the user demanded
``accelerator=true:tpu``, which then fails loudly.  ``custom=executor:torch``
forces the host path.

Note: the reference test-zoo's ``pytorch_lenet5.pt`` is in the legacy
TorchScript serialization no current torch release can load
("Legacy model format is not supported"); the loadable zoo samples
(``sample_3x4_two_input_two_output.pt`` etc.) are covered by tests, plus a
freshly-scripted LeNet5 matching the reference fixture's architecture.
"""

from __future__ import annotations

import os
import time
from typing import Any, List, Optional, Tuple

import numpy as np

from ...tensor.info import TensorInfo, TensorsInfo
from ...utils.conf import parse_bool
from ...utils.log import logger
from ..framework import (Accelerator, FilterError, FilterFramework,
                         FilterProperties, FilterStatistics, register_filter)
from ._jitexec import JitExecMixin


@register_filter
class PyTorchFilter(JitExecMixin, FilterFramework):
    """``framework=pytorch``: TorchScript model, lowered to XLA (host-CPU
    torch eager as fallback)."""

    NAME = "pytorch"
    SUPPORTED_ACCELERATORS = (Accelerator.TPU, Accelerator.CPU)

    def __init__(self) -> None:
        super().__init__()
        self._module = None
        self._in_info: Optional[TensorsInfo] = None
        self._out_info: Optional[TensorsInfo] = None
        #: "xla" (lowered, on device) or "torch-host" (eager fallback)
        self.executor: str = ""
        #: WHY the host fallback engaged (the blocking op, e.g.
        #: "pool2d ceil_mode") — surfaced by launch --stats and tests
        self.fallback_reason: str = ""
        self.stats = FilterStatistics()

    # -- lifecycle -----------------------------------------------------------
    def open(self, props: FilterProperties) -> None:
        try:
            import torch
        except ImportError as e:  # pragma: no cover
            raise FilterError(f"pytorch backend unavailable: {e}")

        path = str(props.model)
        if not os.path.isfile(path):
            raise FilterError(f"pytorch: model file not found: {path}")
        if props.input_info is None or not props.input_info.is_valid():
            raise FilterError(
                "pytorch: input_info required (TorchScript files carry no "
                "input meta; set the input/inputtype properties — reference "
                "tensor_filter_pytorch.cc contract)")
        try:
            self._module = torch.jit.load(path, map_location="cpu")
        except Exception as e:
            raise FilterError(f"pytorch: cannot load {path}: {e}")
        self._module.eval()
        self._in_info = props.input_info.copy()

        want_tpu = Accelerator.TPU in (props.accelerators or [])
        force_host = props.custom_properties.get("executor") == "torch"
        strict = parse_bool(props.custom_properties.get("strict", ""))
        if force_host and want_tpu:
            raise FilterError(
                "pytorch: executor:torch contradicts accelerator=true:tpu")
        if force_host and strict:
            raise FilterError(
                "pytorch: executor:torch contradicts strict:true "
                "(strict forbids the host fallback)")
        self.executor = ""
        self.fallback_reason = ""
        if not force_host:
            try:
                self._open_xla(props)
            except Exception as e:
                if want_tpu or strict:
                    demand = ("accelerator=true:tpu" if want_tpu
                              else "strict:true")
                    raise FilterError(
                        f"pytorch: {demand} demanded but the TorchScript "
                        f"graph does not lower to XLA: {e}")
                self.fallback_reason = str(e)
                logger.warning(
                    "pytorch: %s — falling back to host-CPU TorchScript "
                    "eager execution", e)
        if not self.executor:
            self._open_torch_host(props)
        # batching rides the vmapped XLA executable; the host interpreter
        # has no batched path (instance attr shadows the mixin class attr)
        self.SUPPORTS_BATCHING = self.executor == "xla"
        super().open(props)

    def _open_xla(self, props: FilterProperties) -> None:
        from ..torchscript import lower_torchscript

        fn, ts_params = lower_torchscript(self._module,
                                          self._in_info.num_tensors)
        device = self._pick_device(props.accelerators)
        zeros = [np.zeros(i.np_shape, i.np_dtype) for i in self._in_info]
        # the warm-up outputs double as the output-meta probe (the
        # reference probes the interpreter the same way at open)
        outs = self._setup_exec(fn, ts_params, device, warmup_inputs=zeros,
                                mesh=self._resolve_mesh(props, device))
        probed = TensorsInfo([TensorInfo.from_np(np.asarray(o))
                              for o in outs])
        self._check_declared_output(props, probed)
        self.executor = "xla"

    def _open_torch_host(self, props: FilterProperties) -> None:
        zeros = [np.zeros(i.np_shape, i.np_dtype) for i in self._in_info]
        outs = self._run_torch(zeros)
        probed = TensorsInfo([TensorInfo.from_np(o) for o in outs])
        self._check_declared_output(props, probed)
        self.executor = "torch-host"

    def _check_declared_output(self, props: FilterProperties,
                               probed: TensorsInfo) -> None:
        if props.output_info is not None and props.output_info.is_valid():
            if not props.output_info.is_equal(probed):
                raise FilterError(
                    f"pytorch: declared output {props.output_info} != "
                    f"model output {probed}")
            self._out_info = props.output_info.copy()
        else:
            self._out_info = probed

    def close(self) -> None:
        self._module = None
        self._teardown_exec()
        super().close()

    # -- model meta ----------------------------------------------------------
    def get_model_info(self) -> Tuple[TensorsInfo, TensorsInfo]:
        if self._module is None:
            raise FilterError("pytorch: not opened")
        return self._in_info, self._out_info

    def set_input_info(self, in_info: TensorsInfo) -> Tuple[TensorsInfo, TensorsInfo]:
        """Re-probe with new input shapes (reference SET_INPUT_INFO)."""
        self._in_info = in_info.copy()
        if self.executor == "xla":
            zeros = [np.zeros(i.np_shape, i.np_dtype) for i in in_info]
            outs = self._invoke_device(zeros)
            self._out_info = TensorsInfo(
                [TensorInfo.from_np(np.asarray(o)) for o in outs])
        else:
            zeros = [np.zeros(i.np_shape, i.np_dtype) for i in in_info]
            outs = self._run_torch(zeros)
            self._out_info = TensorsInfo([TensorInfo.from_np(o)
                                          for o in outs])
        return self._in_info, self._out_info

    # -- hot path ------------------------------------------------------------
    def _run_torch(self, inputs: List[Any]) -> List[np.ndarray]:
        import torch

        tins = [torch.from_numpy(np.ascontiguousarray(x)) for x in inputs]
        with torch.no_grad():
            out = self._module(*tins)
        if isinstance(out, (tuple, list)):
            outs = list(out)
        else:
            outs = [out]
        return [o.detach().cpu().numpy() for o in outs]

    def invoke(self, inputs: List[Any],
               emit_device: bool = False) -> List[Any]:
        if self.executor == "xla":
            return JitExecMixin.invoke(self, inputs,
                                       emit_device=emit_device)
        t0 = time.monotonic_ns()
        outs = self._run_torch([np.asarray(x) for x in inputs])
        self.stats.record(time.monotonic_ns() - t0)
        return outs

    def invoke_batched(self, frames, bucket: int, emit_device: bool = False):
        if self.executor != "xla":
            raise FilterError("pytorch: host executor has no batched path")
        return JitExecMixin.invoke_batched(self, frames, bucket,
                                           emit_device=emit_device)

    def warmup_batched(self, bucket: int) -> None:
        if self.executor == "xla":
            JitExecMixin.warmup_batched(self, bucket)

    def set_postprocess(self, fn) -> bool:
        if self.executor != "xla":
            return False
        return JitExecMixin.set_postprocess(self, fn)

    @classmethod
    def handles_model(cls, model: Any) -> bool:
        return isinstance(model, str) and model.endswith((".pt", ".pth"))
