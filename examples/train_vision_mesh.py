"""Data-parallel vision training from the stream (tutorial T6 §3).

A tiny ViT trains over a dp mesh: AppSrc pushes (frames, labels)
batches, tensor_trainer framework=mesh-vision shards each batch over
the mesh's dp axis (params replicated, gradient psum inserted by XLA),
and the checkpoint written at EOS is directly servable by
``tensor_filter framework=xla model=vit custom=checkpoint:...``.

Run on the host with a virtual mesh:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_vision_mesh.py
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu.elements import TensorTrainer  # noqa: E402
from nnstreamer_tpu.pipeline import AppSrc, Pipeline  # noqa: E402
from nnstreamer_tpu.pipeline.registry import element_factory  # noqa: E402
from nnstreamer_tpu.tensor import TensorBuffer  # noqa: E402


def main() -> None:
    ckpt = os.path.join(tempfile.mkdtemp(), "vit_ckpt")
    p = Pipeline()
    src = AppSrc("src", caps=(
        "other/tensors,format=static,num_tensors=2,"
        "dimensions=3:32:32:8.8,types=uint8.int32,framerate=0/1"))
    trainer = TensorTrainer("tr", framework="mesh-vision", **{
        "num-epochs": 4, "model-save-path": ckpt,
        "custom": ("model:vit,input_size:32,patch:16,dim:32,depth:1,"
                   "heads:2,num_classes:4,dtype:float32,lr:0.01")})
    sink = element_factory("tensor_sink")("out")
    p.add(src, trainer, sink)
    p.link(src, trainer, sink)

    rng = np.random.default_rng(0)
    for i in range(6):
        # learnable toy task: the class is the frame's brightness band
        labels = rng.integers(0, 4, 8).astype(np.int32)
        frames = np.repeat(
            (labels * 64 + 32).astype(np.uint8)[:, None, None, None],
            32 * 32 * 3, axis=1).reshape(8, 32, 32, 3)
        src.push_buffer(TensorBuffer(tensors=[frames, labels], pts=i))
    src.end_of_stream()
    p.run(timeout=600)

    s = trainer.summary
    print(f"trained {s['model']} over mesh {s['mesh']}: "
          f"loss {trainer.trainer.losses[0]:.3f} -> {s['final_loss']:.3f}")
    print(f"checkpoint: {ckpt}")


if __name__ == "__main__":
    main()
