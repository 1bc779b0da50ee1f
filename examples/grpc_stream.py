"""Tensor streaming between processes over the gRPC TensorService
(reference tensor_src_grpc / tensor_sink_grpc).

This process hosts the receiving service; a child process dials in and
pushes frames via SendTensors.
"""

import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402

SENDER = r"""
import sys
sys.path.insert(0, %(root)r)
import numpy as np
from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.tensor.buffer import TensorBuffer

caps = ("other/tensors,format=static,num_tensors=1,dimensions=8:4,"
        "types=float32,framerate=30/1")
p = parse_launch(f"appsrc caps={caps} name=in ! "
                 f"tensor_sink_grpc server=false port=%(port)d")
p.play()
for i in range(5):
    p.get("in").push_buffer(
        TensorBuffer(tensors=[np.full((4, 8), float(i), np.float32)]))
p.get("in").end_of_stream()
p.wait(timeout=60)
p.stop()
"""


def main() -> None:
    rx = parse_launch(
        "tensor_src_grpc server=true port=0 num-buffers=5 name=rx ! "
        "tensor_sink name=out")
    rx.get("out").connect(
        "new-data", lambda b: print(f"received {b.np(0).shape} "
                                    f"mean={float(b.np(0).mean()):.1f}"))
    rx.play()
    root = os.path.join(os.path.dirname(__file__), "..")
    code = SENDER % {"root": os.path.abspath(root),
                     "port": rx.get("rx").port}
    proc = subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    rx.wait(timeout=60)
    rx.stop()
    print("sender exit:", proc.returncode)


if __name__ == "__main__":
    main()
