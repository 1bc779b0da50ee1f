"""Recurrent pipeline: state feeds back through the tensor repository.

The reference's repo_rnn topology (tests/nnstreamer_repo_rnn): input frames
and the previous state meet in a ``tensor_mux``, a filter computes the new
state, a ``tee`` sends it both downstream and back through
``tensor_reposink`` → ``tensor_reposrc``.  The reposrc bootstraps the loop
with a zero frame, so frame 0 sees state 0.

Here the "RNN" is an exponential moving average over the video stream's
mean brightness: state' = 0.9·state + 0.1·frame_mean.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.filter.backends.custom import register_custom_easy  # noqa: E402
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsInfo  # noqa: E402
from nnstreamer_tpu.tensor.types import TensorType  # noqa: E402


def main() -> None:
    f32 = TensorType.FLOAT32
    state_info = TensorsInfo([TensorInfo(dtype=f32, dims=(1,))])
    pair = TensorsInfo([
        TensorInfo(dtype=TensorType.UINT8, dims=(3, 64, 64, 1)),
        TensorInfo(dtype=f32, dims=(1,)),
    ])
    register_custom_easy(
        "ema_state",
        lambda ins: [np.asarray(
            0.9 * np.asarray(ins[1], np.float32)
            + 0.1 * np.asarray(ins[0], np.float32).mean(), np.float32
        ).reshape(1)],
        pair, state_info)

    caps = ("other/tensors,format=static,num_tensors=1,dimensions=1,"
            "types=float32,framerate=0/1")
    p = parse_launch(
        "tensor_mux name=mux sync-mode=nosync ! "
        "tensor_filter framework=custom-easy model=ema_state ! "
        "tee name=t ! queue ! tensor_reposink slot-index=0 "
        "videotestsrc num-buffers=30 pattern=gradient ! "
        "video/x-raw,format=RGB,width=64,height=64,framerate=30/1 ! "
        "tensor_converter ! mux.sink_0 "
        f"tensor_reposrc slot-index=0 caps={caps} ! mux.sink_1 "
        "t. ! queue ! tensor_sink name=out")
    p.get("out").connect(
        "new-data",
        lambda b: print(f"EMA brightness: "
                        f"{float(np.asarray(b.tensors[0]).ravel()[0]):.3f}"))
    p.run(timeout=120)


if __name__ == "__main__":
    main()
