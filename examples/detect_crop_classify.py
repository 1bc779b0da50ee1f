"""Two-stage cascade: detect → crop → classify.

The composite pipeline shape the reference builds from tensor_crop
(gsttensor_crop.c: crop-info from one branch applied to raw tensors
from another): SSD finds boxes on device (the detection tail — prior
decode, threshold, NMS — runs INSIDE the serving executable via the
pushdown, ops/nms.py), the surviving boxes become tensor_crop regions
over the raw frames, and each crop is classified by a second model
through the Single API.

  videotestsrc ─ tee ─ tensor_filter(ssd) ─ bounding_boxes ─ objects ┐
               └───── raw frames ────────────────► tensor_crop ◄─────┘
                                                        │ crops
                                                  FilterSingle(classifier)

Run: JAX_PLATFORMS=cpu python examples/detect_crop_classify.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.elements import TensorCrop  # noqa: E402
from nnstreamer_tpu.filter.single import FilterSingle  # noqa: E402
from nnstreamer_tpu.models.registry import get_model  # noqa: E402
from nnstreamer_tpu.pipeline import AppSrc, Pipeline  # noqa: E402
from nnstreamer_tpu.pipeline.registry import element_factory  # noqa: E402
from nnstreamer_tpu.tensor import TensorBuffer  # noqa: E402

N_FRAMES = 6
SIZE = 300


def priors_file() -> str:
    """Synthetic box priors (the zoo ships none; same shape as the
    reference's box_priors.txt)."""
    n = get_model("ssd_mobilenet_v2",
                  {"seed": "0"}).out_info[0].np_shape[0]
    rng = np.random.default_rng(0)
    f = tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False)
    for row in (rng.random(n), rng.random(n),
                np.full(n, 0.2), np.full(n, 0.2)):
        f.write(" ".join(f"{v:.6f}" for v in row) + "\n")
    f.close()
    return f.name


def main() -> None:
    frames = []
    detections = []

    # stage 1: one pipeline, tee'd — detection branch + raw-frame branch
    p = parse_launch(
        f"videotestsrc num-buffers={N_FRAMES} pattern=random ! "
        f"video/x-raw,format=RGB,width={SIZE},height={SIZE},"
        "framerate=30/1 ! tensor_converter ! tee name=t "
        "t. ! queue ! tensor_filter framework=xla model=ssd_mobilenet_v2 "
        "custom=seed:0,dtype:float32 name=f ! "
        "tensor_decoder mode=bounding_boxes option1=mobilenet-ssd "
        f"option3={priors_file()} option4={SIZE}:{SIZE} "
        f"option5={SIZE}:{SIZE} ! tensor_sink name=det "
        "t. ! queue ! tensor_sink name=raw")
    p.get("det").connect(
        "new-data", lambda b: detections.append(b.extra["objects"]))
    p.get("raw").connect("new-data", lambda b: frames.append(b.np(0)))
    p.run(timeout=600)
    print(f"stage 1: {len(frames)} frames, "
          f"{sum(len(d) for d in detections)} detections "
          "(ssd tail ran on device)")

    # stage 2: detections -> crop regions -> per-crop classification
    cp = Pipeline()
    raw_src = AppSrc("raw", caps=(
        f"other/tensors,format=static,num_tensors=1,"
        f"dimensions=3:{SIZE}:{SIZE},types=uint8,framerate=0/1"))
    info_src = AppSrc("info", caps=(
        "other/tensors,format=static,num_tensors=1,"
        "dimensions=4:4,types=int32,framerate=0/1"))
    crop = TensorCrop("c")
    sink = element_factory("tensor_sink")("crops")
    cp.add(raw_src, info_src, crop, sink)
    raw_src.src_pad.link(crop.sink_pads[0])
    info_src.src_pad.link(crop.sink_pads[1])
    cp.link(crop, sink)

    classifier = FilterSingle(
        framework="xla", model="mobilenet_v2",
        custom="seed:0,dtype:float32,input_size:64")
    with classifier:
        crops_seen = 0
        for frame, objs in zip(frames, detections):
            regions = []
            for o in objs[:4]:                # top regions per frame
                x = int(np.clip(o.xmin, 0, 1) * (SIZE - 1))
                y = int(np.clip(o.ymin, 0, 1) * (SIZE - 1))
                w = max(8, int((np.clip(o.xmax, 0, 1)
                                - np.clip(o.xmin, 0, 1)) * SIZE))
                h = max(8, int((np.clip(o.ymax, 0, 1)
                                - np.clip(o.ymin, 0, 1)) * SIZE))
                regions.append([x, y, min(w, SIZE - x), min(h, SIZE - y)])
            while len(regions) < 4:           # static region count
                regions.append([0, 0, 8, 8])
            raw_src.push_buffer(TensorBuffer(tensors=[frame]))
            info_src.push_buffer(TensorBuffer(
                tensors=[np.asarray(regions, np.int32)]))
        raw_src.end_of_stream()
        info_src.end_of_stream()
        cp.run(timeout=600)

        for buf in cp.get("crops").results:
            for i in range(buf.num_tensors):
                patch = np.asarray(buf.np(i))
                # classifier expects its input size: nearest resize
                ys = (np.linspace(0, patch.shape[0] - 1, 64)).astype(int)
                xs = (np.linspace(0, patch.shape[1] - 1, 64)).astype(int)
                logits, = classifier.invoke([patch[ys][:, xs]])
                crops_seen += 1
    print(f"stage 2: {crops_seen} crops classified "
          f"(last top-1 class {int(np.argmax(logits))})")


if __name__ == "__main__":
    main()
