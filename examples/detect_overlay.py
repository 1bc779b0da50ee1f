"""Object detection with a drawn overlay (BASELINE config 2).

SSD-MobileNetV2 → bounding_boxes decoder (box-prior decode, NMS, label
sprites) → RGBA overlay written to /tmp/overlay.rgba.
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.models.registry import (get_model,  # noqa: E402
                                            graft_params, restore_params,
                                            save_checkpoint)

REF = "/root/reference/tests/test_models"
MNET_CKPT = "/tmp/nns_tpu_mobilenet_ckpt"
SSD_CKPT = "/tmp/nns_tpu_ssd_graft_ckpt"


def grafted_checkpoint_props() -> str:
    """When the reference artifacts exist, graft the REAL ImageNet
    MobileNetV2 trunk under the SSD head (the heads stay untrained — the
    reference zoo ships no SSD weights either), so decode sees
    real-graph activation scales."""
    tfl = os.path.join(REF, "models", "mobilenet_v2_1.0_224_quant.tflite")
    if not os.path.isfile(tfl):
        return "seed:0"
    if os.path.isdir(SSD_CKPT):
        # cached from an earlier run: make sure it still matches the
        # CURRENT model definition before trusting it
        import shutil

        try:
            ssd = get_model("ssd_mobilenet_v2",
                            {"seed": "0", "dtype": "float32"})
            restore_params(ssd.params, SSD_CKPT)
        except Exception:
            shutil.rmtree(SSD_CKPT, ignore_errors=True)
    if not os.path.isdir(SSD_CKPT):
        if not os.path.isdir(MNET_CKPT):
            sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                            "..", "tools"))
            from tflite_weights import import_weights

            import_weights("mobilenet_v2", tfl, MNET_CKPT)
        mnet = get_model("mobilenet_v2", {"seed": "0", "dtype": "float32"})
        real = restore_params(mnet.params, MNET_CKPT)
        ssd = get_model("ssd_mobilenet_v2",
                        {"seed": "0", "dtype": "float32"})
        ssd.params, n = graft_params(ssd.params, real)
        if n < 100:
            # trunk naming drifted — better a random demo than a stale
            # checkpoint masquerading as real weights
            print(f"graft matched only {n} leaves; using fresh init")
            return "seed:0"
        print(f"grafted {n} real-trunk leaves under the SSD head")
        save_checkpoint(ssd, SSD_CKPT)
    return f"seed:0,checkpoint:{SSD_CKPT},dtype:float32"


def priors_file(n: int) -> str:
    """Synthetic box priors (a real deployment loads the model's
    box_priors.txt, reference tests/test_models/data)."""
    rng = np.random.default_rng(0)
    f = tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False)
    for row in (rng.random(n), rng.random(n),
                np.full(n, 0.2), np.full(n, 0.2)):
        f.write(" ".join(f"{v:.6f}" for v in row) + "\n")
    f.close()
    return f.name


def main() -> None:
    n_anchors = get_model("ssd_mobilenet_v2",
                          {"seed": "0"}).out_info[0].np_shape[0]
    labels = tempfile.NamedTemporaryFile("w", suffix=".txt", delete=False)
    labels.write("\n".join(f"class{i}" for i in range(91)))
    labels.close()
    p = parse_launch(
        "videotestsrc num-buffers=8 pattern=random ! "
        "video/x-raw,format=RGB,width=300,height=300,framerate=30/1 ! "
        "tensor_converter ! "
        "tensor_filter framework=xla model=ssd_mobilenet_v2 "
        f"custom={grafted_checkpoint_props()} ! "
        "tensor_decoder mode=bounding_boxes option1=mobilenet-ssd "
        f"option2={labels.name} option3={priors_file(n_anchors)} "
        "option4=640:480 option5=300:300 option6=0.3 ! "
        "tensor_sink name=out")
    frames = []
    p.get("out").connect("new-data", lambda b: frames.append(b))
    p.run(timeout=600)
    overlay = frames[-1].np(0)
    out = "/tmp/overlay.rgba"
    overlay.tofile(out)
    objs = frames[-1].extra["objects"]
    print(f"{len(frames)} frames; last frame: {len(objs)} detections "
          f"→ {out} ({overlay.shape})")
    for o in objs[:5]:
        print(f"  {o.label or o.class_id}: score={o.score:.2f} "
              f"box=({o.ymin:.2f},{o.xmin:.2f},{o.ymax:.2f},{o.xmax:.2f})")


if __name__ == "__main__":
    main()
