"""Image classification pipeline (BASELINE config 1).

videotestsrc → tensor_converter → tensor_filter (MobileNetV2, batch=8) →
image_labeling → tensor_sink.  When the reference checkout is present the
real ImageNet weights are imported from its quant tflite on first run.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402

REF = "/root/reference/tests/test_models"
CKPT = "/tmp/nns_tpu_mobilenet_ckpt"


def checkpoint_props() -> str:
    """Import real weights once, if the reference artifacts exist."""
    tfl = os.path.join(REF, "models", "mobilenet_v2_1.0_224_quant.tflite")
    if not os.path.isfile(tfl):
        return ""
    if not os.path.isdir(CKPT):
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "tools"))
        from tflite_weights import import_weights

        import_weights("mobilenet_v2", tfl, CKPT)
    return f",checkpoint:{CKPT},dtype:float32"


def main() -> None:
    # any registry classifier works here; `vit` swaps in the
    # attention-family model (Pallas flash encoder on TPU)
    model = sys.argv[1] if len(sys.argv) > 1 else "mobilenet_v2"
    props = checkpoint_props() if model == "mobilenet_v2" else ""
    labels = os.path.join(REF, "labels", "labels.txt")
    label_opt = f"option1={labels}" if os.path.isfile(labels) else ""
    p = parse_launch(
        "videotestsrc num-buffers=32 pattern=gradient ! "
        "video/x-raw,format=RGB,width=224,height=224,framerate=30/1 ! "
        "tensor_converter ! "
        f"tensor_filter framework=xla model={model} "
        f"custom=seed:0{props} batch=8 ! "
        "queue ! "
        f"tensor_decoder mode=image_labeling {label_opt} ! "
        "tensor_sink name=out")
    p.get("out").connect(
        "new-data",
        lambda b: print(f"pts={b.pts}  class={b.extra['index']}"
                        f"  label={b.extra.get('label')}"))
    p.run(timeout=600)


if __name__ == "__main__":
    main()
