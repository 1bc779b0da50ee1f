"""Speech-command recognition: wav file → TF graph → label.

The whole audio front-end (DecodeWav host hoist, Hann-window spectrogram,
TF mel-filterbank MFCC) plus the conv net run as ONE XLA executable inside
``tensor_filter framework=tensorflow`` — the reference's
tests/nnstreamer_filter_tensorflow case 3 as a runnable example.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402

REF = "/root/reference/tests/test_models"
LABELS = ["silence", "unknown", "yes", "no", "up", "down",
          "left", "right", "on", "off", "stop", "go"]


def main() -> None:
    model = os.path.join(REF, "models", "conv_actions_frozen.pb")
    wav = os.path.join(REF, "data", "yes.wav")
    if not (os.path.isfile(model) and os.path.isfile(wav)):
        print("reference checkout not present; nothing to run")
        return
    p = parse_launch(
        f"filesrc location={wav} blocksize=-1 ! application/octet-stream ! "
        "tensor_converter input-dim=1:16022 input-type=int16 ! "
        f"tensor_filter framework=tensorflow model={model} "
        "input-dim=1:16022 input-type=int16 "
        "output-dim=12:1 output-type=float32 "
        "custom=inputname:wav_data,outputname:labels_softmax ! "
        "tensor_sink name=out")

    def report(buf):
        sm = np.asarray(buf.tensors[0]).ravel()
        k = int(sm.argmax())
        print(f"heard: {LABELS[k]!r}  (p={sm[k]:.3f})")

    p.get("out").connect("new-data", report)
    p.run(timeout=300)


if __name__ == "__main__":
    main()
