"""On-device training from a file dataset (reference tensor_trainer +
datareposrc pattern, gstdatareposrc.c:15-21).

A synthetic dataset file streams through the native prefetch reader into
tensor_trainer, which runs a jitted Adam step per batch and writes a
checkpoint at EOS.
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402


def make_dataset(path: str, n: int = 64) -> None:
    """Frames of (8 features, 4 one-hot labels) — linearly separable."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 4)).astype(np.float32)
    rows = []
    for _ in range(n):
        x = rng.standard_normal(8).astype(np.float32)
        y = np.zeros(4, np.float32)
        y[int((x @ w).argmax())] = 1.0
        rows.append(x.tobytes() + y.tobytes())
    with open(path, "wb") as f:
        f.write(b"".join(rows))


def main() -> None:
    with tempfile.NamedTemporaryFile(suffix=".dat", delete=False) as f:
        data = f.name
    make_dataset(data)
    ckpt = os.path.join(tempfile.mkdtemp(), "model")
    p = parse_launch(
        f"datareposrc location={data} input-dim=8,4 "
        "input-type=float32,float32 epochs=2 ! "
        f"tensor_trainer name=tr num-inputs=1 num-labels=1 batch-size=8 "
        f"lr=0.01 model-save-path={ckpt} ! "
        "tensor_sink name=out")
    p.run(timeout=600)
    tr = p.get("tr")
    print("summary:", tr.summary)
    print("loss first→last:",
          f"{tr.trainer.losses[0]:.4f} → {tr.trainer.losses[-1]:.4f}")
    print("checkpoint:", ckpt, os.path.isdir(ckpt) or os.path.exists(ckpt))
    os.unlink(data)


if __name__ == "__main__":
    main()
