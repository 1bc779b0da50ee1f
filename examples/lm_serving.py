"""LM serving two ways: a token-stream pipeline and the generate() API.

1. Pipeline: appsrc pushes token windows through ``tensor_filter
   framework=xla model=streamformer_lm`` (full-sequence next-token
   logits, the Pallas flash-attention prefill path on TPU); the sink
   callback reads the last position's argmax as the next token.
2. API: KV-cache incremental decoding — the whole prompt prefill +
   continuation runs as ONE compiled ``lax.scan`` (models/streamformer_lm
   ``generate``), so repeat calls skip XLA entirely.

No reference analogue (the reference has no LM path) — this is the
net-new long-context serving axis.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.tensor.buffer import TensorBuffer  # noqa: E402

SEQ = 64


def pipeline_logits() -> None:
    """Token windows in, per-position logits out; the next token is the
    argmax at the LAST position of each window."""
    p = parse_launch(
        "appsrc caps=other/tensors,format=static,num_tensors=1,"
        f"dimensions={SEQ},types=int32,framerate=0/1 name=in ! "
        f"tensor_filter framework=xla model=streamformer_lm "
        f"custom=seq:{SEQ},vocab:256,seed:0 ! "
        "tensor_sink name=out")
    results = []
    p.get("out").connect(
        "new-data",
        lambda b: results.append(int(np.asarray(b.tensors[0])[-1].argmax())))
    p.play()
    rng = np.random.default_rng(0)
    for _ in range(3):
        window = rng.integers(0, 256, (SEQ,), dtype=np.int32)
        p.get("in").push_buffer(TensorBuffer(tensors=[window]))
    p.get("in").end_of_stream()
    p.wait(timeout=600)
    p.stop()
    print(f"pipeline: next token per window = {results}")


def api_generate() -> None:
    import jax.numpy as jnp

    from nnstreamer_tpu.models.streamformer_lm import generate
    from nnstreamer_tpu.parallel.train_step import (StreamFormerConfig,
                                                    init_params)

    cfg = StreamFormerConfig(vocab=256, dim=128, heads=8, head_dim=16,
                             mlp=512, layers=2, experts=2, max_seq=128,
                             dtype=jnp.bfloat16)
    params = init_params(cfg, 0)
    prompt = np.arange(16, dtype=np.int32)
    t0 = time.monotonic()
    toks = generate(params, cfg, prompt, n_tokens=32)   # compiles
    t1 = time.monotonic()
    toks = generate(params, cfg, prompt, n_tokens=32)   # cached program
    t2 = time.monotonic()
    print(f"generate: {toks[:8]}... "
          f"(compile+run {t1 - t0:.2f}s, cached run {t2 - t1:.3f}s, "
          f"{32 / (t2 - t1):.1f} tok/s)")


if __name__ == "__main__":
    pipeline_logits()
    api_generate()
