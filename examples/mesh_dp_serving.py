"""Multi-chip data-parallel serving from the stream (custom=mesh:dp=N).

The reference's among-device story offloads whole sub-pipelines to
other devices over TCP (tensor_query_client.c:656-743).  The TPU-native
superset needs no second pipeline: `tensor_filter custom=mesh:dp=N`
makes the ONE batched serving executable span an N-device ("dp",)
jax mesh — params replicated, the stream micro-batch split along axis 0
by XLA's partitioner.  This example runs the same frames through the
single-device and the dp=4-sharded pipelines and checks the outputs are
identical (they are the SAME executable semantics, just placed wider).

Run (virtual 4-device CPU mesh — the same strategy the test suite and
the driver's dryrun use for multi-chip validation without hardware):

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python examples/mesh_dp_serving.py

On a real multi-chip TPU host the same launch line shards over real
chips; collectives ride ICI.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402

N_FRAMES = 24
BATCH = 8


def run(mesh_prop: str):
    labels = []
    p = parse_launch(
        f"videotestsrc num-buffers={N_FRAMES} pattern=random cache-frames=8 ! "
        "video/x-raw,format=RGB,width=224,height=224,framerate=30/1 ! "
        "tensor_converter ! "
        "tensor_filter framework=xla model=mobilenet_v2 "
        f"custom=seed:0{mesh_prop} batch={BATCH} name=f ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out")
    p.get("out").connect("new-data",
                         lambda b: labels.append(b.extra.get("index")))
    p.run(timeout=300)
    return labels


def main() -> int:
    import jax

    n_dev = len(jax.devices())
    # largest dp <= 4 that divides BATCH (the element requires an even
    # split; a 3-device host clamps to dp=2)
    dp = next((d for d in (4, 2) if d <= n_dev and BATCH % d == 0), 1)
    if dp < 2:
        print(f"need >=2 devices for a dp mesh, have {n_dev} — "
              "set XLA_FLAGS=--xla_force_host_platform_device_count=4")
        return 1

    single = run("")
    sharded = run(f",mesh:dp={dp}")
    assert len(single) == len(sharded) == N_FRAMES, (
        f"{len(single)} vs {len(sharded)} of {N_FRAMES}")
    assert single == sharded, "sharded serving diverged from single-device"
    uniq = sorted(set(single))
    print(f"OK: {N_FRAMES} frames, dp={dp} sharded == single-device "
          f"(labels seen: {uniq})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
