"""Token-streaming LLM serving: continuous batching over the query wire.

One launch string serves N concurrent token streams from a single
device loop (``nnstreamer_tpu/llm``): ``tensor_query_serversrc``
admits prompt requests (QoS + queue-depth admission unchanged),
``tensor_llm`` holds one KV-cache slot per live stream and advances
EVERY resident sequence per padded device step (vLLM-style continuous
batching — sequences join after their flash-path prefill, leave on
stop-token/max-new/disconnect), and ``tensor_query_serversink``
streams the per-token ``[1, 1]`` reply frames back in exact per-client
order.

No reference analogue — this is the stateful serving tier the
request/response plane grew into.  Run with ``--trace`` flags via
launch.py for the merged prefill/decode timeline.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.llm.client import TokenStreamClient  # noqa: E402
from nnstreamer_tpu.query.server import shutdown_server  # noqa: E402

SID = 71
REQ_CAP = 96
CUSTOM = ("vocab:512,dim:256,heads:8,head_dim:32,mlp:1024,layers:4,"
          "max_seq:512,dtype:float32")


def main() -> None:
    p = parse_launch(
        f"tensor_query_serversrc name=qsrc id={SID} port=0 "
        f"caps=other/tensors,format=static,num_tensors=1,"
        f"dimensions={REQ_CAP},types=int32,framerate=0/1 ! "
        f"tensor_llm name=llm custom={CUSTOM} slots=8 batch=4 "
        f"id={SID} ! "
        f"tensor_query_serversink id={SID}")
    p.play()
    port = p.get("qsrc").bound_port
    print(f"serving on 127.0.0.1:{port}")

    results = {}

    def run(i: int) -> None:
        cli = TokenStreamClient("127.0.0.1", port, timeout=60.0)
        cli.connect()
        try:
            rng = np.random.default_rng(i)
            prompt = rng.integers(0, 512, 6 + 4 * i).astype(np.int32)
            t0 = time.monotonic()
            toks = cli.generate(prompt, max_new=24 + 8 * i,
                                frame_len=REQ_CAP)
            results[i] = (toks, time.monotonic() - t0)
        finally:
            cli.close()

    threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, (toks, dt) in sorted(results.items()):
        print(f"client {i}: {len(toks)} tokens in {dt:.2f}s "
              f"({len(toks) / dt:.1f} tok/s) head={toks[:6]}")
    report = p.get("llm").engine.report()
    print(f"engine: mean fill {report['mean_fill']}, "
          f"{report['tokens']} tokens, phases "
          f"{report['phases']['states_pct']}")
    p.stop()
    shutdown_server(SID)


if __name__ == "__main__":
    main()
