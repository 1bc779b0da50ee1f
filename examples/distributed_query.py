"""Among-device offload: a client pipeline sends frames to a server
pipeline that runs inference and answers (BASELINE config 5 pattern;
reference tensor_query_client/server over localhost, the two-process
strategy of tests/nnstreamer_edge/query/runTest.sh).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from nnstreamer_tpu import parse_launch  # noqa: E402
from nnstreamer_tpu.query.server import shutdown_server  # noqa: E402

SERVER_ID = 7
CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=3:224:224:1,"
        "types=uint8,framerate=30/1")


def main() -> None:
    # the serving pipeline: frames arrive from remote clients, run through
    # the model, answers route back by client id
    srv = parse_launch(
        f"tensor_query_serversrc name=qsrc id={SERVER_ID} port=0 "
        f"caps={CAPS} ! "
        "tensor_filter framework=xla model=mobilenet_v2 custom=seed:0 ! "
        f"tensor_query_serversink id={SERVER_ID}")
    srv.play()
    port = srv.get("qsrc").bound_port

    # the client pipeline: offloads inference to the server
    cli = parse_launch(
        "videotestsrc num-buffers=8 pattern=checkers ! "
        "video/x-raw,format=RGB,width=224,height=224,framerate=30/1 ! "
        "tensor_converter ! "
        f"tensor_query_client port={port} timeout=60 ! "
        "tensor_decoder mode=image_labeling ! tensor_sink name=out")
    cli.get("out").connect(
        "new-data", lambda b: print(f"pts={b.pts} class={b.extra['index']}"))
    cli.run(timeout=600)
    srv.stop()
    shutdown_server(SERVER_ID)


if __name__ == "__main__":
    main()
