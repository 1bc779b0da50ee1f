"""Load-generator child process: the clients of one run, outside the
process that holds the chip (its threads would share the server's GIL).

    python3 -m benchmarks.loadgen        (started by a driver, never by hand)

Protocol, over the child's own pipes: the parent writes one JSON plan
line; the child builds its clients, dials the persistent ones, prints
``ready``; the parent writes ``go <t0>`` (``t0`` on ``time.monotonic()``,
one clock for every process of the machine); the child runs its part of
the traffic and prints one JSON result line.

Imports the wire clients and numpy, never JAX.  Latency is taken from
when a request was DUE, not from when it was sent, and every request
records how late it was sent (``slo/loadgen.py``'s discipline).
"""

from __future__ import annotations

import json
import sys
import threading
import time
from typing import Any, Dict, List

import numpy as np


def _sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


# -- token streams ------------------------------------------------------------

def _stream(cli, req: Dict[str, Any], plan: Dict[str, Any],
            hard_end: float, dial: bool = False) -> Dict[str, Any]:
    """One request through ``cli`` (dialled first when ``dial``: a user
    arriving); the record the parent reduces."""
    from benchmarks.traffic import prompt_tokens
    from nnstreamer_tpu.llm.client import TokenTimeoutError
    from nnstreamer_tpu.query.overload import ShedError

    rec = {"id": req["id"], "due": req["abs_due"], "ok": False,
           "outcome": "error", "stamps": [], "tokens": [],
           "prompt_len": req["prompt_len"], "max_new": req["max_new"]}
    prompt = prompt_tokens(req, plan["vocab"])
    # before dialling: lateness is the generator's, the dial is the
    # user's own wait and belongs to the latency
    rec["sent"] = time.monotonic()
    try:
        if dial:
            cli.connect()
        for _, tok in cli.stream(prompt, req["max_new"], req["stop_token"],
                                 frame_len=plan["frame_len"]):
            rec["tokens"].append(tok)
            if time.monotonic() >= hard_end:
                rec["outcome"] = "cut"
                break
        else:
            rec["ok"] = (len(rec["tokens"]) == req["max_new"]
                         and min(rec["tokens"]) >= 0)
            rec["outcome"] = "done" if rec["ok"] else "short"
    except ShedError:
        rec["outcome"] = "shed"
    except TokenTimeoutError:
        rec["outcome"] = "timeout"
    except (ConnectionError, OSError, ValueError) as exc:
        rec["outcome"] = f"error: {type(exc).__name__}"
    rec["stamps"] = [ns / 1e9 for ns in cli.stamps_ns[:len(rec["tokens"])]]
    return rec


def _token_client(plan: Dict[str, Any]):
    from nnstreamer_tpu.llm.client import TokenStreamClient

    return TokenStreamClient(plan["host"], plan["port"],
                             timeout=plan["timeout_s"], qos=plan.get("qos"),
                             token_timeout=plan["token_timeout_s"])


def run_token_open(plan: Dict[str, Any], t0: float) -> List[Dict[str, Any]]:
    """Open loop: each request is sent when it is due, on a connection
    and a thread of its own (a user arriving), whatever the server is
    doing with the earlier ones."""
    hard_end = t0 + plan["seconds"] + plan["drain_s"]
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def one(req):
        cli = _token_client(plan)
        try:
            rec = _stream(cli, req, plan, hard_end, dial=True)
        finally:
            cli.close()
        with lock:
            records.append(rec)

    threads = []
    for req in plan["requests"]:
        req["abs_due"] = t0 + req["due"]
        _sleep_until(req["abs_due"])
        th = threading.Thread(target=one, args=(req,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=max(0.0, hard_end - time.monotonic())
                + plan["token_timeout_s"] + 5.0)
    return records


def run_token_closed(plan: Dict[str, Any], clients, t0: float
                     ) -> List[Dict[str, Any]]:
    """Closed loop: each client sends its next request when its last
    stream ended, from the ramp's start until the window's end."""
    from benchmarks.traffic import closed_token_request

    start = t0 - plan["ramp_s"]
    end = t0 + plan["seconds"]
    records: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def loop(client: int, cli) -> None:
        _sleep_until(start)
        k = 0
        while time.monotonic() < end:
            req = closed_token_request(plan["traffic"], plan["seed"],
                                       client, k)
            req["abs_due"] = time.monotonic()
            rec = _stream(cli, req, plan, end)
            rec["client"] = client
            with lock:
                records.append(rec)
            if rec["outcome"] not in ("done", "cut"):
                # a refused or broken stream: do not spin on it
                time.sleep(0.05)
            k += 1

    threads = [threading.Thread(target=loop, args=(c, cli), daemon=True)
               for c, cli in clients]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(0.0, end - time.monotonic())
                + plan["token_timeout_s"] + 5.0)
    return records


# -- camera frames ------------------------------------------------------------

def run_cameras(plan: Dict[str, Any], cams, t0: float
                ) -> List[Dict[str, Any]]:
    """Open loop per camera: a frame every ``1/fps`` seconds from the
    camera's phase offset, one outstanding query per connection (the
    query client's discipline), latency from the frame's due time."""
    from nnstreamer_tpu.query.overload import ShedError
    from nnstreamer_tpu.tensor.buffer import TensorBuffer

    period = 1.0 / plan["fps"]
    start = t0 - plan["ramp_s"]
    end = t0 + plan["seconds"]
    keep = {tuple(k) for k in plan.get("keep_logits", [])}
    out: List[Dict[str, Any]] = []
    lock = threading.Lock()

    def loop(cam: Dict[str, Any], conn, frames: np.ndarray) -> None:
        rec = {"cam": cam["id"], "due": [], "sent": [], "done": [],
               "ok": [], "outcome": {}, "label": [], "logits": {}}
        k = 0
        while True:
            due = start + cam["phase"] + k * period
            if due >= end:
                break
            _sleep_until(due)
            sent = time.monotonic()
            ok, done, label = False, None, -1
            try:
                reply = conn.query(TensorBuffer(
                    tensors=[frames[k % len(frames)]]))
                done = time.monotonic()
                if reply is not None:
                    logits = np.asarray(reply.np(0),
                                        np.float32).reshape(-1)
                    label, ok = int(logits.argmax()), True
                    if (cam["id"], k) in keep:
                        rec["logits"][str(k)] = logits.tolist()
            except ShedError:
                rec["outcome"]["shed"] = rec["outcome"].get("shed", 0) + 1
            except (TimeoutError, ConnectionError, OSError) as exc:
                name = type(exc).__name__
                rec["outcome"][name] = rec["outcome"].get(name, 0) + 1
            rec["due"].append(due)
            rec["sent"].append(sent)
            rec["done"].append(done)
            rec["ok"].append(ok)
            rec["label"].append(label)
            k += 1
        with lock:
            out.append(rec)

    threads = [threading.Thread(target=loop, args=c, daemon=True)
               for c in cams]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=max(0.0, end - time.monotonic())
                + plan["timeout_s"] + 5.0)
    return out


# -- entry --------------------------------------------------------------------

def main() -> int:
    plan = json.loads(sys.stdin.readline())
    kind = plan["kind"]
    # everything a request will import, before "ready": the first
    # request must not pay for it
    import benchmarks.traffic  # noqa: F401
    import nnstreamer_tpu.llm.client  # noqa: F401
    import nnstreamer_tpu.query.client  # noqa: F401

    prepared: Any = None
    if kind == "token_closed":
        prepared = [(c, _token_client(plan).connect())
                    for c in plan["clients"]]
    elif kind == "cameras":
        from benchmarks.traffic import camera_frames
        from nnstreamer_tpu.query.client import QueryConnection

        prepared = []
        for cam in plan["cameras"]:
            conn = QueryConnection(plan["host"], plan["port"],
                                   timeout=plan["timeout_s"])
            conn.connect()
            prepared.append((cam, conn, camera_frames(
                plan["seed"], cam["id"], plan["frame_pool"],
                plan["frame_shape"])))
    elif kind != "token_open":
        raise ValueError(f"unknown plan kind {kind!r}")
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    if len(go) != 2 or go[0] != "go":
        return 2                       # the parent gave up before the start
    t0 = float(go[1])
    try:
        if kind == "token_open":
            records = run_token_open(plan, t0)
        elif kind == "token_closed":
            records = run_token_closed(plan, prepared, t0)
        else:
            records = run_cameras(plan, prepared, t0)
    finally:
        for item in prepared or ():
            item[1].close()
    print(json.dumps({"records": records}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
