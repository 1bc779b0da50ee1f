"""One scenario for both families of ``tensor_llm``: the element's loop,
which keeps one decode step in flight, must serve the token streams the
engine's synchronous path (``prefill`` then ``step``, one stream alone)
gives — with streams joining and ending mid-run, one ending by its stop
token, one ending exactly at ``max_seq``, slots reused, and EOS arriving
while a step is in flight.  Not a test module: ``test_llm.py`` and
``test_llm_sambay.py`` call it with their family's sizes."""

import time

import numpy as np

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.filter.framework import FilterProperties
from nnstreamer_tpu.llm.client import encode_request
from nnstreamer_tpu.llm.engine import DecodeEngine
from nnstreamer_tpu.llm.family import family_of_custom
from nnstreamer_tpu.llm.pool import KVCachePool
from nnstreamer_tpu.tensor.buffer import TensorBuffer


def world(custom: str, seed: int):
    """(family, cfg, params) as the element builds them from its
    ``custom=`` and ``seed=``."""
    family, rest = family_of_custom(FilterProperties.parse_custom(custom))
    cfg = family.config_from_custom(rest)
    return family, cfg, family.init_params(cfg, seed)


def sync_stream(eng, prompt, max_new, stop=-1):
    """One stream alone through ``prefill`` and the synchronous
    ``step``, ended as the element ends it."""
    sess = eng.pool.acquire("alone")
    try:
        toks = [eng.prefill(sess, prompt)]
        while len(toks) < max_new and not (stop >= 0 and toks[-1] == stop):
            toks += eng.step([sess])
        return toks
    finally:
        eng.pool.release("alone")


def requests_for(cfg, frame_len: int, eng, seed: int = 5):
    """Six requests ``(prompt, max_new, stop_token)`` and the stream
    each must be served: [2] ends by a stop token taken from the middle
    of its own continuation, [4] fills its slot to ``max_seq`` exactly,
    the others differ in length so they leave at different steps."""
    rng = np.random.default_rng(seed)
    room = frame_len - 3
    shapes = [(5, 9), (9, 4), (7, 12), (3, 6), (room, cfg.max_seq - room),
              (11, 7)]
    out = []
    for i, (plen, max_new) in enumerate(shapes):
        while True:
            prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
            want = sync_stream(eng, prompt, max_new)
            # a token first seen mid-stream, neither first nor last (a
            # toy model repeats itself: draw until a stream has one)
            fresh = [t for k, t in enumerate(want[:-2])
                     if k >= 2 and t not in want[:k]]
            if i != 2 or fresh:
                break
        stop = -1
        if i == 2:
            stop = fresh[0]
            want = want[:want.index(stop) + 1]
        out.append(((prompt, max_new, stop), want))
    return out


def serve(custom: str, seed: int, props: str, frame_len: int, requests,
          drain: bool = False):
    """The requests through the element: the first three at once, the
    rest once the first stream is three tokens in (they join mid-run, or
    wait for a slot), then EOS while steps are in flight — or, with
    ``drain``, the element's drain hook.  Returns the streams by request
    index, as ``(pts, token, more)`` lists, and the engine's report."""
    p = parse_launch(
        "appsrc name=src caps=other/tensors,format=static,num_tensors=1,"
        f"dimensions={frame_len},types=int32,framerate=0/1 ! "
        f"tensor_llm name=llm custom={custom} seed={seed} {props} "
        "max-new-tokens=64 admit-timeout-ms=60000 ! tensor_sink name=out")
    got = {}
    p.get("out").connect("new-data", lambda b: got.setdefault(
        b.extra["tag"], []).append(
            (b.pts, int(np.asarray(b.tensors[0]).reshape(-1)[0]),
             bool(b.extra.get("nns_more")))))
    p.play()
    try:
        src, llm = p.get("src"), p.get("llm")

        def push(i):
            prompt, max_new, stop = requests[i][0]
            buf = TensorBuffer(tensors=[encode_request(
                prompt, max_new=max_new, stop_token=stop,
                frame_len=frame_len)])
            buf.extra["tag"] = i
            src.push_buffer(buf)

        for i in range(3):
            push(i)
        deadline = time.monotonic() + 120
        while len(got.get(0, ())) < 3 and time.monotonic() < deadline:
            time.sleep(0.002)
        for i in range(3, len(requests)):
            push(i)
        if drain:
            # every request admitted, then the hook: it returns when
            # the resident streams have ended
            while llm.sessions_total < len(requests) \
                    and time.monotonic() < deadline:
                time.sleep(0.002)
            llm.drain(deadline=120.0)
            report = llm.engine.report()
            live = llm.pool.live
            src.end_of_stream()
            p.wait(timeout=120)
            return got, dict(report, live_after_drain=live)
        src.end_of_stream()
        p.wait(timeout=180)
        return got, llm.engine.report()
    finally:
        p.stop()


def check(got, report, requests, every_lane: bool = True):
    """Every stream whole, in order, marked; the counters agree.
    ``every_lane``: the bucket holds every resident stream, so each is
    in every step."""
    for i, (_, want) in enumerate(requests):
        frames = got[i]
        assert [t for _, t, _ in frames] == want, (i, frames, want)
        assert [q for q, _, _ in frames] == list(range(len(want)))
        assert [m for _, _, m in frames] == [True] * (len(want) - 1) + [
            False]
    tokens = sum(len(want) for _, want in requests)
    assert report["tokens"] == tokens
    assert report["prefills"] == len(requests)
    # the one stream that ends by its stop token before its granted
    # length rode the step that was in flight when the host learnt it
    # (where the pick is round-robin, only if that step had picked it)
    assert report["lanes_discarded"] in ((1,) if every_lane else (0, 1))
    assert 0 < report["steps_ahead"] < report["steps"]
