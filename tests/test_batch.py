"""Micro-batched tensor_filter invoke path.

The ``batch`` property coalesces N frames into ONE device dispatch
(double-buffered, so batch k's d2h overlaps batch k+1's collection) — the
answer to per-frame dispatch RTT bounding streaming throughput on
accelerators.  The reference's hot loop is strictly
one-buffer-one-invoke (tensor_filter.c:631-894); this is a TPU-native
extension, so correctness parity is against the batch=1 path itself:
identical outputs, order, timestamps, and EOS semantics.
"""

import numpy as np
import pytest

from nnstreamer_tpu.models.registry import _MODELS, Model, register_model
from nnstreamer_tpu.tensor.buffer import TensorBuffer
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsInfo
from nnstreamer_tpu.tensor.types import TensorType


@pytest.fixture()
def tiny_model():
    import jax.numpy as jnp

    w = np.arange(32, dtype=np.float32).reshape(4, 8)

    def build(custom):
        def forward(params, x):
            return (jnp.asarray(x, jnp.float32) @ params,)

        return Model(name="tiny_batch", forward=forward, params=w,
                     in_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                     (4,))]),
                     out_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                      (8,))]))

    register_model("tiny_batch")(build)
    yield w
    _MODELS.pop("tiny_batch", None)


CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=4,"
        "types=float32,framerate=0/1")


def _run(pipeline, feeds, pts=None):
    got = []
    pipeline.get("out").connect("new-data", lambda b: got.append(b))
    pipeline.play()
    src = pipeline.get("in")
    for i, arr in enumerate(feeds):
        ts = pts[i] if pts is not None else None
        src.push_buffer(TensorBuffer(tensors=[arr], pts=ts))
    src.end_of_stream()
    pipeline.wait(timeout=60)
    pipeline.stop()
    return got


def _feeds(n):
    rng = np.random.default_rng(7)
    return [rng.standard_normal(4).astype(np.float32) for _ in range(n)]


class TestBatchedInvoke:
    def _launch(self, batch):
        from nnstreamer_tpu import parse_launch

        return parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            f"tensor_filter framework=xla model=tiny_batch batch={batch} "
            "name=f ! tensor_sink name=out")

    @pytest.mark.parametrize("n,batch", [
        (12, 4),   # exact multiple: 3 full batches
        (10, 4),   # EOS flush pads the 2-frame remainder
        (3, 4),    # stream shorter than one batch
        (7, 16),   # batch larger than whole stream
        (33, 32),  # 1-frame EOS tail at a big bucket: the per-frame
                   # flush path (≤ bucket/8), not a 32-wide padded batch
        (2, 64),   # whole stream goes through the flush path
    ])
    def test_matches_unbatched_and_preserves_order(self, tiny_model, n,
                                                   batch):
        feeds = _feeds(n)
        pts = [i * 1000 for i in range(n)]
        ref = _run(self._launch(1), feeds, pts)
        got = _run(self._launch(batch), feeds, pts)
        assert len(got) == len(ref) == n
        for i, (r, g) in enumerate(zip(ref, got)):
            assert g.pts == r.pts == i * 1000
            np.testing.assert_allclose(g.np(0), r.np(0), rtol=1e-5)

    def test_double_buffering_defers_exactly_one_batch(self, tiny_model):
        """Batch k is pushed only when batch k+1 dispatches (or at EOS)."""
        from nnstreamer_tpu import parse_launch

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch batch=4 name=f ! "
            "tensor_sink name=out")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        feeds = _feeds(8)
        for arr in feeds[:4]:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        # first full batch dispatched but held in flight — nothing pushed yet
        import time

        f = p.get("f")
        deadline = time.monotonic() + 10
        while not f._inflight and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(f._inflight) == 1 and len(got) == 0
        for arr in feeds[4:]:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        src.end_of_stream()
        p.wait(timeout=60)
        p.stop()
        assert len(got) == 8

    @pytest.mark.parametrize("n,batch,depth", [
        (24, 4, 3),   # 6 full batches through a 3-deep queue
        (10, 4, 3),   # EOS flush drains a part-full queue + remainder
        (8, 4, 8),    # depth larger than the whole stream: EOS drains all
        (33, 8, 2),   # 1-frame EOS tail behind a 2-deep queue
    ])
    def test_inflight_depth_matches_unbatched(self, tiny_model, n, batch,
                                              depth):
        """A deeper dispatch queue (inflight=K) must change throughput
        only — outputs, order, and timestamps stay identical to the
        per-frame path."""
        from nnstreamer_tpu import parse_launch

        feeds = _feeds(n)
        pts = [i * 1000 for i in range(n)]
        ref = _run(self._launch(1), feeds, pts)
        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            f"tensor_filter framework=xla model=tiny_batch batch={batch} "
            f"inflight={depth} name=f ! tensor_sink name=out")
        got = _run(p, feeds, pts)
        assert len(got) == len(ref) == n
        for i, (r, g) in enumerate(zip(ref, got)):
            assert g.pts == r.pts == i * 1000
            np.testing.assert_allclose(g.np(0), r.np(0), rtol=1e-5)

    def test_inflight_queue_holds_depth_batches(self, tiny_model):
        """With inflight=2, the first TWO full batches are held in the
        dispatch queue; the oldest is pushed only when the third
        dispatches (or at EOS)."""
        import time

        from nnstreamer_tpu import parse_launch

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch batch=4 "
            "inflight=2 name=f ! tensor_sink name=out")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        feeds = _feeds(12)
        f = p.get("f")
        for arr in feeds[:8]:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        deadline = time.monotonic() + 10
        while len(f._inflight) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        # two dispatched batches queued, nothing surfaced yet
        assert len(f._inflight) == 2 and len(got) == 0
        for arr in feeds[8:]:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        src.end_of_stream()
        p.wait(timeout=60)
        p.stop()
        assert len(got) == 12

    def test_inflight_drains_midstream_on_model_update(self, tiny_model):
        """A model-update event behind a DEEP dispatch queue: every
        frame pushed before the event flushes through the OLD weights
        in stream order (queued batches + the collecting partial), and
        every frame after runs the NEW weights — the mid-stream
        _drain_batches path, not the EOS one."""
        import jax.numpy as jnp

        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.models.registry import (_MODELS, Model,
                                                    register_model)
        from nnstreamer_tpu.pipeline.element import CustomEvent

        w2 = np.full((4, 8), 2.0, np.float32)

        @register_model("tiny_batch_b")
        def build_b(custom):
            def forward(params, x):
                return (jnp.asarray(x, jnp.float32) @ params,)

            return Model(name="tiny_batch_b", forward=forward, params=w2,
                         in_info=TensorsInfo(
                             [TensorInfo(TensorType.FLOAT32, (4,))]),
                         out_info=TensorsInfo(
                             [TensorInfo(TensorType.FLOAT32, (8,))]))

        try:
            p = parse_launch(
                f"appsrc caps={CAPS} name=in ! "
                "tensor_filter framework=xla model=tiny_batch batch=4 "
                "inflight=3 is-updatable=true name=f ! "
                "tensor_sink name=out")
            got = []
            p.get("out").connect("new-data", lambda b: got.append(b))
            p.play()
            src = p.get("in")
            feeds = _feeds(20)
            # 10 frames = 2 full batches (queued, depth 3) + 2 collecting
            for arr in feeds[:10]:
                src.push_buffer(TensorBuffer(tensors=[arr]))
            src.push_event(CustomEvent("tensor_filter_update_model",
                                       {"model": "tiny_batch_b"}))
            for arr in feeds[10:]:
                src.push_buffer(TensorBuffer(tensors=[arr]))
            src.end_of_stream()
            p.wait(timeout=60)
            p.stop()
            assert len(got) == 20
            w_old = np.arange(32, dtype=np.float32).reshape(4, 8)
            for i, (f_in, g) in enumerate(zip(feeds, got)):
                want = f_in @ (w_old if i < 10 else w2)
                np.testing.assert_allclose(g.np(0), want, rtol=1e-5)
        finally:
            _MODELS.pop("tiny_batch_b", None)

    def test_model_name_reload_with_pushdown_decoder(self, tiny_model):
        """Model-NAME reload behind a pushdown-fused decoder: the
        close+open swap resets the backend's fused reduction, and the
        element re-applies it (the reload interface check guarantees
        the tensor io is unchanged) — decode results stay correct
        across the swap and the device-fused tail survives."""
        import jax.numpy as jnp

        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.models.registry import (_MODELS, Model,
                                                    register_model)
        from nnstreamer_tpu.pipeline.element import CustomEvent

        # weights chosen so argmax(f(x)) differs between models for
        # one-hot inputs: A routes class i -> i, B routes i -> 7-i
        w_a = np.eye(4, 8, dtype=np.float32) * 10.0
        w_b = np.fliplr(np.eye(4, 8, dtype=np.float32) * 10.0)

        @register_model("tiny_batch_c")
        def build_c(custom):
            def forward(params, x):
                return (jnp.asarray(x, jnp.float32) @ params,)

            return Model(name="tiny_batch_c", forward=forward, params=w_b,
                         in_info=TensorsInfo(
                             [TensorInfo(TensorType.FLOAT32, (4,))]),
                         out_info=TensorsInfo(
                             [TensorInfo(TensorType.FLOAT32, (8,))]))

        import nnstreamer_tpu.models.registry as registry

        # rebind tiny_batch's params to w_a for deterministic argmax
        orig_builder = registry._MODELS["tiny_batch"]

        def build_a(custom):
            m = orig_builder(custom)
            m.params = w_a
            return m

        registry._MODELS["tiny_batch"] = build_a
        try:
            p = parse_launch(
                f"appsrc caps={CAPS} name=in ! "
                "tensor_filter framework=xla model=tiny_batch batch=4 "
                "inflight=2 is-updatable=true name=f ! "
                "tensor_decoder mode=image_labeling ! tensor_sink name=out")
            got = []
            p.get("out").connect("new-data",
                                 lambda b: got.append(b.extra["index"]))
            p.play()
            src = p.get("in")
            onehots = [np.eye(4, dtype=np.float32)[i % 4] for i in range(8)]
            for arr in onehots:
                src.push_buffer(TensorBuffer(tensors=[arr]))
            src.push_event(CustomEvent("tensor_filter_update_model",
                                       {"model": "tiny_batch_c"}))
            for arr in onehots:
                src.push_buffer(TensorBuffer(tensors=[arr]))
            src.end_of_stream()
            p.wait(timeout=60)
            # the device-fused tail must have been re-applied to the
            # swapped backend (not silently dropped to host decode)
            assert p.get("f").fw.has_postprocess()
            p.stop()
            assert len(got) == 16
            for i in range(8):
                assert got[i] == i % 4, (i, got[i])
            for i in range(8):
                assert got[8 + i] == 7 - (i % 4), (i, got[8 + i])
        finally:
            registry._MODELS["tiny_batch"] = orig_builder
            _MODELS.pop("tiny_batch_c", None)

    def test_same_model_reload_does_not_double_fuse(self, tiny_model):
        """Params-only reload (same model name, xla fast path): the
        backend keeps its fused executable, and the element must NOT
        re-apply the reduction — set_postprocess composes over the
        forward fn, so a second application would argmax the argmax."""
        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.pipeline.element import CustomEvent

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch batch=4 "
            "inflight=2 is-updatable=true name=f ! "
            "tensor_decoder mode=image_labeling ! tensor_sink name=out")
        got = []
        p.get("out").connect("new-data",
                             lambda b: got.append(b.extra["index"]))
        p.play()
        src = p.get("in")
        # the decoder's pushdown must actually be fused BEFORE the
        # reload, or this test passes vacuously on the host-decode path
        import time

        deadline = time.monotonic() + 10
        while (not p.get("f").fw.has_postprocess()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert p.get("f").fw.has_postprocess()
        onehots = [np.eye(4, dtype=np.float32)[i % 4] for i in range(8)]
        for arr in onehots:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        src.push_event(CustomEvent("tensor_filter_update_model",
                                   {"model": "tiny_batch"}))
        for arr in onehots:
            src.push_buffer(TensorBuffer(tensors=[arr]))
        src.end_of_stream()
        p.wait(timeout=60)
        p.stop()
        assert len(got) == 16
        # tiny_batch is x @ arange(32): one-hot i selects row i, whose
        # argmax is always column 7
        assert all(v == 7 for v in got), got

    def test_inflight_without_batching_is_clamped(self, tiny_model):
        """inflight>1 without micro-batching has nothing to queue: warn
        and run per-frame (inert perf prop, reference behavior)."""
        from nnstreamer_tpu import parse_launch

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch inflight=4 "
            "name=f ! tensor_sink name=out")
        feeds = _feeds(5)
        got = _run(p, feeds)
        assert p.get("f")._inflight_depth == 1
        assert len(got) == 5

    def test_batched_with_output_combination(self, tiny_model):
        from nnstreamer_tpu import parse_launch

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch batch=4 "
            "output-combination=0/0 name=f ! tensor_sink name=out")
        feeds = _feeds(6)
        got = _run(p, feeds)
        assert len(got) == 6
        w = np.arange(32, dtype=np.float32).reshape(4, 8)
        for f_in, g in zip(feeds, got):
            assert g.num_tensors == 2
            np.testing.assert_allclose(g.np(0), f_in, rtol=1e-6)
            np.testing.assert_allclose(g.np(1), f_in @ w, rtol=1e-5)

    def test_batch_ignored_for_nonbatching_backend(self, tiny_model):
        """Backends without SUPPORTS_BATCHING silently fall back to the
        per-frame path (reference behavior: unknown perf props are inert)."""
        from nnstreamer_tpu import parse_launch
        from nnstreamer_tpu.filter.backends.custom import DummyFilter

        assert not DummyFilter.SUPPORTS_BATCHING
        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=dummy model=passthrough batch=4 "
            "input-dim=4 input-type=float32 output-dim=4 "
            "output-type=float32 name=f ! tensor_sink name=out")
        feeds = _feeds(5)
        got = _run(p, feeds)
        assert p.get("f")._batch == 1
        assert len(got) == 5

    def test_batched_pushdown_fusion(self, tiny_model):
        """Device-reduce pushdown composes with batching: the vmapped
        executable includes the fused reduction after the event."""
        from nnstreamer_tpu import parse_launch

        p = parse_launch(
            f"appsrc caps={CAPS} name=in ! "
            "tensor_filter framework=xla model=tiny_batch batch=4 name=f ! "
            "tensor_decoder mode=image_labeling ! tensor_sink name=out")
        feeds = [np.eye(4, dtype=np.float32)[i % 4] for i in range(9)]
        got = _run(p, feeds)
        assert len(got) == 9
        w = np.arange(32, dtype=np.float32).reshape(4, 8)
        for f_in, g in zip(feeds, got):
            assert g.extra["index"] == int(np.argmax(f_in @ w))
