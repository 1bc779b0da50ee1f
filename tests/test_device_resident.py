"""Device-resident streaming: frames live in HBM for their pipeline life.

TPU-native extension (no reference counterpart; the closest discipline is
the zero-copy mapping rule of tensor_filter.c:631-894): ``videotestsrc
device-cache=N`` stages N rendered frames to the default jax device ONCE,
then cycles the device handles; tensor_converter passes device payloads
through untouched; the filter's micro-batch path stacks device inputs ON
DEVICE (one tiny dispatch) instead of syncing to host and re-uploading.
Net effect: zero h2d payload bytes per frame — throughput is bound by
dispatch latency and device compute, not host-to-device bandwidth.

All tests run on the CPU jax backend (conftest): a CPU jax.Array exercises
the identical handle-passthrough/stacking code paths.
"""

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.models.registry import _MODELS, Model, register_model
from nnstreamer_tpu.tensor.buffer import is_device_array
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsInfo
from nnstreamer_tpu.tensor.types import TensorType

VIDEO_CAPS = ("video/x-raw,format=RGB,width=8,height=8,framerate=30/1")


@pytest.fixture()
def pixel_model():
    """(8,8,3) u8 video tensor -> (8,) f32 logits; deterministic."""
    import jax.numpy as jnp

    w = np.linspace(-1.0, 1.0, 8 * 8 * 3 * 8, dtype=np.float32)
    w = w.reshape(8 * 8 * 3, 8)

    def build(custom):
        def forward(params, x):
            flat = jnp.asarray(x, jnp.float32).reshape(-1)
            return (flat @ params,)

        return Model(name="pixel8", forward=forward, params=w,
                     in_info=TensorsInfo([TensorInfo(TensorType.UINT8,
                                                     (3, 8, 8))]),
                     out_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                      (8,))]))

    register_model("pixel8")(build)
    yield
    _MODELS.pop("pixel8", None)


def _collect(line, n_expected, grab=lambda b: np.asarray(b.tensors[0]).copy()):
    got = []
    p = parse_launch(line)
    p.get("out").connect("new-data", lambda b: got.append(grab(b)))
    p.run(timeout=60)
    assert len(got) == n_expected
    return got


class TestDeviceCacheSource:
    def test_emits_device_handles_and_cycles(self):
        handles = []
        p = parse_launch(
            "videotestsrc num-buffers=6 pattern=random device-cache=3 ! "
            f"{VIDEO_CAPS} ! tensor_converter ! tensor_sink name=out")
        p.get("out").connect("new-data",
                             lambda b: handles.append(b.tensors[0]))
        p.run(timeout=60)
        assert len(handles) == 6
        assert all(is_device_array(h) for h in handles)
        # converter passed the SAME HBM handle through (no copy, no sync)
        assert handles[0] is handles[3]
        assert handles[2] is handles[5]
        # distinct cached frames differ; device render == host render
        a, b = np.asarray(handles[0]), np.asarray(handles[1])
        assert not np.array_equal(a, b)

    def test_device_render_matches_host_render(self):
        """Same seed+pattern: the device cache holds exactly the frames the
        host cache path would produce."""
        host = _collect(
            "videotestsrc num-buffers=3 pattern=random seed=7 "
            f"cache-frames=3 ! {VIDEO_CAPS} ! tensor_converter ! "
            "tensor_sink name=out", 3)
        dev = _collect(
            "videotestsrc num-buffers=3 pattern=random seed=7 "
            f"device-cache=3 ! {VIDEO_CAPS} ! tensor_converter ! "
            "tensor_sink name=out", 3)
        for h, d in zip(host, dev):
            np.testing.assert_array_equal(h, d)


@pytest.fixture()
def head_model():
    """(8,) f32 -> (3,) f32 second-stage head for cascade tests."""
    import jax.numpy as jnp

    w2 = np.linspace(1.0, -1.0, 8 * 3, dtype=np.float32).reshape(8, 3)

    def build(custom):
        def forward(params, x):
            return (jnp.asarray(x, jnp.float32) @ params,)

        return Model(name="head3", forward=forward, params=w2,
                     in_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                     (8,))]),
                     out_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                      (3,))]))

    register_model("head3")(build)
    yield
    _MODELS.pop("head3", None)


class TestDeviceCascade:
    """A->B filter cascades with ``output-device=true`` on A: the
    intermediate tensors stay in HBM as BatchView handles and B's stager
    re-joins them with at most one device op per contiguous run."""

    def _line(self, n, a_batch, b_batch, a_dev="output-device=true",
              src="device-cache=4"):
        return (f"videotestsrc num-buffers={n} pattern=random seed=9 {src} ! "
                f"{VIDEO_CAPS} ! tensor_converter ! "
                f"tensor_filter framework=xla model=pixel8 batch={a_batch} "
                f"{a_dev} name=a ! "
                f"tensor_filter framework=xla model=head3 batch={b_batch} "
                "name=b ! tensor_sink name=out")

    @pytest.mark.parametrize("a_batch,b_batch", [(4, 4), (4, 8), (8, 4),
                                                 (4, 1), (1, 4)])
    def test_cascade_matches_host_path(self, pixel_model, head_model,
                                       a_batch, b_batch):
        dev = _collect(self._line(12, a_batch, b_batch), 12)
        host = _collect(self._line(12, a_batch, b_batch, a_dev="",
                                   src="cache-frames=4"), 12)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-3)

    def test_intermediate_payloads_are_batchviews(self, pixel_model):
        from nnstreamer_tpu.tensor.buffer import BatchView

        got = []
        p = parse_launch(
            "videotestsrc num-buffers=8 pattern=random seed=9 "
            f"device-cache=4 ! {VIDEO_CAPS} ! tensor_converter ! "
            "tensor_filter framework=xla model=pixel8 batch=4 "
            "output-device=true name=a ! tensor_sink name=out")
        p.get("out").connect("new-data", lambda b: got.append(b.tensors[0]))
        p.run(timeout=60)
        assert len(got) == 8
        assert all(isinstance(t, BatchView) for t in got)
        # sibling views share one underlying batch; materialization is a
        # cached one-shot per batch
        assert got[0].batch is got[3].batch
        assert got[0].batch is not got[4].batch
        a = np.asarray(got[1])
        assert a.shape == (8,) and a.dtype == np.float32

    def test_cascade_tail_flush(self, pixel_model, head_model):
        # 9 frames at a_batch=8: 8-frame batch + 1-frame flush tail
        # (per-frame device arrays as payloads) through a batched B
        dev = _collect(self._line(9, 8, 4), 9)
        host = _collect(self._line(9, 8, 4, a_dev="", src="cache-frames=4"),
                        9)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-3)

    def test_host_source_device_cascade(self, pixel_model, head_model):
        # host frames in (normal videotestsrc), device-resident between
        # A and B: the h2d happens once at A, never between A and B
        dev = _collect(self._line(12, 4, 4, src="cache-frames=4"), 12)
        host = _collect(self._line(12, 4, 4, a_dev="", src="cache-frames=4"),
                        12)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-3)


class TestCrossDevicePinning:
    def test_mismatched_device_inputs_are_recommitted(self, pixel_model,
                                                      jax_cpu_devices):
        """Inputs pinned to a DIFFERENT virtual device than the filter's:
        _ensure_device re-commits them (once per distinct handle) instead
        of the jitted call rejecting mixed-device arguments."""
        import jax

        from nnstreamer_tpu.elements import TensorFilter, TensorSink
        from nnstreamer_tpu.pipeline import AppSrc, Pipeline
        from nnstreamer_tpu.tensor import TensorBuffer

        other = jax_cpu_devices[1]  # filter defaults to jax.devices()[0]
        rng = np.random.default_rng(0)
        frames = [jax.device_put(
            rng.integers(0, 256, (8, 8, 3), np.uint8), other)
            for _ in range(3)]

        def run(batch):
            src = AppSrc("in", caps=(
                "other/tensors,format=static,num_tensors=1,"
                "dimensions=3:8:8,types=uint8,framerate=30/1"))
            f = TensorFilter("f", framework="xla", model="pixel8",
                             batch=batch)
            sink = TensorSink("out")
            p = Pipeline()
            p.add(src, f, sink)
            p.link(src, f, sink)
            got = []
            sink.connect("new-data",
                         lambda b: got.append(np.asarray(b.tensors[0]).copy()))
            for fr in frames * 2:   # cycled handles: memoized move
                src.push_buffer(TensorBuffer(tensors=[fr]))
            src.end_of_stream()
            p.run(timeout=60)
            return got

        batched = run(batch=3)
        unbatched = run(batch=1)
        assert len(batched) == len(unbatched) == 6
        for b, u in zip(batched, unbatched):
            # vmap vs unbatched matmul reassociates the f32 reduction
            np.testing.assert_allclose(b, u, rtol=1e-3)


class TestDeviceFramesPerTensor:
    def test_fpt_accumulates_on_device(self):
        """frames-per-tensor > 1 with device frames stacks ON DEVICE (the
        zero-h2d property survives temporal batching)."""
        line = ("videotestsrc num-buffers=4 pattern=random seed=5 %s ! "
                f"{VIDEO_CAPS} ! tensor_converter frames-per-tensor=2 ! "
                "tensor_sink name=out")
        dev_bufs = []
        p = parse_launch(line % "device-cache=4")
        p.get("out").connect("new-data", lambda b: dev_bufs.append(b.tensors[0]))
        p.run(timeout=60)
        assert len(dev_bufs) == 2
        assert all(is_device_array(t) for t in dev_bufs)
        host = _collect(line % "cache-frames=4", 2)
        for h, d in zip(host, dev_bufs):
            np.testing.assert_array_equal(h, np.asarray(d))


class TestDeviceResidentFilterPath:
    def _pipeline(self, src_extra, batch, n):
        return ("videotestsrc num-buffers=%d pattern=random seed=3 %s ! "
                "%s ! tensor_converter ! "
                "tensor_filter framework=xla model=pixel8 batch=%d name=f ! "
                "tensor_sink name=out" % (n, src_extra, VIDEO_CAPS, batch))

    def test_batched_device_inputs_match_host_path(self, pixel_model):
        host = _collect(self._pipeline("cache-frames=4", 4, 8), 8)
        dev = _collect(self._pipeline("device-cache=4", 4, 8), 8)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-5)

    def test_padded_short_batch_and_flush_tail(self, pixel_model):
        # 14 frames at batch=8: one full batch, then a 6-frame EOS drain
        # (6*8 > 8 -> padded batched dispatch with device padding), plus
        # run a 9th-frame case (1*8 <= 8 -> per-frame flush) for the tail
        host = _collect(self._pipeline("cache-frames=5", 8, 14), 14)
        dev = _collect(self._pipeline("device-cache=5", 8, 14), 14)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-5)
        host = _collect(self._pipeline("cache-frames=3", 8, 9), 9)
        dev = _collect(self._pipeline("device-cache=3", 8, 9), 9)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-5)

    def test_unbatched_filter_accepts_device_frames(self, pixel_model):
        host = _collect(self._pipeline("cache-frames=2", 1, 4), 4)
        dev = _collect(self._pipeline("device-cache=2", 1, 4), 4)
        for h, d in zip(host, dev):
            np.testing.assert_allclose(h, d, rtol=1e-5)
