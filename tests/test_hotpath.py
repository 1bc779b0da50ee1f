"""Zero-copy dataflow hot path: buffer pool, iovec framing, coalescer.

Covers the PR-2 tentpole end to end:

- :class:`TensorBufferPool` ownership: recycle on release, recycle on
  plain drop (release-on-EOS through a real pipeline), and the
  no-alias guarantee — a slab with live numpy views is never handed to
  a new writer;
- scatter-gather wire framing (``send_tensors`` / ``recv_msg(pool=)``):
  payload equality across dtypes, partial-``sendmsg`` handling, and the
  copy budget (serialize materializes headers only — the regression
  gate also runs standalone via ``tools/hotpath_bench.py --assert``,
  wired into tier-1 by the ``perf``-marked smoke below);
- tee fan-out sharing ONE pooled payload across branches;
- the query path of a flagship-style launch line doing zero
  full-frame copies, asserted through the ``--trace`` counters;
- adaptive micro-batching: ``batch-timeout-ms`` dispatches a partial
  bucket when the oldest frame's budget expires, with ``inflight>1``
  overlap preserved and EOS semantics unchanged.
"""

import gc
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.models.registry import _MODELS, Model, register_model
from nnstreamer_tpu.pipeline import AppSrc, Pipeline
from nnstreamer_tpu.pipeline.tracing import copy_probe
from nnstreamer_tpu.query import (TensorQueryClient, TensorQueryServerSink,
                                  TensorQueryServerSrc, shutdown_server)
from nnstreamer_tpu.query import protocol
from nnstreamer_tpu.tensor.buffer import TensorBuffer, TensorBufferPool
from nnstreamer_tpu.tensor.info import TensorInfo, TensorsInfo
from nnstreamer_tpu.tensor.types import TensorType

HEADER_BUDGET_1T = protocol.HEADER.size + 4 + 128   # hdr + count + 1 meta

# Perf-COMPARISON gates pit two timed variants against each other and
# assert on the ratio; on a single-core host the contending threads (or
# back-to-back timed loops under suite load) serialize and the ratio
# measures scheduler interleaving, not the optimization.  A noise
# measurement is neither a pass nor a fail, so these skip rather than
# flake.  Cheap absolute-budget smokes (serialize/dispatch/admit)
# stay on everywhere.
_needs_cores = pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="perf-comparison gate needs >=2 cores: timed variants "
           "serialize on one core and the ratio measures scheduler "
           "noise, not the change under test")


# ---------------------------------------------------------------------------
# pool semantics
# ---------------------------------------------------------------------------

class TestBufferPool:
    def test_recycle_on_release(self):
        pool = TensorBufferPool()
        a = pool.acquire(1024)
        a.memory()[:4] = b"abcd"
        a.release()
        b = pool.acquire(1024)
        assert pool.stats["hits"] == 1
        b.release()

    def test_release_is_final(self):
        pool = TensorBufferPool()
        a = pool.acquire(64)
        a.release()
        with pytest.raises(RuntimeError):
            a.memory()
        with pytest.raises(RuntimeError):
            a.retain()

    def test_no_alias_after_recycle(self):
        """A released lease whose numpy views are still alive must NOT
        be recycled under them: the next writer gets different storage,
        and the old view's bytes stay stable."""
        pool = TensorBufferPool()
        a = pool.acquire(128)
        a.memory()[:] = b"\x11" * 128
        view = a.view(np.uint8, (128,))
        a.release()                     # view still alive → slab parked
        b = pool.acquire(128)
        assert pool.stats["hits"] == 0  # not served the aliased slab
        b.memory()[:] = b"\x22" * 128   # writer scribbles its own slab
        assert view[0] == 0x11          # old view unaffected
        del view
        b.release()
        c = pool.acquire(128)           # parked slab is sweepable now
        assert pool.stats["hits"] >= 1
        c.release()

    def test_retain_release_refcount(self):
        pool = TensorBufferPool()
        a = pool.acquire(32)
        a.retain()                      # two owners (tee-style)
        a.release()
        assert pool.stats["free"] == 0  # one owner still holds it
        a.release()
        assert pool.stats["free"] == 1

    def test_drop_reclaims_like_release(self):
        """The common pipeline flow never calls release() — the buffer
        wrapper dropping at the sink IS the release (CPython refcount
        finalizes the lease promptly)."""
        pool = TensorBufferPool()
        lease = pool.acquire(256)
        del lease
        gc.collect()
        b = pool.acquire(256)
        assert pool.stats["hits"] == 1
        b.release()

    def test_free_bytes_cap_bounds_variable_size_streams(self):
        """Per-bucket caps alone would let a stream of ever-changing
        payload sizes grow one 16-slab bucket per size forever; the
        pool-wide byte cap bounds total retention."""
        pool = TensorBufferPool(max_free_bytes=8192)
        for size in range(1024, 1024 + 64):   # 64 distinct sizes
            pool.acquire(size).release()
        assert pool.stats["free_bytes"] <= 8192

    def test_release_on_eos_through_pipeline(self):
        """Pooled payloads attached to stream buffers return to the
        pool once the stream reaches EOS and the pipeline stops — the
        ref-count release-on-EOS contract."""
        pool = TensorBufferPool()
        caps = ("other/tensors,format=static,num_tensors=1,dimensions=16,"
                "types=uint8,framerate=0/1")
        p = parse_launch(f"appsrc caps={caps} name=in ! queue ! "
                         "tensor_sink name=out collect=false")
        src = p.get("in")
        p.play()
        for i in range(8):
            lease = pool.acquire(16)
            lease.memory()[:] = bytes([i]) * 16
            src.push_buffer(TensorBuffer(
                tensors=[lease.view(np.uint8, (16,))], pts=i,
                lease=lease))
            del lease
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()                        # stop() runs a gc collection
        gc.collect()
        stats = pool.stats
        assert stats["free"] + stats["pending"] >= 1
        again = pool.acquire(16)        # and the slabs actually recycle
        assert pool.stats["hits"] >= 1
        again.release()


class TestTeeSharesPayload:
    def test_fanout_one_payload_two_branches(self):
        pool = TensorBufferPool()
        caps = ("other/tensors,format=static,num_tensors=1,dimensions=8,"
                "types=uint8,framerate=0/1")
        p = parse_launch(
            f"appsrc caps={caps} name=in ! tee name=t "
            "t. ! queue ! tensor_sink name=o1 "
            "t. ! queue ! tensor_sink name=o2")
        src = p.get("in")
        o1, o2 = p.get("o1"), p.get("o2")
        p.play()
        lease = pool.acquire(8)
        lease.memory()[:] = b"ABCDEFGH"
        src.push_buffer(TensorBuffer(
            tensors=[lease.view(np.uint8, (8,))], pts=0, lease=lease))
        del lease
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()
        assert len(o1.results) == 1 and len(o2.results) == 1
        a, b = o1.results[0].np(0), o2.results[0].np(0)
        np.testing.assert_array_equal(a, b)
        # both branches alias the SAME slab bytes — no copy happened
        assert np.shares_memory(a, b)
        # and both wrappers share one lease (refcounted payload)
        assert o1.results[0].lease is o2.results[0].lease is not None
        assert pool.stats["misses"] == 1   # exactly one allocation


# ---------------------------------------------------------------------------
# scatter-gather wire framing
# ---------------------------------------------------------------------------

class TestIovecFraming:
    def _roundtrip(self, buf, pool=None):
        a, b = socket.socketpair()
        out = []
        rd = threading.Thread(
            target=lambda: out.append(protocol.recv_msg(b, pool=pool)),
            daemon=True)
        rd.start()
        protocol.send_tensors(a, protocol.T_DATA, buf, seq=7,
                              pts=buf.pts or 0)
        rd.join(timeout=30)
        a.close()
        b.close()
        assert out and out[0] is not None
        return out[0]

    @pytest.mark.parametrize("dtype", [np.uint8, np.float32, np.int16])
    def test_roundtrip_matches_legacy_codec(self, dtype):
        rng = np.random.default_rng(3)
        buf = TensorBuffer(tensors=[
            rng.integers(0, 100, (2, 3)).astype(dtype),
            rng.integers(0, 100, (5,)).astype(dtype)], pts=42)
        msg = self._roundtrip(buf, pool=TensorBufferPool())
        assert msg.seq == 7 and msg.pts == 42
        # wire bytes are identical to the legacy single-blob framing
        assert bytes(msg.payload) == protocol.encode_tensors(buf)
        back = protocol.decode_tensors(msg.payload)
        for i in range(2):
            np.testing.assert_array_equal(back[i], buf.np(i))

    def test_pooled_receive_is_zero_copy_view(self):
        pool = TensorBufferPool()
        buf = TensorBuffer(tensors=[np.arange(12, dtype=np.float32)])
        msg = self._roundtrip(buf, pool=pool)
        assert msg.lease is not None
        back = protocol.decode_tensors(msg.payload)
        # the decoded tensor aliases the pooled slab (no materialize)
        assert np.shares_memory(
            back[0], np.frombuffer(msg.lease.memory(), np.uint8))
        assert not back[0].flags.writeable   # shared payload contract

    def test_noncontiguous_input_pays_exactly_one_copy(self):
        base = np.arange(64, dtype=np.float32).reshape(8, 8)
        buf = TensorBuffer(tensors=[base[:, ::2]])   # non-contiguous
        with copy_probe() as probe:
            parts = protocol.tensor_parts(buf)
        assert probe.bytes_copied == base[:, ::2].nbytes
        back = protocol.decode_tensors(
            b"".join(bytes(p) for p in parts))
        np.testing.assert_array_equal(back[0], base[:, ::2])

    def test_serialize_copy_budget(self):
        """The copy-regression contract: framing a contiguous frame
        materializes ONLY header-class bytes (count + metas on
        tensor_parts; + wire header via send_tensors)."""
        buf = TensorBuffer(
            tensors=[np.zeros((224, 224, 3), np.uint8)])
        with copy_probe() as probe:
            protocol.tensor_parts(buf)
        assert probe.bytes_copied == 0
        msg = None
        a, b = socket.socketpair()
        rd = threading.Thread(target=lambda: protocol.recv_msg(b),
                              daemon=True)
        rd.start()
        with copy_probe() as probe:
            protocol.send_tensors(a, protocol.T_DATA, buf)
        rd.join(timeout=30)
        a.close(), b.close()
        assert probe.bytes_copied <= HEADER_BUDGET_1T
        del msg

    def test_partial_sendmsg_delivers_everything(self):
        """Tiny send buffers force many partial sendmsg returns; the
        iovec walk must resume mid-part without loss or reorder."""
        a, b = socket.socketpair()
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        payload = np.arange(300_000, dtype=np.uint8) % 251
        buf = TensorBuffer(tensors=[payload])
        out = []
        rd = threading.Thread(
            target=lambda: out.append(protocol.recv_msg(b)), daemon=True)
        rd.start()
        protocol.send_tensors(a, protocol.T_DATA, buf, seq=1)
        rd.join(timeout=30)
        a.close()
        b.close()
        assert out and out[0] is not None
        np.testing.assert_array_equal(
            protocol.decode_tensors(out[0].payload)[0], payload)


class TestQueryPathZeroCopy:
    SERVER_ID = 31

    def test_flagship_query_path_copies_headers_only(self):
        """--trace observability gate: a flagship-style stream offloaded
        through tensor_query_client shows per-frame bytes_copied within
        the header budget — the query serialize path performs zero
        full-tensor-payload copies — and reply payloads ride pooled
        zero-copy views all the way into the sink."""
        caps = ("other/tensors,format=static,num_tensors=1,"
                "dimensions=3:224:224,types=uint8,framerate=0/1")
        server = Pipeline("server")
        ssrc = TensorQueryServerSrc("qsrc", id=self.SERVER_ID, port=0,
                                    caps=caps)
        ssink = TensorQueryServerSink("qsink", id=self.SERVER_ID)
        server.add(ssrc, ssink)
        server.link(ssrc, ssink)
        server.play()
        try:
            p = Pipeline("client")
            src = AppSrc("src", caps=caps)
            qc = TensorQueryClient("qc", port=ssrc.bound_port,
                                   timeout=10.0)
            from nnstreamer_tpu.elements import TensorSink

            sink = TensorSink("out")
            p.add(src, qc, sink)
            p.link(src, qc, sink)
            tracer = p.enable_tracing()
            n = 6
            frame = np.zeros((224, 224, 3), np.uint8)
            for i in range(n):
                src.push_buffer(TensorBuffer(tensors=[frame], pts=i))
            src.end_of_stream()
            p.run(timeout=30)
            report = tracer.report()
            assert report["qc"]["buffers"] == n
            per_frame = report["qc"]["bytes_copied"] / n
            assert per_frame <= HEADER_BUDGET_1T, (
                f"query serialize path copied {per_frame} B/frame "
                f"(budget {HEADER_BUDGET_1T}): full-payload copy is "
                "back on the hot path")
            # replies decoded zero-copy over pooled slabs
            assert len(sink.results) == n
            assert sink.results[0].lease is not None
        finally:
            server.stop()
            shutdown_server(self.SERVER_ID)


# ---------------------------------------------------------------------------
# adaptive micro-batch dispatch
# ---------------------------------------------------------------------------

@pytest.fixture()
def tiny_model():
    import jax.numpy as jnp

    w = np.arange(32, dtype=np.float32).reshape(4, 8)

    def build(custom):
        def forward(params, x):
            return (jnp.asarray(x, jnp.float32) @ params,)

        return Model(name="tiny_hotpath", forward=forward, params=w,
                     in_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                     (4,))]),
                     out_info=TensorsInfo([TensorInfo(TensorType.FLOAT32,
                                                      (8,))]))

    register_model("tiny_hotpath")(build)
    yield w
    _MODELS.pop("tiny_hotpath", None)


CAPS4 = ("other/tensors,format=static,num_tensors=1,dimensions=4,"
         "types=float32,framerate=0/1")


def _await(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


class TestBatchTimeout:
    def _pipeline(self, tiny_model, extra=""):
        return parse_launch(
            f"appsrc caps={CAPS4} name=in ! "
            f"tensor_filter framework=xla model=tiny_hotpath name=f "
            f"{extra} ! tensor_sink name=out")

    def test_deadline_dispatches_partial_bucket(self, tiny_model):
        """A paced source that underruns the bucket still sees its
        results within the latency budget — WITHOUT waiting for EOS
        (the fixed-batch behavior this property replaces)."""
        p = self._pipeline(tiny_model, "batch=4 batch-timeout-ms=80")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        feeds = [np.full(4, i, np.float32) for i in range(2)]
        for i, f in enumerate(feeds):
            src.push_buffer(TensorBuffer(tensors=[f], pts=i))
        # 2 frames < batch=4: only the deadline can dispatch them
        assert _await(lambda: len(got) == 2), (
            f"partial bucket not dispatched on deadline (got "
            f"{len(got)}/2)")
        # stream continues after a deadline flush: fill a full bucket
        for i in range(2, 6):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()
        assert [b.pts for b in got] == list(range(6))   # order holds
        for i, b in enumerate(got):
            np.testing.assert_allclose(
                b.np(0), np.full(4, i, np.float32) @ tiny_model)

    def test_deadline_flush_preserves_inflight_overlap(self, tiny_model):
        """inflight>1 keeps dispatch overlap under load; on underrun the
        deadline drains the in-flight queue too (frames already
        dispatched must not outwait their budget)."""
        p = self._pipeline(
            tiny_model, "batch=2 inflight=2 batch-timeout-ms=80")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        # 5 frames = 2 full buckets (both held in flight at depth 2)
        # + 1 partial: everything must surface via the deadline
        for i in range(5):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        assert _await(lambda: len(got) == 5), (
            f"deadline left dispatched batches queued (got "
            f"{len(got)}/5)")
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()
        assert [b.pts for b in got] == list(range(5))
        for i, b in enumerate(got):
            np.testing.assert_allclose(
                b.np(0), np.full(4, i, np.float32) @ tiny_model)

    def test_timeout_without_batching_is_ignored(self, tiny_model):
        p = self._pipeline(tiny_model, "batch-timeout-ms=50")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        src.push_buffer(TensorBuffer(
            tensors=[np.ones(4, np.float32)], pts=0))
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()
        assert len(got) == 1

    def test_full_buckets_do_not_wait_for_deadline(self, tiny_model):
        """Throughput sanity: when the stream keeps buckets full, the
        coalescer dispatches on fill — results arrive long before any
        80 ms deadline could have fired per batch."""
        p = self._pipeline(tiny_model, "batch=2 batch-timeout-ms=5000")
        got = []
        p.get("out").connect("new-data", lambda b: got.append(b))
        p.play()
        src = p.get("in")
        for i in range(8):
            src.push_buffer(TensorBuffer(
                tensors=[np.full(4, i, np.float32)], pts=i))
        # 8 frames = 4 full buckets; at depth 1 at least 3 dispatch+push
        # cycles complete without any 5 s deadline involvement
        assert _await(lambda: len(got) >= 6, timeout=10.0)
        src.end_of_stream()
        p.wait(timeout=30)
        p.stop()
        assert [b.pts for b in got] == list(range(8))


# ---------------------------------------------------------------------------
# copy-regression smoke (tier-1 fast, `perf` marker)
# ---------------------------------------------------------------------------

@pytest.mark.perf
def test_hotpath_bench_copy_gate():
    """CI gate: tools/hotpath_bench.py --assert fails when the
    serialize path copies more than the header budget per frame.  A
    copy regression (tobytes / b"".join back on the hot path) turns
    tier-1 red here, not in a quarterly bench capture."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "serialize"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"copy gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_copy_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_dispatch_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage dispatch fails
    when the segment compiler stops fusing a linear identity chain or
    when fused dispatch loses its >=2x per-element overhead win over
    interpreted Pad.push dispatch (measured margin ~5-13x, so the gate
    trips on a real scheduling regression, not machine noise)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "dispatch"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"dispatch gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_dispatch_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_obs_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage obs fails when
    an untraced compiled plan references obs/tracer state (the
    zero-cost-when-off contract) or when metrics-off dispatch overhead
    exceeds 2% — the observability layer must stay free until a tracer
    or scrape actually asks for data."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "obs"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"obs gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_obs_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_telemetry_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage telemetry fails
    when an untraced compiled plan references timeseries/federation/
    signal state (the extended obs-vocabulary scan) or when fused
    dispatch with a 25 ms ring sampler + federation collector +
    loopback publisher attached costs more than 2% over bare — the
    telemetry plane must be cheap enough to leave on in production."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "telemetry"],
                       capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, (
        f"telemetry gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_telemetry_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_profile_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage profile fails
    when an untraced compiled plan references profiler/attribution
    state (extended PR 5 obs-ref scan) or when pure-dispatch overhead
    after a full profile session exceeds 2% of the never-profiled
    baseline — profiling is a per-pipeline session, never a process
    tax."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "profile"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"profile gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_profile_gate"' in r.stdout


@pytest.mark.perf
@_needs_cores
def test_hotpath_bench_xbatch_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage xbatch fails
    when cross-stream batching (tensor_query_serversrc batch=N) no
    longer sustains >= 2x the per-frame server's throughput with 8
    concurrent clients at bucket 8, or when a SINGLE connected client
    pays > 2% for the batching config (the solo fast path + fill-target
    rule must keep a lone client at per-frame cost)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "xbatch"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (
        f"xbatch gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_xbatch_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_admit_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage admit fails
    when the un-overloaded admission decision (query/overload.py —
    the only overload-layer cost an ADMITTED frame pays) exceeds 2%
    of the wire frame round trip it gates.  Overload protection must
    not tax the protected path."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "admit"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (
        f"admit gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_admit_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_fusexla_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage fusexla fails
    when whole-segment XLA lowering (fuse=xla, pipeline/schedule.py)
    no longer sustains >= 2x fuse-python on the bucket-8
    transform→filter→decode chain, when the chain stops lowering
    (fallback to python), or when the per-segment executable cache
    recompiles in steady state (the 100%-hit-after-warmup contract:
    no per-fill or per-frame recompiles)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "fusexla"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (
        f"fusexla gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_fusexla_gate"' in r.stdout


@pytest.mark.perf
@_needs_cores
def test_hotpath_bench_fleet_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage fleet fails
    when the single-worker ROUTED path (fleet/router.py fronting one
    out-of-process MLP serving worker) adds more than 5% p99 service
    latency over direct-to-worker — the ISSUE 14 bound on what the
    fleet tier may cost a request that never needed it."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "fleet"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"fleet gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_fleet_gate"' in r.stdout


@pytest.mark.perf
@_needs_cores
def test_hotpath_bench_llmdecode_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage llmdecode fails
    when the LLM tier's batched decode step drops under 2x the
    sequential per-session decode rate at bucket 8, or a lone session
    inside a bucket-capacity engine pays more than 5% vs a dedicated
    capacity-1 engine (the ISSUE 15 continuous-batching bounds: the
    shared-step win must hold, and nobody pays for a pool they don't
    share — a donation regression shows up here as a whole-pool copy
    per step)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "llmdecode"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"llmdecode gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_llmdecode_gate"' in r.stdout


@pytest.mark.perf
@_needs_cores
def test_hotpath_bench_llmpaged_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage llmpaged fails
    when the block-paged KV cache (ISSUE 17) loses any of its bounds:
    paged decode must stay within 10% of dense tok/s at equal
    residency, admit >= 2x the short-chat sessions at equal arena
    bytes, re-prefill a shared long prompt >= 5x faster on a
    prefix-cache hit than cold, and add zero steady-state compiles
    after warmup (the bounded block-table/chunk executable grid)."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "llmpaged"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"llmpaged gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_llmpaged_gate"' in r.stdout


@pytest.mark.perf
@_needs_cores
def test_hotpath_bench_llmobs_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage llmobs fails
    when running the token-level observability hooks (per-token
    TTFT/ITL observation + PhaseClock blame absorption,
    llm/tokenobs.py) costs more than 2% decode tok/s over the
    hooks-off attribute test at bucket 8 — the ISSUE 20
    zero-cost-when-off bound on the serving hot path."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "llmobs"],
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"llmobs gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_llmobs_gate"' in r.stdout


@pytest.mark.perf
def test_hotpath_bench_jitledger_gate():
    """CI gate: tools/hotpath_bench.py --assert --stage jitledger fails
    when the compile-ledger sentinel (ISSUE 19) breaks its bargain:
    the sentinel-OFF guard on the dispatch path must cost < 2% of a
    stacked dispatch, warmup must record >= 1 attributed compile at
    the filter site, the steady-state window over every fill level
    must record ZERO novel compiles, and an over-budget signature must
    raise CompileBudgetExceeded naming the differing field."""
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "hotpath_bench.py")
    r = subprocess.run([sys.executable, tool, "--assert", "--stage",
                        "jitledger"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (
        f"jitledger gate failed:\nstdout: {r.stdout}\nstderr: {r.stderr}")
    assert '"hotpath_jitledger_gate"' in r.stdout
