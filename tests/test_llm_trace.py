"""The token tier inside the JAX profiler's trace: the decode thread's
``PhaseClock`` phases as ``llm.<state>`` spans with their arguments, the
``llm.<state>.<part>`` children around the device calls, and the
``llm.engine.*`` / ``sflm.*`` scopes of the four step programs.

A profiler session is one per process, so every test that starts one
lives in this file and reads the one round trip the module's fixture
makes on the toy ``tensor_llm`` pipeline (CPU, Python tracer off, as
``benchmarks/tracing.py`` sets it)."""

import glob
import subprocess
import sys
import time

import numpy as np
import pytest

from nnstreamer_tpu import parse_launch
from nnstreamer_tpu.llm.client import encode_request
from nnstreamer_tpu.llm.engine import (PHASES, SPANS, DecodeEngine,
                                       PhaseClock)
from nnstreamer_tpu.tensor.buffer import TensorBuffer

CUSTOM = ("vocab:61,dim:32,heads:4,head_dim:8,mlp:64,layers:2,"
          "max_seq:48,dtype:float32")
REQ_CAPS = ("other/tensors,format=static,num_tensors=1,dimensions=24,"
            "types=int32,framerate=0/1")
TOP_LEVEL = set(SPANS.values())


def _request(prompt, max_new, tag, stop_token=-1):
    buf = TensorBuffer(tensors=[encode_request(
        np.asarray(prompt, np.int32), max_new=max_new,
        stop_token=stop_token, frame_len=24)])
    buf.extra["tag"] = tag
    return buf


def _traced(props, requests, trace_dir):
    """Play the toy pipeline with ``props``, trace it from before the
    first request to after the last token, and return the program's
    spans by start (the outer first) and what the element counted."""
    import jax
    from jax.profiler import ProfileData

    p = parse_launch(
        f"appsrc name=src caps={REQ_CAPS} ! "
        f"tensor_llm name=llm custom={CUSTOM} seed=0 {props} ! "
        "tensor_sink name=out")
    p.play()
    llm = p.get("llm")
    eng = llm.engine
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        steps0, prefills0 = eng.steps_total, eng.prefills_total
        chunks0 = eng.prefill_chunks_total
        src = p.get("src")
        for request in requests:
            src.push_buffer(request)
        src.end_of_stream()
        p.wait(timeout=120)
        counts = {"steps": eng.steps_total - steps0,
                  "report": eng.report(),
                  "prefills": eng.prefills_total - prefills0,
                  "chunks": eng.prefill_chunks_total - chunks0,
                  "shed": llm.shed_total, "rejected": llm.rejected_total}
        # the loop idles on: its 50 ms ticks are the thread's time too
        time.sleep(0.3)
    finally:
        jax.profiler.stop_trace()
        p.stop()
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("llm."):
                    spans.append((ev.name, float(ev.start_ns),
                                  float(ev.start_ns)
                                  + float(ev.duration_ns),
                                  dict(ev.stats)))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return spans, counts


@pytest.fixture(scope="module")
def round_trip(tmp_path_factory):
    """Two streams admitted into two slots, a third shed for want of a
    slot, a fourth refused as over-length."""
    return _traced("slots=2 batch=2", [
        _request([5, 6, 7], 12, 0),
        _request([1, 2, 3, 4, 5, 6, 7, 8, 9], 9, 1),
        _request([3, 1], 20, 2),
        _request(np.arange(20), 40, 3, stop_token=9)],
        str(tmp_path_factory.mktemp("llm_trace")))


@pytest.fixture(scope="module")
def paged_round_trip(tmp_path_factory):
    """The paged pool with chunked prefill and one slot: the second
    request waits inside its admit-timeout until the first stream ends."""
    return _traced(
        "slots=1 batch=1 page-size=4 prefill-chunk=4 admit-timeout-ms=60000",
        [_request([1, 2, 3, 4, 5, 6, 7, 8, 9], 6, 0),
         _request([3, 1, 4, 1, 5], 4, 1)],
        str(tmp_path_factory.mktemp("llm_trace_paged")))


def test_every_state_has_a_span_name_and_phases_did_not_change():
    assert PHASES == ("idle", "admit", "prefill", "llm-prefill-chunk",
                      "decode", "egress", "compile")
    assert SPANS == {"idle": "llm.idle", "admit": "llm.admit",
                     "prefill": "llm.prefill",
                     "llm-prefill-chunk": "llm.prefill_chunk",
                     "decode": "llm.decode", "egress": "llm.egress",
                     "compile": "llm.compile"}


def test_one_decode_span_with_a_step_per_engine_step(round_trip):
    spans, counts = round_trip
    steps = [s for s in spans if s[0] == "llm.decode" and "step" in s[3]]
    assert counts["steps"] >= 10
    assert len(steps) == counts["steps"]
    numbers = [s[3]["step"] for s in steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(steps)))
    assert {s[3]["lanes"] for s in steps} <= {1, 2}
    assert steps[0][3]["lanes"] == 2


def test_a_decode_span_says_how_many_positions_its_lanes_attend(
        round_trip):
    """``attended`` = the sum over the step's lanes of ``pos + 1``, a
    host integer known at dispatch: prompts of 3 and 9 tokens make the
    first step attend 4 + 10 positions, and every step a lane one more;
    the engine totals them beside what the lanes' slots reserve."""
    spans, counts = round_trip
    steps = [s[3] for s in spans if s[0] == "llm.decode" and "step" in s[3]]
    assert steps[0]["attended"] == (3 + 1) + (9 + 1)
    for was, now in zip(steps, steps[1:]):
        if was["lanes"] == now["lanes"] == 2:
            assert now["attended"] == was["attended"] + 2
    assert all(s["attended"] >= s["lanes"] for s in steps)
    report = counts["report"]
    assert report["attended_positions"] == sum(s["attended"]
                                               for s in steps)
    assert report["reserved_positions"] == 48 * sum(s["lanes"]
                                                    for s in steps)
    assert 0 < report["attended_positions"] < report["reserved_positions"]


def test_every_step_but_the_first_of_a_busy_run_is_dispatched_ahead(
        round_trip):
    """Both streams are resident before the first step and one goes on
    until the last: every step but the first was sent while the step
    before was uncollected, and says so; the engine counts them."""
    spans, counts = round_trip
    steps = [s[3] for s in spans if s[0] == "llm.decode" and "step" in s[3]]
    assert [s["ahead"] for s in steps] == [0] + [1] * (len(steps) - 1)
    report = counts["report"]
    assert report["steps"] == counts["steps"]
    assert report["steps_ahead"] == counts["steps"] - 1
    assert report["lanes_discarded"] == 0
    # the pieces of ``llm.decode`` that carry no ``step`` are the
    # collects: they hold ``wait`` and ``sample``, of the step before
    # the one whose ``operands`` and ``dispatch`` the piece before holds
    bare = [s for s in spans if s[0] == "llm.decode" and not s[3]]
    waits = [s for s in spans if s[0] == "llm.decode.wait"]
    assert len(bare) == len(waits) == counts["steps"]
    assert all(b[1] <= w[1] and w[2] <= b[2] for b, w in zip(bare, waits))
    sends = [s for s in spans if s[0] == "llm.decode.dispatch"]
    # step k is sent before step k-1 is waited for
    assert all(sends[k][1] < waits[k - 1][1] for k in range(1, len(sends)))


def test_phase_spans_are_a_partition_of_the_threads_time(round_trip):
    spans, _ = round_trip
    top = [s for s in spans if s[0] in TOP_LEVEL]
    assert {s[0] for s in top} >= {"llm.idle", "llm.admit", "llm.prefill",
                                   "llm.decode", "llm.egress"}
    for a, b in zip(top, top[1:]):
        assert a[2] <= b[1], (a, b)          # flat: never two open
    covered = sum(s[2] - s[1] for s in top)
    whole = top[-1][2] - top[0][1]
    assert covered <= whole
    assert (whole - covered) / whole < 0.01


def test_children_lie_inside_a_span_of_their_parents_name(round_trip):
    spans, counts = round_trip
    top = [s for s in spans if s[0] in TOP_LEVEL]
    children = [s for s in spans if s[0] not in TOP_LEVEL]
    assert {s[0] for s in children} == {
        "llm.decode.operands", "llm.decode.dispatch", "llm.decode.wait",
        "llm.decode.sample", "llm.prefill.dispatch", "llm.prefill.wait"}
    for name, lo, hi, _ in children:
        # a dense prefill is sent without waiting: its token is read by
        # a collect, so its wait lies in a piece of ``llm.decode``
        parent = ("llm.decode" if name == "llm.prefill.wait"
                  else name.rsplit(".", 1)[0])
        assert any(t[0] == parent and t[1] <= lo and hi <= t[2]
                   for t in top), name
    for part in ("operands", "dispatch", "wait", "sample"):
        assert sum(1 for s in children
                   if s[0] == f"llm.decode.{part}") == counts["steps"]
    assert sum(1 for s in children
               if s[0] == "llm.prefill.dispatch") == counts["prefills"]
    # ... each read before the wait on the step sent after it, which it
    # names, with the request's keys (none without a wire)
    reads = [s for s in children if s[0] == "llm.prefill.wait"]
    assert len(reads) == counts["prefills"]
    steps = [s[3]["step"] for s in spans
             if s[0] == "llm.decode" and "step" in s[3]]
    waits = [s for s in children if s[0] == "llm.decode.wait"]
    for _, _, hi, stats in reads:
        assert (stats["cid"], stats["seq"]) == (-1, -1)
        assert hi <= waits[stats["step"] - steps[0]][1]


def test_first_piece_of_an_admit_carries_the_wait_and_the_verdict(
        round_trip):
    spans, counts = round_trip
    assert counts["prefills"] == 2
    assert counts["shed"] == 1 and counts["rejected"] == 1
    admits = [s for s in spans if s[0] == "llm.admit"]
    first = [s[3] for s in admits if "waited_us" in s[3]]
    assert all(w["waited_us"] >= 0 for w in first)
    assert sorted(w["outcome"] for w in first) == [
        "admit", "admit", "reject", "shed"]
    # the pieces a prefill or an emit interrupted carry nothing
    rest = [s[3] for s in admits if "waited_us" not in s[3]]
    assert rest and all(not stats for stats in rest)
    # the dense prefill says what it was padded to
    padded = [s[3]["padded"] for s in spans
              if s[0] == "llm.prefill" and "padded" in s[3]]
    assert sorted(padded) == [8, 16]


def test_paged_chunks_and_requeues_are_named_too(paged_round_trip):
    spans, counts = paged_round_trip
    top = [s for s in spans if s[0] in TOP_LEVEL]
    for a, b in zip(top, top[1:]):
        assert a[2] <= b[1], (a, b)
    assert counts["prefills"] == 2 and counts["chunks"] == 3 + 2
    chunks = [s for s in top if s[0] == "llm.prefill_chunk"]
    assert len(chunks) == counts["chunks"]
    names = {s[0] for s in spans if s[0] not in TOP_LEVEL}
    assert names == {
        "llm.decode.operands", "llm.decode.dispatch", "llm.decode.wait",
        "llm.decode.sample", "llm.prefill_chunk.dispatch",
        # only a prompt's last chunk waits for its logits
        "llm.prefill_chunk.wait"}
    assert sum(1 for s in spans
               if s[0] == "llm.prefill_chunk.wait") == counts["prefills"]
    assert sum(1 for s in spans if s[0] == "llm.decode"
               and "step" in s[3]) == counts["steps"]
    # the second request is looked at again and again until the slot
    # frees: each look reports its wait so far, the last one admits
    looks = [s[3] for s in spans
             if s[0] == "llm.admit" and "waited_us" in s[3]]
    outcomes = [w["outcome"] for w in looks]
    assert outcomes[0] == "admit" and outcomes[-1] == "admit"
    assert set(outcomes[1:-1]) == {"requeue"} and len(outcomes) > 2
    waits = [w["waited_us"] for w in looks[1:]]
    assert waits == sorted(waits) and waits[-1] > waits[0]


def test_totals_are_unchanged_by_annotating():
    """The hand-cranked sequence of ``TestPhaseClock``, on a bare clock
    and on one that annotates: the same integers."""
    def crank(clock_of):
        now = [0]
        clk = clock_of(lambda: now[0])
        with clk.on_this_thread():
            for state, dt, kw in (("admit", 5, {"waited_us": 3}),
                                  ("prefill", 7, {}), ("admit", 11, {}),
                                  ("decode", 13, {"step": 0, "lanes": 2}),
                                  ("egress", 17, {}), ("idle", 19, {})):
                now[0] += dt
                clk.enter(state, **kw)
                clk.note(outcome="admit")
                with clk.child("dispatch"):
                    now[0] += 1
        now[0] += 23
        return clk.totals_ns()

    bare = crank(lambda c: PhaseClock(clock_ns=c))
    annotating = crank(lambda c: PhaseClock(clock_ns=c, annotate=True))
    assert bare == annotating
    assert bare == {"idle": 5 + 24, "admit": 8 + 14, "prefill": 12,
                    "llm-prefill-chunk": 0, "decode": 18, "egress": 20,
                    "compile": 0}
    assert sum(bare.values()) == 5 + 7 + 11 + 13 + 17 + 19 + 6 + 23


def test_a_bare_clock_never_imports_the_profiler():
    code = ("import sys\n"
            "import nnstreamer_tpu.llm.client\n"
            "assert 'jax' not in sys.modules\n"
            "from nnstreamer_tpu.llm.engine import PhaseClock\n"
            "clk = PhaseClock()\n"
            "with clk.on_this_thread():\n"
            "    clk.enter('decode', step=1)\n"
            "    clk.note(outcome='admit')\n"
            "    with clk.child('wait'):\n"
            "        clk.enter('idle')\n"
            "assert 'jax.profiler' not in sys.modules\n"
            "assert 'jax' not in sys.modules\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# the four step programs name themselves and their parts
# ---------------------------------------------------------------------------

MATH = {"sflm.embed", "sflm.qkv", "sflm.attn", "sflm.mlp", "sflm.moe",
        "sflm.head"}
ALL_SCOPES = MATH | {"sflm.kv_write", "sflm.kv_read"}


def _lowered(program):
    """``as_text(debug_info=True)`` of one step program at a toy size
    (the plain ``as_text()`` drops the names)."""
    fn, args = _program(_engine(CUSTOM, program in ("pstep", "chunk")),
                        program)
    return fn.lower(*args).as_text(debug_info=True)


def _program(eng, program, lanes=2):
    """One of the engine's four executables at a toy size, and operands
    to lower it with."""
    import jax.numpy as jnp

    i32, pool = jnp.int32, eng.pool
    vec = jnp.zeros((lanes,), i32)
    if program == "step":
        fn, args = eng._step_fn(lanes), (vec, vec)
    elif program == "pstep":
        fn = eng._pstep_fn(lanes, 2)
        args = (vec, vec, jnp.zeros((lanes, 2), i32))
    elif program == "prefill" and eng.chunk_len:
        fn = eng._prefill_fn(eng.chunk_len)
        args = (jnp.zeros((eng.chunk_len,), i32), i32(0), i32(0), i32(1),
                jnp.bool_(True))
    elif program == "prefill":
        fn = eng._prefill_fn(8)
        args = (jnp.zeros((8,), i32), i32(0), i32(1))
    else:
        fn = eng._chunk_fn(8, 2)
        args = (jnp.zeros((8,), i32), jnp.zeros((2,), i32), i32(0),
                i32(1), i32(pool.scratch), i32(0))
    return fn, (eng.params, pool.arrays, eng._sampled, *args)


def _engine(family_custom, paged):
    import llm_ahead
    from nnstreamer_tpu.llm.paged import PagedKVCachePool
    from nnstreamer_tpu.llm.pool import KVCachePool

    family, cfg, params = llm_ahead.world(family_custom, 0)
    pool = (PagedKVCachePool(cfg, pages=12, page_size=8, slots=2) if paged
            else KVCachePool(cfg, 2, family=family))
    return DecodeEngine(params, cfg, pool, capacity=2)


SAMBAY = ("arch:sambay_lm,vocab:61,dim:32,heads:4,kv_heads:2,head_dim:8,"
          "mlp:64,layers:8,window:8,d_state:4,dt_rank:2,max_seq:64,"
          "dtype:float32")


@pytest.mark.parametrize("custom, program", [
    (CUSTOM, "step"), (CUSTOM, "pstep"), (CUSTOM, "prefill"),
    (CUSTOM, "chunk"), (SAMBAY, "step"), (SAMBAY, "prefill")])
def test_no_executable_of_the_warm_grid_returns_logits(custom, program):
    """What leaves each executable: the sampled tokens (``int32``, a
    lane each; one for a prefill), the ``(slots + 1,)`` vector they are
    kept in, and the donated state — nothing with a vocabulary axis."""
    eng = _engine(custom, paged=program in ("pstep", "chunk"))
    lanes = 2
    fn, args = _program(eng, program, lanes)
    out = fn.lower(*args).out_info
    tokens, sampled, state = out
    assert tokens.dtype == np.int32
    assert tokens.shape == ((lanes,) if "step" in program else ())
    assert sampled.dtype == np.int32 and sampled.shape == (3,)
    assert [(s.shape, s.dtype) for s in state] == [
        (a.shape, a.dtype) for a in eng.pool.arrays]
    vocab = eng.cfg.vocab
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        assert vocab not in leaf.shape, leaf


@pytest.mark.parametrize("program, scopes", [
    ("step", ALL_SCOPES), ("pstep", ALL_SCOPES), ("chunk", ALL_SCOPES),
    # the dense prefill reads no cache; its write is the engine's
    ("prefill", MATH | {"sflm.kv_write"})])
def test_lowered_step_program_names_its_scopes(program, scopes):
    text = _lowered(program)
    assert f"llm.engine.{program}/" in text
    for scope in sorted(ALL_SCOPES):
        assert (f"llm.engine.{program}/{scope}/" in text) == (
            scope in scopes), scope
    others = {"step", "pstep", "prefill", "chunk"} - {program}
    assert not any(f"llm.engine.{o}/" in text for o in others)
