"""Continuous-batching decode engine: one padded device invoke per step
over every resident sequence, flash-path prefill, conserved wall-time
attribution.

The decode loop's economics are the PR 9 bucket economics applied to
token generation: B single-token GEMV steps become ONE GEMM-shaped
``decode_step_pooled`` invoke, and the padded-lane quantization
(:meth:`~nnstreamer_tpu.filter.backends._jitexec.JitExecMixin.pad_rows`)
bounds the executable set so sequences joining and leaving the bucket
every step NEVER recompile — the same discipline that made partial
cross-stream buckets free.  Prompt prefill runs the full-sequence
forward (``models/streamformer_lm.prefill_kv``) with the Pallas
flash-attention path length-gated in, so long prompts never materialize
(T, T) scores; prompt lengths quantize to powers of two for the same
bounded-executables reason.

**Attribution is conserved by construction**: the engine's
:class:`PhaseClock` assigns every nanosecond of the decode thread's
life to exactly one of ``idle`` / ``admit`` / ``prefill`` / ``decode``
/ ``egress`` (state transitions stamp a monotonic clock; there are no
gaps and no overlaps), so the profiler's prefill-vs-decode shares sum
to 100 % of loop wall time exactly — the PR 8 conservation spine,
applied to the one thread the frame-window partitioner cannot see
inside.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..analysis import compileledger
from ..analysis.compileledger import compile_budget
from ..filter.backends._jitexec import JitExecMixin
from .pool import KVCachePool, Session

#: PhaseClock states (closed set; every decode-thread nanosecond lands
#: in exactly one).  ``llm-prefill-chunk`` is the paged tier's
#: interleaved-prefill share: time spent advancing ONE bounded prompt
#: chunk between decode steps — its presence (and the decode share
#: staying alive next to it) is the proof a long prompt no longer
#: stalls resident token streams.  ``compile`` is the cold-executable
#: share: a dispatch whose executable was not yet in this engine's warm
#: set charges its whole device call here instead of decode/prefill, so
#: a mid-serve XLA compile is NAMED in the attribution (and the
#: zero-steady-state-compiles discipline shows up as this share being
#: exactly the warmup, never growing after).
PHASES = ("idle", "admit", "prefill", "llm-prefill-chunk", "decode",
          "egress", "compile")

#: the name each state takes as a span in the JAX profiler's trace
#: (``llm.idle`` … ``llm.prefill_chunk`` … ``llm.compile``)
SPANS = {p: "llm." + p.replace("llm-", "").replace("-", "_")
         for p in PHASES}


def quantize_pages(n: int, table_max: int) -> int:
    """Padded block-table WIDTH for a paged dispatch: next power of two
    capped at ``table_max`` (= ``max_seq // page_size``) — the
    ``quantize_prompt`` discipline applied to the page axis, so block
    tables of every length land on a bounded ``log2``-ish executable
    set.  Padding entries point at the scratch page."""
    cap = max(1, int(table_max))
    q = 1
    while q < n:
        q <<= 1
    return min(q, cap)


def _cfg_key(cfg) -> tuple:
    # arity is fixed: cfg is a frozen StreamFormerConfig dataclass, so
    # the field set is a compile-time constant of the class
    # nnsjit: allow(unbounded-signature)
    return tuple(sorted((k, str(v)) for k, v in vars(cfg).items()))


#: process-wide jitted-callable memo: engines with the SAME model
#: config share one jit object per executable family (jax re-
#: specializes per operand shape inside it), so a test suite or fleet
#: restarting elements does not re-trace identical math.  Per-engine
#: ``compiles`` counters still count warm-set entries per engine — the
#: bounded-executables evidence is unchanged.
_EXEC_MEMO: Dict[tuple, Any] = {}


def _memo_jit(key: tuple, make):
    fn = _EXEC_MEMO.get(key)
    if fn is None:
        fn = make()
        _EXEC_MEMO[key] = fn
    return fn


def _sample(logits, sampled, rows):
    """How every engine executable ends: the greedy token of ``logits``
    (the lowest index among equals, as ``np.argmax`` takes it) and
    ``sampled`` with it written at ``rows``.  Logits stay on the chip."""
    import jax.numpy as jnp

    out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return out, sampled.at[rows].set(out)


class PhaseClock:
    """Exact wall-time attribution for one thread: ``enter(state)``
    transitions stamp ``mono_ns`` once, accumulate the outgoing state's
    interval, and by construction the per-state sums partition the
    thread's total wall time — conservation is an identity, not a
    measurement.

    ``annotate=True`` (the engine's clock) also makes the partition
    visible on the device's clock: inside :meth:`on_this_thread` every
    transition closes the open ``jax.profiler.TraceAnnotation`` and
    opens ``llm.<state>`` (:data:`SPANS`), so a profiler session that is
    recording — the benchmark's traced slice, ``launch.py --jax-trace``
    — holds the thread's phases as flat spans, never overlapping, one
    open at any instant.  With no session an annotation records nothing.
    A phase an inner phase interrupts (``admit`` around ``prefill`` and
    ``egress``) shows as several pieces; arguments ride on the piece
    ``enter(state, **kw)`` opens, a restore passes none.  A bare clock
    never imports ``jax``."""

    def __init__(self, clock_ns=None, annotate: bool = False) -> None:
        from ..obs.clock import mono_ns

        self._clock_ns = clock_ns if clock_ns is not None else mono_ns
        self.ns: Dict[str, int] = {p: 0 for p in PHASES}
        self._state = "idle"
        self._t0 = self._clock_ns()
        self._born = self._t0
        self._annotation = None
        if annotate:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
        #: the open annotation; None outside :meth:`on_this_thread`
        self._span = None

    def enter(self, state: str, **kw) -> str:
        """Transition; returns the OUTGOING state so nested phases
        (engine prefill/decode inside the element's admit/egress) can
        restore their caller's state on exit.  ``kw`` become the
        arguments of the span this opens (an annotating clock only)."""
        now = self._clock_ns()
        self.ns[self._state] += now - self._t0
        prev, self._state = self._state, state
        self._t0 = now
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = self._annotation(SPANS[state], **kw)
            self._span.__enter__()
        return prev

    @contextlib.contextmanager
    def on_this_thread(self):
        """The clock's one thread runs its loop inside this: the span of
        the current state opens here and the open one closes on the way
        out.  Transitions made outside it (``warmup()`` on the thread
        that starts the element) open nothing — an annotation closed on
        another thread than it was opened on is lost."""
        if self._annotation is not None:
            self._span = self._annotation(SPANS[self._state])
            self._span.__enter__()
        try:
            yield
        finally:
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None

    def note(self, **kw) -> None:
        """Add arguments to the open span — what a phase learns after it
        began (an admission's verdict).  Nothing on a clock that does
        not annotate."""
        if self._span is not None:
            self._span.set_metadata(**kw)

    def child(self, part: str, state: Optional[str] = None, **kw):
        """``llm.<state>.<part>``: an annotation to nest, with ``with``,
        inside the open phase — of the open phase's name, or of
        ``state``'s where the part is another phase's work done inside
        this one (a prefill's token read by a collect); ``kw`` are its
        arguments.  Nothing on a clock that does not annotate."""
        if self._annotation is None:
            return contextlib.nullcontext()
        return self._annotation(
            f"{SPANS[state or self._state]}.{part}", **kw)

    def totals_ns(self) -> Dict[str, int]:
        """Integer per-state totals INCLUDING the in-progress state's
        open interval — the per-session blame-snapshot primitive: two
        snapshots subtract into an EXACT integer partition of the wall
        time between them (sum of per-state deltas == clock delta, the
        same identity :meth:`report` rounds for humans), so a session's
        accumulated blame reconciles with its admit→terminal window to
        the nanosecond."""
        now = self._clock_ns()
        ns = dict(self.ns)
        ns[self._state] += now - self._t0
        return ns

    def report(self) -> Dict[str, Any]:
        """Per-state seconds + shares; ``conserved_pct`` is exactly 100
        by construction (asserted: the identity IS the contract)."""
        now = self._clock_ns()
        ns = dict(self.ns)
        ns[self._state] += now - self._t0
        total = max(1, now - self._born)
        attributed = sum(ns.values())
        return {
            "total_s": total / 1e9,
            "states_s": {p: round(v / 1e9, 6) for p, v in ns.items()},
            "states_pct": {p: round(100.0 * v / total, 3)
                           for p, v in ns.items()},
            "conserved_pct": round(100.0 * attributed / total, 3),
        }


class _Flight(NamedTuple):
    """Work sent to the device whose tokens the host has not read."""
    out: Any                    # the tokens, still on the device
    sessions: List[Session]     # a step's lanes; a prefill's one session
    t0: float                   # when it was sent (the engine's clock)
    #: a dense prefill's: the number of the step dispatched after it,
    #: whose wait its token must not follow; ``None`` for a step
    ahead_of: Optional[int] = None
    #: a dense prefill's arguments for the span of its token's read
    trace: Optional[Dict[str, Any]] = None


def quantize_prompt(t: int, max_seq: int) -> int:
    """Padded prompt length for one prefill executable: next power of
    two from 8, capped at ``max_seq`` — a bounded ``log2(max_seq)``-ish
    executable set over arbitrary client prompt lengths (the decode
    lanes' ``pad_rows`` policy, applied to the sequence axis)."""
    cap = max(1, int(max_seq))
    q = 8
    while q < t:
        q <<= 1
    return min(q, cap)


class DecodeEngine:
    """The device half of the ``tensor_llm`` element: compiled prefill
    and pooled-decode executables over a :class:`KVCachePool`, plus the
    live accounting (tokens, step EWMA, phase attribution) the
    observability tier reads.

    Single-threaded by contract: exactly one decode thread calls
    :meth:`prefill` / :meth:`dispatch` / :meth:`collect` (the element's
    loop), so the pool arrays mutate without locks.  The jitted
    executables are cached per padded shape — sequences joining/leaving
    between steps change only the LANE COUNT, which quantizes onto the
    same warm set.

    The engine imports no model: what it compiles are the functions of
    the pool's FAMILY (``llm/family.py``), and what it passes them is
    ``pool.arrays`` as ONE donated tuple, whatever the family's sessions
    keep.  For the default ``streamformer_lm`` the dense pool's arrays
    are ``(layers, slots + 1, max_seq, heads * head_dim)``
    (:func:`~nnstreamer_tpu.llm.pool.dense_pool_shape`, the one place
    that shape is written): the step indexes ``[layer, slot,
    pos]``, the dense prefill installs a prompt's ``(L, 1, T, H * Dh)``
    run at ``(0, slot, 0, 0)``.  They are DONATED into the step and
    prefill executables (``donate_argnums``): XLA updates the pool in place
    instead of materializing an input+output copy per step — without
    donation the per-step cost scales with POOL size (the whole cache
    copies to scatter one row per layer), which taxed a lone session by
    >50 % for merely sharing a big pool.  Every call site reassigns
    ``pool.arrays`` from the outputs (a donated input buffer is
    dead).  A family whose ``chunk_len`` is not 0 has its prompts
    prefilled in chunks of that length through ONE ``_prefill``
    executable (positions, not a power-of-two bucket a prompt), each
    chunk taking the state the last one left in the slot.

    **Tokens are sampled on the chip and stay there.**  Every executable
    ends in :func:`_sample`: no logits leave the device, and the token a
    stream sampled last lives in ``_sampled``, an int32 ``(slots + 1,)``
    vector beside ``pool.arrays`` (row ``slots`` takes the padding
    lanes' writes), donated and reassigned with them.  A prompt's last
    chunk writes the stream's first token into its row, a step gathers
    its lanes' input tokens from there and scatters their outputs back,
    so a step needs nothing of the step before from the host:
    :meth:`dispatch` sends step k without waiting for it and
    :meth:`collect` reads the ``B`` int32 of the oldest step in flight.
    :meth:`step` is the two in a row.  The dense whole-prompt prefill
    joins the same pipeline (:meth:`dispatch_prefill`): sent without
    waiting, its first token read by :meth:`collect` in the order the
    work was sent, while the next step may already consume it.
    """

    def __init__(self, params, cfg, pool: KVCachePool,
                 capacity: int, prefill_mode: str = "auto",
                 clock=None, chunk: int = 0, family=None) -> None:
        import jax

        if family is None:
            from .family import get_family

            # the paged arena has no family of its own: the default's
            family = getattr(pool, "family", None) or get_family()
        self.family = family

        # the weights live where the pool lives, placed ONCE: params
        # built under models.registry.host_init and kept as given are
        # host arrays that every prefill and decode dispatch would copy
        # to the device again
        device = next(iter(pool.arrays[0].devices()))
        self.params = jax.device_put(params, device)
        # ... and the fresh pool arrays are committed there too, like
        # every later generation (a donated call's outputs are
        # committed): jit keys its executables on commitment, so an
        # uncommitted first generation would make the first shape
        # warmed compile a second time on its first live dispatch
        pool.arrays = tuple(jax.device_put(tuple(pool.arrays), device))
        self._device = device
        self._sampled = jax.device_put(
            np.zeros((pool.slots + 1,), np.int32), device)
        #: work sent and not yet collected, in the order it was sent
        #: (:class:`_Flight`): at most two steps — one, and a second
        #: between a dispatch and the collect that follows it — and the
        #: dense prefills sent between them, one a slot at most: a
        #: prefill holds its session's slot until it is collected
        # nnslint: allow(unbounded-queue)
        self._flights: collections.deque = collections.deque()
        self._collected_s = 0.0
        self.cfg = cfg
        self.pool = pool
        self.capacity = max(1, int(capacity))
        if prefill_mode not in ("auto", "flash", "naive", "step"):
            raise ValueError(f"prefill mode {prefill_mode!r} "
                             "(want auto | flash | naive | step)")
        self.prefill_mode = prefill_mode
        self._clock = clock if clock is not None else time.monotonic
        self._jax = jax
        #: paged pool?  (block-paged arena + tables instead of slots)
        self.paged = getattr(pool, "page_size", 0) > 0
        #: interleaved-prefill chunk size in tokens (paged only;
        #: 0 = whole remaining prompt in one chunk executable)
        self.chunk = max(0, int(chunk)) if self.paged else 0
        #: dense prefill in fixed chunks of this many positions (the
        #: family's; 0 = the whole prompt, padded to a power of two)
        self.chunk_len = 0 if self.paged else int(family.chunk_len(cfg))
        self._step_jit: Dict[Any, Any] = {}      # padded B[, W] -> exec
        self._prefill_jit: Dict[Any, Any] = {}   # padded T / (C, W)
        self.phases = PhaseClock(annotate=True)
        # live accounting the gauges read.  tokens_total counts every
        # GENERATED token (incl. each session's first, argmaxed from
        # the prefill logits); step_tokens only the decode-step ones —
        # the honest numerator for mean bucket fill.
        self.tokens_total = 0
        self.step_tokens = 0
        self.steps_total = 0
        self.prefills_total = 0
        self.prefill_chunks_total = 0
        #: dense prefills sent without waiting for their token, and of
        #: those the ones sent while other work was in flight on the
        #: device (the overlap the pipeline buys)
        self.prefills_async = 0
        self.prefills_behind = 0
        #: steps dispatched while the step before was uncollected, and
        #: lanes stepped after their stream had ended (a stop token or an
        #: eviction the host learnt of a step late): their output is
        #: dropped and counts as no token
        self.steps_ahead = 0
        self.lanes_discarded = 0
        #: cache positions the real lanes of every dispatched step
        #: attended (each lane its ``pos + 1``), and what their slots
        #: reserve (``max_seq`` a lane): the share of the reserved
        #: positions a step that follows its lanes' contexts reads
        self.attended_positions = 0
        self.reserved_positions = 0
        self.last_fill = 0
        self.ewma_step_s = 0.0
        self.compiles = 0
        #: set by the executable getters on a per-engine warm-set miss,
        #: consumed by the next dispatch (:meth:`_enter_cold`): that
        #: dispatch's device call charges the ``compile`` phase instead
        #: of decode/prefill.  Per-ENGINE coldness on purpose — the
        #: process-wide ``_EXEC_MEMO`` may make the call cheap, but the
        #: attribution question is "did THIS engine meet a cold
        #: executable", which after :meth:`warmup` must never happen.
        self._cold_exec = False

    # -- executables -----------------------------------------------------
    @compile_budget(16, site="llm.engine.step")
    def _step_fn(self, padded: int):
        fn = self._step_jit.get(padded)
        if fn is None:
            compileledger.record("llm.engine.step",
                                 (("padded", padded),))
            cfg, family = self.cfg, self.family

            def _make():
                @self._jax.named_scope("llm.engine.step")
                def _step(params, state, sampled, pos, slots):
                    logits, state = family.decode_step(
                        params, state, sampled[slots], pos, slots, cfg)
                    return (*_sample(logits, sampled, slots), state)

                return self._jax.jit(_step, donate_argnums=(1, 2))

            fn = _memo_jit(("step", family.name, _cfg_key(cfg)), _make)
            self._step_jit[padded] = fn
            self.compiles += 1
            self._cold_exec = True
        return fn

    @compile_budget(64, site="llm.engine.pstep")
    def _pstep_fn(self, padded: int, width: int):
        """Paged decode executable: one per ``(padded B, table width)``
        pair — both axes quantized, so the warm set stays a bounded
        ``|pad_rows| x |quantize_pages|`` grid."""
        key = (padded, width)
        fn = self._step_jit.get(key)
        if fn is None:
            compileledger.record("llm.engine.pstep",
                                 (("padded", padded),
                                  ("width", width)))
            cfg, family = self.cfg, self.family
            ps = self.pool.page_size

            def _make():
                @self._jax.named_scope("llm.engine.pstep")
                def _step(params, state, sampled, pos, rows, tables):
                    logits, state = family.decode_step_paged(
                        params, state, sampled[rows], pos, tables, cfg,
                        ps)
                    return (*_sample(logits, sampled, rows), state)

                return self._jax.jit(_step, donate_argnums=(1, 2))

            fn = _memo_jit(("pstep", family.name, _cfg_key(cfg), ps),
                           _make)
            self._step_jit[key] = fn
            self.compiles += 1
            self._cold_exec = True
        return fn

    @compile_budget(64, site="llm.engine.chunk")
    def _chunk_fn(self, padded_c: int, width: int):
        """Paged prefill-chunk executable per ``(padded C, table
        width)``; chunk origin and real length ride as traced operands,
        so ONE executable serves every chunk of every prompt at every
        prefix-hit offset under its quantized bucket.  ``row`` is where
        the chunk's token goes: the session's on a prompt's last chunk,
        the padding lanes' before."""
        key = ("chunk", padded_c, width)
        fn = self._prefill_jit.get(key)
        if fn is None:
            compileledger.record("llm.engine.chunk",
                                 (("padded_c", padded_c),
                                  ("width", width)))
            cfg, family = self.cfg, self.family
            ps = self.pool.page_size

            def _make():
                @self._jax.named_scope("llm.engine.chunk")
                def _chunk(params, state, sampled, tokens, table, start,
                           true_len, scratch, row):
                    logits, state = family.prefill_chunk_paged(
                        params, state, tokens, table, start, true_len,
                        cfg, ps, scratch)
                    return (*_sample(logits, sampled, row), state)

                return self._jax.jit(_chunk, donate_argnums=(1, 2))

            fn = _memo_jit(("chunk", family.name, _cfg_key(cfg), ps),
                           _make)
            self._prefill_jit[key] = fn
            self.compiles += 1
            self._cold_exec = True
        return fn

    @compile_budget(32, site="llm.engine.prefill")
    def _prefill_fn(self, padded_t: int):
        fn = self._prefill_jit.get(padded_t)
        if fn is None:
            compileledger.record("llm.engine.prefill",
                                 (("padded_t", padded_t),))
            cfg, family = self.cfg, self.family
            flash = {"auto": None, "flash": True,
                     "naive": False}[self.prefill_mode]
            jax = self._jax
            chunked = self.chunk_len > 0

            def _make():
                if chunked:
                    # only the last chunk has logits (the others answer
                    # zeros): theirs goes to the padding lanes' row
                    @jax.named_scope("llm.engine.prefill")
                    def _prefill(params, state, sampled, tokens, slot,
                                 start, true_len, last):
                        logits, state = family.prefill_chunk(
                            params, state, tokens, slot, start, true_len,
                            last, cfg)
                        row = jax.numpy.where(last, slot,
                                              sampled.shape[0] - 1)
                        return (*_sample(logits, sampled, row), state)
                else:
                    # the family installs the whole padded run into the
                    # slot (``sflm.kv_write``) and answers with the
                    # logits of position ``true_len - 1``
                    @jax.named_scope("llm.engine.prefill")
                    def _prefill(params, state, sampled, tokens, slot,
                                 true_len):
                        logits, state = family.prefill(
                            params, state, tokens, slot, true_len, cfg,
                            flash)
                        return (*_sample(logits, sampled, slot), state)

                return jax.jit(_prefill, donate_argnums=(1, 2))

            fn = _memo_jit(("prefill", family.name, _cfg_key(cfg), flash),
                           _make)
            self._prefill_jit[padded_t] = fn
            self.compiles += 1
            self._cold_exec = True
        return fn

    def _enter_cold(self) -> Optional[str]:
        """Consume the cold-executable flag: when the last getter
        missed this engine's warm set, move the PhaseClock to
        ``compile`` and return the phase to restore after the dispatch
        (None when warm — the hot path pays one attribute read)."""
        if not self._cold_exec:
            return None
        self._cold_exec = False
        return self.phases.enter("compile")

    def warmup(self) -> None:
        """Pre-compile every executable live serving can dispatch (the
        PR 9 warmup_stacked discipline): the padded decode-lane shapes
        AND the pow2-quantized prefill lengths.  Both sets are small
        and enumerable; without this, each shape's first live use
        stalls the SINGLE decode thread for a full XLA compile —
        token emission for every resident session stops for seconds,
        exactly the mid-soak latency spike warmup exists to prevent
        (prefills were the gap a code-review pass caught: a fresh
        prompt-length bucket compiled mid-serve)."""
        # the whole warmup charges the ``compile`` phase: it IS the
        # compile cost, paid up front — after it the share must never
        # grow (the zero-steady-state-compiles gate, made visible in
        # the attribution instead of only the ledger)
        cprev = self.phases.enter("compile")
        try:
            self._warmup_impl()
        finally:
            self.phases.enter(cprev)
            self._cold_exec = False

    def _warmup_impl(self) -> None:
        import jax.numpy as jnp

        if self.paged:
            self._warmup_paged()
            return
        shapes = sorted({JitExecMixin.pad_rows(n, self.capacity)
                         for n in range(1, self.capacity + 1)})
        for rows in shapes:
            pos = jnp.zeros((rows,), jnp.int32)
            slots = jnp.full((rows,), self.pool.scratch, jnp.int32)
            fn = self._step_fn(rows)
            # donated operands: the pool arrays and the sampled tokens
            # MUST be reassigned from the outputs (the inputs' buffers
            # are dead after the call)
            out, self._sampled, self.pool.arrays = fn(
                self.params, self.pool.arrays, self._sampled, pos, slots)
            self._jax.block_until_ready(out)
        if self.prefill_mode == "step":
            return   # prompt decode rides the step executables above
        if self.chunk_len > 0:
            # ONE executable whatever the prompt's length, both ways
            # through its ``last`` branch
            fn = self._prefill_fn(self.chunk_len)
            for last in (False, True):
                out, self._sampled, self.pool.arrays = fn(
                    self.params, self.pool.arrays, self._sampled,
                    jnp.zeros((self.chunk_len,), jnp.int32),
                    jnp.int32(self.pool.scratch), jnp.int32(0),
                    jnp.int32(1), jnp.bool_(last))
                self._jax.block_until_ready(out)
            return
        lengths, t = [], 8
        while True:
            lengths.append(min(t, self.cfg.max_seq))
            if t >= self.cfg.max_seq:
                break
            t <<= 1
        for padded in sorted(set(lengths)):
            fn = self._prefill_fn(padded)
            out, self._sampled, self.pool.arrays = fn(
                self.params, self.pool.arrays, self._sampled,
                jnp.zeros((padded,), jnp.int32),
                jnp.int32(self.pool.scratch), jnp.int32(1))
            self._jax.block_until_ready(out)
        # scratch writes during warmup are garbage by design; zero the
        # scratch lane is unnecessary (no session ever reads it)

    def _widths(self):
        """The pow2-quantized block-table widths live dispatch can
        produce — a bounded ``log2(table_max)``-ish set."""
        table_max = self.pool.table_max
        out, w = set(), 1
        while True:
            out.add(min(w, table_max))
            if w >= table_max:
                break
            w <<= 1
        return sorted(out)

    def _chunk_lengths(self):
        """Padded chunk sizes the paged prefill path can dispatch:
        the fixed chunk when interleaving, else the pow2 prompt
        quantization (one whole-suffix chunk per bucket)."""
        if self.chunk > 0:
            return [self.chunk]
        lengths, t = [], 8
        while True:
            lengths.append(min(t, self.cfg.max_seq))
            if t >= self.cfg.max_seq:
                break
            t <<= 1
        return sorted(set(lengths))

    def _warmup_paged(self) -> None:
        """Paged warm set: the ``pad_rows x quantize_pages`` decode
        grid plus every ``(chunk length, width)`` prefill pair whose
        width can cover the chunk — all dispatched at the scratch page,
        so live serving never meets a cold executable (the
        zero-steady-state-compiles acceptance)."""
        import jax.numpy as jnp

        pool = self.pool
        widths = self._widths()
        rows_set = sorted({JitExecMixin.pad_rows(n, self.capacity)
                           for n in range(1, self.capacity + 1)})
        for rows in rows_set:
            for w in widths:
                pos = jnp.zeros((rows,), jnp.int32)
                lanes = jnp.full((rows,), pool.slots, jnp.int32)
                tables = jnp.full((rows, w), pool.scratch, jnp.int32)
                fn = self._pstep_fn(rows, w)
                out, self._sampled, pool.arrays = fn(
                    self.params, pool.arrays, self._sampled, pos, lanes,
                    tables)
                self._jax.block_until_ready(out)
        if self.prefill_mode == "step":
            return   # prompt decode rides the paged step grid above
        ps = pool.page_size
        for c in self._chunk_lengths():
            min_w = quantize_pages(-(-c // ps), pool.table_max)
            for w in widths:
                if w < min_w:
                    continue
                fn = self._chunk_fn(c, w)
                out, self._sampled, pool.arrays = fn(
                    self.params, pool.arrays, self._sampled,
                    jnp.zeros((c,), jnp.int32),
                    jnp.full((w,), pool.scratch, jnp.int32),
                    jnp.int32(0), jnp.int32(1),
                    jnp.int32(pool.scratch), jnp.int32(pool.slots))
                self._jax.block_until_ready(out)

    # -- prefill ---------------------------------------------------------
    def prefill(self, sess: Session, prompt: np.ndarray) -> int:
        """Seed ``sess``'s cache slot from its prompt and return the
        session's FIRST generated token (greedy argmax of the last
        prompt position's logits — :func:`generate`'s semantics), which
        the executable also left in the session's row of ``_sampled``
        for its first step.  Synchronous: it waits for that one int32,
        behind whatever is in flight (:meth:`dispatch_prefill` does
        not).

        ``prefill_mode="step"`` decodes the prompt token-by-token
        through the pooled step instead (the decode-without-prefill
        path the verifier warns about: correct, but T GEMV steps and no
        flash win)."""
        prev = self.phases.enter("prefill")
        try:
            if self.prefill_mode == "step":
                return self._prefill_by_steps(sess, prompt)
            if self.paged:
                # the non-interleaved path: ``chunk == 0`` makes it ONE
                # whole-suffix chunk
                while True:
                    first = self._advance_chunk(sess)
                    if first is not None:
                        return first
            if self.chunk_len > 0:
                first = self._prefill_chunks(sess, prompt)
            else:
                out = self._send_prefill(sess, prompt)
                with self.phases.child("wait"):
                    first = int(out)
            sess.pos = int(prompt.shape[0])
            self._prefilled(sess)
            return first
        finally:
            self.phases.enter(prev)

    def dispatch_prefill(self, sess: Session, prompt: np.ndarray,
                         **trace) -> Optional[int]:
        """:meth:`prefill` without the wait where the engine can: the
        dense whole-prompt prefill (no ``chunk_len``, not paged, not
        ``prefill_mode="step"``) is sent and left in flight, and
        ``None`` comes back.  Its first token is already in the
        session's row of ``_sampled``, so the session may ride the next
        step (``pos`` is the prompt's length, the token counted in its
        ``in_flight``); :meth:`collect` reads the token in the order
        the work was sent, so before the wait on any step dispatched
        after it.  ``trace`` are arguments for the span of that read
        (the request's keys).  Every other path waits, as
        :meth:`prefill` does, and returns the token."""
        if self.prefill_mode == "step" or self.paged or self.chunk_len:
            return self.prefill(sess, prompt)
        behind = bool(self._flights)
        prev = self.phases.enter("prefill")
        try:
            out = self._send_prefill(sess, prompt)
        finally:
            self.phases.enter(prev)
        sess.pos = int(prompt.shape[0])
        sess.in_flight += 1
        sess.last_step_s = self._clock()
        self.prefills_total += 1
        self.prefills_async += 1
        self.prefills_behind += behind
        self._flights.append(_Flight(
            out, [sess], sess.last_step_s,
            ahead_of=self.steps_total + self._steps_out(), trace=trace))
        return None

    def _send_prefill(self, sess: Session, prompt: np.ndarray):
        """Send the dense prefill: the prompt padded to its power of
        two, installed into ``sess``'s slot, its first token written to
        the session's row of ``_sampled``.  Returns that token, still
        on the device."""
        import jax.numpy as jnp

        t = int(prompt.shape[0])
        padded = quantize_prompt(t, self.cfg.max_seq)
        self.phases.note(padded=padded)
        buf = np.zeros((padded,), np.int32)
        buf[:t] = prompt
        fn = self._prefill_fn(padded)
        cold = self._enter_cold()
        try:
            with self.phases.child("dispatch"):
                out, self._sampled, self.pool.arrays = fn(
                    self.params, self.pool.arrays, self._sampled,
                    jnp.asarray(buf), jnp.int32(sess.slot), jnp.int32(t))
        finally:
            if cold is not None:
                self.phases.enter(cold)
        return out

    def _prefilled(self, sess: Session) -> None:
        self.prefills_total += 1
        self.tokens_total += 1
        sess.last_step_s = self._clock()

    def _prefill_by_steps(self, sess: Session, prompt: np.ndarray) -> int:
        """The prompt through the step executables, a position a step,
        each token forced on its step (``next_token``); a paged session
        starts behind its prefix hit.  Only the last step's token is
        waited for."""
        t = int(prompt.shape[0])
        for i in range(getattr(sess, "prefill_pos", 0), t):
            sess.pos, sess.next_token = i, int(prompt[i])
            out = self._launch([sess])
        sess.pos = t
        if self.paged:
            self.pool.note_prefill(sess, t)
        self._prefilled(sess)
        with self.phases.child("wait"):
            return int(np.asarray(out)[0])

    def _prefill_chunks(self, sess: Session, prompt: np.ndarray) -> int:
        """The prompt through the family's one chunk executable: every
        chunk is dispatched behind the last (each takes the state the
        one before left in the slot, so the device runs them in order
        while the host goes on), and only the last chunk's token is
        waited for."""
        import jax.numpy as jnp

        c, t = self.chunk_len, int(prompt.shape[0])
        n = -(-t // c)
        self.phases.note(padded=n * c, chunks=n)
        buf = np.zeros((n * c,), np.int32)
        buf[:t] = prompt
        fn = self._prefill_fn(c)
        cold = self._enter_cold()
        try:
            with self.phases.child("dispatch"):
                slot = jnp.int32(sess.slot)
                for i in range(n):
                    out, self._sampled, self.pool.arrays = fn(
                        self.params, self.pool.arrays, self._sampled,
                        jnp.asarray(buf[i * c:(i + 1) * c]), slot,
                        jnp.int32(i * c), jnp.int32(min(c, t - i * c)),
                        jnp.bool_(i == n - 1))
            self.prefill_chunks_total += n
            with self.phases.child("wait"):
                return int(out)
        finally:
            if cold is not None:
                self.phases.enter(cold)

    # -- paged prefill ---------------------------------------------------
    def prefill_chunk_step(self, sess) -> Optional[int]:
        """Advance ``sess``'s prefill by ONE bounded chunk — the
        element's decode loop interleaves these between decode steps so
        a long prompt cannot stall resident token streams.  Returns the
        session's first generated token when the prompt completes,
        ``None`` while chunks remain.  Attributed to the PhaseClock's
        ``llm-prefill-chunk`` share (the interleaving proof)."""
        prev = self.phases.enter("llm-prefill-chunk")
        try:
            return self._advance_chunk(sess)
        finally:
            self.phases.enter(prev)

    def _advance_chunk(self, sess) -> Optional[int]:
        """One paged prefill chunk: grow the table over the chunk's
        real positions, dispatch the ``(padded C, width)`` executable
        (origin and real length as traced operands), register any
        newly-full prompt pages with the prefix cache.  Returns the
        first generated token on the FINAL chunk (sampled from position
        ``plen - 1``'s logits into the session's row), else ``None``."""
        import jax.numpy as jnp

        pool = self.pool
        ps = pool.page_size
        cfg = self.cfg
        start = sess.prefill_pos
        remaining = sess.plen - start
        if remaining <= 0:
            raise RuntimeError(f"session {sess.key!r} is not prefilling")
        c_real = remaining if self.chunk <= 0 \
            else min(self.chunk, remaining)
        c_pad = self.chunk if self.chunk > 0 \
            else quantize_prompt(c_real, cfg.max_seq)
        pool.grow(sess, start + c_real)
        span = min(start + c_pad, cfg.max_seq)
        w = quantize_pages(-(-span // ps), pool.table_max)
        toks = np.zeros((c_pad,), np.int32)
        toks[:c_real] = sess.prompt[start:start + c_real]
        table = np.full((w,), pool.scratch, np.int32)
        m = min(len(sess.table), w)
        table[:m] = sess.table[:m]
        row = sess.slot if c_real == remaining else pool.slots
        fn = self._chunk_fn(c_pad, w)
        cold = self._enter_cold()
        try:
            with self.phases.child("dispatch"):
                out, self._sampled, pool.arrays = fn(
                    self.params, pool.arrays, self._sampled,
                    jnp.asarray(toks), jnp.asarray(table),
                    jnp.int32(start), jnp.int32(c_real),
                    jnp.int32(pool.scratch), jnp.int32(row))
        finally:
            if cold is not None:
                self.phases.enter(cold)
        pool.note_prefill(sess, start + c_real)
        self.prefill_chunks_total += 1
        sess.last_step_s = self._clock()
        if sess.prefilling:
            return None
        sess.pos = sess.plen
        self._prefilled(sess)
        with self.phases.child("wait"):
            return int(out)

    # -- decode ----------------------------------------------------------
    def _launch(self, sessions: Sequence[Session]):
        """One step over ``sessions``, each at its ``pos``, sent to the
        device: the tokens it will sample, still there.  Padding lanes
        point at the scratch slot (the scratch page of a paged pool),
        position 0, and at the last row of ``_sampled``: their writes
        can never touch a live session.  A paged lane's tail page is
        allocated here (lazily, from the reservation admission made)."""
        import jax.numpy as jnp

        pool = self.pool
        forced = [s for s in sessions if s.next_token is not None]
        if forced:
            # the rare way in for a token of the caller's: read the
            # vector (behind whatever is in flight), set, put it back
            sampled = np.array(self._sampled)
            for s in forced:
                sampled[s.slot], s.next_token = s.next_token, None
            self._sampled = self._jax.device_put(sampled, self._device)
        with self.phases.child("operands"):
            padded = JitExecMixin.pad_rows(len(sessions), self.capacity)
            rows = np.full((padded,), pool.slots, np.int32)
            pos = np.zeros((padded,), np.int32)
            for i, s in enumerate(sessions):
                rows[i], pos[i] = s.slot, s.pos
            operands = [jnp.asarray(pos), jnp.asarray(rows)]
            if self.paged:
                ps = pool.page_size
                for s in sessions:
                    pool.grow(s, s.pos + 1)
                w = quantize_pages(max(-(-(s.pos + 1) // ps)
                                       for s in sessions), pool.table_max)
                tables = np.full((padded, w), pool.scratch, np.int32)
                for i, s in enumerate(sessions):
                    m = min(len(s.table), w)
                    tables[i, :m] = s.table[:m]
                operands.append(jnp.asarray(tables))
                fn = self._pstep_fn(padded, w)
            else:
                fn = self._step_fn(padded)
        cold = self._enter_cold()
        try:
            with self.phases.child("dispatch"):
                out, self._sampled, pool.arrays = fn(
                    self.params, pool.arrays, self._sampled, *operands)
        finally:
            if cold is not None:
                self.phases.enter(cold)
        return out

    @property
    def in_flight(self) -> int:
        """Steps and dense prefills sent and not yet collected."""
        return len(self._flights)

    @property
    def prefill_next(self) -> bool:
        """The oldest work in flight is a dense prefill: the next
        :meth:`collect` reads its session's first token."""
        return bool(self._flights) and \
            self._flights[0].ahead_of is not None

    def _steps_out(self) -> int:
        return sum(1 for f in self._flights if f.ahead_of is None)

    def dispatch(self, sessions: Sequence[Session]) -> None:
        """Send one continuous-batching decode step over ``sessions`` (≤
        ``capacity``; the element's round-robin pick) and return without
        waiting for it: each lane consumes the token the chip sampled
        for its stream last and advances its cache position.  The
        step's tokens are :meth:`collect`'s to read."""
        ahead = self._steps_out()
        if ahead > 1:
            raise RuntimeError("two steps in flight: collect() the "
                               "older before a third is dispatched")
        t0 = self._clock()
        attended = sum(s.pos for s in sessions) + len(sessions)
        prev = self.phases.enter("decode", step=self.steps_total + ahead,
                                 lanes=len(sessions), ahead=ahead,
                                 attended=attended)
        try:
            out = self._launch(sessions)
        finally:
            self.phases.enter(prev)
        for s in sessions:
            s.pos += 1
            s.in_flight += 1
        self.steps_ahead += ahead
        self.attended_positions += attended
        self.reserved_positions += len(sessions) * self.cfg.max_seq
        self._flights.append(_Flight(out, list(sessions), t0))

    def collect(self) -> List[Tuple[Session, int]]:
        """Wait for the oldest work in flight and read its tokens.  A
        step's ``B`` int32: its lanes, each with the NEXT token of its
        stream (the caller emits it and decides stop-token/max-new
        completion); a lane whose session the pool took back while the
        step ran is left out and counted in ``lanes_discarded``.  A
        dense prefill's one int32: its session with its FIRST token
        (:meth:`prefill_next`), or nothing where the pool took the
        session back."""
        flight = self._flights.popleft()
        if flight.ahead_of is not None:
            return self._collect_prefill(flight)
        out, sessions, t0 = flight.out, flight.sessions, flight.t0
        prev = self.phases.enter("decode")
        with self.phases.child("wait"):
            tokens = np.asarray(out).tolist()
        with self.phases.child("sample"):
            now = self._clock()
            lanes = []
            for s, tok in zip(sessions, tokens):
                s.in_flight -= 1
                if not s.released:
                    s.last_step_s = now
                    lanes.append((s, tok))
            self.steps_total += 1
            self.tokens_total += len(lanes)
            self.step_tokens += len(lanes)
            self.lanes_discarded += len(sessions) - len(lanes)
            self.last_fill = len(sessions)
            # the loop's period where steps run one behind another, the
            # step's own time where each is collected at once
            dt = now - max(t0, self._collected_s)
            self._collected_s = now
            self.ewma_step_s = (dt if self.ewma_step_s == 0.0
                                else 0.8 * self.ewma_step_s + 0.2 * dt)
        self.phases.enter(prev)
        return lanes

    def _collect_prefill(self, flight: _Flight) -> List[Tuple[Session,
                                                                int]]:
        """Read a dense prefill's first token: a piece of ``decode``
        (the thread waits on the device, as for a step) holding
        ``llm.prefill.wait`` with the number of the step sent after it
        and the request's keys."""
        (sess,) = flight.sessions
        trace = flight.trace or {}
        prev = self.phases.enter("decode", **trace)
        try:
            with self.phases.child("wait", state="prefill",
                                   step=flight.ahead_of, **trace):
                first = int(flight.out)
        finally:
            self.phases.enter(prev)
        sess.in_flight -= 1
        if sess.released:
            return []
        self.tokens_total += 1
        return [(sess, first)]

    def step(self, sessions: Sequence[Session]) -> List[int]:
        """:meth:`dispatch` then :meth:`collect`: one synchronous step,
        the next token per session — for callers that run no step
        ahead."""
        if not sessions:
            return []
        if self._flights:
            raise RuntimeError("step() with work in flight: collect() "
                               "it first")
        self.dispatch(sessions)
        return [tok for _, tok in self.collect()]

    # -- hints / report --------------------------------------------------
    def retry_after_hint(self) -> float:
        """Retry-after for a no-free-slot shed: the soonest-finishing
        resident session's expected remaining wall time under the live
        step EWMA (floored — a hint of 0 would invite an instant
        re-offer into the same full pool)."""
        sessions = self.pool.sessions()
        step_s = self.ewma_step_s or 0.01
        if not sessions:
            return max(0.05, step_s)
        remaining = min(max(1, s.max_new - s.emitted) for s in sessions)
        return max(0.05, remaining * step_s)

    def report(self) -> Dict[str, Any]:
        phases = self.phases.report()
        out = {
            "tokens": self.tokens_total,
            "steps": self.steps_total,
            "steps_ahead": self.steps_ahead,
            "lanes_discarded": self.lanes_discarded,
            "attended_positions": self.attended_positions,
            "reserved_positions": self.reserved_positions,
            "prefills": self.prefills_total,
            "prefills_async": self.prefills_async,
            "prefills_behind": self.prefills_behind,
            "mean_fill": round(self.step_tokens
                               / max(1, self.steps_total), 2),
            "ewma_step_ms": round(self.ewma_step_s * 1e3, 3),
            "compiles": self.compiles,
            "cache_bytes": self.pool.cache_bytes(),
            "cache_bytes_by_kind": self.pool.bytes_by_kind(),
            "phases": phases,
        }
        if self.paged or self.chunk_len > 0:
            out["prefill_chunks"] = self.prefill_chunks_total
        if self.paged:
            out["paged"] = self.pool.stats()
        counters = getattr(self.family, "state_counters", None)
        if counters is not None:
            # a host read of a few floats of the state: a report's, on
            # request, never the loop's
            try:
                out["state_counters"] = counters(self.cfg,
                                                 self.pool.arrays)
            except RuntimeError:
                pass        # donated to a step in flight: not now
        return out
