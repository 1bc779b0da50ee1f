"""Session-keyed KV-cache slot pool: bounded, admission-controlled,
LRU/deadline-evicted.

A token-streaming session's device state is one STATIC-shape cache slot
(``max_seq`` rows of ``heads × head_dim`` per layer, for K and for V —
the ``models/streamformer_lm.py`` pooled-decode contract,
:func:`dense_pool_shape`), so the whole tier's cache memory is fixed at
construction: ``layers × (slots + 1) × max_seq × heads × head_dim × 2 ×
itemsize`` bytes, one scratch slot included for padding lanes.  There is NO per-session allocation on the
admission path — a session either gets a pre-allocated slot or an
explicit shed with a retry-after hint, never unbounded memory (the
PR 7 overload doctrine applied to session state instead of queue
depth).

WHAT a slot holds is the served family's affair (``llm/family.py``):
:class:`SlotPool` is the bookkeeping alone, and :class:`KVCachePool`
lays the family's ``init_state`` arrays beside it — keys and values by
position for every layer (``streamformer_lm``), or one layer's rows, a
ring per windowed layer and fixed recurrent rows side by side
(``sambay_lm``).

Slot admission composes the existing
:class:`~nnstreamer_tpu.query.overload.AdmissionController`: a
watermark policy over SLOT occupancy sheds bronze sessions before the
pool is full (so background traffic cannot take the last slots a gold
prompt needs), drain mode sheds everything, and "no free slot" is the
hard watermark underneath.  Eviction is explicit — client disconnect,
EOS, or a deadline on sessions that stopped making progress — and an
evicted slot returns to the free list with its device memory untouched
(the next session's prefill overwrites it; positions beyond the new
session's ``pos`` are masked by the decode math, so stale bytes can
never leak into another session's attention).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from ..analysis.sanitizer import make_lock
from ..query.overload import AdmissionController, WatermarkShedPolicy

#: slot-occupancy arm watermarks for the default slot shed policy:
#: bronze sessions shed at 80 % occupancy, silver at 95 %; gold only
#: sheds on the hard no-free-slot boundary (arm > 1 never arms).
#: Hysteresis (disarm at half the arm point) rides the policy unchanged.
SLOT_ARM = {"gold": 2.0, "silver": 0.95, "bronze": 0.80}


def slot_admission_controller(retry_after_s: float = 0.25
                              ) -> AdmissionController:
    """The default slot-admission controller: the PR 7 watermark policy
    re-pointed at slot occupancy (depth = live sessions, capacity =
    slots).  Same hysteresis, same drain-mode shed-everything."""
    return AdmissionController(
        policy=WatermarkShedPolicy(arm=dict(SLOT_ARM),
                                   retry_after_s=retry_after_s))


def dense_pool_shape(cfg, slots: int) -> Tuple[int, int, int, int]:
    """THE shape of each dense pool array (K and V alike), and the only
    place it is written: ``(layers, slots + 1, max_seq, heads *
    head_dim)``.  Layer-major, so a decode step takes its layer with a
    static leading index (a slice, not a gather over every layer), and
    lane-dense, so a row is ``heads * head_dim`` wide (1024 at GPT-2
    medium's widths: whole 128-lane registers, where a minor dimension
    of ``head_dim`` = 64 made the compiler convert the whole pool to a
    padded layout and back around every gather).  Index ``slots`` of the
    second dimension is the scratch slot.  Where ``heads * head_dim`` is
    not a multiple of 128 the layout is as correct, only not
    lane-dense."""
    return (cfg.layers, int(slots) + 1, cfg.max_seq,
            cfg.heads * cfg.head_dim)


@dataclasses.dataclass
class Session:
    """One live token stream resident in the pool."""

    key: Any                    # (client_id, wire seq) — or a local id
    slot: int                   # cache slot id (stable for the life)
    pos: int = 0                # next cache write position
    #: a token the CALLER makes the next decode step consume in place of
    #: the one the chip sampled for this stream and kept (teacher
    #: forcing, a prompt decoded step by step); ``None``, as the element
    #: leaves it: the chip's own.  The step that consumes it clears it.
    next_token: Optional[int] = None
    in_flight: int = 0          # steps dispatched, tokens not yet read
    released: bool = False      # the pool took the slot back
    emitted: int = 0            # tokens answered so far
    max_new: int = 0            # granted continuation length
    stop_token: int = -1        # ends the stream when emitted (<0: none)
    truncated: bool = False     # granted < asked: end with a marker
    qos: str = "silver"
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    born_s: float = 0.0
    last_step_s: float = 0.0    # progress stamp (deadline eviction)
    order: int = 0              # admission order (stable round-robin)
    #: per-session lifecycle record (llm/tokenobs.SessionRecord) when
    #: the element's token-level observability is on; None when off —
    #: every hot-path hook gates on this single attribute test (the
    #: annotation_active() zero-cost discipline)
    obs: Any = None


class SlotPool:
    """Slot bookkeeping of a dense pool, whatever a slot holds: free
    list, live sessions by key, LRU order, occupancy, admission — under
    one small lock.  Slot index ``slots`` is the SCRATCH slot padding
    lanes write into, never handed to a session."""

    def __init__(self, slots: int,
                 admission: Optional[AdmissionController] = None,
                 clock=None) -> None:
        import time as _time

        if int(slots) < 1:
            raise ValueError(f"KVCachePool needs >= 1 slot (got {slots})")
        self.slots = int(slots)
        self.scratch = self.slots          # padding lanes' slot id
        self.admission = (admission if admission is not None
                          else slot_admission_controller())
        self._clock = clock if clock is not None else _time.monotonic
        self._free: List[int] = list(range(self.slots))
        self._live: Dict[Any, Session] = {}
        self._order = 0
        self._lock = make_lock("llm.pool")

    @property
    def live(self) -> int:
        with self._lock:
            return len(self._live)

    @property
    def occupancy(self) -> float:
        return self.live / self.slots

    def sessions(self) -> List[Session]:
        """Live sessions in admission order (the engine's stable
        round-robin basis)."""
        with self._lock:
            return sorted(self._live.values(), key=lambda s: s.order)

    def get(self, key) -> Optional[Session]:
        with self._lock:
            return self._live.get(key)

    # -- admission -------------------------------------------------------
    def admit(self, qos: str, no_slot_retry_s: float = 0.25,
              prompt=None, max_new: int = 0) -> Optional[float]:
        """Slot-admission decision BEFORE allocation: ``None`` admits
        (a free slot exists and the occupancy policy agrees), a float
        sheds with that retry-after hint.  Policy first (QoS-tiered
        occupancy watermarks + drain mode), the hard no-free-slot
        boundary second — its hint is ``no_slot_retry_s``, which the
        engine sizes from its live step-time EWMA (≈ when the
        soonest-finishing session should free a slot).  ``prompt`` /
        ``max_new`` are accepted for pool-interface parity (the paged
        pool admits on page commitment) and ignored here: a dense slot
        costs ``max_seq`` regardless of what the session uses."""
        return self.admit_verdict(qos, no_slot_retry_s, prompt,
                                  max_new)[0]

    def admit_verdict(self, qos: str, no_slot_retry_s: float = 0.25,
                      prompt=None, max_new: int = 0
                      ) -> Tuple[Optional[float], str]:
        """:meth:`admit` with the cause of a refusal: ``drain``,
        ``policy`` (the occupancy watermarks) or ``no_slot`` (the hard
        boundary); ``(None, "")`` admits."""
        with self._lock:
            depth = len(self._live)
            free = bool(self._free)
        verdict, why = self.admission.verdict(qos or "silver", depth,
                                              self.slots)
        if verdict is not None:
            return verdict, why
        if not free:
            return max(float(no_slot_retry_s), 0.01), "no_slot"
        return None, ""

    def acquire(self, key, qos: str = "silver",
                extra: Optional[Dict[str, Any]] = None,
                prompt=None, max_new: int = 0) -> Session:
        """Allocate a slot for ``key``.  Caller must have gotten a
        ``None`` from :meth:`admit`; raises when no slot is free (the
        admit/acquire pair runs on the single decode thread, so the
        check cannot go stale).  ``prompt`` / ``max_new`` are ignored
        (pool-interface parity with the paged pool)."""
        now = self._clock()
        with self._lock:
            if key in self._live:
                raise ValueError(f"session {key!r} already live")
            if not self._free:
                raise RuntimeError("no free cache slot")
            slot = self._free.pop()
            self._order += 1
            sess = Session(key=key, slot=slot, qos=qos or "silver",
                           extra=dict(extra or {}), born_s=now,
                           last_step_s=now, order=self._order)
            self._live[key] = sess
            return sess

    def release(self, key) -> Optional[Session]:
        """Return ``key``'s slot to the free list (EOS, stop token,
        disconnect, eviction).  Device memory is untouched — the next
        occupant's prefill overwrites it."""
        with self._lock:
            sess = self._live.pop(key, None)
            if sess is not None:
                self._free.append(sess.slot)
                sess.released = True
            return sess

    def touch(self, key) -> None:
        sess = self.get(key)
        if sess is not None:
            sess.last_step_s = self._clock()

    # -- eviction --------------------------------------------------------
    def lru_key(self):
        """Least-recently-progressed live session's key (None when
        empty) — the LRU eviction candidate."""
        with self._lock:
            if not self._live:
                return None
            return min(self._live.values(),
                       key=lambda s: s.last_step_s).key

    def aged_keys(self, max_age_s: float) -> List[Any]:
        """Sessions older (since admission) than ``max_age_s`` seconds —
        deadline-eviction candidates: a slot is a bounded LEASE, and a
        session that outlives its deadline (wedged egress, a client
        trickling an enormous continuation) is force-completed so the
        pool's turnover — and with it every retry-after hint the
        admission path hands out — stays honest."""
        if max_age_s <= 0:
            return []
        cutoff = self._clock() - max_age_s
        with self._lock:
            return [s.key for s in self._live.values()
                    if s.born_s < cutoff]


class KVCachePool(SlotPool):
    """Bounded slot pool + the pooled device arrays of one family.

    ``arrays`` is the family's ``init_state(cfg, slots)``: for the
    default ``streamformer_lm`` the :func:`dense_pool_shape` pair
    ``(k, v)`` — ``(layers, slots + 1, max_seq, heads * head_dim)`` in
    ``cfg.dtype``, ONE array each (``models/streamformer_lm
    .decode_step_pooled``'s operand), also reachable as ``pool.k`` /
    ``pool.v``; for another family whatever its sessions keep.  The
    decode engine passes the tuple, donated, to the family's functions
    and assigns what they return, from the single decode thread, so
    array access needs no lock.
    """

    def __init__(self, cfg, slots: int,
                 admission: Optional[AdmissionController] = None,
                 clock=None, family=None) -> None:
        super().__init__(slots, admission, clock)
        if family is None:
            from .family import get_family

            family = get_family()
        self.cfg = cfg
        self.family = family
        self.arrays: Tuple[Any, ...] = tuple(
            family.init_state(cfg, self.slots))

    # the streamformer_lm pair by name, to read (arrays[0], arrays[1])
    @property
    def k(self):
        return self.arrays[0]

    @property
    def v(self):
        return self.arrays[1]

    # -- sizing ----------------------------------------------------------
    def cache_bytes(self) -> int:
        """Device bytes the pooled state occupies — CONSTANT for the
        pool's life (the bounded-memory evidence the soak gates on)."""
        return sum(int(a.nbytes) for a in self.arrays)

    def bytes_by_kind(self) -> Dict[str, int]:
        """:meth:`cache_bytes` split by the family's kinds of state."""
        out: Dict[str, int] = {}
        for kind, a in zip(self.family.state_kinds, self.arrays):
            out[kind] = out.get(kind, 0) + int(a.nbytes)
        return out
