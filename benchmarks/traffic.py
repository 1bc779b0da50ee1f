"""The one general traffic generator: every mix under ``traffic/`` is a
file of parameters that this module turns into arrivals, lengths, token
prompts and camera frames — all from ``--seed``, nothing else.

A later PR adds a mix by adding a data file.  What a file may say:

``loop``       ``open`` (requests sent on a schedule whatever the system
               does) or ``closed`` (each client sends its next request
               when the last one ended)
``arrivals``   open loop: ``process`` ``poisson`` | ``constant``,
               ``rate_per_s``, ``fixed_count`` (Poisson conditioned on
               its expected count, so every seed offers the same amount
               of work)
``prompt_len`` / ``max_new``
               a length distribution: ``fixed`` (``value``),
               ``uniform_int`` (``min``, ``max``) or ``lognormal``
               (``median``, ``sigma``, clipped to ``min``..``max``).
               ``stratified`` (either of the last two) draws the same
               quantile grid for every seed and lets the seed permute
               it; in a closed loop the grid spans the clients, and
               each round of requests, one a client, draws it again
``sharing``    ``prefix_len`` tokens shared by the requests of one of
               ``groups`` groups (absent = nothing shared)
``cameras`` / ``fps``
               stream mixes: that many constant-rate sources, each with
               a seeded phase offset

Schedules follow ``slo/loadgen.py`` (seeded exponential inter-arrivals;
constant rate with a phase per client), copied so the yardstick does not
move with the program.  Imports numpy only: the load-generator children
use this module and must never touch JAX.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List, Sequence

import numpy as np

#: sub-streams of one seed, so arrivals, lengths and tokens stay
#: independent of each other and of how many of each are drawn
_ARRIVALS, _PROMPT_LEN, _MAX_NEW, _TOKENS, _PREFIX, _GROUP, _PHASE, \
    _FRAMES, _ROUND = range(9)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, sub-stream...)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  *(int(s) for s in stream)])


# -- arrivals -----------------------------------------------------------------

def poisson_offsets(rate_per_s: float, seconds: float,
                    rng: np.random.Generator,
                    fixed_count: bool = False) -> List[float]:
    """Arrival offsets in ``[0, seconds)`` of a Poisson process.  With
    ``fixed_count`` the process is conditioned on its expected count
    (arrivals are then sorted uniforms), so the amount of work offered
    does not vary with the seed."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError("rate_per_s and seconds must be positive")
    if fixed_count:
        n = max(1, int(round(rate_per_s * seconds)))
        return sorted(float(t) for t in rng.uniform(0.0, seconds, n))
    out: List[float] = []
    t = float(rng.exponential(1.0 / rate_per_s))
    while t < seconds:
        out.append(t)
        t += float(rng.exponential(1.0 / rate_per_s))
    return out


def constant_offsets(rate_per_s: float, seconds: float,
                     phase: float = 0.0) -> List[float]:
    """One arrival every ``1/rate_per_s`` seconds, shifted by ``phase``
    so many sources interleave instead of arriving together."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError("rate_per_s and seconds must be positive")
    period = 1.0 / rate_per_s
    n = int(np.ceil((seconds - phase) / period))
    return [phase + i * period for i in range(max(0, n))
            if phase + i * period < seconds]


def arrival_offsets(spec: Dict[str, Any], seconds: float,
                    rng: np.random.Generator) -> List[float]:
    """Offsets of every request of an open-loop mix."""
    rate = float(spec["rate_per_s"])
    process = spec.get("process", "poisson")
    if process == "poisson":
        return poisson_offsets(rate, seconds, rng,
                               bool(spec.get("fixed_count", False)))
    if process == "constant":
        return constant_offsets(rate, seconds,
                                float(spec.get("phase", 0.0)))
    raise ValueError(f"arrival process {process!r} "
                     "(want poisson | constant)")


# -- lengths ------------------------------------------------------------------

def draw_lengths(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> List[int]:
    """``n`` integer lengths from a length distribution."""
    dist = spec.get("dist")
    if dist == "fixed":
        return [int(spec["value"])] * n
    lo, hi = int(spec["min"]), int(spec["max"])
    if lo > hi:
        raise ValueError(f"length range {lo}..{hi} is empty")
    if dist == "uniform_int":
        if spec.get("stratified", False):
            # the midpoints of n equal shares of lo..hi, in seeded order
            grid = lo + np.floor((np.arange(n) + 0.5) * (hi - lo + 1) / n)
            return [int(v) for v in rng.permutation(grid)]
        return [int(v) for v in rng.integers(lo, hi + 1, n)]
    if dist == "lognormal":
        median, sigma = float(spec["median"]), float(spec["sigma"])
        if spec.get("stratified", False):
            # the same quantile grid whatever the seed; the seed only
            # orders it — the tail of every run is the same tail
            grid = NormalDist()
            z = np.array([grid.inv_cdf((i + 0.5) / n) for i in range(n)])
            z = rng.permutation(z)
        else:
            z = rng.standard_normal(n)
        raw = median * np.exp(sigma * z)
        return [int(v) for v in np.clip(np.rint(raw), lo, hi)]
    raise ValueError(f"length distribution {dist!r} "
                     "(want fixed | uniform_int | lognormal)")


# -- token requests -----------------------------------------------------------

def _token_request(traffic: Dict[str, Any], seed: int, rid: int,
                   prompt_len: int, max_new: int) -> Dict[str, Any]:
    req = {"id": rid, "prompt_len": prompt_len, "max_new": max_new,
           "stop_token": int(traffic.get("stop_token", -1)),
           "seed": int(seed), "prefix_len": 0, "group": 0}
    sharing = traffic.get("sharing")
    if sharing:
        req["prefix_len"] = min(int(sharing["prefix_len"]), prompt_len - 1)
        req["group"] = int(rng_for(seed, _GROUP, rid).integers(
            0, int(sharing.get("groups", 1))))
    return req


def open_token_requests(traffic: Dict[str, Any], seed: int,
                        seconds: float) -> List[Dict[str, Any]]:
    """Every request of an open-loop token mix, in due order: ``id``,
    ``due`` (offset from the window's start), lengths, and what
    :func:`prompt_tokens` needs to rebuild the prompt anywhere."""
    dues = arrival_offsets(traffic["arrivals"], seconds,
                           rng_for(seed, _ARRIVALS))
    n = len(dues)
    plens = draw_lengths(traffic["prompt_len"], n,
                         rng_for(seed, _PROMPT_LEN))
    news = draw_lengths(traffic["max_new"], n, rng_for(seed, _MAX_NEW))
    out = []
    for rid, (due, plen, new) in enumerate(zip(dues, plens, news)):
        req = _token_request(traffic, seed, rid, plen, new)
        req["due"] = due
        out.append(req)
    return out


def closed_token_request(traffic: Dict[str, Any], seed: int, client: int,
                         k: int) -> Dict[str, Any]:
    """The ``k``-th request of closed-loop ``client``.  Request ids are
    ``client + k * clients``: unique, and derivable on either side."""
    clients = int(traffic["clients"])
    rid = client + k * clients

    def length(spec: Dict[str, Any], stream: int) -> int:
        if spec.get("stratified", False):
            # round k's grid over the clients, the same for every seed
            return draw_lengths(spec, clients,
                                rng_for(seed, _ROUND, stream, k))[client]
        return draw_lengths(spec, 1, rng_for(seed, stream, rid))[0]

    return _token_request(traffic, seed, rid,
                          length(traffic["prompt_len"], _PROMPT_LEN),
                          length(traffic["max_new"], _MAX_NEW))


def prompt_tokens(req: Dict[str, Any], vocab: int) -> np.ndarray:
    """The prompt of ``req``: ``prefix_len`` tokens shared by its group,
    then tokens of its own — the same array in the generator's child
    process and in the correctness check."""
    seed, plen, shared = req["seed"], req["prompt_len"], req["prefix_len"]
    own = rng_for(seed, _TOKENS, req["id"]).integers(
        0, vocab, plen - shared)
    if not shared:
        return own.astype(np.int32)
    head = rng_for(seed, _PREFIX, req["group"]).integers(0, vocab, shared)
    return np.concatenate([head, own]).astype(np.int32)


# -- camera frames ------------------------------------------------------------

def camera_phases(traffic: Dict[str, Any], seed: int) -> List[float]:
    """Seeded phase offset of each camera inside one frame period."""
    period = 1.0 / float(traffic["fps"])
    return [float(p) for p in rng_for(seed, _PHASE).uniform(
        0.0, period, int(traffic["cameras"]))]


def camera_frames(seed: int, camera: int, count: int,
                  shape: Sequence[int]) -> np.ndarray:
    """The ``count`` distinct uint8 frames camera ``camera`` cycles
    through (frame *k* of the camera is ``[k % count]``)."""
    return rng_for(seed, _FRAMES, camera).integers(
        0, 256, (count, *shape), dtype=np.uint8)
