"""The traffic generator: everything from the seed, and the stated load."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import traffic  # noqa: E402
from benchmarks.manifest import Manifest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHAT = {
    "loop": "open",
    "arrivals": {"process": "poisson", "rate_per_s": 8.0,
                 "fixed_count": True},
    "prompt_len": {"dist": "lognormal", "median": 200, "sigma": 0.9,
                   "min": 16, "max": 760, "stratified": True},
    "max_new": {"dist": "lognormal", "median": 100, "sigma": 0.7,
                "min": 8, "max": 256, "stratified": True},
    "stop_token": -1}


def test_one_seed_one_schedule_another_seed_another():
    a = traffic.open_token_requests(CHAT, 5, 30.0)
    b = traffic.open_token_requests(CHAT, 5, 30.0)
    c = traffic.open_token_requests(CHAT, 6, 30.0)
    assert a == b
    assert [r["due"] for r in a] != [r["due"] for r in c]
    assert [r["prompt_len"] for r in a] != [r["prompt_len"] for r in c]


def test_fixed_count_offers_exactly_the_stated_rate():
    for seed in (1, 2, 3):
        reqs = traffic.open_token_requests(CHAT, seed, 30.0)
        assert len(reqs) == 240                      # 8 /s x 30 s
        dues = [r["due"] for r in reqs]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0


def test_plain_poisson_mean_rate_and_gaps():
    rng = traffic.rng_for(9, 0)
    offs = traffic.poisson_offsets(50.0, 400.0, rng)
    assert len(offs) == pytest.approx(20000, rel=0.03)
    gaps = np.diff(offs)
    # exponential gaps: standard deviation equals the mean
    assert gaps.mean() == pytest.approx(0.02, rel=0.03)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)


def test_constant_offsets_keep_the_phase():
    offs = traffic.constant_offsets(30.0, 1.0, phase=0.01)
    assert len(offs) == 30
    assert offs[0] == pytest.approx(0.01)
    assert np.diff(offs) == pytest.approx(np.full(29, 1 / 30))
    assert traffic.constant_offsets(2.0, 1.0) == [0.0, 0.5]


def test_arrivals_follow_the_process_the_mix_names():
    spec = {"process": "poisson", "rate_per_s": 16.0, "fixed_count": True}
    offs = traffic.arrival_offsets(spec, 10.0, traffic.rng_for(1, 0))
    assert len(offs) == 160 and offs == sorted(offs)
    spec = {"process": "constant", "rate_per_s": 4.0, "phase": 0.1}
    assert traffic.arrival_offsets(spec, 1.0, traffic.rng_for(1, 0)) == \
        pytest.approx([0.1, 0.35, 0.6, 0.85])
    with pytest.raises(ValueError):
        traffic.arrival_offsets({"process": "gamma", "rate_per_s": 1.0},
                                1.0, traffic.rng_for(1, 0))


def test_stratified_lengths_are_one_multiset_for_every_seed():
    a = traffic.draw_lengths(CHAT["prompt_len"], 240,
                             traffic.rng_for(1, 1))
    b = traffic.draw_lengths(CHAT["prompt_len"], 240,
                             traffic.rng_for(2, 1))
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 16 and max(a) <= 760
    assert np.median(a) == pytest.approx(200, abs=3)
    assert np.mean(a) > np.median(a)                 # the heavy tail


@pytest.mark.parametrize("spec, lo, hi", [
    ({"dist": "fixed", "value": 256}, 256, 256),
    ({"dist": "uniform_int", "min": 16, "max": 48}, 16, 48),
    ({"dist": "lognormal", "median": 100, "sigma": 0.7, "min": 8,
      "max": 256}, 8, 256)])
def test_length_distributions_stay_in_range(spec, lo, hi):
    got = traffic.draw_lengths(spec, 2000, traffic.rng_for(3, 2))
    assert len(got) == 2000 and min(got) >= lo and max(got) <= hi
    assert all(isinstance(v, int) for v in got)
    if spec["dist"] == "uniform_int":
        assert min(got) == lo and max(got) == hi     # both ends reached
    if spec["dist"] == "lognormal":
        assert max(got) == hi                        # the clipped tail


def test_unknown_length_distribution_is_refused():
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "zipf", "min": 1, "max": 2}, 3,
                             traffic.rng_for(1, 1))
    with pytest.raises(ValueError):
        traffic.draw_lengths({"dist": "uniform_int", "min": 9, "max": 2},
                             3, traffic.rng_for(1, 1))


def test_prompts_are_rebuilt_identically_from_the_request():
    req = traffic.open_token_requests(CHAT, 5, 10.0)[7]
    a = traffic.prompt_tokens(req, 50257)
    b = traffic.prompt_tokens(dict(req), 50257)
    assert a.dtype == np.int32 and len(a) == req["prompt_len"]
    assert (a == b).all() and 0 <= a.min() and a.max() < 50257


def test_shared_prefix_is_shared_inside_a_group_only():
    mix = dict(CHAT, sharing={"prefix_len": 12, "groups": 2})
    reqs = traffic.open_token_requests(mix, 5, 10.0)
    by_group = {}
    for r in reqs:
        by_group.setdefault(r["group"], []).append(
            traffic.prompt_tokens(r, 1000))
    assert set(by_group) == {0, 1}
    for prompts in by_group.values():
        assert all((p[:12] == prompts[0][:12]).all() for p in prompts)
        assert any((p[12:20] != prompts[0][12:20]).any()
                   for p in prompts[1:])
    assert (by_group[0][0][:12] != by_group[1][0][:12]).any()


def test_closed_loop_requests_are_derivable_on_either_side():
    mix = Manifest(os.path.join(ROOT, "BENCHMARK.json")).traffic(
        "decode_saturate")
    a = traffic.closed_token_request(mix, 4, client=3, k=2)
    assert a == traffic.closed_token_request(mix, 4, client=3, k=2)
    assert a["id"] == 3 + 2 * mix["clients"]
    assert a["id"] % mix["clients"] == 3 and a["id"] // mix["clients"] == 2
    assert 512 <= a["prompt_len"] <= 760 and a["max_new"] == 256
    assert a != traffic.closed_token_request(mix, 5, client=3, k=2)


def test_cameras_get_a_phase_inside_one_period_and_their_own_frames():
    mix = {"cameras": 40, "fps": 30.0}
    phases = traffic.camera_phases(mix, 8)
    assert phases == traffic.camera_phases(mix, 8)
    assert len(phases) == 40 and len(set(phases)) == 40
    assert 0 <= min(phases) and max(phases) < 1 / 30
    a = traffic.camera_frames(8, 0, 4, (224, 224, 3))
    assert a.shape == (4, 224, 224, 3) and a.dtype == np.uint8
    assert a.nbytes == 4 * 150528
    assert (a == traffic.camera_frames(8, 0, 4, (224, 224, 3))).all()
    assert (a != traffic.camera_frames(8, 1, 4, (224, 224, 3))).any()


@pytest.mark.parametrize("seed", [1, 3600600001, 2**31 + 7])
def test_the_latency_cell_offers_every_seed_the_same_work(seed):
    """``gpt2m.steady_short`` (PR 36): evenly paced arrivals at four
    fifths of the swept knee, the same arrival times and the same
    multiset of prompt lengths whatever the seed, which only orders
    them; what differs between two runs is the system, not the load."""
    m = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    mix = m.traffic("steady_short")
    rate, knee = mix["arrivals"]["rate_per_s"], mix["knee"]["rate_per_s"]
    assert mix["arrivals"]["process"] == "constant"
    assert rate == pytest.approx(0.8 * knee)
    seconds = float(m.doc["run_seconds"])
    base = traffic.open_token_requests(mix, 0, seconds)
    got = traffic.open_token_requests(mix, seed, seconds)
    assert len(got) == len(base) == round(rate * seconds)
    assert [r["due"] for r in got] == [r["due"] for r in base]
    assert np.diff([r["due"] for r in got]) == pytest.approx(
        np.full(len(got) - 1, 1.0 / rate))
    assert sorted(r["prompt_len"] for r in got) == sorted(
        r["prompt_len"] for r in base)
    assert [r["prompt_len"] for r in got] != [r["prompt_len"]
                                              for r in base]
    # the p95 rests on hundreds of requests
    assert len(got) // 20 >= 100
