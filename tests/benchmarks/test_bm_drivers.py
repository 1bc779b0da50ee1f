"""A rehearsal of every serving path on the CPU, at toy sizes kept under
``toy/``: the whole command from the manifest to the last line, with only
the device check stood in for.  Counts and correctness; never a speed."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

TOY = os.path.join(ROOT, "tests", "benchmarks", "toy", "manifest.json")
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(monkeypatch, capsys, cell, seconds="1.5"):
    """The whole run but the harness's look for a chip."""
    monkeypatch.setattr(harness, "device_or_exit", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    assert harness.main(["--manifest", TOY, "--workload", cell,
                         "--seed", "3", "--seconds", seconds,
                         "--trace", "0"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[0]), json.loads(lines[-1])


def rehearse(monkeypatch, capsys, cell, seconds="1.5"):
    detail, last = drive(monkeypatch, capsys, cell, seconds)
    # and, last, what the check compared, where the driver names it
    assert CONTRACT_KEYS <= set(last) <= CONTRACT_KEYS | {"compared"}
    assert last["correct"] is True, detail["checks"]
    assert last["failed"] == 0 and last["attempted"] > 0
    assert detail["checks"]["compiles_in_window"] == 0
    assert last["metrics"]["setup_s"]["value"] > 0
    return detail, last


def test_token_driver_closed_loop(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_lm.closed")
    assert set(last["metrics"]) == {"tok_s", "setup_s"}
    assert last["metrics"]["tok_s"]["value"] > 0
    assert list(last)[-1] == "compared"
    assert last["compared"]["near_top_share"] == {"value": 1.0,
                                                  "limit": ">= 1.0"}
    assert {got["value"] for name, got in last["compared"].items()
            if got["limit"] == "== 0"} == {0}
    checks = detail["checks"]
    # float32 toy against the float32 reference: every sampled token is
    # the reference's own argmax
    assert checks["sampled"] == 2
    assert checks["exact"] == checks["tokens"] == 16
    # four clients, each cut at most once at the window's end
    assert checks["streams_cut"] == detail["outcomes"].get("cut", 0) <= 4
    assert set(detail["outcomes"]) <= {"done", "cut"}
    # beside memory_peak_bytes: the cache the traffic WROTE, priced by
    # the family's cost functions (2 layers x K, V x 4 heads x 8 wide x
    # 4 B a position; 12 slots and the padding slot of 64 positions)
    memory = detail["memory"]
    assert memory["kv_bytes_per_position"] == 512
    assert memory["kv_pool_bytes"] == 13 * 64 * 512
    assert 0 < memory["kv_written_bytes_peak"] <= memory["kv_pool_bytes"]
    assert memory["weights_plus_kv_written_bytes"] == (
        memory["weights_bytes"] + memory["kv_written_bytes_peak"])
    # beside tok_s: token frames over the window's length
    assert detail["tokens_over_window_per_s"] > 0


def test_token_driver_open_loop(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_lm.open", "2")
    assert set(last["metrics"]) == {"ttft_p95_ms", "setup_s"}
    # fixed_count: 6 requests/s x 2 s, all drained after the window
    assert last["attempted"] == 12
    assert detail["outcomes"] == {"done": 12}
    assert detail["generator_late_ms"]["median"] >= 0


def test_stream_local_driver(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_mnv2.local")
    assert set(last["metrics"]) == {"fps", "setup_s"}
    assert detail["checks"]["in_order"] and detail["checks"]["labels_ok"]


def test_stream_query_driver(monkeypatch, capsys):
    detail, last = rehearse(monkeypatch, capsys, "toy_mnv2.cams", "2")
    assert set(last["metrics"]) == {"frame_p95_ms", "setup_s"}
    checks = detail["checks"]
    assert checks["answered"] == checks["frames"] == last["attempted"]
    assert checks["checked"] == 2
    # 3 cameras x 4 frames/s x (0.5 s ramp + 2 s)
    assert last["attempted"] == pytest.approx(30, abs=3)


@pytest.mark.parametrize("cell", ["toy_lm.closed", "toy_lm.open"])
def test_a_token_altered_where_it_is_produced_reads_not_correct(
        monkeypatch, capsys, cell):
    """The timed path broken underneath a whole run: every token the
    engine hands to the element is the one after the chip's own.  The
    lengths, the vocabulary and the compile count all still pass; the
    comparison with the reference is what fails the run."""
    from nnstreamer_tpu.llm.engine import DecodeEngine

    collect = DecodeEngine.collect
    monkeypatch.setattr(
        DecodeEngine, "collect",
        lambda self: [(sess, (tok + 1) % 61) for sess, tok in collect(self)])
    detail, last = drive(monkeypatch, capsys, cell)
    assert last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 0
    share = last["compared"]["near_top_share"]
    assert share["limit"] == ">= 1.0" and share["value"] < 0.5
    assert {got["value"] for name, got in last["compared"].items()
            if got["limit"] == "== 0"} == {0}
