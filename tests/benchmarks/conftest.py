"""Shared by the benchmark's tests: the stream tier's cells.

``stream_cells.json`` lists the two MobileNetV2 cells in the manifest's
shape WITHOUT bounds: they are not in ``BENCHMARK.json`` (the
configuration holds 1 % of the chip, under the driver's floor), so no
bound was measured for them and only ``BENCHMARK.json`` makes claims.
Their configuration, mixes, drivers, readers, cost and reference stay
tested through this fixture, which fills in the validator's cap as a
stand-in bound.  A ``benchmark`` PR that brings the cells in copies the
entries to ``BENCHMARK.json`` with bounds from its own runs.
"""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STREAM_CELLS = os.path.join(ROOT, "tests", "benchmarks",
                            "stream_cells.json")


@pytest.fixture(scope="session")
def stream_manifest(tmp_path_factory):
    from benchmarks.manifest import Manifest

    with open(STREAM_CELLS, encoding="utf-8") as fh:
        doc = json.load(fh)
    for metric in doc["end_to_end"]:
        assert "bound" not in metric
        metric["bound"] = 0.1
    path = tmp_path_factory.mktemp("stream_cells") / "manifest.json"
    path.write_text(json.dumps(doc))
    return Manifest(str(path), root=ROOT)
