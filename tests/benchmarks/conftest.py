"""Shared by the benchmark's tests: the stream tier's cells, and the
trace the profiler overran (``overrun_trace``, at the end).

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
import re

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


OVERRUN = os.path.join(ROOT, "tests", "benchmarks", "fixtures",
                       "slice_overrun.xspace.textproto")


@pytest.fixture()
def xplane_file(tmp_path):
    """Writes an XSpace given in text form as a ``.xplane.pb`` file."""
    def write(text, name="vm.xplane.pb"):
        from jax.profiler import ProfileData

        path = tmp_path / name
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
        return str(path)
    return write


@pytest.fixture()
def overrun_trace(xplane_file):
    """``fixtures/slice_overrun`` as a file, with its ``bench.slice``
    span moved to ``[lo, hi)`` us and ``more`` planes appended."""
    def make(lo_us=100, hi_us=400, more="", name="vm.xplane.pb"):
        with open(OVERRUN, encoding="utf-8") as fh:
            text = fh.read()
        line = re.compile(
            r"offset_ps: \d+ duration_ps: \d+ \}  # bench.slice")
        assert len(line.findall(text)) == 1
        text = line.sub(f"offset_ps: {lo_us * 10**6} duration_ps: "
                        f"{(hi_us - lo_us) * 10**6} }}", text)
        return xplane_file(text + more, name)
    return make
