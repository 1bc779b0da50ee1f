"""``BENCHMARK.json``: loading, the contract's checks, and the lookup
from a cell's name to the files that define it.

The manifest is the only list there is.  A cell names a configuration
and a traffic mix; a configuration names its file and, inside it, the
driver of its serving path; a metric names its reader.  Files are looked
up under every directory of ``paths``, in order, so a directory added by
a later PR (or a test's toy directory) extends the benchmark without an
edit here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

KEYS = ("command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
_NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
#: a layer's name, as ``PERF.md`` section 3 spells it: no spaces
_LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: what ``reduced`` may never name: a width
_WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$"
                    r"|head_size|head_dim|expansion|experts_per_tok"
                    r"|^dim$|^mlp$|^width$)")
#: ... unless it counts layers: ``num_hidden_layers`` is a depth
_DEPTH = re.compile(r"(^|_)layers$")


class ManifestError(ValueError):
    """The manifest breaks its contract."""


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _inside(path: str, roots: List[str]) -> bool:
    return any(path == r or path.startswith(r.rstrip("/") + "/")
               for r in roots)


def validate(doc: Dict[str, Any]) -> None:
    """Raise :class:`ManifestError` on the first breach of the
    benchmark's contract that can be seen without running anything."""
    _need(tuple(sorted(doc)) == tuple(sorted(KEYS)),
          f"keys must be exactly {KEYS}, got {tuple(doc)}")
    paths, command = doc["paths"], doc["command"]
    _need(isinstance(paths, list) and 1 <= len(paths) <= 16,
          "paths: 1 to 16 directories")
    for p in paths:
        _need(bool(_PATH.match(p)) and not p.startswith("/")
              and ".." not in p.split("/"), f"path {p!r} is not plain")
    _need(isinstance(command, list) and 1 <= len(command) <= 32
          and all(isinstance(c, str) for c in command),
          "command: a list of 1 to 32 strings")
    for arg in command[1:]:
        _need(not arg.startswith("/") and ".." not in arg.split("/"),
              f"command argument {arg!r} leaves the repo")
        if "/" in arg or os.path.splitext(arg)[1]:
            _need(_inside(arg, paths),
                  f"command names {arg!r}, outside paths")
    rs = doc["run_seconds"]
    _need(isinstance(rs, int) and not isinstance(rs, bool)
          and 1 <= rs <= 51, "run_seconds: a whole number from 1 to 51")

    names: set = set()

    def name(n: Any, what: str) -> str:
        _need(isinstance(n, str) and bool(_NAME.match(n)),
              f"{what} name {n!r} is not plain")
        _need(n not in names, f"name {n!r} is used twice")
        names.add(n)
        return n

    configs, files = {}, set()
    _need(1 <= len(doc["configs"]) <= 24, "configs: 1 to 24")
    for c in doc["configs"]:
        _need(set(c) == {"name", "source", "file", "reduced", "why"},
              f"config keys: {sorted(c)}")
        configs[name(c["name"], "config")] = c
        _need(_inside(c["file"], paths) and bool(_PATH.match(c["file"])),
              f"config file {c['file']!r} is not under paths")
        _need(c["file"] not in files, f"{c['file']!r} serves two configs")
        files.add(c["file"])
        _need(isinstance(c["reduced"], list), "reduced: a list")
        for key in c["reduced"]:
            _need(bool(_DEPTH.search(key)) or not _WIDTH.search(key),
                  f"reduced names a width: {key!r}")
        _need(len(c["why"]) <= 200, "why: at most 200 characters")

    cells, pairs = {}, set()
    _need(2 <= len(doc["workloads"]) <= 24, "workloads: 2 to 24 cells")
    for w in doc["workloads"]:
        _need(set(w) == {"name", "config", "traffic", "chips", "why"},
              f"workload keys: {sorted(w)}")
        cells[name(w["name"], "workload")] = w
        _need(w["config"] in configs,
              f"cell {w['name']!r}: unknown config {w['config']!r}")
        _need(bool(_NAME.match(w["traffic"])),
              f"traffic name {w['traffic']!r} is not plain")
        _need(w["chips"] in (1, 4), "chips: 1 or 4")
        _need((w["config"], w["traffic"]) not in pairs,
              f"pair {(w['config'], w['traffic'])} appears twice")
        pairs.add((w["config"], w["traffic"]))
        _need(len(w["why"]) <= 200, "why: at most 200 characters")
    used = {w["config"] for w in cells.values()}
    _need(used == set(configs),
          f"configs no cell uses: {sorted(set(configs) - used)}")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    _need(four <= max(1, len(cells) // 4),
          f"{four} cells ask for 4 chips: at most a quarter may")

    def metric(m: Dict[str, Any], extra: set) -> List[str]:
        required = {"name", "unit", "better", "source"} | extra
        _need(required <= set(m) <= required | {"workloads"},
              f"metric keys: {sorted(m)}")
        name(m["name"], "metric")
        _need(m["better"] in ("lower", "higher"), "better: lower|higher")
        _need(m["source"] in SOURCES, f"source {m['source']!r}")
        where = m.get("workloads", list(cells))
        for cell in where:
            _need(cell in cells, f"metric {m['name']!r}: unknown cell "
                                 f"{cell!r}")
        return where

    e2e: Dict[str, List[str]] = {}
    _need(1 <= len(doc["end_to_end"]) <= 16, "end_to_end: 1 to 16")
    for m in doc["end_to_end"]:
        e2e[m["name"]] = metric(m, {"bound"})
        _need(m["source"] in E2E_SOURCES,
              f"end-to-end source {m['source']!r}")
        _need(isinstance(m["bound"], float) and 0.01 <= m["bound"] <= 0.1,
              f"bound of {m['name']!r} outside 0.01..0.1")
    _need("setup_s" in e2e and set(e2e["setup_s"]) == set(cells),
          "setup_s must be an end-to-end metric of every cell")
    layer: Dict[str, List[str]] = {}
    _need(1 <= len(doc["per_layer"]) <= 128, "per_layer: 1 to 128")
    for m in doc["per_layer"]:
        layer[m["name"]] = where = metric(m, {"layer", "moves"})
        _need(isinstance(m["layer"], str)
              and bool(_LAYER.match(m["layer"])),
              f"{m['name']!r}: layer {m['layer']!r} is not plain")
        _need(m["moves"] in e2e, f"{m['name']!r} moves unknown "
                                 f"{m['moves']!r}")
        _need(set(where) <= set(e2e[m["moves"]]),
              f"{m['name']!r} is reported where {m['moves']!r} is not")
        if m["name"].endswith("_roofline"):
            _need(m["unit"] == "%", "a roofline share has the unit %")
    for cell in cells:
        others = [n for n, where in e2e.items()
                  if cell in where and n != "setup_s"]
        _need(bool(others), f"cell {cell!r} has no end-to-end metric "
                            "besides setup_s")
        _need(any(cell in where for where in layer.values()),
              f"cell {cell!r} has no per-layer metric")


class Manifest:
    """A loaded manifest, rooted at the checkout that holds it."""

    def __init__(self, path: str, root: Optional[str] = None) -> None:
        self.path = os.path.abspath(path)
        self.root = os.path.abspath(root) if root else os.path.dirname(
            self.path)
        with open(self.path, encoding="utf-8") as fh:
            self.doc = json.load(fh)
        validate(self.doc)

    # -- lookup ---------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.doc["workloads"]]
        raise ManifestError(f"no cell {name!r} in {self.path} "
                            f"(cells: {known})")

    def config(self, name: str) -> Dict[str, Any]:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        with open(os.path.join(self.root, entry["file"]),
                  encoding="utf-8") as fh:
            cfg = json.load(fh)
        cfg["name"] = name
        return cfg

    def find(self, *relative: str) -> str:
        """The first ``<path>/<relative>`` that exists, over ``paths``
        in order."""
        for base in self.doc["paths"]:
            candidate = os.path.join(self.root, base, *relative)
            if os.path.exists(candidate):
                return candidate
        raise ManifestError(f"{os.path.join(*relative)} is in none of "
                            f"{self.doc['paths']}")

    def traffic(self, name: str) -> Dict[str, Any]:
        """Parameters of one traffic mix.  The generator reads JSON; the
        other suffixes the contract allows are for recorded inputs a
        later mix may replay."""
        with open(self.find("traffic", name + ".json"),
                  encoding="utf-8") as fh:
            mix = json.load(fh)
        mix["name"] = name
        return mix

    def module(self, kind: str, name: str):
        """Import ``<path>/<kind>/<name>.py`` by file, under a module
        name of its own."""
        path = self.find(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"_benchmark_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, cell: str, kind: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries ``cell`` reports."""
        return [m for m in self.doc[kind]
                if cell in m.get("workloads", [cell])]
