"""The benchmark's description, read from its data files.

``BENCHMARK.json`` at the root of the checkout names every cell
(``workloads``), configuration and metric. Everything that belongs to one
of them lives in a file of its own, found by name:

- ``bench/cells/<cell>.json``      the cell's load: rate or client count,
  front-end queue depth, and any serving size it overrides;
- ``bench/traffic/<mix>.json``     the traffic mix's parameters, read by
  the one generator in ``traffic.py``;
- the configuration's ``file``     sizes as run, source, the serving
  shape (``max_slots``, ``max_seq``) and the plain reference module;
- ``bench/metrics/<metric>.py``    each per-layer metric's reader.

Adding a cell, a mix, a configuration or a per-layer metric adds files
and ``BENCHMARK.json`` entries; no file here changes. Imports nothing but
the standard library.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

#: what BENCHMARK.json allows in a name and in a unit
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, mix and load
    resolved from their files."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    load: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def serving(self) -> Dict[str, Any]:
        """The configuration's serving shape with the cell's overrides."""
        out = dict(self.config["serving"])
        out.update(self.load.get("serving", {}))
        return out


def read_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return read_json(root / "BENCHMARK.json")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Optional[Dict[str, Any]] = None,
            root: Path = ROOT) -> Cell:
    """The cell called ``name`` with every file it names read in."""
    bench = bench if bench is not None else benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = read_json(root / cfg_entry["file"])
    traffic = read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    load = read_json(BENCH_DIR / "cells" / f"{name}.json")
    if load.get("config") != w["config"] or load.get("traffic") != w["traffic"]:
        raise ValueError(f"bench/cells/{name}.json names "
                         f"{load.get('config')}/{load.get('traffic')}, "
                         f"BENCHMARK.json {w['config']}/{w['traffic']}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, load=load,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file by path (metric readers carry dots in their names,
    so they are not importable as packages)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


def reference_module(config: Dict[str, Any]) -> ModuleType:
    """The configuration's plain reference, by the path its file names."""
    return load_module(ROOT / config["reference"],
                       "bench_reference_" + config["name"].replace(
                           ".", "_").replace("-", "_"))
