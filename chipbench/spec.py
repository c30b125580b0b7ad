"""Find a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
files are ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``limits/<workload>.json`` beside this file.  A configuration names its
model family, found as ``families/<family>.py`` (the contract is in
``families/__init__.py``); a traffic mix names the program entry it
drives, found as ``entries/<entry>.py``; each per-layer metric is read
by ``metrics/<metric>.py``.  A new configuration is new ``configs/``,
``families/``, ``traffic/`` and ``limits/`` files and new
``BENCHMARK.json`` entries; a new cell, entry or metric likewise: never
an edit of a file that is there.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FAMILIES = os.path.join(HERE, "families")


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    per_layer: List[Dict[str, Any]]     # BENCHMARK.json entries for it
    end_to_end: List[Dict[str, Any]]

    @property
    def family(self):
        """The configuration's model family module."""
        return family(self.config.get("family"))


def load_cell(workload: str, bench: Dict[str, Any] = None) -> Cell:
    bench = benchmark() if bench is None else bench
    hits = [w for w in bench["workloads"] if w["name"] == workload]
    if not hits:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json — "
                       f"have {[w['name'] for w in bench['workloads']]}")
    w = hits[0]
    config = _load_json(os.path.join(HERE, "configs", w["config"] + ".json"))
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      w["traffic"] + ".json"))
    limits = _load_json(os.path.join(HERE, "limits", workload + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, int(w["chips"]), config, traffic,
                limits["limits"],
                [m for m in bench["per_layer"] if mine(m)],
                [m for m in bench["end_to_end"] if mine(m)])


@functools.lru_cache(maxsize=None)
def _module(directory: str, name: str):
    """The module ``<directory>/<name>.py``, loaded once."""
    path = os.path.join(directory, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {name}.py in {directory}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + os.path.basename(directory) + "_"
        + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    # registered first, as an import does, so that the module can define
    # dataclasses and name itself
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Any], Any]:
    """``read(ctx)`` of ``metrics/<name>.py``."""
    return _module(os.path.join(HERE, "metrics"), name).read


def entry_driver(name: str) -> Callable[..., Any]:
    """``make(config, traffic, seed)`` of ``entries/<name>.py``: the
    driver of one program entry (``drivers.py`` says what it does)."""
    return _module(os.path.join(HERE, "entries"), name).make


def family(name: Optional[str]):
    """The model family ``families/<name>.py``.  A configuration with no
    family, or an unknown one, is an error, never a default."""
    known = sorted(f[:-3] for f in os.listdir(FAMILIES)
                   if f.endswith(".py") and not f.startswith("_"))
    if name not in known:
        raise KeyError(f"no model family {name!r} in {FAMILIES}: a "
                       f"configuration's \"family\" is one of {known}")
    return _module(FAMILIES, name)


def peaks(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = _load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json — known: {sorted(table)}")
    return table[device_kind]
