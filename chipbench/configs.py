"""Find configurations, traffic mixes, drivers and metric readers by name.

A later change adds a file and a ``BENCHMARK.json`` entry; nothing here
lists them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _load_json(kind: str, name: str) -> Dict[str, Any]:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def config(name: str) -> Dict[str, Any]:
    """A model configuration: ``configs/<name>.json``."""
    return _load_json("configs", name)


def workload(name: str) -> Dict[str, Any]:
    """A cell's traffic mix: ``workloads/<name>.json``."""
    return _load_json("workloads", name)


def _module(kind: str, name: str) -> ModuleType:
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str) -> ModuleType:
    """The driver of a kind of traffic: ``drivers/<kind>.py``."""
    return _module("drivers", kind)


def reader(metric: str) -> ModuleType:
    """A per-layer metric's reader: ``metrics/<metric>.py``."""
    return _module("metrics", metric)


def reference(name: str) -> ModuleType:
    """A configuration's plain reference: ``references/<name>.py``."""
    return _module("references", name)


def benchmark() -> Dict[str, Any]:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bench: Dict[str, Any], cell: str, section: str):
    """The entries of ``end_to_end`` or ``per_layer`` that list the cell
    (an end-to-end entry without a list is reported by every cell)."""
    if section == "end_to_end":
        return [m for m in bench[section]
                if cell in m.get("workloads", [cell])]
    return [m for m in bench[section] if cell in m["workloads"]]
