"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; they are read from
``configs/<name>.json`` and ``traffic/<name>.json`` beside this file.
A per-layer metric ``<name>`` is read by ``metrics/<name>.py``, a module
with a ``read(obs)`` function.  Adding a cell, a mix or a metric is
adding files and entries: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def benchmark(path: pathlib.Path = BENCHMARK) -> Dict:
    return json.loads(path.read_text())


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, base: pathlib.Path = HERE) -> Dict:
    return json.loads((base / "configs" / f"{name}.json").read_text())


def traffic(name: str, base: pathlib.Path = HERE) -> Dict:
    return json.loads((base / "traffic" / f"{name}.json").read_text())


def metrics_of(bench: Dict, cell_name: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    whose ``workloads`` list names it, or that have no such list."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, base: pathlib.Path = HERE) -> Callable:
    """The ``read`` function of ``metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
