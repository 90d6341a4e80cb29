"""Finds what a run needs by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds a file and an entry and edits nothing that is there.

    cell            -> an entry of ``workloads``
    configuration   -> the JSON file its ``configs`` entry names
    traffic mix     -> ``benchmark/traffic/<traffic>.json``
    system          -> ``benchmark/systems/<config["system"]>.py``
    reference       -> the file the configuration names under ``reference``
    per-layer metric-> ``benchmark/layer_metrics/<name>.py``, ``read(ctx)``
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


def _read_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries: List[Dict[str, Any]], name: str,
           what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"BENCHMARK.json has no {what} named {name!r}; it has "
        f"{[e['name'] for e in entries]}")


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _entry(bench["workloads"], name, "workload")


def load_config(bench: Dict[str, Any], cell: Dict[str, Any],
                root: str = ROOT) -> Dict[str, Any]:
    entry = _entry(bench["configs"], cell["config"], "configuration")
    cfg = _read_json(os.path.join(root, entry["file"]))
    cfg["name"] = entry["name"]
    return cfg


def load_traffic(cell: Dict[str, Any], root: str = ROOT) -> Dict[str, Any]:
    path = os.path.join(root, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    if not os.path.exists(path):
        raise BenchmarkError(f"traffic mix {cell['traffic']!r}: no {path}")
    return _read_json(path)


def load_module(path: str) -> ModuleType:
    """Import one file by path. Names with dots and dashes are fine."""
    if not os.path.exists(path):
        raise BenchmarkError(f"no such file: {path}")
    modname = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def load_system(config: Dict[str, Any], root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, "benchmark", "systems",
                                    config["system"] + ".py"))


def load_reference(config: Dict[str, Any], root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, config["reference"]))


def load_metric_reader(name: str, root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, "benchmark", "layer_metrics",
                                    name + ".py"))


def metrics_of_cell(bench: Dict[str, Any], group: str,
                    cell_name: str) -> List[Dict[str, Any]]:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports:
    those without a ``workloads`` key and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]
