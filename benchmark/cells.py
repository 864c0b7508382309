"""Find a cell and everything that belongs to it by name.

``BENCHMARK.json`` at the root of the checkout is the one list of cells
(four of them), configurations and metrics. A cell names a configuration (a
JSON file of sizes), a traffic mix (``benchmark/traffic/<traffic>.json``) and
its chips; the traffic file names its feed (``benchmark/feeds/<feed>.py``)
and the configuration's file its system under test
(``benchmark/systems/<system>.py``: how the model, its state and its seeded
batches are built, and what unit the step counts). Per-layer metrics are
``benchmark/layer_metrics/<metric>.py``; a configuration's plain reference
and analytic FLOPs are ``benchmark/reference/<config>.py`` and
``benchmark/flops/<config>.py``. A later PR adds files and entries; nothing
here branches on a name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchmarkError(Exception):
    """The benchmark cannot run as asked (no such cell, no chip, a file of
    the cell missing). ``run.py`` turns it into a non-zero exit with no
    result line."""


def _read_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json reported by this cell
    per_layer: tuple
    root: str


def _applies(metric: dict, workload: str) -> bool:
    only = metric.get("workloads")
    return only is None or workload in only


def load_benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(
        (c for c in bench["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise BenchmarkError(
            f"workload {workload!r} names configuration {entry['config']!r}, "
            "which BENCHMARK.json does not list"
        )
    traffic_path = os.path.join(
        root, "benchmark", "traffic", entry["traffic"] + ".json"
    )
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic_name=entry["traffic"],
        traffic=_read_json(traffic_path),
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, workload)),
        root=root,
    )


def load_module(kind: str, name: str, root: str = ROOT):
    """The Python file ``benchmark/<kind>/<name>.py`` of the checkout at
    ``root``, imported under a name of its own (so two checkouts in one
    process never share a module)."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {kind} file for {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_system(cell: Cell):
    """The system under test that the cell's configuration names:
    ``benchmark/systems/<system>.py``."""
    name = cell.config.get("system")
    if not isinstance(name, str):
        raise BenchmarkError(f"configuration {cell.config_name!r} names no \"system\"")
    return load_module("systems", name, cell.root)


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    table = _read_json(os.path.join(root, "benchmark", "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(has: {sorted(table)}): a device with no published peak is an "
            "error, not a default"
        )
    return table[device_kind]
