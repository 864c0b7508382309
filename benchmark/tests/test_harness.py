"""The harness end to end at a tiny size on the CPU, through the test-only
stand-in for the device check (``tiny.fake_devices``); and ``run.py`` as it
ships, which refuses a CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import cells, run, trace_reduce
from benchmark.tests import tiny

WORKLOADS = [w["name"] for w in cells.load_benchmark()["workloads"]]
RECORDED = os.path.join(os.path.dirname(__file__), "data", "recorded.trace.json.gz")


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.fake_devices(monkeypatch, run)
    # in-process runs keep their programs out of the checkout's cache
    from tpuddp.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    return tiny.make_root(tmp_path)


def _check_line(result, cell, traced):
    json.dumps(result)  # the line is JSON
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == (want | {"breakdown"} if traced else want)
    assert isinstance(result["correct"], bool)
    assert result["attempted"] > 0 and result["failed"] == 0
    names = [m["name"] for m in (cell.per_layer if traced else cell.end_to_end)]
    units = {m["name"]: m["unit"] for m in (cell.per_layer if traced else cell.end_to_end)}
    assert set(result["metrics"]) <= set(names) and result["metrics"]
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["unit"] == units[name]
        assert isinstance(metric["value"], float)
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["device"]) == (device | {"busy_s", "window_s"} if traced else device)
    assert result["device"]["count"] == cell.chips


@pytest.mark.parametrize("workload", WORKLOADS)
def test_last_line_of_every_cell(root, workload):
    """Each cell: exactly the contract's keys, every end-to-end metric, none
    of them zero."""
    cell = cells.load_cell(workload, root)
    result = run.run_cell(workload, seed=0, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in result["metrics"].values())


WAITING = {
    # cells whose files are here but which BENCHMARK.json does not list yet
    # (PERF.md section 7): name -> (traffic, chips, readers it alone reports)
    "alexnet_b2048_loader": ("b2048_loader", 1, ("input_wait_ms_per_step",)),
    "alexnet_b2048_dp4": ("b2048_dp4", 4, ("collective_ms_per_step", "grad_wire_mb_per_step")),
}


def _list_waiting_cell(root, name):
    """What the PR that measures a waiting cell adds: entries, no code."""
    traffic, chips, readers = WAITING[name]
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({
        "name": name, "config": "alexnet_cifar224", "traffic": traffic,
        "chips": chips, "why": "a test",
    })
    for metric in readers:
        reader = cells.load_module("layer_metrics", metric, root)
        bench["per_layer"].append({
            "name": metric, "unit": reader.UNIT, "better": "lower", "source": reader.SOURCE,
            "layer": reader.LAYER, "moves": reader.MOVES, "workloads": [name],
        })
    json.dump(bench, open(path, "w"))
    return cells.load_cell(name, root)


def test_the_loader_fed_cell(root):
    """Pass after pass through the real loader and ``run_pass``: two fused
    chunks and two single steps a pass at this size, the last batch padded."""
    cell = _list_waiting_cell(root, "alexnet_b2048_loader")
    result = run.run_cell(cell.name, seed=0, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert result["attempted"] > 0


def test_traced_line(root, monkeypatch):
    """A traced run reports per-layer metrics, the device's busy time and a
    breakdown. A CPU capture has no device plane, so the reduction is handed
    the recorded chip capture; everything round it is the real path."""
    monkeypatch.setattr(
        trace_reduce, "reduce_capture",
        lambda trace_dir, param_shapes=None: trace_reduce.reduce_events(
            trace_reduce.load_events(RECORDED), param_shapes
        ),
    )
    workload = "alexnet_b2048_loader"
    cell = _list_waiting_cell(root, workload)
    result = run.run_cell(workload, seed=1, seconds=5, trace=True, root=root)
    _check_line(result, cell, traced=True)
    assert {"compile_s", "input_wait_ms_per_step", "host_dispatch_ms_per_step",
            "device_ms_per_step", "device_mfu_pct", "device_idle_pct"} <= set(result["metrics"])
    assert "collective_ms_per_step" not in result["metrics"]  # a one-chip cell
    assert result["device"]["busy_s"] > 0
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 10
    # the profiler really ran and wrote inside the checkout
    assert trace_reduce.find_capture(os.path.join(root, ".bench_out", workload, "trace"))


def test_a_fifth_cell_needs_no_code(root):
    """A throw-away cell: one entry in BENCHMARK.json and one traffic file,
    no file edited. (More resident batches than K, so the chunks cycle.)"""
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({
        "name": "throwaway", "config": "alexnet_cifar224", "traffic": "b4_cycled",
        "chips": 1, "why": "a test",
    })
    json.dump(bench, open(path, "w"))
    with open(os.path.join(root, "benchmark", "traffic", "b4_cycled.json"), "w") as f:
        json.dump({"feed": "resident", "batch_per_chip": 4, "resident_batches": 4,
                   "scan_steps": 2, "mesh": {"data": 1}, "ddp": {}}, f)
    result = run.run_cell("throwaway", seed=3, seconds=0.3, trace=False, root=root)
    _check_line(result, cells.load_cell("throwaway", root), traced=False)
    assert result["attempted"] % 2 == 0


def test_the_four_chip_cell_on_four_virtual_devices(root):
    """Mesh data=4, the check batch split four ways against the
    single-worker reference."""
    cell = _list_waiting_cell(root, "alexnet_b2048_dp4")
    assert cell.chips == 4 and cell.traffic["mesh"] == {"data": 4}
    result = run.run_cell(cell.name, seed=0, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert result["correct"] is True  # the allreduced step matches the single worker


def test_no_branch_on_a_name():
    """``run.py`` and what it imports name no cell, configuration, traffic
    mix or feed."""
    bench = cells.load_benchmark()
    names = (
        [w["name"] for w in bench["workloads"]] + [c["name"] for c in bench["configs"]]
        + [w["traffic"] for w in bench["workloads"]] + ["resident", "loader"]
    )
    for module in ("run", "cells", "system", "check", "spans", "data"):
        source = open(os.path.join(cells.ROOT, "benchmark", module + ".py")).read()
        code = "\n".join(
            line for line in source.split("\n") if not line.lstrip().startswith("#")
        )
        for name in names:
            assert f'"{name}"' not in code and f"'{name}'" not in code, (module, name)


def test_run_py_refuses_a_cpu():
    """As shipped, with no stand-in: a non-zero exit and nothing on standard
    output that looks like a result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(cells.ROOT, "benchmark", "run.py"),
         "--workload", WORKLOADS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no accelerator" in proc.stderr


def _fake_jax_devices(monkeypatch, n, kind):
    import jax

    devices = [types.SimpleNamespace(platform="tpu", device_kind=kind) for _ in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)


def test_refuses_an_unknown_device_kind(monkeypatch):
    _fake_jax_devices(monkeypatch, 1, "TPU v9 imaginary")
    with pytest.raises(cells.BenchmarkError, match="not in benchmark/peaks.json"):
        run.require_devices(1, cells.ROOT)


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    _fake_jax_devices(monkeypatch, 1, "TPU v5 lite")
    assert run.require_devices(1, cells.ROOT)[1]["bf16_flops_per_s"] == 197e12
    with pytest.raises(cells.BenchmarkError, match="needs 4 chip"):
        run.require_devices(4, cells.ROOT)


def test_missing_memory_statistics_are_an_error():
    device = types.SimpleNamespace(memory_stats=lambda: None)
    with pytest.raises(cells.BenchmarkError, match="peak_bytes_in_use"):
        run.peak_memory_bytes([device])
    device = types.SimpleNamespace(
        memory_stats=lambda: {"peak_bytes_in_use": 5, "peak_bytes_reserved": 7}
    )
    assert run.peak_memory_bytes([device]) == 12
