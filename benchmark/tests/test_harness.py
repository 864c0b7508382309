"""The harness end to end at a tiny size on the CPU, through the test-only
stand-in for the device check (``tiny.fake_devices``); and ``run.py`` as it
ships, which refuses a CPU."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchmark import cells, run, trace_reduce
from benchmark.tests import tiny

WORKLOADS = [w["name"] for w in cells.load_benchmark()["workloads"]]
RECORDED = os.path.join(os.path.dirname(__file__), "data", "recorded.trace.json.gz")
SECOND_SYSTEM = os.path.join(os.path.dirname(__file__), "second_system")


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


def test_the_loader_fed_cell(root):
    """Pass after pass through the real loader and ``run_pass``: two fused
    chunks and two single steps a pass at this size, the last batch padded."""
    cell = cells.load_cell("alexnet_b2048_loader", root)
    result = run.run_cell(cell.name, seed=0, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert result["attempted"] > 0


def _hand_over_the_recorded_capture(monkeypatch):
    """A CPU capture has no device plane, so the reduction is handed the
    recorded chip capture; everything round it is the real path."""
    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )


def test_traced_line(root, monkeypatch):
    """A traced run reports per-layer metrics, the device's busy time and a
    breakdown."""
    _hand_over_the_recorded_capture(monkeypatch)
    workload = "alexnet_b2048_loader"
    cell = cells.load_cell(workload, root)
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


def test_a_second_system_needs_no_code(root, monkeypatch, capfd):
    """A token model arrives as files: a system, a configuration that names
    it, its reference and FLOPs, a traffic file, and one entry each in
    BENCHMARK.json. No file of the harness is edited; the step's weight-1
    unit, which the harness counts, is then a token."""
    for kind in ("systems", "configs", "reference", "flops", "traffic"):
        for name in os.listdir(os.path.join(SECOND_SYSTEM, kind)):
            shutil.copy(os.path.join(SECOND_SYSTEM, kind, name), os.path.join(root, "benchmark", kind))
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({
        "name": "tiny_lm", "source": "benchmark/tests/second_system/README.md",
        "file": "benchmark/configs/tiny_lm.json", "reduced": [], "why": "a test",
    })
    bench["workloads"].append({
        "name": "tiny_lm_resident", "config": "tiny_lm", "traffic": "lm_b4_resident",
        "chips": 1, "why": "a test",
    })
    json.dump(bench, open(path, "w"))
    cell = cells.load_cell("tiny_lm_resident", root)
    assert cell.config["sample_unit"] == "token"

    result = run.run_cell(cell.name, seed=5, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert result["correct"] is True
    said = [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')]
    tokens = cell.traffic["batch_per_chip"] * cell.config["tokens"]["seq_len"]
    assert said[-1]["samples"] == result["attempted"] * tokens
    assert said[-1]["reference"]["loss_rel_err"] < 1e-4

    _hand_over_the_recorded_capture(monkeypatch)
    traced = run.run_cell(cell.name, seed=6, seconds=5, trace=True, root=root)
    _check_line(traced, cell, traced=True)
    assert traced["correct"] is True
    assert {"compile_s", "host_dispatch_ms_per_step", "device_ms_per_step"} <= set(traced["metrics"])
    assert "input_wait_ms_per_step" not in traced["metrics"]


def test_the_four_chip_cell_on_four_virtual_devices(root):
    """Mesh data=4, the check batch split four ways against the
    single-worker reference."""
    cell = cells.load_cell("alexnet_b2048_dp4", root)
    assert cell.chips == 4 and cell.traffic["mesh"] == {"data": 4}
    result = run.run_cell(cell.name, seed=0, seconds=0.3, trace=False, root=root)
    _check_line(result, cell, traced=False)
    assert result["correct"] is True  # the allreduced step matches the single worker


def _harness_sources():
    """Every Python file of the benchmark that is not a system, a test or one
    configuration's own reference or FLOPs: path -> code without comments."""
    top = os.path.join(cells.ROOT, "benchmark")
    out = {}
    for folder, _, files in os.walk(top):
        kind = os.path.relpath(folder, top).split(os.sep)[0]
        if kind in ("systems", "tests", "reference", "flops", "__pycache__"):
            continue
        for name in files:
            if name.endswith(".py"):
                source = open(os.path.join(folder, name)).read()
                out[os.path.relpath(os.path.join(folder, name), top)] = "\n".join(
                    line for line in source.split("\n") if not line.lstrip().startswith("#")
                )
    return out


@pytest.mark.parametrize("lower", ["float8_e5m2", "float8_e4m3fn"])
def test_the_next_precision_down_is_not_correct(root, capfd, lower):
    """The control of the comparison with the reference. The configuration
    states bfloat16 compute; the program's own path one step down, an 8-bit
    float as its compute type, is what would tempt a later PR, and it has to
    fail the limits, not graze them (on the chip at the cell's size:
    PERF.md section 2)."""
    cell = cells.load_cell(WORKLOADS[0], root)
    assert cell.config["compute_dtype"] == "bfloat16"
    entry = next(c for c in cells.load_benchmark(root)["configs"] if c["name"] == cell.config_name)
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump({**cell.config, "compute_dtype": lower}, f)
    result = run.run_cell(cell.name, seed=3, seconds=0.3, trace=False, root=root)
    assert result["correct"] is False
    said = [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')]
    reference = said[-1]["reference"]
    assert reference["ok"] is False
    assert reference["loss_rel_err"] > 3 * reference["loss_rtol"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(root, monkeypatch):
    """The timed path broken underneath: the step computes its metrics and
    hands back the state it was given. The loss then never falls in the
    window and the parameters never move from the reference's start."""
    import jax
    import jax.numpy as jnp
    from tpuddp.parallel.ddp import DistributedDataParallel

    def unchanged(step):
        def broken(self, state, batch):
            kept = jax.tree_util.tree_map(jnp.copy, state)  # the real step donates its state
            return kept, step(self, state, batch)[1]
        return broken

    for name in ("train_step", "train_step_many"):
        monkeypatch.setattr(
            DistributedDataParallel, name, unchanged(getattr(DistributedDataParallel, name))
        )
    result = run.run_cell(WORKLOADS[0], seed=2, seconds=0.3, trace=False, root=root)
    assert result["correct"] is False and result["attempted"] > 0


def test_no_branch_on_a_name():
    """The harness names no cell, configuration, traffic mix, feed or system,
    and reads none of a configuration's keys that say what a batch or a model
    looks like: those belong to ``benchmark/systems/``."""
    bench = cells.load_benchmark()
    systems = [f[:-3] for f in os.listdir(os.path.join(cells.ROOT, "benchmark", "systems"))
               if f.endswith(".py")]
    names = (
        [w["name"] for w in bench["workloads"]] + [c["name"] for c in bench["configs"]]
        + [w["traffic"] for w in bench["workloads"]] + ["resident", "loader"]
        + systems + ["input", "model", "num_classes"]
    )
    sources = _harness_sources()
    assert {"run.py", "cells.py", "check.py", "spans.py", "trace_reduce.py",
            os.path.join("feeds", "resident.py"), os.path.join("feeds", "loader.py")} <= set(sources)
    assert systems
    for path, code in sources.items():
        for name in names:
            assert f'"{name}"' not in code and f"'{name}'" not in code, (path, name)
        for module in ("tpuddp.models", "tpuddp.nn", "tpuddp.data.transforms", "tpuddp import nn"):
            assert module not in code, (path, module)


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
