"""Every file the benchmark names loads by name, and what the files say of
themselves agrees with ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark import cells

BENCH = cells.load_benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]


def _files(kind, suffix):
    return sorted(
        f[: -len(suffix)] for f in os.listdir(os.path.join(cells.ROOT, "benchmark", kind))
        if f.endswith(suffix) and not f.startswith("_")
    )


def test_contract_shape():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["benchmark"] and BENCH["command"][-1] == "benchmark/run.py"
    assert isinstance(BENCH["run_seconds"], int)
    four_chip = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four_chip) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for entry in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(entry["why"]) <= 200


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_loads(workload):
    cell = cells.load_cell(workload)
    assert cell.traffic["feed"] in _files("feeds", ".py")
    assert hasattr(cells.load_module("feeds", cell.traffic["feed"]), "Feed")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "samples_per_s_per_chip"}
    assert cell.per_layer
    moved = {m["name"] for m in cell.end_to_end}
    assert all(m["moves"] in moved for m in cell.per_layer)


def test_every_cell_has_its_traffic_file():
    # a traffic file or a reader may wait for the PR that lists it
    assert {w["traffic"] for w in BENCH["workloads"]} <= set(_files("traffic", ".json"))


@pytest.mark.parametrize("config", CONFIGS)
def test_configuration_files(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    system = cells.load_module("systems", cfg["system"])
    for asked in ("build_ddp", "init_variables", "init_state", "make_batches",
                  "unit_weights", "shrunk"):
        assert callable(getattr(system, asked)), asked
    assert cfg["sample_unit"] and system.unit_weights(cfg, 3, 2).sum() >= 6
    assert {"steps", "batch", "loss_rtol", "update_norm_rtol", "reason"} <= set(cfg["check"])
    assert callable(cells.load_module("reference", config).train_steps)
    assert cells.load_module("flops", config).train_flops_per_sample(cfg) > 0


def test_configs_on_disk_are_listed():
    assert _files("configs", ".json") == sorted(CONFIGS)
    assert _files("reference", ".py") == sorted(CONFIGS)
    assert _files("flops", ".py") == sorted(CONFIGS)


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    reader = cells.load_module("layer_metrics", metric)
    assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"],
    )
    # a reader that finds nothing to read returns nothing
    empty = {
        "cell": cells.load_cell(WORKLOADS[0]), "trace": None, "setup": {},
        "window": {"steps": 0, "samples": 0, "counters": {}},
        "spans": {"seconds": {}, "counts": {}}, "counters": {},
        "flops_per_sample": 1.0, "peaks": {"bf16_flops_per_s": 1.0},
    }
    assert reader.read(empty) is None


def test_listed_layer_metrics_are_on_disk():
    assert set(LAYER_METRICS) <= set(_files("layer_metrics", ".py"))


def test_analytic_flops():
    """The published multiply-accumulate counts: 0.71 G for AlexNet at 224,
    4.09 G for ResNet-50; a training step is three forwards' worth less the
    first layer's input gradient."""
    for config, macs, flops in (
        ("alexnet_cifar224", 710_133_440, 4_120_247_040.0),
        ("resnet50_imagenet224", 4_089_184_256, 24_299_077_632.0),
    ):
        cfg = cells.load_cell(next(
            w["name"] for w in BENCH["workloads"] if w["config"] == config
        )).config
        module = cells.load_module("flops", config)
        assert sum(m for m, _ in module.products(cfg)) == macs
        assert module.train_flops_per_sample(cfg) == flops


def test_peaks_table():
    for kind in ("TPU v5 lite", "TPU v5e"):
        peaks = cells.load_peaks(kind)
        assert peaks["bf16_flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.BenchmarkError, match="not in benchmark/peaks.json"):
        cells.load_peaks("TPU v9 imaginary")
    with pytest.raises(cells.BenchmarkError, match="not in benchmark/peaks.json"):
        cells.load_peaks("cpu")


def test_unknown_workload_is_an_error():
    with pytest.raises(cells.BenchmarkError, match="no workload"):
        cells.load_cell("no_such_cell")
