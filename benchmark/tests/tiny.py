"""A copy of the benchmark cut to a size the CPU runs in seconds, and the
test-only stand-in for the device check: ``run.py`` itself refuses a CPU."""

from __future__ import annotations

import json
import os
import shutil

from benchmark import cells

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAKE_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
              "ici_bytes_per_s": 1e10}


def make_root(tmp_path) -> str:
    """``BENCHMARK.json`` and ``benchmark/`` copied under ``tmp_path`` with
    every configuration and traffic file shrunk: same models, feeds and code
    paths, small images, batches and data."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    for name in os.listdir(os.path.join(root, "benchmark", "configs")):
        path = os.path.join(root, "benchmark", "configs", name)
        cfg = json.load(open(path))
        cfg = cells.load_module("systems", cfg["system"], root).shrunk(cfg)
        cfg["check"]["batch"] = 4
        # the CPU computes the "bfloat16" system in float32-accumulated
        # bf16 too, but tiny batches make BatchNorm statistics noisy
        cfg["check"]["loss_rtol"] = 0.05
        cfg["check"]["update_norm_rtol"] = 0.1
        json.dump(cfg, open(path, "w"))
    for name in os.listdir(os.path.join(root, "benchmark", "traffic")):
        path = os.path.join(root, "benchmark", "traffic", name)
        traffic = json.load(open(path))
        traffic["batch_per_chip"] = 4
        if "resident_batches" in traffic:
            traffic["resident_batches"] = traffic["scan_steps"] = 3
        if "dataset_samples" in traffic:
            traffic["dataset_samples"] = 30  # 8 batches, the last padded
            traffic["scan_steps"] = 3  # two fused chunks and two single steps
        traffic["trace_seconds"] = 0.2
        json.dump(traffic, open(path, "w"))
    return root


def fake_devices(monkeypatch, run_module):
    """Stand in for ``run.require_devices`` and ``run.peak_memory_bytes``:
    hand the CPU's virtual devices over with made-up peaks. The override
    lives here, in the tests; ``run.py`` has no switch for it."""
    import jax

    monkeypatch.setattr(
        run_module, "require_devices",
        lambda chips, root: (jax.devices()[:chips], dict(FAKE_PEAKS)),
    )
    monkeypatch.setattr(run_module, "peak_memory_bytes", lambda devices: 12345)
