"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the main path once through the entry point a user calls: AlexNet at
224x224 (58M parameters, the source paper's workload) trained by
``train_native.py`` on every local chip for two epochs of synthetic data
(leg 1), then resumed from its checkpoint for a third epoch by a fresh process
(leg 2, which must start from the compile cache leg 1 wrote). Then it checks
the artifacts by the repo's own means. Nothing it prints is a benchmark
metric.

    python chip_smoke.py

Exit 0 and, as the LAST stdout line, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` —
or a non-zero exit with the reasons on stderr and no result line: when JAX
finds no TPU, when any check fails, or when the rest of the repo is not
beside this file. No command-line argument relaxes the device check; tests
import :func:`smoke` and pass a backend and a tiny model in Python.

A chip belongs to one process at a time, so this parent never imports JAX:
a short probe child asks JAX what it sees and exits, then the legs run as
children in turn.
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time

import yaml

ROOT = os.path.dirname(os.path.abspath(__file__))
# small artifacts (settings, child logs, history) — what the chip tool brings back
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# the run's out_dir: three 0.7 GB checkpoints at full width, too big to bring back
RUN_DIR = os.path.join(ROOT, "out", "chip_smoke")

# the training block of configs/cifar10_alexnet_tpu.yaml with the data named
# outright (no missing-CIFAR fallback, no download) and two checkpointed epochs;
# everything else default: scan_steps auto, the pipeline on, prefetch on
TRAINING = {
    "model": "alexnet",
    "dataset": "synthetic",
    "train_batch_size": 128,
    "test_batch_size": 100,
    "learning_rate": 0.001,
    "num_epochs": 2,
    "checkpoint_epoch": 1,
    "image_size": 224,
    "seed": 0,
}
LEG_TIMEOUT_S = 500  # two legs + probe stay inside the 1200 s contract

_PROBE = """
import importlib.metadata as md, json
import jax, jaxlib
from tpuddp.observability.recorder import PEAK_FLOPS
from tpuddp.utils import compile_cache
def version(pkg):
    try:
        return md.version(pkg)
    except md.PackageNotFoundError:
        return None
d = jax.devices()
print(json.dumps({
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": version("libtpu"), "peak_kinds": sorted(PEAK_FLOPS),
    "cache_dir": compile_cache.directory(),
}))
"""

# children log jax._src.compiler at DEBUG so persistent-cache hits and misses
# can be counted; those lines go to the leg's log file, not to the tails below
_COMPILER_DEBUG = re.compile(r"^DEBUG:.*?:jax\._src\.compiler:\d+: (.*)$")


def probe_device() -> dict:
    """What JAX sees, asked by a child that exits (and frees the chip)."""
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"device probe failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(1 for f in os.listdir(cache_dir) if f.endswith("-cache"))


def run_leg(settings_path: str, log_path: str) -> dict:
    """One ``train_native.py`` child. Returns its exit code (None on
    timeout), its output lines stamped with seconds since launch, and the
    persistent-cache hit/miss counts."""
    env = dict(
        os.environ, PYTHONUNBUFFERED="1",
        JAX_DEBUG_LOG_MODULES="jax._src.compiler",
    )
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "train_native.py", "--settings_file", settings_path],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True,
    )
    leg = {"rc": None, "lines": [], "cache_hits": 0, "cache_misses": 0}

    def pump():
        last_debug = None
        with open(log_path, "w") as log:
            for line in proc.stdout:
                log.write(line)
                line = line.rstrip("\n")
                m = _COMPILER_DEBUG.match(line)
                if m:
                    last_debug = m.group(1)
                    leg["cache_hits"] += "cache hit" in last_debug
                    leg["cache_misses"] += "CACHE MISS" in last_debug
                elif line != last_debug:  # the root logger echoes each one
                    leg["lines"].append((time.monotonic() - t0, line))

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    try:
        leg["rc"] = proc.wait(timeout=LEG_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        pass
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
    reader.join(timeout=30)
    leg["seconds"] = time.monotonic() - t0
    return leg


def first_dispatch_seconds(leg: dict):
    """Seconds from launch to the first train-loss line: the first fused
    dispatch (scan_steps train steps) has completed and been read back."""
    return next(
        (round(t, 1) for t, line in leg["lines"]
         if line.startswith("Train loss on replica")),
        None,
    )


def read_history(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def check(device: dict, legs: list, run_dir: str) -> list:
    """Every reason the run is not right; empty when it is."""
    bad = []
    for i, leg in enumerate(legs, 1):
        if leg["rc"] != 0:
            tail = "\n".join(line for _, line in leg["lines"][-30:])
            bad.append(f"leg {i} exited {leg['rc']} (None = timeout):\n{tail}")
    history = os.path.join(run_dir, "history.jsonl")
    if not os.path.exists(history):
        return bad + [f"{history} was not written"]
    validate = subprocess.run(
        [sys.executable, os.path.join("tools", "tpuddp_inspect.py"), history,
         "--validate"], cwd=ROOT, capture_output=True, text=True,
    )
    if validate.returncode != 0:
        bad.append(f"history.jsonl does not validate: {validate.stdout[-500:]}")
    rows = read_history(history)
    metas = [r for r in rows if r.get("type") == "run_meta"]
    epochs = [r for r in rows if r.get("type") == "epoch"]
    if len(metas) != len(legs):
        bad.append(f"{len(metas)} run_meta rows for {len(legs)} legs")
    for meta in metas:
        if meta.get("device_kind") not in device["peak_kinds"]:
            bad.append(
                f"run_meta.device_kind {meta.get('device_kind')!r} is not a "
                f"key of PEAK_FLOPS {device['peak_kinds']}"
            )
        if meta.get("device_kind") != device["kind"]:
            bad.append(
                f"run_meta.device_kind {meta.get('device_kind')!r} != probed "
                f"{device['kind']!r}"
            )
        if meta.get("world_size") != device["count"]:
            bad.append(
                f"run_meta.world_size {meta.get('world_size')} != "
                f"{device['count']} devices"
            )
        if meta.get("mesh_shape") != {"data": device["count"]}:
            bad.append(f"run_meta.mesh_shape is {meta.get('mesh_shape')}")
    if [r.get("epoch") for r in epochs] != [0, 1, 2]:
        bad.append(
            f"epoch rows are {[r.get('epoch') for r in epochs]}, expected "
            "[0, 1] from leg 1 and [2] from the resumed leg 2"
        )
    for r in epochs:
        for key in ("train_loss", "test_loss"):
            v = r.get(key)
            if not isinstance(v, (int, float)) or not math.isfinite(v):
                bad.append(f"epoch {r.get('epoch')}: {key} is {v!r}")
        if r.get("mfu_p50") is None:
            bad.append(
                f"epoch {r.get('epoch')}: mfu_p50 is null (the FLOPs probe "
                "failed, or the chip has no PEAK_FLOPS entry)"
            )
    losses = [r.get("train_loss") for r in epochs]
    if len(losses) > 1 and not losses[-1] < losses[0]:
        bad.append(f"train loss did not fall over the epochs: {losses}")
    for epoch in range(3):
        for name in (f"ckpt_{epoch}.npz", f"ckpt_{epoch}.npz.sha256"):
            if not os.path.exists(os.path.join(run_dir, name)):
                bad.append(f"{name} is missing")
    replicas = {
        line.split("replica")[1].split(":")[0].strip()
        for _, line in legs[0]["lines"] if line.startswith("Train loss on replica")
    }
    if len(replicas) != device["count"]:
        bad.append(
            f"leg 1 printed loss lines for replicas {sorted(replicas)}, "
            f"expected {device['count']}"
        )
    if len(legs) > 1:
        resumed = any(
            "Auto-resume: continuing from epoch 2." in line
            for _, line in legs[1]["lines"]
        )
        if not resumed or (len(metas) > 1 and metas[1].get("start_epoch") != 2):
            bad.append("leg 2 did not say it resumed at epoch 2")
        if legs[1]["cache_hits"] < 1:
            bad.append(
                "leg 2 compiled everything again: no persistent-cache hit "
                f"({legs[1]['cache_misses']} misses)"
            )
    return bad


def smoke(backend: str, training: dict, device: dict,
          out_dir: str = OUT_DIR, run_dir: str = RUN_DIR) -> list:
    """Run both legs on ``backend`` and return the failed checks."""
    for d in (out_dir, run_dir):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    settings = {
        "script_path": "train_native.py",
        "out_dir": run_dir,
        "optional_args": {"set_epoch": True, "print_rand": False},
        "local": {"device": backend},  # no num_chips: every local chip
        "training": dict(training),
    }
    legs = []
    for i, extra in enumerate(({}, {"num_epochs": training["num_epochs"] + 1,
                                    "resume": True}), 1):
        settings["training"].update(extra)
        path = os.path.join(out_dir, f"leg{i}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(settings, f)
        leg = run_leg(path, os.path.join(out_dir, f"leg{i}.log"))
        leg["cache_entries"] = cache_entries(device["cache_dir"])
        legs.append(leg)
        print(
            f"leg {i}: exit {leg['rc']} in {leg['seconds']:.1f} s; first fused "
            f"train dispatch read back after {first_dispatch_seconds(leg)} s; "
            f"persistent cache {leg['cache_hits']} hits / "
            f"{leg['cache_misses']} misses, {leg['cache_entries']} entries in "
            f"{device['cache_dir']}", flush=True,
        )
        if leg["rc"] != 0:
            break
    history = os.path.join(run_dir, "history.jsonl")
    if os.path.exists(history):
        shutil.copy(history, out_dir)
        epochs = [r for r in read_history(history) if r.get("type") == "epoch"]
        print(
            f"train steps taken: {sum(r.get('train_steps', 0) for r in epochs)}; "
            f"train loss by epoch: {[r.get('train_loss') for r in epochs]}; "
            f"mfu_p50 by epoch (dispatch-resolution, not a benchmark): "
            f"{[r.get('mfu_p50') for r in epochs]}"
        )
    output = "\n".join(line for leg in legs for _, line in leg["lines"])
    print(
        "loader gather: "
        + ("numpy (native library unavailable, see the leg logs)"
           if "the loader gathers in numpy" in output else "native")
    )
    return check(device, legs, run_dir)


def main() -> int:
    if not os.path.exists(os.path.join(ROOT, "train_native.py")):
        print(f"chip_smoke: no train_native.py beside {__file__}: run it from "
              "a checkout of the repo", file=sys.stderr)
        return 2
    device = probe_device()
    print(
        f"platform {device['platform']}, device_kind {device['kind']}, "
        f"{device['count']} device(s); jax {device['jax']}, jaxlib "
        f"{device['jaxlib']}, libtpu {device['libtpu']}", flush=True,
    )
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX found platform {device['platform']!r} "
              f"({device['kind']}), not a TPU; nothing was run",
              file=sys.stderr)
        return 1
    failures = smoke("tpu", TRAINING, device)
    if failures:
        print("chip_smoke FAILED:\n- " + "\n- ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
