"""Multi-process DP — the multi-host contract (SURVEY.md §2c: the one place
the build exceeds the reference's single-node scope). Two OS processes with 4
virtual CPU devices each rendezvous via jax.distributed into one 8-device
world and train together."""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest
import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# Probe script for the multi-process backend env: two 1-device processes
# rendezvous and run the cheapest cross-process collective the framework
# uses (broadcast_one_to_all). Some jaxlib builds rendezvous fine but then
# refuse the computation itself ("Multiprocess computations aren't
# implemented on the CPU backend") — probing initialize alone would miss
# exactly the failure mode these tests die of.
_PROBE = """
import sys
import numpy as np
import jax
from jax.experimental import multihost_utils
jax.distributed.initialize(
    coordinator_address="127.0.0.1:%s", num_processes=2,
    process_id=int(sys.argv[1]),
)
out = multihost_utils.broadcast_one_to_all(np.ones((1,), np.float32))
assert float(out[0]) == 1.0
print("MULTIHOST_PROBE_OK")
"""

_probe_cache = {}


def multiprocess_backend_reason():
    """None when this host can run 2-process CPU-backend collectives; else a
    typed one-line reason (the skip message) naming what is absent."""
    if "reason" in _probe_cache:
        return _probe_cache["reason"]
    port = free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # 1 device per probe process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE % port, str(i)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    reason = None
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                reason = ("multi-process backend env absent: 2-process "
                          "rendezvous hung")
                break
            if p.returncode != 0 or "MULTIHOST_PROBE_OK" not in out:
                tail = [l for l in out.strip().splitlines() if l][-1:] or ["no output"]
                reason = (
                    "multi-process backend env absent: cross-process CPU "
                    f"collective failed ({tail[0][:160]})"
                )
                break
    finally:
        # a failed probe leaves its SIBLING blocked in rendezvous on the
        # dead coordinator: kill + reap every process on every exit path
        # (no lingering port holder, no zombie)
        for q in procs:
            if q.poll() is None:
                q.kill()
            try:
                q.communicate(timeout=30)
            except Exception:  # noqa: BLE001 — best-effort reap
                pass
    _probe_cache["reason"] = reason
    return reason


@pytest.fixture(scope="module")
def multiprocess_backend():
    """Skip (typed reason), never error, when the multi-process backend env
    is absent — e.g. a jaxlib whose CPU backend rejects multiprocess
    computations, or a sandbox without loopback rendezvous."""
    reason = multiprocess_backend_reason()
    if reason is not None:
        pytest.skip(reason)


@pytest.mark.slow
def test_two_process_dp_world(tmp_path, multiprocess_backend):
    port = free_port()
    env = dict(os.environ)
    # clean CPU-only children: 4 host devices each
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TPUDDP_BACKEND"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "_multihost_worker.py"),
             str(i), "2", str(port), str(tmp_path)],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\n{out[-2000:]}\n{err[-3000:]}"
        outs.append(out)

    results = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("WORKER_RESULT ")][0]
        results.append(json.loads(line[len("WORKER_RESULT "):]))
    results.sort(key=lambda r: r["proc"])

    # each process owns a disjoint half of the 8 global replicas, mesh order
    assert results[0]["local_ranks"] == [0, 1, 2, 3]
    assert results[1]["local_ranks"] == [4, 5, 6, 7]

    # both processes computed IDENTICAL global metrics (the psum contract)
    np.testing.assert_allclose(
        results[0]["train_loss"], results[1]["train_loss"], rtol=1e-6
    )
    assert results[0]["n"] == results[1]["n"] == [128.0, 128.0]

    # the managed (Accelerator) path agrees across processes too
    assert len(results[0]["managed_losses"]) == 3
    np.testing.assert_allclose(
        results[0]["managed_losses"], results[1]["managed_losses"], rtol=1e-6
    )
    assert results[0]["is_main"] and not results[1]["is_main"]

    # a custom sampler drawn independently (unseeded) per process still
    # yields globally disjoint shards covering the dataset exactly once —
    # proof that process 0's materialized order was broadcast
    for key in ("sampler_shards", "sampler_shards_ep1"):
        all_idx = [i for r in results for shard in r[key] for i in shard]
        assert sorted(all_idx) == list(range(128))
    # set_epoch invalidated the memo: epoch 1 re-drew (and re-broadcast) a
    # fresh order rather than replaying epoch 0's cached one
    assert results[0]["sampler_shards_ep1"] != results[0]["sampler_shards"]

    # process 0 only wrote the checkpoints; the loop's epoch log printed once
    assert os.path.exists(tmp_path / "ckpt_0.npz")
    assert os.path.exists(tmp_path / "ckpt_1.npz")
    epoch_lines_0 = [l for l in outs[0].splitlines() if l.startswith("Epoch ")]
    epoch_lines_1 = [l for l in outs[1].splitlines() if l.startswith("Epoch ")]
    assert len(epoch_lines_0) == 2  # process 0 logs
    assert len(epoch_lines_1) == 0  # process 1 gated


@pytest.mark.slow
def test_two_host_world_from_cli(tmp_path, multiprocess_backend):
    """The multi-host world must be reachable from the actual
    CLI surface — one shared settings file with a ``local.rendezvous`` block,
    per-host process id via $TPUDDP_PROCESS_ID, no library code written by the
    user. Reference analog: MASTER_ADDR/PORT env + mp.spawn
    (multi-GPU-training-torch.py:29-47)."""
    port = free_port()
    settings = {
        "script_path": "train_native.py",
        "out_dir": str(tmp_path / "out"),
        "optional_args": {"set_epoch": True, "print_rand": False},
        "local": {
            "device": "cpu",
            "tpu": {"num_chips": 8},  # GLOBAL world: 2 hosts x 4 devices
            "rendezvous": {
                "coordinator_address": f"127.0.0.1:{port}",
                "num_processes": 2,
                # process_id comes from $TPUDDP_PROCESS_ID, per host
            },
        },
        "training": {
            "model": "toy_mlp",
            "data_root": "/nonexistent",  # synthetic fallback
            "train_batch_size": 8,
            "test_batch_size": 8,
            "num_epochs": 1,
            "checkpoint_epoch": 1,
            "image_size": None,
            "seed": 0,
            "synthetic_n": [64, 32],
        },
    }
    sf = tmp_path / "shared.yaml"
    sf.write_text(yaml.dump(settings))

    def child_env(proc_id):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the multihost re-exec launcher sets it
        env["JAX_PLATFORMS"] = "cpu"
        env["TPUDDP_BACKEND"] = "cpu"
        env["TPUDDP_PROCESS_ID"] = str(proc_id)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        return env

    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "train_native.py"),
             "--settings_file", str(sf)],
            env=child_env(i), cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\n{out[-2000:]}\n{err[-3000:]}"
        outs.append(out)

    # both processes entered the training loop with the 8-wide global world
    assert "Running DDP training on process 0 (8-chip world)." in outs[0]
    assert "Running DDP training on process 1 (8-chip world)." in outs[1]
    # process-0-only epoch log + checkpoint (the dist.barrier/rank-0 contract)
    assert any(l.startswith("Epoch 1/1") for l in outs[0].splitlines())
    assert not any(l.startswith("Epoch 1/1") for l in outs[1].splitlines())
    assert os.path.exists(tmp_path / "out" / "ckpt_0.npz")
