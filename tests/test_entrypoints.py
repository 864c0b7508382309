"""End-to-end entrypoint runs (SURVEY.md §3.1-§3.3 call-stack parity) on the
8-device CPU world with synthetic-fallback data."""

import os
import subprocess
import sys

import pytest
import yaml

import submit_job as submit_mod
from tpuddp.parallel import backend


TINY_TRAINING = {
    "model": "toy_mlp",
    "dataset": "cifar10",
    "data_root": "/nonexistent",  # forces synthetic fallback
    "train_batch_size": 8,
    "test_batch_size": 8,
    "learning_rate": 0.01,
    "num_epochs": 1,
    "checkpoint_epoch": 1,
    "image_size": None,
    "seed": 0,
    "mode": "shard_map",
    "sync_bn": False,
}


@pytest.fixture(autouse=True)
def fresh_backend():
    backend.cleanup()
    yield
    backend.cleanup()


@pytest.mark.slow
def test_native_entrypoint_end_to_end(tmp_path, capsys):
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel.spawn import run_ddp_training

    run_ddp_training(
        partial(basic_ddp_training_loop, training=TINY_TRAINING),
        world_size=8,
        save_dir=str(tmp_path),
        optional_args={"set_epoch": True, "print_rand": True},
        backend="cpu",
    )
    # checkpoint written with reference naming, epoch 0 (quirk Q6 parity)
    assert os.path.exists(tmp_path / "ckpt_0.npz")
    out = capsys.readouterr().out
    assert "Epoch 1/1" in out
    assert "Test Accuracy" in out
    assert "Python random state" in out  # print_rand probe
    assert "TRAIN: Batch 0" in out  # shard-disjointness probe


@pytest.mark.slow
def test_accelerate_entrypoint_end_to_end(tmp_path, capsys):
    from train_accelerate import basic_accelerate_training

    training = dict(TINY_TRAINING, num_epochs=1)
    basic_accelerate_training(str(tmp_path), training)
    assert os.path.exists(tmp_path / "model.npz")
    out = capsys.readouterr().out
    assert "Epoch 1/1" in out
    assert "Finished Training." in out


@pytest.mark.slow
def test_accelerate_entrypoint_resume(tmp_path, capsys):
    """training.resume on the managed path: a first run leaves
    state_{epoch}.npz files; a restarted run restores the newest (weights +
    optimizer moments + RNG position) and continues from the next epoch."""
    from train_accelerate import basic_accelerate_training

    training = dict(TINY_TRAINING, num_epochs=1, deferred_metrics=True)
    basic_accelerate_training(str(tmp_path), training)
    assert os.path.exists(tmp_path / "state_0.npz")
    capsys.readouterr()

    training = dict(TINY_TRAINING, num_epochs=2, resume=True, deferred_metrics=True)
    basic_accelerate_training(str(tmp_path), training)
    out = capsys.readouterr().out
    assert "Resumed from epoch 0 state." in out
    assert "Epoch 2/2" in out
    assert "Epoch 1/2" not in out  # epoch 0 was not re-trained
    assert os.path.exists(tmp_path / "state_1.npz")


def test_submit_job_tpu_dry_run(tmp_path):
    settings = {
        "script_path": "train_native.py",
        "out_dir": str(tmp_path / "out"),
        "local": {
            "device": "tpu",
            "tpu": {"name": "pod0", "zone": "us-central2-b", "num_chips": 32},
        },
    }
    sf = tmp_path / "s.yaml"
    sf.write_text(yaml.dump(settings))
    rc = submit_mod.main(["--settings_file", str(sf), "--dry_run"])
    assert rc == 0
    script = tmp_path / "out" / "launch_tpu.sh"
    text = script.read_text()
    assert "gcloud compute tpus tpu-vm ssh pod0" in text
    assert "--worker=all" in text
    assert "train_native.py --settings_file" in text
    assert os.access(script, os.X_OK)


def test_submit_job_condor_dry_run(tmp_path):
    """Reference condor schema keeps working (submit_job.py:7-43 contract)."""
    settings = {
        "script_path": "train_native.py",
        "out_dir": str(tmp_path / "out"),
        "local": {
            "device": "cuda",
            "condor": {
                "bid": 50,
                "num_cpus": 2,
                "memory_cpus": 128000,
                "num_gpus": 2,
                "memory_gpus": 60000,
            },
        },
    }
    sf = tmp_path / "s.yaml"
    sf.write_text(yaml.dump(settings))
    rc = submit_mod.main(["--settings_file", str(sf), "--dry_run"])
    assert rc == 0
    sub = (tmp_path / "out" / "submission_file.sub").read_text()
    assert f"executable = {sys.executable}" in sub
    assert "request_gpus = 2" in sub
    assert "TARGET.CUDAGlobalMemoryMb > 60000" in sub
    assert sub.rstrip().endswith("queue")


def test_submit_job_requires_tpu_or_condor(tmp_path):
    sf = tmp_path / "s.yaml"
    sf.write_text(yaml.dump({"script_path": "x", "out_dir": str(tmp_path), "local": {}}))
    with pytest.raises(ValueError):
        submit_mod.main(["--settings_file", str(sf), "--dry_run"])


@pytest.mark.slow
def test_native_cli_subprocess_with_reexec_launcher(tmp_path):
    """Full CLI parity run: `python train_native.py --settings_file ...` on a
    chipless config exercises the spawn-analog re-exec launcher."""
    settings = {
        "script_path": "train_native.py",
        "out_dir": str(tmp_path / "out"),
        "optional_args": {"set_epoch": True, "print_rand": False},
        "local": {"device": "cpu", "tpu": {"num_chips": 4}},
        "training": dict(TINY_TRAINING, train_batch_size=16, test_batch_size=16),
    }
    sf = tmp_path / "s.yaml"
    sf.write_text(yaml.dump(settings))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["TPUDDP_BACKEND"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "train_native.py", "--settings_file", str(sf)],
        capture_output=True,
        text=True,
        timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "Epoch 1/1" in proc.stdout
    assert os.path.exists(tmp_path / "out" / "ckpt_0.npz")
    # provenance copy of the settings file into out_dir
    assert os.path.exists(tmp_path / "out" / "s.yaml")


@pytest.mark.slow
def test_accelerate_entrypoint_observability_parity(tmp_path, capsys, monkeypatch):
    """The managed loop honors the same observability hooks as the native
    one: history.jsonl written by process 0, and $TPUDDP_DEBUG_NANS guards
    the aggregated losses."""
    import json

    from train_accelerate import basic_accelerate_training

    training = dict(TINY_TRAINING, num_epochs=2, deferred_metrics=True)
    basic_accelerate_training(str(tmp_path), training)
    capsys.readouterr()
    lines = [
        json.loads(l)
        for l in open(tmp_path / "history.jsonl").read().splitlines()
    ]
    # typed stream: a run_meta header opens the file, then one epoch row per
    # epoch, each carrying the step recorder's percentile fields
    assert lines[0]["type"] == "run_meta" and lines[0]["api"] == "managed"
    epochs = [l for l in lines if l.get("type") == "epoch"]
    assert len(epochs) == 2
    assert {"epoch", "train_loss", "test_loss", "test_accuracy"} <= set(epochs[0])
    assert epochs[0]["step_time_ms_p50"] is not None
    from tpuddp.observability import schema as obs_schema

    assert obs_schema.validate_history_records(lines) == []

    # NaN guard: a poisoned epoch must still write its post-mortem row
    # (record-before-check, native-driver parity) and then raise
    monkeypatch.setenv("TPUDDP_DEBUG_NANS", "1")
    monkeypatch.setattr(
        "train_accelerate.train", lambda *a, **k: (float("nan"), 8.0)
    )
    monkeypatch.setattr(
        "train_accelerate.evaluate", lambda *a, **k: (0.1, 50.0, 8)
    )
    with pytest.raises(FloatingPointError, match="train loss"):
        basic_accelerate_training(str(tmp_path / "nan"), training)
    raw = open(tmp_path / "nan" / "history.jsonl").read()
    # strict-JSON contract (ISSUE 3): the poisoned metric lands as null,
    # never the bare NaN token strict parsers reject
    assert "NaN" not in raw
    last = json.loads(raw.splitlines()[-1])
    assert last["epoch"] == 0 and last["train_loss"] is None
