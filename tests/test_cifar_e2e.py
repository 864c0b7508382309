"""Entrypoint-level e2e over miniature CIFAR-formatted archives — both the
``cifar-10-batches-py`` pickle layout and the ``-bin`` binary layout
(reference data_and_toy_model.py:8-38). The real CIFAR-10 archive cannot be
staged in this zero-egress environment (BASELINE.md), so these fixtures make
the ONLY untested link in the reference workload the real archive's bytes:
the exact on-disk formats flow through `python train_native.py
--settings_file ...` as a real subprocess, producing the epoch log and
checkpoints."""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest
import yaml

from tpuddp.data.cifar10 import CIFAR10

N_PER_BATCH = 16  # 5 train batches of 16 + one test batch of 16


def _images_labels(seed: int, n: int):
    rs = np.random.RandomState(seed)
    # class-dependent mean so the toy model has signal to fit
    labels = rs.randint(0, 10, n).astype(np.int64)
    images = (
        rs.randint(0, 64, (n, 32, 32, 3)) + labels[:, None, None, None] * 19
    ).astype(np.uint8)
    return images, labels


def make_cifar_py_fixture(root) -> None:
    """data_batch_{1-5} / test_batch pickles with the exact torchvision keys:
    b'data' (N, 3072) uint8 rows in CHW order, b'labels' list of ints."""
    d = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(d, exist_ok=True)
    for i, name in enumerate([f"data_batch_{j}" for j in range(1, 6)] + ["test_batch"]):
        images, labels = _images_labels(100 + i, N_PER_BATCH)
        rows = images.transpose(0, 3, 1, 2).reshape(N_PER_BATCH, 3072)
        with open(os.path.join(d, name), "wb") as f:
            pickle.dump({b"data": rows, b"labels": labels.tolist()}, f)


def make_cifar_bin_fixture(root) -> None:
    """data_batch_{1-5}.bin / test_batch.bin: rows of 1 label byte + 3072
    CHW image bytes (the same pixels as the py fixture, by seed)."""
    d = os.path.join(root, "cifar-10-batches-bin")
    os.makedirs(d, exist_ok=True)
    names = [f"data_batch_{j}.bin" for j in range(1, 6)] + ["test_batch.bin"]
    for i, name in enumerate(names):
        images, labels = _images_labels(100 + i, N_PER_BATCH)
        rows = images.transpose(0, 3, 1, 2).reshape(N_PER_BATCH, 3072)
        raw = np.concatenate(
            [labels.astype(np.uint8)[:, None], rows], axis=1
        ).astype(np.uint8)
        raw.tofile(os.path.join(d, name))


def test_py_and_bin_fixtures_load_identically(tmp_path):
    """The two on-disk formats must decode to the same pixels/labels — the
    loader-level guarantee behind running either archive flavor."""
    py_root = tmp_path / "py"
    bin_root = tmp_path / "bin"
    make_cifar_py_fixture(str(py_root))
    make_cifar_bin_fixture(str(bin_root))
    for train in (True, False):
        a = CIFAR10(str(py_root), train=train)
        b = CIFAR10(str(bin_root), train=train)
        np.testing.assert_array_equal(a.images, b.images)
        np.testing.assert_array_equal(a.labels, b.labels)
    assert len(CIFAR10(str(py_root), train=True)) == 5 * N_PER_BATCH


def _run_native_cli(tmp_path, data_root: str):
    settings = {
        "script_path": "train_native.py",
        "out_dir": str(tmp_path / "out"),
        "optional_args": {"set_epoch": True, "print_rand": False},
        "local": {"device": "cpu", "tpu": {"num_chips": 4}},
        "training": {
            "model": "toy_mlp",
            "dataset": "cifar10",
            "data_root": data_root,
            "train_batch_size": 4,
            "test_batch_size": 4,
            "learning_rate": 0.01,
            "num_epochs": 2,
            "checkpoint_epoch": 1,
            "image_size": None,
            "seed": 0,
            "mode": "shard_map",
            "sync_bn": False,
        },
    }
    sf = tmp_path / "s.yaml"
    sf.write_text(yaml.dump(settings))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("TPUDDP_DATA", None)  # the settings' data_root must be what loads
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
    # the fixture data actually loaded: no synthetic-fallback warning
    combined = proc.stdout + proc.stderr
    assert "synthetic" not in combined.lower()
    assert "Epoch 1/2" in proc.stdout and "Epoch 2/2" in proc.stdout
    assert "Test Accuracy" in proc.stdout
    assert os.path.exists(tmp_path / "out" / "ckpt_0.npz")
    assert os.path.exists(tmp_path / "out" / "ckpt_1.npz")


@pytest.mark.slow
def test_native_cli_on_cifar_py_fixture(tmp_path):
    data_root = str(tmp_path / "data")
    make_cifar_py_fixture(data_root)
    _run_native_cli(tmp_path, data_root)


@pytest.mark.slow
def test_native_cli_on_cifar_bin_fixture(tmp_path):
    data_root = str(tmp_path / "data")
    make_cifar_bin_fixture(data_root)
    _run_native_cli(tmp_path, data_root)
