"""THE north-star metric (BASELINE.json): loss-curve parity between tpuddp
data-parallel training and the reference stack's real DDP loop — 2 torch
processes over gloo (the reference's own CPU backend rung,
multi-GPU-training-torch.py:36-37), same data, same initial weights, same
hyperparameters, compared epoch by epoch."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EPOCHS, BATCH, LR = 4, 16, 1e-3
N, FEATURES = 256, 192


@pytest.mark.slow
def test_loss_curve_parity_vs_torch_ddp(tmp_path, cpu_devices):
    import jax
    import jax.numpy as jnp
    import torch

    from tpuddp import nn as tnn
    from tpuddp import optim
    from tpuddp.data import ShardedDataLoader
    from tpuddp.parallel import make_mesh
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training.step import accumulate_metrics, finalize_metrics

    rng = np.random.RandomState(3)
    labels = rng.randint(0, 10, N).astype(np.int64)
    means = rng.randn(10, FEATURES).astype(np.float32)
    x = (means[labels] + 0.5 * rng.randn(N, FEATURES)).astype(np.float32)
    data_path = tmp_path / "data.npz"
    np.savez(data_path, x=x, y=labels)

    # --- reference run: 2-process torch DDP over gloo ---
    out_path = tmp_path / "torch_curve.json"
    env = dict(os.environ)
    env["MASTER_PORT"] = "29517"
    env["JAX_PLATFORMS"] = "cpu"  # torch-only workers
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_torch_ddp_worker.py"),
         str(data_path), str(out_path), str(EPOCHS), str(BATCH), str(LR)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    torch_curve = json.load(open(out_path))["train_loss"]

    # --- tpuddp run: 2-device DP mesh, identical init/hparams/data split ---
    class ArrayDataset:
        def __init__(self, images, labels):
            self.images, self.labels = images, labels.astype(np.int32)

        def __len__(self):
            return len(self.labels)

        def get_batch(self, idx):
            i = np.asarray(idx)
            return self.images[i], self.labels[i]

    mesh = make_mesh(cpu_devices[:2])
    sd = torch.load(str(out_path) + ".init.pt", weights_only=True)

    def tpuddp_curve(weight_update_sharding: bool):
        model = tnn.Sequential(
            tnn.Linear(256), tnn.ReLU(), tnn.Linear(128), tnn.ReLU(), tnn.Linear(10)
        )
        ddp = DistributedDataParallel(
            model, optim.Adam(LR), tnn.CrossEntropyLoss(), mesh=mesh,
            weight_update_sharding=weight_update_sharding,
        )
        state = ddp.init_state(jax.random.key(0), jnp.zeros((1, FEATURES)))

        # graft the torch run's initial weights (Linear: (out,in) -> (in,out))
        params = list(state.params)
        for layer_idx, torch_idx in [(0, 0), (2, 2), (4, 4)]:
            params[layer_idx] = {
                "weight": jnp.asarray(sd[f"{torch_idx}.weight"].numpy().T),
                "bias": jnp.asarray(sd[f"{torch_idx}.bias"].numpy()),
            }
        state = state.__class__(
            params=tuple(params),
            model_state=state.model_state,
            opt_state=state.opt_state,
            step=state.step,
            rng=state.rng,
        )

        loader = ShardedDataLoader(ArrayDataset(x, labels), BATCH, mesh, shuffle=False)
        curve = []
        for _ in range(EPOCHS):
            acc = None
            for host_batch in loader:
                state, m = ddp.train_step(state, ddp.shard(host_batch))
                acc = accumulate_metrics(acc, m)
            final = finalize_metrics(acc)
            curve.append(final["loss_sum"] / final["n"])
        return curve

    # the north star: loss-curve parity with the reference's DDP baseline —
    # for BOTH optimizer layouts (replicated update AND weight-update-sharded)
    for wus in (False, True):
        ours_curve = tpuddp_curve(wus)
        np.testing.assert_allclose(ours_curve, torch_curve, rtol=2e-3)
        # and the model actually learned
        assert ours_curve[-1] < ours_curve[0] * 0.7
