"""Training worker for the chaos suite (launched by test_chaos.py).

Runs a small native DP training job (toy MLP, synthetic-fallback data, 4
virtual CPU devices) through the full spawn path so the resilience wiring is
live: SIGTERM drain handlers installed, ``TrainingPreempted`` -> exit 75,
``$TPUDDP_FAULT`` injection hooks armed, ``$TPUDDP_AUTO_RESUME`` resume.

Usage: python _chaos_train_worker.py <out_dir> <num_epochs>

``$TPUDDP_CHAOS_TRAINING`` may hold a JSON object of training-config
overrides (e.g. ``{"guard": {"max_consecutive_skips": 0}}``) so chaos
scenarios can arm the numerical guard without a worker per knob.
``$TPUDDP_CHAOS_OBS`` does the same for the ``observability`` block (e.g.
``{"exporter": true}`` to scrape a live chaos run); the defaults (flight
recorder on, exporter off) apply otherwise. ``$TPUDDP_WORLD_SIZE``
overrides the 4-device default world — the elastic chaos matrix (and the
restart supervisor's shrink policy) resumes the same out_dir on a
different world size through the v2 reshard path.
"""

import json
import os
import sys
from functools import partial

out_dir, num_epochs = sys.argv[1], int(sys.argv[2])
world_size = int(os.environ.get("TPUDDP_WORLD_SIZE") or 4)

from tpuddp.parallel.spawn import run_ddp_training  # noqa: E402
from train_native import basic_ddp_training_loop  # noqa: E402

TRAINING = {
    "model": "toy_mlp",
    "dataset": "cifar10",
    "data_root": "/nonexistent",  # forces the zero-egress synthetic fallback
    "train_batch_size": 8,  # per replica: 32-sample global batches
    "test_batch_size": 8,
    "learning_rate": 0.01,
    "num_epochs": num_epochs,
    "checkpoint_epoch": 1,
    "image_size": None,
    "seed": 0,
    "mode": "shard_map",
    "synthetic_n": (256, 64),  # 8 train batch groups per epoch
}
TRAINING.update(json.loads(os.environ.get("TPUDDP_CHAOS_TRAINING") or "{}"))
OBSERVABILITY = json.loads(os.environ.get("TPUDDP_CHAOS_OBS") or "null")
# 2-D mesh override (e.g. '{"data": 2, "model": 2}') for ad-hoc chaos
# scenarios on a factored mesh. The tensor-parallel legs run
# tests/_chaos_tp_worker.py (a token workload — this worker's CNN data
# cannot feed a tensor-parallel transformer); this env hook exists so future
# chaos legs can pin the mesh shape without a worker per knob.
PARALLEL = json.loads(os.environ.get("TPUDDP_CHAOS_PARALLEL") or "null")

run_ddp_training(
    partial(
        basic_ddp_training_loop, training=TRAINING,
        observability=OBSERVABILITY, parallel=PARALLEL,
    ),
    world_size=world_size,
    save_dir=out_dir,
    optional_args={"set_epoch": True, "print_rand": False},
    backend="cpu",
)
