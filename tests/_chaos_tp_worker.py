"""Tensor-parallel training worker for the chaos suite (launched by
test_chaos.py — the ISSUE 16 elastic-mesh-failover legs).

Runs a small token-LM job (transformer_small, synthetic next-token batches)
on a 2-D ``(data, model)`` mesh through the full spawn path, so the
resilience wiring is live exactly like the DP worker: SIGTERM drain -> exit
75, ``$TPUDDP_FAULT`` injection, ``$TPUDDP_AUTO_RESUME`` resume — and, new
here, ``reshard_on_mismatch`` so a relaunch on a DIFFERENT mesh shape
reshards the emergency checkpoint instead of refusing it.

Usage: python _chaos_tp_worker.py <out_dir> <num_epochs>

Env levers (the supervisor/fleet relaunch contract):

- ``$TPUDDP_WORLD_SIZE``  — total chips (default 4);
- ``$TPUDDP_MODEL_SIZE``  — tensor-parallel width (default 2; model=1 is a
  pure-DP run of the same workload — the cross-shape parity baseline);
- ``$TPUDDP_CHAOS_TRAINING`` — JSON training-config overrides (e.g.
  ``{"comm_hook": "bf16_ef"}``; the default is the f32 ``none`` hook so the
  cross-shape loss-parity legs compare float-reassociation-only drift).

The loader keeps the matched-global-batch contract: the same seed
yields the SAME global batches on any mesh shape, which is what makes
"resumed at a different shape, landed the same loss trajectory" a testable
claim rather than a vibe.
"""

import json
import os
import sys

import numpy as np

out_dir, num_epochs = sys.argv[1], int(sys.argv[2])
world_size = int(os.environ.get("TPUDDP_WORLD_SIZE") or 4)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tpuddp.parallel.spawn import run_ddp_training  # noqa: E402

CFG = {
    "vocab": 64,
    "seq_len": 32,
    "global_batch": 8,
    "n_batches": 4,
    "seed": 0,
    "learning_rate": 1e-3,
    "comm_hook": "none",  # f32 wire: parity legs compare pure reassociation
    "checkpoint_epoch": 1,
}
CFG.update(json.loads(os.environ.get("TPUDDP_CHAOS_TRAINING") or "{}"))
PARALLEL = json.loads(os.environ.get("TPUDDP_CHAOS_PARALLEL") or "null")
OBSERVABILITY = json.loads(os.environ.get("TPUDDP_CHAOS_OBS") or "null")


class TokenLMLoader:
    """Synthetic next-token LM loader with the epoch-driver loader protocol
    (len / set_epoch / make_batch_plan / iter): a fixed token corpus sampled
    per epoch into ``(tokens, shifted targets, weights)`` batches. The same
    seed yields the same global batches on ANY mesh shape — the matched-
    global-batch contract the DP-vs-TP parity comparison needs."""

    def __init__(self, vocab: int, seq_len: int, global_batch: int,
                 n_batches: int, seed: int = 0):
        self.vocab = vocab
        self.seq_len = seq_len
        self.global_batch = global_batch
        self.n_batches = n_batches
        self.seed = seed
        self.epoch = 0
        self.batch_nbytes = global_batch * seq_len * 4

    def set_epoch(self, epoch: int) -> None:
        self.epoch = int(epoch)

    def __len__(self) -> int:
        return self.n_batches

    def make_batch_plan(self):
        rng = np.random.default_rng((self.seed, self.epoch))
        # one contiguous token stream per epoch; batches slice it
        data = rng.integers(
            0, self.vocab,
            (self.n_batches, self.global_batch, self.seq_len + 1),
        ).astype(np.int32)

        def fetch(s: int):
            chunk = data[s]
            x = chunk[:, :-1]
            y = chunk[:, 1:].astype(np.int32)
            w = np.ones(x.shape, np.float32)
            return x, y, w

        return self.n_batches, fetch

    def __iter__(self):
        steps, fetch = self.make_batch_plan()
        for s in range(steps):
            yield fetch(s)


def tp_training_loop(rank, world, save_dir, optional_args):
    import jax
    import jax.numpy as jnp

    from tpuddp import config as cfg_lib
    from tpuddp import nn, optim
    from tpuddp.models import load_model
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training.loop import run_training_loop

    # resolve_parallel honors $TPUDDP_MODEL_SIZE (data falls back to
    # "auto" = world // model) — the exact lever the supervisor/fleet
    # relaunch uses; default mesh when neither env nor block pins it: TP=2
    parallel = PARALLEL
    if parallel is None and not os.environ.get("TPUDDP_MODEL_SIZE"):
        parallel = {"data": "auto", "model": 2}
    mesh = cfg_lib.mesh_from(parallel, world)
    print(f"TP chaos worker: rank {rank}, mesh shape "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    model = load_model(
        "transformer_small", num_classes=CFG["vocab"],
        max_seq_len=CFG["seq_len"],
    )
    ddp = DistributedDataParallel(
        model, optim.Adam(lr=CFG["learning_rate"]), nn.CrossEntropyLoss(),
        mesh=mesh, comm_hook=str(CFG["comm_hook"]),
    )
    state = ddp.init_state(
        jax.random.PRNGKey(CFG["seed"]),
        jnp.zeros((1, CFG["seq_len"]), jnp.int32),
    )
    train = TokenLMLoader(
        CFG["vocab"], CFG["seq_len"], CFG["global_batch"], CFG["n_batches"],
        seed=CFG["seed"],
    )
    test = TokenLMLoader(
        CFG["vocab"], CFG["seq_len"], CFG["global_batch"],
        max(2, CFG["n_batches"] // 2), seed=CFG["seed"] + 1,
    )
    run_training_loop(
        ddp, state, train, test, save_dir,
        num_epochs=num_epochs,
        checkpoint_epoch=CFG["checkpoint_epoch"],
        set_epoch=True,
        scan_steps=min(4, CFG["n_batches"]),
        per_replica_log=False,
        auto_resume=bool(os.environ.get("TPUDDP_AUTO_RESUME")),
        # the leg under test: a checkpoint from ANOTHER (data, model) shape
        # reshards onto this mesh at restore instead of refusing
        reshard_on_mismatch=True,
        observability=OBSERVABILITY,
        run_meta={"model": "transformer_small", "dataset": "synthetic_tokens"},
    )


run_ddp_training(
    tp_training_loop,
    world_size=world_size,
    save_dir=out_dir,
    optional_args={},
    backend="cpu",
)
