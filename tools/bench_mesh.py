#!/usr/bin/env python
"""bench_mesh — the 2-D mesh proving run (ISSUE 14 deliverable).

Trains ``transformer_small`` as a next-token LM on a synthetic token stream
through the REAL epoch driver (``tpuddp.training.loop.run_training_loop``)
in two configurations on the 4-device CPU mesh:

- **TP=2 x DP=2** — the 2-D ``("data", "model")`` mesh: attention heads,
  MLP hidden units, and vocabulary rows sharded 1/2 per chip
  (tpuddp/parallel/tensor.py), gradient collectives over the data axis
  only, schema-v8 history with the ``mesh`` block;
- **DP=4** — the pure data-parallel reference at the SAME global batch.

It then asserts, in-process:

- **loss-trajectory parity**: per-epoch train losses of the two runs agree
  within a float-reduction tolerance (the TP row-split contractions change
  only the summation order of each matmul, never the math — asserted
  |Δloss| <= max(2e-3, 1e-3·|loss|) every epoch);
- **per-chip parameter-byte cut**: the TP run's per-chip parameter bytes
  land under the replicated footprint by ~the sharded fraction of the
  attention+MLP+vocab weights.

The emitted bench payload (``--out``) is the ``MULTICHIP_r06.json`` row
format: both configs with ms_per_step + samples_per_sec_per_chip (token
steps), plus ``param_bytes_per_chip`` / ``param_bytes_cut`` on the TP row.
``tools/bench_trend.py`` ingests the MULTICHIP family; the full gate's mesh
leg runs this with ``--quick`` and re-validates the history independently.

Usage:
    python tools/bench_mesh.py --out MULTICHIP_r06.json [--history-dir DIR]
                               [--quick] [--epochs N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# the proving run is a CPU-mesh artifact: pin the 4-device world BEFORE jax
# initializes (mirrors tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()
os.environ.setdefault("TPUDDP_BACKEND", "cpu")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


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


def run_one(tag: str, data: int, model_width: int, *, history_dir, epochs,
            n_batches, global_batch, vocab, seq_len, seed=0):
    """One training run through the real epoch driver; returns the per-epoch
    losses, wall-clock rate, and the wrap's accounting."""
    from tpuddp import nn, optim
    from tpuddp import config as cfg_lib
    from tpuddp.models import load_model
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training.loop import run_training_loop

    mesh = cfg_lib.mesh_from({"data": data, "model": model_width}, data * model_width)
    model = load_model("transformer_small", num_classes=vocab, max_seq_len=seq_len)
    ddp = DistributedDataParallel(
        model, optim.Adam(lr=1e-3), nn.CrossEntropyLoss(), mesh=mesh,
    )
    state = ddp.init_state(
        jax.random.PRNGKey(seed), jnp.zeros((1, seq_len), jnp.int32)
    )
    train = TokenLMLoader(vocab, seq_len, global_batch, n_batches, seed=seed)
    test = TokenLMLoader(vocab, seq_len, global_batch, max(2, n_batches // 4),
                         seed=seed + 1)
    out_dir = os.path.join(history_dir, tag) if history_dir else None
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    t0 = time.perf_counter()
    state, history = run_training_loop(
        ddp, state, train, test, out_dir,
        num_epochs=epochs, checkpoint_epoch=max(1, epochs - 1),
        set_epoch=True, scan_steps=min(4, n_batches), per_replica_log=False,
        run_meta={"model": "transformer_small", "dataset": "synthetic_tokens"},
        log=lambda *a, **k: None,
    )
    wall = time.perf_counter() - t0
    steps = epochs * n_batches
    tokens = steps * global_batch * seq_len
    from tpuddp.parallel import tensor as tp_lib

    if ddp.model_size > 1:
        tp_params = jax.tree_util.tree_map(np.asarray, state.params)
        per_chip = tp_lib.per_chip_param_bytes(
            tp_params, ddp.tp_param_specs, ddp.model_size
        )
        full = sum(
            int(np.prod(np.shape(l))) * 4
            for l in jax.tree_util.tree_leaves(tp_params)
        )
    else:
        full = sum(
            int(np.prod(np.shape(l))) * 4
            for l in jax.tree_util.tree_leaves(state.params)
        )
        per_chip = full
    return {
        "tag": tag,
        "losses": [h["train_loss"] for h in history],
        "wall_s": wall,
        "ms_per_step": 1000.0 * wall / steps,
        "tokens_per_sec": tokens / wall,
        "samples_per_sec_per_chip": (steps * global_batch) / wall / (data * model_width),
        "param_bytes_per_chip": per_chip,
        "param_bytes_full": full,
        "grad_comm_bytes_per_step": ddp.grad_comm_bytes_per_step,
        "out_dir": out_dir,
        "data": data,
        "model": model_width,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None, help="bench payload path")
    ap.add_argument("--history-dir", default=None,
                    help="keep the runs' history.jsonl under this dir")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="small corpus (the gate's setting)")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if len(devs) < 4:
        print(f"bench_mesh: needs 4 devices, found {len(devs)}", file=sys.stderr)
        return 2
    vocab, seq_len = 64, 32
    n_batches = 4 if args.quick else 8
    global_batch = 8
    epochs = max(2, args.epochs if not args.quick else 2)

    import tempfile

    history_dir = args.history_dir or tempfile.mkdtemp(prefix="tpuddp_mesh_")
    common = dict(
        history_dir=history_dir, epochs=epochs, n_batches=n_batches,
        global_batch=global_batch, vocab=vocab, seq_len=seq_len,
    )
    # --quick rows are correctness probes on a compile-dominated corpus, not
    # perf measurements: a distinct row name keeps bench_trend from judging
    # them against the committed full-size MULTICHIP rows
    suffix = "_quick" if args.quick else ""
    tp = run_one(f"transformer_small_tp2xdp2{suffix}", 2, 2, **common)
    dp = run_one(f"transformer_small_dp4{suffix}", 4, 1, **common)

    # ---- loss-trajectory parity at matched global batch -------------------
    worst = 0.0
    for e, (lt, ld) in enumerate(zip(tp["losses"], dp["losses"])):
        tol = max(2e-3, 1e-3 * abs(ld))
        worst = max(worst, abs(lt - ld))
        if abs(lt - ld) > tol:
            print(
                f"bench_mesh: PARITY FAIL epoch {e}: tp {lt:.6f} vs dp "
                f"{ld:.6f} (tol {tol:.1e})", file=sys.stderr,
            )
            return 1
    # ---- per-chip parameter-byte cut --------------------------------------
    cut = 1.0 - tp["param_bytes_per_chip"] / tp["param_bytes_full"]
    # attention+MLP+vocab weights halve at TP=2; LN/bias/pos stay replicated
    # — on transformer_small the sharded fraction is ~97% of all parameters,
    # so the per-chip footprint must land well under 60% of the full copy
    if tp["param_bytes_per_chip"] >= 0.6 * tp["param_bytes_full"]:
        print(
            f"bench_mesh: per-chip cut too small: {cut * 100:.1f}%",
            file=sys.stderr,
        )
        return 1

    payload = {
        "metric": "tokens_per_sec",
        "value": tp["tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": tp["tokens_per_sec"] / dp["tokens_per_sec"],
        "device": devs[0].device_kind,
        "note": (
            "2-D (data, model) mesh proving run: transformer_small LM, "
            "TP=2xDP=2 vs pure DP=4 at matched global batch; loss parity "
            f"worst |d|={worst:.2e}; per-chip param bytes cut "
            f"{cut * 100:.1f}% (attention+MLP+vocab sharded 1/2)"
        ),
        "configs": {
            tp["tag"]: {
                "ms_per_step": tp["ms_per_step"],
                "tokens_per_sec": tp["tokens_per_sec"],
                "data": tp["data"], "model": tp["model"],
                "param_bytes_per_chip": tp["param_bytes_per_chip"],
                "param_bytes_full": tp["param_bytes_full"],
                "param_bytes_cut": cut,
                "grad_comm_bytes_per_step": tp["grad_comm_bytes_per_step"],
                "final_train_loss": tp["losses"][-1],
            },
            dp["tag"]: {
                "ms_per_step": dp["ms_per_step"],
                "tokens_per_sec": dp["tokens_per_sec"],
                "data": dp["data"], "model": dp["model"],
                "param_bytes_per_chip": dp["param_bytes_per_chip"],
                "final_train_loss": dp["losses"][-1],
            },
        },
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2, allow_nan=False)
            f.write("\n")
    # the parseable-summary contract: the LAST stdout line is the payload
    # summary (tools/run_full_gate.py parses it)
    print(json.dumps({
        "ok": True,
        "parity_worst_abs": worst,
        "param_bytes_cut": cut,
        "tp_history": os.path.join(tp["out_dir"], "history.jsonl"),
        "dp_history": os.path.join(dp["out_dir"], "history.jsonl"),
        "tokens_per_sec_tp": tp["tokens_per_sec"],
        "tokens_per_sec_dp": dp["tokens_per_sec"],
    }, allow_nan=False))
    return 0


if __name__ == "__main__":
    from tpuddp.utils import compile_cache

    compile_cache.enable()
    sys.exit(main())
