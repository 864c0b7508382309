"""Explicit (native) DP training entrypoint — the tpuddp analog of the
reference's ``multi-GPU-training-torch.py`` (call stack SURVEY.md §3.1).

Same shape, TPU-native pieces:

    setup/process group        -> tpuddp.parallel.backend (local.device, required)
    mp.spawn per-GPU workers   -> one process drives all local chips
                                  (tpuddp.parallel.spawn.run_ddp_training)
    set_seed_based_on_rank     -> tpuddp.seeding
    DistributedSampler loaders -> ShardedDataLoader (per-replica samplers)
    DDP(model) + NCCL allreduce-> DistributedDataParallel (shard_map + pmean)
    run_training_loop          -> tpuddp.training.loop (same per-epoch flow)

Usage parity:  python train_native.py --settings_file local_settings.yaml
"""

from __future__ import annotations

import argparse
import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import config as cfg_lib
from tpuddp import nn, observability as obs, seeding
from tpuddp.data import (
    PrefetchLoader,
    ShardedDataLoader,
    compute_dtype_for,
    flip_for,
    load_datasets_for,
    norm_stats_for,
)
from tpuddp.data.transforms import make_eval_transform, make_train_augment
from tpuddp.models import load_model
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.parallel.spawn import run_ddp_training
from tpuddp.training.loop import run_training_loop
from tpuddp.utils import compile_cache

logging.basicConfig(level=logging.INFO, format="%(message)s")


def basic_ddp_training_loop(
    rank, world_size, save_dir, optional_args, training=None, observability=None,
    parallel=None,
):
    """Per-process worker — parity with the reference's
    ``basic_DDP_training_loop`` (multi-GPU-training-torch.py:228-266). The
    process group is already up (run_ddp_training called setup)."""
    print(f"Running DDP training on process {rank} ({world_size}-chip world).")
    training = training or cfg_lib.TRAINING_DEFAULTS
    # Tune overlay ($TPUDDP_TUNE_OVERLAY) applies here too so workers handed
    # a pre-resolved training dict (fleet relaunch, chaos harness) pick it
    # up; re-application after training_config is an idempotent merge.
    training, _tune_prov = cfg_lib.apply_tune_overlay(training, section="training")

    # Seeds per rank (reference :234); the data permutation seed stays shared
    # across ranks (DistributedSampler contract) and independent of model seed.
    key, _base_seed = seeding.set_seed_based_on_rank(rank, training.get("seed"))

    # Mesh: the ``parallel`` block factors the world into the 2-D
    # ("data", "model") grid (config.mesh_from; model=1 is exactly today's
    # flat mesh), and comm_topology: hierarchical factors the data axis
    # ("host", "local") so the comm hooks can split the intra-/inter-host
    # hops (parallel/comm.py). Bad factorizations refuse at mesh_from.
    comm_topology = str(training.get("comm_topology") or "flat")
    mesh = cfg_lib.mesh_from(parallel, world_size, comm_topology=comm_topology)

    # Data + model (reference :237-238); synthetic fallback keeps the tutorial
    # runnable with no dataset staged (zero-egress environments).
    train_ds, test_ds = load_datasets_for(training)
    train_loader = ShardedDataLoader(
        train_ds, training["train_batch_size"], mesh, shuffle=True
    )
    test_loader = ShardedDataLoader(
        test_ds, training["test_batch_size"], mesh, shuffle=True
    )
    # async pipeline (training.pipeline, tpuddp/training/pipeline.py):
    # staged-chunk depth + host worker count + the synchronous A/B mode
    from tpuddp.training.pipeline import resolve_pipeline

    pipeline = resolve_pipeline(training.get("pipeline"))
    if training.get("prefetch", True) and pipeline.host_workers > 0:
        # overlap host batch assembly with device compute (the reference's
        # num_workers analog, multi-GPU-training-torch.py:90-98); workers > 1
        # parallelize assembly itself over the loaders' batch plan
        train_loader = PrefetchLoader(train_loader, workers=pipeline.host_workers)
        test_loader = PrefetchLoader(test_loader, workers=pipeline.host_workers)

    # Device-side transform pipeline (replaces data_and_toy_model.py:13-29);
    # normalization stats follow the dataset, and flip is a config knob
    # (digits are not flip-invariant, unlike CIFAR photos).
    size = training.get("image_size")
    mean, std = norm_stats_for(training)
    cdtype = compute_dtype_for(training)
    sample, _ = train_ds[0]
    if np.ndim(sample) != 3:
        # the data says what it is: a sample that is not (H, W, C) is a row of
        # token ids, and the image augment/normalize pipeline does not apply
        # (the TP wrap refuses it outright)
        augment = eval_transform = None
    else:
        augment = make_train_augment(
            size=size, flip=flip_for(training), mean=mean, std=std,
            compute_dtype=cdtype,
        )
        eval_transform = make_eval_transform(
            size=size, mean=mean, std=std, compute_dtype=cdtype
        )

    # Model, optionally fine-tuning from a torch checkpoint on disk — the
    # reference's central pretrained-AlexNet workflow (data_and_toy_model.py:41-45).
    init_params = init_mstate = None
    if training.get("pretrained_path"):
        from tpuddp.models.torch_import import pretrained_from_config

        model, init_params, init_mstate = pretrained_from_config(training, key)
        print(
            f"Loaded pretrained {training['model']} weights from "
            f"{training['pretrained_path']}."
        )
    else:
        model = load_model(training["model"], cfg_lib.num_classes_from(training))
    if training.get("sync_bn"):
        nn.convert_sync_batchnorm(model)

    # Loss + optimizer (reference :248-249). training.optimizer selects the
    # update rule (adam default; lars/lamb for large-batch trust-ratio
    # scaling, sgdw as their decay-only baseline — config.optimizer_from,
    # shared with the managed entrypoint). optimizer_state_dtype: bfloat16
    # stores Adam m/v in bf16 (f32 math, f32 master params) — halves the
    # optimizer HBM traffic that dominates FC-heavy steps (BASELINE.md).
    criterion = nn.CrossEntropyLoss()
    optimizer = cfg_lib.optimizer_from(training)

    # The DDP wrap (reference :245): builds the shard_map'd pmean train step.
    # weight_update_sharding swaps the allreduce+replicated-update for
    # reduce-scatter + 1/N-shard update + all-gather (ZeRO-1 on ICI).
    clip = training.get("clip_grad_norm")
    ddp = DistributedDataParallel(
        model,
        optimizer,
        criterion,
        mesh=mesh,
        mode=training.get("mode", "shard_map"),
        augment=augment,
        eval_transform=eval_transform,
        remat=bool(training.get("remat", False)),
        clip_grad_norm=float(clip) if clip is not None else None,
        weight_update_sharding=bool(training.get("weight_update_sharding", False)),
        # effective-batch control (reference multi-GPU-training-torch.py:88's
        # batch-size knob): one optimizer update per A micro-batches, fused
        # into the scan step — same knob name as the managed path
        grad_accumulation=int(training.get("gradient_accumulation_steps") or 1),
        # gradient-comm hook (torch DDP comm-hook analog, parallel/comm.py):
        # bf16/bf16_ef halve the gradient interconnect bytes per step;
        # int8_ef cuts ~75%, topk_ef ~87.5% at density 0.1 (error-feedback
        # residual carries what compression dropped)
        comm_hook=str(training.get("comm_hook") or "none"),
        bucket_cap_mb=float(training.get("bucket_cap_mb") or 25),
        comm_topology=comm_topology,
        topk_density=float(training.get("topk_density") or 0.1),
        # numerical guard (resilience/guard.py): non-finite-update firewall +
        # desync auditor + rollback-to-last-good; off (exact legacy step)
        # unless the training.guard block asks for it
        guard=training.get("guard"),
    )
    if augment is None:
        init_sample = jnp.asarray(sample)[None]
    else:
        in_hw = size if size else train_ds.images.shape[1]
        init_sample = jnp.zeros((1, in_hw, in_hw, 3))
    state = ddp.init_state(key, init_sample, params=init_params, model_state=init_mstate)

    # Resume path (the reference only documents loading, README.md:51-52):
    # training.resume: true restores the newest ckpt_{epoch}.npz in out_dir —
    # routed through the epoch driver's auto-resume restore (one restore
    # implementation), which also reshards elastically onto THIS mesh and
    # lands the topology-change event rows in history.jsonl.
    run_training_loop(
        ddp,
        state,
        train_loader,
        test_loader,
        save_dir,
        num_epochs=training["num_epochs"],
        checkpoint_epoch=training["checkpoint_epoch"],
        set_epoch=optional_args.get("set_epoch", True),
        print_rand=optional_args.get("print_rand", False),
        data_probe_every=100,  # shard-disjointness probe (reference :112-115)
        scan_steps=training.get("scan_steps", "auto"),
        per_replica_log=True,  # reference's per-device loss lines (:186-191)
        # resilience knobs: auto_resume restores the newest INTACT checkpoint
        # (training.resume rides the same path; also forced by
        # $TPUDDP_AUTO_RESUME=1, the scheduler-requeue contract);
        # keep_last bounds checkpoint disk on long runs
        auto_resume=bool(training.get("auto_resume") or training.get("resume")),
        # elastic mesh failover: opt into re-shaping a checkpoint written on
        # a different (data, model) mesh at restore (training/reshard.py)
        reshard_on_mismatch=bool(training.get("reshard_on_mismatch")),
        keep_last=(
            int(training["keep_last"]) if training.get("keep_last") else None
        ),
        # telemetry (tpuddp.observability): per-window step_stats cadence +
        # run provenance for the history.jsonl run_meta header
        step_stats_every=int(training.get("step_stats_every") or 0),
        pipeline=pipeline,
        # live telemetry plane (observability block): opt-in /metrics
        # exporter, pod aggregation + straggler detection, flight recorder
        observability=observability,
        # async step-granular checkpointing (training/snapshot.py): step
        # snapshots with v4 data cursors for exact mid-epoch resume
        snapshot=training.get("snapshot"),
        run_meta={
            "config_hash": obs.config_hash(training),
            "model": training.get("model"),
            "dataset": training.get("dataset"),
        },
    )


if __name__ == "__main__":
    compile_cache.enable()
    parser = argparse.ArgumentParser(
        description="tpuddp explicit-API DP training (ShardedDataLoader + "
        "DistributedDataParallel over the XLA mesh backend).",
    )
    parser.add_argument(
        "--settings_file",
        type=str,
        required=True,
        help="YAML settings (see local_settings.yaml for the schema: out_dir, "
        "local.{device,tpu}, optional_args, training overrides).",
    )
    args = parser.parse_args()

    settings = cfg_lib.load_settings(args.settings_file)
    out_dir = cfg_lib.prepare_out_dir(settings, args.settings_file)
    world_size = cfg_lib.world_size_from(settings)
    optional_args = cfg_lib.optional_args_from(settings)
    training = cfg_lib.training_config(settings)
    # multi-host rendezvous (local.rendezvous / TPUDDP_* env) — the analog of
    # the reference's MASTER_ADDR:MASTER_PORT (multi-GPU-training-torch.py:30-31)
    rendezvous = cfg_lib.rendezvous_from(settings)

    run_ddp_training(
        partial(
            basic_ddp_training_loop,
            training=training,
            observability=cfg_lib.observability_config(settings),
            parallel=cfg_lib.parallel_config(settings),
        ),
        world_size,
        out_dir,
        optional_args,
        backend=cfg_lib.device_from(settings),
        **rendezvous,
    )
