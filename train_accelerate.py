"""Managed training entrypoint — the tpuddp analog of the reference's
``multi-GPU-training-accelerate.py`` (call stack SURVEY.md §3.2): the
``Accelerator`` hides process topology, sharding, and gradient sync, and
routes through the same XLA backend as train_native.py.

Deliberate reference-parity behaviors (quirk Q3, SURVEY.md §3.5): the test
loader is NOT prepared, so eval runs the full test set on every process with
per-batch-mean (not sample-weighted) averaging and no cross-process reduction
— exactly like the reference (multi-GPU-training-accelerate.py:60-75,129-131).

Usage parity:  python train_accelerate.py --settings_file local_settings.yaml
"""

from __future__ import annotations

import argparse
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import config as cfg_lib
from tpuddp import nn
from tpuddp.accelerate import Accelerator
from tpuddp.resilience import faults
from tpuddp.resilience.guard import ReplicaDesync
from tpuddp.resilience.preemption import (
    EXIT_DESYNC,
    EXIT_PREEMPTED,
    TrainingPreempted,
    auto_resume_requested,
    install_preemption_handler,
    preemption_requested,
)
from tpuddp.data import (
    DataLoader,
    compute_dtype_for,
    flip_for,
    load_datasets_for,
    norm_stats_for,
)
from tpuddp.data.transforms import make_eval_transform, make_train_augment
from tpuddp.parallel.mesh import data_mesh
from tpuddp.utils import compile_cache

logging.basicConfig(level=logging.INFO, format="%(message)s")


def setup_dataloaders(training):
    """Plain, distribution-unaware loaders (reference :22-36); prepare() later
    re-creates the train loader sharded."""
    train_dataset, test_dataset = load_datasets_for(training)
    train_loader = DataLoader(
        train_dataset, batch_size=training["train_batch_size"], shuffle=True
    )
    test_loader = DataLoader(test_dataset, batch_size=training["test_batch_size"])
    return train_loader, test_loader


def train(
    model, train_loader, criterion, optimizer, accelerator, augment,
    tel=None, start_batch=0, carried=None, poll=None, progress=None,
    total_batches=None,
):
    """One training epoch. Returns ``(mean_batch_loss, samples_seen)`` —
    the weighted sample count feeds the history.jsonl throughput fields.
    ``tel`` (observability.RunTelemetry) brackets each optimizer step with
    its host-side timing/profiling hooks; under fuse_steps the laps measure
    dispatch rate (the queue flushes every K steps), never forcing a flush.

    Step-granular resume/drain (training/snapshot.py, the v4 cursor): the
    caller hands a tail loader plus ``start_batch`` (the epoch offset its
    batches start at), ``carried`` ({loss_total, n_seen} from the cursor's
    partial accumulator — seeds this pass's sums so the epoch row equals the
    uninterrupted run's), and ``total_batches`` (the FULL epoch's batch
    count, the mean-loss denominator). ``poll`` is checked once per
    completed gradient-accumulation cycle (never mid-cycle — save_state
    refuses a partial cycle); when it returns True the pass stops and
    ``progress`` (a caller-owned dict) records ``interrupted=True`` plus the
    epoch step / loss total / samples so the drain can write an
    exactly-resumable step snapshot.

    Deferred readback (the async pipeline, tpuddp/training/pipeline.py): the
    per-batch ``loss.item()`` host sync the reference pays (quirk Q5) is
    retired on BOTH metric modes — losses are collected as LazyLoss objects
    and become observable at the end-of-epoch drain (or whenever a fuse-queue
    flush materializes them earlier); the loop itself never fences the
    device. ``augment=None`` means augmentation is folded INTO the compiled
    step (``Accelerator(augment=...)``) and raw decoded batches feed
    ``model(...)`` directly — host workers only decode and stack."""
    model.train()
    n_seen = float(carried["n_seen"]) if carried else 0.0
    carried_loss = float(carried["loss_total"]) if carried else None
    interrupted_at = None
    batch_losses = []
    # step-site chaos hook (resilience/faults.py): armed only while an
    # un-fired step fault exists, so normal runs pay nothing per batch
    fault_step = {"i": start_batch} if faults.has_step_fault() else None
    # fuse_steps bookkeeping for the step recorder: an optimizer.step() that
    # merely queues (fuse_steps=K enqueues K-1 of every K) is host-side
    # microseconds, and crediting it as a step would report bookkeeping time
    # as p50 while the Kth lap absorbs K steps of work. Steps accumulate here
    # and are posted as ONE group when the queue has actually drained.
    pend_steps, pend_samples = 0, 0
    from tpuddp.training.pipeline import StallClock, stalled_iter

    stall = StallClock()  # host-blocked time -> step_stats occupancy fields
    # deepest the fuse queue ran since the last posted group — sampled at
    # enqueue time (post time always sees a just-drained queue)
    queue_peak = [0]

    def post_if_flushed(force=False):
        nonlocal pend_steps, pend_samples
        if tel is None or pend_steps == 0:
            return
        if force or not getattr(optimizer, "_queue", None):
            tel.post_dispatch(
                pend_steps, int(pend_samples), host_stall_s=stall.take(),
                inflight_depth=queue_peak[0],
            )
            pend_steps, pend_samples = 0, 0
            queue_peak[0] = 0

    # ONE fresh key per epoch when augmentation runs as its own jitted op
    # (device_augment: false); with in-step augment the key derives from the
    # step rng inside the compiled program and no epoch key is drawn.
    aug_base = accelerator.next_rng_key() if augment is not None else None
    for i, (inputs, labels, weights) in enumerate(
        stalled_iter(train_loader, stall)
    ):
        if fault_step is not None:
            # preempt@step=N / crash@step=N kill the managed run MID-epoch
            # (the step index is the epoch-global batch count, tail-resume
            # aware); the drain poll below runs AFTER the fault so the
            # signal it raised is seen at this same accum-cycle boundary
            faults.maybe_fire("step", step=fault_step["i"])
            fault_step["i"] += 1
        # no .to(device): placement is the backend's job (reference :44 note)
        batch_n = float(np.sum(weights))
        n_seen += batch_n
        optimizer.zero_grad()

        if augment is not None:
            # Flip-augmented inputs (reference transform_train includes
            # RandomHorizontalFlip, data_and_toy_model.py:14-19), keyed off
            # the accelerator's per-process PRNG stream. The fold index is
            # the epoch-global batch position (start_batch + i) so a
            # tail-resumed pass keys each batch exactly as the original did.
            x = augment(aug_base, start_batch + i, jnp.asarray(inputs))
        else:
            x = inputs  # normalize/flip/resize run inside the step program

        if tel is not None:
            # the step about to be enqueued is global_step + pend_steps, and
            # the dispatch that will carry it is the WHOLE queued group — so
            # the window profiler must see pend_steps + 1 upcoming steps, or
            # a TPUDDP_PROFILE_STEPS window falling inside a not-yet-flushed
            # fused group would arm one flush too late and trace the wrong
            # steps
            tel.pre_dispatch(pend_steps + 1)
        # model(...) and criterion(...) record lazily; accelerator.backward
        # runs them as ONE jitted value_and_grad over the sharded global batch,
        # and step() applies the stashed averaged grads.
        outputs = model(x)
        loss = criterion(outputs, labels, weights)
        accelerator.backward(loss)
        optimizer.step()
        pend_steps += 1
        pend_samples += batch_n
        queue_peak[0] = max(
            queue_peak[0], len(getattr(optimizer, "_queue", ()) or ())
        )
        post_if_flushed()

        # collect the LazyLoss; its value materializes when the fuse queue
        # flushes (or at the epoch-end drain) — never a per-batch host sync
        batch_losses.append(loss)
        if (
            poll is not None
            and not getattr(optimizer, "_accum_count", 0)
            and poll()
        ):
            # drain request seen at an accum-cycle boundary: stop HERE —
            # every applied update is a committed step, the cursor names
            # the epoch step the resume continues at
            interrupted_at = start_batch + i + 1
            break
    # a partial gradient-accumulation cycle applies at dataloader end (the
    # HF accumulate() contract) instead of leaking into the next epoch; an
    # interrupted pass stopped AT a cycle boundary, so this is a no-op there
    flush_accum = getattr(optimizer, "flush_accumulation", None)
    if flush_accum is not None and interrupted_at is None:
        flush_accum()
    # the deferred readback drain: sum on device (array-at-a-time over fused
    # flushes), ONE host fetch — per-batch scalar reads cost a dispatch AND a
    # round trip each, and dominated the steps themselves on
    # dispatch-latency-bound runtimes (BASELINE.md's 1,532 samples/s row)
    from tpuddp.accelerate import sum_losses

    running_loss = float(sum_losses(batch_losses, initial=carried_loss))
    # a ragged tail left in the fuse queue was flushed by sum_losses (or by
    # flush_accumulation above): attribute its steps now, post-fence
    post_if_flushed(force=True)
    if progress is not None:
        progress["interrupted"] = interrupted_at is not None
        progress["step"] = (
            interrupted_at if interrupted_at is not None
            else start_batch + len(train_loader)
        )
        progress["loss_total"] = running_loss
        progress["n_seen"] = n_seen
    denom = total_batches if total_batches is not None else len(train_loader)
    return running_loss / denom, n_seen


def transform_host(transform, inputs):
    """Resize+normalize before the managed forward (the managed path keeps the
    torch-like 'model(inputs)' shape, so the transform runs as a separate
    jitted op rather than fused into the step)."""
    return transform(jnp.asarray(inputs))


def evaluate(model, test_loader, criterion, device, transform, deferred=False):
    """Returns ``(mean_batch_loss, accuracy_pct, total_samples)``."""
    model.eval()
    if deferred:
        # scan-fused eval: transform + forward + loss + metric accumulation
        # for K batches per jit dispatch, one host fetch at the end — the
        # managed analog of the native build_eval_scan_step (same quirk-Q3
        # semantics: full test stream on every process, per-batch-mean loss).
        # ONE evaluator per (model, criterion, transform), cached on the
        # model: a fresh instance per epoch would retrace its scan program
        # every epoch.
        from tpuddp.accelerate import FusedEvaluator

        ev = getattr(model, "_tpuddp_fused_eval", None)
        if ev is None or ev.criterion is not criterion or ev.transform is not transform:
            ev = FusedEvaluator(model, criterion, transform=transform)
            model._tpuddp_fused_eval = ev
        for inputs, labels, weights in test_loader:
            ev.add(inputs, labels, weights)
        test_loss, correct, total = ev.finalize()
        accuracy = 100 * correct / total
        return test_loss / len(test_loader), accuracy, total
    correct = 0
    total = 0
    test_loss = 0.0
    for inputs, labels, weights in test_loader:
        inputs = transform_host(transform, inputs)
        outputs = model(inputs)
        loss = criterion(outputs, labels, weights)
        test_loss += loss.item()
        predicted = np.asarray(outputs.argmax(axis=-1))
        mask = weights > 0
        total += int(mask.sum())
        correct += int(((predicted == labels) & mask).sum())
    accuracy = 100 * correct / total
    return test_loss / len(test_loader), accuracy, total


def run_training_loop(
    model,
    train_loader,
    test_loader,
    criterion,
    optimizer,
    save_dir,
    accelerator,
    augment,
    eval_transform,
    num_epochs=20,
    checkpoint_epoch=5,
    deferred_metrics=False,
    start_epoch=0,
    step_stats_every=0,
    run_meta=None,
    pipeline=None,
    observability=None,
    snapshot=None,
):
    # Observability parity with the native epoch driver (training/loop.py):
    # the typed run_meta header opens history.jsonl, epoch rows carry the
    # step recorder's percentile/MFU fields, $TPUDDP_PROFILE traces the
    # first epoch ($TPUDDP_PROFILE_STEPS a step window, SIGUSR1 the next
    # epoch on demand), and $TPUDDP_DEBUG_NANS guards the aggregated losses.
    # The live plane (ISSUE 10) rides too: opt-in /metrics exporter, pod
    # shard publishing + aggregation, crash flight recorder.
    from tpuddp.observability import (
        MetricsWriter,
        RunTelemetry,
        check_finite,
        make_run_meta,
        maybe_start_profiler,
        stamp,
        stop_profiler,
    )
    from tpuddp.observability import aggregate as agg_lib
    from tpuddp.observability import exporter as exp_lib
    from tpuddp.observability import flight as flight_lib
    from tpuddp.resilience import faults
    from tpuddp.resilience import guard as guard_lib
    from tpuddp.resilience import watchdog as wd_lib

    from tpuddp.training.pipeline import resolve_pipeline
    from tpuddp.training import snapshot as snapshot_lib

    obs_cfg = cfg_lib.resolve_observability(observability)
    # training.snapshot (managed flavor): step-granular preemption drains +
    # exact mid-epoch resume. The managed path has no background writer (the
    # fuse queue is its own overlap story) — armed, a drain caught at an
    # accum-cycle boundary writes state_{epoch}_s{step}.npz with the v4 data
    # cursor, and load_state's cursor routes the NEXT run back here to
    # continue that epoch at that step with zero batches replayed.
    snap_cfg = snapshot_lib.resolve_snapshot(snapshot)
    pending_cursor = {"c": getattr(accelerator, "last_restore_cursor", None)}
    flight = None
    if obs_cfg["flight_recorder"] and save_dir is not None:
        flight = flight_lib.install(flight_lib.FlightRecorder(
            save_dir, capacity=int(obs_cfg["flight_capacity"]),
        ))
    metrics_writer = MetricsWriter(save_dir, flight=flight)
    profiling = maybe_start_profiler(save_dir)
    guard_cfg = guard_lib.resolve_guard(getattr(accelerator, "guard", None))
    pipeline = resolve_pipeline(pipeline)
    # elastic resume (ISSUE 7): load_state stashed any topology-change events
    # (the restored state was written on a different world size); the header
    # names the provenance and the typed event rows land right after it
    restore_events = list(getattr(accelerator, "last_restore_events", []) or [])
    meta_extra = {
        "api": "managed",
        "fuse_steps": getattr(accelerator, "fuse_steps", None),
        "grad_accumulation": getattr(
            accelerator, "gradient_accumulation_steps", 1
        ),
        "start_epoch": start_epoch,
        "num_epochs": num_epochs,
        "step_stats_every": int(step_stats_every or 0),
        "pipeline": pipeline.as_dict(),
        # comm compression v2 accounting: the managed emulation's wire is the
        # XLA-inserted f32 psum; density is provenance (it shapes the
        # quantization). The per-update byte counter exists only once the
        # lazily-initialized model/optimizer have materialized (a resumed
        # run); a fresh run learns it at the first step, so the header omits
        # the key rather than recording a null that reads as "no bytes".
        "comm_density": getattr(accelerator, "topk_density", None),
        **(
            {"grad_comm_bytes_per_update": optimizer.grad_comm_bytes_per_step}
            if getattr(optimizer, "grad_comm_bytes_per_step", None) is not None
            else {}
        ),
        **(run_meta or {}),
    }
    topo_change = next(
        (ev for ev in restore_events if ev.get("event") == "topology_change"),
        None,
    )
    if topo_change is not None:
        meta_extra["resumed_from_world"] = topo_change.get("from_world")
    # exporter starts BEFORE the header so the header records the bound port
    exporter = exp_lib.exporter_from_config(obs_cfg, run_dir=save_dir)
    if exporter is not None:
        exporter.start()
    obs_meta = {
        "exporter": exporter.describe() if exporter is not None else False,
        "aggregate": bool(obs_cfg["aggregate"]),
        "straggler_ratio": float(obs_cfg["straggler_ratio"]),
        "straggler_windows": int(obs_cfg["straggler_windows"]),
        "flight_recorder": (
            flight.describe() if flight is not None else False
        ),
    }
    # v10 comm block: the exchange trails the backward pass (XLA-inserted psum)
    _overlap = getattr(accelerator, "comm_overlap_meta", None)
    metrics_writer.write(make_run_meta(
        mesh=getattr(accelerator, "mesh", None),
        comm_hook=getattr(accelerator, "comm_hook", None),
        comm_topology=getattr(accelerator, "comm_topology", "flat"),
        guard=guard_cfg,
        observability=obs_meta,
        comm={"overlap": dict(_overlap)} if _overlap is not None else None,
        # v11 snapshot provenance: the managed flavor (drain-time step
        # snapshots, no background writer); False = epoch-granular only
        snapshot=(
            {**snap_cfg.as_dict(), "mode": "drain"}
            if snap_cfg.enabled else False
        ),
        # v12 tuning provenance: which overlay (if any) shaped this run's
        # knobs; null = advisor off / no overlay
        tuning=cfg_lib.tuning_provenance_from_env(),
        extra=meta_extra,
    ))
    for ev in restore_events:
        metrics_writer.write(stamp("event", ev))
    # managed-path step timing is dispatch-resolution (a mid-epoch device
    # fence would flush the fuse_steps queue and break the fusion it is
    # measuring) — the epoch boundary's loss materialization is the fence
    acc_mesh = getattr(accelerator, "mesh", None)
    tel = RunTelemetry(
        writer=metrics_writer,
        save_dir=save_dir,
        step_stats_every=step_stats_every,
        device_kind=(
            acc_mesh.devices.flat[0].device_kind if acc_mesh is not None else None
        ),
    )
    # live plane: shard publishing + main-process aggregation (multi-host
    # only), exporter sources (native-driver parity)
    aggregator = None
    shard_dir = None
    if obs_cfg["aggregate"] and jax.process_count() > 1:
        shard_dir = wd_lib.heartbeat_dir(save_dir)
        if shard_dir is not None:
            os.makedirs(shard_dir, exist_ok=True)
            if accelerator.is_local_main_process:
                aggregator = agg_lib.PodAggregator(
                    shard_dir,
                    jax.process_count(),
                    writer=metrics_writer,
                    straggler_ratio=float(obs_cfg["straggler_ratio"]),
                    straggler_windows=int(obs_cfg["straggler_windows"]),
                )
    tel.attach_live(
        exporter=exporter,
        aggregator=aggregator,
        shard_dir=shard_dir,
        process_id=jax.process_index(),
    )
    prev_skips = optimizer.skip_counters()[0] if guard_cfg.enabled else 0
    rollback_count = {"n": 0}

    def rollback_to_last_good(epoch, reason):
        """Managed rollback-to-last-good (native-driver parity): restore the
        newest intact ``state_{epoch}.npz`` via load_state — weights,
        moments, EF residual, skip counters, RNG stream — record the event,
        and hand back the epoch to redo (``set_epoch`` re-derives its data
        order). Returns None when no state file exists (caller escalates)."""
        from tpuddp.training import checkpoint as _ckpt

        if _ckpt.latest(save_dir, prefix="state") is None:
            return None
        rollback_count["n"] += 1
        if rollback_count["n"] > guard_cfg.max_rollbacks:
            raise RuntimeError(
                f"guard rollback limit ({guard_cfg.max_rollbacks}) exceeded; "
                f"last trigger: {reason}. The failure recurs after restoring "
                "known-good state — a systematic divergence, not a transient."
            )
        redo_epoch = accelerator.load_state(model, optimizer, save_dir)
        metrics_writer.write(stamp("event", {
            "event": "rollback",
            "epoch": epoch,
            "resume_epoch": redo_epoch,
            "reason": reason,
        }))
        if accelerator.is_local_main_process:
            print(
                f"Guard rollback ({reason}): restored last-good state, "
                f"redoing from epoch {redo_epoch}."
            )
        return redo_epoch
    def drain(last_completed_epoch):
        """Preemption drain (SIGTERM/SIGINT seen at a managed-loop boundary):
        publish the lossless state of the last fully-trained epoch so a
        requeued ``training.resume``/auto-resume run continues after it, then
        raise for the exit-75 conversion in ``__main__``."""
        if last_completed_epoch >= 0:
            accelerator.wait_for_everyone()
            accelerator.save_state(
                model, optimizer, save_dir, epoch=last_completed_epoch
            )
            if accelerator.is_local_main_process:
                print(
                    f"Preempted: emergency state for epoch "
                    f"{last_completed_epoch} saved."
                )
        # the drain's event row, fsync'd before the SIGKILL window closes
        metrics_writer.write(stamp("event", {
            "event": "preempt",
            "epoch": last_completed_epoch + 1,
            "completed": True,
            "step": tel.recorder.global_step,
        }))
        metrics_writer.sync()
        # the exit-75 flight recording (the preempt event rode the tee above)
        if flight is not None:
            flight.note(
                emergency_epoch=last_completed_epoch,
                emergency_step=tel.recorder.global_step,
            )
            flight.dump("preempt")
        raise TrainingPreempted(last_completed_epoch + 1)

    def mid_drain(epoch, prog):
        """Step-granular preemption drain (training.snapshot armed): the
        train pass stopped at an accum-cycle boundary mid-epoch — publish
        ``state_{epoch}_s{step}.npz`` with the v4 data cursor (plan key +
        partial loss/sample totals) so the requeued run continues THIS epoch
        at THIS step with zero batches replayed, retiring the managed
        path's redo-the-epoch resume."""
        step = int(prog["step"])
        accelerator.wait_for_everyone()
        accelerator.save_state(
            model, optimizer, save_dir, epoch=epoch, step=step,
            cursor={
                "plan_key": snapshot_lib.epoch_plan_key(train_loader, epoch),
                "acc": {
                    "loss_total": np.asarray(prog["loss_total"], np.float64),
                    "n_seen": np.asarray(prog["n_seen"], np.float64),
                },
            },
        )
        if accelerator.is_local_main_process:
            print(
                f"Preempted: step snapshot for epoch {epoch} step {step} "
                f"saved (exact resume)."
            )
        metrics_writer.write(stamp("event", {
            "event": "preempt",
            "epoch": epoch,
            "completed": False,
            "step": tel.recorder.global_step,
            "snapshot_step": step,
        }))
        metrics_writer.sync()
        if flight is not None:
            flight.note(
                emergency_epoch=epoch,
                emergency_step=tel.recorder.global_step,
                snapshot_final_step=step,
            )
            flight.dump("preempt")
        raise TrainingPreempted(epoch)

    # per-batch drain polling is single-host-only (one host stopping
    # mid-pass while peers still issue step collectives would wedge the
    # pod) and opt-in via the snapshot block
    poll_cb = (
        preemption_requested
        if snap_cfg.enabled and jax.process_count() == 1 else None
    )

    try:
        epoch = start_epoch
        while epoch < num_epochs:
            # $TPUDDP_FAULT chaos hook (native-driver parity): injected
            # crash/preempt/hang fire at the managed epoch boundary too, so
            # the elastic chaos matrix can kill the Accelerator entrypoint
            # at a deterministic point
            faults.maybe_fire("epoch", epoch=epoch)
            if preemption_requested():
                drain(epoch - 1)
            if (
                guard_cfg.enabled
                and guard_cfg.audit_every_n_epochs
                and (epoch - start_epoch) % guard_cfg.audit_every_n_epochs == 0
                and model._params is not None
            ):
                # periodic cross-replica desync audit (one fingerprint
                # reduction; resilience/guard.py): divergence rolls back to
                # the newest state_{epoch}.npz when configured, else (or
                # with nothing to restore) exits 77 into auto-resume
                bad_leaf = guard_lib.audit_params(accelerator.mesh, model._params)
                if bad_leaf is not None:
                    metrics_writer.write(stamp(
                        "event",
                        {"event": "desync", "epoch": epoch, "leaf": bad_leaf},
                    ))
                    if guard_cfg.on_desync == "rollback":
                        redo = rollback_to_last_good(
                            epoch, f"replica desync at leaf {bad_leaf}"
                        )
                        if redo is not None:
                            epoch = redo
                            prev_skips = optimizer.skip_counters()[0]
                            continue
                    raise guard_lib.ReplicaDesync(
                        bad_leaf, where=f"epoch {epoch} audit"
                    )
            train_loader.set_epoch(epoch)
            # exact mid-epoch resume: a v4 cursor stashed by load_state for
            # THIS epoch skips the already-applied batch-plan prefix and
            # seeds the loss/sample totals it carried — the epoch row comes
            # out equal to the uninterrupted run's. A plan-key mismatch
            # (different sampler config, resharded restore) falls back to
            # the legacy redo-the-epoch path.
            start_batch, carried, pass_loader = 0, None, train_loader
            cur = pending_cursor["c"]
            if cur is not None and int(cur.get("epoch", -1)) == epoch:
                pending_cursor["c"] = None
                expect = snapshot_lib.epoch_plan_key(train_loader, epoch)
                if cur.get("plan_key") == expect:
                    start_batch = int(cur["step"])
                    acc = snapshot_lib.acc_from_cursor(cur)
                    carried = {
                        "loss_total": float(
                            np.asarray(acc.get("loss_total", 0.0))
                        ),
                        "n_seen": float(np.asarray(acc.get("n_seen", 0.0))),
                    }
                    pass_loader = snapshot_lib.EpochTailLoader(
                        train_loader, start_batch
                    )
                    if accelerator.is_local_main_process:
                        print(
                            f"Exact resume: epoch {epoch} continues at step "
                            f"{start_batch} (zero batches replayed)."
                        )
                else:
                    logging.getLogger("tpuddp").warning(
                        "step snapshot plan key mismatch for epoch %d: data "
                        "order changed, redoing the epoch from the restored "
                        "state", epoch,
                    )
            elif cur is not None:
                pending_cursor["c"] = None
            epoch_t0 = time.perf_counter()
            tel.start_epoch(epoch)
            progress = {}
            train_loss, train_samples = train(
                model,
                pass_loader,
                criterion,
                optimizer,
                accelerator,
                augment,
                tel=tel,
                start_batch=start_batch,
                carried=carried,
                poll=poll_cb,
                progress=progress,
                total_batches=len(train_loader),
            )
            # the train pass is done (its end-of-epoch drain materialized
            # the losses — the fence); summarize before eval time can leak
            # in, but keep any SIGUSR1 epoch trace running through evaluation
            step_fields = tel.end_epoch(stop_trace=False)
            if progress.get("interrupted"):
                # the pass stopped at an accum-cycle boundary mid-epoch:
                # write the exactly-resumable step snapshot (never the
                # "epoch done" drain below — its updates are NOT all applied)
                mid_drain(epoch, progress)
            if preemption_requested():
                # the train pass completed, so every update of this epoch is
                # applied — save it as done and lose only the eval metrics
                drain(epoch)
            test_loss, test_accuracy, test_samples = evaluate(
                model,
                test_loader,
                criterion,
                accelerator.device,
                eval_transform,
                deferred=deferred_metrics,
            )
            # the SIGUSR1 'next full epoch' capture includes eval (native
            # parity — an operator tracing a slow eval must see it)
            tel.stop_epoch_trace()
            epoch_time = time.perf_counter() - epoch_t0

            if profiling and epoch == start_epoch:
                stop_profiler()  # trace the first epoch only
                profiling = False

            # epoch summary, gated to one process (reference :96-102)
            if accelerator.is_local_main_process:
                print(
                    f"Epoch {epoch + 1}/{num_epochs}, "
                    f"Train Loss: {train_loss:.4f}, "
                    f"Test Loss: {test_loss:.4f}, "
                    f"Test Accuracy: {test_accuracy:.2f}%"
                )
            # guard skip accounting: one tiny counter fetch per epoch, and
            # a skip is never silent next to a checkpoint
            guard_fields = {}
            consec_skips = 0
            if guard_cfg.enabled:
                total_skips, consec_skips = optimizer.skip_counters()
                guard_fields = {
                    "skipped_steps": total_skips,
                    "skipped_steps_epoch": total_skips - prev_skips,
                }
                prev_skips = total_skips
                if guard_fields["skipped_steps_epoch"] and accelerator.is_local_main_process:
                    print(
                        f"Guard: skipped {guard_fields['skipped_steps_epoch']} "
                        f"non-finite update(s) in epoch {epoch} "
                        f"(total {total_skips})."
                    )

            # live-plane gauges (native-driver parity): last epoch losses +
            # guard skip totals reach /metrics and the published shard
            tel.update_live(
                train_loss=train_loss,
                test_loss=test_loss,
                test_accuracy=test_accuracy,
                skipped_steps=guard_fields.get("skipped_steps", 0),
            )
            if aggregator is not None:
                aggregator.update()
            # native-driver record schema (training/loop.py), written BEFORE
            # the NaN guard so a blown-up epoch still leaves its post-mortem
            # row in history.jsonl (non-finite values land as strict-JSON
            # null, never a bare NaN token)
            metrics_writer.write(stamp("epoch", {
                "epoch": epoch,
                "train_loss": train_loss,
                "test_loss": test_loss,
                "test_accuracy": test_accuracy,
                "train_samples": train_samples,
                "test_samples": test_samples,
                "epoch_time_s": epoch_time,
                "samples_per_sec": (train_samples + test_samples)
                / max(epoch_time, 1e-9),
                **step_fields,
                **guard_fields,
            }))
            if guard_fields.get("skipped_steps_epoch"):
                metrics_writer.write(stamp("event", {
                    "event": "skipped_updates",
                    "epoch": epoch,
                    "count": guard_fields["skipped_steps_epoch"],
                    "total": guard_fields["skipped_steps"],
                }))
            # $TPUDDP_DEBUG_NANS: both losses guarded BEFORE the checkpoint
            # below — a poisoned epoch must never persist its state
            check_finite(train_loss, "train loss")
            if test_samples:
                check_finite(test_loss, "test loss")

            if consec_skips > guard_cfg.max_consecutive_skips:
                # the firewall is skipping updates back to back — training
                # stalled on frozen weights. Roll back to the last saved
                # state, or fail loudly; never finish 0 having silently
                # trained nothing (native-driver parity, training/loop.py).
                redo = rollback_to_last_good(
                    epoch,
                    f"{consec_skips} consecutive non-finite updates skipped",
                )
                if redo is not None:
                    epoch = redo
                    prev_skips = optimizer.skip_counters()[0]
                    continue
                raise FloatingPointError(
                    f"non-finite gradients forced {consec_skips} consecutive "
                    "skipped updates and no saved state exists to roll back "
                    "to (lower checkpoint_epoch to arm rollback)"
                )

            if epoch % checkpoint_epoch == 0:
                # barrier, then a single-writer save of the unwrapped weights
                # (reference :104-108) PLUS the lossless full state (weights +
                # optimizer moments + RNG position) that training.resume
                # restores
                accelerator.wait_for_everyone()
                accelerator.save_model(model, save_dir)
                accelerator.save_state(model, optimizer, save_dir, epoch=epoch)
            epoch += 1
    except TrainingPreempted:
        raise  # drain() already dumped the "preempt" recording
    except ReplicaDesync:
        if flight is not None:
            flight.dump("desync")
        raise
    except BaseException:
        if flight is not None:
            flight.dump("exception")
        raise
    finally:
        # an exception mid-epoch must still flush any active trace (it is
        # the post-mortem artifact) and never leave the JSONL history
        # unflushed/truncated; the live plane tears down with it
        tel.finish()
        if profiling:
            stop_profiler()
        metrics_writer.close()
        if exporter is not None:
            exporter.stop()
        if flight is not None:
            flight_lib.uninstall(flight)

    print("Finished Training.")


def basic_accelerate_training(
    out_dir: str, training=None, num_chips=None, observability=None,
    backend=None,
):
    """``backend``: ``local.device`` from the settings file — honoured or
    refused (BackendUnavailableError), like the native entrypoint's."""
    training = training or cfg_lib.TRAINING_DEFAULTS
    # SIGTERM/SIGINT -> drain flag (polled at managed-loop boundaries);
    # main-thread only, a no-op under threaded test runners
    install_preemption_handler()
    # Topology discovery (reference :115): the first num_chips devices of
    # the named backend — a configured sub-world on multi-chip hosts.
    # fuse_steps batches K optimizer.step()s into one scan dispatch; it only
    # pays off when loss reads are deferred, so "auto" keys off that.
    accum = int(training.get("gradient_accumulation_steps") or 1)
    fuse = training.get("fuse_steps", "auto")
    if fuse in (None, "auto"):
        # fusion pays off only with deferred metric reads (an eager
        # loss.item() per batch flushes the queue every step); "auto" then
        # resolves size-aware inside the Accelerator at the first step
        fuse = "auto" if training.get("deferred_metrics") else 1
    # async pipeline config (training.pipeline): staged depth / host workers
    # / in-step augment; resolved once, recorded in the run_meta header
    from tpuddp.training.pipeline import resolve_pipeline

    pipeline_cfg = resolve_pipeline(training.get("pipeline"))
    # augmentation pipeline: with pipeline.device_augment (the default) the
    # normalize/flip/resize is folded INTO the compiled step programs
    # (Accelerator(augment=...)) so the host loop feeds raw decoded batches
    # — one dispatch per step, host workers only decode and stack
    mean, std = norm_stats_for(training)
    cdtype = compute_dtype_for(training)
    _aug = make_train_augment(
        size=training.get("image_size"),
        flip=flip_for(training),
        mean=mean,
        std=std,
        compute_dtype=cdtype,
    )
    # an EXPLICIT fuse_steps conflicting with accumulation surfaces the
    # library's own mutually-exclusive error instead of a silent override
    accelerator = Accelerator(
        seed=training.get("seed"),
        fuse_steps=fuse if fuse == "auto" else int(fuse),
        mesh=data_mesh(num_chips, backend),
        clip_grad_norm=training.get("clip_grad_norm"),
        gradient_accumulation_steps=accum,
        weight_update_sharding=bool(training.get("weight_update_sharding", False)),
        # gradient-comm hook (managed emulation; parallel/comm.py): same
        # training.comm_hook / comm_topology / topk_density knobs as the
        # native entrypoint (hierarchical topology is explicit-path-only and
        # refused here rather than silently run flat)
        comm_hook=str(training.get("comm_hook") or "none"),
        bucket_cap_mb=float(training.get("bucket_cap_mb") or 25),
        comm_topology=str(training.get("comm_topology") or "flat"),
        topk_density=float(training.get("topk_density") or 0.1),
        # numerical guard (resilience/guard.py): non-finite-update firewall
        # in the fused/scan/accumulation programs + prepare-time desync audit
        guard=training.get("guard"),
        augment=_aug if pipeline_cfg.device_augment else None,
    )

    # Data + model (reference :118-122); placement is implicit on this path.
    train_loader, test_loader = setup_dataloaders(training)
    model = load_model_for(training)

    criterion = nn.CrossEntropyLoss()
    # training.optimizer: adam default, lars/lamb/sgdw for large-batch runs —
    # config.optimizer_from, the SAME factory the native entrypoint uses
    optimizer = cfg_lib.optimizer_from(training)

    # prepare() wraps model/optimizer/train loader for the mesh backend
    # (reference :129-131); test_loader deliberately stays unprepared
    # (quirk Q3 parity).
    model, optimizer, training_dataloader = accelerator.prepare(
        model, optimizer, train_loader
    )

    if training.get("prefetch", True) and pipeline_cfg.host_workers > 0:
        from tpuddp.accelerate import StagedUploadLoader
        from tpuddp.data import PrefetchLoader

        # host batch assembly overlaps device compute (PrefetchLoader, the
        # reference's num_workers analog; workers > 1 parallelize assembly
        # over the loader's batch plan) and batch N+1's host->device upload
        # is issued while batch N's step runs (StagedUploadLoader)
        training_dataloader = StagedUploadLoader(
            PrefetchLoader(training_dataloader, workers=pipeline_cfg.host_workers)
        )
        test_loader = StagedUploadLoader(
            PrefetchLoader(test_loader, workers=pipeline_cfg.host_workers)
        )

    if pipeline_cfg.device_augment:
        # augment is compiled into the step programs (Accelerator(augment=)
        # above); train() feeds raw decoded batches straight to model(...)
        augment = None
    else:
        # legacy cadence: one separate jitted augment dispatch per batch;
        # (base_key, batch_index, x) — the per-batch key derivation happens
        # inside the jit (see train()'s aug_base note)
        augment = jax.jit(lambda rng, i, x: _aug(jax.random.fold_in(rng, i), x))
    eval_transform = jax.jit(
        make_eval_transform(
            size=training.get("image_size"), mean=mean, std=std,
            compute_dtype=cdtype,
        )
    )
    # Managed resume (training.resume: true): restore the newest lossless
    # state_{epoch}.npz in out_dir — weights, optimizer moments, RNG stream
    # position. The structure to load into is created by one LAZY forward on
    # a transformed single-sample probe (LazyForward materializes nothing and
    # _ensure_init only reads shape/dtype, so no batch assembly, no prefetch
    # thread, and only the transform's tiny dispatch runs).
    start_epoch = 0
    resume = (
        training.get("resume")
        or training.get("auto_resume")
        # the scheduler-requeue path: same command, env flag set (exit-75 contract)
        or auto_resume_requested()
    )
    if resume:
        img0, _label0 = train_loader.dataset[0]
        x0 = eval_transform(jnp.asarray(np.asarray(img0)[None]))
        model(x0)
        start_epoch = accelerator.load_state(model, optimizer, out_dir)
        cursor = getattr(accelerator, "last_restore_cursor", None)
        if cursor is not None and accelerator.is_local_main_process:
            print(
                f"Resumed from step snapshot: epoch {start_epoch} step "
                f"{int(cursor.get('step', 0))}."
            )
        elif start_epoch and accelerator.is_local_main_process:
            print(f"Resumed from epoch {start_epoch - 1} state.")

    from tpuddp.observability import config_hash

    run_training_loop(
        model,
        training_dataloader,
        test_loader,
        criterion,
        optimizer,
        out_dir,
        accelerator,
        augment,
        eval_transform,
        num_epochs=training["num_epochs"],
        checkpoint_epoch=training["checkpoint_epoch"],
        deferred_metrics=bool(training.get("deferred_metrics")),
        start_epoch=start_epoch,
        step_stats_every=int(training.get("step_stats_every") or 0),
        pipeline=pipeline_cfg,
        observability=observability,
        # step-granular preemption drains + exact mid-epoch resume
        snapshot=training.get("snapshot"),
        # run provenance for the history header: which configuration was this?
        run_meta={
            "config_hash": config_hash(training),
            "model": training.get("model"),
            "dataset": training.get("dataset"),
        },
    )


def load_model_for(training):
    from tpuddp.models import load_model

    from tpuddp.config import num_classes_from

    if training.get("pretrained_path"):
        from tpuddp.models.torch_import import pretrained_from_config

        model, params, mstate = pretrained_from_config(training)
        # consumed by PreparedModel._ensure_init instead of a fresh init
        model._tpuddp_initial_variables = (params, mstate)
    else:
        model = load_model(training["model"], num_classes_from(training))
    if training.get("sync_bn"):
        nn.convert_sync_batchnorm(model)
    return model


if __name__ == "__main__":
    compile_cache.enable()
    parser = argparse.ArgumentParser(
        description="tpuddp managed-API training (Accelerator over the XLA "
        "mesh backend).",
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
    training = cfg_lib.training_config(settings)
    # 2-D mesh: the managed path has no tensor-parallel step (the TP
    # exchanges are written over the explicit shard_map axes) — refuse a
    # model-parallel parallel block here instead of training something else
    if cfg_lib.parallel_config(settings)["model"] > 1:
        raise ValueError(
            "parallel.model > 1 needs the explicit API (train_native.py / "
            "DistributedDataParallel); the managed Accelerator path runs "
            "pure data parallelism"
        )

    # Managed path: world size comes from the runtime, not config — but honor
    # the dev-mode CPU world request like the native entrypoint does, and a
    # configured sub-world (local.tpu.num_chips) on multi-chip hosts.
    world_size = cfg_lib.world_size_from(settings)
    if world_size:
        from tpuddp.parallel.spawn import maybe_reexec_for_world

        maybe_reexec_for_world(world_size, cfg_lib.device_from(settings))

    try:
        basic_accelerate_training(
            out_dir, training, num_chips=world_size,
            observability=cfg_lib.observability_config(settings),
            backend=cfg_lib.device_from(settings),
        )
    except TrainingPreempted as e:
        # the exit-code contract (README "Fault tolerance"): 75 = EX_TEMPFAIL,
        # drained after SIGTERM — requeue the same command to auto-resume
        logging.getLogger("tpuddp").warning(
            "%s; exiting %d (requeue+resume)", e, EXIT_PREEMPTED
        )
        raise SystemExit(EXIT_PREEMPTED)
    except ReplicaDesync as e:
        # 77: a replica's parameters diverged (guard auditor) — the state is
        # untrustworthy; requeue into auto-resume from the last intact state
        logging.getLogger("tpuddp").critical("%s; exiting %d", e, EXIT_DESYNC)
        raise SystemExit(EXIT_DESYNC)
