"""Epoch driver — parity with the reference's ``run_training_loop``
(multi-GPU-training-torch.py:156-225), TPU-first in the hot path.

Per epoch: ``set_epoch`` reshuffle (toggleable, :175-178), optional RNG probe
(:180-183), train pass, eval pass, barrier (:194), five-scalar metric
aggregation (:198-206), process-0 logging (:209-215), process-0 checkpoint
every ``checkpoint_epoch`` epochs + barrier (:217-223).

Quirk decisions (SURVEY.md §3.5):
- Q1 fixed: the banner says *batches*, not samples.
- Q2 fixed: ``set_epoch`` is applied to the test loader too (harmless for the
  reference's metrics, removes the frozen-eval-order oddity).
- Q5 fixed: metric accumulation stays on device; one host sync per epoch.
- Q6 kept: checkpoint fires at epoch 0 (parity with the reference's
  ``epoch % checkpoint_epoch == 0``).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Optional

import jax
import numpy as np

from tpuddp import seeding
from tpuddp.parallel import collectives as col
from tpuddp.resilience import faults
from tpuddp.resilience import guard as guard_lib
from tpuddp.resilience.preemption import (
    TrainingPreempted,
    auto_resume_requested,
    preemption_requested,
)
from tpuddp.observability import (
    CommBytesCounter,
    MetricsWriter,
    RunTelemetry,
    check_finite,
    make_run_meta,
    maybe_start_profiler,
    stamp,
    stop_profiler,
)
from tpuddp.training import checkpoint as ckpt
from tpuddp.training import pipeline as pipeline_lib
from tpuddp.training import snapshot as snapshot_lib
from tpuddp.utils import batching
from tpuddp.training.step import finalize_metrics

logger = logging.getLogger("tpuddp")


_AUTO_SCAN_CAP = 64  # fusing amortizes the per-dispatch latency at no
# semantic cost. This is the depth the bench's CNN rows publish — the product
# default and the bench agree.
_AUTO_SCAN_FALLBACK_CAP = 32  # when the staged-chunk size cannot be known
# bound on one staged (K, batch) chunk — the shared budget every auto depth
# policy (native scan, managed fuse, eval fusion, serving) caps against
_STAGE_BYTES_BUDGET = batching.STAGE_BYTES_BUDGET
_SMALL_PARAM_BYTES = 4 * 1024 * 1024
_AUTO_MIN_DISPATCHES = 4  # a pass is cut into at least this many dispatches
# where it has the batches: the runner's shipped two staged chunks
# (pipeline.PIPELINE_DEFAULTS["depth"]), one running and one being filled.
# One dispatch a pass leaves the runner nothing to overlap (PERF.md, PR 40)


def resolve_scan_steps(
    scan_steps, n_batches: int, param_bytes=None, batch_nbytes=None
) -> int:
    """Resolve the per-dispatch fusion factor K.

    ``"auto"`` (the default) fuses up to 64 batches per dispatch when the
    epoch has at least that many — the measured per-dispatch runtime latency
    dominates per-step time otherwise (K is the amortization lever). The
    staged ``(K, batch, ...)``
    super-chunk must stay bounded, so whenever ``batch_nbytes`` (one host
    batch's input bytes) is known the ~256 MB staging budget caps K — for
    EVERY model size: a small model fed large batches still stages
    K x batch bytes, so the budget binds there too. Model size only decides
    the starting cap when batch bytes are unknowable: small models (whole
    parameter set under ~4 MB) start from 64 — dispatch latency dominates
    them even deeper (the bench's toy-MLP K-sweep) — while unknown-size
    batches on non-small models fall back to a conservative 32.

    K is also at most a quarter of the pass (``n_batches // 4``, at least
    1): the async runner overlaps the loader, ``np.stack`` and the transfer
    of chunk N+1 with the device's run of chunk N, and a pass that is one
    dispatch gives it no chunk N+1. Measured on a v5e (PERF.md, PR 39-40):
    25 batches of 6.29 MB resolved to K=25, the device then waited out the
    whole pass's assembly and staging, 0.39 s of every 2.26 s; at K=5 it
    waits for the first chunk alone. Where that share binds, the largest K
    in its upper half that divides ``n_batches`` is taken (5 for 25, where
    the share is 6): no single-step remainder, so the pass compiles one
    program and its tail is not a row of one-batch transfers. A long epoch
    never meets the share (1,000 batches of 1 MB still resolve to 64, with
    today's remainder), and a pass of fewer than 8 batches runs batch by
    batch. Any integer pins K explicitly; 1 disables fusion (one dispatch
    per batch, the reference's cadence)."""
    if scan_steps in (None, "auto"):
        small = param_bytes is not None and param_bytes < _SMALL_PARAM_BYTES
        cap = _AUTO_SCAN_CAP if (small or batch_nbytes) else _AUTO_SCAN_FALLBACK_CAP
        # the staging budget binds regardless of model size — a small model
        # on large inputs still stages K x batch bytes (shared cap policy,
        # tpuddp/utils/batching.py)
        cap = batching.resolve_fuse(batch_nbytes, cap=cap)
        share = max(1, n_batches // _AUTO_MIN_DISPATCHES)
        if share >= cap:
            return cap
        return next(
            (k for k in range(share, share // 2, -1) if n_batches % k == 0), share
        )
    k = int(scan_steps)
    if k < 1:
        raise ValueError(f"scan_steps must be >= 1 or 'auto', got {scan_steps!r}")
    return k


def _param_bytes(params) -> int:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params)
        if hasattr(l, "shape") and hasattr(l, "dtype")
    )


def _never():
    return False


def _fused_pass(
    ddp, state, loader, scan_k: int, step_one, step_many, probe_cb=None,
    accum: int = 1, poll=preemption_requested, inject_cb=None, tel=None,
    pipeline: Optional[pipeline_lib.PipelineConfig] = None,
    tracer=None, trace_parent=None, comm_attrs=None, snap_cb=None,
    init_acc=None,
):
    """One pass over ``loader`` — the async pipelined runner
    (:mod:`tpuddp.training.pipeline`): K-fused dispatch, a ``depth``-chunk
    staged device queue (host->HBM transfers overlap the previous dispatch's
    compute), and a deferred readback drain. ``pipeline`` (None -> the
    default config) only changes *when* host work happens, never what is
    dispatched: results are bitwise identical at every depth. See
    :func:`tpuddp.training.pipeline.run_pass` for the full contract."""
    return pipeline_lib.run_pass(
        ddp, state, loader, scan_k, step_one, step_many,
        cfg=pipeline if pipeline is not None else pipeline_lib.DEFAULT,
        probe_cb=probe_cb, accum=accum, poll=poll, inject_cb=inject_cb,
        tel=tel, tracer=tracer, trace_parent=trace_parent,
        comm_attrs=comm_attrs, snap_cb=snap_cb, init_acc=init_acc,
    )


def run_training_loop(
    ddp,
    state,
    train_loader,
    test_loader,
    save_dir: Optional[str],
    num_epochs: int = 20,
    checkpoint_epoch: int = 5,
    set_epoch: bool = True,
    print_rand: bool = False,
    data_probe_every: Optional[int] = None,
    start_epoch: int = 0,
    scan_steps="auto",
    per_replica_log: bool = False,
    auto_resume: bool = False,
    reshard_on_mismatch: bool = False,
    keep_last: Optional[int] = None,
    step_stats_every: int = 0,
    run_meta: Optional[dict] = None,
    pipeline=None,
    observability=None,
    snapshot=None,
    log=print,
):
    """Run the full training loop; returns ``(state, history)`` where history
    is a list of per-epoch metric dicts.

    ``ddp``: a DistributedDataParallel (or Accelerator-prepared equivalent)
    exposing shard/train_step/eval_step. Loaders yield host ``(x, y, w)``
    batches (ShardedDataLoader for DP; see tpuddp.data.loader).

    Resilience: ``auto_resume=True`` (or ``$TPUDDP_AUTO_RESUME=1``) restores
    the newest intact checkpoint in ``save_dir`` before training — including a
    preemption-drain emergency save, whose interrupted epoch is redone. A
    SIGTERM/SIGINT during training (see tpuddp.resilience.preemption) is
    polled at batch-group boundaries: the loop writes an emergency checkpoint
    and raises :class:`TrainingPreempted`, which ``spawn.run_ddp_training``
    turns into exit code 75. ``keep_last=K`` prunes all but the K newest
    checkpoints after each save. ``reshard_on_mismatch=True`` (the
    ``training.reshard_on_mismatch`` knob) lets the restore re-shape a
    checkpoint written on a DIFFERENT ``(data, model)`` mesh onto this one
    via the cross-topology reshaper (training/reshard.py) — the elastic
    mesh failover path; the reshard lands typed event rows and, when
    tracing is on, a named ``elastic reshard`` span.

    Numerical guard (``ddp.guard``, resilience/guard.py): the wrap owns the
    in-step firewall; this driver owns the epoch policy — it reads the skip
    counters once per epoch into the history record, runs the desync auditor
    every ``guard.audit_every_n_epochs`` (divergence -> ReplicaDesync/exit 77,
    or rollback), rolls back to the newest intact checkpoint when more than
    ``guard.max_consecutive_skips`` updates were skipped back to back, and
    guards BOTH aggregated losses (``$TPUDDP_DEBUG_NANS``) before any
    checkpoint so a poisoned epoch can never persist its state.

    Telemetry (tpuddp.observability): ``history.jsonl`` opens with a typed
    ``run_meta`` header, every epoch row carries step-time p50/p95/p99/max
    and achieved-MFU fields from the per-dispatch step recorder, and
    ``step_stats_every=N > 0`` additionally emits one ``step_stats`` row per
    N train steps (ONE host-side device fence per window — the compiled step
    program is untouched). ``run_meta`` (the dict) merges entrypoint-level
    fields (config hash, model, dataset) into the header row. Profiling:
    ``$TPUDDP_PROFILE`` (first epoch), ``$TPUDDP_PROFILE_STEPS=a:b`` (step
    window), SIGUSR1 (trace the next epoch of a live run).

    Async pipeline (``pipeline``, the ``training.pipeline`` block — see
    :mod:`tpuddp.training.pipeline`): depth of the staged device chunk
    queue, host loader workers, and the synchronous A/B mode. Bitwise
    identical to the synchronous path at every depth; ``step_stats`` windows
    gain the occupancy fields (host_stall_ms, staging/in-flight depth).

    Live telemetry plane (``observability``, the ``observability`` block —
    ISSUE 10): an opt-in background /metrics exporter fed by the same
    recorder state the history flushes, per-host telemetry shards through
    the heartbeat channel with a main-process pod aggregator + straggler
    detector, and a crash flight recorder dumped on abnormal exits. All
    host-side: the compiled step, the fence cadence, and the HLO are
    untouched with the whole plane on.

    Async step snapshots (``snapshot``, the ``training.snapshot`` block —
    :mod:`tpuddp.training.snapshot`): a background checkpoint engine takes
    device snapshots every N real micro-batches between dispatches (no step
    stall, no HLO change) and records the v4 data cursor. A preempt drain
    then flushes the in-flight snapshot and writes a final step delta
    instead of re-serializing the whole state; auto-resume from a cursor-
    bearing snapshot continues the interrupted epoch AT the recorded step —
    zero batches replayed, loss trajectory bitwise-equal to an
    uninterrupted same-seed run — and guard rollbacks restore to the last
    good STEP, not epoch. Off (None) keeps the pre-v4 epoch-granular
    contract, including redo-the-interrupted-epoch (deprecated — see README
    "Async checkpointing & exact resume").
    """
    from tpuddp import config as cfg_lib
    from tpuddp.observability import aggregate as agg_lib
    from tpuddp.observability import exporter as exp_lib
    from tpuddp.observability import flight as flight_lib
    from tpuddp.observability import trace as trace_lib
    from tpuddp.resilience import watchdog as wd_lib

    is_main = jax.process_index() == 0
    pipeline = pipeline_lib.resolve_pipeline(pipeline)
    pbytes = _param_bytes(state.params) if hasattr(state, "params") else None
    eval_scan_steps = (
        resolve_scan_steps(
            scan_steps, len(test_loader), pbytes,
            getattr(test_loader, "batch_nbytes", None),
        )
        if hasattr(ddp, "eval_step_many")
        else 1
    )
    scan_steps = resolve_scan_steps(
        scan_steps, len(train_loader), pbytes,
        getattr(train_loader, "batch_nbytes", None),
    )
    accum = int(getattr(ddp, "grad_accumulation", 1) or 1)
    if accum > 1:
        # chunks must hold whole accumulation cycles: round K up to the
        # cycle length, then down to a multiple of it
        scan_steps = max(accum, (scan_steps // accum) * accum)
        bnb = getattr(train_loader, "batch_nbytes", None)
        if bnb and scan_steps * bnb > _STAGE_BYTES_BUDGET:
            # respect the staging budget in whole cycles; one cycle is the
            # floor (the accumulation step needs whole cycles), warn if even
            # that exceeds the budget
            scan_steps = max(
                accum, (_STAGE_BYTES_BUDGET // bnb) // accum * accum
            )
            if scan_steps * bnb > _STAGE_BYTES_BUDGET:
                logger.warning(
                    "gradient_accumulation_steps=%d forces a staged chunk of "
                    "%.0f MB (one whole cycle), over the ~%d MB staging "
                    "budget; reduce the accumulation depth or batch size if "
                    "the host/device cannot hold it",
                    accum, scan_steps * bnb / 1e6, _STAGE_BYTES_BUDGET // 2**20,
                )
    train_dispatches = pipeline_lib.dispatches_per_pass(
        len(train_loader), scan_steps, accum
    )
    want_resume = auto_resume or auto_resume_requested()
    if want_resume and save_dir is None and is_main:
        log("Auto-resume requested but no save_dir configured; starting fresh.")

    history = []
    # ---- live telemetry plane (observability/{exporter,aggregate,flight}):
    # the flight ring tees every history record (every process keeps one);
    # the exporter/aggregator start below once the telemetry bundle exists.
    obs_cfg = cfg_lib.resolve_observability(observability)
    # causal tracing plane (observability/trace.py, default OFF): epoch ->
    # stage/dispatch/collective/readback span trees, exported as
    # trace_train.json at drain and served on /trace. Host bracketing only.
    tracer = trace_lib.tracer_from_config(obs_cfg, "train", run_dir=save_dir)
    flight = None
    if obs_cfg["flight_recorder"] and save_dir is not None:
        flight = flight_lib.install(flight_lib.FlightRecorder(
            save_dir, capacity=int(obs_cfg["flight_capacity"]),
        ))
        if tracer.enabled:
            # a crash dump embeds the still-open spans: the exact stage the
            # process died in, not just the last flushed window
            flight.add_context("open_spans", tracer.open_span_summaries)
        if obs_cfg.get("advisor") or os.environ.get(cfg_lib.TUNE_OVERLAY_ENV):
            # advisor-armed runs: a preempt/crash must not lose the pending
            # recommendation — the dump carries the top advice so the next
            # launch (or a human) can act on what this run already learned
            from tpuddp.observability import advisor as advisor_lib
            flight.add_context(
                "pending_tune",
                lambda: advisor_lib.pending_summary(save_dir),
            )
    metrics_writer = MetricsWriter(save_dir, flight=flight)
    # ---- async step-granular snapshots (training/snapshot.py): the engine
    # copies state on-device between dispatches and serializes on a
    # background writer; pending_cursor carries a restored v4 data cursor to
    # the epoch that consumes it (exact mid-epoch resume, zero replay).
    snap_cfg = snapshot_lib.resolve_snapshot(snapshot)
    snap_engine = None
    if snap_cfg.enabled and save_dir is not None:
        snap_engine = snapshot_lib.SnapshotEngine(
            save_dir, snap_cfg,
            world_size=getattr(ddp, "world_size", None),
            keep_last=keep_last,
            tracer=tracer, flight=flight,
        )
    pending_cursor = {"c": None}
    # the run's ONE trace id: minted before the restore below so an elastic
    # reshard episode lands as a named span in the SAME trace as the epochs
    # it precedes — the tracing plane shows recovery, not a gap
    run_trace_id = tracer.new_trace()
    # elastic resume (ISSUE 7 / ISSUE 16): restore_latest reshards a
    # checkpoint written on a different world size — and, with
    # reshard_on_mismatch, a different (data, model) MESH SHAPE — onto this
    # one (training/checkpoint.py + training/reshard.py) and hands back the
    # typed topology-change events, written below once the history's
    # run_meta header exists.
    reshard_log = []
    if want_resume and save_dir is not None:
        resume_span = tracer.start_span(
            "auto-resume restore", trace_lib.KIND_ACTION,
            trace_id=run_trace_id, tid="train",
        )
        resume_cursor = []
        state, resumed = ckpt.restore_latest(
            save_dir, state,
            world_size=getattr(ddp, "world_size", None),
            model_size=getattr(ddp, "model_size", None),
            reshard_log=reshard_log,
            reshard_on_mismatch=reshard_on_mismatch,
            cursor_out=resume_cursor,
        )
        if resume_cursor:
            # a v4 step snapshot: the cursor's epoch resumes AT its step
            # (the epoch below that consumes pending_cursor verifies the
            # plan key first — a changed data order falls back to redo)
            pending_cursor["c"] = resume_cursor[-1]
            if flight is not None:
                flight.note(snapshot_resume={
                    "epoch": resume_cursor[-1].get("epoch"),
                    "step": resume_cursor[-1].get("step"),
                    "provenance": resume_cursor[-1].get("provenance"),
                    "path": os.path.basename(
                        resume_cursor[-1].get("path") or ""
                    ),
                })
        if resumed > start_epoch:
            start_epoch = resumed
            if is_main:
                log(f"Auto-resume: continuing from epoch {start_epoch}.")
        topo_ev = next(
            (ev for ev in reshard_log if ev.get("event") == "topology_change"),
            None,
        )
        if topo_ev is not None:
            # name the reshard episode on every observability surface: a
            # child span in the run trace, a flight-recorder note, and (just
            # below) the typed history event rows
            reshard_span = tracer.start_span(
                "elastic reshard", trace_lib.KIND_ACTION,
                parent=resume_span,
                attrs={k: topo_ev.get(k) for k in (
                    "from_world", "to_world", "from_model", "to_model",
                    "checkpoint", "residual",
                )},
            )
            tracer.end_span(
                reshard_span, resharded_leaves=len(topo_ev.get(
                    "resharded_leaves") or ()),
            )
            if flight is not None:
                # namespaced note key: any later crash dump carries the
                # episode under notes["elastic_reshard"]
                flight.note(elastic_reshard={
                    k: topo_ev.get(k) for k in (
                        "from_world", "to_world", "from_model", "to_model",
                        "checkpoint",
                    )
                })
        tracer.end_span(
            resume_span, resumed_epoch=start_epoch,
            resharded=bool(reshard_log),
        )
    # gradient-comm wire-bytes accounting (parallel/comm.py counter): one
    # optimizer update per accumulation cycle; the payload per update is
    # static, so the counter is free host arithmetic next to the device step
    comm_counter = CommBytesCounter(
        getattr(ddp, "grad_comm_bytes_per_step", None)
    )
    profiling = maybe_start_profiler(save_dir)  # $TPUDDP_PROFILE hook

    # ---- numerical guard (resilience/guard.py): the ddp wrap owns the
    # config; the driver owns the epoch-level policy — skip accounting,
    # periodic desync audits, rollback-to-last-good.
    guard_cfg = guard_lib.resolve_guard(getattr(ddp, "guard", None))

    # ---- telemetry (tpuddp.observability): typed run_meta header first,
    # then the per-dispatch step recorder + on-demand profiling triggers.
    meta_extra = {
        "api": "native",
        "scan_steps": scan_steps,
        "dispatches_per_pass": train_dispatches,
        "grad_accumulation": accum,
        "start_epoch": start_epoch,
        "num_epochs": num_epochs,
        "step_stats_every": int(step_stats_every or 0),
        "pipeline": pipeline.as_dict(),
        "grad_comm_bytes_per_update": getattr(
            ddp, "grad_comm_bytes_per_step", None
        ),
        "grad_comm_bytes_per_update_f32": getattr(
            ddp, "grad_comm_bytes_per_step_f32", None
        ),
        # comm compression v2 accounting: which wire topology the bytes
        # crossed, the top-k density, and the intra/inter-host hop split
        # (the hierarchical topology's whole point — parallel/comm.py)
        "comm_density": getattr(ddp, "topk_density", None),
        "grad_comm_bytes_inter_host": getattr(
            ddp, "grad_comm_bytes_inter_host", None
        ),
        "grad_comm_bytes_intra_host": getattr(
            ddp, "grad_comm_bytes_intra_host", None
        ),
        **(run_meta or {}),
    }
    topo_change = next(
        (ev for ev in reshard_log if ev.get("event") == "topology_change"), None
    )
    if topo_change is not None:
        # the header states the elastic provenance: this run CONTINUES a
        # trajectory that was training on a different world size (and,
        # after a mesh failover, a different model width)
        meta_extra["resumed_from_world"] = topo_change.get("from_world")
        if topo_change.get("from_model") is not None:
            meta_extra["resumed_from_model"] = topo_change.get("from_model")
    # exporter starts BEFORE the header so the header can record the BOUND
    # port (ephemeral binds resolve at start); sources attach once the
    # telemetry bundle exists below
    exporter = exp_lib.exporter_from_config(obs_cfg, run_dir=save_dir)
    if exporter is not None:
        exporter.start()
        if tracer.enabled:
            exporter.set_trace_source(tracer.endpoint_payload)
    obs_meta = {
        "exporter": exporter.describe() if exporter is not None else False,
        "aggregate": bool(obs_cfg["aggregate"]),
        "straggler_ratio": float(obs_cfg["straggler_ratio"]),
        "straggler_windows": int(obs_cfg["straggler_windows"]),
        "flight_recorder": (
            flight.describe() if flight is not None else False
        ),
    }
    # v10 comm block: the gradient-exchange execution provenance (a constant
    # since the step is one program; null on wraps that carry none)
    overlap_meta = getattr(ddp, "comm_overlap_meta", None)
    comm_block = (
        {"overlap": dict(overlap_meta)} if overlap_meta is not None else None
    )
    metrics_writer.write(make_run_meta(
        mesh=getattr(ddp, "mesh", None),
        world_size=getattr(ddp, "world_size", None),
        comm_hook=getattr(ddp, "comm_hook", None),
        comm_topology=getattr(ddp, "comm_topology", "flat"),
        guard=guard_cfg,
        observability=obs_meta,
        # v8 mesh block: names the TP rule table when the mesh carries a
        # real model axis (None on pure-DP wraps)
        tp_rules_hash=getattr(ddp, "tp_rules_hash", None),
        # v9 tracing block: ring capacity + artifact name (null = off)
        tracing=tracer.describe(),
        # v11 snapshot block: async step-checkpoint engine provenance
        # (config + writer identity), or False when the engine is off
        snapshot=(
            snap_engine.describe() if snap_engine is not None
            else (snap_cfg.as_dict() if snap_cfg.enabled else False)
        ),
        comm=comm_block,
        # v12 tuning block: tune-overlay provenance when this process was
        # relaunched under $TPUDDP_TUNE_OVERLAY (null = advisor off / no
        # overlay — the bitwise-identical default)
        tuning=cfg_lib.tuning_provenance_from_env(),
        extra=meta_extra,
    ))
    for ev in reshard_log:
        metrics_writer.write(stamp("event", ev))
    # FLOPs probe for the MFU fields: the single-step program, lowered here
    # and compiled once by the telemetry at the first epoch boundary — only
    # when the per-batch step exists (grad accumulation refuses it) and
    # shapes are capturable.
    flops_lower_fn = None
    if accum == 1 and hasattr(ddp, "train_step"):
        try:
            state_struct = jax.tree_util.tree_map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state
            )
        except Exception:
            state_struct = None
        if state_struct is not None:
            def flops_lower_fn():
                if not tel.batch_struct:
                    raise ValueError("no batch structure captured")
                return jax.jit(
                    lambda s, b: ddp.train_step(s, b)
                ).lower(state_struct, tel.batch_struct)
    ddp_mesh = getattr(ddp, "mesh", None)
    tel = RunTelemetry(
        writer=metrics_writer,
        save_dir=save_dir,
        step_stats_every=step_stats_every,
        flops_lower_fn=flops_lower_fn,
        device_kind=(
            ddp_mesh.devices.flat[0].device_kind if ddp_mesh is not None else None
        ),
    )
    # cross-host aggregation: every process publishes its shard through the
    # heartbeat channel; process 0 merges + detects stragglers. Inert on
    # single-process runs (there is no pod to aggregate).
    aggregator = None
    shard_dir = None
    if obs_cfg["aggregate"] and jax.process_count() > 1:
        shard_dir = wd_lib.heartbeat_dir(save_dir)
        if shard_dir is not None:
            os.makedirs(shard_dir, exist_ok=True)
            if is_main:
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

    prev_total_skips = (
        guard_lib.read_skip_counters(state)[0] if guard_cfg.enabled else 0
    )
    rollback_count = {"n": 0}

    def rollback_to_last_good(cur_state, epoch, reason):
        """Restore the newest integrity-verified checkpoint and hand back
        ``(state, epoch_to_redo)``. The caller re-enters the epoch loop
        there, so ``set_epoch`` re-derives the redone epoch's data order.
        With step snapshots armed the newest checkpoint is usually a v4
        STEP snapshot — the rollback then lands on the last good STEP, not
        epoch: its cursor goes through ``pending_cursor`` and the redone
        epoch continues at the recorded step. The rollback is a recorded
        event in history.jsonl, and a bounded one — replaying a
        persistently-poisoned epoch forever is not recovery."""
        rollback_count["n"] += 1
        if rollback_count["n"] > guard_cfg.max_rollbacks:
            raise RuntimeError(
                f"guard rollback limit ({guard_cfg.max_rollbacks}) exceeded; "
                f"last trigger: {reason}. The failure recurs after restoring "
                "known-good state — a systematic divergence, not a transient."
            )
        rb_log = []
        rb_cursor = []
        restored, redo_epoch = ckpt.restore_latest(
            save_dir, cur_state,
            world_size=getattr(ddp, "world_size", None),
            model_size=getattr(ddp, "model_size", None),
            reshard_log=rb_log,
            reshard_on_mismatch=reshard_on_mismatch,
            cursor_out=rb_cursor,
        )
        resume_step = None
        if rb_cursor:
            pending_cursor["c"] = rb_cursor[-1]
            resume_step = rb_cursor[-1].get("step")
        metrics_writer.write(stamp("event", {
            "event": "rollback",
            "epoch": epoch,
            "resume_epoch": redo_epoch,
            "resume_step": resume_step,
            "reason": reason,
        }))
        for ev in rb_log:
            metrics_writer.write(stamp("event", ev))
        if is_main:
            log(
                f"Guard rollback ({reason}): restored last-good checkpoint, "
                f"redoing from epoch {redo_epoch}."
            )
            if resume_step is not None:
                log(
                    f"Rollback target is a step snapshot: epoch {redo_epoch} "
                    f"continues at step {resume_step}."
                )
        return restored, redo_epoch

    def can_roll_back() -> bool:
        return save_dir is not None and ckpt.latest(save_dir) is not None

    # ---- step-site chaos hooks (resilience/faults.py): wired only while an
    # un-fired step fault is armed, so normal runs pay nothing per batch. The
    # step index is the global train micro-batch count from loop entry.
    # nan@step=N poisons the batch (the guard-firewall proof);
    # preempt@step=N / crash@step=N kill the run MID-epoch — the elastic
    # chaos matrix's resize scenarios (resume redoes the interrupted epoch
    # from the saved mid-epoch state, possibly on a different world size).
    nan_inject = None
    if faults.has_step_fault():
        _nan_step = {"i": 0}

        def nan_inject(host_batch):
            i = _nan_step["i"]
            _nan_step["i"] += 1
            faults.maybe_fire("step", step=i)  # process-level kinds
            return faults.maybe_corrupt_batch(host_batch, i)

    multihost = jax.process_count() > 1
    # single-host: poll the drain flag at every batch-group boundary.
    # multi-host: never inside a pass — one host returning early while peers
    # still issue step collectives wedges the pod; drains happen only at the
    # globally-agreed epoch boundary below.
    poll = _never if multihost else preemption_requested

    def drain_requested():
        if not multihost:
            return preemption_requested()
        # SIGTERMs land on hosts milliseconds apart; before anyone enters the
        # save collectives all hosts must agree a drain is on, or the ones
        # that didn't see the flag yet deadlock the pod. Process 0's flag is
        # the decision; this broadcast is one tiny per-epoch collective.
        return bool(col.broadcast_one_to_all(np.asarray(preemption_requested())))

    def emergency_stop(epoch, completed=False, partial=None):
        """Preemption drain: one atomic full-state save, then the distinct
        exit path via TrainingPreempted. ``completed=False`` (the default)
        marks a mid-train-pass drain — resume redoes ``epoch`` from the saved
        state. ``completed=True`` is the eval-pass interruption: every
        optimizer update of ``epoch`` is already applied, so the save counts
        as end-of-epoch and resume starts at ``epoch + 1`` (re-training it
        would double-apply the whole epoch); only its eval metrics are lost.

        With the snapshot engine armed, a mid-train-pass drain (``partial``:
        the epoch's progress dict + partial accumulator) reuses the async
        writer's flush path instead of re-serializing from scratch: flush
        the in-flight snapshot (work already done), then write only the
        final step delta. Resume then continues AT the drained step."""
        path = None
        flushed_step = None
        snap_drain = (
            snap_engine is not None and not completed and partial is not None
        )
        if save_dir is not None:
            if snap_drain:
                flushed_step = snap_engine.flush()
                path = snap_engine.final_snapshot(
                    state, epoch=epoch, step=int(partial["step"]),
                    plan_key=partial.get("plan_key"), acc=partial.get("acc"),
                )
            if path is None:
                snap_drain = False
                path = ckpt.save_on_main(
                    save_dir, epoch, state, completed=completed,
                    world_size=getattr(ddp, "world_size", None),
                )
                if is_main:
                    log(f"Preempted: emergency checkpoint for epoch {epoch} saved.")
            elif is_main:
                log(
                    f"Preempted: drained snapshot writer (flushed step "
                    f"{flushed_step}) and saved final step snapshot for "
                    f"epoch {epoch} step {int(partial['step'])}."
                )
        # the drain's event row, fsync'd NOW: the SIGKILL that follows the
        # grace window must not be able to eat the post-mortem record
        event = {
            "event": "preempt",
            "epoch": epoch,
            "completed": bool(completed),
            "step": tel.recorder.global_step,
        }
        if snap_drain:
            event["snapshot_step"] = int(partial["step"])
        metrics_writer.write(stamp("event", event))
        metrics_writer.sync()
        # the exit-75 flight recording: the writer tee above means the
        # preempt event (and the last windows before it) are in the ring
        if flight is not None:
            notes = dict(
                emergency_checkpoint=path,
                emergency_epoch=epoch,
                emergency_step=tel.recorder.global_step,
            )
            if snap_drain:
                # the chaos contract: the recording NAMES the flushed step
                # (the last snapshot the writer published before the final
                # delta) and the final step the drain itself wrote
                notes["snapshot_flushed_step"] = flushed_step
                notes["snapshot_final_step"] = int(partial["step"])
            flight.note(**notes)
            flight.dump("preempt")
        raise TrainingPreempted(epoch, path)

    if is_main:
        log(
            f"Training on {len(train_loader)} batches, test on {len(test_loader)} batches"
        )
        log(
            f"Dispatch: {scan_steps} train steps fused ({train_dispatches} "
            f"dispatches a pass), {eval_scan_steps} eval steps "
            f"({pipeline_lib.dispatches_per_pass(len(test_loader), eval_scan_steps)})"
        )

    # the whole run is ONE trace: every epoch span (and its stage/dispatch/
    # collective/readback children) shares run_trace_id, minted above before
    # the auto-resume restore so a reshard episode rides the same tree. The
    # comm annotation only arms on the train pass of a hooked run — eval
    # dispatches carry no gradient exchange.
    epoch_span = None
    comm_attrs = None
    if tracer.enabled and getattr(ddp, "comm_hook", "none") != "none":
        comm_attrs = {
            "hook": ddp.comm_hook,
            "topology": getattr(ddp, "comm_topology", "flat"),
            "wire_bytes_per_update": getattr(
                ddp, "grad_comm_bytes_per_step", None
            ),
            "wire_bytes_per_update_f32": getattr(
                ddp, "grad_comm_bytes_per_step_f32", None
            ),
            "inter_host_bytes_per_update": getattr(
                ddp, "grad_comm_bytes_inter_host", None
            ),
        }

    try:
        epoch = start_epoch
        while epoch < num_epochs:
            faults.maybe_fire("epoch", epoch=epoch)  # $TPUDDP_FAULT chaos hook
            if drain_requested():
                emergency_stop(epoch)
            if (
                guard_cfg.enabled
                and guard_cfg.audit_every_n_epochs
                and (epoch - start_epoch) % guard_cfg.audit_every_n_epochs == 0
                and getattr(ddp, "mesh", None) is not None
            ):
                # desync audit: ONE fingerprint reduction over the parameter
                # tree per audited epoch (guard.audit_params cost model) —
                # the periodic re-run of the wrap-time verify
                bad_leaf = guard_lib.audit_params(
                    ddp.mesh, state.params,
                    specs=getattr(ddp, "tp_param_specs", None),
                )
                if bad_leaf is not None:
                    metrics_writer.write(stamp(
                        "event",
                        {"event": "desync", "epoch": epoch, "leaf": bad_leaf},
                    ))
                    if guard_cfg.on_desync == "rollback" and can_roll_back():
                        state, epoch = rollback_to_last_good(
                            state, epoch, f"replica desync at leaf {bad_leaf}"
                        )
                        prev_total_skips = guard_lib.read_skip_counters(state)[0]
                        continue
                    # no checkpoint to fall back to (or exit policy): the
                    # distinct code 77 requeues into auto-resume
                    raise guard_lib.ReplicaDesync(
                        bad_leaf, where=f"epoch {epoch} audit"
                    )
            t0 = time.perf_counter()
            tel.start_epoch(epoch)
            epoch_span = tracer.start_span(
                f"epoch {epoch}", trace_lib.KIND_EPOCH,
                trace_id=run_trace_id, tid="train",
                attrs={"epoch": epoch},
            )
            if is_main:
                log(f"Process {jax.process_index()}, Epoch {epoch}")
            if set_epoch:
                # Per-epoch reshuffle; without it every epoch replays epoch-0
                # order (the pitfall toggle, reference :175-178 / README.md:82-84).
                train_loader.set_epoch(epoch)
                test_loader.set_epoch(epoch)
                if is_main:
                    log(f"DistributedSampler.set_epoch: {set_epoch}")

            if print_rand:
                log(f"Process {jax.process_index()}, {seeding.rng_probe_string()}")

            # ---- exact mid-epoch resume: a v4 cursor restored for THIS epoch
            # skips the already-applied prefix of the batch plan (zero batches
            # replayed) instead of redoing the epoch. The cursor's plan key
            # must match what this loader would produce for this epoch — a
            # mismatch (different sampler config, resharded data order) falls
            # back to the legacy redo-the-epoch path. ----
            resume_skip = None
            cur = pending_cursor["c"]
            if cur is not None and int(cur.get("epoch", -1)) == epoch:
                pending_cursor["c"] = None
                if cur.get("plan_key"):
                    expect = snapshot_lib.epoch_plan_key(train_loader, epoch)
                    if cur["plan_key"] == expect:
                        resume_skip = cur
                        if is_main:
                            log(
                                f"Exact resume: epoch {epoch} continues at "
                                f"step {int(cur['step'])} (zero batches "
                                f"replayed)."
                            )
                    else:
                        logger.warning(
                            "Step snapshot plan key mismatch for epoch %d "
                            "(%s != %s): data order changed, redoing the "
                            "epoch from the restored state.",
                            epoch, cur["plan_key"], expect,
                        )
                else:
                    logger.warning(
                        "Step snapshot for epoch %d carries no plan key "
                        "(resharded restore): redoing the epoch.", epoch,
                    )
            elif cur is not None and int(cur.get("epoch", -1)) != epoch:
                pending_cursor["c"] = None

            base_step = int(resume_skip["step"]) if resume_skip else 0
            pass_loader = train_loader
            init_acc = None
            if base_step > 0:
                pass_loader = snapshot_lib.EpochTailLoader(
                    train_loader, base_step
                )
                init_acc = snapshot_lib.acc_from_cursor(resume_skip)

            # snapshot engine arming for this epoch: the snap_cb fires between
            # step dispatches (post-dispatch, pre-next-stage) so the staged
            # queue never drains — the snapshot is an async on-device copy,
            # serialized off-thread.
            snap_cb = None
            epoch_prog = None
            if snap_engine is not None:
                plan_key = snapshot_lib.epoch_plan_key(train_loader, epoch)
                epoch_prog = {
                    "epoch": epoch, "step": base_step, "plan_key": plan_key,
                }
                snap_engine.begin_epoch(epoch, base_step)
                snap_engine.trace_parent = epoch_span

                def snap_cb(st, batches_done, drain, _base=base_step,
                            _ep=epoch, _pk=plan_key, _prog=epoch_prog):
                    _prog["step"] = _base + batches_done
                    snap_engine.maybe(
                        st, epoch=_ep, step=_base + batches_done,
                        plan_key=_pk, drain=drain,
                    )

            # ---- train pass (hot loop: one jitted step per batch, or per
            # `scan_steps` batches fused into a single lax.scan dispatch) ----
            def train_probe(batch_idx, host_batch):
                if data_probe_every and batch_idx % data_probe_every == 0:
                    probe = getattr(train_loader, "probe_fingerprint", None)
                    if probe is not None:
                        log(f"TRAIN: Batch {batch_idx}, Data {probe(host_batch[0])}")

            state, train_acc, interrupted = _fused_pass(
                ddp, state, pass_loader, scan_steps,
                ddp.train_step, ddp.train_step_many, probe_cb=train_probe,
                accum=accum, poll=poll, inject_cb=nan_inject, tel=tel,
                pipeline=pipeline, tracer=tracer, trace_parent=epoch_span,
                comm_attrs=comm_attrs, snap_cb=snap_cb, init_acc=init_acc,
            )
            if interrupted:
                emergency_stop(
                    epoch,
                    partial=(
                        {**epoch_prog, "acc": train_acc}
                        if epoch_prog is not None else None
                    ),
                )

            # ---- eval pass (same K-fused dispatch + upload lookahead; without
            # it the eval epoch is per-batch dispatch-bound). State threads
            # through untouched. ----
            _, eval_acc, interrupted = _fused_pass(
                ddp, state, test_loader, eval_scan_steps,
                lambda s, b: (s, ddp.eval_step(s, b)),
                lambda s, b: (s, ddp.eval_step_many(s, b)),
                poll=poll, pipeline=pipeline,
                tracer=tracer, trace_parent=epoch_span,
            )
            if interrupted:
                # The train pass landed every optimizer update of this epoch
                # (that is what completed=True means), so the epoch row must
                # land too: resume starts at epoch + 1 and never rewrites it,
                # and a drain that raced the eval pass would otherwise leave a
                # permanent hole in history.jsonl. Eval metrics are honestly
                # NaN — same shape as the empty-test-loader row.
                if train_acc is not None:
                    tm = finalize_metrics({"train": train_acc})["train"]
                    epoch_time = time.perf_counter() - t0
                    epoch_updates = -(-len(train_loader) // accum)
                    comm_counter.add_updates(epoch_updates)
                    record = {
                        "epoch": epoch,
                        "train_loss": tm["loss_sum"] / max(tm["n"], 1.0),
                        "test_loss": float("nan"),
                        "test_accuracy": float("nan"),
                        "train_samples": tm["n"],
                        "test_samples": 0.0,
                        "epoch_time_s": epoch_time,
                        "samples_per_sec": tm["n"] / max(epoch_time, 1e-9),
                    }
                    record.update(tel.end_epoch())
                    record.update(comm_counter.snapshot(epoch_updates))
                    if guard_cfg.enabled:
                        total_skips, _ = guard_lib.read_skip_counters(state)
                        record["skipped_steps"] = total_skips
                        record["skipped_steps_epoch"] = (
                            total_skips - prev_total_skips
                        )
                    record = stamp("epoch", record)
                    history.append(record)
                    metrics_writer.write(record)
                emergency_stop(epoch, completed=True)

            if train_acc is None:
                raise RuntimeError(
                    "train loader yielded no batches this epoch; check the "
                    "dataset and batch size"
                )

            # Sync all processes before aggregating (reference :194).
            col.barrier("tpuddp_epoch", wait_for=(train_acc, eval_acc))

            if (
                per_replica_log
                and eval_acc is not None
                # per-replica values are host-fetchable only when this process can
                # address every shard (single-host); multi-host keeps the line out
                and getattr(train_acc["loss_sum"], "is_fully_addressable", True)
            ):
                # pre-aggregation per-device loss lines (reference :186-191);
                # ONE host fetch for all four arrays, not four round trips
                tl, tn, el, en = jax.device_get(
                    (
                        train_acc["loss_sum"],
                        train_acc["n"],
                        eval_acc["loss_sum"],
                        eval_acc["n"],
                    )
                )
                def _count(v):
                    # a poisoned batch (e.g. an injected NaN sample weight)
                    # makes the weighted count non-finite; the post-mortem
                    # log line must print it, not crash on int(NaN)
                    return int(v) if np.isfinite(v) else float(v)

                for r in range(tl.size):
                    log(
                        f"Train loss on replica {r}: {tl[r] / max(tn[r], 1):.4f} "
                        f"based on {_count(tn[r])} samples"
                    )
                for r in range(el.size):
                    log(
                        f"Test loss on replica {r}: {el[r] / max(en[r], 1):.4f} "
                        f"based on {_count(en[r])} samples"
                    )

            # Aggregate the five scalars (reference :198-204) in ONE fused
            # cross-device pass + one host fetch.
            combined = {"train": train_acc}
            if eval_acc is not None:
                combined["eval"] = eval_acc
            sums = finalize_metrics(combined)
            train_m, eval_m = sums["train"], sums.get("eval")
            train_loss = train_m["loss_sum"] / max(train_m["n"], 1.0)
            if eval_m is not None:
                test_loss = eval_m["loss_sum"] / max(eval_m["n"], 1.0)
                test_accuracy = 100.0 * eval_m["correct"] / max(eval_m["n"], 1.0)
            else:  # empty test loader: report train-only metrics
                eval_m = {"n": 0.0}
                test_loss = float("nan")
                test_accuracy = float("nan")

            epoch_time = time.perf_counter() - t0
            # optimizer updates this epoch: one per accumulation cycle over
            # the dispatched micro-batches (the padded tail rounds up)
            epoch_updates = -(-len(train_loader) // accum)
            comm_counter.add_updates(epoch_updates)
            record = {
                "epoch": epoch,
                "train_loss": train_loss,
                "test_loss": test_loss,
                "test_accuracy": test_accuracy,
                "train_samples": train_m["n"],
                "test_samples": eval_m["n"],
                "epoch_time_s": epoch_time,
                "samples_per_sec": (train_m["n"] + eval_m["n"]) / max(epoch_time, 1e-9),
                # the model's own additive counters (model.counter_names), summed
                # over the epoch's train steps: the expert layer's load, for one
                **{k: v for k, v in train_m.items() if k not in ("loss_sum", "n")},
            }
            # step-time percentiles + achieved-MFU from the train-pass
            # recorder (the finalize_metrics fetch above already fenced the
            # device, so the aggregate wall time is honest)
            record.update(tel.end_epoch())
            record.update(comm_counter.snapshot(epoch_updates))

            # ---- guard skip accounting: ONE tiny counter fetch per epoch.
            epoch_skips = consec_skips = 0
            if guard_cfg.enabled:
                total_skips, consec_skips = guard_lib.read_skip_counters(state)
                epoch_skips = total_skips - prev_total_skips
                prev_total_skips = total_skips
                record["skipped_steps"] = total_skips
                record["skipped_steps_epoch"] = epoch_skips

            # live-plane gauges the recorder cannot see: last epoch losses,
            # guard skips, cumulative comm bytes (host dict updates only)
            tel.update_live(
                train_loss=train_loss,
                test_loss=test_loss,
                test_accuracy=test_accuracy,
                skipped_steps=record.get("skipped_steps", 0),
                grad_comm_bytes_total=comm_counter.total_bytes,
            )
            if aggregator is not None:
                aggregator.update()  # epoch-boundary merge (windows may be off)
            record = stamp("epoch", record)
            history.append(record)
            metrics_writer.write(record)  # post-mortem row always lands
            if epoch_skips:
                # the firewall's skips as a discrete event next to the epoch
                # fields, so event timelines see them without scanning rows
                metrics_writer.write(stamp("event", {
                    "event": "skipped_updates",
                    "epoch": epoch,
                    "count": epoch_skips,
                    "total": record["skipped_steps"],
                }))
            # $TPUDDP_DEBUG_NANS: BOTH aggregated losses are guarded BEFORE
            # any checkpoint below — a poisoned epoch must never persist its
            # state (the pre-fix ordering only checked the train loss, so a
            # finite-train/NaN-test epoch could still be checkpointed).
            check_finite(train_loss, "train loss")
            if eval_m["n"]:  # the empty-test-loader NaN placeholder is benign
                check_finite(test_loss, "test loss")

            if profiling and epoch == start_epoch:
                stop_profiler()  # trace the first epoch only
                profiling = False

            if is_main:
                # Exact reference log format (:209-215).
                log(
                    f"Epoch {epoch + 1}/{num_epochs}, "
                    f"Train Loss: {train_loss:.4f}, "
                    f"Test Loss: {test_loss:.4f}, "
                    f"Test Accuracy: {test_accuracy:.2f}%"
                )

            if consec_skips > guard_cfg.max_consecutive_skips:
                # the firewall is skipping updates back to back: training is
                # not progressing, and the last pre-skip metrics/EF residual
                # may already be suspect — restore last-good instead of
                # checkpointing a wedged trajectory
                if can_roll_back():
                    tracer.end_span(epoch_span, rollback="consecutive_skips")
                    state, epoch = rollback_to_last_good(
                        state, epoch,
                        f"{consec_skips} consecutive non-finite updates skipped",
                    )
                    prev_total_skips = guard_lib.read_skip_counters(state)[0]
                    continue
                raise FloatingPointError(
                    f"non-finite gradients forced {consec_skips} consecutive "
                    "skipped updates and no checkpoint exists to roll back to "
                    "(set save_dir / checkpoint_epoch to arm rollback)"
                )

            if save_dir is not None and epoch % checkpoint_epoch == 0:
                if epoch_skips:
                    # a guarded state is safe to checkpoint (skipped updates
                    # are bitwise no-ops), but never silently: the save and
                    # the skips it survived are one logged fact
                    logger.warning(
                        "checkpointing epoch %d after %d skipped update(s) "
                        "this epoch (total %d)",
                        epoch, epoch_skips, record["skipped_steps"],
                    )
                ckpt.save_on_main(
                    save_dir, epoch, state, keep_last=keep_last,
                    world_size=getattr(ddp, "world_size", None),
                )
            tracer.end_span(
                epoch_span,
                train_loss=float(train_loss),
                skipped_steps=epoch_skips,
            )
            epoch += 1
    except TrainingPreempted:
        raise  # emergency_stop already dumped the "preempt" recording
    except guard_lib.ReplicaDesync:
        if flight is not None:
            flight.dump("desync")
        raise
    except BaseException:
        if flight is not None:
            flight.dump("exception")
        raise
    finally:
        # An exception mid-epoch (preemption, NaN guard, a worker crash) must
        # not lose the trace — it is the post-mortem artifact — nor leave the
        # JSONL metrics record unflushed/truncated. The live plane tears
        # down too: endpoint closed, flight ring deregistered.
        if snap_engine is not None:
            snap_engine.close()
        tel.finish()
        stop_profiler()
        if tracer.enabled:
            # the causal artifact lands on EVERY exit path (clean drain,
            # preempt, crash): the typed summary goes into the history
            # stream before it closes, the Chrome trace next to it — spans
            # still open (an interrupted epoch) export flagged `open`
            metrics_writer.write(stamp("trace_summary", tracer.summary_record()))
            tracer.export()
        metrics_writer.close()
        if exporter is not None:
            exporter.stop()
        if flight is not None:
            flight_lib.uninstall(flight)

    if is_main:
        log(f"Finished Training on process {jax.process_index()}.")
    return state, history
