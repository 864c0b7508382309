"""YAML config system — schema parity with the reference's settings file
(local_settings.yaml:1-13; parsed identically in all three __main__ blocks,
multi-GPU-training-torch.py:282-308).

Kept: ``script_path``, ``out_dir``, ``optional_args.{set_epoch,print_rand}``,
and the provenance copy of the settings file into ``out_dir`` (:300-303).
Retargeted: ``local.device: tpu`` with a ``local.tpu`` block (accelerator
type + num_chips) replacing the role of ``local.condor.num_gpus`` as the
world-size source; the ``local.condor`` block remains supported for the
condor submission path. New optional ``training`` block exposes the
constants the reference hardcodes (batch sizes 128/100, Adam lr 1e-3,
epochs 20, checkpoint every 5 — BASELINE.md workload constants).
"""

from __future__ import annotations

import os
import shutil
from typing import Any, Dict, Optional

import yaml

# Reference-hardcoded workload constants (BASELINE.md).
TRAINING_DEFAULTS = {
    "model": "alexnet",
    "dataset": "cifar10",
    "data_root": "./data",
    "train_batch_size": 128,  # per replica, multi-GPU-training-torch.py:88
    "test_batch_size": 100,  # per replica, :95
    "learning_rate": 0.001,  # :249
    "num_epochs": 20,  # :166
    "checkpoint_epoch": 5,  # :167
    "image_size": 224,  # data_and_toy_model.py:14
    "flip": None,  # RandomHorizontalFlip (:15); None -> on except for digits
    "compute_dtype": "float32",  # activation dtype: bfloat16 = mixed precision
    # (f32 master params; bf16 activations through the MXU — BASELINE.md)
    "seed": None,  # None -> fresh per run, like torch initial_seed
    "mode": "shard_map",
    "sync_bn": False,
    "scan_steps": "auto",  # K train steps fused per dispatch (lax.scan);
    # "auto" = size-resolved: up to 64, capped by a ~256MB staged-chunk
    # budget (32 when the batch size in bytes is unknowable)
    "clip_grad_norm": None,  # clip the cross-replica-AVERAGED grad (README's
    # clip-before-aggregate caveat: clipping per-shard grads then averaging
    # would differ; tpuddp clips after the pmean, identically on all replicas)
    "remat": False,  # jax.checkpoint: recompute activations in backward
    "weight_update_sharding": False,  # ZeRO-1 on ICI (arxiv 2004.13336):
    # reduce-scatter grads, 1/N-shard optimizer update per chip (moments
    # sharded over the data axis), all-gather params. shard_map mode only.
    "comm_hook": "none",  # gradient-comm hook (torch DDP comm-hook analog,
    # parallel/comm.py): "bf16" = bucketed bf16-compressed allreduce (half
    # the gradient interconnect bytes on the explicit path); "bf16_ef" adds
    # the persistent error-feedback residual (checkpointed) so compression
    # error doesn't bias convergence
    "bucket_cap_mb": 25,  # comm-hook bucket size cap (torch's bucket_cap_mb):
    # small tensors coalesce into one collective per <= cap-sized bucket
    "comm_topology": "flat",  # gradient-reduction topology (parallel/comm.py):
    # "hierarchical" = intra-host f32 reduce-scatter over the factored mesh's
    # "local" axis, COMPRESSED inter-host exchange over "host", all-gather —
    # only the compressed shard crosses the slow link. Explicit path
    # (mode: shard_map) only; excludes weight_update_sharding.
    "topk_density": 0.1,  # comm_hook: topk_ef's keep fraction per bucket
    # (int8 values + int32 indices + per-bucket scale on the wire; 0.1 =>
    # ~87.5% fewer gradient bytes, with the unsent complement riding the
    # error-feedback residual)
    "optimizer": "adam",  # adam | sgd | sgdw | lars | lamb (tpuddp/optim.py).
    # lars/lamb apply per-LAYER trust ratios (You et al. 1708.03888 /
    # 1904.00962, the MLPerf large-batch recipe) so the bandwidth a
    # compressed hook frees converts into bigger global batches that still
    # converge; sgdw is the trust-ratio-free decoupled-decay baseline.
    "weight_decay": 0.0,  # decoupled weight decay for sgdw/lars/lamb (adam/
    # sgd keep their torch-parity L2-into-grad convention via this knob too)
    "momentum": 0.9,  # momentum for sgd/sgdw/lars
    "trust_coefficient": 0.001,  # LARS eta (the layer-wise LR scale)
    "prefetch": True,  # background-thread host batch prefetch
    "pipeline": None,  # async pipeline block (training/pipeline.py): None/
    # true -> overlapped defaults {depth: 2, host_workers: 2, device_augment:
    # true, sync_readback: false}; false -> the synchronous A/B reference
    # (no lookahead, blocking readback per dispatch); a dict overrides the
    # defaults with unknown-key refusal. Bitwise-identical at every depth.
    "deferred_metrics": False,  # managed path: epoch-end (not per-batch) metric sync
    "fuse_steps": "auto",  # managed path: K step()s per dispatch (auto, with
    # deferred_metrics: 32, capped by a ~256MB queued-batch staging budget)
    "gradient_accumulation_steps": 1,  # one averaged update every N micro-batches (both paths)
    "optimizer_state_dtype": None,  # Adam m/v storage dtype ("bfloat16" halves
    # optimizer HBM traffic; math stays f32). None -> params' dtype.
    "pretrained_path": None,  # torch checkpoint to fine-tune from (alexnet,
    # vgg11/13/16, resnet18/34; their _s2d names are aliases of the plain ones)
    "num_classes": None,  # None -> derived from training.dataset
    "resume": False,  # restore the newest checkpoint from out_dir (native:
    # ckpt_{epoch}.npz full TrainState; managed: state_{epoch}.npz)
    "auto_resume": False,  # resilience resume: restore the newest INTACT
    # checkpoint at loop entry (corrupt ones skipped; a preemption-drain
    # emergency save redoes its interrupted epoch). Env: TPUDDP_AUTO_RESUME=1
    # lets a scheduler requeue the exact same command after exit 75.
    "reshard_on_mismatch": False,  # elastic mesh failover: a checkpoint
    # written on a different (data, model) mesh shape is re-shaped in-memory
    # by the cross-topology reshaper (training/reshard.py) at restore time
    # instead of refusing with TopologyMismatch. Opt-in because a reshard
    # can reset the error-feedback residual (model-width changes) — the
    # reshard lands typed topology_change/comm_state_reset event rows.
    "keep_last": None,  # checkpoint retention: prune all but the K newest
    # ckpt_{epoch}.npz (+ .sha256 manifests) after each save; None keeps all
    "snapshot": None,  # async step-granular checkpointing (training/
    # snapshot.py): None/false -> off (epoch-granular checkpoints only);
    # true -> defaults {every_steps: 50, async: true, inflight: 2,
    # peer_redundancy: false}; a dict overrides the defaults with unknown-key
    # refusal. Armed, the loop snapshots TrainState every N optimizer steps
    # between dispatches (on-device copy + background writer — no step
    # stall), records a v4 data cursor (epoch, step, plan key, partial
    # accumulator) so auto_resume continues the interrupted epoch AT the
    # snapshot step with zero batches replayed, and with peer_redundancy
    # spills each process's shard bytes to its ring neighbor's directory so
    # one lost host directory still restores.
    "guard": None,  # numerical guard block (resilience/guard.py): true, or
    # {max_consecutive_skips, audit_every_n_epochs, on_desync, max_rollbacks}.
    # Arms the in-step non-finite-gradient firewall (a poisoned update is a
    # bitwise no-op counted in TrainState.skipped_steps), the cross-replica
    # desync auditor (wrap-time + every N epochs; divergence -> exit 77 or
    # rollback), and the epoch driver's rollback-to-last-good. None/false:
    # strict no-op — the step lowers to the identical HLO.
    "synthetic_n": None,  # (train, test) sizes for the synthetic dataset /
    # fallback; None -> (2048, 512)
    "step_stats_every": 0,  # telemetry window (tpuddp/observability): N > 0
    # writes one `step_stats` record (step-time p50/p95/p99/max, samples/sec,
    # MFU) to history.jsonl every N train steps — ONE host-side device fence
    # per window, nothing in the compiled step. 0 (default) disables window
    # rows; epoch rows always carry the full-epoch percentiles either way.
}

# Serving-engine knobs (tpuddp/serving/) — the ``serving`` block of a
# settings file, consumed by ``python -m tpuddp.serving`` and tools/loadgen.py.
# Same unknown-key-refusal contract as the ``training`` block.
SERVING_DEFAULTS = {
    "model": "toy_mlp",  # model-zoo name (tpuddp/models)
    "num_classes": 10,
    "input_shape": [32, 32, 3],  # one sample's x shape (no batch axis) — the
    # shape requests carry and the checkpoint template is initialized from
    "checkpoint_dir": None,  # restore the newest INTACT checkpoint from here
    # via training/checkpoint.restore_latest (sha256-verified, corrupt files
    # skipped); None -> fresh seeded init (CI / loadgen worlds)
    "checkpoint_prefix": "auto",  # which checkpoint family to restore:
    # "ckpt" (native TrainState files), "state" (managed full-state files),
    # or "auto" -> whichever family has the newest intact file
    "num_replicas": "auto",  # independent model replicas, one per local
    # device; "auto" -> every local device
    "max_batch_size": 32,  # coalescing ceiling: requests stack into
    # power-of-two row buckets up to this (compile cache holds at most
    # log2(max)+1 programs per sample shape)
    "max_queue_depth": 256,  # admission control: total queued requests
    # beyond this are rejected with reason "queue_full"
    "per_tenant_quota": None,  # max queued requests per tenant (None -> no
    # per-tenant bound); excess rejected with reason "tenant_quota"
    "batch_timeout_ms": 2.0,  # how long a dispatch loop waits for more rows
    # after the first request is in hand (latency/occupancy tradeoff)
    "stats_window": 64,  # completed requests per serving_stats history row
    "unhealthy_after": 3,  # graceful degradation: K consecutive dispatch
    # errors mark a replica unhealthy (stop routing to it, emit a
    # replica_unhealthy event row) and send it to PROBATION (see the
    # survivability knobs below). 0 never marks (every batch on a broken
    # replica fails individually).
    # -- survivability knobs (tpuddp/serving/survive.py, README "Serving
    # survivability"):
    "request_ttl_s": None,  # admission-time deadline: a request still
    # QUEUED this long after submit is shed with reason deadline_exceeded
    # before it wastes device time (in-flight work is never deadline-
    # killed); None -> no TTL. Clients may pass a tighter per-call
    # deadline_s to submit() either way.
    "max_recoveries": 2,  # lifetime probation episodes per replica: an
    # unhealthy replica rebuilds + canaries with jittered backoff and
    # rejoins routing on success (replica_recovered event); past this many
    # rejoins the next incident removes it permanently (the fallback, not
    # the policy)
    "recovery_attempts": 2,  # rebuild+canary tries within one probation
    # episode (resilience/retry.py jittered exponential backoff between)
    "recovery_backoff_s": 0.1,  # base backoff between in-episode tries
    "retry_budget": 0,  # per-tenant transient-dispatch retry tokens: a
    # failed batch's requests re-enter the queue (front of lane) within
    # this budget instead of failing through; tokens are refunded when a
    # retried request succeeds. 0 disables (failures surface immediately).
    "seed": 0,  # fresh-init parameter seed (ignored with a checkpoint)
    "decode": None,  # autoregressive decode block (tpuddp/serving/decode/):
    # None -> request-granularity CNN serving only; a dict (or true for all
    # defaults) arms the token-level engine — see DECODE_DEFAULTS. Same
    # unknown-key-refusal contract as every other block.
}


# Autoregressive decode knobs (tpuddp/serving/decode/) — the
# ``serving.decode`` block, consumed by ``python -m tpuddp.serving --decode``
# and ``tools/loadgen.py --decode``. Same unknown-key-refusal contract.
DECODE_DEFAULTS = {
    "model": "transformer_tiny",  # model-zoo name; must be a TransformerLM
    # family member (prefill/decode_step protocol, tpuddp/models/transformer.py)
    "vocab_size": 256,  # token id space (the model's num_classes)
    "checkpoint_dir": None,  # restore params via the integrity path (the
    # request-granularity engine's contract); None -> fresh seeded init
    "checkpoint_prefix": "auto",
    "num_replicas": 1,  # independent decode replicas, each with its own KV
    # pool + slot set + loop; "auto" -> every local device
    "max_slots": 8,  # the fixed decode batch width: EVERY decode step runs
    # the one compiled (max_slots, 1) program — sequences join/leave slots
    # per step, the shape never changes, compile storms are structurally
    # impossible on the decode path
    "kv_blocks": 64,  # KV-pool blocks per replica (block 0 is the reserved
    # garbage block, so kv_blocks - 1 are allocatable)
    "kv_block_size": 16,  # tokens per KV block
    "max_seq_len": 128,  # prompt + generated ceiling per sequence (also the
    # position-embedding table length the model must cover)
    "max_new_tokens": 32,  # per-request generation cap (requests may ask
    # for fewer, never more)
    "stop_token": None,  # token id that terminates a sequence when sampled
    # (consumed, not emitted); None -> max_new_tokens is the only terminator
    "temperature": 0.0,  # 0 = greedy argmax; > 0 = softmax sampling with a
    # per-sequence deterministic stream (batch composition cannot change it)
    "max_queue_depth": 256,  # admission control, as the outer serving block
    "per_tenant_quota": None,
    "stats_window": 64,  # generated tokens per decode_stats history row
    # -- survivability knobs (tpuddp/serving/survive.py): same semantics as
    # the outer serving block. A decode replica that dies mid-stream parks
    # its live sequences into host-side session journals; they fail over
    # to a healthy replica (or to this one, once it passes probation) and
    # continue BITWISE-equal to an undisturbed run. No retry_budget here:
    # the failover journal is the decode path's retry mechanism.
    "request_ttl_s": None,  # shed requests still queued this long after
    # submit (deadline_exceeded); in-flight sequences are never killed
    "max_recoveries": 2,  # lifetime probation episodes per decode replica
    "recovery_attempts": 2,  # rebuild-KV-pool + canary tries per episode
    "recovery_backoff_s": 0.1,  # base jittered backoff between tries
    "max_failovers": 1,  # per-SESSION failover episodes, charged only to
    # the attributed CULPRIT of a place-phase incident: past the budget
    # the request fails with the dispatch error instead of re-parking —
    # the poisoned-request firewall (a request whose own content kills
    # any dispatch must not ride its journal around the pool; innocent
    # sessions parked by someone else's incident ride free). 0 = a
    # culprit is never re-parked (legacy stream-dies behavior).
    "seed": 0,  # fresh-init parameter seed (ignored with a checkpoint)
}


def decode_config(serving: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Resolve a resolved serving block's ``decode`` sub-block: ``None``/
    ``False`` -> None (no decode engine), ``True`` -> all defaults, a dict
    -> defaults + overrides with unknown-key refusal."""
    block = serving.get("decode")
    if block is None or block is False:
        return None
    if block is True:
        cfg = dict(DECODE_DEFAULTS)
        cfg, _ = apply_tune_overlay(cfg, section="decode")
        return cfg
    if not isinstance(block, dict):
        raise ValueError(
            f"serving.decode must be a mapping or bool, got {block!r}"
        )
    cfg = _merge_refusing_unknown(DECODE_DEFAULTS, block, "serving.decode")
    cfg, _ = apply_tune_overlay(cfg, section="decode")
    return cfg


# Live telemetry plane knobs (tpuddp/observability/{exporter,aggregate,
# flight}.py) — the ``observability`` block of a settings file, consumed by
# both training entrypoints, the serving engine, and tools/loadgen.py.
# Same unknown-key-refusal contract as the ``training`` block.
OBSERVABILITY_DEFAULTS = {
    "exporter": False,  # opt-in /metrics + /healthz + /snapshot HTTP endpoint
    # (observability/exporter.py): true serves on exporter_host:exporter_port;
    # everything it publishes is host-side state the per-window fence already
    # materialized — no new device fences, HLO untouched
    "exporter_host": "127.0.0.1",  # bind address (0.0.0.0 to scrape off-host)
    "exporter_port": 0,  # 0 = ephemeral; the bound port lands in
    # <out_dir>/exporter.port and the run_meta observability header field
    "aggregate": True,  # multi-host pod aggregation: each host publishes its
    # last-window telemetry shard through the heartbeat-file channel
    # (resilience/watchdog.py line 2); the main process merges shards into
    # pod-level percentiles every window. Inert on single-process runs.
    "straggler_ratio": 1.5,  # a host is straggling when its window step-time
    # p50 exceeds ratio x the pod median ...
    "straggler_windows": 3,  # ... for this many CONSECUTIVE fresh windows —
    # then exactly one typed `straggler` event row lands in history.jsonl
    "flight_recorder": True,  # bounded in-memory ring of the last N history
    # records per kind (step_stats/event/epoch/serving_stats), dumped to
    # flightrec_<reason>.json on abnormal exits (preempt 75 / watchdog 76 /
    # desync 77 / unhandled exception / serving dispatch death)
    "flight_capacity": 64,  # ring length per record kind
    "tracing": False,  # causal tracing plane (observability/trace.py):
    # host-side span trees through training (epoch/stage/dispatch/
    # collective/readback), serving (request/admission/queue_wait/prefill/
    # decode_step + failover links) and the fleet controller, exported as a
    # Perfetto-loadable trace_<role>.json at drain and served live on the
    # exporter's /trace endpoint. Pure host bracketing: zero new device
    # fences, HLO and loss trajectory identical tracing on/off.
    "trace_capacity": 4096,  # completed-span ring length per process
    # (oldest spans dropped past it, counted in the trace_summary record)
    "advisor": False,  # arm the autotuning advisor's crash hook
    # (observability/advisor.py): on preempt/exception the flight recorder
    # dumps the PENDING (unendorsed) knob recommendation over this run dir
    # as a `pending_tune` context block, so a crash never silently discards
    # the evidence that was about to be acted on. Read-only: the advisor
    # never changes a knob itself — applying one is $TPUDDP_TUNE_OVERLAY's
    # job (the fleet tuner / tools/autotune.py), and advisor-off runs are
    # bitwise- and HLO-identical to pre-advisor behavior.
}


# 2-D mesh knobs (tpuddp/parallel/mesh2d.py) — the top-level ``parallel``
# block of a settings file: how the device world factors into the
# ("data", "model") grid. Same unknown-key-refusal contract as every block.
PARALLEL_DEFAULTS = {
    "data": "auto",  # data-parallel width; "auto" -> world_size / model
    "model": 1,  # tensor-parallel width (1 = plain DDP, today's behavior —
    # the 2-D mesh with model=1 collapses to the flat data mesh and lowers
    # to byte-identical HLO). > 1 shards the transformer family's
    # attention/MLP/vocab weights 1/M per chip (parallel/tensor.py).
}


def parallel_config(settings: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the settings file's ``parallel`` block over
    :data:`PARALLEL_DEFAULTS`, refusing unknown keys."""
    return resolve_parallel(settings.get("parallel"))


def resolve_parallel(block) -> Dict[str, Any]:
    """Resolve a ``parallel`` block (None/dict) to the full knob dict.

    ``$TPUDDP_MODEL_SIZE`` overrides the model width the way
    ``$TPUDDP_WORLD_SIZE`` overrides the world: it is the restart
    supervisor's / fleet controller's elastic-mesh lever — a relaunch after
    capacity loss sets both so the child derives ``data = world / model``
    on the surviving devices. The override also resets an explicit ``data``
    to ``"auto"`` (the settings file's factorization was for the OLD world)."""
    if block is None:
        cfg = dict(PARALLEL_DEFAULTS)
    elif not isinstance(block, dict):
        raise ValueError(f"parallel block must be a mapping, got {block!r}")
    else:
        cfg = _merge_refusing_unknown(PARALLEL_DEFAULTS, block, "parallel")
    env_model = os.environ.get("TPUDDP_MODEL_SIZE")
    if env_model:
        cfg["model"] = int(env_model)
        cfg["data"] = "auto"
    model = int(cfg["model"])
    if model < 1:
        raise ValueError(f"parallel.model must be >= 1, got {cfg['model']!r}")
    cfg["model"] = model
    if cfg["data"] != "auto":
        data = int(cfg["data"])
        if data < 1:
            raise ValueError(f"parallel.data must be >= 1 or 'auto', got {cfg['data']!r}")
        cfg["data"] = data
    return cfg


def mesh_from(
    parallel,
    world_size: Optional[int] = None,
    comm_topology: str = "flat",
    devices=None,
    backend: Optional[str] = None,
):
    """Build the run's device mesh from the ``parallel`` block.

    ``model=1`` keeps today's meshes exactly: the flat data mesh, or the
    factored ``("host", "local")`` mesh under ``comm_topology:
    hierarchical``. ``model > 1`` builds the 2-D ``("data", "model")`` grid
    (tpuddp/parallel/mesh2d.py). Refused loudly, never guessed:

    - ``data * model != device_count`` (an explicit ``data`` that does not
      tile the world would silently train a different replica count);
    - ``hierarchical`` + ``model > 1`` (the factored data axis and the model
      axis would need a 3-D mesh the comm hooks do not express).
    """
    from tpuddp.parallel.mesh import data_mesh, hierarchical_mesh, local_mesh_devices
    from tpuddp.parallel.mesh2d import mesh2d

    cfg = resolve_parallel(parallel)
    model = cfg["model"]
    if comm_topology == "hierarchical" and model > 1:
        raise ValueError(
            "parallel.model > 1 with comm_topology='hierarchical' is "
            "refused: pick the 2-D ('data', 'model') mesh OR the factored "
            "('host', 'local') data axis, not both"
        )
    if model == 1 and cfg["data"] == "auto":
        if comm_topology == "hierarchical":
            return hierarchical_mesh(world_size, devices=devices, backend=backend)
        if devices is not None:
            from tpuddp.parallel.mesh import make_mesh

            return make_mesh(devices)
        return data_mesh(world_size, backend)
    if devices is None:
        devices = local_mesh_devices(world_size, backend)
    world = len(devices)
    data = cfg["data"]
    if data == "auto":
        if world % model:
            raise ValueError(
                f"parallel.model={model} does not tile the {world}-device "
                "world; data * model must equal the device count"
            )
        data = world // model
    if data * model != world:
        raise ValueError(
            f"parallel: data={data} x model={model} != device count {world}; "
            "the mesh must tile the world exactly (set data: auto to derive it)"
        )
    if model == 1 and comm_topology == "hierarchical":
        return hierarchical_mesh(world_size, devices=devices, backend=backend)
    return mesh2d(data, model, devices=devices)


def observability_config(settings: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the settings file's ``observability`` block over
    :data:`OBSERVABILITY_DEFAULTS`, refusing unknown keys."""
    return resolve_observability(settings.get("observability"))


def resolve_observability(block) -> Dict[str, Any]:
    """Resolve an ``observability`` block (None/bool/dict) to the full knob
    dict. ``None``/``True`` -> defaults (exporter off, aggregation + flight
    on); ``False`` -> the whole live plane off; a dict overrides the
    defaults with unknown-key refusal. ``exporter`` itself accepts a dict
    (``{host, port}``) as shorthand for the three exporter knobs."""
    if block is None or block is True:
        return dict(OBSERVABILITY_DEFAULTS)
    if block is False:
        off = dict(OBSERVABILITY_DEFAULTS)
        off.update(exporter=False, aggregate=False, flight_recorder=False)
        return off
    if not isinstance(block, dict):
        raise ValueError(
            f"observability block must be a mapping or bool, got {block!r}"
        )
    block = dict(block)
    exporter = block.get("exporter")
    if isinstance(exporter, dict):
        unknown = set(exporter) - {"host", "port"}
        if unknown:
            raise ValueError(
                f"unknown observability.exporter key(s) {sorted(unknown)}; "
                "expected host, port"
            )
        if "host" in exporter:
            block.setdefault("exporter_host", exporter["host"])
        if "port" in exporter:
            block.setdefault("exporter_port", exporter["port"])
        block["exporter"] = True
    return _merge_refusing_unknown(
        OBSERVABILITY_DEFAULTS, block, "observability"
    )


def _merge_refusing_unknown(defaults, overrides, block: str):
    """Defaults + overrides, refusing unknown keys with a did-you-mean hint —
    a typo'd knob silently ignored would run a different configuration than
    the file says. Shared by the ``training`` and ``serving`` blocks."""
    unknown = set(overrides) - set(defaults)
    if unknown:
        import difflib

        hints = []
        for k in sorted(unknown):
            close = difflib.get_close_matches(k, defaults, n=1)
            hints.append(f"{k!r}" + (f" (did you mean {close[0]!r}?)" if close else ""))
        raise ValueError(
            f"unknown {block} key(s): {', '.join(hints)}. Known keys: "
            f"{sorted(defaults)}"
        )
    cfg = dict(defaults)
    cfg.update(overrides)
    return cfg


def serving_config(settings: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the settings file's ``serving`` block over
    :data:`SERVING_DEFAULTS`, refusing unknown keys (the ``training.guard``
    contract). ``$TPUDDP_SERVING_REPLICAS`` overrides ``num_replicas`` the
    way ``$TPUDDP_WORLD_SIZE`` overrides the training world
    (:func:`world_size_from`): the fleet controller resizes a serving job
    by draining it (exit 75) and relaunching the same command with this
    set — one elastic contract for both job kinds. A ``serving`` section of
    ``$TPUDDP_TUNE_OVERLAY`` (the fleet tuner's knob lever) merges last."""
    cfg = _merge_refusing_unknown(
        SERVING_DEFAULTS, settings.get("serving") or {}, "serving"
    )
    env = os.environ.get("TPUDDP_SERVING_REPLICAS")
    if env:
        cfg["num_replicas"] = int(env)
    cfg, _ = apply_tune_overlay(cfg, section="serving")
    return cfg


# ---------------------------------------------------------- tune overlay --
# The fleet tuner's knob lever (tpuddp/tune/online.py): a JSON object in
# this env var carries per-section config diffs plus the provenance fields
# that land in run_meta.tuning. It rides the drain-and-relaunch contract
# the way $TPUDDP_WORLD_SIZE does — the controller mutates the supervisor's
# env and SIGTERMs the child; the relaunch resolves its config THROUGH the
# overlay. Absent env = advisor off = bitwise-identical config resolution.
TUNE_OVERLAY_ENV = "TPUDDP_TUNE_OVERLAY"
_TUNE_OVERLAY_SECTIONS = ("training", "serving", "decode")


def _tune_overlay() -> Optional[Dict[str, Any]]:
    """Parse ``$TPUDDP_TUNE_OVERLAY``; None when unset. A garbled overlay
    refuses loudly — silently training the BASELINE config while run_meta
    claims a tuned one would poison every downstream A/B comparison."""
    raw = os.environ.get(TUNE_OVERLAY_ENV)
    if not raw:
        return None
    import json

    try:
        overlay = json.loads(raw)
    except ValueError as e:
        raise ValueError(f"${TUNE_OVERLAY_ENV} is not valid JSON: {e}")
    if not isinstance(overlay, dict):
        raise ValueError(
            f"${TUNE_OVERLAY_ENV} must be a JSON object, got {overlay!r}"
        )
    unknown = set(overlay) - set(_TUNE_OVERLAY_SECTIONS) - {
        "source", "rule", "generation"
    }
    if unknown:
        raise ValueError(
            f"unknown ${TUNE_OVERLAY_ENV} key(s) {sorted(unknown)}; expected "
            f"sections {_TUNE_OVERLAY_SECTIONS} plus source/rule/generation"
        )
    return overlay


def apply_tune_overlay(
    cfg: Dict[str, Any], section: str = "training"
) -> tuple:
    """Merge ``$TPUDDP_TUNE_OVERLAY``'s ``section`` diff over a RESOLVED
    config dict. Returns ``(config, tuning_provenance)`` — provenance is
    None when no overlay is set (the advisor-off identity path: the input
    dict is returned untouched, not copied). Unknown knobs refuse with the
    config system's did-you-mean contract; dict-valued knobs (pipeline,
    snapshot, guard) merge shallowly so a one-field diff does not clobber
    its siblings."""
    overlay = _tune_overlay()
    if overlay is None:
        return cfg, None
    diff = overlay.get(section) or {}
    if not isinstance(diff, dict):
        raise ValueError(
            f"${TUNE_OVERLAY_ENV}.{section} must be an object, got {diff!r}"
        )
    merged = dict(cfg)
    if diff:
        # knob names validate against the SECTION's full default set, not
        # just the incoming dict — callers hand partial dicts (a worker's
        # hand-built training block) and a knob absent from the partial is
        # still a real knob the overlay may set
        defaults = {
            "training": TRAINING_DEFAULTS,
            "serving": SERVING_DEFAULTS,
            "decode": DECODE_DEFAULTS,
        }.get(section) or {}
        known = set(defaults) | set(cfg)
        unknown = set(diff) - known
        if unknown:
            raise ValueError(
                f"${TUNE_OVERLAY_ENV}.{section} carries unknown knob(s) "
                f"{sorted(unknown)}; known: {sorted(known)}"
            )
        for knob, value in diff.items():
            if isinstance(value, dict) and isinstance(merged.get(knob), dict):
                merged[knob] = {**merged[knob], **value}
            else:
                merged[knob] = value
    return merged, tuning_provenance_from_env(section=section)


def tuning_provenance_from_env(section: str = "training") -> Optional[dict]:
    """The ``run_meta.tuning`` block (schema v12): which overlay this run's
    knobs came from. None (the required key's null value) when no overlay
    is set — a reader must distinguish "human-chosen knobs" from "the fleet
    tuner's generation-N diff"."""
    overlay = _tune_overlay()
    if overlay is None:
        return None
    return {
        "source": overlay.get("source") or "overlay",
        "rule": overlay.get("rule"),
        "generation": overlay.get("generation"),
        "applied": {
            sec: overlay[sec]
            for sec in _TUNE_OVERLAY_SECTIONS
            if isinstance(overlay.get(sec), dict) and overlay[sec]
        },
        "section": section,
    }


# Label-space size by dataset name; the reference hardcodes 10 because its only
# dataset is CIFAR-10 (data_and_toy_model.py:44's Linear(4096, 10)).
DATASET_NUM_CLASSES = {
    "cifar10": 10,
    "synthetic": 10,
    "digits": 10,
}


def num_classes_from(training: Dict[str, Any]) -> int:
    """Head size for the configured dataset: explicit ``training.num_classes``
    wins, else derived from ``training.dataset``."""
    nc = training.get("num_classes")
    if nc is not None:
        return int(nc)
    ds = str(training.get("dataset") or "cifar10")
    if ds not in DATASET_NUM_CLASSES:
        raise ValueError(
            f"cannot derive num_classes for dataset {ds!r}; set "
            "training.num_classes explicitly"
        )
    return DATASET_NUM_CLASSES[ds]


def load_settings(path: str) -> Dict[str, Any]:
    with open(path, "r") as f:
        settings = yaml.safe_load(f)
    if not isinstance(settings, dict):
        raise ValueError(f"settings file {path} did not parse to a mapping")
    return settings


def prepare_out_dir(settings: Dict[str, Any], settings_file: str) -> str:
    """mkdir out_dir + copy the settings file into it for provenance —
    the reference's __main__ ritual (multi-GPU-training-torch.py:293-303)."""
    out_dir = settings["out_dir"]
    os.makedirs(out_dir, exist_ok=True)
    dest = os.path.join(out_dir, os.path.basename(settings_file))
    if os.path.abspath(dest) != os.path.abspath(settings_file):
        with open(dest, "w") as f:
            yaml.dump(settings, f)
    return out_dir


def world_size_from(settings: Dict[str, Any]) -> Optional[int]:
    """World size: ``$TPUDDP_WORLD_SIZE`` (the restart supervisor's elastic
    override — tools/supervise.py shrinks a repeatedly-dying world by
    re-launching the same command with this set), else ``local.tpu.num_chips``
    (TPU-native) or the reference's ``local.condor.num_gpus`` (:306).
    None -> all local devices."""
    env = os.environ.get("TPUDDP_WORLD_SIZE")
    if env:
        return int(env)
    local = settings.get("local", {})
    if "tpu" in local and "num_chips" in local["tpu"]:
        return int(local["tpu"]["num_chips"])
    if "condor" in local and "num_gpus" in local["condor"]:
        return int(local["condor"]["num_gpus"])
    return None


def device_from(settings: Dict[str, Any]) -> Optional[str]:
    """``local.device``: 'tpu' or 'cpu' (the dev/test rung). Maps onto the
    backend ladder's prefer argument."""
    dev = settings.get("local", {}).get("device")
    if dev in (None, "tpu", "cpu"):
        return dev
    if dev == "cuda":
        # GPU settings files from the reference keep working: on a TPU host the
        # ladder resolves to tpu, elsewhere to cpu.
        return None
    raise ValueError(f"unsupported local.device {dev!r} (expected tpu or cpu)")


def rendezvous_from(settings: Dict[str, Any]) -> Dict[str, Any]:
    """``local.rendezvous`` block -> kwargs for ``run_ddp_training``.

    The TPU-native analog of the reference's ``MASTER_ADDR``/``MASTER_PORT``
    rendezvous (multi-GPU-training-torch.py:30-31): ``coordinator_address``
    ("host:port"), ``num_processes``, ``process_id``. Environment overrides
    ``TPUDDP_COORDINATOR`` / ``TPUDDP_NUM_PROCESSES`` / ``TPUDDP_PROCESS_ID``
    let one shared settings file serve every host of a pod — the launcher sets
    the per-host process id in the environment, exactly as torchrun exports
    RANK alongside a shared MASTER_ADDR.
    """
    rdv = dict(settings.get("local", {}).get("rendezvous") or {})
    env = os.environ
    if env.get("TPUDDP_COORDINATOR"):
        rdv["coordinator_address"] = env["TPUDDP_COORDINATOR"]
    if env.get("TPUDDP_NUM_PROCESSES"):
        rdv["num_processes"] = env["TPUDDP_NUM_PROCESSES"]
    if env.get("TPUDDP_PROCESS_ID"):
        rdv["process_id"] = env["TPUDDP_PROCESS_ID"]

    out: Dict[str, Any] = {}
    if rdv.get("coordinator_address"):
        out["coordinator_address"] = str(rdv["coordinator_address"])
    if rdv.get("num_processes") is not None:
        out["num_processes"] = int(rdv["num_processes"])
    if rdv.get("process_id") is not None:
        out["process_id"] = int(rdv["process_id"])
    unknown = set(rdv) - {"coordinator_address", "num_processes", "process_id"}
    if unknown:
        raise ValueError(
            f"unknown local.rendezvous keys {sorted(unknown)}; expected "
            "coordinator_address, num_processes, process_id"
        )
    if out.get("num_processes", 1) > 1:
        if not out.get("coordinator_address") and device_from(settings) != "tpu":
            # Only TPU pods can auto-discover peers (initialize() reads the
            # pod environment; set local.device: tpu to use that). Anywhere
            # else — cpu, unset, or a migrated cuda settings file — a missing
            # coordinator would skip the dev re-exec (which gates on it) yet
            # still reach jax.distributed.initialize(None, ...), dying late
            # with an obscure runtime error; fail clearly here instead.
            raise ValueError(
                "local.rendezvous with num_processes > 1 needs a "
                "coordinator_address (host:port of process 0; set "
                "TPUDDP_COORDINATOR or the YAML key) — or local.device: tpu "
                "to use TPU pod auto-discovery"
            )
        if out.get("coordinator_address") and "process_id" not in out:
            raise ValueError(
                "local.rendezvous with num_processes > 1 needs a process_id "
                "(set TPUDDP_PROCESS_ID per host, or the YAML key)"
            )
    return out


def optional_args_from(settings: Dict[str, Any]) -> Dict[str, Any]:
    return dict(settings.get("optional_args") or {})


OPTIMIZERS = ("adam", "sgd", "sgdw", "lars", "lamb")


def optimizer_from(training: Dict[str, Any]):
    """Build the configured optimizer (``training.optimizer``) — ONE factory
    for both entrypoints, so the knob set and defaults cannot drift between
    the native and managed paths. ``adam`` keeps the reference's exact
    construction (lr + optional bf16 moment storage); the large-batch
    optimizers take the decoupled ``weight_decay`` / ``momentum`` /
    ``trust_coefficient`` knobs (LARS/LAMB per-layer trust ratios,
    tpuddp/optim.py)."""
    from tpuddp import optim

    name = str(training.get("optimizer") or "adam").lower()
    lr = training["learning_rate"]
    wd = float(training.get("weight_decay") or 0.0)
    momentum = float(
        training["momentum"] if training.get("momentum") is not None else 0.9
    )
    if name == "adam":
        return optim.Adam(
            lr=lr,
            weight_decay=wd,
            state_dtype=training.get("optimizer_state_dtype"),
        )
    if training.get("optimizer_state_dtype"):
        raise ValueError(
            "training.optimizer_state_dtype is an Adam knob (bf16 moment "
            f"storage); optimizer {name!r} stores its state in f32"
        )
    if name == "sgd":
        return optim.SGD(lr, momentum=momentum, weight_decay=wd)
    if name == "sgdw":
        return optim.SGDW(lr, momentum=momentum, weight_decay=wd)
    if name == "lars":
        return optim.LARS(
            lr, momentum=momentum, weight_decay=wd,
            trust_coefficient=float(
                training.get("trust_coefficient") or 0.001
            ),
        )
    if name == "lamb":
        return optim.LAMB(lr, weight_decay=wd)
    raise ValueError(
        f"unknown training.optimizer {name!r}; one of {OPTIMIZERS}"
    )


def training_config(settings: Dict[str, Any]) -> Dict[str, Any]:
    """Merge the settings file's ``training`` block over the defaults.
    Unknown keys are REFUSED with a did-you-mean hint — a typo'd knob
    (``wieght_update_sharding``) silently ignored would train a different
    configuration than the file says."""
    cfg = _merge_refusing_unknown(
        TRAINING_DEFAULTS, settings.get("training") or {}, "training"
    )
    cfg, _ = apply_tune_overlay(cfg, section="training")
    return cfg
