"""Typed record schema for ``history.jsonl`` (and the bench artifact).

Every line of ``history.jsonl`` is one JSON object carrying ``type`` (one of
:data:`RECORD_TYPES`) and ``schema_version``:

- ``run_meta`` — the header row, written once at loop start (and again by a
  resumed run appending to an existing file): mesh shape, process/replica
  counts, jax/tpuddp versions, config hash, comm-hook mode, guard config.
- ``epoch``    — one row per completed epoch: losses/accuracy/throughput plus
  step-time percentiles and achieved-MFU fields from the step recorder.
- ``step_stats`` — one row per recorder window (``training.step_stats_every``
  steps) inside an epoch: the intra-epoch resolution that makes a 10x
  step-time regression or a straggler *within* an epoch visible.
- ``event``    — discrete occurrences: rollback, desync, preempt, skipped
  updates, watchdog staleness, profiler captures, serving drain.
- ``serving_stats`` — one row per serving-engine reporting window
  (tpuddp/serving/stats.py): request/completion/reject counts, queue /
  device / end-to-end latency percentiles, throughput, and batch occupancy
  — the SLO record stream of the inference engine.

``tools/tpuddp_inspect.py --validate`` enforces this schema, so drift fails
a gate instead of corrupting downstream consumers. The validators live here
(not in the tool) so writer tests and the CLI share one definition.

Version history: v1 introduced the envelope and the four training record
types; v2 added ``serving_stats``; v3 added the async-pipeline occupancy
fields to ``step_stats`` (``host_stall_ms``, ``inflight_depth``,
``staging_queue_depth`` — tpuddp/training/pipeline.py); v4 added
``comm_topology`` to ``run_meta`` (the comm-compression-v2 topology knob —
flat vs hierarchical multi-hop reduction, parallel/comm.py; the header also
gained the non-required ``comm_density`` / ``grad_comm_bytes_inter_host`` /
``grad_comm_bytes_intra_host`` accounting fields); v5 added the live
telemetry plane's ``observability`` header field (exporter endpoint /
pod-aggregation / flight-recorder provenance — a reader of a v5 history can
tell whether a missing ``straggler`` event means "no straggler" or
"aggregation was off") plus the ``straggler`` typed event and the
``flight_recording`` sidecar artifact (``flightrec_<reason>.json``,
:func:`validate_flight_payload`); v6 added the ``decode_stats`` record (the
autoregressive decode engine's token-level SLO window —
tpuddp/serving/decode/: tokens/sec, time-to-first-token, inter-token
latency percentiles, KV-cache occupancy) and the required run_meta
``decode`` provenance field (null = not a decode run; a decode header
carries the KV-pool geometry, so a reader can tell "no decode windows"
from "this was never a decode engine"); v7 added the serving
survivability layer's accounting (tpuddp/serving/survive.py): the required
run_meta ``survivability`` provenance field (null = not a serving writer;
a serving header carries the TTL / probation / retry-budget knobs), the
required ``shed`` field on ``serving_stats`` and ``decode_stats`` windows
(deadline-expired requests dropped before dispatch) and the required
``failovers`` field on ``decode_stats`` (sessions migrated off a dead
replica), plus the typed ``session_failover`` / ``replica_recovered`` /
``replica_removed`` / ``no_healthy_replica`` event rows; v8 added the
required run_meta ``mesh`` block (the 2-D device-mesh provenance,
tpuddp/parallel/mesh2d.py): ``data``/``model`` axis widths plus the
``tp_rules_hash`` of the tensor-parallel rule table when ``model > 1`` —
a reader of a v8 header can tell a 4-chip pure-DP run from a TP=2xDP=2
run without parsing mesh_shape, and two TP runs sharded under different
rule tables never read as the same configuration. Null for writers with
no mesh (serving headers), but the KEY must exist — absence is drift;
v9 added the causal tracing plane (tpuddp/observability/trace.py): the
required run_meta ``tracing`` provenance field (null = tracing off — a
reader must distinguish "no spans because tracing was off" from
"predates the tracing plane"), the ``trace_summary`` record type (span
and drop accounting plus the slowest-span table, written once at drain
by every traced writer), and the ``trace_<role>.json`` sidecar artifact
(a Chrome-trace-event file with a ``tpuddp`` provenance block,
:func:`validate_trace_payload` — loadable in Perfetto as-is);
v10 added the required run_meta ``comm`` block (the gradient-exchange
execution provenance): its ``overlap`` member records whether the step
ran segmented-backward ({enabled, segments}: histories written while
that second step program existed) or the barrier step and why (the
constant every writer records since; ROADMAP D12). Null for writers
with no gradient exchange (serving headers), but the KEY must exist;
v11 added the required run_meta ``snapshot`` field (the async
step-checkpoint engine, training/snapshot.py): an armed block carries
the resolved config (``every_steps``/``async``/``inflight``/
``peer_redundancy``) plus the writer's identity (prefix, process
index), so a reader of a resumed history can tell which snapshot
cadence produced the checkpoint family it restored from. ``false`` =
the engine was off (epoch-granular checkpoints only); the KEY must
exist — absence is drift, and a reader must distinguish "no step
snapshots because the engine was off" from "predates the engine";
v12 added the autotuning plane (tpuddp/observability/advisor.py +
tpuddp/tune/): the required run_meta ``tuning`` provenance field (null =
advisor off — a tuned-off run must be bitwise-identical to a pre-v12
run; an armed block names the overlay source, rule and generation that
produced the knobs this run trained under), the ``tune_report`` record
type (the ``TUNE_r*.json`` A/B probe artifact: per-rule predicted vs
measured deltas + endorsement verdicts, :func:`validate_tune_payload`)
and the typed ``tune_action`` event rows the fleet tuner appends when it
applies or reverts a knob change through drain-and-relaunch.
Readers accept every version up to their own ``SCHEMA_VERSION`` and
reject newer files; the per-version required-field sets apply at the
version each record CARRIES, so a v2 history (no occupancy fields) stays
valid under a v5 reader.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Dict, Iterable, List, Optional, Tuple

SCHEMA_VERSION = 12

RECORD_TYPES = (
    "run_meta", "epoch", "step_stats", "event", "serving_stats",
    "decode_stats", "trace_summary", "tune_report",
)

# Required keys per record type (beyond the envelope's type/schema_version).
# Values may be null where a metric can legitimately blow up (strict-JSON
# post-mortem rows) or be unknowable (MFU without a known chip peak).
_REQUIRED = {
    "run_meta": (
        "jax_version",
        "tpuddp_version",
        "world_size",
        "process_count",
        "process_index",
        "mesh_shape",
        "comm_hook",
        "guard",
    ),
    "epoch": (
        "epoch",
        "train_loss",
        "test_loss",
        "test_accuracy",
        "train_samples",
        "test_samples",
        "epoch_time_s",
        "samples_per_sec",
        "step_time_ms_p50",
        "step_time_ms_p95",
        "step_time_ms_p99",
        "step_time_ms_max",
        "mfu_p50",
    ),
    "step_stats": (
        "epoch",
        "step_start",
        "steps",
        "step_time_ms_p50",
        "step_time_ms_p95",
        "step_time_ms_p99",
        "step_time_ms_max",
        "samples_per_sec",
    ),
    "event": ("event",),
    "serving_stats": (
        "window",
        "requests",
        "completed",
        "rejected",
        "queue_ms_p50",
        "device_ms_p50",
        "e2e_ms_p50",
        "e2e_ms_p95",
        "e2e_ms_p99",
        "throughput_rps",
        "batch_occupancy",
    ),
    # one row per decode-engine reporting window (tpuddp/serving/decode/):
    # token-granularity throughput + the two latencies token traffic lives
    # by (TTFT, ITL) + the KV-pool pressure gauge. Percentiles may be null
    # in a window that completed zero tokens of its kind (e.g. a drain
    # flush), never absent.
    "decode_stats": (
        "window",
        "tokens",
        "completed",
        "rejected",
        "tokens_per_sec",
        "ttft_ms_p50",
        "ttft_ms_p95",
        "itl_ms_p50",
        "itl_ms_p95",
        "itl_ms_p99",
        "kv_occupancy",
        "active_sequences",
    ),
    # the tracing plane's drain digest (schema v9, observability/trace.py):
    # one row per traced writer — completed-span count, ring drops (the
    # honesty field: a reader knows whether the artifact is the WHOLE run
    # or the newest window of it), still-open spans at drain, per-kind
    # counts, and the slowest-span table.
    "trace_summary": (
        "role",
        "spans",
        "dropped",
        "open_spans",
        "by_kind",
        "slowest",
    ),
    # the autotuner's A/B probe artifact (schema v12, tools/autotune.py +
    # tpuddp/tune/probe.py): ONE JSON object — baseline metrics plus one
    # row per advisor rule carrying the predicted delta it promised, the
    # measured delta the probe observed, and the endorsement verdict. The
    # measured field is the honesty contract: a rule whose measured delta
    # regresses MUST carry endorsed=false, so the fleet tuner never acts
    # on a prediction that failed its own A/B.
    "tune_report": (
        "device",
        "mode",
        "baseline_metrics",
        "results",
    ),
}

# Fields additionally required of records stamped at schema_version >= N:
# applied at the version a record CARRIES (older histories keep validating
# under newer readers). v3: the async pipeline's occupancy accounting.
# v4: the gradient-reduction topology knob in the header (comm compression
# v2 — a run_meta without it cannot say which wire its comm bytes crossed).
_REQUIRED_SINCE = {
    3: {
        "step_stats": (
            "host_stall_ms",
            "inflight_depth",
            "staging_queue_depth",
        ),
    },
    4: {
        "run_meta": ("comm_topology",),
    },
    # v5: the live telemetry plane's provenance. The value may be null (a
    # writer with the whole plane off) but the KEY must exist — absence is
    # drift, and downstream consumers need to distinguish "no straggler
    # events because all hosts were uniform" from "aggregation never ran".
    5: {
        "run_meta": ("observability",),
    },
    # v6: the decode engine's provenance. Null for every non-decode writer
    # (training, request-granularity serving), but the KEY must exist — a
    # reader needs to distinguish "no decode_stats windows because nothing
    # decoded" from "this header predates the decode subsystem".
    6: {
        "run_meta": ("decode",),
    },
    # v7: the serving survivability layer (tpuddp/serving/survive.py).
    # run_meta.survivability is null for non-serving writers but the KEY
    # must exist (a reader must tell "no sheds because the layer was off"
    # from "predates the layer"); serving/decode windows carry their shed
    # counts and decode windows their session-failover counts, so the
    # autoscaler's shed-rate rule and the chaos gate read typed records,
    # not log lines.
    7: {
        "run_meta": ("survivability",),
        "serving_stats": ("shed",),
        "decode_stats": ("shed", "failovers"),
    },
    # v8: the 2-D device-mesh provenance (tpuddp/parallel/mesh2d.py). The
    # value may be null (a writer with no mesh — serving headers) but the
    # KEY must exist: a reader needs to distinguish "pure DP" (model=1)
    # from "predates the 2-D mesh", and a model>1 block carries the
    # tp_rules_hash naming the rule table that sharded the run.
    8: {
        "run_meta": ("mesh",),
    },
    # v9: the causal tracing plane (observability/trace.py). Null for every
    # untraced writer (the default — tracing is opt-in) but the KEY must
    # exist: a reader needs to distinguish "no trace artifact because
    # tracing was off" from "this header predates the tracing plane"; an
    # armed block names the ring capacity and the artifact file.
    9: {
        "run_meta": ("tracing",),
    },
    # v10: the gradient-exchange execution provenance. Null for writers with
    # no gradient exchange (serving headers) but the KEY must exist. An
    # enabled block (a history from the segmented-backward step, since
    # removed) counts its backward segments in ``overlap.segments``; every
    # writer now records the barrier step's constant (ROADMAP D12).
    10: {
        "run_meta": ("comm",),
    },
    # v11: the async step-checkpoint engine's provenance (``snapshot``,
    # training/snapshot.py). ``false`` for writers with the engine off (the
    # default — epoch-granular checkpoints only) but the KEY must exist: a
    # reader of a resumed history needs to distinguish "no step snapshots
    # because the engine was off" from "this header predates step-granular
    # checkpointing". An armed block names the cadence (every_steps), the
    # writer mode (async/inflight) and peer-redundancy placement.
    11: {
        "run_meta": ("snapshot",),
    },
    # v12: the autotuning plane's provenance (``tuning``, tpuddp/tune/).
    # Null for every untuned writer (the default — the advisor is read-only
    # until a human or the fleet tuner applies an overlay) but the KEY must
    # exist: a reader needs to distinguish "these knobs were human-chosen"
    # from "this header predates the autotuner". An armed block names the
    # overlay source (fleet/operator), the rule that proposed it, the
    # overlay generation counter, and the knob diff actually applied — so a
    # before/after pair of resumed headers is self-explaining.
    12: {
        "run_meta": ("tuning",),
    },
}

def stamp(record_type: str, record: dict) -> dict:
    """Return ``record`` wrapped in the schema envelope (type first, so the
    line is eyeball-able with ``head``)."""
    if record_type not in RECORD_TYPES:
        raise ValueError(
            f"unknown record type {record_type!r}; expected one of {RECORD_TYPES}"
        )
    return {"type": record_type, "schema_version": SCHEMA_VERSION, **record}


def config_hash(training: Optional[dict]) -> Optional[str]:
    """Stable short hash of a training-config mapping — the run_meta field
    that answers "were these two runs the same configuration?" without
    embedding the whole config in every history file."""
    if not training:
        return None
    canon = json.dumps(training, sort_keys=True, default=str)
    return hashlib.sha256(canon.encode()).hexdigest()[:16]


def make_run_meta(
    *,
    mesh=None,
    world_size: Optional[int] = None,
    comm_hook: Optional[str] = None,
    comm_topology: Optional[str] = None,
    guard=None,
    observability: Optional[dict] = None,
    decode: Optional[dict] = None,
    survivability: Optional[dict] = None,
    tp_rules_hash: Optional[str] = None,
    tracing: Optional[dict] = None,
    comm: Optional[dict] = None,
    snapshot=None,
    tuning: Optional[dict] = None,
    extra: Optional[dict] = None,
) -> dict:
    """Build the run_meta header row from live run objects.

    ``mesh`` is a ``jax.sharding.Mesh`` (or None); ``guard`` is a
    ``GuardConfig``/dict/None; ``tp_rules_hash`` names the tensor-parallel
    rule table when the mesh carries a model axis (the v8 ``mesh`` block);
    ``extra`` carries entrypoint-level fields (config_hash, model, dataset,
    scan_steps, ...)."""
    import jax

    import tpuddp

    mesh_shape: Optional[Dict[str, int]] = None
    device_kind = None
    if mesh is not None:
        mesh_shape = {
            str(name): int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        }
        if world_size is None:
            world_size = int(mesh.devices.size)
        # the device actually running the step — NOT jax.devices()[0], which
        # reports whatever platform happens to be default on this host (a
        # CPU-ladder run on a TPU-attached host, or vice versa, would lie)
        device_kind = mesh.devices.flat[0].device_kind
    elif jax.devices():
        device_kind = jax.devices()[0].device_kind
    if dataclasses.is_dataclass(guard):
        guard = dataclasses.asdict(guard)
    # required since schema v8: the 2-D mesh block — data/model axis widths
    # (the hierarchical factoring folds into data) plus the TP rule-table
    # hash when the model axis is real. Null when the writer has no mesh.
    mesh_block = None
    if mesh_shape is not None:
        model_width = int(mesh_shape.get("model", 1))
        data_width = 1
        for name, size in mesh_shape.items():
            if name != "model":
                data_width *= int(size)
        mesh_block = {
            "data": data_width,
            "model": model_width,
            "tp_rules_hash": tp_rules_hash if model_width > 1 else None,
        }
    record = {
        "jax_version": jax.__version__,
        "tpuddp_version": tpuddp.__version__,
        "world_size": world_size,
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "mesh_shape": mesh_shape,
        # required since schema v8: the 2-D mesh provenance (null = no mesh)
        "mesh": mesh_block,
        "device_kind": device_kind,
        "comm_hook": comm_hook,
        # required since schema v4: which wire topology the comm bytes
        # crossed (null = no comm configured, e.g. serving headers)
        "comm_topology": comm_topology,
        "guard": guard,
        # required since schema v5: the live telemetry plane's provenance —
        # exporter endpoint (bound port), pod aggregation + straggler knobs,
        # flight recorder (null = the whole plane off, e.g. minimal headers)
        "observability": observability,
        # required since schema v6: the decode engine's provenance (model,
        # slot width, KV-pool geometry; null = not an autoregressive run)
        "decode": decode,
        # required since schema v7: the serving survivability knobs
        # (request TTL, probation bounds, retry budget; null = not a
        # serving writer — training runs have no shedding/failover story)
        "survivability": survivability,
        # required since schema v9: the causal tracing plane's provenance
        # (ring capacity + artifact name; null = tracing off, the default)
        "tracing": tracing,
        # required since schema v10: the gradient-exchange execution
        # provenance — comm.overlap records whether the step ran
        # segmented-backward ({enabled, segments}) or the barrier step and
        # why (null = no gradient exchange, e.g. serving headers)
        "comm": comm,
        # required since schema v11: the async step-checkpoint engine's
        # provenance — resolved config + writer identity when armed, False
        # when off (epoch-granular checkpoints only)
        "snapshot": False if snapshot is None else snapshot,
        # required since schema v12: the autotuning plane's provenance —
        # the overlay source/rule/generation + knob diff this run trained
        # under (null = advisor off, the run's knobs were human-chosen)
        "tuning": tuning,
    }
    if extra:
        record.update(extra)
    return stamp("run_meta", record)


# ------------------------------------------------------------- validation --


def validate_record(record, index: int = 0) -> List[str]:
    """Schema errors for one history record (empty list = valid)."""
    where = f"record {index}"
    if not isinstance(record, dict):
        return [f"{where}: not a JSON object"]
    errors = []
    rtype = record.get("type")
    if rtype not in RECORD_TYPES:
        return [f"{where}: unknown type {rtype!r} (expected one of {RECORD_TYPES})"]
    version = record.get("schema_version")
    if not isinstance(version, int) or version < 1:
        errors.append(f"{where}: schema_version {version!r} is not a positive int")
    elif version > SCHEMA_VERSION:
        errors.append(
            f"{where}: schema_version {version} is newer than this reader's "
            f"{SCHEMA_VERSION}"
        )
    required = list(_REQUIRED[rtype])
    if isinstance(version, int):
        for since, extra in _REQUIRED_SINCE.items():
            if version >= since:
                required += list(extra.get(rtype, ()))
    missing = [k for k in required if k not in record]
    if missing:
        errors.append(f"{where} ({rtype}): missing required field(s) {missing}")
    if rtype == "event" and not isinstance(record.get("event"), str):
        errors.append(f"{where} (event): 'event' must be a string")
    if rtype == "run_meta":
        shape = record.get("mesh_shape")
        if shape is not None and not isinstance(shape, dict):
            errors.append(f"{where} (run_meta): mesh_shape must be an object or null")
        if isinstance(version, int) and version >= 10 and "comm" in record:
            comm = record.get("comm")
            if comm is not None and (
                not isinstance(comm, dict) or "overlap" not in comm
            ):
                errors.append(
                    f"{where} (run_meta): comm must be null or an object "
                    "with an 'overlap' member"
                )
    return errors


def validate_history_records(records: Iterable[dict]) -> List[str]:
    """Schema errors for a whole history (empty list = valid).

    The FIRST record must be ``run_meta``; later ``run_meta`` rows are legal
    (a resumed run appends a fresh header before its epochs)."""
    errors: List[str] = []
    n = 0
    for i, record in enumerate(records):
        n += 1
        if i == 0 and (
            not isinstance(record, dict) or record.get("type") != "run_meta"
        ):
            errors.append(
                "record 0: history must start with a run_meta header row, got "
                f"type {record.get('type') if isinstance(record, dict) else record!r}"
            )
        errors.extend(validate_record(record, i))
    if n == 0:
        errors.append("empty history: no records")
    return errors


def validate_history_file(path: str) -> Tuple[List[str], int]:
    """Parse + validate a ``history.jsonl`` file. Returns (errors, n_records).
    Non-strict JSON (bare NaN/Infinity tokens) is itself a schema error."""

    def _reject(token):
        raise ValueError(f"non-strict JSON token {token}")

    errors: List[str] = []
    records = []
    try:
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                if not line.strip():
                    continue
                try:
                    records.append(json.loads(line, parse_constant=_reject))
                except ValueError as e:
                    errors.append(f"line {lineno}: invalid JSON ({e})")
    except OSError as e:
        return [f"cannot read {path}: {e}"], 0
    errors.extend(validate_history_records(records))
    return errors, len(records)


# Bench artifact (bench_results.json) — a single JSON object, not JSONL.
_BENCH_REQUIRED = ("metric", "value", "unit", "vs_baseline", "device", "configs")
_BENCH_ROW_REQUIRED = ("ms_per_step",)
# every row must carry one RATE: samples/sec/chip (training + request
# serving) or tokens/sec (autoregressive decode curves, loadgen --decode)
_BENCH_ROW_RATES = ("samples_per_sec_per_chip", "tokens_per_sec")


def validate_bench_payload(payload) -> List[str]:
    """Schema errors for a ``bench_results.json`` payload (empty = valid)."""
    if not isinstance(payload, dict):
        return ["bench payload is not a JSON object"]
    errors = [f"missing field {k!r}" for k in _BENCH_REQUIRED if k not in payload]
    configs = payload.get("configs")
    if not isinstance(configs, dict):
        errors.append("'configs' must be an object of name -> row")
        return errors
    for name, row in configs.items():
        if not isinstance(row, dict):
            errors.append(f"config {name!r}: not an object")
            continue
        missing = [k for k in _BENCH_ROW_REQUIRED if k not in row]
        if missing:
            errors.append(f"config {name!r}: missing field(s) {missing}")
        if not any(k in row for k in _BENCH_ROW_RATES):
            errors.append(
                f"config {name!r}: needs one of {_BENCH_ROW_RATES}"
            )
    return errors


def validate_bench_file(path: str) -> Tuple[List[str], int]:
    def _reject(token):
        raise ValueError(f"non-strict JSON token {token}")

    try:
        with open(path) as f:
            payload = json.load(f, parse_constant=_reject)
    except (OSError, ValueError) as e:
        return [f"cannot parse {path}: {e}"], 0
    errors = validate_bench_payload(payload)
    n = len(payload.get("configs", {})) if isinstance(payload, dict) else 0
    return errors, n


# Flight recording (flightrec_<reason>.json) — the crash post-mortem sidecar
# dumped by tpuddp/observability/flight.py on abnormal exit paths. ONE JSON
# object: envelope fields plus per-category rings of ordinary history
# records, so every ring entry validates with the same per-record rules the
# history stream uses.
FLIGHT_TYPE = "flight_recording"
FLIGHT_REASONS = (
    "preempt",          # SIGTERM/SIGINT drain (exit 75)
    "preempt_forced",   # drain blew the grace window; failsafe forced exit 75
    "watchdog",         # a peer's heartbeat went stale (exit 76)
    "desync",           # the guard's auditor found a divergent replica (77)
    "exception",        # unhandled exception in an epoch driver
    "serving_dispatch", # the serving engine lost its last healthy replica
)
_FLIGHT_REQUIRED = (
    "reason",
    "process_index",
    "capacity",
    "counts",
    "records",
)
_FLIGHT_RINGS = ("step_stats", "event", "epoch", "serving_stats", "decode_stats")


def validate_flight_payload(payload) -> List[str]:
    """Schema errors for a flight-recording payload (empty = valid)."""
    if not isinstance(payload, dict):
        return ["flight payload is not a JSON object"]
    errors = []
    if payload.get("type") != FLIGHT_TYPE:
        errors.append(
            f"'type' must be {FLIGHT_TYPE!r}, got {payload.get('type')!r}"
        )
    version = payload.get("schema_version")
    if not isinstance(version, int) or version < 5:
        errors.append(
            f"schema_version {version!r} is not an int >= 5 (flight "
            "recordings were introduced at v5)"
        )
    elif version > SCHEMA_VERSION:
        errors.append(
            f"schema_version {version} is newer than this reader's "
            f"{SCHEMA_VERSION}"
        )
    errors += [f"missing field {k!r}" for k in _FLIGHT_REQUIRED if k not in payload]
    reason = payload.get("reason")
    if "reason" in payload and reason not in FLIGHT_REASONS:
        errors.append(
            f"unknown reason {reason!r}; expected one of {FLIGHT_REASONS}"
        )
    records = payload.get("records")
    if records is not None:
        if not isinstance(records, dict):
            errors.append("'records' must be an object of ring -> [records]")
        else:
            for ring in _FLIGHT_RINGS:
                entries = records.get(ring, [])
                if not isinstance(entries, list):
                    errors.append(f"ring {ring!r} is not a list")
                    continue
                for i, rec in enumerate(entries):
                    for e in validate_record(rec, i):
                        errors.append(f"ring {ring!r}: {e}")
                    if isinstance(rec, dict) and rec.get("type") != ring:
                        errors.append(
                            f"ring {ring!r} record {i}: type "
                            f"{rec.get('type')!r} does not belong in this ring"
                        )
    run_meta = payload.get("run_meta")
    if run_meta is not None:
        for e in validate_record(run_meta, 0):
            errors.append(f"run_meta: {e}")
    return errors


# Trace artifact (trace_<role>.json) — the causal tracing plane's
# Chrome-trace-event sidecar (tpuddp/observability/trace.py), loadable in
# Perfetto as-is. ONE JSON object: ``traceEvents`` (complete "X" span
# events + metadata/flow events) plus a ``tpuddp`` provenance block.
TRACE_TYPE = "trace"
_TRACE_META_REQUIRED = (
    "role",
    "process_index",
    "capacity",
    "spans",
    "dropped",
    "open_spans",
    "by_kind",
    "slowest",
    "clock_sync",
)


def validate_trace_payload(payload) -> List[str]:
    """Schema errors for a trace-artifact payload (empty = valid).

    Nesting is part of the contract: every X event's ``parent_id`` must
    resolve to a span present in the artifact — but only when the ring
    dropped nothing (``tpuddp.dropped == 0``); once the ring has evicted
    old spans, orphaned children of evicted parents are expected, not
    drift."""
    if not isinstance(payload, dict):
        return ["trace payload is not a JSON object"]
    errors = []
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        errors.append("'traceEvents' must be a list")
        events = []
    meta = payload.get("tpuddp")
    if not isinstance(meta, dict):
        return errors + ["missing 'tpuddp' provenance block"]
    if meta.get("type") != TRACE_TYPE:
        errors.append(
            f"tpuddp.type must be {TRACE_TYPE!r}, got {meta.get('type')!r}"
        )
    version = meta.get("schema_version")
    if not isinstance(version, int) or version < 9:
        errors.append(
            f"tpuddp.schema_version {version!r} is not an int >= 9 (trace "
            "artifacts were introduced at v9)"
        )
    elif version > SCHEMA_VERSION:
        errors.append(
            f"tpuddp.schema_version {version} is newer than this reader's "
            f"{SCHEMA_VERSION}"
        )
    errors += [
        f"tpuddp block missing field {k!r}"
        for k in _TRACE_META_REQUIRED
        if k not in meta
    ]
    clock = meta.get("clock_sync")
    if isinstance(clock, dict):
        for k in ("unix_us", "perf_ns"):
            if not isinstance(clock.get(k), (int, float)):
                errors.append(f"clock_sync.{k} is not a number")
    span_ids = set()
    x_events = []
    for i, e in enumerate(events):
        if not isinstance(e, dict) or "ph" not in e:
            errors.append(f"event {i}: not an object with a 'ph' field")
            continue
        if e["ph"] != "X":
            continue
        x_events.append((i, e))
        missing = [k for k in ("name", "ts", "dur", "pid", "tid") if k not in e]
        if missing:
            errors.append(f"event {i} (X): missing field(s) {missing}")
        args = e.get("args")
        if not isinstance(args, dict) or "span_id" not in args or (
            "trace_id" not in args
        ):
            errors.append(
                f"event {i} (X): args must carry span_id and trace_id"
            )
            continue
        span_ids.add(args["span_id"])
    if meta.get("dropped") == 0:
        for i, e in x_events:
            parent = (e.get("args") or {}).get("parent_id")
            if parent is not None and parent not in span_ids:
                errors.append(
                    f"event {i} (X): orphan parent_id {parent} — no such "
                    "span in the artifact (and the ring dropped nothing)"
                )
    return errors


def validate_trace_file(path: str) -> Tuple[List[str], int]:
    """Parse + validate a ``trace_<role>.json`` artifact. Returns
    ``(errors, n_span_events)``; non-strict JSON is itself an error."""

    def _reject(token):
        raise ValueError(f"non-strict JSON token {token}")

    try:
        with open(path) as f:
            payload = json.load(f, parse_constant=_reject)
    except (OSError, ValueError) as e:
        return [f"cannot parse {path}: {e}"], 0
    errors = validate_trace_payload(payload)
    n = 0
    if isinstance(payload, dict) and isinstance(payload.get("traceEvents"), list):
        n = sum(
            1 for e in payload["traceEvents"]
            if isinstance(e, dict) and e.get("ph") == "X"
        )
    return errors, n


# Tune artifact (TUNE_r*.json) — the autotuner's A/B probe report
# (schema v12, tools/autotune.py + tpuddp/tune/probe.py). ONE JSON object
# stamped ``type: tune_report``: envelope + baseline metrics + one result
# row per advisor rule probed.
TUNE_MODES = ("train", "serving")
_TUNE_ROW_REQUIRED = (
    "rule",
    "rule_class",
    "knob",
    "diff",
    "metric",
    "predicted_delta_pct",
    "measured_delta_pct",
    "endorsed",
    "evidence",
)
TUNE_RULE_CLASSES = ("pipeline", "comm", "snapshot", "serving")


def validate_tune_payload(payload) -> List[str]:
    """Schema errors for a ``TUNE_r*.json`` payload (empty = valid).

    The endorsement contract is validated, not just typed: a row whose
    ``measured_delta_pct`` is negative (a regression on its own metric)
    must not carry ``endorsed: true`` — the whole point of the artifact is
    that the fleet never applies a knob the probe watched regress."""
    if not isinstance(payload, dict):
        return ["tune payload is not a JSON object"]
    errors = []
    if payload.get("type") != "tune_report":
        errors.append(
            f"'type' must be 'tune_report', got {payload.get('type')!r}"
        )
    version = payload.get("schema_version")
    if not isinstance(version, int) or version < 12:
        errors.append(
            f"schema_version {version!r} is not an int >= 12 (tune reports "
            "were introduced at v12)"
        )
    elif version > SCHEMA_VERSION:
        errors.append(
            f"schema_version {version} is newer than this reader's "
            f"{SCHEMA_VERSION}"
        )
    errors += [
        f"missing field {k!r}"
        for k in _REQUIRED["tune_report"]
        if k not in payload
    ]
    if "mode" in payload and payload.get("mode") not in TUNE_MODES:
        errors.append(
            f"unknown mode {payload.get('mode')!r}; expected one of {TUNE_MODES}"
        )
    baseline = payload.get("baseline_metrics")
    if "baseline_metrics" in payload and not isinstance(baseline, dict):
        errors.append("'baseline_metrics' must be an object of metric -> value")
    results = payload.get("results")
    if results is None:
        return errors
    if not isinstance(results, list):
        return errors + ["'results' must be a list of rule rows"]
    for i, row in enumerate(results):
        if not isinstance(row, dict):
            errors.append(f"result {i}: not an object")
            continue
        missing = [k for k in _TUNE_ROW_REQUIRED if k not in row]
        if missing:
            errors.append(f"result {i}: missing field(s) {missing}")
        rclass = row.get("rule_class")
        if "rule_class" in row and rclass not in TUNE_RULE_CLASSES:
            errors.append(
                f"result {i}: unknown rule_class {rclass!r}; expected one "
                f"of {TUNE_RULE_CLASSES}"
            )
        if "diff" in row and not isinstance(row.get("diff"), dict):
            errors.append(f"result {i}: 'diff' must be a config-diff object")
        if "evidence" in row and not isinstance(row.get("evidence"), list):
            errors.append(f"result {i}: 'evidence' must be a list of citations")
        measured = row.get("measured_delta_pct")
        if (
            isinstance(measured, (int, float))
            and measured < 0
            and row.get("endorsed") is True
        ):
            errors.append(
                f"result {i}: endorsed=true with a regressing measured "
                f"delta ({measured:+.2f}%) — the probe must refuse"
            )
    return errors


def validate_tune_file(path: str) -> Tuple[List[str], int]:
    """Parse + validate a ``TUNE_r*.json`` artifact. Returns
    ``(errors, n_result_rows)``; non-strict JSON is itself an error."""

    def _reject(token):
        raise ValueError(f"non-strict JSON token {token}")

    try:
        with open(path) as f:
            payload = json.load(f, parse_constant=_reject)
    except (OSError, ValueError) as e:
        return [f"cannot parse {path}: {e}"], 0
    errors = validate_tune_payload(payload)
    n = 0
    if isinstance(payload, dict) and isinstance(payload.get("results"), list):
        n = len(payload["results"])
    return errors, n


def validate_flight_file(path: str) -> Tuple[List[str], int]:
    """Parse + validate a flight recording. Returns (errors, n_ring_records);
    non-strict JSON (bare NaN/Infinity) is itself a schema error."""

    def _reject(token):
        raise ValueError(f"non-strict JSON token {token}")

    try:
        with open(path) as f:
            payload = json.load(f, parse_constant=_reject)
    except (OSError, ValueError) as e:
        return [f"cannot parse {path}: {e}"], 0
    errors = validate_flight_payload(payload)
    n = 0
    if isinstance(payload, dict) and isinstance(payload.get("records"), dict):
        n = sum(
            len(v) for v in payload["records"].values() if isinstance(v, list)
        )
    return errors, n
