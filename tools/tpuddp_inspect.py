#!/usr/bin/env python
"""tpuddp_inspect — validate and summarize tpuddp telemetry artifacts.

Works on both machine-readable artifacts the framework writes:

- ``history.jsonl`` (a run's typed record stream: ``run_meta`` / ``epoch``
  / ``step_stats`` / ``event`` / ``serving_stats`` / ``decode_stats``,
  tpuddp/observability/schema.py) — prints the run header (including the
  schema-v6 decode provenance block for autoregressive runs), a per-epoch
  table with step-time percentiles, serving/decode SLO window tables
  (tokens/sec, TTFT, ITL, KV occupancy for decode), the event timeline,
  and the gradient-comm byte savings a compressed hook achieved;
- ``bench_results.json`` (``tools/loadgen.py``'s payload, a row a config);
- ``flightrec_<reason>.json`` (the crash flight recorder's post-mortem
  sidecar, tpuddp/observability/flight.py) — validates the ring contents
  against the same per-record schema and pretty-prints the last windows,
  epochs, and event timeline the crashed run saw.

An elastically-resumed history (several ``run_meta`` headers back to back)
attributes every epoch row to the header that OWNS it: the per-epoch table
gains a ``run`` column and the grad-comm savings line uses only the latest
run segment, so pre- and post-resume worlds never mix in one figure.

Checkpoint subcommands (numpy, no jax — both run on analysis hosts):

- ``ckpt <file-or-dir>`` — summarize a format-v3 checkpoint: the recorded
  ``(data, model)`` topology, per-leaf placement tags, shard-tagged flat
  leaves, reshard provenance, and the sha256 manifest status. Pointed at a
  run dir it lists every ``ckpt_*.npz`` (+ stale ``.tmp`` debris count)
  and summarizes the newest.
- ``reshard <src> --to data=D,model=M [--out PATH]`` — the offline
  cross-topology reshaper (tpuddp/training/reshard.py): rewrite a
  checkpoint saved on one mesh shape for another, atomically, with a fresh
  manifest — what ``training.reshard_on_mismatch: true`` does at load
  time, runnable before the relaunch instead.

Advisor subcommand (pure python — the whole CLI runs without jax):

- ``tune <run_dir>`` — the offline evidence engine
  (tpuddp/observability/advisor.py): parse the run's history, traces, and
  writer sidecars into typed evidence and print knob recommendations with
  per-rule evidence citations + predicted deltas. ``--emit PATH`` writes
  the tuned ``$TPUDDP_TUNE_OVERLAY`` payload; ``--json`` is the
  machine-readable report. Read-only: inspecting a run never changes it.
  TUNE_r*.json probe artifacts (tools/autotune.py) validate and summarize
  through the bare-path mode like every other artifact.

Usage:
    python tools/tpuddp_inspect.py <path> [--validate] [--events]
    python tools/tpuddp_inspect.py ckpt <file-or-dir>
    python tools/tpuddp_inspect.py reshard <src> --to data=D,model=M
    python tools/tpuddp_inspect.py tune <run_dir> [--emit PATH] [--json]

``--validate`` checks the schema only (exit 0 valid / 1 invalid, errors on
stderr) — the mode ``tools/run_full_gate.py`` runs over the dryrun history
and the bench artifact, so schema drift fails a gate instead of corrupting
downstream consumers. No flags: validate AND print the summary.

The file kind is detected by content (a JSON-lines stream vs one JSON
object), not by name, so renamed artifacts still inspect.
"""

from __future__ import annotations

import argparse
import collections
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_schema():
    """Load tpuddp/observability/schema.py by file path — NOT through the
    tpuddp package, whose observability __init__ imports jax/numpy. The
    validators are pure python, so this CLI stays usable on analysis hosts
    where the accelerator runtime is absent."""
    path = os.path.join(_REPO, "tpuddp", "observability", "schema.py")
    spec = importlib.util.spec_from_file_location("_tpuddp_inspect_schema", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_reshard():
    """Load tpuddp/training/reshard.py by file path — numpy + stdlib only,
    same rationale as _load_schema: the checkpoint subcommands must work
    where the accelerator runtime is absent."""
    path = os.path.join(_REPO, "tpuddp", "training", "reshard.py")
    spec = importlib.util.spec_from_file_location(
        "_tpuddp_inspect_reshard", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_advisor():
    """Load tpuddp/observability/advisor.py by file path (pure stdlib —
    the evidence engine reads artifacts, never the runtime), so the
    ``tune`` subcommand works on analysis hosts without jax."""
    path = os.path.join(_REPO, "tpuddp", "observability", "advisor.py")
    spec = importlib.util.spec_from_file_location(
        "_tpuddp_inspect_advisor", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_integrity():
    """tpuddp/resilience/integrity.py by file path (stdlib-only module)."""
    path = os.path.join(_REPO, "tpuddp", "resilience", "integrity.py")
    spec = importlib.util.spec_from_file_location(
        "_tpuddp_inspect_integrity", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _detect_kind(path: str) -> str:
    """'bench' (ONE JSON object with metric+configs — possibly
    pretty-printed across lines), 'flight' (one object stamped
    type=flight_recording — the crash post-mortem sidecar), 'trace' (one
    object with traceEvents + a tpuddp provenance block — the causal
    tracing plane's Chrome-trace artifact), or 'history' (a JSONL record
    stream, which fails whole-file json.load with 'Extra data' beyond one
    record)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except ValueError:
        return "history"
    if isinstance(obj, dict) and obj.get("type") == "flight_recording":
        return "flight"
    if isinstance(obj, dict) and "traceEvents" in obj:
        return "trace"
    if isinstance(obj, dict) and obj.get("type") == "tune_report":
        return "tune"
    if isinstance(obj, dict) and "configs" in obj and "metric" in obj:
        return "bench"
    return "history"


def _read_history(path: str):
    records = []
    with open(path) as f:
        for line in f:
            if line.strip():
                try:
                    records.append(json.loads(line))
                except ValueError:
                    records.append({"type": "<unparseable>"})
    return records


def _writer_sidecars(run_dir: str):
    """Every parseable ``*.writer.json`` under ``run_dir`` (the async
    snapshot writer's per-publish statistics sidecars), recursive so
    peer_ckpt/ spill copies count too."""
    import glob as _glob

    out = []
    pattern = os.path.join(_glob.escape(run_dir), "**", "*.writer.json")
    for p in sorted(_glob.glob(pattern, recursive=True)):
        try:
            with open(p) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(payload, dict):
            out.append(payload)
    return out


def _fmt(v, nd=4):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}f}"
    return str(v)


def _print_table(rows, headers):
    widths = [
        max(len(h), *(len(r[i]) for r in rows)) if rows else len(h)
        for i, h in enumerate(headers)
    ]
    line = "  ".join(h.rjust(w) for h, w in zip(headers, widths))
    print(line)
    print("-" * len(line))
    for r in rows:
        print("  ".join(c.rjust(w) for c, w in zip(r, widths)))


def summarize_history(path: str) -> None:
    records = _read_history(path)
    metas = [r for r in records if r.get("type") == "run_meta"]
    # attribute every row to the run_meta header that OWNS it (the newest
    # header ABOVE it in the stream): an elastically-resumed history holds
    # several runs back to back, and a summary mixing their worlds — or
    # computing byte savings from the newest header over the oldest run's
    # epochs — reads as one run that never happened.
    run_idx = -1
    epochs, epoch_runs = [], []
    for r in records:
        if r.get("type") == "run_meta":
            run_idx += 1
        elif r.get("type") == "epoch":
            epochs.append(r)
            epoch_runs.append(max(run_idx, 0))
    # legacy (pre-schema) histories: epoch rows are the ones with losses
    if not epochs:
        epochs = [r for r in records if "train_loss" in r]
        epoch_runs = [0] * len(epochs)
    latest_epochs = [
        e for e, ri in zip(epochs, epoch_runs) if ri == max(run_idx, 0)
    ]
    events = [r for r in records if r.get("type") == "event" or (
        "type" not in r and "event" in r)]
    steps = [r for r in records if r.get("type") == "step_stats"]
    serving = [r for r in records if r.get("type") == "serving_stats"]
    decode = [r for r in records if r.get("type") == "decode_stats"]

    if metas:
        m = metas[-1]
        print(f"run_meta ({len(metas)} header(s); newest):")
        for k in (
            "api", "model", "dataset", "config_hash", "mesh_shape",
            # the v8 2-D mesh block: data/model axis widths + the TP
            # rule-table hash when the model axis is real
            "mesh",
            "world_size", "process_count", "device_kind", "jax_version",
            "tpuddp_version", "comm_hook", "comm_topology", "comm_density",
            "scan_steps", "dispatches_per_pass", "grad_accumulation",
            "step_stats_every",
            # serving run_meta fields (api == "serving")
            "num_replicas", "max_batch_size", "max_queue_depth",
            "per_tenant_quota", "batch_timeout_ms", "buckets", "input_shape",
            "restored_epoch", "checkpoint_dir",
            # elastic + live-plane provenance (schema v5)
            "resumed_from_world", "observability",
        ):
            if m.get(k) is not None:
                print(f"  {k:>20}: {m[k]}")
        guard = m.get("guard")
        if isinstance(guard, dict) and guard.get("enabled"):
            print(f"  {'guard':>20}: {guard}")
        # decode provenance (required since schema v6; null = not an
        # autoregressive run): the KV-pool geometry + sampling contract
        dec = m.get("decode")
        if isinstance(dec, dict):
            geom = (
                f"{dec.get('kv_blocks')}x{dec.get('kv_block_size')} KV "
                f"blocks, {dec.get('max_slots')} slots, max_seq_len "
                f"{dec.get('max_seq_len')}"
            )
            print(f"  {'decode':>20}: model={dec.get('model')} "
                  f"vocab={dec.get('vocab_size')} {geom}")
            print(f"  {'':>20}  temperature={dec.get('temperature')} "
                  f"stop_token={dec.get('stop_token')} "
                  f"prefill_buckets={dec.get('prefill_buckets')}")
        # survivability provenance (required since schema v7; null = not a
        # serving writer): the deadline/probation/retry knob block
        sur = m.get("survivability")
        if isinstance(sur, dict):
            print(f"  {'survivability':>20}: "
                  f"request_ttl_s={sur.get('request_ttl_s')} "
                  f"max_recoveries={sur.get('max_recoveries')} "
                  f"recovery_attempts={sur.get('recovery_attempts')} "
                  f"retry_budget={sur.get('retry_budget')}")
        # comm provenance (required since schema v10; null = meshless /
        # serving header): histories written before the step became one
        # program may say the backward issued its collectives per segment
        comm = m.get("comm")
        if isinstance(comm, dict):
            ov = comm.get("overlap") or {}
            line = (f"  {'comm.overlap':>20}: enabled={ov.get('enabled')} "
                    f"segments={ov.get('segments')}")
            if ov.get("reason"):
                line += f" ({ov['reason']})"
            print(line)
    else:
        print("run_meta: MISSING (pre-schema history?)")

    if epochs:
        multi_run = len(metas) > 1
        if multi_run:
            print(f"\nepochs ({len(epochs)} across {len(metas)} runs; "
                  f"'run' column = owning header, newest is "
                  f"{len(metas) - 1}):")
        else:
            print(f"\nepochs ({len(epochs)}):")
        rows = []
        for e, ri in zip(epochs, epoch_runs):
            row = [
                str(e.get("epoch")),
                _fmt(e.get("train_loss")),
                _fmt(e.get("test_loss")),
                _fmt(e.get("test_accuracy"), 2),
                _fmt(e.get("epoch_time_s"), 1),
                _fmt(e.get("samples_per_sec"), 0),
                _fmt(e.get("step_time_ms_p50"), 2),
                _fmt(e.get("step_time_ms_p95"), 2),
                _fmt(e.get("step_time_ms_p99"), 2),
                _fmt(e.get("mfu_p50")),
                str(e.get("skipped_steps_epoch", 0) or 0),
            ]
            if multi_run:
                row.insert(0, str(ri))
            rows.append(row)
        headers = [
            "ep", "train", "test", "acc%", "t(s)", "sps",
            "p50ms", "p95ms", "p99ms", "mfu50", "skip",
        ]
        if multi_run:
            headers.insert(0, "run")
        _print_table(rows, headers)
        if steps:
            line = (f"\nstep_stats windows: {len(steps)} "
                    f"(finest p99 {max(s.get('step_time_ms_p99') or 0 for s in steps):.2f} ms, "
                    f"window size {steps[0].get('steps')})")
            # pipeline occupancy (schema v3): total host stall across windows
            # plus the deepest staged/in-flight queues any window saw
            stalls = [s.get("host_stall_ms") for s in steps]
            if any(v is not None for v in stalls):
                total_stall = sum(v or 0 for v in stalls)
                line += (
                    f"\npipeline occupancy: host stall {total_stall:.1f} ms total "
                    f"(worst window {max(v or 0 for v in stalls):.1f} ms), "
                    f"staging depth <= {max(s.get('staging_queue_depth') or 0 for s in steps)}, "
                    f"in-flight <= {max(s.get('inflight_depth') or 0 for s in steps)}"
                )
            print(line)
        host_stall_epoch = [e.get("host_stall_ms") for e in epochs]
        if any(v for v in host_stall_epoch):
            print(f"host stall per epoch (ms): "
                  f"{[round(v, 1) for v in host_stall_epoch if v is not None]}")

    # async-writer sidecar rollup: every ckpt_*.npz.writer.json beside the
    # history (the snapshot engine's per-publish statistics — the same
    # sidecar `ckpt` prints next to the v4 cursor, aggregated run-wide
    # here so backlog shows up without opening each checkpoint)
    sidecars = _writer_sidecars(os.path.dirname(os.path.abspath(path)))
    if sidecars:
        snaps = sum(int(w.get("snapshots") or 0) for w in sidecars)
        skipped = sum(int(w.get("skipped_queue_full") or 0) for w in sidecars)
        write_s = sum(float(w.get("write_s") or 0.0) for w in sidecars)
        total_b = sum(int(w.get("bytes") or 0) for w in sidecars)
        n_async = sum(1 for w in sidecars if w.get("async"))
        line = (f"\nsnapshot writer: {len(sidecars)} sidecar(s) "
                f"({n_async} async), {snaps} snapshot(s), "
                f"{skipped} skipped_queue_full, "
                f"{write_s:.2f} s writing, {total_b:,} B")
        if skipped:
            line += "  <- backlog: writer dropped snapshots (queue full)"
        print(line)

    if serving:
        print(f"\nserving_stats windows ({len(serving)}):")
        rows = []
        for s in serving:
            rows.append([
                str(s.get("window")),
                str(s.get("requests")),
                str(s.get("completed")),
                str(s.get("rejected")),
                _fmt(s.get("queue_ms_p50"), 2),
                _fmt(s.get("device_ms_p50"), 2),
                _fmt(s.get("e2e_ms_p50"), 2),
                _fmt(s.get("e2e_ms_p95"), 2),
                _fmt(s.get("e2e_ms_p99"), 2),
                _fmt(s.get("throughput_rps"), 0),
                _fmt(s.get("batch_occupancy"), 3),
                str(s.get("shed") if s.get("shed") is not None else "-"),
                str(s.get("retries")
                    if s.get("retries") is not None else "-"),
            ])
        _print_table(rows, [
            "win", "req", "done", "rej", "q50ms", "d50ms",
            "e2e50", "e2e95", "e2e99", "rps", "occ", "shed", "rty",
        ])
        done = sum(s.get("completed") or 0 for s in serving)
        rej = sum(s.get("rejected") or 0 for s in serving)
        shed = sum(s.get("shed") or 0 for s in serving)
        retries = sum(s.get("retries") or 0 for s in serving)
        worst = max((s.get("e2e_ms_p99") or 0) for s in serving)
        print(f"  totals: {done} completed, {rej} rejected "
              f"({shed} shed past deadline), {retries} retried, "
              f"worst-window e2e p99 {worst:.2f} ms")

    if decode:
        # token-level SLO windows (schema v6, tpuddp/serving/decode/):
        # throughput in tokens/sec plus the two latencies token traffic
        # lives by — TTFT (submit -> first streamed token) and ITL (gap
        # between consecutive tokens of one sequence) — and KV-pool pressure
        print(f"\ndecode_stats windows ({len(decode)}):")
        rows = []
        for s in decode:
            rows.append([
                str(s.get("window")),
                str(s.get("tokens")),
                str(s.get("completed")),
                str(s.get("rejected")),
                _fmt(s.get("tokens_per_sec"), 0),
                _fmt(s.get("ttft_ms_p50"), 2),
                _fmt(s.get("ttft_ms_p95"), 2),
                _fmt(s.get("itl_ms_p50"), 2),
                _fmt(s.get("itl_ms_p99"), 2),
                _fmt(s.get("kv_occupancy"), 3),
                str(s.get("active_sequences")
                    if s.get("active_sequences") is not None else "-"),
                str(s.get("shed") if s.get("shed") is not None else "-"),
                str(s.get("failovers")
                    if s.get("failovers") is not None else "-"),
            ])
        _print_table(rows, [
            "win", "tok", "done", "rej", "tok/s", "ttft50", "ttft95",
            "itl50", "itl99", "kvocc", "act", "shed", "fo",
        ])
        tok = sum(s.get("tokens") or 0 for s in decode)
        done = sum(s.get("completed") or 0 for s in decode)
        shed = sum(s.get("shed") or 0 for s in decode)
        failovers = sum(s.get("failovers") or 0 for s in decode)
        worst_itl = max((s.get("itl_ms_p99") or 0) for s in decode)
        peak_kv = max((s.get("kv_occupancy") or 0) for s in decode)
        print(f"  totals: {tok} tokens across {done} sequences "
              f"({shed} shed past deadline, {failovers} session "
              f"failover(s)), worst-window ITL p99 {worst_itl:.2f} ms, "
              f"peak KV occupancy {peak_kv:.3f}")

    # gradient-comm byte savings: compressed vs the f32 baseline the header
    # records. ONLY the latest run segment's epochs belong to the latest
    # header — after an elastic resume the older epochs trained on a
    # different world (different per-update bytes), and their cumulative
    # counter reset at the resume anyway.
    if metas and latest_epochs:
        m = metas[-1]
        per, base = m.get("grad_comm_bytes_per_update"), m.get(
            "grad_comm_bytes_per_update_f32")
        total = latest_epochs[-1].get("grad_comm_bytes_total")
        if per is not None and base:
            saved = 1.0 - per / base
            line = (f"\ngrad comm: {per:,} B/update on the wire vs {base:,} B "
                    f"uncompressed ({saved * 100:.1f}% saved"
                    f", hook {m.get('comm_hook')}"
                    f", topology {m.get('comm_topology') or 'flat'})")
            if total is not None:
                line += (
                    f"; {total:,} B total this run"
                    + (f" (latest of {len(metas)})" if len(metas) > 1 else "")
                )
            print(line)
            # hierarchical hop split (schema v4): the compressed inter-host
            # share vs the f32 intra-host (ICI) traffic per update
            inter = m.get("grad_comm_bytes_inter_host")
            intra = m.get("grad_comm_bytes_intra_host")
            if inter is not None and intra:
                print(f"  hop split: {inter:,} B inter-host (compressed) + "
                      f"{intra:,} B intra-host (f32 ICI) per update")

    # survivability episode rollup (schema v7): one line a chaos gate (or
    # an operator) reads to know how many sessions migrated, which
    # replicas came back, and whether the pool ever terminally died
    sur_counts = {
        kind: sum(1 for ev in events if ev.get("event") == kind)
        for kind in (
            "session_failover", "replica_unhealthy", "replica_recovered",
            "replica_removed", "no_healthy_replica",
        )
    }
    if any(sur_counts.values()):
        print("\nsurvivability: " + ", ".join(
            f"{k}={v}" for k, v in sur_counts.items() if v
        ))

    # tracing digest (schema v9): the drain-time trace_summary rows — span
    # counts, ring drops, and the single slowest span per traced writer
    for ts in (r for r in records if r.get("type") == "trace_summary"):
        slowest = (ts.get("slowest") or [{}])[0]
        print(f"\ntracing: role={ts.get('role')} spans={ts.get('spans')} "
              f"dropped={ts.get('dropped')} open={ts.get('open_spans')} "
              f"by_kind={ts.get('by_kind')}")
        if slowest:
            print(f"  slowest span: {slowest.get('name')} "
                  f"({slowest.get('kind')}) "
                  f"{_fmt(slowest.get('duration_ms'), 3)} ms")

    if events:
        print(f"\nevents ({len(events)}):")
        for ev in events:
            fields = {
                k: v for k, v in ev.items()
                if k not in ("type", "schema_version", "event")
            }
            print(f"  [{ev.get('epoch', '-')}] {ev.get('event')}: {fields}")
    else:
        print("\nevents: none")


def summarize_flight(path: str) -> None:
    """Pretty-print a flightrec_<reason>.json crash recording (pure-python
    mirror of observability.flight.summarize_recording — this CLI stays
    importable on analysis hosts without the accelerator runtime)."""
    with open(path) as f:
        payload = json.load(f)
    print(f"flight recording: reason={payload.get('reason')} "
          f"process={payload.get('process_index')} "
          f"capacity={payload.get('capacity')} "
          f"observed={payload.get('observed_records')}")
    meta = payload.get("run_meta") or {}
    if meta:
        print(f"  run: api={meta.get('api')} model={meta.get('model')} "
              f"world={meta.get('world_size')} comm_hook={meta.get('comm_hook')}")
    notes = payload.get("notes") or {}
    if notes:
        print(f"  notes: {notes}")
    records = payload.get("records") or {}
    counts = payload.get("counts") or {}
    print("  rings: " + ", ".join(
        f"{k}={counts.get(k, 0)}" for k in sorted(counts)))
    windows = records.get("step_stats") or []
    if windows:
        last = windows[-1]
        print(f"  last window: epoch {last.get('epoch')} steps "
              f"[{last.get('step_start')}, "
              f"{(last.get('step_start') or 0) + (last.get('steps') or 0)}) "
              f"p50 {_fmt(last.get('step_time_ms_p50'), 2)} ms")
    epochs = records.get("epoch") or []
    if epochs:
        last = epochs[-1]
        print(f"  last epoch: {last.get('epoch')} train "
              f"{_fmt(last.get('train_loss'))} test {_fmt(last.get('test_loss'))}"
              f" skips {last.get('skipped_steps_epoch', 0) or 0}")
    events = records.get("event") or []
    if events:
        print(f"  events ({len(events)}):")
        for ev in events:
            fields = {
                k: v for k, v in ev.items()
                if k not in ("type", "schema_version", "event")
            }
            print(f"    [{ev.get('epoch', '-')}] {ev.get('event')}: {fields}")


_TRACE_NAMES = 16  # rows of the trace summary's per-name table


def summarize_trace(path: str) -> None:
    """Pretty-print a trace_<role>.json artifact: provenance, per-kind time
    share, and the slowest-span table (the ``trace`` subcommand's summary —
    pure python, no accelerator runtime needed)."""
    with open(path) as f:
        payload = json.load(f)
    meta = payload.get("tpuddp") or {}
    print(f"trace: role={meta.get('role')} process={meta.get('process_index')} "
          f"spans={meta.get('spans')} dropped={meta.get('dropped')} "
          f"open={meta.get('open_spans')} traces={meta.get('traces')} "
          f"capacity={meta.get('capacity')}")
    clock = meta.get("clock_sync") or {}
    if clock:
        print(f"  clock_sync: unix_us={clock.get('unix_us')} "
              f"perf_ns={clock.get('perf_ns')}")
    spans = [
        e for e in (payload.get("traceEvents") or [])
        if isinstance(e, dict) and e.get("ph") == "X"
    ]
    # per-kind time share: where the traced wall time went, by span kind.
    # Kinds NEST (a stage span lives inside its epoch span), so shares can
    # exceed 100% of any one kind — the table answers "which kind is the
    # fat one", not "how do these partition the run".
    by_kind = collections.Counter()
    counts = collections.Counter()
    for e in spans:
        kind = e.get("cat") or "?"
        by_kind[kind] += float(e.get("dur") or 0.0)
        counts[kind] += 1
    total = sum(by_kind.values())
    if by_kind and total > 0:
        print(f"\nper-kind device-free host time ({total / 1e3:.1f} ms "
              "summed across nested spans):")
        rows = [
            [k, str(counts[k]), f"{d / 1e3:.1f}", f"{100 * d / total:.1f}%"]
            for k, d in by_kind.most_common()
        ]
        _print_table(rows, ["kind", "spans", "ms", "share"])
        # the same time by span name: a kind holds several (load: the
        # loader's order / gather / pad and a stage's stack / put), and which
        # of them is the fat one is what points at a fix
        by_name = collections.Counter()
        name_counts = collections.Counter()
        for e in spans:
            key = (e.get("cat") or "?", e.get("name") or "?")
            by_name[key] += float(e.get("dur") or 0.0)
            name_counts[key] += 1
        print(f"\nper-name host time (top {min(len(by_name), _TRACE_NAMES)}):")
        rows = [
            [n, k, str(name_counts[k, n]), f"{d / 1e3:.1f}",
             f"{d / 1e3 / name_counts[k, n]:.3f}"]
            for (k, n), d in by_name.most_common(_TRACE_NAMES)
        ]
        _print_table(rows, ["name", "kind", "spans", "ms", "ms/span"])
    slowest = meta.get("slowest") or []
    if slowest:
        print(f"\nslowest spans (top {len(slowest)}):")
        rows = [
            [
                str(r.get("name")), str(r.get("kind")),
                _fmt(r.get("duration_ms"), 3),
            ]
            for r in slowest
        ]
        _print_table(rows, ["name", "kind", "ms"])
    opens = [e for e in spans if (e.get("args") or {}).get("open")]
    if opens:
        print(f"\nstill-open at export ({len(opens)}):")
        for e in opens:
            print(f"  {e.get('name')} ({e.get('cat')})")


def summarize_bench(path: str) -> None:
    with open(path) as f:
        payload = json.load(f)
    print(f"bench: {payload.get('metric')} = {payload.get('value')} "
          f"{payload.get('unit')} on {payload.get('device')} "
          f"(vs_baseline {payload.get('vs_baseline')} over "
          f"{payload.get('vs_baseline_basis')})")
    configs = payload.get("configs", {})
    if any(
        isinstance(r, dict) and "tokens_per_sec" in r for r in configs.values()
    ):
        # decode token-curve rows (tools/loadgen.py --decode): tokens/sec +
        # TTFT/ITL vs offered sequence rate, with the sequential-decode
        # baseline row anchoring vs_baseline
        rows = []
        for name, r in configs.items():
            rows.append([
                name,
                str(r.get("mode", "-")),
                _fmt(r.get("offered_rps"), 1),
                _fmt(r.get("achieved_rps"), 1),
                _fmt(r.get("tokens_per_sec"), 0),
                _fmt(r.get("ttft_ms_p50"), 2),
                _fmt(r.get("ttft_ms_p95"), 2),
                _fmt(r.get("itl_ms_p50"), 2),
                _fmt(r.get("itl_ms_p99"), 2),
                str(r.get("rejected", "-")),
            ])
        _print_table(rows, [
            "config", "mode", "offered", "seq/s", "tok/s", "ttft50",
            "ttft95", "itl50", "itl99", "rej",
        ])
        return
    if any(isinstance(r, dict) and "offered_rps" in r for r in configs.values()):
        # serving curve rows (tools/loadgen.py): offered-vs-achieved
        # throughput with client-side latency percentiles
        rows = []
        for name, r in configs.items():
            rows.append([
                name,
                _fmt(r.get("offered_rps"), 0),
                _fmt(r.get("achieved_rps"), 0),
                _fmt(r.get("e2e_ms_p50"), 2),
                _fmt(r.get("e2e_ms_p99"), 2),
                _fmt(r.get("batch_occupancy"), 3),
                str(r.get("rejected", "-")),
                _fmt(r.get("samples_per_sec_per_chip"), 0),
            ])
        _print_table(rows, [
            "config", "offered", "rps", "e2e50ms", "e2e99ms", "occ",
            "rej", "rows/chip",
        ])
        return
    rows = []
    for name, r in configs.items():
        rows.append([
            name,
            _fmt(r.get("samples_per_sec_per_chip"), 0),
            _fmt(r.get("ms_per_step"), 2),
            _fmt(r.get("ms_per_step_p50"), 2),
            _fmt(r.get("ms_per_step_p99"), 2),
            _fmt(r.get("mfu")),
            # async-pipeline columns: wall/device ratio and host-stall
            # percentiles — '-' on rows without them
            _fmt(r.get("wall_to_device_ratio"), 2),
            _fmt(r.get("host_stall_ms_p50"), 2),
            _fmt(r.get("host_stall_ms_p95"), 2),
        ])
    _print_table(rows, [
        "config", "sps/chip", "ms", "p50ms", "p99ms", "mfu",
        "w/dev", "stall50", "stall95",
    ])


def summarize_tune(path: str) -> None:
    """Pretty-print a TUNE_r*.json A/B probe report (schema v12): the
    predicted-vs-measured delta per rule and the endorsement verdicts."""
    with open(path) as f:
        payload = json.load(f)
    print(f"tune report: mode={payload.get('mode')} "
          f"device={payload.get('device')} "
          f"(schema v{payload.get('schema_version')})")
    baseline = payload.get("baseline_metrics") or {}
    if baseline:
        print("  baseline: " + ", ".join(
            f"{k}={_fmt(v, 2)}" for k, v in sorted(baseline.items())
        ))
    results = payload.get("results") or []
    rows = []
    for r in results:
        rows.append([
            str(r.get("rule")),
            str(r.get("rule_class")),
            str(r.get("metric")),
            _fmt(r.get("predicted_delta_pct"), 1),
            _fmt(r.get("measured_delta_pct"), 1),
            "yes" if r.get("endorsed") else "NO",
        ])
    if rows:
        _print_table(rows, [
            "rule", "class", "metric", "pred%", "meas%", "endorsed",
        ])
    n_endorsed = sum(1 for r in results if r.get("endorsed"))
    print(f"  {n_endorsed}/{len(results)} endorsed (measured improvement "
          "only — a regressing diff is never endorsed, whatever was "
          "predicted)")


def tune_main(argv) -> int:
    parser = argparse.ArgumentParser(
        prog="tpuddp_inspect.py tune",
        description="Offline advisor: read a run dir's history.jsonl, "
        "trace_*.json, and writer sidecars, and print knob recommendations "
        "with evidence citations + predicted deltas. Read-only — nothing "
        "is applied unless you --emit an overlay and launch with it.",
    )
    parser.add_argument("run_dir", help="run directory (holds history.jsonl)")
    parser.add_argument(
        "--emit", metavar="PATH", default=None,
        help="write the tuned config overlay (the $TPUDDP_TUNE_OVERLAY "
        "payload) for the recommendations to PATH",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="print the full report as JSON (machine-readable)",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.run_dir):
        print(f"no such run dir: {args.run_dir}", file=sys.stderr)
        return 2
    advisor = _load_advisor()
    report = advisor.advise(args.run_dir)
    if args.as_json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(advisor.format_report(report))
    if args.emit:
        overlay = advisor.overlay_from(report["recommendations"])
        overlay["source"] = "advisor"
        tmp = args.emit + ".tmp"
        with open(tmp, "w") as f:
            json.dump(overlay, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, args.emit)
        print(f"\noverlay written: {args.emit} "
              f"(launch with TPUDDP_TUNE_OVERLAY=\"$(cat {args.emit})\")")
    return 0


def summarize_ckpt(path: str) -> int:
    """Print one checkpoint's recorded topology, shard tags, placement
    table, v4 data cursor (step snapshots), writer statistics, peer-shard
    provenance, and manifest status. Returns 0 (1 when the manifest
    mismatches — a torn file an operator should know about before trusting
    it)."""
    import json as _json

    import numpy as np

    reshard = _load_reshard()
    integrity = _load_integrity()
    with np.load(path) as f:
        stored = dict(f.items())
    topo = reshard.parse_topology(stored)
    leaves = [
        k for k in stored
        if k != reshard.TOPO_MARK
        and not k.startswith(reshard.META_MARK)
        and not k.startswith(reshard.CURSOR_MARK)
    ]
    n_bf16 = sum(1 for k in leaves if k.startswith(reshard.BF16_MARK))
    n_keys = sum(1 for k in leaves if k.startswith(reshard.KEY_MARK))
    total_b = sum(int(stored[k].nbytes) for k in leaves)
    print(f"checkpoint: {path}")
    # peer-redundant spill provenance: the file's own location says whether
    # this is a host's local checkpoint or a ring-neighbor copy under the
    # heartbeat channel's peer_ckpt/ directory
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    if "peer_ckpt" in parts:
        ring = parts[parts.index("peer_ckpt") + 1] if (
            parts.index("peer_ckpt") + 1 < len(parts)
        ) else "?"
        print(f"  provenance: peer-redundant spill ({ring} — a ring "
              "neighbor's copy; restore prefers freshest-intact across "
              "local + peers)")
    print(f"  leaves: {len(leaves)} ({n_bf16} bf16-packed, {n_keys} PRNG "
          f"key(s)), {total_b:,} payload bytes")
    # v4 data cursor: the exact-resume record of a step-granular snapshot
    if "__cursor__" in stored:
        cur = _json.loads(str(np.asarray(stored["__cursor__"]).item()))
        acc_keys = cur.get("acc_keys") or []
        print(f"  cursor (v{cur.get('version')}): epoch={cur.get('epoch')} "
              f"step={cur.get('step')} plan_key={cur.get('plan_key')}")
        if acc_keys:
            names = [k[len("__cursor_acc__"):] for k in acc_keys]
            print(f"  cursor accumulator: {len(acc_keys)} partial metric "
                  f"leaf(s) {names}")
        print("  resume: exact — the driver continues this epoch AT the "
              "recorded step (zero batches replayed) when the plan key "
              "matches")
    # async-writer statistics sidecar (deliberately outside the payload:
    # the npz must stay byte-identical between async and sync writers)
    try:
        with open(path + ".writer.json", "r", encoding="utf-8") as wf:
            ws = _json.load(wf)
    except (OSError, ValueError):
        ws = None
    if ws is not None:
        print(f"  writer: async={ws.get('async')} inflight={ws.get('inflight')} "
              f"snapshots={ws.get('snapshots')} "
              f"skipped_queue_full={ws.get('skipped_queue_full')} "
              f"write_s={ws.get('write_s')} bytes={ws.get('bytes'):,} "
              f"peer_redundancy={ws.get('peer_redundancy')}")
    if topo is None:
        print("  topology: MISSING (format v1 — predates shard provenance; "
              "resharding refuses this file, resume it at model=1 or re-save "
              "through save_on_main)")
    else:
        d, m = reshard.topology_shape(topo)
        print(f"  topology: format v{topo.get('format')} world="
              f"{topo.get('world_size')} mesh=(data={d}, model={m}) "
              f"axes={topo.get('mesh_axes')}")
        re_prov = topo.get("resharded")
        if re_prov:
            print(f"  resharded: {re_prov.get('from')} -> {re_prov.get('to')}"
                  + (f", dropped {re_prov['dropped']}"
                     if re_prov.get("dropped") else ""))
        tags = topo.get("leaves") or {}
        if tags:
            print(f"  shard-tagged flat leaves ({len(tags)}):")
            for k in sorted(tags):
                print(f"    {k}: {tags[k]}")
        placement = topo.get("placement") or {}
        if placement:
            print(f"  placement tags ({len(placement)}):")
            for k in sorted(placement):
                print(f"    {k}: {placement[k]}")
        else:
            print("  placement tags: none (every leaf replicated)")
    manifest = integrity.read_manifest(path)
    if manifest is None:
        print("  manifest: ABSENT (.sha256 sidecar missing — structural "
              "zip check only at restore)")
        return 0
    ok = integrity.verify_file(path, require_manifest=True)
    status = (
        "verified"
        if ok
        else "MISMATCH (torn file: restore will skip this candidate)"
    )
    print(f"  manifest: sha256={manifest['digest'][:12]}... "
          f"size={manifest['size']} -> {status}")
    return 0 if ok else 1


def ckpt_main(argv) -> int:
    parser = argparse.ArgumentParser(
        prog="tpuddp_inspect.py ckpt",
        description="Summarize a tpuddp checkpoint (topology record, "
        "placement tags, manifest status) or a checkpoint directory.",
    )
    parser.add_argument("path", help="ckpt_<epoch>.npz file, or a run dir")
    args = parser.parse_args(argv)
    if os.path.isdir(args.path):
        import re as _re

        names = sorted(os.listdir(args.path))
        pat = _re.compile(r"^ckpt_(\d+)(?:_s(\d+))?\.npz$")
        matched = [(n, pat.match(n)) for n in names]
        ckpts = [(n, m) for n, m in matched if m]
        n_steps = sum(1 for _, m in ckpts if m.group(2) is not None)
        stale = [
            n for n in names
            if _re.match(r"^ckpt_\d+(_s\d+)?\.npz(\.sha256)?\.tmp$", n)
        ]
        steps_note = f" ({n_steps} step snapshot(s))" if n_steps else ""
        print(f"{args.path}: {len(ckpts)} checkpoint(s){steps_note}, "
              f"{len(stale)} stale .tmp file(s)"
              + (f" {stale}" if stale else ""))
        if not ckpts:
            return 0

        # same family ordering as restore_latest: a full-epoch save ranks
        # newer than any step snapshot of the same epoch
        def family(item):
            _, m = item
            step = m.group(2)
            return (int(m.group(1)), 1 if step is None else 0,
                    0 if step is None else int(step))

        newest = max(ckpts, key=family)[0]
        print()
        return summarize_ckpt(os.path.join(args.path, newest))
    if not os.path.isfile(args.path):
        print(f"no such file: {args.path}", file=sys.stderr)
        return 2
    return summarize_ckpt(args.path)


def reshard_main(argv) -> int:
    parser = argparse.ArgumentParser(
        prog="tpuddp_inspect.py reshard",
        description="Offline cross-topology checkpoint reshaper: rewrite a "
        "format-v3 checkpoint saved on one (data, model) mesh for another "
        "(atomic publish + fresh sha256 manifest). The load-time equivalent "
        "is training.reshard_on_mismatch: true.",
    )
    parser.add_argument("src", help="source ckpt_<epoch>.npz")
    parser.add_argument(
        "--to", required=True, metavar="data=D,model=M",
        help="target mesh shape, e.g. --to data=2,model=1",
    )
    parser.add_argument(
        "--out", default=None, metavar="PATH",
        help="output file (default: <src stem>.d<D>m<M>.npz alongside src; "
        "pass the src path itself to reshape in place)",
    )
    args = parser.parse_args(argv)
    if not os.path.isfile(args.src):
        print(f"no such file: {args.src}", file=sys.stderr)
        return 2
    shape = {}
    for part in args.to.split(","):
        if "=" not in part:
            parser.error(f"--to expects data=D,model=M, got {args.to!r}")
        k, v = part.split("=", 1)
        shape[k.strip()] = v.strip()
    unknown = set(shape) - {"data", "model"}
    if unknown or "data" not in shape:
        parser.error(f"--to expects data=D,model=M, got {args.to!r}")
    try:
        data = int(shape["data"])
        model = int(shape.get("model", 1))
    except ValueError:
        parser.error(f"--to expects integer widths, got {args.to!r}")
    out = args.out
    if out is None:
        stem = args.src[:-len(".npz")] if args.src.endswith(".npz") else args.src
        out = f"{stem}.d{data}m{model}.npz"
    reshard = _load_reshard()
    try:
        report = reshard.reshard_checkpoint(args.src, out, data, model)
    except reshard.ReshardError as e:
        print(f"REFUSED: {e}", file=sys.stderr)
        return 1
    f, t = report["from"], report["to"]
    print(f"resharded {report['src']} -> {report['dst']}")
    print(f"  mesh: (data={f['data']}, model={f['model']}) -> "
          f"(data={t['data']}, model={t['model']}), "
          f"{report['leaves']} leaves")
    for a in report["actions"]:
        detail = {
            k: v for k, v in a.items() if k not in ("leaf", "action")
        }
        print(f"  {a['action']}: {a['leaf']} {detail}")
    if not report["actions"]:
        print("  (no per-leaf surgery needed: payloads are mesh-shape-"
              "independent at these shapes)")
    return 0


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "ckpt":
        return ckpt_main(argv[1:])
    if argv and argv[0] == "reshard":
        return reshard_main(argv[1:])
    if argv and argv[0] == "tune":
        return tune_main(argv[1:])
    # `tpuddp_inspect.py trace <path>` — the explicit trace subcommand:
    # validates the artifact against schema v9 and prints the slowest-span
    # table + per-kind time share (content detection still recognizes a
    # trace artifact passed as a bare path, so both spellings work)
    trace_mode = bool(argv) and argv[0] == "trace"
    if trace_mode:
        argv = argv[1:]
    parser = argparse.ArgumentParser(
        description="Validate/summarize a tpuddp history.jsonl, "
        "bench_results.json, flightrec_*.json, or trace_<role>.json "
        "artifact ('trace <path>' forces the trace reader).",
    )
    parser.add_argument("path", help="artifact to inspect")
    parser.add_argument(
        "--validate", action="store_true",
        help="schema check only: exit 0 when valid, 1 with errors on stderr",
    )
    parser.add_argument(
        "--events", action="store_true",
        help="print only the event timeline (history files)",
    )
    args = parser.parse_args(argv)

    if not os.path.isfile(args.path):
        print(f"no such file: {args.path}", file=sys.stderr)
        return 2

    schema = _load_schema()
    kind = "trace" if trace_mode else _detect_kind(args.path)
    if kind == "bench":
        errors, n = schema.validate_bench_file(args.path)
    elif kind == "flight":
        errors, n = schema.validate_flight_file(args.path)
    elif kind == "trace":
        errors, n = schema.validate_trace_file(args.path)
    elif kind == "tune":
        errors, n = schema.validate_tune_file(args.path)
    else:
        errors, n = schema.validate_history_file(args.path)

    if errors:
        for e in errors:
            print(f"INVALID: {e}", file=sys.stderr)
        if args.validate:
            return 1
        print(f"({len(errors)} schema error(s) — summary follows)\n",
              file=sys.stderr)
    if args.validate:
        print(f"OK: {args.path} — {n} {kind} record(s), schema v"
              f"{schema.SCHEMA_VERSION}")
        return 0

    if kind == "bench":
        summarize_bench(args.path)
    elif kind == "flight":
        summarize_flight(args.path)
    elif kind == "trace":
        summarize_trace(args.path)
    elif kind == "tune":
        summarize_tune(args.path)
    elif args.events:
        for r in _read_history(args.path):
            if r.get("event"):
                print(json.dumps(r))
    else:
        summarize_history(args.path)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
