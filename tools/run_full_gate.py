#!/usr/bin/env python
"""Run the FULL test gate — both tiers plus the telemetry-schema gate.

``pytest.ini`` sets ``addopts = -m "not slow"``, so a bare ``pytest`` run is
the fast tier-1 gate only: the subprocess/CLI end-to-end runs, the multichip
dryrun, the big pretrained-import donors, the fuzz sweeps, and the chaos
suite (chaos tests are also slow-marked) all silently fall out of any default
invocation. This runner makes "run everything" a command instead of a marker
expression someone must remember: it selects ``-m "slow or not slow"`` —
every collected test, both tiers — and inherits pytest's exit-code contract
(non-zero on failures, 4/5 if the expression ever selects nothing, i.e. the
two-tier contract itself drifted).

Tier membership note: the numerical-guard/desync suite (tests/test_guard.py)
is deliberately UNMARKED so it rides in tier-1 — the firewall/auditor
contracts are fast compiled-step assertions, not subprocess chaos; only the
subprocess proofs (nan@step, exit-77, rollback in tests/test_chaos.py) live
in the chaos tier.

Schema gate (after the suites pass): a dryrun training subprocess produces a
``history.jsonl`` and ``tools/tpuddp_inspect.py --validate`` must accept it;
if a ``bench_results.json`` exists at the repo root, it is validated too. A
writer drifting off the typed record schema (tpuddp/observability/schema.py)
fails the gate here instead of corrupting downstream consumers.

Pipeline gate (after the schema gate): a ``pipeline.depth=2`` dryrun and a
``pipeline: false`` (synchronous) dryrun of the same seed must produce a
schema-valid history whose ``step_stats`` windows carry the v3 occupancy
fields (host_stall_ms / inflight_depth / staging_queue_depth), bitwise-equal
checkpoints leaf for leaf, and byte-identical step HLO — the async pipeline's
"zero semantic cost" contract, enforced every gate run.

Compression-matrix gate (after the pipeline gate): dryrun trainings across
the comm hook x topology grid (none/bf16_ef/int8_ef/topk_ef x
flat/hierarchical) must each produce a schema-valid history whose run_meta
carries the comm accounting; the quantized/sparse hooks must show their
acceptance byte cuts (>= 70% / >= 85%) against the header's own f32
baseline, final-epoch losses must sit within the documented per-hook parity
bound of the uncompressed run, and hierarchical rows must report inter-host
bytes below the flat total.

Serving gate (after the comm-matrix gate): ``tools/loadgen.py --quick`` stands
the continuous-batching engine up on the CPU mesh (2 replicas, 2 tenants,
~170 requests across a closed-loop calibration + 3 offered-load points) and
both emitted artifacts — the engine's ``history.jsonl`` (run_meta +
serving_stats + events) and the latency-vs-throughput ``bench_results.json``
curve — must pass ``tpuddp_inspect --validate``. The serving SLO record stream drifting
off schema v2 fails the gate the same way training telemetry drift does.

Decode gate (after the serving gate): ``tools/loadgen.py --decode --quick``
stands the TOKEN-level autoregressive engine (tpuddp/serving/decode/) up on
the CPU mesh — transformer prefill/decode split, paged KV cache, continuous
batching at token granularity — and both artifacts (the schema-v6
``history.jsonl`` with run_meta decode provenance + decode_stats windows,
and the tokens/sec + TTFT ``bench_results.json`` curve) must pass
``tpuddp_inspect --validate``. Then the drain leg: a ``--decode`` server
is SIGTERMed mid-decode and must let every in-flight sequence finish
streaming (summary ``completed == submitted``, zero truncation) before
exiting 75 — the resilience drain contract at token granularity.

Serving-chaos gate (after the decode gate): ``tools/loadgen.py --decode
--quick --chaos`` re-runs the token sweep and then kills a replica
MID-SWEEP through the real ``$TPUDDP_FAULT`` env contract
(``replica_kill@step=N``). The survivability layer (ISSUE 13,
tpuddp/serving/survive.py) must lose ZERO streams: every live sequence
parks into its session journal, fails over, and completes **bitwise-equal**
to an undisturbed same-seed twin (loadgen verifies the equality in-process
and this leg re-checks the accounting: completed == submitted - shed); the
killed replica passes probation and rejoins routing
(``replica_recovered``); an expired queued request is shed with a typed
``deadline_exceeded`` rejection; and both artifacts — the history with its
``session_failover``/``replica_recovered`` event rows and the bench curve's
chaos row — validate under schema v7.

Elastic-resume gate (after the serving-chaos gate): a bf16_ef training run on 4
local devices is preempted (injected SIGTERM -> exit 75, emergency
checkpoint), then resumed on 2 devices THROUGH the restart supervisor
(tools/supervise.py) — the v2 checkpoint reshards onto the smaller world.
The merged history.jsonl must validate and carry a topology_change event
row; elastic restore drifting (a reshard that crashes, or stops recording
its provenance) fails the gate here.

Reshard gate (after the elastic gate): the ISSUE 16 cross-topology leg — a
TP=2 x DP=2 token-LM run is preempted at an epoch boundary (exit 75,
emergency v3 checkpoint with per-leaf placement tags); the checkpoint is
round-tripped offline through ``tpuddp_inspect reshard`` across the
model-width crossing (TP -> canonical -> TP) and must come back
byte-identical; then the same run dir resumes at TP=1 x DP=2 through the
reshard-on-load path and the merged history must validate and carry the
``(model 2 -> 1)`` topology_change event. Placement-tag drift, a lossy QKV
relayout, or a reshard that stops recording provenance fails here.

Snapshot gate (after the reshard gate): the ISSUE 18 exact-resume leg — a
training run with step-granular async snapshots armed
(``training.snapshot.every_steps``) is killed MID-epoch via
``preempt@step=N`` (exit 75; the drain flushes the async writer and lands a
``ckpt_<epoch>_s<step>.npz`` with a v4 data cursor), ``tpuddp_inspect ckpt``
must print that cursor, then the run auto-resumes and must (a) log the
"Exact resume ... zero batches replayed" line, (b) finish with per-epoch
losses BITWISE-equal to an uninterrupted same-seed twin, and (c) leave a
schema-v11 history whose run_meta carries the ``snapshot`` provenance
block. A snapshot drain that replays batches, loses the cursor, or stops
recording provenance fails here.

Fleet gate (after the snapshot gate): ``tools/fleet.py chaos-demo`` shares
one CPU-mesh pool between 2 training jobs and 1 serving job under the
fleet controller (ISSUE 11): one training job is SIGKILLed mid-run and
resumes elastically, a late high-priority arrival preempts capacity
through the drain contract (exit 75 -> shrunk $TPUDDP_WORLD_SIZE resume,
never SIGKILL-first), and the serving job autoscales its replicas on a
p99 SLO breach ($TPUDDP_SERVING_REPLICAS). Every job's namespaced
history.jsonl is then independently re-validated with tpuddp_inspect —
a controller that lets co-scheduled jobs corrupt each other's channels
fails here.

Tracing gate (after the observability gate, last): the causal tracing
plane (ISSUE 15, tpuddp/observability/trace.py). A traced training dryrun
(``observability.tracing: true``) and an untraced same-seed twin must
produce IDENTICAL loss trajectories (train/test loss + accuracy per epoch,
compared bitwise on the serialized values) — tracing changes zero
semantics; the traced run must leave a schema-v9-valid ``trace_train.json``
whose span tree nests (no orphan parent_ids — enforced by the validator
whenever the ring dropped nothing) and a run_meta carrying the ``tracing``
provenance block, while the untraced twin must leave NO trace artifact and
a null ``tracing`` field. Then a traced serving sweep (``python -m
tpuddp.serving --demo`` with tracing on) must drain to a schema-valid
``trace_serving.json`` with request/admission/queue_wait span trees and a
``trace_summary`` history row.

Observability gate: a live exporter scrape (a serving engine with the
observability.exporter block must answer /healthz + the serving /metrics
families while running, then SIGTERM-drain to exit 75 with a schema-v5
history), and a flight-recorder leg (a chaos-preempted training run must
leave a tpuddp_inspect-valid flightrec_preempt.json which the restart
supervisor summarizes — --flight-dir — before resuming the run to
completion). A dead endpoint, schema-v5 drift or a missing crash recording
fails here.

Autotune gate (last): the self-tuning loop (ISSUE 19). A deliberately
mis-knobbed traced dryrun (synchronous pipeline, per-step snapshots, no
comm compression) must make ``tpuddp_inspect tune`` fire recommendations
across >= 3 distinct rule classes with evidence citations; ``tools/
autotune.py --quick`` must A/B the diffs through the real epoch driver and
land a schema-v12-valid TUNE report (endorsement honesty validated, not
trusted); and the fleet tuner's apply/measure/revert unit matrix — with an
injected regression forcing the auto-revert — must pass.

Usage: python tools/run_full_gate.py [extra pytest args]

The two-tier contract is documented in README "Testing"; the chaos tier can
still be run alone via tools/run_chaos.py.
"""

import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _schema_gate(env) -> int:
    """Dryrun-train, then validate the artifacts with tpuddp_inspect."""
    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_gate_") as out_dir:
        # the chaos suite's training worker IS the dryrun entry: the full
        # native spawn path (4 virtual CPU devices, synthetic data) with the
        # telemetry window armed so step_stats rows are exercised too
        worker_env = dict(env)
        worker_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
            "TPUDDP_CHAOS_TRAINING": '{"step_stats_every": 4}',
        })
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tests", "_chaos_train_worker.py"),
                out_dir, "2",
            ],
            cwd=REPO, env=worker_env,
        )
        if rc != 0:
            print(f"schema gate: dryrun training exited {rc}", file=sys.stderr)
            return rc
        rc = subprocess.call(
            [sys.executable, inspect, "--validate",
             os.path.join(out_dir, "history.jsonl")],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("schema gate: dryrun history.jsonl failed validation",
                  file=sys.stderr)
            return rc
    bench_json = os.path.join(REPO, "bench_results.json")
    if os.path.exists(bench_json):
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", bench_json],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("schema gate: bench_results.json failed validation",
                  file=sys.stderr)
            return rc
    else:
        print("schema gate: no bench_results.json at repo root (skipped)")
    return 0


def _serving_gate(env) -> int:
    """Drive the serving engine with loadgen, then validate its artifacts."""
    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_serve_gate_") as out_dir:
        worker_env = dict(env)
        worker_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        bench_json = os.path.join(out_dir, "bench_results.json")
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "loadgen.py"),
                "--quick", "--replicas", "2", "--tenants", "2",
                "--history-dir", out_dir, "--out", bench_json,
            ],
            cwd=REPO, env=worker_env,
        )
        if rc != 0:
            print(f"serving gate: loadgen exited {rc}", file=sys.stderr)
            return rc
        for artifact in (os.path.join(out_dir, "history.jsonl"), bench_json):
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", artifact],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(
                    f"serving gate: {os.path.basename(artifact)} failed "
                    "validation", file=sys.stderr,
                )
                return rc
    return 0


def _decode_gate(env) -> int:
    """Decode leg (ISSUE 12): (a) loadgen's --quick token sweep on the CPU
    mesh with both artifacts schema-validated; (b) the drain contract — a
    SIGTERM landing mid-decode must let every in-flight sequence finish
    streaming (completed == submitted, nothing truncated) and exit 75."""
    import json
    import signal
    import time

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_decode_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # -- leg a: the token sweep + artifact validation
        sweep_dir = os.path.join(tmp, "sweep")
        os.makedirs(sweep_dir)
        bench_json = os.path.join(sweep_dir, "bench_results.json")
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "loadgen.py"),
                "--decode", "--quick", "--replicas", "2", "--tenants", "2",
                "--history-dir", sweep_dir, "--out", bench_json,
            ],
            cwd=REPO, env=base_env,
        )
        if rc != 0:
            print(f"decode gate: loadgen --decode exited {rc}",
                  file=sys.stderr)
            return rc
        for artifact in (os.path.join(sweep_dir, "history.jsonl"), bench_json):
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", artifact],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(f"decode gate: {os.path.basename(artifact)} failed "
                      "validation", file=sys.stderr)
                return rc
        # -- leg b: SIGTERM mid-decode -> finish in-flight streams -> 75
        out_dir = os.path.join(tmp, "drain")
        settings = os.path.join(tmp, "settings.yaml")
        with open(settings, "w") as f:
            f.write(
                "out_dir: %s\n"
                "serving:\n"
                "  decode:\n"
                "    vocab_size: 64\n"
                "    max_slots: 4\n"
                "    kv_blocks: 65\n"
                "    kv_block_size: 8\n"
                "    max_seq_len: 128\n"
                # 24 sequences x 96 tokens on 4 slots is seconds of decode
                # on the CPU mesh — the SIGTERM below cannot miss the window,
                # and the in_flight_at_drain assertion proves it didn't
                "    max_new_tokens: 96\n"
                "    stats_window: 32\n" % out_dir
            )
        n_demo = 24
        proc = subprocess.Popen(
            [
                sys.executable, "-u", "-m", "tpuddp.serving",
                "--settings", settings, "--decode",
                "--demo", str(n_demo), "--serve", "120",
            ],
            cwd=REPO, env=base_env,
            stdout=subprocess.PIPE, text=True,
        )
        import threading

        # stdout is drained by a daemon thread so the readiness wait below
        # can enforce a REAL deadline — a blocking readline here would hang
        # the whole gate on a server wedged before its first output line
        lines = []
        ready = threading.Event()

        def _drain_stdout():
            for line in proc.stdout:
                lines.append(line)
                if line.strip() == "serving: ready":
                    ready.set()

        reader = threading.Thread(target=_drain_stdout, daemon=True)
        reader.start()
        try:
            # demo prompts are submitted (NOT waited) before the ready line,
            # so a SIGTERM here lands with sequences genuinely in flight
            deadline = time.time() + 300
            while (time.time() < deadline and not ready.is_set()
                   and proc.poll() is None):
                time.sleep(0.2)
            if not ready.is_set():
                proc.kill()
                print("decode gate: server never reached 'serving: ready' "
                      f"(rc {proc.poll()})", file=sys.stderr)
                return 1
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=300)
            except subprocess.TimeoutExpired:
                proc.kill()
                print("decode gate: drain hung after SIGTERM",
                      file=sys.stderr)
                return 1
            reader.join(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if proc.returncode != 75:
            print(f"decode gate: drained server exited {proc.returncode}, "
                  "expected 75", file=sys.stderr)
            return proc.returncode or 1
        summary = json.loads([l for l in lines if l.strip()][-1])
        if summary.get("completed") != n_demo or summary.get("submitted") != n_demo:
            print(
                "decode gate: drain truncated in-flight sequences "
                f"(submitted {summary.get('submitted')}, completed "
                f"{summary.get('completed')}, expected {n_demo})",
                file=sys.stderr,
            )
            return 1
        if not summary.get("in_flight_at_drain"):
            # completed == submitted proves nothing if the engine was idle
            # when the signal landed — the drain contract is only exercised
            # when sequences were genuinely mid-stream
            print(
                "decode gate: SIGTERM landed on an idle engine "
                f"(in_flight_at_drain={summary.get('in_flight_at_drain')}); "
                "the drain contract was not exercised",
                file=sys.stderr,
            )
            return 1
        rc = subprocess.call(
            [sys.executable, inspect, "--validate",
             os.path.join(out_dir, "history.jsonl")],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("decode gate: drained server history failed validation",
                  file=sys.stderr)
            return rc
        print("decode gate: token sweep artifacts valid + SIGTERM drain "
              f"finished all {n_demo} in-flight sequences (exit 75)")
    return 0


def _serving_chaos_gate(env) -> int:
    """Serving-chaos leg (ISSUE 13, README "Serving survivability"): the
    decode sweep re-runs with ``--chaos`` — a replica is killed MID-SWEEP
    via the real ``$TPUDDP_FAULT`` contract and loadgen itself enforces the
    bitwise headline (every migrated stream equal to its undisturbed
    same-seed twin, replica back after probation, typed deadline shed).
    This leg re-checks the OBSERVABLE evidence independently: the summary
    accounting (zero lost streams: completed == submitted - shed, with
    >= 1 failover and >= 1 shed), the ``session_failover`` /
    ``replica_recovered`` event rows in history.jsonl, and schema-v7
    validity of both artifacts."""
    import json

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_schaos_gate_") as out_dir:
        worker_env = dict(env)
        worker_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        bench_json = os.path.join(out_dir, "bench_results.json")
        out = subprocess.run(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "loadgen.py"),
                "--decode", "--quick", "--chaos",
                "--replicas", "2", "--tenants", "2",
                "--history-dir", out_dir, "--out", bench_json,
            ],
            cwd=REPO, env=worker_env, stdout=subprocess.PIPE, text=True,
        )
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            print(f"serving-chaos gate: loadgen --chaos exited "
                  f"{out.returncode}", file=sys.stderr)
            return out.returncode
        summary = json.loads(
            [l for l in out.stdout.splitlines() if l.strip()][-1]
        )
        if summary.get("failovers", 0) < 1 or summary.get("shed", 0) < 1:
            print(
                "serving-chaos gate: the chaos phase left no evidence "
                f"(failovers={summary.get('failovers')}, "
                f"shed={summary.get('shed')})", file=sys.stderr,
            )
            return 1
        expected = summary.get("submitted", 0) - summary.get("shed", 0)
        if summary.get("completed") != expected:
            print(
                "serving-chaos gate: streams were lost (completed "
                f"{summary.get('completed')} != submitted "
                f"{summary.get('submitted')} - shed {summary.get('shed')})",
                file=sys.stderr,
            )
            return 1
        history = os.path.join(out_dir, "history.jsonl")
        events = set()
        with open(history) as f:
            for line in f:
                if line.strip():
                    rec = json.loads(line)
                    if rec.get("type") == "event":
                        events.add(rec.get("event"))
        for required in ("session_failover", "replica_unhealthy",
                         "replica_recovered"):
            if required not in events:
                print(
                    f"serving-chaos gate: required event {required!r} "
                    f"missing from history (saw {sorted(events)})",
                    file=sys.stderr,
                )
                return 1
        for artifact in (history, bench_json):
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", artifact],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(
                    f"serving-chaos gate: {os.path.basename(artifact)} "
                    "failed validation", file=sys.stderr,
                )
                return rc
        print(
            "serving-chaos gate: replica killed mid-sweep, zero lost "
            f"streams ({summary['completed']} completed, "
            f"{summary['failovers']} failover(s), {summary['shed']} typed "
            "shed), events + schema v7 verified"
        )
    return 0


def _elastic_gate(env) -> int:
    """Preempt a 4-device run, resume it on 2 via the supervisor, validate."""
    import json

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_elastic_gate_") as out_dir:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # leg 1: train on 4 devices with the bf16_ef residual armed; an
        # injected preempt at the epoch-1 boundary drains to exit 75
        env1 = dict(base_env)
        env1.update({
            "TPUDDP_WORLD_SIZE": "4",
            "TPUDDP_FAULT": "preempt@epoch=1",
            "TPUDDP_CHAOS_TRAINING": '{"comm_hook": "bf16_ef"}',
        })
        rc = subprocess.call(
            [sys.executable, "-u", worker, out_dir, "3"],
            cwd=REPO, env=env1,
        )
        if rc != 75:
            print(f"elastic gate: preempted run exited {rc}, expected 75",
                  file=sys.stderr)
            return rc or 1
        # leg 2: resume on 2 devices through the restart supervisor — the
        # elastic v2 restore redistributes the residual onto the halved world
        env2 = dict(base_env)
        env2["TPUDDP_CHAOS_TRAINING"] = (
            '{"comm_hook": "bf16_ef", "train_batch_size": 16, '
            '"test_batch_size": 16}'
        )
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "supervise.py"),
                "--world", "2", "--max-restarts", "2", "--auto-resume",
                "--backoff-base", "0.2",
                "--",
                sys.executable, "-u", worker, out_dir, "3",
            ],
            cwd=REPO, env=env2,
        )
        if rc != 0:
            print(f"elastic gate: supervised resume exited {rc}",
                  file=sys.stderr)
            return rc
        history = os.path.join(out_dir, "history.jsonl")
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", history],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("elastic gate: merged history.jsonl failed validation",
                  file=sys.stderr)
            return rc
        with open(history) as f:
            records = [json.loads(line) for line in f if line.strip()]
        if not any(r.get("event") == "topology_change" for r in records):
            print("elastic gate: no topology_change event row in the resumed "
                  "history", file=sys.stderr)
            return 1
    return 0


def _reshard_gate(env) -> int:
    """Elastic mesh failover (ISSUE 16): preempt a TP=2 x DP=2 job, round-trip
    its emergency checkpoint offline (W -> W' -> W byte-identical through the
    model-width crossing), then resume it at TP=1 x DP=2 — the reshard-on-load
    path — and validate the merged history names the episode."""
    import json

    import numpy as np

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_tp_worker.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_reshard_gate_") as out_dir:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # leg 1: TP=2 x DP=2 (the worker's default mesh), drained at the
        # epoch-1 boundary -> exit 75 + an emergency v3 checkpoint
        env1 = dict(base_env)
        env1.update({
            "TPUDDP_WORLD_SIZE": "4",
            "TPUDDP_FAULT": "preempt@epoch=1",
        })
        rc = subprocess.call(
            [sys.executable, "-u", worker, out_dir, "3"],
            cwd=REPO, env=env1,
        )
        if rc != 75:
            print(f"reshard gate: preempted TP run exited {rc}, expected 75",
                  file=sys.stderr)
            return rc or 1
        src = os.path.join(out_dir, "ckpt_1.npz")
        # leg 2: the offline round trip through the CLI — TP layout ->
        # canonical -> TP layout must be byte-identical
        down = os.path.join(out_dir, "rt_down.npz")
        back = os.path.join(out_dir, "rt_back.npz")
        for args in (
            [src, "--to", "data=4,model=1", "--out", down],
            [down, "--to", "data=2,model=2", "--out", back],
        ):
            rc = subprocess.call(
                [sys.executable, inspect, "reshard", *args],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(f"reshard gate: tpuddp_inspect reshard {args} exited "
                      f"{rc}", file=sys.stderr)
                return rc
        with np.load(src) as f:
            want = dict(f.items())
        with np.load(back) as f:
            got = dict(f.items())
        keys = {k for k in want if k != "__topology__"}
        if keys != {k for k in got if k != "__topology__"}:
            print("reshard gate: round trip changed the leaf set",
                  file=sys.stderr)
            return 1
        for k in keys:
            if not np.array_equal(want[k], got[k]):
                print(f"reshard gate: round trip not byte-identical at {k}",
                      file=sys.stderr)
                return 1
        # leg 3: resume the SAME run dir at TP=1 x DP=2 — the in-loader
        # reshard (worker sets training.reshard_on_mismatch) re-splits the
        # model-axis leaves onto the surviving mesh
        env3 = dict(base_env)
        env3.update({
            "TPUDDP_WORLD_SIZE": "2",
            "TPUDDP_MODEL_SIZE": "1",
            "TPUDDP_AUTO_RESUME": "1",
        })
        rc = subprocess.call(
            [sys.executable, "-u", worker, out_dir, "3"],
            cwd=REPO, env=env3,
        )
        if rc != 0:
            print(f"reshard gate: cross-shape resume exited {rc}",
                  file=sys.stderr)
            return rc
        history = os.path.join(out_dir, "history.jsonl")
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", history],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("reshard gate: merged history.jsonl failed validation",
                  file=sys.stderr)
            return rc
        with open(history) as f:
            records = [json.loads(line) for line in f if line.strip()]
        changes = [
            r for r in records if r.get("event") == "topology_change"
        ]
        if not any(
            r.get("from_model") == 2 and r.get("to_model") == 1
            for r in changes
        ):
            print("reshard gate: no (model 2 -> 1) topology_change event in "
                  "the resumed history", file=sys.stderr)
            return 1
    return 0


def _snapshot_gate(env) -> int:
    """Async step-granular checkpointing (ISSUE 18): kill a snapshot-armed
    run MID-epoch, inspect the cursor-bearing step snapshot, auto-resume to
    completion, and demand bitwise loss parity with an uninterrupted twin."""
    import json
    import re as _re

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    overrides = json.dumps({
        # scan_steps=1 keeps step dispatches batch-granular so the injected
        # preempt lands mid-epoch between snapshot boundaries
        "snapshot": {"every_steps": 3}, "scan_steps": 1,
    })
    with tempfile.TemporaryDirectory(prefix="tpuddp_snap_gate_") as tmp:
        out_dir = os.path.join(tmp, "run")
        twin_dir = os.path.join(tmp, "twin")
        os.makedirs(out_dir)
        os.makedirs(twin_dir)
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "TPUDDP_CHAOS_TRAINING": overrides,
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # leg 1: the uninterrupted twin — the bitwise reference trajectory
        rc = subprocess.call(
            [sys.executable, "-u", worker, twin_dir, "2"],
            cwd=REPO, env=base_env,
        )
        if rc != 0:
            print(f"snapshot gate: twin run exited {rc}", file=sys.stderr)
            return rc or 1
        # leg 2: same seed, killed mid-epoch-0 by an injected SIGTERM; the
        # drain must flush the async writer and land a step snapshot
        env1 = dict(base_env)
        env1["TPUDDP_FAULT"] = "preempt@step=5"
        rc = subprocess.call(
            [sys.executable, "-u", worker, out_dir, "2"],
            cwd=REPO, env=env1,
        )
        if rc != 75:
            print(f"snapshot gate: preempted run exited {rc}, expected 75",
                  file=sys.stderr)
            return rc or 1
        steps = sorted(
            n for n in os.listdir(out_dir)
            if _re.match(r"^ckpt_\d+_s\d+\.npz$", n)
        )
        if not steps:
            print("snapshot gate: the drain left no ckpt_<epoch>_s<step>.npz "
                  f"step snapshot (dir: {sorted(os.listdir(out_dir))})",
                  file=sys.stderr)
            return 1
        # leg 3: the cursor-bearing ckpt summary — tpuddp_inspect must print
        # the v4 data cursor of the freshest step snapshot
        out = subprocess.run(
            [sys.executable, inspect, "ckpt",
             os.path.join(out_dir, steps[-1])],
            cwd=REPO, env=env, stdout=subprocess.PIPE, text=True,
        )
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            print(f"snapshot gate: tpuddp_inspect ckpt exited "
                  f"{out.returncode}", file=sys.stderr)
            return out.returncode
        if "cursor (v4):" not in out.stdout:
            print("snapshot gate: inspect summary of the step snapshot "
                  "prints no v4 cursor", file=sys.stderr)
            return 1
        # leg 4: auto-resume — must continue AT the drained step (zero
        # batches replayed), not redo the epoch
        env2 = dict(base_env)
        env2["TPUDDP_AUTO_RESUME"] = "1"
        out = subprocess.run(
            [sys.executable, "-u", worker, out_dir, "2"],
            cwd=REPO, env=env2, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            print(f"snapshot gate: resumed run exited {out.returncode}",
                  file=sys.stderr)
            return out.returncode
        if "zero batches replayed" not in out.stdout:
            print("snapshot gate: the resumed run never took the exact-"
                  "resume path (no 'zero batches replayed' line)",
                  file=sys.stderr)
            return 1
        # leg 5: bitwise loss parity + schema-v11 provenance
        def epoch_losses(run_dir):
            with open(os.path.join(run_dir, "history.jsonl")) as f:
                records = [json.loads(l) for l in f if l.strip()]
            return records, {
                r["epoch"]: r["train_loss"]
                for r in records if r["type"] == "epoch"
            }

        records, resumed = epoch_losses(out_dir)
        _, ref = epoch_losses(twin_dir)
        if resumed != ref:
            print(f"snapshot gate: resumed losses {resumed} are not bitwise-"
                  f"equal to the uninterrupted twin's {ref}", file=sys.stderr)
            return 1
        metas = [r for r in records if r["type"] == "run_meta"]
        if not any(
            isinstance(m.get("snapshot"), dict)
            and m["snapshot"].get("every_steps") == 3
            for m in metas
        ):
            print("snapshot gate: no run_meta carries the snapshot "
                  "provenance block", file=sys.stderr)
            return 1
        rc = subprocess.call(
            [sys.executable, inspect, "--validate",
             os.path.join(out_dir, "history.jsonl")],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("snapshot gate: merged history.jsonl failed validation",
                  file=sys.stderr)
            return rc
        print(
            "snapshot gate: mid-epoch kill drained to step snapshot "
            f"{steps[-1]}, cursor inspected, exact resume replayed zero "
            "batches, losses bitwise-equal to the twin, v11 provenance "
            "verified"
        )
    return 0


def _comm_matrix_gate(env) -> int:
    """Compression-matrix leg (ISSUE 9): dryrun trainings across the hook x
    topology grid (none/bf16_ef/int8_ef/topk_ef x flat/hierarchical), each
    producing a history.jsonl that must (a) validate against the typed
    schema, (b) carry the comm accounting fields in its run_meta header,
    (c) show the acceptance byte cuts for the quantized/sparse hooks
    (int8_ef >= 70%, topk_ef >= 85% vs the header's own f32 baseline), and
    (d) finish with a final-epoch train loss within the documented per-hook
    parity bound of the uncompressed flat run
    (tpuddp.parallel.comm.loss_parity_tol). Hierarchical rows must also
    report inter-host bytes BELOW the flat run's total — the topology's
    reason to exist, enforced every gate run."""
    import json

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    sys.path.insert(0, REPO)
    from tpuddp.parallel.comm import loss_parity_tol

    with tempfile.TemporaryDirectory(prefix="tpuddp_comm_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        results = {}
        for hook in ("none", "bf16_ef", "int8_ef", "topk_ef"):
            for topology in ("flat", "hierarchical"):
                out_dir = os.path.join(tmp, f"{hook}_{topology}")
                os.makedirs(out_dir)
                worker_env = dict(base_env)
                worker_env["TPUDDP_CHAOS_TRAINING"] = json.dumps({
                    "comm_hook": hook, "comm_topology": topology,
                    "num_epochs": 3,
                })
                rc = subprocess.call(
                    [sys.executable, "-u", worker, out_dir, "3"],
                    cwd=REPO, env=worker_env,
                )
                if rc != 0:
                    print(f"comm gate: {hook}/{topology} dryrun exited {rc}",
                          file=sys.stderr)
                    return rc or 1
                history = os.path.join(out_dir, "history.jsonl")
                rc = subprocess.call(
                    [sys.executable, inspect, "--validate", history],
                    cwd=REPO, env=env,
                )
                if rc != 0:
                    print(f"comm gate: {hook}/{topology} history failed "
                          "validation", file=sys.stderr)
                    return rc
                with open(history) as f:
                    records = [json.loads(l) for l in f if l.strip()]
                meta = next(r for r in records if r["type"] == "run_meta")
                epochs = [r for r in records if r["type"] == "epoch"]
                if meta.get("comm_topology") != topology:
                    print(f"comm gate: {hook}/{topology} header records "
                          f"topology {meta.get('comm_topology')!r}",
                          file=sys.stderr)
                    return 1
                results[(hook, topology)] = {
                    "meta": meta, "final_loss": epochs[-1]["train_loss"],
                }
        base = results[("none", "flat")]
        f32 = base["meta"]["grad_comm_bytes_per_update_f32"]
        for hook, floor in (("int8_ef", 0.70), ("topk_ef", 0.85)):
            per = results[(hook, "flat")]["meta"]["grad_comm_bytes_per_update"]
            cut = 1 - per / f32
            if cut < floor:
                print(f"comm gate: {hook} byte cut {cut * 100:.1f}% is under "
                      f"the {floor * 100:.0f}% floor", file=sys.stderr)
                return 1
        for (hook, topology), row in results.items():
            tol = loss_parity_tol(hook, base["final_loss"])
            if abs(row["final_loss"] - base["final_loss"]) > tol:
                print(
                    f"comm gate: {hook}/{topology} final-epoch loss "
                    f"{row['final_loss']:.4f} diverged from uncompressed "
                    f"{base['final_loss']:.4f} (documented tol {tol:.4f})",
                    file=sys.stderr,
                )
                return 1
            if topology == "hierarchical":
                inter = row["meta"]["grad_comm_bytes_inter_host"]
                flat_total = results[(hook, "flat")]["meta"][
                    "grad_comm_bytes_per_update"
                ]
                if inter >= flat_total:
                    print(
                        f"comm gate: {hook} hierarchical inter-host bytes "
                        f"{inter} not below the flat total {flat_total}",
                        file=sys.stderr,
                    )
                    return 1
        print("comm gate: byte cuts + loss parity + hierarchical hop split "
              "verified across the hook x topology matrix")
    return 0


def _pipeline_gate(env) -> int:
    """Async-pipeline leg (ISSUE 8): a depth-2 pipelined dryrun must produce
    a schema-valid history whose step_stats windows carry the occupancy
    fields, land bitwise-identical checkpoints to a synchronous (pipeline:
    false) run of the same seed, and keep the step HLO identical pipeline
    on/off (the HLO assertion runs as its test, which lowers both programs)."""
    import json

    import numpy as np

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_pipe_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        dirs = {}
        for mode, pipe_cfg in (("on", '{"depth": 2}'), ("off", "false")):
            out_dir = os.path.join(tmp, mode)
            os.makedirs(out_dir)
            dirs[mode] = out_dir
            worker_env = dict(base_env)
            worker_env["TPUDDP_CHAOS_TRAINING"] = (
                '{"step_stats_every": 4, "pipeline": %s}' % pipe_cfg
            )
            rc = subprocess.call(
                [sys.executable, "-u", worker, out_dir, "2"],
                cwd=REPO, env=worker_env,
            )
            if rc != 0:
                print(f"pipeline gate: {mode} dryrun exited {rc}",
                      file=sys.stderr)
                return rc or 1
        history = os.path.join(dirs["on"], "history.jsonl")
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", history],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("pipeline gate: pipelined history.jsonl failed validation",
                  file=sys.stderr)
            return rc
        with open(history) as f:
            records = [json.loads(line) for line in f if line.strip()]
        windows = [r for r in records if r.get("type") == "step_stats"]
        if not windows or any(
            k not in w
            for w in windows
            for k in ("host_stall_ms", "inflight_depth", "staging_queue_depth")
        ):
            print("pipeline gate: step_stats windows missing the occupancy "
                  "fields", file=sys.stderr)
            return 1
        # bitwise parity: the pipelined run's checkpoints must equal the
        # synchronous run's, leaf for leaf (params, moments, counters — the
        # whole TrainState lands in ckpt_{epoch}.npz)
        for fname in ("ckpt_0.npz", "ckpt_1.npz"):
            a = np.load(os.path.join(dirs["on"], fname), allow_pickle=False)
            b = np.load(os.path.join(dirs["off"], fname), allow_pickle=False)
            if sorted(a.files) != sorted(b.files):
                print(f"pipeline gate: {fname} key sets differ",
                      file=sys.stderr)
                return 1
            for k in a.files:
                if a[k].dtype.kind in "SU" or b[k].dtype.kind in "SU":
                    ok = bool(np.array_equal(a[k], b[k]))
                else:
                    ok = a[k].tobytes() == b[k].tobytes()
                if not ok:
                    print(
                        f"pipeline gate: {fname} leaf {k!r} differs between "
                        "pipelined and synchronous runs", file=sys.stderr,
                    )
                    return 1
        # HLO identity pipeline-on/off: the dedicated test lowers the step
        # program under both configs and compares the text byte for byte.
        # Plain env: tests/conftest.py owns its own 8-device XLA_FLAGS and
        # refuses a world pre-pinned to the gate's 4.
        rc = subprocess.call(
            [
                sys.executable, "-m", "pytest", "-q",
                "tests/test_pipeline.py", "-k", "hlo_identity",
                "-p", "no:cacheprovider",
            ],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("pipeline gate: HLO identity test failed", file=sys.stderr)
            return rc
    return 0


def _fleet_gate(env) -> int:
    """Fleet-control-plane leg (ISSUE 11): the scripted multi-job chaos
    demo (2 training + 1 serving + 1 late high-priority arrival on one
    pool: kill one, preempt one, autoscale one) must pass its own checks,
    and every job's namespaced history must ALSO validate when this gate
    re-runs tpuddp_inspect over it independently."""
    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_fleet_gate_") as out_dir:
        gate_env = dict(env)
        gate_env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "fleet.py"),
                "chaos-demo", "--out", out_dir,
            ],
            cwd=REPO, env=gate_env,
        )
        if rc != 0:
            print(f"fleet gate: chaos demo exited {rc}", file=sys.stderr)
            return rc
        jobs_dir = os.path.join(out_dir, "jobs")
        job_names = sorted(os.listdir(jobs_dir))
        if len(job_names) < 4:
            print(f"fleet gate: expected >= 4 namespaced job dirs, found "
                  f"{job_names}", file=sys.stderr)
            return 1
        for name in job_names:
            history = os.path.join(jobs_dir, name, "history.jsonl")
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", history],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(f"fleet gate: {name}/history.jsonl failed validation",
                      file=sys.stderr)
                return rc
        print("fleet gate: kill + preempt + autoscale survived with every "
              "namespaced history valid")
    return 0


def _observability_gate(env) -> int:
    """Live-telemetry leg (ISSUE 10): (a) exporter scrape — a serving engine
    stood up with the observability.exporter block must answer /healthz and
    serve the expected /metrics families while live, then drain to exit 75
    with a schema-v5-valid history; (b) flight recorder — a chaos-preempted
    training run (exit 75) must leave a flightrec_preempt.json that
    tpuddp_inspect validates, and the restart supervisor must summarize it
    (--flight-dir) before resuming the run to completion."""
    import json
    import signal
    import time
    import urllib.request

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")

    # -- exporter scrape leg ------------------------------------------------
    with tempfile.TemporaryDirectory(prefix="tpuddp_obs_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        out_dir = os.path.join(tmp, "serve")
        os.makedirs(out_dir)
        settings = os.path.join(tmp, "settings.yaml")
        with open(settings, "w") as f:
            f.write(
                "out_dir: %s\n"
                "serving:\n"
                "  num_replicas: 2\n"
                "  max_batch_size: 8\n"
                "  stats_window: 16\n"
                "observability:\n"
                "  exporter: true\n"
                "  exporter_port: 0\n" % out_dir
            )
        proc = subprocess.Popen(
            [
                sys.executable, "-u", "-m", "tpuddp.serving",
                "--settings", settings, "--demo", "48", "--serve", "120",
            ],
            cwd=REPO, env=base_env,
        )
        try:
            port_file = os.path.join(out_dir, "exporter.port")
            deadline = time.time() + 120
            port = None
            while time.time() < deadline:
                if os.path.exists(port_file):
                    # line 1 is the port; line 2 the bound host
                    port = int(open(port_file).read().splitlines()[0])
                    break
                if proc.poll() is not None:
                    print("observability gate: serving process died before "
                          f"binding the exporter (rc {proc.returncode})",
                          file=sys.stderr)
                    return proc.returncode or 1
                time.sleep(0.2)
            if port is None:
                print("observability gate: exporter.port never appeared",
                      file=sys.stderr)
                return 1
            # the engine may still be mid-demo: poll until the serving
            # series report traffic (a dead endpoint fails the gate here)
            scraped = None
            while time.time() < deadline:
                health = json.load(urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10
                ))
                if health.get("status") != "ok":
                    print(f"observability gate: /healthz said {health}",
                          file=sys.stderr)
                    return 1
                scraped = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10
                ).read().decode()
                done = [
                    line for line in scraped.splitlines()
                    if line.startswith("tpuddp_serving_completed_total ")
                ]
                if done and float(done[0].split()[-1]) >= 48:
                    break
                time.sleep(0.2)
            for family in (
                "tpuddp_serving_completed_total",
                "tpuddp_serving_e2e_ms",
                "tpuddp_serving_throughput_rps",
                "tpuddp_serving_replicas_healthy",
            ):
                if family not in (scraped or ""):
                    print(f"observability gate: /metrics is missing "
                          f"{family}", file=sys.stderr)
                    return 1
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 75:
            print(f"observability gate: drained server exited {rc}, "
                  "expected 75", file=sys.stderr)
            return rc or 1
        rc = subprocess.call(
            [sys.executable, inspect, "--validate",
             os.path.join(out_dir, "history.jsonl")],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("observability gate: drained server history failed "
                  "validation", file=sys.stderr)
            return rc

        # -- flight recorder leg -------------------------------------------
        train_dir = os.path.join(tmp, "train")
        os.makedirs(train_dir)
        env1 = dict(base_env)
        env1.update({
            "TPUDDP_FAULT": "preempt@epoch=1",
            "TPUDDP_CHAOS_TRAINING": '{"step_stats_every": 2}',
        })
        worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
        rc = subprocess.call(
            [sys.executable, "-u", worker, train_dir, "3"],
            cwd=REPO, env=env1,
        )
        if rc != 75:
            print(f"observability gate: preempted run exited {rc}, "
                  "expected 75", file=sys.stderr)
            return rc or 1
        flightrec = os.path.join(train_dir, "flightrec_preempt.json")
        if not os.path.exists(flightrec):
            print("observability gate: no flightrec_preempt.json after the "
                  "exit-75 drain", file=sys.stderr)
            return 1
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", flightrec],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("observability gate: flight recording failed validation",
                  file=sys.stderr)
            return rc
        # the supervisor picks the recording up (--flight-dir) and resumes
        # the run to completion
        resume = subprocess.run(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "supervise.py"),
                "--max-restarts", "2", "--auto-resume",
                "--backoff-base", "0.2", "--flight-dir", train_dir,
                "--",
                sys.executable, "-u", worker, train_dir, "3",
            ],
            cwd=REPO, env=base_env, capture_output=True, text=True,
        )
        if resume.returncode != 0:
            print("observability gate: supervised resume exited "
                  f"{resume.returncode}\n{resume.stdout}\n{resume.stderr}",
                  file=sys.stderr)
            return resume.returncode
        if "flight recording" not in resume.stderr + resume.stdout:
            print("observability gate: supervisor never summarized the "
                  "flight recording", file=sys.stderr)
            return 1
    print("observability gate: live scrape + flight "
          "recording verified")
    return 0


def _tracing_gate(env) -> int:
    """Causal-tracing leg (ISSUE 15): (a) a traced training dryrun vs an
    untraced same-seed twin — identical loss trajectories, a valid
    trace_train.json with correctly-nesting spans on the traced side, no
    artifact on the untraced side; (b) a traced serving demo draining to a
    valid trace_serving.json with request-tree spans."""
    import json

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_trace_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # -- leg a: traced vs untraced training twins (same seed 0)
        dirs = {}
        for mode, obs in (("traced", '{"tracing": true}'), ("plain", "null")):
            out_dir = os.path.join(tmp, mode)
            os.makedirs(out_dir)
            dirs[mode] = out_dir
            worker_env = dict(base_env)
            worker_env["TPUDDP_CHAOS_OBS"] = obs
            rc = subprocess.call(
                [sys.executable, "-u", worker, out_dir, "2"],
                cwd=REPO, env=worker_env,
            )
            if rc != 0:
                print(f"tracing gate: {mode} dryrun exited {rc}",
                      file=sys.stderr)
                return rc or 1
        trajectories = {}
        metas = {}
        for mode, out_dir in dirs.items():
            with open(os.path.join(out_dir, "history.jsonl")) as f:
                records = [json.loads(l) for l in f if l.strip()]
            metas[mode] = next(r for r in records if r["type"] == "run_meta")
            trajectories[mode] = [
                (r["epoch"], r["train_loss"], r["test_loss"],
                 r["test_accuracy"])
                for r in records if r["type"] == "epoch"
            ]
        if trajectories["traced"] != trajectories["plain"]:
            print("tracing gate: traced and untraced loss trajectories "
                  f"differ:\n  traced: {trajectories['traced']}\n  plain:  "
                  f"{trajectories['plain']}", file=sys.stderr)
            return 1
        if not isinstance(metas["traced"].get("tracing"), dict):
            print("tracing gate: traced run_meta carries no tracing block",
                  file=sys.stderr)
            return 1
        if metas["plain"].get("tracing") is not None:
            print("tracing gate: UNTRACED run_meta carries a tracing block",
                  file=sys.stderr)
            return 1
        trace_art = os.path.join(dirs["traced"], "trace_train.json")
        if not os.path.exists(trace_art):
            print("tracing gate: traced run left no trace_train.json",
                  file=sys.stderr)
            return 1
        if os.path.exists(os.path.join(dirs["plain"], "trace_train.json")):
            print("tracing gate: UNTRACED run left a trace_train.json",
                  file=sys.stderr)
            return 1
        for target in (trace_art, os.path.join(dirs["traced"], "history.jsonl")):
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", target],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(f"tracing gate: {os.path.basename(target)} failed "
                      "validation", file=sys.stderr)
                return rc
        with open(trace_art) as f:
            payload = json.load(f)
        spans = [
            e for e in payload["traceEvents"]
            if isinstance(e, dict) and e.get("ph") == "X"
        ]
        kinds = {e.get("cat") for e in spans}
        for required in ("epoch", "stage", "dispatch", "readback"):
            if required not in kinds:
                print(f"tracing gate: training trace has no {required!r} "
                      f"spans (saw {sorted(kinds)})", file=sys.stderr)
                return 1
        if payload["tpuddp"]["dropped"] == 0:
            # the validator already enforced no-orphans; double-check here
            # so the gate's contract is explicit even if the validator drifts
            ids = {e["args"]["span_id"] for e in spans}
            orphans = [
                e for e in spans
                if e["args"].get("parent_id") is not None
                and e["args"]["parent_id"] not in ids
            ]
            if orphans:
                print(f"tracing gate: {len(orphans)} orphan parent_id(s) in "
                      "the training trace", file=sys.stderr)
                return 1
        # -- leg b: traced serving demo
        serve_dir = os.path.join(tmp, "serve")
        os.makedirs(serve_dir)
        settings = os.path.join(tmp, "settings.yaml")
        with open(settings, "w") as f:
            f.write(
                "out_dir: %s\n"
                "serving:\n"
                "  num_replicas: 2\n"
                "  max_batch_size: 8\n"
                "  stats_window: 16\n"
                "observability:\n"
                "  tracing: true\n" % serve_dir
            )
        rc = subprocess.call(
            [
                sys.executable, "-u", "-m", "tpuddp.serving",
                "--settings", settings, "--demo", "24",
            ],
            cwd=REPO, env=base_env, stdout=subprocess.DEVNULL,
        )
        if rc != 0:
            print(f"tracing gate: traced serving demo exited {rc}",
                  file=sys.stderr)
            return rc
        serve_trace = os.path.join(serve_dir, "trace_serving.json")
        if not os.path.exists(serve_trace):
            print("tracing gate: serving drain left no trace_serving.json",
                  file=sys.stderr)
            return 1
        for target in (serve_trace, os.path.join(serve_dir, "history.jsonl")):
            rc = subprocess.call(
                [sys.executable, inspect, "--validate", target],
                cwd=REPO, env=env,
            )
            if rc != 0:
                print(f"tracing gate: {os.path.basename(target)} failed "
                      "validation", file=sys.stderr)
                return rc
        with open(serve_trace) as f:
            kinds = {
                e.get("cat")
                for e in json.load(f)["traceEvents"]
                if isinstance(e, dict) and e.get("ph") == "X"
            }
        for required in ("request", "admission", "queue_wait"):
            if required not in kinds:
                print(f"tracing gate: serving trace has no {required!r} "
                      f"spans (saw {sorted(kinds)})", file=sys.stderr)
                return 1
        with open(os.path.join(serve_dir, "history.jsonl")) as f:
            has_summary = any(
                json.loads(l).get("type") == "trace_summary"
                for l in f if l.strip()
            )
        if not has_summary:
            print("tracing gate: serving history has no trace_summary row",
                  file=sys.stderr)
            return 1
    print("tracing gate: traced/untraced twins bitwise-equal, both trace "
          "artifacts schema-v9 valid with nesting span trees")
    return 0


def _autotune_gate(env) -> int:
    """Self-tuning leg (ISSUE 19): (a) a deliberately mis-knobbed traced
    dryrun (synchronous pipeline, per-step snapshots, no comm compression)
    must make ``tpuddp_inspect tune`` fire recommendations across >= 3
    distinct rule classes, each citing its evidence; (b) ``tools/autotune.py
    --quick`` must A/B the advisor's diffs through the real epoch driver and
    write a TUNE report that ``tpuddp_inspect --validate`` accepts under
    schema v12 (the endorsement-honesty contract is validated, not trusted);
    (c) the fleet tuner's apply/measure/revert state machine must pass its
    unit matrix — including the injected-regression auto-revert — via
    ``pytest tests/test_tune.py -k fleet``."""
    import json

    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    worker = os.path.join(REPO, "tests", "_chaos_train_worker.py")
    with tempfile.TemporaryDirectory(prefix="tpuddp_tune_gate_") as tmp:
        base_env = dict(env)
        base_env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "TPUDDP_BACKEND": "cpu",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        # -- leg a: the bad-knob dryrun the advisor must see through
        run_dir = os.path.join(tmp, "badknobs")
        os.makedirs(run_dir)
        worker_env = dict(base_env)
        worker_env.update({
            "TPUDDP_CHAOS_TRAINING": json.dumps({
                "pipeline": False,
                "snapshot": {"every_steps": 1, "inflight": 1},
                "step_stats_every": 4,
            }),
            "TPUDDP_CHAOS_OBS": '{"tracing": true}',
        })
        rc = subprocess.call(
            [sys.executable, "-u", worker, run_dir, "2"],
            cwd=REPO, env=worker_env,
        )
        if rc != 0:
            print(f"autotune gate: bad-knob dryrun exited {rc}",
                  file=sys.stderr)
            return rc or 1
        out = subprocess.run(
            [sys.executable, inspect, "tune", run_dir, "--json"],
            cwd=REPO, env=env, stdout=subprocess.PIPE, text=True,
        )
        if out.returncode != 0:
            print(f"autotune gate: tpuddp_inspect tune exited "
                  f"{out.returncode}", file=sys.stderr)
            return out.returncode
        report = json.loads(out.stdout)
        recs = report.get("recommendations") or []
        classes = sorted({r.get("rule_class") for r in recs})
        if len(classes) < 3:
            print(
                "autotune gate: the advisor fired "
                f"{[r.get('rule') for r in recs]} — expected >= 3 distinct "
                f"rule classes on the bad-knob run, got {classes}",
                file=sys.stderr,
            )
            return 1
        if any(not r.get("evidence") for r in recs):
            print("autotune gate: a recommendation shipped without evidence "
                  "citations", file=sys.stderr)
            return 1
        # -- leg b: the A/B probe must measure the diffs and write a report
        # its own reader accepts (validated again here, independently)
        tune_json = os.path.join(tmp, "TUNE_gate.json")
        rc = subprocess.call(
            [
                sys.executable, "-u",
                os.path.join(REPO, "tools", "autotune.py"),
                "--quick", "--out", tune_json,
            ],
            cwd=REPO, env=base_env,
        )
        if rc != 0:
            print(f"autotune gate: autotune --quick exited {rc}",
                  file=sys.stderr)
            return rc
        if not os.path.exists(tune_json):
            print("autotune gate: autotune --quick wrote no report",
                  file=sys.stderr)
            return 1
        rc = subprocess.call(
            [sys.executable, inspect, "--validate", tune_json],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("autotune gate: the TUNE report failed schema-v12 "
                  "validation", file=sys.stderr)
            return rc
        # -- leg c: the online tuner's unit matrix (apply -> measure ->
        # keep/revert, injected regression, endorsement gating). Plain env:
        # tests/conftest.py owns its own 8-device XLA_FLAGS.
        rc = subprocess.call(
            [
                sys.executable, "-m", "pytest", "-q",
                "tests/test_tune.py", "-k", "fleet",
                "-p", "no:cacheprovider",
            ],
            cwd=REPO, env=env,
        )
        if rc != 0:
            print("autotune gate: fleet tuner unit matrix failed",
                  file=sys.stderr)
            return rc
        print(
            f"autotune gate: advisor fired rule classes {classes} on the "
            "bad-knob run, A/B probe report schema-v12 valid, fleet "
            "apply/measure/revert matrix green"
        )
    return 0


def main(argv=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")  # the full gate never needs a real TPU
    cmd = [
        sys.executable, "-m", "pytest", "tests", "-q",
        "-m", "slow or not slow",
        "-p", "no:cacheprovider",
        *(argv if argv is not None else sys.argv[1:]),
    ]
    rc = subprocess.call(cmd, cwd=REPO, env=env)
    if rc != 0:
        return rc
    rc = _schema_gate(env)
    if rc != 0:
        return rc
    rc = _pipeline_gate(env)
    if rc != 0:
        return rc
    rc = _comm_matrix_gate(env)
    if rc != 0:
        return rc
    rc = _serving_gate(env)
    if rc != 0:
        return rc
    rc = _decode_gate(env)
    if rc:
        return rc
    rc = _serving_chaos_gate(env)
    if rc != 0:
        return rc
    rc = _elastic_gate(env)
    if rc != 0:
        return rc
    rc = _reshard_gate(env)
    if rc != 0:
        return rc
    rc = _snapshot_gate(env)
    if rc != 0:
        return rc
    rc = _fleet_gate(env)
    if rc != 0:
        return rc
    rc = _observability_gate(env)
    if rc != 0:
        return rc
    rc = _tracing_gate(env)
    if rc != 0:
        return rc
    return _autotune_gate(env)


if __name__ == "__main__":
    sys.exit(main())
