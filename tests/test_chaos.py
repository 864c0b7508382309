"""Chaos suite (ISSUE 1 + ISSUE 3 acceptance): real subprocess kills and
injected faults against the resilience layer.

Scenarios: an external SIGTERM mid-training drains into a valid emergency
checkpoint and exit 75, auto-resume continues exactly where it left off; a
corrupted newest checkpoint is skipped in favor of the previous good one; an
injected ``hang@barrier`` dead peer is detected by the heartbeat watchdog
within the configured timeout (exit 76) instead of hanging forever; an
injected ``nan@step=N`` gradient is skipped by the numerical-guard firewall
(state stays finite, run finishes 0); a single-replica parameter
perturbation is caught by the desync auditor — exit 77, or a recorded
rollback-to-last-good when ``on_desync="rollback"``.

Marked ``chaos`` + ``slow``: run with ``tools/run_chaos.py`` or
``pytest -m chaos``; never part of the tier-1 fast path.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from tpuddp.resilience import integrity
from tpuddp.resilience.preemption import (
    EXIT_DESYNC,
    EXIT_INJECTED_CRASH,
    EXIT_PREEMPTED,
    EXIT_WATCHDOG,
)
from tpuddp.training import checkpoint as ckpt

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_WORKER = os.path.join(REPO, "tests", "_chaos_train_worker.py")
ACCEL_WORKER = os.path.join(REPO, "tests", "_chaos_accel_worker.py")
HANG_WORKER = os.path.join(REPO, "tests", "_chaos_hang_worker.py")
DESYNC_WORKER = os.path.join(REPO, "tests", "_chaos_desync_worker.py")
SUPERVISE = os.path.join(REPO, "tools", "supervise.py")


def chaos_env(**extra):
    env = dict(os.environ)
    # clean CPU-only children: no inherited fault/resume flags
    for k in (
        "TPUDDP_FAULT", "TPUDDP_AUTO_RESUME", "TPUDDP_WATCHDOG_TIMEOUT",
        "TPUDDP_CHAOS_TRAINING", "TPUDDP_DEBUG_NANS", "TPUDDP_WORLD_SIZE",
        "TPUDDP_MODEL_SIZE", "TPUDDP_CHAOS_PARALLEL",
    ):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TPUDDP_BACKEND"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({k: str(v) for k, v in extra.items()})
    return env


def run_train_worker(out_dir, epochs, env, timeout=300, worker=TRAIN_WORKER):
    return subprocess.run(
        [sys.executable, "-u", worker, str(out_dir), str(epochs)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout,
    )


def validate_history(out_dir):
    """tpuddp_inspect --validate must accept the (merged, multi-run)
    history.jsonl — the schema-v2 stream the elastic matrix asserts."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
            "--validate", os.path.join(str(out_dir), "history.jsonl"),
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def topology_events(out_dir):
    return [
        r for r in history_records(out_dir)
        if r.get("event") == "topology_change"
    ]


def history_records(out_dir):
    with open(os.path.join(str(out_dir), "history.jsonl")) as f:
        return [json.loads(line) for line in f]


def history_epochs(out_dir):
    # typed record stream (tpuddp/observability/schema.py): epoch progress is
    # the `epoch`-type rows; run_meta headers and event rows ride alongside
    return [r["epoch"] for r in history_records(out_dir) if r.get("type") == "epoch"]


def test_sigterm_drain_then_auto_resume_round_trip(tmp_path):
    """The headline scenario: a scheduler SIGTERMs the run mid-training; it
    drains into an intact emergency checkpoint and exits 75; the requeued
    command (same argv + $TPUDDP_AUTO_RESUME=1) continues from the recorded
    epoch with no epoch skipped and none lost."""
    epochs = 30
    proc = subprocess.Popen(
        [sys.executable, "-u", TRAIN_WORKER, str(tmp_path), str(epochs)],
        env=chaos_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    killed = False
    deadline = time.time() + 240
    lines = []
    for line in proc.stdout:  # epoch banners stream as training progresses
        lines.append(line)
        if not killed and ", Epoch 1" in line:
            proc.send_signal(signal.SIGTERM)
            killed = True
        assert time.time() < deadline, "worker did not finish draining in time"
    rc = proc.wait(timeout=60)
    out = "".join(lines)
    assert killed, f"never saw the epoch-1 banner:\n{out[-2000:]}"
    assert rc == EXIT_PREEMPTED, f"exit {rc} != {EXIT_PREEMPTED}:\n{out[-2000:]}"
    assert "emergency checkpoint" in out

    # the emergency save is the newest checkpoint, intact, and marked as a
    # mid-epoch drain (completed=0 -> resume redoes that epoch)
    found = ckpt.latest(str(tmp_path))
    assert found is not None
    path, interrupted_epoch = found
    assert integrity.verify_file(path)
    assert ckpt.read_meta(path)["completed"] == 0

    # the drain's fsync'd event row survived the kill (MetricsWriter.sync on
    # the preemption path): the interrupted run's LAST record is a complete
    # preempt event — never a truncated line
    records = history_records(tmp_path)
    assert records[-1].get("event") == "preempt", records[-1]
    assert records[-1]["epoch"] == interrupted_epoch
    assert records[0].get("type") == "run_meta"

    resumed = run_train_worker(tmp_path, epochs=6, env=chaos_env(TPUDDP_AUTO_RESUME=1))
    assert resumed.returncode == 0, resumed.stdout[-2000:] + resumed.stderr[-2000:]
    assert f"Auto-resume: continuing from epoch {interrupted_epoch}." in resumed.stdout
    assert "Finished Training" in resumed.stdout
    # exact continuation: run 1 logged epochs [0..k), run 2 logged [k..6) —
    # appended history covers every epoch exactly once, in order
    assert history_epochs(tmp_path) == list(range(6))


def test_injected_preempt_is_deterministic(tmp_path):
    """preempt@epoch=1 SIGTERMs the process from inside at a known point: the
    drain must land the emergency checkpoint at exactly epoch 1."""
    first = run_train_worker(
        tmp_path, epochs=4, env=chaos_env(TPUDDP_FAULT="preempt@epoch=1")
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    emergency = os.path.join(str(tmp_path), "ckpt_1.npz")
    assert integrity.verify_file(emergency)
    assert ckpt.read_meta(emergency) == {"epoch": 1, "completed": 0}

    resumed = run_train_worker(tmp_path, epochs=4, env=chaos_env(TPUDDP_AUTO_RESUME=1))
    assert resumed.returncode == 0, resumed.stdout[-2000:] + resumed.stderr[-2000:]
    assert "Auto-resume: continuing from epoch 1." in resumed.stdout
    assert history_epochs(tmp_path) == [0, 1, 2, 3]


def test_corrupt_newest_checkpoint_falls_back_on_resume(tmp_path):
    """corrupt@ckpt_1 garbles the epoch-1 checkpoint after publish, then
    crash@epoch=2 kills the run uncleanly (exit 113). The resumed run must
    skip the corrupt newest file with a logged warning and continue from the
    previous good epoch — redoing epoch 1 rather than crashing or trusting
    torn bytes."""
    first = run_train_worker(
        tmp_path, epochs=4,
        env=chaos_env(TPUDDP_FAULT="corrupt@ckpt_1,crash@epoch=2"),
    )
    assert first.returncode == EXIT_INJECTED_CRASH, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    assert integrity.verify_file(os.path.join(str(tmp_path), "ckpt_0.npz"))
    assert not integrity.verify_file(os.path.join(str(tmp_path), "ckpt_1.npz"))

    resumed = run_train_worker(tmp_path, epochs=4, env=chaos_env(TPUDDP_AUTO_RESUME=1))
    assert resumed.returncode == 0, resumed.stdout[-2000:] + resumed.stderr[-2000:]
    both = resumed.stdout + resumed.stderr
    assert "failed integrity verification" in both
    assert "Auto-resume: continuing from epoch 1." in resumed.stdout
    # epoch 1 ran twice: its first checkpoint was corrupted, so the resumed
    # run redid it from the epoch-0 state
    assert history_epochs(tmp_path) == [0, 1, 1, 2, 3]
    assert integrity.verify_file(os.path.join(str(tmp_path), "ckpt_3.npz"))


def test_nan_gradient_firewalled_end_to_end(tmp_path):
    """ISSUE 3 chaos proof, firewall leg: a nan@step=N fault poisons one
    train micro-batch's gradient mid-run; the guarded run must skip exactly
    that update (recorded in history.jsonl), keep every later epoch finite,
    and finish with exit 0 — the poisoned step never reaches the state."""
    proc = run_train_worker(
        tmp_path, epochs=4,
        env=chaos_env(
            TPUDDP_FAULT="nan@step=12",  # epoch 1 (8 batch groups/epoch)
            TPUDDP_CHAOS_TRAINING=json.dumps({"guard": True}),
        ),
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "nan@step=12 fired" in proc.stdout + proc.stderr
    rows = [r for r in history_records(tmp_path) if r.get("type") == "epoch"]
    assert [r["epoch"] for r in rows] == [0, 1, 2, 3]
    # the skip also landed as a typed event row next to the epoch fields
    assert any(
        r.get("event") == "skipped_updates" and r["epoch"] == 1
        for r in history_records(tmp_path)
    )
    by_epoch = {r["epoch"]: r for r in rows}
    assert by_epoch[1]["skipped_steps_epoch"] == 1
    assert by_epoch[0]["skipped_steps_epoch"] == 0
    assert by_epoch[3]["skipped_steps"] == 1
    # the poisoned epoch's row is a strict-JSON post-mortem (null, not NaN);
    # every later epoch trains on finite numbers
    assert by_epoch[1]["train_loss"] is None
    for e in (2, 3):
        assert by_epoch[e]["train_loss"] is not None
        assert np.isfinite(by_epoch[e]["train_loss"])


def test_desync_auditor_exits_77(tmp_path):
    """ISSUE 3 chaos proof, auditor leg: one device's copy of a replicated
    parameter is perturbed; the next epoch-boundary audit must name the
    divergent leaf and exit EXIT_DESYNC (77)."""
    proc = subprocess.run(
        [sys.executable, "-u", DESYNC_WORKER, str(tmp_path), "exit"],
        env=chaos_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == EXIT_DESYNC, (
        f"exit {proc.returncode}:\n" + proc.stdout[-2000:] + proc.stderr[-2000:]
    )
    both = proc.stdout + proc.stderr
    assert "cross-replica desync" in both
    assert "bias" in both or "weight" in both  # the leaf is named
    rows = [
        json.loads(line)
        for line in open(os.path.join(str(tmp_path), "history.jsonl"))
    ]
    assert any(r.get("event") == "desync" for r in rows)


def test_desync_rollback_recovers_and_finishes(tmp_path):
    """ISSUE 3 chaos proof, rollback leg: with on_desync="rollback" and an
    intact epoch-0 checkpoint, the perturbed state is discarded, the run
    restores last-good, redoes the epoch, and finishes with exit 0 and a
    rollback event in history.jsonl."""
    proc = subprocess.run(
        [sys.executable, "-u", DESYNC_WORKER, str(tmp_path), "rollback"],
        env=chaos_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (
        f"exit {proc.returncode}:\n" + proc.stdout[-2000:] + proc.stderr[-2000:]
    )
    assert "Guard rollback" in proc.stdout + proc.stderr
    rows = [
        json.loads(line)
        for line in open(os.path.join(str(tmp_path), "history.jsonl"))
    ]
    events = [r for r in rows if r.get("event") == "rollback"]
    assert events and events[0]["resume_epoch"] == 1
    assert [r["epoch"] for r in rows if "train_loss" in r] == [0, 1, 2]


ELASTIC_CFG = {"comm_hook": "bf16_ef", "flip": False}  # bf16_ef arms the
# per-replica error-feedback residual — the hardest state to move between
# world sizes; flip off keeps the trajectory partition-independent so the
# parity leg compares like with like.


def _elastic_training(world_bs, **extra):
    cfg = dict(ELASTIC_CFG)
    cfg.update(train_batch_size=world_bs, test_batch_size=world_bs)
    cfg.update(extra)
    return json.dumps(cfg)


def test_elastic_shrink_resume_with_loss_parity(tmp_path):
    """ISSUE 7 chaos proof, headline leg: a bf16_ef run killed on 4 devices
    at the epoch-2 boundary resumes on 2 devices (same GLOBAL batch: the
    per-replica batch size doubles) through the elastic v2 restore — the
    residual redistributes sum-preservingly (M | N: no reset), a
    topology-change event row lands in history.jsonl, the merged stream
    validates as schema v2, and the post-resume loss trajectory matches an
    uninterrupted same-seed 4-device run (the trajectory only moves by the
    partition's f32/bf16 reassociation, not by any lost state)."""
    epochs = 4
    # uninterrupted baseline, world 4 x bs 8 (global 32)
    base_dir = tmp_path / "baseline"
    base = run_train_worker(
        base_dir, epochs,
        env=chaos_env(TPUDDP_CHAOS_TRAINING=_elastic_training(8)),
    )
    assert base.returncode == 0, base.stdout[-2000:] + base.stderr[-2000:]
    base_rows = {
        r["epoch"]: r for r in history_records(base_dir)
        if r.get("type") == "epoch"
    }

    # killed run: same seed/config, preempted at the epoch-2 boundary
    out = tmp_path / "elastic"
    first = run_train_worker(
        out, epochs,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(8),
            TPUDDP_FAULT="preempt@epoch=2",
        ),
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    emergency = os.path.join(str(out), "ckpt_2.npz")
    assert ckpt.read_meta(emergency) == {"epoch": 2, "completed": 0}
    topo = ckpt.read_topology(emergency)
    assert topo["world_size"] == 4
    assert topo["leaves"][".comm_state"]["kind"] == "per_replica"

    # resume on HALF the world, per-replica batch doubled (global unchanged)
    resumed = run_train_worker(
        out, epochs,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(16),
            TPUDDP_AUTO_RESUME=1,
            TPUDDP_WORLD_SIZE=2,
        ),
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert "Auto-resume: continuing from epoch 2." in resumed.stdout

    # every epoch trained exactly once across the two runs
    assert history_epochs(out) == list(range(epochs))
    # the topology change is a typed, validated record
    events = topology_events(out)
    assert events and events[0]["from_world"] == 4
    assert events[0]["to_world"] == 2
    assert events[0]["residual"] == "redistributed"  # M | N: NO reset
    assert ".comm_state" in events[0]["resharded_leaves"]
    assert not any(
        r.get("event") == "comm_state_reset" for r in history_records(out)
    )
    # the resumed run's header names its provenance
    metas = [
        r for r in history_records(out)
        if r.get("type") == "run_meta" and r.get("resumed_from_world")
    ]
    assert metas and metas[0]["resumed_from_world"] == 4
    assert metas[0]["world_size"] == 2
    validate_history(out)

    # loss-trajectory parity vs the uninterrupted run: epochs 0-1 ran on the
    # identical world (bitwise-equal states feed epoch 2), epochs 2-3 see the
    # SAME global batches partitioned 2-ways instead of 4 — only f32
    # reduction order and per-replica bf16 rounding move, bounded small
    el_rows = {
        r["epoch"]: r for r in history_records(out) if r.get("type") == "epoch"
    }
    for e in range(epochs):
        assert np.isfinite(el_rows[e]["train_loss"])
        np.testing.assert_allclose(
            el_rows[e]["train_loss"], base_rows[e]["train_loss"],
            rtol=0.05, atol=0.05,
            err_msg=f"epoch {e} train-loss parity broken",
        )
        np.testing.assert_allclose(
            el_rows[e]["test_loss"], base_rows[e]["test_loss"],
            rtol=0.05, atol=0.05,
            err_msg=f"epoch {e} test-loss parity broken",
        )


def test_elastic_grow_resume_after_midepoch_kill(tmp_path):
    """N < M leg: a 2-device run is killed MID-epoch (preempt@step fires
    inside epoch 1's train pass) and resumes on 4 devices. The emergency
    checkpoint carries mid-epoch state (completed=0 -> epoch 1 is redone
    from it), the residual redistributes by placement (N | M), and the
    finished stream validates."""
    out = tmp_path / "grow"
    first = run_train_worker(
        out, 3,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(16),
            TPUDDP_WORLD_SIZE=2,
            TPUDDP_FAULT="preempt@step=12",  # epoch 1, batch 4 of 8
        ),
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    assert "preempt@step fired" in first.stdout + first.stderr
    found = ckpt.latest(str(out))
    assert found is not None
    path, epoch = found
    assert epoch == 1 and ckpt.read_meta(path)["completed"] == 0
    assert ckpt.read_topology(path)["world_size"] == 2

    resumed = run_train_worker(
        out, 3,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(8),
            TPUDDP_AUTO_RESUME=1,
            TPUDDP_WORLD_SIZE=4,
        ),
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert "Auto-resume: continuing from epoch 1." in resumed.stdout
    # run 1 completed epoch 0 only; the interrupted epoch 1 is redone on 4
    assert history_epochs(out) == [0, 1, 2]
    events = topology_events(out)
    assert events and (events[0]["from_world"], events[0]["to_world"]) == (2, 4)
    assert events[0]["residual"] == "redistributed"
    validate_history(out)


def test_elastic_resume_managed_entrypoint(tmp_path):
    """Accelerator-entrypoint leg: a managed run with weight-update sharding
    (flat world-padded moment vectors — the data_flat reshard) killed on 4
    devices resumes on 2 through load_state's elastic path, lands the
    topology-change event row, and finishes with a valid stream."""
    cfg = {"weight_update_sharding": True, "flip": False}
    out = tmp_path / "managed"
    first = run_train_worker(
        out, 4,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=json.dumps(dict(cfg, train_batch_size=8,
                                                  test_batch_size=8)),
            TPUDDP_FAULT="preempt@epoch=2",
        ),
        worker=ACCEL_WORKER,
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    # the managed drain publishes the last COMPLETED epoch's lossless state
    found = ckpt.latest(str(out), prefix="state")
    assert found is not None and found[1] == 1
    assert ckpt.read_topology(found[0])["world_size"] == 4

    resumed = run_train_worker(
        out, 4,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=json.dumps(dict(cfg, train_batch_size=16,
                                                  test_batch_size=16)),
            TPUDDP_AUTO_RESUME=1,
            TPUDDP_WORLD_SIZE=2,
        ),
        worker=ACCEL_WORKER,
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert "Resumed from epoch 1 state." in resumed.stdout
    assert history_epochs(out) == [0, 1, 2, 3]
    events = topology_events(out)
    assert events and (events[0]["from_world"], events[0]["to_world"]) == (4, 2)
    # WUS flat moments re-padded onto the smaller world
    assert any(
        leaf.startswith("['opt_state']")
        for leaf in events[0]["resharded_leaves"]
    ), events[0]
    metas = [
        r for r in history_records(out)
        if r.get("type") == "run_meta" and r.get("resumed_from_world")
    ]
    assert metas and metas[0]["resumed_from_world"] == 4
    validate_history(out)


def test_elastic_mismatched_world_resets_residual(tmp_path):
    """M∤N leg (4 -> 3): no sum-preserving redistribution exists, so the
    bf16_ef residual RESETS — the run must still resume and finish, with the
    documented comm_state_reset event row beside the topology change."""
    out = tmp_path / "mismatch"
    first = run_train_worker(
        out, 3,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(8),
            TPUDDP_FAULT="preempt@epoch=1",
        ),
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    resumed = run_train_worker(
        out, 3,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=_elastic_training(8),
            TPUDDP_AUTO_RESUME=1,
            TPUDDP_WORLD_SIZE=3,
        ),
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    events = topology_events(out)
    assert events and events[0]["residual"] == "reset"
    resets = [
        r for r in history_records(out)
        if r.get("event") == "comm_state_reset"
    ]
    assert resets and resets[0]["from_world"] == 4
    assert resets[0]["to_world"] == 3
    assert history_epochs(out) == [0, 1, 2]
    validate_history(out)


TP_WORKER = os.path.join(REPO, "tests", "_chaos_tp_worker.py")


def test_tp_mesh_failover_both_smaller_shapes_with_loss_parity(tmp_path):
    """ISSUE 16 headline: a TP=2 x DP=2 token-LM job killed mid-epoch
    auto-resumes at BOTH feasible 2-chip shapes — TP=2 x DP=1 (data shrink)
    AND TP=1 x DP=2 (model-width crossing, full reshard) — and each lands
    the same loss trajectory as the uninterrupted 4-chip run. The reshard
    episode is named on every surface: typed topology_change rows with
    model widths, a run_meta resumed_from_model header, an 'elastic
    reshard' trace span, and (second leg, preempted again post-reshard) a
    flight-recorder note in the crash dump."""
    epochs = 3
    base_dir = tmp_path / "baseline"
    base = run_train_worker(base_dir, epochs, env=chaos_env(),
                            worker=TP_WORKER)
    assert base.returncode == 0, base.stdout[-2000:] + base.stderr[-2000:]
    base_rows = {
        r["epoch"]: r for r in history_records(base_dir)
        if r.get("type") == "epoch"
    }

    killed = tmp_path / "tp_elastic"
    first = run_train_worker(
        killed, epochs,
        env=chaos_env(TPUDDP_FAULT="preempt@epoch=1",
                      TPUDDP_CHAOS_OBS='{"tracing": true}'),
        worker=TP_WORKER,
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    emergency = os.path.join(str(killed), "ckpt_1.npz")
    assert ckpt.read_meta(emergency) == {"epoch": 1, "completed": 0}
    topo = ckpt.read_topology(emergency)
    assert topo["world_size"] == 4
    assert topo["model_size"] == 2
    assert topo["placement"]  # model-sharded leaves are tagged

    # fork the killed run dir: ONE capacity-loss event, both target shapes
    shrunk_tp = tmp_path / "tp2dp1"
    shutil.copytree(str(killed), str(shrunk_tp))

    # --- leg 1: TP=2 x DP=1 (the data axis absorbed the loss) -----------
    resumed = run_train_worker(
        shrunk_tp, epochs,
        env=chaos_env(TPUDDP_AUTO_RESUME=1, TPUDDP_WORLD_SIZE=2,
                      TPUDDP_MODEL_SIZE=2,
                      TPUDDP_CHAOS_OBS='{"tracing": true}'),
        worker=TP_WORKER,
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert "Auto-resume: continuing from epoch 1." in resumed.stdout
    assert history_epochs(shrunk_tp) == list(range(epochs))
    events = topology_events(shrunk_tp)
    assert events and (events[0]["from_world"], events[0]["to_world"]) == (4, 2)
    assert (events[0]["from_model"], events[0]["to_model"]) == (2, 2)
    metas = [
        r for r in history_records(shrunk_tp)
        if r.get("type") == "run_meta" and r.get("resumed_from_world")
    ]
    assert metas and metas[0]["resumed_from_world"] == 4
    assert metas[0]["resumed_from_model"] == 2
    assert metas[0]["mesh"] == {
        "data": 1, "model": 2, "tp_rules_hash": metas[0]["mesh"]["tp_rules_hash"],
    }
    validate_history(shrunk_tp)
    # the reshard episode is a named span in the resumed run's trace
    with open(os.path.join(str(shrunk_tp), "trace_train.json")) as f:
        spans = [
            e for e in json.load(f)["traceEvents"]
            if isinstance(e, dict) and e.get("ph") == "X"
        ]
    reshard_spans = [e for e in spans if e["name"] == "elastic reshard"]
    assert reshard_spans, [e["name"] for e in spans]
    assert reshard_spans[0]["args"]["from_world"] == 4
    assert reshard_spans[0]["args"]["to_world"] == 2

    # --- leg 2: TP=1 x DP=2 (model-width crossing) — preempted AGAIN so
    # the crash dump proves the flight recorder names the episode ---------
    second = run_train_worker(
        killed, epochs,
        env=chaos_env(TPUDDP_AUTO_RESUME=1, TPUDDP_WORLD_SIZE=2,
                      TPUDDP_MODEL_SIZE=1,
                      TPUDDP_FAULT="preempt@epoch=2"),
        worker=TP_WORKER,
    )
    assert second.returncode == EXIT_PREEMPTED, (
        second.stdout[-2000:] + second.stderr[-2000:]
    )
    with open(os.path.join(str(killed), "flightrec_preempt.json")) as f:
        flight = json.load(f)
    note = flight["notes"]["elastic_reshard"]
    assert (note["from_world"], note["to_world"]) == (4, 2)
    assert (note["from_model"], note["to_model"]) == (2, 1)
    final = run_train_worker(
        killed, epochs,
        env=chaos_env(TPUDDP_AUTO_RESUME=1, TPUDDP_WORLD_SIZE=2,
                      TPUDDP_MODEL_SIZE=1),
        worker=TP_WORKER,
    )
    assert final.returncode == 0, final.stdout[-2000:] + final.stderr[-2000:]
    assert history_epochs(killed) == list(range(epochs))
    events = topology_events(killed)
    assert (events[0]["from_model"], events[0]["to_model"]) == (2, 1)
    # the QKV relayout touched params AND their path-congruent moments
    assert any(
        leaf.endswith("['attn']['wqkv']") and leaf.startswith(".opt_state")
        for leaf in events[0]["resharded_leaves"]
    ), events[0]
    validate_history(killed)

    # --- loss-trajectory parity vs uninterrupted: pre-kill epochs fed
    # bitwise-equal state; post-reshard epochs see the SAME global batches
    # partitioned differently — only f32 reassociation moves (the f32
    # 'none' hook keeps compression out of the comparison)
    for out in (shrunk_tp, killed):
        rows = {
            r["epoch"]: r for r in history_records(out)
            if r.get("type") == "epoch"
        }
        for e in range(epochs):
            assert np.isfinite(rows[e]["train_loss"])
            np.testing.assert_allclose(
                rows[e]["train_loss"], base_rows[e]["train_loss"],
                rtol=1e-3, atol=2e-3,
                err_msg=f"{out}: epoch {e} train-loss parity broken",
            )


def test_fleet_resize_tp_job_rides_drain_contract(tmp_path):
    """ISSUE 16 fleet leg: a running TP=2 job resized by the controller
    (displaced by a higher-priority arrival) drains to exit 75 and
    relaunches at the clamped smaller world with $TPUDDP_MODEL_SIZE pinned
    — the child reshards onto TP=2 x DP=1 and finishes."""
    from tpuddp.fleet.controller import FleetController
    from tpuddp.fleet.spec import JobSpec
    from tpuddp.resilience.supervisor import SupervisorPolicy

    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "TPUDDP_BACKEND": "cpu",
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    c = FleetController(
        4, fleet_dir=str(tmp_path), env=env,
        supervisor_policy=SupervisorPolicy(backoff_base=0.1, backoff_cap=0.5),
    )
    tp = c.submit(JobSpec(
        name="tp-job", kind="training", priority=0,
        min_world=2, max_world=4, model_size=2,
        argv=(sys.executable, "-u", TP_WORKER, "{run_dir}", "6"),
    ))
    c.step()
    assert tp.state == "running" and tp.supervisor.world_size == 4
    assert tp.supervisor.model_size == 2
    # let it reach steady training (first checkpoint published) before the
    # displacement, so the SIGTERM drains a live epoch, not a compile
    deadline = time.time() + 300
    while not os.path.exists(os.path.join(tp.run_dir, "ckpt_0.npz")):
        assert time.time() < deadline, "tp job never published ckpt_0"
        assert tp.state == "running"
        c.step()
        time.sleep(0.5)
    c.submit(JobSpec(
        name="filler", kind="training", priority=1,
        min_world=2, max_world=2,
        argv=(sys.executable, "-c", "import time; time.sleep(600)"),
    ))
    # the plan shrinks tp-job 4 -> 2 through the drain; keep ticking until
    # the TP job finishes all 6 epochs at the smaller shape
    assert c.run_until(
        lambda ctl: ctl.jobs["tp-job"].state in ("done", "failed"),
        poll=0.5, timeout=480,
    )
    assert tp.state == "done", (tp.state, tp.exit_code)
    assert tp.resizes >= 1
    c.stop_job("filler")
    c.shutdown(timeout=60)

    assert history_epochs(tp.run_dir) == list(range(6))
    events = topology_events(tp.run_dir)
    assert events and (events[0]["from_world"], events[0]["to_world"]) == (4, 2)
    assert (events[0]["from_model"], events[0]["to_model"]) == (2, 2)
    metas = [
        r for r in history_records(tp.run_dir)
        if r.get("type") == "run_meta" and r.get("resumed_from_world")
    ]
    # the relaunched child derived data = 2 // 2 = 1 from the pinned width
    assert metas and metas[0]["mesh"]["data"] == 1
    assert metas[0]["mesh"]["model"] == 2
    validate_history(tp.run_dir)


def test_supervisor_end_to_end_preempt_then_resume(tmp_path):
    """The restart supervisor drives the whole cycle in ONE command: the
    first attempt is preempted (injected fault, applied to attempt 0 only),
    exits 75, and the supervisor relaunches the same argv with auto-resume —
    the run finishes 0 with every epoch trained exactly once."""
    env = chaos_env(TPUDDP_CHAOS_TRAINING=_elastic_training(8))
    proc = subprocess.run(
        [
            sys.executable, "-u", SUPERVISE,
            "--world", "4", "--max-restarts", "3",
            "--backoff-base", "0.1", "--backoff-cap", "0.5",
            "--first-env", "TPUDDP_FAULT=preempt@epoch=1",
            "--",
            sys.executable, "-u", TRAIN_WORKER, str(tmp_path), "3",
        ],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    both = proc.stdout + proc.stderr
    assert "resuming immediately" in both
    assert history_epochs(tmp_path) == [0, 1, 2]
    validate_history(tmp_path)


def test_wedged_drain_forced_exit_summarized_before_restart(tmp_path):
    """Hang-then-escalate leg, failsafe half (ISSUE 11 satellite): a child
    whose SIGTERM drain WEDGES (never reaches a batch-group boundary) must
    be force-exited 75 by the failsafe only after $TPUDDP_PREEMPT_GRACE,
    dumping flightrec_preempt_forced.json on the way out — and the restart
    supervisor must summarize that recording BEFORE its restart decision."""
    wedge = os.path.join(REPO, "tests", "_chaos_wedge_worker.py")
    proc = subprocess.run(
        [
            sys.executable, "-u", SUPERVISE,
            "--max-restarts", "2", "--backoff-base", "0.1",
            "--flight-dir", str(tmp_path),
            "--",
            sys.executable, "-u", wedge, str(tmp_path), "wedge-drain",
        ],
        env=chaos_env(TPUDDP_PREEMPT_GRACE=3),
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    both = proc.stdout + proc.stderr
    assert proc.returncode == 0, both[-3000:]
    # the drain wedged and the FAILSAFE ended it — not a clean drain, and
    # not a SIGKILL: the grace window was honored, then exit 75
    assert "exceeded the 3s grace window" in both
    flightrec = os.path.join(str(tmp_path), "flightrec_preempt_forced.json")
    assert os.path.exists(flightrec)
    validate = subprocess.run(
        [
            sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
            "--validate", flightrec,
        ],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert validate.returncode == 0, validate.stdout + validate.stderr
    # ordering: the supervisor read the post-mortem BEFORE deciding to
    # resume — the summary line precedes the restart line in its log
    summary_at = both.find("reason=preempt_forced")
    resume_at = both.find("resuming immediately")
    assert 0 <= summary_at < resume_at, both[-3000:]
    # the recording carried the worker's seeded ring + notes
    with open(flightrec) as f:
        payload = json.load(f)
    assert payload["reason"] == "preempt_forced"
    assert payload["notes"]["wedge_mode"] == "wedge-drain"
    assert any(
        e.get("event") == "wedge_armed" for e in payload["records"]["event"]
    )


def test_fleet_chaos_multi_job_pool(tmp_path):
    """ISSUE 11 acceptance: the scripted fleet chaos demo — >= 3 jobs
    (2 training + 1 serving + a late high-priority arrival) share one pool;
    one training job is SIGKILLed mid-run and resumes, the high-priority
    arrival shrinks a neighbor through the drain contract, the serving job
    autoscales replicas on a p99 SLO breach — then every job's namespaced
    history must validate with correct resumed_from_world attribution."""
    proc = subprocess.run(
        [
            sys.executable, "-u", os.path.join(REPO, "tools", "fleet.py"),
            "chaos-demo", "--out", str(tmp_path), "--timeout", "780",
        ],
        env=chaos_env(), cwd=REPO, capture_output=True, text=True, timeout=840,
    )
    assert proc.returncode == 0, (
        proc.stdout[-4000:] + "\n---\n" + proc.stderr[-4000:]
    )
    assert "fleet chaos: PASS" in proc.stdout
    jobs_dir = os.path.join(str(tmp_path), "jobs")
    names = sorted(os.listdir(jobs_dir))
    assert names == ["serve-c", "train-a", "train-b", "train-d"]
    # independent re-verification over the artifacts the demo left behind
    for name in names:
        validate_history(os.path.join(jobs_dir, name))
    a_records = [
        r for r in history_records(os.path.join(jobs_dir, "train-a"))
    ]
    topo = [r for r in a_records if r.get("event") == "topology_change"]
    assert any(t["from_world"] == 2 and t["to_world"] == 1 for t in topo)
    assert any(
        r.get("type") == "run_meta" and r.get("resumed_from_world") == 2
        for r in a_records
    )
    c_metas = [
        r for r in history_records(os.path.join(jobs_dir, "serve-c"))
        if r.get("type") == "run_meta"
    ]
    assert [m.get("num_replicas") for m in c_metas][0] == 1
    assert any(m.get("num_replicas") == 2 for m in c_metas)
    # namespacing: every training job kept its own checkpoint channel
    # under its own dir (per-job exporter ports are proven distinct by the
    # demo itself, mid-run, via read_live_port against each run dir)
    for name in ("train-a", "train-b", "train-d"):
        run_dir = os.path.join(jobs_dir, name)
        assert any(f.startswith("ckpt_") for f in os.listdir(run_dir))


def test_hang_at_barrier_detected_by_watchdog(tmp_path):
    """A peer that stops making progress (hang@barrier — indistinguishable
    from a preempted host) must be detected by the survivor's watchdog within
    the configured timeout, exiting 76 instead of blocking forever in the
    next collective."""
    timeout_s = 3.0
    survivor = subprocess.Popen(
        [sys.executable, "-u", HANG_WORKER, "0", "2", str(tmp_path)],
        env=chaos_env(TPUDDP_WATCHDOG_TIMEOUT=timeout_s), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    hanger = subprocess.Popen(
        [sys.executable, "-u", HANG_WORKER, "1", "2", str(tmp_path)],
        env=chaos_env(
            TPUDDP_WATCHDOG_TIMEOUT=timeout_s, TPUDDP_FAULT="hang@barrier"
        ),
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # bound: two jax imports + rendezvous + the 3s stale window; anything
        # near the 120s ceiling means the watchdog failed and the test hung
        out, err = survivor.communicate(timeout=120)
        assert survivor.returncode == EXIT_WATCHDOG, (
            f"exit {survivor.returncode}:\n{out[-1000:]}\n{err[-2000:]}"
        )
        assert "WORKER 0 armed" in out
        assert "stale" in err  # the watchdog named the dead peer before exiting
        # heartbeat lag exported as a typed event record, fsync'd by the
        # detector BEFORE its os._exit(76)
        events = [
            r for r in history_records(tmp_path)
            if r.get("event") == "watchdog_stale"
        ]
        assert events, "no watchdog_stale event record written"
        assert events[0]["process"] == 0
        assert events[0]["stale_peers"][0]["process"] == 1
        assert events[0]["stale_peers"][0]["lag_s"] >= timeout_s
    finally:
        hanger.kill()
        hanger.communicate(timeout=30)
    assert hanger.returncode is not None


# --------------------------------------------- step-granular exact resume --


def train_losses(out_dir):
    return {
        r["epoch"]: r["train_loss"]
        for r in history_records(out_dir) if r.get("type") == "epoch"
    }


SNAPSHOT_TRAINING = {"snapshot": {"every_steps": 3}, "scan_steps": 1}


@pytest.mark.parametrize(
    "variant,extra",
    [
        ("explicit", {}),
        ("wus", {"weight_update_sharding": True}),
        ("bf16_ef", {"comm_hook": "bf16_ef"}),
    ],
)
def test_preempt_at_step_exact_resume_bitwise_parity(tmp_path, variant, extra):
    """ISSUE 18 acceptance: ``preempt@step=N`` kills the run MID-epoch with
    the snapshot engine armed; the drain flushes the async writer into a
    cursor-bearing step snapshot (the flight recording NAMES the flushed
    step), and the supervised auto-resume continues the epoch AT the
    recorded step — zero batches replayed, loss trajectory bitwise-equal to
    an uninterrupted same-seed twin. Across the explicit, weight-update-
    sharded, and error-feedback-compressed paths."""
    overrides = json.dumps(dict(SNAPSHOT_TRAINING, **extra))
    twin = tmp_path / "twin"
    out = tmp_path / "run"
    ref = run_train_worker(
        twin, 2, env=chaos_env(TPUDDP_CHAOS_TRAINING=overrides)
    )
    assert ref.returncode == 0, ref.stdout[-2000:] + ref.stderr[-2000:]

    first = run_train_worker(
        out, 2,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=overrides, TPUDDP_FAULT="preempt@step=5"
        ),
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    assert "drained snapshot writer" in first.stdout
    # the drain's artifact is a STEP snapshot (v4 cursor), not ckpt_0.npz
    steps = sorted(
        f for f in os.listdir(out)
        if f.startswith("ckpt_0_s") and f.endswith(".npz")
    )
    assert steps and not os.path.exists(os.path.join(str(out), "ckpt_0.npz"))
    cur = ckpt.read_cursor(os.path.join(str(out), steps[-1]))
    assert cur["epoch"] == 0 and cur["plan_key"]
    drained_step = cur["step"]
    # satellite contract: the exit-75 flight recording names both the
    # writer-flushed step and the final drain step
    with open(os.path.join(str(out), "flightrec_preempt.json")) as f:
        notes = json.load(f)["notes"]
    assert notes["snapshot_final_step"] == drained_step
    assert "snapshot_flushed_step" in notes
    assert notes["snapshot_last"]["path"] in steps

    # requeue through the restart supervisor — the scheduler-shaped path
    resumed = subprocess.run(
        [
            sys.executable, "-u", SUPERVISE,
            "--world", "4", "--max-restarts", "2", "--auto-resume",
            "--backoff-base", "0.2",
            "--",
            sys.executable, "-u", TRAIN_WORKER, str(out), "2",
        ],
        env=chaos_env(TPUDDP_CHAOS_TRAINING=overrides),
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert (
        f"Exact resume: epoch 0 continues at step {drained_step} "
        "(zero batches replayed)." in resumed.stdout
    )
    # bitwise: the resumed trajectory equals the twin's, both epochs
    assert train_losses(out) == train_losses(twin)
    metas = [
        r for r in history_records(out)
        if r.get("type") == "run_meta" and isinstance(r.get("snapshot"), dict)
    ]
    assert metas and metas[-1]["snapshot"]["every_steps"] == 3
    validate_history(out)


def test_preempt_at_step_managed_exact_resume(tmp_path):
    """The managed-entrypoint leg: a mid-epoch ``preempt@step`` drains a
    ``state_<e>_s<s>.npz`` step snapshot whose cursor carries the partial
    loss accumulator; the requeued run continues AT the step and lands a
    loss trajectory bitwise-equal to the uninterrupted twin."""
    overrides = json.dumps({"snapshot": {"every_steps": 1}})
    twin = tmp_path / "twin"
    out = tmp_path / "run"
    ref = run_train_worker(
        twin, 2, env=chaos_env(TPUDDP_CHAOS_TRAINING=overrides),
        worker=ACCEL_WORKER,
    )
    assert ref.returncode == 0, ref.stdout[-2000:] + ref.stderr[-2000:]

    first = run_train_worker(
        out, 2,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=overrides, TPUDDP_FAULT="preempt@step=2"
        ),
        worker=ACCEL_WORKER,
    )
    assert first.returncode == EXIT_PREEMPTED, (
        first.stdout[-2000:] + first.stderr[-2000:]
    )
    assert "step snapshot for epoch 0" in first.stdout
    steps = sorted(
        f for f in os.listdir(out)
        if f.startswith("state_0_s") and f.endswith(".npz")
    )
    assert steps, sorted(os.listdir(out))
    cur = ckpt.read_cursor(os.path.join(str(out), steps[-1]))
    drained_step = cur["step"]
    assert cur["epoch"] == 0 and cur["plan_key"]
    acc_keys = set(json.loads(json.dumps(list(cur["acc"]))))
    assert any("loss_total" in k for k in acc_keys)
    assert any("n_seen" in k for k in acc_keys)

    resumed = run_train_worker(
        out, 2,
        env=chaos_env(
            TPUDDP_CHAOS_TRAINING=overrides, TPUDDP_AUTO_RESUME=1
        ),
        worker=ACCEL_WORKER,
    )
    assert resumed.returncode == 0, (
        resumed.stdout[-2000:] + resumed.stderr[-2000:]
    )
    assert f"Resumed from step snapshot: epoch 0 step {drained_step}." in (
        resumed.stdout
    )
    assert (
        f"Exact resume: epoch 0 continues at step {drained_step} "
        "(zero batches replayed)." in resumed.stdout
    )
    assert train_losses(out) == train_losses(twin)
    validate_history(out)
