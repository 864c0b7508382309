"""Resilience subsystem (ISSUE 1): retry/backoff, fault-spec parsing,
checkpoint integrity + retention, preemption drain round-trip, and the
heartbeat watchdog — all in-process on the 8-device CPU world. The
subprocess-kill scenarios live in test_chaos.py (chaos marker)."""

import logging
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import optim
from tpuddp.data import ShardedDataLoader, SyntheticClassification
from tpuddp.models import ToyMLP
from tpuddp.nn import CrossEntropyLoss
from tpuddp.observability import MetricsWriter
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.resilience import faults, integrity, preemption, retry as retry_mod, watchdog
from tpuddp.resilience.preemption import TrainingPreempted
from tpuddp.resilience.retry import RetryError, RetryPolicy, retry
from tpuddp.training import checkpoint as ckpt
from tpuddp.training.loop import run_training_loop


# ---------------------------------------------------------------- retry


def test_retry_first_attempt_success_no_sleep():
    sleeps = []
    assert retry(lambda: 42, sleep=sleeps.append) == 42
    assert sleeps == []


def test_retry_eventual_success_backs_off():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    out = retry(
        flaky,
        RetryPolicy(max_attempts=5, base_delay=1.0, jitter=0.0),
        sleep=sleeps.append,
    )
    assert out == "ok" and calls["n"] == 3
    assert sleeps == [1.0, 2.0]  # exponential, jitter disabled


def test_retry_exhaustion_raises_retry_error_with_cause():
    sleeps = []
    with pytest.raises(RetryError, match="the-op failed after 3 attempt"):
        try:
            retry(
                lambda: (_ for _ in ()).throw(OSError("boom")),
                RetryPolicy(max_attempts=3, base_delay=0.01),
                describe="the-op",
                sleep=sleeps.append,
            )
        except RetryError as e:
            assert isinstance(e.__cause__, OSError)
            assert len(sleeps) == 2  # no sleep after the final attempt
            raise


def test_retry_non_retryable_propagates_immediately():
    calls = {"n": 0}

    def bad():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        retry(bad, RetryPolicy(max_attempts=5, retry_on=(OSError,)), sleep=lambda _: None)
    assert calls["n"] == 1


def test_retry_policy_delay_caps_and_jitter_bounds():
    p = RetryPolicy(max_attempts=10, base_delay=1.0, max_delay=4.0, jitter=0.5)
    import random

    rng = random.Random(0)
    for attempt, base in ((1, 1.0), (2, 2.0), (3, 4.0), (6, 4.0)):
        for _ in range(20):
            d = p.delay(attempt, rng)
            assert 0.5 * base <= d <= 1.5 * base


def test_retry_policy_validation():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


# ---------------------------------------------------------------- faults


def test_fault_spec_parsing():
    specs = faults.parse_fault_specs("crash@epoch=2, hang@barrier,corrupt@ckpt_1")
    assert [(s.kind, s.site, s.arg) for s in specs] == [
        ("crash", "epoch", "2"),
        ("hang", "barrier", None),
        ("corrupt", "ckpt", "ckpt_1"),
    ]


@pytest.mark.parametrize("bad", ["explode@epoch=1", "crash@nowhere", "crash"])
def test_fault_spec_parsing_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_specs(bad)


def test_fault_no_env_is_noop(monkeypatch):
    monkeypatch.delenv("TPUDDP_FAULT", raising=False)
    faults.reload_faults()
    faults.maybe_fire("epoch", epoch=0)  # nothing to fire
    assert faults.active_faults() == []


def test_fault_corrupt_fires_once_per_spec(tmp_path, monkeypatch):
    victim = tmp_path / "ckpt_1.npz"
    victim.write_bytes(b"PK" + b"x" * 100)
    monkeypatch.setenv("TPUDDP_FAULT", "corrupt@ckpt_1")
    faults.reload_faults()
    try:
        faults.maybe_fire("ckpt", name="ckpt_0", path=None)  # no match
        faults.maybe_fire("ckpt", name="ckpt_1", path=str(victim))
        garbled = victim.read_bytes()
        assert not garbled.startswith(b"PK")
        # fired-once: a second matching hook leaves the file alone
        victim.write_bytes(b"PK" + b"y" * 100)
        faults.maybe_fire("ckpt", name="ckpt_1", path=str(victim))
        assert victim.read_bytes().startswith(b"PK")
    finally:
        monkeypatch.delenv("TPUDDP_FAULT", raising=False)
        faults.reload_faults()


# ---------------------------------------------------------------- integrity


def test_manifest_round_trip_and_tamper_detection(tmp_path):
    f = tmp_path / "a.npz"
    f.write_bytes(b"PK\x03\x04 payload bytes")
    integrity.write_manifest(str(f))
    assert os.path.exists(str(f) + ".sha256")
    assert integrity.verify_file(str(f))
    f.write_bytes(b"PK\x03\x04 payload byteZ")  # same size, different content
    assert not integrity.verify_file(str(f))


def test_truncation_detected_by_size(tmp_path):
    f = tmp_path / "a.npz"
    f.write_bytes(b"PK\x03\x04" + b"d" * 100)
    integrity.write_manifest(str(f))
    f.write_bytes(f.read_bytes()[:50])
    assert not integrity.verify_file(str(f))


def test_verify_without_manifest_uses_structural_check(tmp_path):
    good = tmp_path / "legacy.npz"
    good.write_bytes(b"PK\x03\x04data")  # pre-resilience checkpoint: no sidecar
    assert integrity.verify_file(str(good))
    assert not integrity.verify_file(str(good), require_manifest=True)
    bad = tmp_path / "torn.npz"
    bad.write_bytes(b"\x00garbage")
    assert not integrity.verify_file(str(bad))
    empty = tmp_path / "empty.npz"
    empty.write_bytes(b"")
    assert not integrity.verify_file(str(empty))
    assert not integrity.verify_file(str(tmp_path / "absent.npz"))


# ------------------------------------------------- checkpoint crash-consistency


def make_state():
    model = ToyMLP(hidden=(8,))
    from tpuddp.training.train_state import create_train_state

    return create_train_state(
        model, optim.Adam(1e-3), jax.random.key(0), jnp.zeros((1, 4, 4, 3))
    )


def assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b
    )


def test_meta_round_trip(tmp_path):
    state = make_state()
    path = ckpt.save(str(tmp_path / "s.npz"), state, meta={"epoch": 7, "completed": 0})
    assert ckpt.read_meta(path) == {"epoch": 7, "completed": 0}
    # meta keys are invisible to the template-driven load
    restored = ckpt.load(path, state)
    assert_tree_equal(restored.params, state.params)


def test_kill_between_tmp_write_and_replace_recovers(tmp_path, caplog):
    """A writer killed between the ``.tmp`` write and ``os.replace``
    (checkpoint.py save) leaves a stale .tmp and NO new checkpoint; the .tmp
    must not shadow the previous good epoch."""
    state = make_state()
    ckpt.save_on_main(str(tmp_path), 0, state)
    # simulate the torn epoch-1 save: the .tmp exists, the publish never ran
    (tmp_path / "ckpt_1.npz.tmp").write_bytes(b"PK\x03\x04 half-written")
    found = ckpt.latest(str(tmp_path))
    assert found is not None and found[1] == 0
    restored, next_epoch = ckpt.restore_latest(str(tmp_path), state)
    assert next_epoch == 1
    assert_tree_equal(restored.params, state.params)


def test_corrupt_newest_falls_back_to_previous_good(tmp_path, caplog):
    state = make_state()
    ckpt.save_on_main(str(tmp_path), 0, state)
    path1 = ckpt.save_on_main(str(tmp_path), 1, state)
    # torn write past the atomic publish (node died mid-flush on NFS): header
    # garbage + truncated tail, manifest now stale
    with open(path1, "r+b") as f:
        f.write(b"\x00CHAOS\x00")
        f.truncate(64)
    with caplog.at_level(logging.WARNING, logger="tpuddp"):
        found = ckpt.latest(str(tmp_path))
        assert found is not None and found[1] == 0
        restored, next_epoch = ckpt.restore_latest(str(tmp_path), state)
    assert next_epoch == 1
    assert_tree_equal(restored.params, state.params)
    assert any("failed integrity" in r.message for r in caplog.records)


def test_all_checkpoints_corrupt_yields_fresh_start(tmp_path):
    state = make_state()
    path = ckpt.save_on_main(str(tmp_path), 0, state)
    with open(path, "wb") as f:
        f.write(b"\x00")
    restored, next_epoch = ckpt.restore_latest(str(tmp_path), state)
    assert next_epoch == 0
    assert restored is state


def test_emergency_checkpoint_redoes_interrupted_epoch(tmp_path, caplog):
    state = make_state()
    ckpt.save_on_main(str(tmp_path), 3, state, completed=False)
    assert ckpt.read_meta(str(tmp_path / "ckpt_3.npz"))["completed"] == 0
    with caplog.at_level(logging.WARNING, logger="tpuddp"):
        restored, next_epoch = ckpt.restore_latest(str(tmp_path), state)
    assert next_epoch == 3  # redo epoch 3, not 4
    assert any("EMERGENCY" in r.message for r in caplog.records)


def test_keep_last_retention(tmp_path):
    state = make_state()
    for e in range(5):
        ckpt.save_on_main(str(tmp_path), e, state, keep_last=2)
    kept = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert kept == ["ckpt_3.npz", "ckpt_4.npz"]
    # manifests pruned alongside their data files
    sidecars = sorted(f for f in os.listdir(tmp_path) if f.endswith(".sha256"))
    assert sidecars == ["ckpt_3.npz.sha256", "ckpt_4.npz.sha256"]
    with pytest.raises(ValueError):
        ckpt.prune_checkpoints(str(tmp_path), keep_last=0)


# ---------------------------------------------------------------- preemption


@pytest.fixture
def preempt_guard(monkeypatch):
    """Keep the grace-window failsafe thread inert and the flag clean."""
    monkeypatch.setenv("TPUDDP_PREEMPT_GRACE", "3600")
    preemption.reset_preemption()
    yield
    preemption.reset_preemption()


def test_grace_env_parsing(monkeypatch):
    monkeypatch.delenv("TPUDDP_PREEMPT_GRACE", raising=False)
    assert preemption.preemption_grace_seconds() == 25.0
    monkeypatch.setenv("TPUDDP_PREEMPT_GRACE", "7.5")
    assert preemption.preemption_grace_seconds() == 7.5
    monkeypatch.setenv("TPUDDP_PREEMPT_GRACE", "not-a-number")
    assert preemption.preemption_grace_seconds() == 25.0


def test_request_sets_flag_and_deadline(preempt_guard):
    assert not preemption.preemption_requested()
    assert preemption.preemption_deadline() is None
    preemption.request_preemption()
    assert preemption.preemption_requested()
    assert preemption.preemption_deadline() is not None
    preemption.reset_preemption()
    assert not preemption.preemption_requested()


class _PreemptingLoader:
    """Delegating loader that requests preemption after ``after`` batches —
    the in-process stand-in for a SIGTERM landing mid-epoch."""

    def __init__(self, inner, after):
        self.inner = inner
        self.after = after

    def __len__(self):
        return len(self.inner)

    def set_epoch(self, epoch):
        self.inner.set_epoch(epoch)

    def __iter__(self):
        for i, batch in enumerate(self.inner):
            if i == self.after:
                preemption.request_preemption()
            yield batch


def _toy_ddp(mesh):
    # batch_size is per replica: 8 x 8 devices = 64-sample global batches,
    # so n=512 gives 8 batch groups per epoch — room for a mid-epoch preempt
    ds = SyntheticClassification(n=512, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    test_loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    return ddp, state, loader, test_loader


def test_preemption_round_trip_exact_state(mesh, tmp_path, preempt_guard):
    """SIGTERM mid-epoch -> emergency checkpoint -> auto_resume continues from
    the recorded epoch with the EXACT saved state (params, optimizer moments,
    RNG stream position) — the fast-tier half of the chaos round-trip."""
    ddp, state, loader, test_loader = _toy_ddp(mesh)
    with pytest.raises(TrainingPreempted) as ei:
        run_training_loop(
            ddp, state, _PreemptingLoader(loader, after=2), test_loader,
            str(tmp_path), num_epochs=3, checkpoint_epoch=1, log=lambda *_: None,
        )
    assert ei.value.epoch == 0
    emergency = tmp_path / "ckpt_0.npz"
    assert emergency.exists()
    assert integrity.verify_file(str(emergency))
    assert ckpt.read_meta(str(emergency)) == {"epoch": 0, "completed": 0}

    # the drain saved the state as of the last completed batch group; resume
    # restores it bit-for-bit and redoes the interrupted epoch
    saved = ckpt.load(str(emergency), state)
    restored, resume_epoch = ckpt.restore_latest(str(tmp_path), state)
    assert resume_epoch == 0
    assert_tree_equal(restored.params, saved.params)
    assert_tree_equal(restored.opt_state, saved.opt_state)
    assert jnp.array_equal(
        jax.random.key_data(restored.rng), jax.random.key_data(saved.rng)
    )

    preemption.reset_preemption()
    ddp2, state2, loader2, test_loader2 = _toy_ddp(mesh)
    _, history = run_training_loop(
        ddp2, state2, loader2, test_loader2, str(tmp_path),
        num_epochs=3, checkpoint_epoch=1, auto_resume=True, log=lambda *_: None,
    )
    # the interrupted epoch 0 was redone, then training ran to completion
    assert [h["epoch"] for h in history] == [0, 1, 2]
    # completed end-of-epoch saves overwrite the emergency marker
    assert ckpt.read_meta(str(tmp_path / "ckpt_2.npz"))["completed"] == 1


def test_auto_resume_env_flag(mesh, tmp_path, monkeypatch):
    ddp, state, loader, test_loader = _toy_ddp(mesh)
    run_training_loop(
        ddp, state, loader, test_loader, str(tmp_path),
        num_epochs=1, checkpoint_epoch=1, log=lambda *_: None,
    )
    monkeypatch.setenv("TPUDDP_AUTO_RESUME", "1")
    logs = []
    _, history = run_training_loop(
        ddp, state, loader, test_loader, str(tmp_path),
        num_epochs=2, checkpoint_epoch=1, log=logs.append,
    )
    assert [h["epoch"] for h in history] == [1]
    assert any("Auto-resume: continuing from epoch 1" in l for l in logs)


# ---------------------------------------------------------------- watchdog


def test_heartbeat_file_round_trip(tmp_path):
    watchdog.write_heartbeat(str(tmp_path), 3, now=123.5)
    assert watchdog.read_heartbeat(str(tmp_path), 3) == 123.5
    assert watchdog.read_heartbeat(str(tmp_path), 4) is None


def test_heartbeat_thread_beats(tmp_path):
    hb = watchdog.Heartbeat(str(tmp_path), 0, interval=0.05).start()
    try:
        first = watchdog.read_heartbeat(str(tmp_path), 0)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if watchdog.read_heartbeat(str(tmp_path), 0) > first:
                break
            time.sleep(0.02)
        else:
            raise AssertionError("heartbeat never advanced")
    finally:
        hb.stop()


def test_watchdog_check_once_semantics(tmp_path):
    wd = watchdog.Watchdog(str(tmp_path), 0, num_processes=3, timeout=10.0)
    wd._started_at = 1000.0
    # no files yet, within startup grace: nothing stale
    assert wd.check_once(now=1005.0) == []
    # past the grace with still no file: both peers stale
    assert [p for p, _ in wd.check_once(now=1011.0)] == [1, 2]
    watchdog.write_heartbeat(str(tmp_path), 1, now=1011.0)
    watchdog.write_heartbeat(str(tmp_path), 2, now=1011.0)
    assert wd.check_once(now=1015.0) == []
    # peer 2 goes quiet past the timeout
    watchdog.write_heartbeat(str(tmp_path), 1, now=1025.0)
    stale = wd.check_once(now=1025.0)
    assert [p for p, _ in stale] == [2]
    assert stale[0][1] == pytest.approx(14.0)


def test_watchdog_fires_callable_action_within_timeout(tmp_path):
    fired = threading.Event()
    stale_seen = []

    def action(stale):
        stale_seen.extend(stale)
        fired.set()

    watchdog.write_heartbeat(str(tmp_path), 1)  # one beat, then silence
    wd = watchdog.Watchdog(
        str(tmp_path), 0, num_processes=2, timeout=0.3, action=action, interval=0.05
    ).start()
    try:
        assert fired.wait(timeout=5.0), "watchdog never fired on a stale peer"
        assert stale_seen and stale_seen[0][0] == 1
    finally:
        wd.stop()


def test_watchdog_timeout_env_parsing(monkeypatch):
    monkeypatch.delenv("TPUDDP_WATCHDOG_TIMEOUT", raising=False)
    assert watchdog.watchdog_timeout_seconds() is None
    monkeypatch.setenv("TPUDDP_WATCHDOG_TIMEOUT", "12")
    assert watchdog.watchdog_timeout_seconds() == 12.0
    monkeypatch.setenv("TPUDDP_WATCHDOG_TIMEOUT", "0")
    assert watchdog.watchdog_timeout_seconds() is None
    monkeypatch.setenv("TPUDDP_WATCHDOG_TIMEOUT", "nope")
    assert watchdog.watchdog_timeout_seconds() is None


def test_watchdog_start_disabled_paths(tmp_path, monkeypatch):
    monkeypatch.delenv("TPUDDP_WATCHDOG_TIMEOUT", raising=False)
    assert watchdog.start(str(tmp_path), 0, 2) is None  # no timeout configured
    monkeypatch.setenv("TPUDDP_WATCHDOG_TIMEOUT", "5")
    assert watchdog.start(str(tmp_path), 0, 1) is None  # no peers
    monkeypatch.delenv("TPUDDP_HEARTBEAT_DIR", raising=False)
    assert watchdog.start(None, 0, 2) is None  # nowhere to beat
    pair = watchdog.start(str(tmp_path), 0, 2)  # armed
    try:
        assert pair is not None
        assert os.path.exists(tmp_path / ".heartbeats" / "hb_0")
    finally:
        watchdog.stop(pair)
    watchdog.stop(None)  # None-safe


def test_watchdog_shrunk_resume_ignores_leftover_heartbeats(tmp_path, monkeypatch):
    """Regression (ISSUE 7 satellite): an elastically-shrunk resume reuses
    the heartbeat_dir of a previous LARGER world. The leftover hb_{i} files —
    both the ids past the new world size and the in-range ids with ancient
    beats — must not make the watchdog kill the healthy smaller run with
    exit 76: start() purges the out-of-range files, and check_once gives
    pre-start beats the startup grace instead of declaring them stale."""
    hb_dir = tmp_path / ".heartbeats"
    os.makedirs(hb_dir)
    ancient = time.time() - 3600.0
    for peer in range(8):  # the previous 8-process world's droppings
        watchdog.write_heartbeat(str(hb_dir), peer, now=ancient)

    monkeypatch.setenv("TPUDDP_WATCHDOG_TIMEOUT", "5")
    monkeypatch.delenv("TPUDDP_HEARTBEAT_DIR", raising=False)
    pair = watchdog.start(str(tmp_path), 0, 2)  # resumed world: 2 processes
    try:
        assert pair is not None
        _hb, wd = pair
        # ids >= num_processes purged outright
        leftover = sorted(os.listdir(hb_dir))
        assert "hb_2" not in leftover and "hb_7" not in leftover
        # peer 1's ancient file is pre-start: startup grace, NOT stale —
        # before the fix this check returned [(1, ~3600s)] and fired exit 76
        assert wd.check_once() == []
        # the grace is not unconditional: past the timeout with still no
        # fresh beat, the peer IS stale
        stale = wd.check_once(now=time.time() + 10.0)
        assert [p for p, _ in stale] == [1]
        # and a fresh in-run beat clears it
        watchdog.write_heartbeat(str(hb_dir), 1)
        assert wd.check_once() == []
    finally:
        watchdog.stop(pair)


def test_purge_stale_peers_counts_and_is_best_effort(tmp_path):
    for peer in (0, 1, 4, 9):
        watchdog.write_heartbeat(str(tmp_path), peer)
    assert watchdog.purge_stale_peers(str(tmp_path), 2) == 2  # hb_4, hb_9
    assert sorted(os.listdir(tmp_path)) == ["hb_0", "hb_1"]
    assert watchdog.purge_stale_peers(str(tmp_path), 2) == 0  # idempotent
    assert watchdog.purge_stale_peers(str(tmp_path / "missing"), 2) == 0


# ------------------------------------------------------------ cifar download


def test_cifar_download_retries_and_cleans_partial(tmp_path, monkeypatch):
    """A flaky download is retried 3x; every failed attempt removes its
    partial file so nothing poisons the next run, and the terminal error names
    the operation."""
    from tpuddp.data import cifar10 as c10

    calls = {"n": 0}

    class FlakyResponse:
        """Yields one chunk, then dies mid-stream — a truncating connection."""

        def __init__(self):
            self.sent = False

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def read(self, n=-1):
            if not self.sent:
                self.sent = True
                return b"half an archive"
            raise OSError("connection reset")

    def fake_urlopen(url, timeout=None):
        calls["n"] += 1
        return FlakyResponse()

    monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
    with pytest.raises(RetryError, match="CIFAR-10 download"):
        c10._maybe_download(str(tmp_path))
    assert calls["n"] == 3
    assert os.listdir(tmp_path) == []  # no .part / truncated archive left


def test_cifar_corrupt_archive_deleted_then_redownloaded(tmp_path, monkeypatch):
    """An archive truncated by an earlier kill fails extraction, is deleted,
    and the retry re-downloads a good copy instead of failing forever."""
    import io
    import tarfile

    from tpuddp.data import cifar10 as c10

    (tmp_path / "cifar-10-python.tar.gz").write_bytes(b"\x1f\x8b not a gzip")

    def fake_urlopen(url, timeout=None):
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            data = b"hello"
            info = tarfile.TarInfo("cifar-10-batches-py/readme")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
        return io.BytesIO(buf.getvalue())

    monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
    c10._maybe_download(str(tmp_path))
    assert (tmp_path / "cifar-10-batches-py" / "readme").read_bytes() == b"hello"


# ---------------------------------------------------------------- observability


def test_metrics_writer_flush_and_close(tmp_path):
    w = MetricsWriter(str(tmp_path))
    w.write({"epoch": 0})
    # flushed after every record: readable mid-run, always whole JSON lines
    assert open(w.path).read() == '{"epoch": 0}\n'
    w.write({"epoch": 1})
    w.close()
    w.close()  # idempotent
    assert open(w.path).read().splitlines() == ['{"epoch": 0}', '{"epoch": 1}']
