"""Serving engine suite (ISSUE 6): queue/admission semantics, round-robin
fairness, bucketed coalescing correctness (served logits bitwise-equal to a
direct forward over the same padded batch), checkpoint restore through the
integrity path, serving_stats schema emission + drift rejection, and — slow
tier — the SIGTERM drain exit-code contract and a loadgen subprocess smoke.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from tpuddp import config as config_lib
from tpuddp.models import load_model
from tpuddp.nn.core import Context
from tpuddp.observability import schema
from tpuddp.resilience.preemption import EXIT_PREEMPTED
from tpuddp.serving import (
    AdmissionError,
    BatchScheduler,
    ReplicaPool,
    Request,
    RequestQueue,
    ServingEngine,
    ServingStats,
)
from tpuddp.serving.replica import _restore_variables
from tpuddp.training import checkpoint as ckpt
from tpuddp.training.train_state import TrainState
from tpuddp.utils import batching

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPE = (8, 8, 3)  # tiny sample shape: keeps every compile trivial


def _req(tenant, rows, seed=0):
    rng = np.random.RandomState(seed + rows)
    return Request(tenant, rng.randn(rows, *SHAPE).astype(np.float32))


def _serving_cfg(**overrides):
    cfg = config_lib.serving_config({})
    cfg.update(
        model="toy_mlp",
        input_shape=list(SHAPE),
        num_replicas=2,
        max_batch_size=8,
        batch_timeout_ms=1.0,
        stats_window=8,
        seed=0,
    )
    cfg.update(overrides)
    return cfg


@pytest.fixture
def engine(cpu_devices):
    eng = ServingEngine.from_config(_serving_cfg(), devices=cpu_devices)
    eng.start()
    yield eng
    eng.drain()


# ---------------------------------------------------------------- admission --


def test_queue_depth_reject():
    q = RequestQueue(max_depth=3)
    for i in range(3):
        q.put(_req("a", 1, seed=i))
    with pytest.raises(AdmissionError) as e:
        q.put(_req("a", 1))
    assert e.value.reason == "queue_full"
    # draining a group frees capacity again
    assert q.take_group(max_rows=8) is not None
    q.put(_req("a", 1))


def test_tenant_quota_reject():
    q = RequestQueue(max_depth=16, per_tenant_quota=2)
    q.put(_req("a", 1))
    q.put(_req("a", 1))
    with pytest.raises(AdmissionError) as e:
        q.put(_req("a", 1))
    assert e.value.reason == "tenant_quota"
    # another tenant is unaffected by a's quota exhaustion
    q.put(_req("b", 1))


def test_draining_reject():
    q = RequestQueue(max_depth=4)
    q.put(_req("a", 1))
    q.close()
    with pytest.raises(AdmissionError) as e:
        q.put(_req("a", 1))
    assert e.value.reason == "draining"
    # queued work still drains, then the closed+empty queue signals exit
    assert len(q.take_group(max_rows=8)) == 1
    assert q.take_group(max_rows=8) is None


def test_round_robin_fairness():
    """A tenant queueing 10 requests must not make another tenant's 2 wait
    behind all 10: groups alternate tenants (at most one request per tenant
    per pass)."""
    q = RequestQueue(max_depth=64)
    for i in range(10):
        q.put(_req("flood", 1, seed=i))
    q.put(_req("small", 1, seed=100))
    q.put(_req("small", 1, seed=101))
    first = q.take_group(max_rows=4)
    tenants = [r.tenant for r in first]
    assert tenants == ["flood", "small", "flood", "small"], tenants
    # per-tenant FIFO preserved within the interleave
    floods = [r for r in first if r.tenant == "flood"]
    assert floods[0].id < floods[1].id


def test_engine_rejects_oversized_and_bad_shape(engine):
    with pytest.raises(AdmissionError) as e:
        engine.submit("a", np.zeros((9,) + SHAPE, np.float32))  # > max_batch 8
    assert e.value.reason == "oversized"
    with pytest.raises(AdmissionError) as e:
        engine.submit("a", np.zeros((1, 4, 4, 3), np.float32))
    assert e.value.reason == "bad_shape"
    with pytest.raises(AdmissionError) as e:
        engine.submit("a", np.zeros((1,) + SHAPE, np.float64))
    assert e.value.reason == "bad_shape"
    rej = engine.stats.summary()["rejected"]
    assert rej == {"oversized": 1, "bad_shape": 2}


# ----------------------------------------------------------------- batching --


def test_scheduler_buckets_and_padding():
    q = RequestQueue(max_depth=64)
    sched = BatchScheduler(q, max_batch_size=8, batch_timeout_ms=0.0)
    assert sched.buckets == [1, 2, 4, 8]
    batch = sched.form([_req("a", 2), _req("b", 3)])
    assert batch.rows == 5 and batch.bucket == 8  # 5 -> next pow2 bucket
    assert batch.slices == [(0, 2), (2, 5)]
    assert batch.x.shape == (8,) + SHAPE
    np.testing.assert_array_equal(batch.w, [1, 1, 1, 1, 1, 0, 0, 0])
    assert abs(batch.occupancy - 5 / 8) < 1e-9
    single = sched.form([_req("a", 4)])
    assert single.bucket == 4 and single.occupancy == 1.0


def test_served_bitwise_equals_direct_forward(engine):
    """Acceptance: logits served through queue+scheduler+replica are bitwise
    those of a direct model forward over the same padded batch."""
    module = engine.pool.module
    params = engine.pool.replicas[0].params
    mstate = engine.pool.replicas[0].model_state

    # params as ARGUMENTS, like the replica's own program — a jit CLOSING
    # over them would embed the weights as constants, which XLA may fold
    # into different (1-ulp-off) arithmetic than the served program
    @jax.jit
    def direct(p, s, x):
        ctx = Context(train=False, rng=jax.random.key(0), axis_name=None)
        return module.apply(p, s, x, ctx)[0]

    rng = np.random.RandomState(7)
    for rows in (1, 2, 3, 5, 8):
        x = rng.randn(rows, *SHAPE).astype(np.float32)
        served = engine.submit("bitwise", x).result(timeout=60)
        xp, _, _ = batching.pad_batch(
            x, None, batching.bucket_for(rows, engine.scheduler.max_batch_size)
        )
        ref = np.asarray(direct(params, mstate, xp))[:rows]
        np.testing.assert_array_equal(served, ref)


def test_coalesced_batch_slices_bitwise(cpu_devices):
    """Multiple requests coalesced into ONE padded batch slice back to
    exactly their own rows' logits."""
    pool = ReplicaPool.from_config(_serving_cfg(num_replicas=1),
                                   devices=cpu_devices[:1])
    q = RequestQueue(max_depth=16)
    sched = BatchScheduler(q, max_batch_size=8)
    reqs = [_req("a", 2, seed=1), _req("b", 3, seed=2), _req("a", 1, seed=3)]
    batch = sched.form(reqs)
    logits = np.asarray(pool.replicas[0].infer(batch.x))
    module = pool.module

    @jax.jit
    def direct(p, s, x):
        ctx = Context(train=False, rng=jax.random.key(0), axis_name=None)
        return module.apply(p, s, x, ctx)[0]

    ref = np.asarray(
        direct(pool.replicas[0].params, pool.replicas[0].model_state, batch.x)
    )
    for r, (lo, hi) in zip(reqs, batch.slices):
        np.testing.assert_array_equal(logits[lo:hi], ref[lo:hi])
        assert hi - lo == r.rows


def test_replicas_on_distinct_devices(engine):
    devs = {r.device for r in engine.pool.replicas}
    assert len(devs) == 2
    # params actually live on their replica's device
    for r in engine.pool.replicas:
        leaf = jax.tree_util.tree_leaves(r.params)[0]
        assert leaf.devices() == {r.device}


# --------------------------------------------------------------- overload ----


def test_per_tenant_fairness_under_overload(cpu_devices):
    """One tenant flooding past its quota gets rejected with reason
    tenant_quota; a well-behaved tenant's requests all complete."""
    eng = ServingEngine.from_config(
        _serving_cfg(per_tenant_quota=4, max_queue_depth=64),
        devices=cpu_devices,
    )
    eng.start()
    try:
        flood_results, quota_rejects = [], 0
        for i in range(60):
            try:
                flood_results.append(
                    eng.submit("flood", np.zeros((1,) + SHAPE, np.float32))
                )
            except AdmissionError as e:
                assert e.reason == "tenant_quota"
                quota_rejects += 1
            if i % 10 == 0:
                ok = eng.submit("polite", np.ones((2,) + SHAPE, np.float32))
                assert ok.result(timeout=60).shape == (2, 10)
        for r in flood_results:
            r.result(timeout=60)
    finally:
        summary = eng.drain()
    assert summary["per_tenant_completed"]["polite"] == 6
    assert quota_rejects > 0
    assert summary["rejected"]["tenant_quota"] == quota_rejects
    assert summary["completed"] == 6 + len(flood_results)


def test_dispatch_error_fails_requests_not_engine(cpu_devices):
    eng = ServingEngine.from_config(
        _serving_cfg(num_replicas=1), devices=cpu_devices[:1]
    )
    eng.start()
    try:
        replica = eng.pool.replicas[0]
        real_infer = replica.infer

        def boom(x):
            raise RuntimeError("injected dispatch failure")

        replica.infer = boom
        res = eng.submit("a", np.zeros((1,) + SHAPE, np.float32))
        with pytest.raises(RuntimeError, match="injected dispatch failure"):
            res.result(timeout=60)
        # the loop survives: restore the forward, the next request serves
        replica.infer = real_infer
        ok = eng.submit("a", np.zeros((1,) + SHAPE, np.float32))
        assert ok.result(timeout=60).shape == (1, 10)
    finally:
        summary = eng.drain()
    assert summary["completed"] == 1


def test_replica_marked_unhealthy_after_consecutive_errors(cpu_devices, tmp_path):
    """Graceful degradation (ISSUE 7 satellite): K consecutive dispatch
    errors mark a replica unhealthy and stop routing to it — a broken
    replica must not fail batches forever. Healthy-replica traffic
    continues, a replica_unhealthy event row lands in history.jsonl, and
    drain still exits cleanly."""
    K = 3
    eng = ServingEngine.from_config(
        _serving_cfg(num_replicas=2, unhealthy_after=K),
        out_dir=str(tmp_path),
        devices=cpu_devices[:2],
    )
    eng.start()
    try:
        broken = eng.pool.replicas[0]

        def boom(x):
            raise RuntimeError("injected persistent replica failure")

        broken.infer = boom
        failures = 0
        served = 0
        deadline = time.time() + 120
        # keep submitting until the broken replica has eaten K batches and
        # been retired; every request either fails (broken took it) or
        # serves (healthy replica took it)
        while broken.healthy and time.time() < deadline:
            res = eng.submit("t", np.zeros((1,) + SHAPE, np.float32))
            try:
                res.result(timeout=60)
                served += 1
            except RuntimeError:
                failures += 1
        assert not broken.healthy, "replica never marked unhealthy"
        assert failures >= K
        # routing has stopped: from here on EVERY request lands healthy
        for _ in range(8):
            ok = eng.submit("t", np.zeros((2,) + SHAPE, np.float32))
            assert ok.result(timeout=60).shape == (2, 10)
            served += 1
        assert eng.pool.replicas[1].healthy
    finally:
        summary = eng.drain()  # clean drain despite the dead replica
    assert summary["completed"] == served
    rows = [
        json.loads(line)
        for line in open(os.path.join(str(tmp_path), "history.jsonl"))
    ]
    unhealthy = [r for r in rows if r.get("event") == "replica_unhealthy"]
    assert unhealthy and unhealthy[0]["replica"] == 0
    assert unhealthy[0]["consecutive_errors"] == K
    errs = schema.validate_history_records(rows)
    assert errs == [], errs


def test_last_replica_unhealthy_fails_queued_requests(cpu_devices, tmp_path):
    """When the LAST healthy replica dies, queued requests must fail with an
    error instead of hanging the client (and the drain)."""
    eng = ServingEngine.from_config(
        _serving_cfg(num_replicas=1, unhealthy_after=2),
        out_dir=str(tmp_path),
        devices=cpu_devices[:1],
    )
    eng.start()
    try:
        replica = eng.pool.replicas[0]
        replica.infer = lambda x: (_ for _ in ()).throw(
            RuntimeError("replica dead")
        )
        # sequential submits: each failure is its own batch, so the second
        # one crosses unhealthy_after=2; later requests hit the no-healthy-
        # replicas branch and still fail fast instead of hanging
        for _ in range(4):
            res = eng.submit("t", np.zeros((1,) + SHAPE, np.float32))
            with pytest.raises(RuntimeError):
                res.result(timeout=60)
        assert not replica.healthy
    finally:
        summary = eng.drain()
    assert summary["completed"] == 0


def test_drain_then_submit_rejected(cpu_devices):
    eng = ServingEngine.from_config(
        _serving_cfg(num_replicas=1), devices=cpu_devices[:1]
    )
    eng.start()
    res = eng.submit("a", np.zeros((2,) + SHAPE, np.float32))
    summary = eng.drain()
    assert res.done() and res.result().shape == (2, 10)
    assert summary["completed"] == 1
    with pytest.raises(AdmissionError) as e:
        eng.submit("a", np.zeros((1,) + SHAPE, np.float32))
    assert e.value.reason == "draining"


# -------------------------------------------------------------- checkpoints --


def _toy_variables(seed):
    module = load_model("toy_mlp", num_classes=10)
    return module, *module.init(
        jax.random.key(seed), jnp.zeros((1,) + SHAPE, jnp.float32)
    )


def test_restore_native_trainstate_checkpoint(tmp_path):
    module, params, mstate = _toy_variables(seed=123)
    state = TrainState(
        params=params,
        model_state=mstate,
        opt_state={"m": jax.tree_util.tree_map(jnp.zeros_like, params)},
        step=jnp.zeros((), jnp.int32),
        rng=jax.random.key(9),
    )
    ckpt.save(ckpt.checkpoint_path(str(tmp_path), 3), state,
              meta={"epoch": 3, "completed": 1})
    # template from a DIFFERENT seed: equality below proves the restore
    _, t_params, t_mstate = _toy_variables(seed=7)
    r_params, _, epoch = _restore_variables(
        str(tmp_path), "ckpt", t_params, t_mstate
    )
    assert epoch == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(r_params), jax.tree_util.tree_leaves(params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_managed_state_checkpoint_and_auto(tmp_path):
    module, params, mstate = _toy_variables(seed=42)
    tree = {"params": params, "model_state": mstate,
            "opt_state": {"v": jnp.zeros((3,))}}
    ckpt.save(ckpt.checkpoint_path(str(tmp_path), 5, prefix="state"), tree,
              meta={"epoch": 5, "completed": 1})
    _, t_params, t_mstate = _toy_variables(seed=7)
    # explicit prefix and "auto" (newest across families) both find it
    for prefix in ("state", "auto"):
        r_params, _, epoch = _restore_variables(
            str(tmp_path), prefix, t_params, t_mstate
        )
        assert epoch == 5
        for a, b in zip(
            jax.tree_util.tree_leaves(r_params),
            jax.tree_util.tree_leaves(params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_missing_checkpoint_raises(tmp_path):
    _, t_params, t_mstate = _toy_variables(seed=7)
    with pytest.raises(FileNotFoundError):
        _restore_variables(str(tmp_path), "auto", t_params, t_mstate)


def test_pool_from_config_restores(tmp_path, cpu_devices):
    module, params, mstate = _toy_variables(seed=5)
    ckpt.save(
        ckpt.checkpoint_path(str(tmp_path), 2, prefix="state"),
        {"params": params, "model_state": mstate},
        meta={"epoch": 2, "completed": 1},
    )
    pool = ReplicaPool.from_config(
        _serving_cfg(checkpoint_dir=str(tmp_path), seed=999, num_replicas=2),
        devices=cpu_devices,
    )
    assert pool.restored_epoch == 2
    for a, b in zip(
        jax.tree_util.tree_leaves(pool.replicas[1].params),
        jax.tree_util.tree_leaves(params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ schema / stats --


def test_serving_stats_rows_validate(tmp_path, cpu_devices):
    eng = ServingEngine.from_config(
        _serving_cfg(num_replicas=1, stats_window=4),
        out_dir=str(tmp_path),
        devices=cpu_devices[:1],
    )
    eng.start()
    for i in range(10):
        eng.submit(f"t{i % 2}", np.ones((1,) + SHAPE, np.float32)).result(60)
    eng.drain()
    path = os.path.join(str(tmp_path), "history.jsonl")
    errors, n = schema.validate_history_file(path)
    assert errors == [] and n >= 4  # run_meta + >=2 windows + drain event
    records = [json.loads(l) for l in open(path) if l.strip()]
    assert records[0]["type"] == "run_meta"
    assert records[0]["api"] == "serving"
    rows = [r for r in records if r["type"] == "serving_stats"]
    assert sum(r["completed"] for r in rows) == 10
    assert all(r["schema_version"] == schema.SCHEMA_VERSION for r in rows)
    assert records[-1]["type"] == "event"
    assert records[-1]["event"] == "serving_drain"


def test_serving_stats_schema_reject_drift():
    good = schema.stamp("serving_stats", {
        "window": 0, "requests": 4, "completed": 4, "rejected": 0,
        "queue_ms_p50": 1.0, "device_ms_p50": 0.5, "e2e_ms_p50": 2.0,
        "e2e_ms_p95": 3.0, "e2e_ms_p99": 4.0, "throughput_rps": 10.0,
        "batch_occupancy": 0.9, "shed": 0,
    })
    assert schema.validate_record(good) == []
    # v7 drift: a window without its shed count is invalid; a v6 copy
    # without it stays valid (versioned requirement)
    drifted = {k: v for k, v in good.items() if k != "shed"}
    errs = schema.validate_record(drifted)
    assert errs and any("shed" in e for e in errs)
    v6 = dict(drifted)
    v6["schema_version"] = 6
    assert schema.validate_record(v6) == []
    missing = dict(good)
    del missing["e2e_ms_p99"]
    assert any("e2e_ms_p99" in e for e in schema.validate_record(missing))
    newer = dict(good, schema_version=schema.SCHEMA_VERSION + 1)
    assert any("newer" in e for e in schema.validate_record(newer))


def test_inspect_cli_rejects_drifted_serving_history(tmp_path):
    """Satellite: tpuddp_inspect --validate must exit 1 on a serving row
    that drifted off the v2 schema."""
    path = tmp_path / "history.jsonl"
    meta = schema.make_run_meta(world_size=1, comm_hook=None, guard=None,
                                extra={"api": "serving"})
    bad = schema.stamp("serving_stats", {"window": 0, "requests": 1})
    with open(path, "w") as f:
        f.write(json.dumps(meta) + "\n")
        f.write(json.dumps(bad) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
         "--validate", str(path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "missing required field" in proc.stderr


def test_stats_mark_since():
    stats = ServingStats(writer=None, window=0)
    q = RequestQueue(max_depth=8)
    sched = BatchScheduler(q, max_batch_size=8)
    batch = sched.form([_req("a", 3)])
    t = time.perf_counter()
    stats.record_submit()
    stats.record_batch(batch, t, t + 0.010)
    m = stats.mark()
    batch2 = sched.form([_req("b", 2)])
    stats.record_submit()
    stats.record_batch(batch2, t, t + 0.020)
    d = stats.since(m)
    assert d["completed"] == 1 and d["rows"] == 2
    assert abs(d["device_ms"]["p50"] - 20.0) < 0.5
    total = stats.summary()
    assert total["completed"] == 2 and total["completed_rows"] == 5


# ---------------------------------------------------------------- slow tier --


def _subprocess_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TPUDDP_BACKEND"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _write_settings(tmp_path, **serving_overrides):
    serving = dict(
        model="toy_mlp", input_shape=[8, 8, 3], num_replicas=2,
        max_batch_size=8, stats_window=8,
    )
    serving.update(serving_overrides)
    path = os.path.join(str(tmp_path), "settings.yaml")
    with open(path, "w") as f:
        yaml.dump({"out_dir": os.path.join(str(tmp_path), "out"),
                   "serving": serving}, f)
    return path


@pytest.mark.slow
def test_demo_entrypoint(tmp_path):
    settings = _write_settings(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "tpuddp.serving", "--settings", settings,
         "--demo", "20", "--tenants", "2"],
        capture_output=True, text=True, env=_subprocess_env(), cwd=REPO,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["completed"] == 20
    assert set(summary["per_tenant_completed"]) == {"tenant0", "tenant1"}
    errors, _ = schema.validate_history_file(
        os.path.join(str(tmp_path), "out", "history.jsonl")
    )
    assert errors == []


@pytest.mark.slow
@pytest.mark.chaos
def test_sigterm_drain_exit75(tmp_path):
    """SIGTERM while serving: admission closes, in-flight work completes,
    stats flush, and the process exits with the resilience contract's 75."""
    settings = _write_settings(tmp_path)
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "tpuddp.serving", "--settings", settings,
         "--serve", "120"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_subprocess_env(), cwd=REPO,
    )
    try:
        deadline = time.time() + 240
        ready = False
        for line in proc.stdout:
            if "serving: ready" in line:
                ready = True
                break
            if time.time() > deadline:
                break
        assert ready, "server never reported ready"
        proc.send_signal(signal.SIGTERM)
        out = proc.stdout.read()
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == EXIT_PREEMPTED, out[-2000:]
    history = os.path.join(str(tmp_path), "out", "history.jsonl")
    errors, _ = schema.validate_history_file(history)
    assert errors == []
    records = [json.loads(l) for l in open(history) if l.strip()]
    drain = [r for r in records if r.get("event") == "serving_drain"]
    assert drain and drain[-1]["reason"] == "sigterm_drain"


@pytest.mark.slow
def test_loadgen_smoke(tmp_path):
    """Acceptance demo: loadgen drives 2 tenants against 2 replicas on the
    CPU mesh; the latency-vs-offered-throughput curve (>=3 open-loop points
    with p50/p99) lands in bench format and validates."""
    out = os.path.join(str(tmp_path), "bench_results.json")
    proc = subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "tools", "loadgen.py"),
         "--quick", "--replicas", "2", "--tenants", "2",
         "--history-dir", str(tmp_path), "--out", out],
        capture_output=True, text=True, env=_subprocess_env(), cwd=REPO,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["completed"] >= 100
    payload = json.load(open(out))
    assert schema.validate_bench_payload(payload) == []
    assert payload["tenants"] == 2 and payload["replicas"] == 2
    open_rows = [r for r in payload["configs"].values()
                 if r.get("mode") == "open"]
    assert len(open_rows) >= 3
    for row in open_rows:
        assert row["offered_rps"] > 0
        assert row["e2e_ms_p50"] is not None
        assert row["e2e_ms_p99"] is not None
    errors, _ = schema.validate_history_file(
        os.path.join(str(tmp_path), "history.jsonl")
    )
    assert errors == []
    # the inspect CLI accepts both artifacts (the full gate's serving leg)
    for artifact in (out, os.path.join(str(tmp_path), "history.jsonl")):
        rc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
             "--validate", artifact],
            capture_output=True, text=True,
        ).returncode
        assert rc == 0
