"""Autoregressive decode suite (ISSUE 12, tpuddp/serving/decode/):
paged-KV-cache accounting, the end-to-end acceptance contract (concurrent
sequences stream token-by-token bitwise-identical to single-sequence
reference decodes; a finishing sequence frees its blocks and a queued
request joins the next step), admission/termination semantics, schema-v6
decode_stats emission + drift rejection, the /metrics scrape-vs-stats
value match, and — slow tier — the --decode demo entrypoint and the
SIGTERM drain exit-75 contract."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import yaml

from tpuddp import config as config_lib
from tpuddp.observability import schema
from tpuddp.resilience.preemption import EXIT_PREEMPTED
from tpuddp.serving import AdmissionError
from tpuddp.serving.decode import DecodeEngine, DecodeStats, PagedKVCache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB = 32


def _decode_cfg(**overrides):
    cfg = config_lib.decode_config({"decode": {}})
    cfg.update(
        model="transformer_tiny",
        vocab_size=VOCAB,
        num_replicas=1,
        max_slots=4,
        kv_blocks=17,  # 16 allocatable = exactly 4 worst-case sequences
        kv_block_size=8,
        max_seq_len=32,
        max_new_tokens=8,
        stats_window=16,
        max_queue_depth=64,
    )
    cfg.update(overrides)
    return cfg


@pytest.fixture(scope="module")
def engine(cpu_devices):
    eng = DecodeEngine.from_config(_decode_cfg(), devices=cpu_devices)
    eng.start()
    yield eng
    eng.drain()


def _prompt(rng, n=None):
    n = n if n is not None else int(rng.randint(1, 13))
    return rng.randint(0, VOCAB, size=n).astype(np.int32)


# -------------------------------------------------------------- KV cache --


def test_cache_allocation_accounting():
    c = PagedKVCache(layers=2, heads=4, head_dim=8, num_blocks=9,
                     block_size=4, max_slots=3, max_seq_len=16)
    assert c.allocatable == 8 and c.max_blocks == 4
    assert c.pool_shape() == (2, 9, 4, 4, 8)
    assert c.occupancy() == 0.0
    s0 = c.allocate(9)  # 3 blocks of 4
    assert c.used_blocks == 3 and c.free_slots == 2
    assert c.occupancy() == pytest.approx(3 / 8)
    # the table row names only this sequence's blocks; tail entries are the
    # garbage block 0
    row = c.tables[s0]
    assert (row[:3] > 0).all() and row[3] == 0
    s1 = c.allocate(16)  # 4 blocks
    assert c.used_blocks == 7
    # 1 block left: a 5-token sequence (2 blocks) cannot be admitted even
    # though a slot is free — lifetime budgets are reserved up front
    assert c.free_slots == 1 and not c.can_admit(5)
    assert c.can_admit(4)
    c.free(s0)
    assert c.used_blocks == 4 and c.free_slots == 2
    assert (c.tables[s0] == 0).all() and c.lengths[s0] == 0
    c.free(s1)
    assert c.occupancy() == 0.0


def test_cache_rejects_bad_geometry_and_misuse():
    with pytest.raises(ValueError, match="reserved"):
        PagedKVCache(layers=1, heads=1, head_dim=4, num_blocks=1,
                     block_size=4, max_slots=1, max_seq_len=4)
    with pytest.raises(ValueError, match="cannot hold even one"):
        PagedKVCache(layers=1, heads=1, head_dim=4, num_blocks=3,
                     block_size=2, max_slots=1, max_seq_len=16)
    c = PagedKVCache(layers=1, heads=1, head_dim=4, num_blocks=5,
                     block_size=4, max_slots=2, max_seq_len=16)
    with pytest.raises(ValueError, match="outside"):
        c.allocate(17)
    with pytest.raises(ValueError, match="not allocated"):
        c.free(0)
    c.allocate(16)
    with pytest.raises(RuntimeError, match="cannot admit"):
        c.allocate(16)


def test_cache_blocks_reused_after_free():
    c = PagedKVCache(layers=1, heads=1, head_dim=4, num_blocks=5,
                     block_size=4, max_slots=2, max_seq_len=16)
    s0 = c.allocate(16)
    first = set(int(b) for b in c.tables[s0] if b)
    c.free(s0)
    s1 = c.allocate(16)
    assert set(int(b) for b in c.tables[s1] if b) == first


# ----------------------------------------------------- acceptance contract --


def test_concurrent_streams_bitwise_equal_solo_reference(engine):
    """THE acceptance test: N concurrent requests with different lengths
    stream token-by-token; each sequence's tokens are bitwise-identical to
    a single-sequence reference decode of the same prompt — continuous
    batching and KV paging are numerically invisible."""
    rng = np.random.RandomState(0)
    prompts = [_prompt(rng, n) for n in (1, 3, 5, 8, 12, 2, 7, 10)]
    # reference: each prompt decoded ALONE (waited before the next submit)
    solo = [
        np.asarray(engine.submit("ref", p, seed=9).result(timeout=120))
        for p in prompts
    ]
    # the same prompts all in flight at once (8 sequences > 4 slots, so the
    # batch churns mid-decode as finishers free slots for queued joiners)
    results = [engine.submit(f"t{i % 3}", p, seed=9)
               for i, p in enumerate(prompts)]
    streamed = [list(r.stream(timeout=120)) for r in results]
    for i, r in enumerate(results):
        final = np.asarray(r.result(timeout=120))
        assert final.dtype == np.int32
        np.testing.assert_array_equal(final, solo[i])
        assert streamed[i] == list(solo[i])


def test_finisher_frees_blocks_and_queued_request_joins(engine):
    """More sequences than slots with wildly different generation lengths:
    everything completes (queued requests joined as slots freed), and the
    pool drains back to zero occupancy."""
    rng = np.random.RandomState(1)
    results = [
        engine.submit("t", _prompt(rng), max_new_tokens=int(rng.randint(1, 9)))
        for _ in range(12)
    ]
    for r in results:
        assert np.asarray(r.result(timeout=120)).ndim == 1
    deadline = time.time() + 10
    while engine.active_sequences() and time.time() < deadline:
        time.sleep(0.01)
    assert engine.kv_occupancy() == 0.0
    assert engine.active_sequences() == 0


def test_stream_is_incremental_and_matches_result(engine):
    rng = np.random.RandomState(2)
    res = engine.submit("t", _prompt(rng, 4))
    toks = []
    for tok in res.stream(timeout=120):
        assert isinstance(tok, int)
        toks.append(tok)
    assert res.first_token_at is not None
    np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                  np.asarray(res.result(timeout=1)))
    assert len(toks) == engine.max_new_tokens


def test_stream_timeout_raises_timeout_error():
    """A stalled stream raises TimeoutError — the same type result() raises
    — never the raw queue.Empty internal."""
    from tpuddp.serving.decode.engine import StreamedResult

    res = StreamedResult()
    with pytest.raises(TimeoutError, match="stalled"):
        next(res.stream(timeout=0.01))


def test_stop_token_terminates_and_is_consumed(engine):
    rng = np.random.RandomState(3)
    p = _prompt(rng, 6)
    full = np.asarray(engine.submit("t", p, seed=4).result(timeout=120))
    stop = int(full[2])
    # the same deterministic decode with full[2] armed as the stop token
    # must deliver exactly the tokens BEFORE it — consumed, never emitted
    out = np.asarray(
        engine.submit("t", p, seed=4, stop_token=stop).result(timeout=120)
    )
    np.testing.assert_array_equal(out, full[:2] if stop not in full[:2]
                                  else full[:list(full).index(stop)])
    # stop on the FIRST sampled token: an empty (but successful) stream
    first = int(full[0])
    empty = engine.submit("t", p, seed=4, stop_token=first)
    assert list(empty.stream(timeout=120)) == []
    assert np.asarray(empty.result(timeout=1)).shape == (0,)


def test_temperature_sampling_deterministic_per_seed(engine):
    """Softmax sampling draws from a stream keyed by (seed, token index)
    only: the same request decodes identically alone or among strangers,
    and a different seed genuinely changes the draw."""
    rng = np.random.RandomState(5)
    p = _prompt(rng, 5)
    a = np.asarray(
        engine.submit("t", p, temperature=0.9, seed=11).result(timeout=120)
    )
    crowd = [engine.submit("t", _prompt(rng), temperature=0.9, seed=100 + i)
             for i in range(5)]
    b = engine.submit("t", p, temperature=0.9, seed=11)
    for r in crowd:
        r.result(timeout=120)
    np.testing.assert_array_equal(a, np.asarray(b.result(timeout=120)))
    c = np.asarray(
        engine.submit("t", p, temperature=0.9, seed=12).result(timeout=120)
    )
    assert not np.array_equal(a, c)


# --------------------------------------------------------------- admission --


def test_admission_rejects_bad_prompts(engine):
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.zeros((2, 3), np.int32))
    assert e.value.reason == "bad_shape"
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.zeros((3,), np.float32))
    assert e.value.reason == "bad_shape"
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.asarray([0, VOCAB], np.int32))
    assert e.value.reason == "bad_shape"
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.zeros((engine.max_prompt_len + 1,), np.int32))
    assert e.value.reason == "oversized"
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.zeros((2,), np.int32), max_new_tokens=0)
    assert e.value.reason == "oversized"
    with pytest.raises(AdmissionError) as e:
        engine.submit("t", np.zeros((28,), np.int32), max_new_tokens=8)
    assert e.value.reason == "oversized"  # prompt + mnt > max_seq_len


def test_engine_rejects_non_transformer_model(cpu_devices):
    with pytest.raises(ValueError, match="not a TransformerLM"):
        DecodeEngine.from_config(_decode_cfg(model="toy_mlp"),
                                 devices=cpu_devices)


def test_engine_rejects_seq_len_beyond_position_table(cpu_devices):
    with pytest.raises(ValueError, match="position table"):
        DecodeEngine.from_config(
            _decode_cfg(max_seq_len=256),  # transformer_tiny holds 128
            devices=cpu_devices,
        )


def test_drain_then_submit_rejected(cpu_devices):
    eng = DecodeEngine.from_config(
        _decode_cfg(max_slots=2, kv_blocks=9), devices=cpu_devices
    )
    eng.start()
    rng = np.random.RandomState(6)
    res = eng.submit("t", _prompt(rng, 3))
    summary = eng.drain(reason="test")
    assert np.asarray(res.result(timeout=1)).ndim == 1  # finished, not cut
    with pytest.raises(AdmissionError) as e:
        eng.submit("t", _prompt(rng, 3))
    assert e.value.reason == "draining"
    assert summary["completed"] == 1
    # drain is idempotent
    assert eng.drain()["completed"] == 1


def test_failed_dispatch_with_consumed_pools_fails_over(cpu_devices):
    """A dispatch that raises after consuming its donated K/V pool buffers
    (real donation semantics on an accelerator; XLA:CPU ignores donation,
    so the injected failure deletes the arrays itself) must not poison the
    replica OR kill its streams: under the survivability layer
    (tpuddp/serving/survive.py) the in-flight sequence parks into its
    session journal, the replica rebuilds through probation, and the
    stream completes BITWISE-equal to an undisturbed same-seed run."""
    eng = DecodeEngine.from_config(_decode_cfg(), devices=cpu_devices)
    eng.start()
    try:
        rng = np.random.RandomState(13)
        p = _prompt(rng)
        # undisturbed twin first, so the failover run has a bitwise anchor
        twin = np.asarray(eng.submit("t", p, seed=3).result(timeout=120))
        replica = eng.replicas[0]
        real_step = replica._step
        fired = threading.Event()

        def consuming_step(params, kpool, vpool, *rest):
            if not fired.is_set():
                fired.set()
                kpool.delete()
                vpool.delete()
                raise RuntimeError("injected dispatch failure")
            return real_step(params, kpool, vpool, *rest)

        replica._step = consuming_step
        out = np.asarray(eng.submit("t", p, seed=3).result(timeout=120))
        assert fired.is_set()
        np.testing.assert_array_equal(out, twin)
        assert not replica.kpool.is_deleted()
        assert replica.recoveries == 1 and replica.healthy
        assert eng.stats.failovers == 1
    finally:
        eng.drain()


# ------------------------------------------------------- schema + history --


def test_decode_stats_rows_and_run_meta_validate(tmp_path, cpu_devices):
    out = str(tmp_path / "run")
    eng = DecodeEngine.from_config(
        _decode_cfg(stats_window=8), out_dir=out, devices=cpu_devices
    )
    eng.start()
    rng = np.random.RandomState(7)
    for r in [eng.submit("t", _prompt(rng)) for _ in range(6)]:
        r.result(timeout=120)
    eng.drain(reason="test_complete")
    history = os.path.join(out, "history.jsonl")
    errors, n = schema.validate_history_file(history)
    assert errors == [] and n >= 3
    records = [json.loads(l) for l in open(history) if l.strip()]
    meta = records[0]
    assert meta["type"] == "run_meta"
    assert meta["schema_version"] == schema.SCHEMA_VERSION
    # v7: the survivability provenance is non-null on decode headers
    assert meta["survivability"]["max_recoveries"] == 2
    dec = meta["decode"]
    assert dec["model"] == "transformer_tiny"
    assert dec["max_slots"] == 4 and dec["kv_block_size"] == 8
    windows = [r for r in records if r["type"] == "decode_stats"]
    assert windows, "no decode_stats rows emitted"
    assert sum(w["tokens"] for w in windows) == 6 * 8
    assert all(w["kv_occupancy"] is not None for w in windows)
    drains = [r for r in records if r.get("event") == "decode_drain"]
    assert drains and drains[-1]["reason"] == "test_complete"
    assert drains[-1]["completed"] == 6


def test_decode_stats_schema_reject_drift():
    good = schema.stamp("decode_stats", {
        "window": 0, "tokens": 16, "completed": 2, "requests": 2,
        "rejected": 0, "tokens_per_sec": 100.0,
        "ttft_ms_p50": 1.0, "ttft_ms_p95": 2.0,
        "itl_ms_p50": 0.5, "itl_ms_p95": 0.9, "itl_ms_p99": 1.1,
        "kv_occupancy": 0.25, "active_sequences": 2,
        "shed": 0, "failovers": 0,
    })
    assert schema.validate_record(good) == []
    bad = dict(good)
    del bad["tokens_per_sec"], bad["kv_occupancy"]
    errs = schema.validate_record(bad)
    assert any("tokens_per_sec" in e and "kv_occupancy" in e for e in errs)
    # v7 drift: a window without its survivability accounting is invalid —
    # but a v6 copy without them stays valid (versioned requirement)
    drifted = {k: v for k, v in good.items() if k not in ("shed", "failovers")}
    errs = schema.validate_record(drifted)
    assert errs and any("shed" in e and "failovers" in e for e in errs)
    v6 = dict(drifted)
    v6["schema_version"] = 6
    assert schema.validate_record(v6) == []


def test_v6_run_meta_requires_decode_provenance(tmp_path):
    """Drift-reject (satellite): a v6 header MISSING the decode key is
    invalid — a reader must always be able to tell 'not a decode run'
    (null) from 'predates the subsystem' (absent) — and the inspect CLI
    refuses the file the same way."""
    meta = schema.make_run_meta(world_size=1)
    assert "decode" in meta and meta["decode"] is None  # null, never absent
    assert schema.validate_record(meta) == []
    drifted = {k: v for k, v in meta.items() if k != "decode"}
    errs = schema.validate_record(drifted)
    assert errs and any("decode" in e for e in errs)
    # a v5 header without the key stays valid (versioned requirement)
    v5 = dict(drifted)
    v5["schema_version"] = 5
    assert schema.validate_record(v5) == []
    path = tmp_path / "history.jsonl"
    path.write_text(json.dumps(drifted) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
         "--validate", str(path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "decode" in proc.stderr


def test_loadgen_token_curve_drift_rejected(tmp_path):
    """Drift-reject (satellite): a decode bench row that loses its rate
    metric fails validation — and the inspect CLI agrees."""
    payload = {
        "metric": "decode_tokens_per_sec", "value": 1.0, "unit": "tokens/sec",
        "vs_baseline": 2.0, "device": "cpu",
        "configs": {"closed_loop": {"tokens_per_sec": 900.0,
                                    "ms_per_step": 1.2}},
    }
    assert schema.validate_bench_payload(payload) == []
    del payload["configs"]["closed_loop"]["tokens_per_sec"]
    errs = schema.validate_bench_payload(payload)
    assert errs and any("needs one of" in e for e in errs)
    path = tmp_path / "bench_results.json"
    path.write_text(json.dumps(payload))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tpuddp_inspect.py"),
         "--validate", str(path)],
        capture_output=True, text=True,
    )
    assert proc.returncode == 1


def test_stats_mark_since_and_flush():
    s = DecodeStats(writer=None, window=4)
    m = s.mark()
    s.record_submit()
    s.record_first_token(5.0, prompt_tokens=3)
    for _ in range(3):
        s.record_token(1.0)
    s.record_finish("a")
    d = s.since(m)
    assert d["tokens"] == 4 and d["completed"] == 1 and d["submitted"] == 1
    assert d["ttft_ms"]["p50"] == 5.0 and d["itl_ms"]["p50"] == 1.0
    # the 4-token window auto-emitted; a second flush with no traffic is None
    assert s.last_window is not None and s.last_window["tokens"] == 4
    assert s.flush_window() is None
    s.record_reject("a", "queue_full")
    w = s.flush_window()
    assert w["rejected"] == 1 and w["tokens"] == 0
    assert w["ttft_ms_p50"] is None  # null, never absent


# ------------------------------------------------- exporter scrape match --


def test_exporter_scrape_matches_decode_stats(tmp_path, cpu_devices):
    """Satellite acceptance: the /metrics decode gauges (tokens, sequences,
    KV occupancy, active sequences, queue depth) must equal the engine's
    own stats/gauges at scrape time."""
    import urllib.request

    eng = DecodeEngine.from_config(
        _decode_cfg(stats_window=8),
        out_dir=str(tmp_path / "run"),
        devices=cpu_devices,
        observability={"exporter": True, "exporter_port": 0},
    )
    eng.start()
    try:
        rng = np.random.RandomState(8)
        for r in [eng.submit("t", _prompt(rng)) for _ in range(4)]:
            r.result(timeout=120)
        deadline = time.time() + 10
        while eng.active_sequences() and time.time() < deadline:
            time.sleep(0.01)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{eng.exporter.port}/metrics", timeout=10
        ).read().decode()

        def value(name):
            for line in text.splitlines():
                if line.startswith(f"tpuddp_{name} "):
                    return float(line.split()[-1])
            raise AssertionError(f"tpuddp_{name} missing from /metrics:\n{text}")

        assert value("decode_tokens_total") == eng.stats.tokens == 4 * 8
        assert value("decode_sequences_completed_total") == eng.stats.completed == 4
        assert value("decode_requests_total") == eng.stats.submitted == 4
        assert value("decode_rejected_total") == 0
        assert value("decode_kv_occupancy") == eng.kv_occupancy() == 0.0
        assert value("decode_active_sequences") == eng.active_sequences() == 0
        assert value("decode_queue_depth") == eng.queue.depth() == 0
        # a full window flushed (32 tokens > window 8): throughput is live,
        # the TTFT/ITL summary families are registered, and any percentile
        # the last window carries is served with the window's exact value
        win = eng.stats.last_window
        assert value("decode_tokens_per_sec") == win["tokens_per_sec"] > 0
        assert "# TYPE tpuddp_decode_ttft_ms summary" in text
        assert "# TYPE tpuddp_decode_itl_ms summary" in text
        for name, key, q in (("decode_ttft_ms", "ttft_ms_p50", "0.5"),
                             ("decode_itl_ms", "itl_ms_p99", "0.99")):
            if win[key] is not None:
                line = f'tpuddp_{name}{{quantile="{q}"}} '
                got = [l for l in text.splitlines() if l.startswith(line)]
                assert got and float(got[0].split()[-1]) == win[key]
    finally:
        eng.drain()


# ------------------------------------------------------------- slow tier --


def _subprocess_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TPUDDP_BACKEND"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _write_settings(tmp_path, **decode_overrides):
    decode = dict(
        vocab_size=VOCAB, max_slots=4, kv_blocks=17, kv_block_size=8,
        max_seq_len=32, max_new_tokens=8, stats_window=16,
    )
    decode.update(decode_overrides)
    path = str(tmp_path / "settings.yaml")
    with open(path, "w") as f:
        yaml.dump({"out_dir": os.path.join(str(tmp_path), "out"),
                   "serving": {"decode": decode}}, f)
    return path


@pytest.mark.slow
def test_decode_demo_entrypoint(tmp_path):
    settings = _write_settings(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "tpuddp.serving", "--settings", settings,
         "--decode", "--demo", "12", "--tenants", "2"],
        capture_output=True, text=True, env=_subprocess_env(), cwd=REPO,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["completed"] == 12
    assert summary["tokens"] == 12 * 8
    assert set(summary["per_tenant_completed"]) == {"tenant0", "tenant1"}
    errors, _ = schema.validate_history_file(
        os.path.join(str(tmp_path), "out", "history.jsonl")
    )
    assert errors == []


@pytest.mark.slow
@pytest.mark.chaos
def test_decode_sigterm_drain_exit75(tmp_path):
    """SIGTERM mid-decode: admission closes, every in-flight sequence
    finishes streaming (completed == submitted — nothing truncated), and
    the process exits 75 with a valid v6 history. The workload is sized so
    the signal lands seconds before decode could finish, and
    in_flight_at_drain proves it did — completed == submitted against an
    already-idle engine would be a vacuous pass."""
    settings = _write_settings(tmp_path, max_new_tokens=96, max_seq_len=128,
                               kv_blocks=65)
    n = 16
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "tpuddp.serving", "--settings", settings,
         "--decode", "--demo", str(n), "--serve", "120"],
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
        proc.send_signal(signal.SIGTERM)  # demo sequences still in flight
        out = proc.stdout.read()
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == EXIT_PREEMPTED, out[-2000:]
    summary = json.loads(out.strip().splitlines()[-1])
    assert summary["submitted"] == n and summary["completed"] == n
    assert summary["in_flight_at_drain"] > 0
    history = os.path.join(str(tmp_path), "out", "history.jsonl")
    errors, _ = schema.validate_history_file(history)
    assert errors == []
    records = [json.loads(l) for l in open(history) if l.strip()]
    drain = [r for r in records if r.get("event") == "decode_drain"]
    assert drain and drain[-1]["reason"] == "sigterm_drain"
    assert drain[-1]["completed"] == n
