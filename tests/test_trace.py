"""Causal tracing plane (ISSUE 15, tpuddp/observability/trace.py).

The contracts: a bounded span ring with honest drop accounting; Chrome-trace
export that validates under schema v9 with correctly-nesting trees and
follows_from flow edges; tracing ON changes ZERO semantics (a traced
training run's loss trajectory is bitwise the untraced twin's, and tracing
OFF writes nothing); serving requests are one span tree each, and a decode
session that fails over stays ONE trace; the exporter's /metrics, /snapshot
and /trace endpoints never serve a torn payload under a concurrent writer
(the MetricsExporter concurrency satellite); and the trace tooling
(tpuddp_inspect trace) consumes the artifacts.
"""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from tpuddp import config as config_lib
from tpuddp.observability import schema as schema_mod
from tpuddp.observability import trace as trace_mod
from tpuddp.observability.trace import NULL, Tracer, tracer_from_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- span model --


def test_ring_bound_and_drop_accounting(tmp_path):
    t = Tracer("train", capacity=4, run_dir=str(tmp_path), process_index=0)
    root = t.start_span("epoch 0", trace_mod.KIND_EPOCH)
    for _ in range(8):
        t.end_span(t.start_span("d", trace_mod.KIND_DISPATCH, parent=root))
    t.end_span(root)
    # 9 completed, ring holds 4, 5 dropped — and the cumulative per-kind
    # counters cover EVERY completed span, not just the ring survivors
    assert t.completed == 9
    assert t.dropped == 5
    assert t.kind_counts["dispatch"] == 8 and t.kind_counts["epoch"] == 1
    rec = t.summary_record()
    assert rec["spans"] == 9 and rec["dropped"] == 5
    assert rec["open_spans"] == 0
    assert rec["slowest"] and rec["slowest"][0]["duration_ms"] >= 0
    assert schema_mod.validate_record(
        schema_mod.stamp("trace_summary", rec)
    ) == []


def test_open_spans_surface_for_flight_embed():
    t = Tracer("train", process_index=0)
    root = t.start_span("epoch 3", trace_mod.KIND_EPOCH)
    child = t.start_span("dispatch", trace_mod.KIND_DISPATCH, parent=root)
    opens = t.open_span_summaries()
    assert [s["name"] for s in opens] == ["epoch 3", "dispatch"]
    assert opens[1]["parent_id"] == root.span_id
    assert opens[0]["duration_ms"] is None  # still open
    t.end_span(child)
    t.end_span(root)
    assert t.open_span_summaries() == []


def test_end_span_idempotent_and_unknown_kind_refused():
    t = Tracer("train", process_index=0)
    with pytest.raises(ValueError, match="unknown span kind"):
        t.start_span("x", "not_a_kind")
    s = t.start_span("x", trace_mod.KIND_STAGE)
    t.end_span(s)
    t.end_span(s)  # second end is a no-op, not a double count
    assert t.completed == 1
    t.end_span(trace_mod.NULL_SPAN)  # the null span is always ignored
    assert t.completed == 1


def test_null_tracer_and_config_gate(tmp_path):
    assert tracer_from_config({"tracing": False}, "train") is NULL
    assert tracer_from_config(None, "train") is NULL
    assert not NULL.enabled
    s = NULL.start_span("x", "anything")  # no kind validation, no recording
    NULL.end_span(s)
    assert NULL.describe() is None
    assert NULL.export(str(tmp_path / "t.json")) is None
    assert not (tmp_path / "t.json").exists()
    live = tracer_from_config(
        config_lib.resolve_observability({"tracing": True}), "train",
        run_dir=str(tmp_path),
    )
    assert live.enabled and live.capacity == 4096


# ----------------------------------------------------------------- export --


def test_export_validates_nests_and_links(tmp_path):
    t = Tracer("decode", capacity=64, run_dir=str(tmp_path), process_index=0)
    root = t.start_span(
        "request", trace_mod.KIND_REQUEST, tid="client",
        attrs={"tenant": "a"},
    )
    q = t.start_span("queue_wait", trace_mod.KIND_QUEUE_WAIT, parent=root)
    t.end_span(q)
    pre = t.start_span(
        "prefill", trace_mod.KIND_PREFILL, parent=root,
        follows_from=q.span_id,
    )
    t.end_span(pre)
    t.end_span(root)
    path = t.export()
    assert path == str(tmp_path / "trace_decode.json")
    errors, n = schema_mod.validate_trace_file(path)
    assert errors == [] and n == 3
    payload = json.load(open(path))
    spans = {
        e["args"]["span_id"]: e
        for e in payload["traceEvents"] if e.get("ph") == "X"
    }
    assert spans[q.span_id]["args"]["parent_id"] == root.span_id
    # one trace, all three spans
    assert len({e["args"]["trace_id"] for e in spans.values()}) == 1
    # follows_from becomes a flow s/f pair
    phases = [e["ph"] for e in payload["traceEvents"]]
    assert "s" in phases and "f" in phases
    # thread metadata rows for the named tids
    names = {
        e["args"]["name"] for e in payload["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "thread_name"
    }
    assert "client" in names


def test_trace_payload_drift_rejected(tmp_path):
    t = Tracer("train", run_dir=str(tmp_path), process_index=0)
    t.end_span(t.start_span("e", trace_mod.KIND_EPOCH))
    payload = t.chrome_payload()
    assert schema_mod.validate_trace_payload(payload) == []
    # missing provenance block
    assert schema_mod.validate_trace_payload(
        {"traceEvents": []}
    )
    # newer-version reject
    newer = json.loads(json.dumps(payload))
    newer["tpuddp"]["schema_version"] = schema_mod.SCHEMA_VERSION + 1
    assert any("newer" in e for e in schema_mod.validate_trace_payload(newer))
    # orphan parent_id is drift — but ONLY while the ring dropped nothing
    orphan = json.loads(json.dumps(payload))
    orphan["traceEvents"][-1]["args"]["parent_id"] = 999999
    errs = schema_mod.validate_trace_payload(orphan)
    assert any("orphan" in e for e in errs)
    orphan["tpuddp"]["dropped"] = 3
    assert not any(
        "orphan" in e for e in schema_mod.validate_trace_payload(orphan)
    )


def test_schema_v9_requires_tracing_field():
    good = schema_mod.make_run_meta(world_size=1, comm_hook=None, guard=None)
    assert good["tracing"] is None
    assert schema_mod.validate_record(good) == []
    dropped = {k: v for k, v in good.items() if k != "tracing"}
    errs = schema_mod.validate_record(dropped)
    assert any("tracing" in e for e in errs)
    # a v8 header (predates the plane) stays valid without the key
    v8 = dict(dropped, schema_version=8)
    assert schema_mod.validate_record(v8) == []
    # trace_summary requires its accounting fields
    bad = schema_mod.stamp("trace_summary", {"role": "train"})
    assert schema_mod.validate_record(bad)


# ------------------------------------------------- training loop end to end --


def _loop_run(mesh, save_dir, observability):
    import jax
    import jax.numpy as jnp

    from tpuddp import optim
    from tpuddp.data import ShardedDataLoader, SyntheticClassification
    from tpuddp.models import ToyMLP
    from tpuddp.nn import CrossEntropyLoss
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training.loop import run_training_loop

    ds = SyntheticClassification(n=64, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    test_loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(),
        mesh=mesh, comm_hook="bf16_ef",
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    return run_training_loop(
        ddp, state, loader, test_loader, save_dir, num_epochs=2,
        checkpoint_epoch=1, log=lambda *_: None,
        observability=observability,
    )


def test_traced_training_bitwise_and_artifact(mesh, tmp_path):
    """THE acceptance pair: tracing on produces the identical loss
    trajectory (bitwise on the recorded floats), a schema-v9 artifact with
    the full span-kind set (incl. the comm-hook collective annotation),
    and the run_meta/trace_summary records; tracing off writes NOTHING."""
    d_on, d_off = str(tmp_path / "on"), str(tmp_path / "off")
    _, hist_on = _loop_run(mesh, d_on, {"tracing": True})
    _, hist_off = _loop_run(mesh, d_off, None)
    traj = lambda h: [  # noqa: E731
        (e["epoch"], e["train_loss"], e["test_loss"], e["test_accuracy"])
        for e in h
    ]
    assert traj(hist_on) == traj(hist_off)

    art = os.path.join(d_on, "trace_train.json")
    assert os.path.exists(art)
    assert not os.path.exists(os.path.join(d_off, "trace_train.json"))
    errors, n = schema_mod.validate_trace_file(art)
    assert errors == [] and n > 0
    payload = json.load(open(art))
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    kinds = {e["cat"] for e in spans}
    assert {"epoch", "stage", "dispatch", "readback", "collective"} <= kinds
    # the pass boundary from inside: the runner's wait and the halves of a
    # stage, the loader's order / gather / pad (ISSUE 39)
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert {e["cat"] for e in by_name["input_wait"]} == {"queue_wait"}
    for name in ("stage_put", "loader_order", "loader_gather", "loader_pad"):
        assert {e["cat"] for e in by_name[name]} == {"load"}, name
    assert "stage_stack" not in by_name  # one batch a pass: nothing to stack
    stage_ids = {e["args"]["span_id"] for e in by_name["stage"]}
    assert len(by_name["stage_put"]) == len(stage_ids) == 4
    assert all(e["args"]["parent_id"] in stage_ids for e in by_name["stage_put"])
    # 2 epochs x (train + eval) passes of 1 batch (8 a replica, 64 samples)
    assert len(by_name["loader_order"]) == 4
    assert len(by_name["loader_gather"]) == len(by_name["loader_pad"]) == 4
    assert len(by_name["input_wait"]) == 8  # a batch and the empty next, a pass
    # the collective annotation carries the hook's wire accounting
    coll = next(e for e in spans if e["cat"] == "collective")
    assert coll["args"]["hook"] == "bf16_ef"
    assert coll["args"]["wire_bytes_per_update"] > 0
    # epochs share ONE run trace; dispatches nest under their epoch
    epochs = [e for e in spans if e["cat"] == "epoch"]
    assert len({e["args"]["trace_id"] for e in epochs}) == 1
    eids = {e["args"]["span_id"] for e in epochs}
    assert all(
        e["args"]["parent_id"] in eids
        for e in spans if e["cat"] == "dispatch"
    )

    records = [
        json.loads(l) for l in open(os.path.join(d_on, "history.jsonl"))
    ]
    assert schema_mod.validate_history_records(records) == []
    meta = records[0]
    assert meta["tracing"] == {"capacity": 4096, "artifact": "trace_train.json"}
    summary = next(r for r in records if r["type"] == "trace_summary")
    assert summary["role"] == "train" and summary["spans"] > 0
    off_meta = json.loads(
        open(os.path.join(d_off, "history.jsonl")).readline()
    )
    assert off_meta["tracing"] is None


def test_traced_step_hlo_identical(mesh):
    """Tracing never touches the compiled program: the wrap has no tracing
    state at all, so the step lowers byte-identical whether the DRIVER
    traces or not — asserted the direct way, by lowering the same wrap's
    step before and after a traced driver pass would run (the wrap is the
    only thing that contributes to the HLO)."""
    import jax
    import jax.numpy as jnp

    from tpuddp import optim
    from tpuddp.models import ToyMLP
    from tpuddp.nn import CrossEntropyLoss
    from tpuddp.parallel.ddp import DistributedDataParallel

    def lower_text():
        ddp = DistributedDataParallel(
            ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(),
            mesh=mesh,
        )
        state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
        b = ddp.shard((
            np.zeros((64, 8, 8, 3), np.float32),
            np.zeros((64,), np.int32),
            np.ones((64,), np.float32),
        ))
        return jax.jit(
            lambda s, x: ddp.train_step(s, x)
        ).lower(state, b).as_text()

    baseline = lower_text()
    # arm a live tracer around a second lowering — identical text
    tracer = Tracer("train", process_index=0)
    sp = tracer.start_span("epoch 0", trace_mod.KIND_EPOCH)
    traced = lower_text()
    tracer.end_span(sp)
    assert traced == baseline


# ------------------------------------------------------------ serving spans --


def _serving_engine(tmp_path, devices, observability):
    from tpuddp.serving.engine import ServingEngine

    cfg = config_lib._merge_refusing_unknown(
        config_lib.SERVING_DEFAULTS,
        {
            "model": "toy_mlp", "num_classes": 10, "input_shape": [4, 4, 1],
            "num_replicas": 2, "max_batch_size": 8, "batch_timeout_ms": 0.0,
            "stats_window": 8,
        },
        "serving",
    )
    return ServingEngine.from_config(
        cfg, out_dir=str(tmp_path), devices=devices,
        observability=observability,
    )


def test_serving_request_trees_and_live_trace_endpoint(tmp_path, cpu_devices):
    eng = _serving_engine(
        tmp_path, cpu_devices[:2],
        {"tracing": True, "exporter": True, "flight_recorder": False},
    )
    eng.start()
    try:
        rng = np.random.RandomState(0)
        results = [
            eng.submit(f"t{i % 2}", rng.randn(2, 4, 4, 1).astype(np.float32))
            for i in range(10)
        ]
        for r in results:
            r.result(timeout=120)
        # the live /trace endpoint serves the same span model
        live = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{eng.exporter.port}/trace", timeout=10
        ))
        assert live["enabled"] and live["role"] == "serving"
        assert live["completed"] > 0
        assert {"trace_id", "span_id", "kind"} <= set(live["spans"][0])
    finally:
        eng.drain()
    art = os.path.join(str(tmp_path), "trace_serving.json")
    errors, _ = schema_mod.validate_trace_file(art)
    assert errors == []
    payload = json.load(open(art))
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    roots = [e for e in spans if e["cat"] == "request"]
    assert len(roots) == 10
    # every request tree: admission + queue_wait + serve under its root,
    # in ITS OWN trace
    for root in roots:
        children = [
            e["cat"] for e in spans
            if e["args"].get("parent_id") == root["args"]["span_id"]
        ]
        assert {"admission", "queue_wait", "serve"} <= set(children)
    assert len({r["args"]["trace_id"] for r in roots}) == 10
    # the per-replica infer rows exist
    assert any(e["cat"] == "dispatch" for e in spans)
    # history carries the drain digest
    records = [
        json.loads(l)
        for l in open(os.path.join(str(tmp_path), "history.jsonl"))
    ]
    assert schema_mod.validate_history_records(records) == []
    assert any(r["type"] == "trace_summary" for r in records)


def test_serving_rejected_request_closes_its_trace(tmp_path, cpu_devices):
    from tpuddp.serving.queue import AdmissionError

    eng = _serving_engine(
        tmp_path, cpu_devices[:2], {"tracing": True, "flight_recorder": False}
    )
    eng.start()
    try:
        with pytest.raises(AdmissionError):
            eng.submit("t", np.zeros((1, 3, 3, 1), np.float32))  # bad shape
        assert eng.tracer.open_span_summaries() == []
        rejected = [
            s for s in eng.tracer.endpoint_payload()["spans"]
            if s["kind"] == "request"
        ]
        assert rejected and rejected[0]["attrs"]["error"] == "bad_shape"
    finally:
        eng.drain()


# ---------------------------------------------------- decode failover trace --


def test_decode_failover_stays_one_trace(tmp_path, cpu_devices):
    """A killed replica's resumed streams: the session's queue_wait /
    failover / resume-prefill spans land in the SAME trace as its original
    request root, with a follows_from edge onto the pre-death span — the
    single-trace failover acceptance criterion."""
    from tpuddp.serving.decode import DecodeEngine

    cfg = config_lib.decode_config({"decode": {}})
    cfg.update(
        model="transformer_tiny", vocab_size=32, num_replicas=1, max_slots=4,
        kv_blocks=17, kv_block_size=8, max_seq_len=32, max_new_tokens=8,
        stats_window=16, max_queue_depth=64, recovery_backoff_s=0.01,
    )
    out = str(tmp_path / "run")
    eng = DecodeEngine.from_config(
        cfg, out_dir=out, devices=cpu_devices[:1],
        observability={"tracing": True, "flight_recorder": False},
    )
    eng.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [
            rng.randint(0, 32, size=n).astype(np.int32) for n in (3, 5, 12)
        ]
        twins = [
            np.asarray(eng.submit("t", p, seed=7 + i).result(timeout=120))
            for i, p in enumerate(prompts)
        ]
        replica = eng.replicas[0]
        real_step = replica._step
        state = {"calls": 0, "fired": False}

        def step(params, kpool, vpool, *rest):
            if not state["fired"] and state["calls"] >= 2:
                state["fired"] = True
                raise RuntimeError("injected replica death")
            state["calls"] += 1
            return real_step(params, kpool, vpool, *rest)

        replica._step = step
        results = [
            eng.submit("t", p, seed=7 + i) for i, p in enumerate(prompts)
        ]
        finals = [np.asarray(r.result(timeout=120)) for r in results]
        assert state["fired"]
        for f, tw in zip(finals, twins):
            np.testing.assert_array_equal(f, tw)
    finally:
        eng.drain()
    art = os.path.join(out, "trace_decode.json")
    errors, _ = schema_mod.validate_trace_file(art)
    assert errors == []
    payload = json.load(open(art))
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    resumes = [
        e for e in spans
        if e["cat"] == "prefill" and e["args"].get("resume")
    ]
    assert resumes, "no resume prefills traced"
    root_by_trace = {
        e["args"]["trace_id"]: e["args"]["span_id"]
        for e in spans if e["cat"] == "request"
    }
    span_ids = {e["args"]["span_id"] for e in spans}
    for r in resumes:
        # the resumed prefill lives in an existing request's trace (ONE
        # trace across the migration), nested under that request's root,
        # causally linked to a pre-death span
        assert r["args"]["trace_id"] in root_by_trace
        assert r["args"]["parent_id"] == root_by_trace[r["args"]["trace_id"]]
        assert r["args"]["follows_from"] in span_ids
    assert any(e["cat"] == "failover" for e in spans)
    assert any(e["cat"] == "probation" for e in spans)
    assert any(e["cat"] == "decode_step" for e in spans)


def test_decode_prefill_death_resume_keeps_linkage(tmp_path, cpu_devices):
    """A PLACE-phase death (the culprit's own prefill raises): the parked
    request reopens a queue_wait in its trace and its re-prefill carries
    the resume attr + a follows_from edge onto the errored prefill — the
    single-trace contract holds for prefill deaths, not just step deaths."""
    from tpuddp.serving.decode import DecodeEngine

    cfg = config_lib.decode_config({"decode": {}})
    cfg.update(
        model="transformer_tiny", vocab_size=32, num_replicas=1, max_slots=4,
        kv_blocks=17, kv_block_size=8, max_seq_len=32, max_new_tokens=8,
        stats_window=16, max_queue_depth=64, recovery_backoff_s=0.01,
    )
    out = str(tmp_path / "run")
    eng = DecodeEngine.from_config(
        cfg, out_dir=out, devices=cpu_devices[:1],
        observability={"tracing": True, "flight_recorder": False},
    )
    eng.start()
    try:
        rng = np.random.RandomState(1)
        p = rng.randint(0, 32, size=5).astype(np.int32)
        twin = np.asarray(eng.submit("t", p, seed=3).result(timeout=120))
        replica = eng.replicas[0]
        real_prefill = replica._prefill
        state = {"fired": False}

        def prefill(params, kpool, vpool, *rest):
            if not state["fired"]:
                state["fired"] = True
                raise RuntimeError("injected prefill death")
            return real_prefill(params, kpool, vpool, *rest)

        replica._prefill = prefill
        got = np.asarray(eng.submit("t", p, seed=3).result(timeout=120))
        assert state["fired"]
        np.testing.assert_array_equal(got, twin)
    finally:
        eng.drain()
    payload = json.load(open(os.path.join(out, "trace_decode.json")))
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    failed = [
        e for e in spans
        if e["cat"] == "prefill" and "error" in e["args"]
    ]
    assert len(failed) == 1
    trace_id = failed[0]["args"]["trace_id"]
    same_trace = [e for e in spans if e["args"].get("trace_id") == trace_id]
    resume = next(
        e for e in same_trace
        if e["cat"] == "prefill" and e["args"].get("resume")
    )
    # the resume follows causally from the ERRORED prefill, and the second
    # wait is a real queue_wait span in the same trace, not a gap
    assert resume["args"]["follows_from"] == failed[0]["args"]["span_id"]
    assert sum(1 for e in same_trace if e["cat"] == "queue_wait") == 2
    fo = next(e for e in same_trace if e["cat"] == "failover")
    assert fo["args"]["from_replica"] == 0


# ---------------------------------------------------------- fleet job spans --


def test_fleet_controller_job_lifecycle_spans(tmp_path):
    from tpuddp.fleet.controller import FleetController
    from tpuddp.fleet.spec import JobSpec

    ctl = FleetController(
        pool_size=2, fleet_dir=str(tmp_path), observability={"tracing": True},
    )
    ctl.submit(JobSpec(
        name="quickie", argv=(sys.executable, "-c", "pass"),
        min_world=1, max_world=1,
    ))
    assert ctl.run_until(
        lambda c: c.training_complete(), poll=0.1, timeout=60
    )
    ctl.shutdown(timeout=30)
    art = os.path.join(str(tmp_path), "trace_fleet.json")
    errors, _ = schema_mod.validate_trace_file(art)
    assert errors == []
    payload = json.load(open(art))
    spans = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    job = next(e for e in spans if e["cat"] == "job")
    assert job["name"] == "job quickie"
    assert job["args"]["state"] == "done" and job["args"]["exit_code"] == 0
    starts = [
        e for e in spans
        if e["cat"] == "action"
        and e["args"].get("parent_id") == job["args"]["span_id"]
    ]
    assert any(e["name"] == "start" for e in starts)


# -------------------------------------- exporter concurrency (satellite 3) --


def test_exporter_never_serves_torn_payloads_under_writer_hammer(tmp_path):
    """Regression for the concurrent-scrape contract: a writer thread
    hammering the recorder + stats + tracer while /metrics, /snapshot and
    /trace are scraped in parallel must yield ONLY complete, parseable
    responses — every prometheus line whole, every JSON document valid."""
    from tpuddp.observability.exporter import MetricsExporter
    from tpuddp.observability.recorder import StepStatsRecorder
    from tpuddp.observability.telemetry import RunTelemetry

    tel = RunTelemetry(writer=None, step_stats_every=4)
    tracer = Tracer("train", capacity=128, process_index=0)
    exporter = MetricsExporter(port=0).start()
    exporter.set_trace_source(tracer.endpoint_payload)
    tel.attach_live(exporter=exporter)
    stop = threading.Event()
    writer_errors = []

    def writer():
        try:
            tel.start_epoch(0)
            i = 0
            while not stop.is_set():
                i += 1
                tel.post_dispatch(1, 8)
                tel.update_live(train_loss=float(i), skipped_steps=i)
                s = tracer.start_span(
                    f"dispatch {i}", trace_mod.KIND_DISPATCH,
                    attrs={"i": i},
                )
                tracer.end_span(s)
        except Exception as e:  # noqa: BLE001 — surfaced below
            writer_errors.append(e)

    scrape_errors = []

    def scraper(path, check):
        try:
            for _ in range(40):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{exporter.port}{path}", timeout=10
                ) as resp:
                    body = resp.read()
                    assert resp.status == 200
                    check(body)
        except Exception as e:  # noqa: BLE001 — surfaced below
            scrape_errors.append((path, e))

    def check_metrics(body):
        text = body.decode()
        assert text.endswith("\n")
        for line in text.splitlines():
            if line and not line.startswith("#"):
                parts = line.rsplit(" ", 1)
                assert len(parts) == 2, f"torn line {line!r}"
                float(parts[1])

    def check_json(body):
        json.loads(body)

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    threads = [
        threading.Thread(target=scraper, args=a, daemon=True)
        for a in (
            ("/metrics", check_metrics),
            ("/snapshot", check_json),
            ("/trace", check_json),
            ("/metrics", check_metrics),
        )
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    stop.set()
    w.join(timeout=30)
    exporter.stop()
    tel.finish()
    assert not writer_errors, writer_errors
    assert not scrape_errors, scrape_errors


def test_exporter_rendering_error_returns_whole_500(tmp_path):
    """A trace source that raises mid-render must produce a COMPLETE 500
    response (Content-Length framed), never a truncated connection the
    client misreads as a torn payload."""
    from tpuddp.observability.exporter import MetricsExporter

    exporter = MetricsExporter(port=0).start()
    exporter.set_trace_source(lambda: (_ for _ in ()).throw(
        RuntimeError("broken trace feeder")
    ))
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/trace", timeout=10
            )
        err = exc_info.value
        assert err.code == 500
        body = err.read().decode()
        assert "broken trace feeder" in body and body.endswith("\n")
        # the endpoint stays up for the next scrape
        health = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/healthz", timeout=10
        ))
        assert health["status"] == "ok"
    finally:
        exporter.stop()


def test_trace_endpoint_404_without_tracing():
    from tpuddp.observability.exporter import MetricsExporter

    exporter = MetricsExporter(port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc_info:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/trace", timeout=10
            )
        assert exc_info.value.code == 404
    finally:
        exporter.stop()


# --------------------------------------------------- flight open-span embed --


def test_flight_dump_embeds_open_spans(tmp_path):
    from tpuddp.observability.flight import FlightRecorder

    flight = FlightRecorder(str(tmp_path), process_index=0)
    tracer = Tracer("train", process_index=0)
    flight.add_context("open_spans", tracer.open_span_summaries)
    root = tracer.start_span("epoch 1", trace_mod.KIND_EPOCH)
    tracer.start_span("dispatch", trace_mod.KIND_DISPATCH, parent=root)
    path = flight.dump("exception")
    payload = json.load(open(path))
    opens = payload["notes"]["open_spans"]
    assert [s["name"] for s in opens] == ["epoch 1", "dispatch"]
    assert schema_mod.validate_flight_payload(payload) == []
    # a raising provider records its failure instead of blocking the dump
    flight2 = FlightRecorder(str(tmp_path / "b"), process_index=0)
    flight2.add_context("boom", lambda: 1 / 0)
    path2 = flight2.dump("exception")
    assert "failed" in json.load(open(path2))["notes"]["boom"]


@pytest.mark.parametrize("workers", [0, 2])
@pytest.mark.parametrize("capacity", [4, 24])
def test_a_pass_that_overflows_the_ring_still_exports_a_valid_payload(
    mesh, capacity, workers
):
    """One pass opens some four spans a batch and three a chunk, so a long
    pass outruns any ring: the oldest spans drop, counted, and what is left
    is still a payload the validator takes, mid-pass (the epoch span open)
    and after it: orphans are allowed exactly when ``dropped`` > 0, and a
    parent here ends after its children, so it is never dropped before them."""
    import jax
    import jax.numpy as jnp

    from tpuddp import optim
    from tpuddp.data import PrefetchLoader, ShardedDataLoader, SyntheticClassification
    from tpuddp.models import ToyMLP
    from tpuddp.nn import CrossEntropyLoss
    from tpuddp.parallel.ddp import DistributedDataParallel
    from tpuddp.training import pipeline as pipe

    ds = SyntheticClassification(n=640, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    if workers:
        loader = PrefetchLoader(loader, workers=workers)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    tracer = Tracer("train", capacity=capacity, process_index=0)
    epoch = tracer.start_span("epoch 0", trace_mod.KIND_EPOCH, tid="train")
    pipe.run_pass(
        ddp, state, loader, 4, ddp.train_step, ddp.train_step_many,
        tracer=tracer, trace_parent=epoch,
    )
    # 10 batches: 11 waits, 1 order, 20 gather + pad, 4 stages with 6 halves,
    # 4 dispatches, 1 readback
    assert tracer.completed == 47 and tracer.dropped == 47 - capacity
    payload = tracer.chrome_payload()  # the epoch span still open: the crash view
    assert schema_mod.validate_trace_payload(payload) == []
    tracer.end_span(epoch)
    payload = tracer.chrome_payload()
    assert payload["tpuddp"]["dropped"] == 48 - capacity
    assert schema_mod.validate_trace_payload(payload) == []
    kept = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert len(kept) == capacity and kept[-1]["name"] == "epoch 0"


# ------------------------------------------------------------ CLI satellites --


def test_inspect_trace_subcommand(tmp_path):
    tracer = Tracer("train", run_dir=str(tmp_path), process_index=0)
    root = tracer.start_span("epoch 0", trace_mod.KIND_EPOCH)
    tracer.end_span(
        tracer.start_span("dispatch", trace_mod.KIND_DISPATCH, parent=root)
    )
    tracer.end_span(root)
    art = tracer.export()
    inspect = os.path.join(REPO, "tools", "tpuddp_inspect.py")
    out = subprocess.run(
        [sys.executable, inspect, "trace", art],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "role=train" in out.stdout and "slowest spans" in out.stdout
    assert "per-name host time" in out.stdout and "ms/span" in out.stdout
    # --validate through content detection too
    assert subprocess.run(
        [sys.executable, inspect, "--validate", art]
    ).returncode == 0
    # a corrupted artifact fails validation with exit 1
    bad = tmp_path / "bad_trace.json"
    payload = json.load(open(art))
    del payload["tpuddp"]["clock_sync"]
    bad.write_text(json.dumps(payload))
    assert subprocess.run(
        [sys.executable, inspect, "trace", str(bad), "--validate"],
        capture_output=True,
    ).returncode == 1
