"""Observability subsystems (SURVEY.md §5, ISSUE 4): typed metrics schema,
step-level telemetry recorder, profiling triggers, NaN guard, loop resume."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp import optim
from tpuddp.data import ShardedDataLoader, SyntheticClassification
from tpuddp.models import ToyMLP
from tpuddp.nn import CrossEntropyLoss
from tpuddp.observability import (
    CommBytesCounter,
    MetricsWriter,
    StepStatsRecorder,
    check_finite,
    json_sanitize,
    percentiles,
    stamp,
)
from tpuddp.observability import profiling as profiling_mod
from tpuddp.observability import schema as schema_mod
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel
from tpuddp.training import checkpoint as ckpt
from tpuddp.training.loop import run_training_loop


def small_run(
    mesh, save_dir, num_epochs=2, start_epoch=0, state=None, n=64, **loop_kw
):
    ds = SyntheticClassification(n=n, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    test_loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    if state is None:
        state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    return ddp, run_training_loop(
        ddp, state, loader, test_loader, save_dir,
        num_epochs=num_epochs, checkpoint_epoch=1, start_epoch=start_epoch,
        log=lambda *_: None, **loop_kw,
    )


def read_history(path):
    return [json.loads(l) for l in open(path).read().splitlines()]


def epoch_rows(records):
    return [r for r in records if r.get("type") == "epoch"]


def test_history_jsonl_written(mesh, tmp_path):
    _, (state, history) = small_run(mesh, str(tmp_path))
    path = tmp_path / "history.jsonl"
    assert path.exists()
    records = read_history(path)
    # typed stream: run_meta header first, then one epoch row per epoch
    assert records[0]["type"] == "run_meta"
    epochs = epoch_rows(records)
    assert len(epochs) == 2
    assert epochs[0]["epoch"] == 0
    assert {"train_loss", "test_loss", "test_accuracy", "epoch_time_s"} <= set(epochs[0])


def test_checkpoints_every_epoch_and_resume(mesh, tmp_path):
    ddp, (state, history) = small_run(mesh, str(tmp_path), num_epochs=2)
    assert os.path.exists(tmp_path / "ckpt_0.npz")
    assert os.path.exists(tmp_path / "ckpt_1.npz")

    # resume: restore newest, continue for one more epoch
    template = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    restored, start = ckpt.restore_latest(str(tmp_path), template)
    assert start == 2
    assert int(restored.step) == int(state.step)
    _, (state2, history2) = small_run(
        mesh, str(tmp_path), num_epochs=3, start_epoch=start, state=restored
    )
    assert [h["epoch"] for h in history2] == [2]
    assert os.path.exists(tmp_path / "ckpt_2.npz")
    # the resumed run appended a SECOND run_meta header before its epochs,
    # and the whole appended file still validates
    records = read_history(tmp_path / "history.jsonl")
    assert [r["type"] for r in records].count("run_meta") == 2
    assert schema_mod.validate_history_records(records) == []


def test_check_finite_guard(monkeypatch):
    check_finite(math.nan, "loss")  # disabled: no raise
    monkeypatch.setenv("TPUDDP_DEBUG_NANS", "1")
    check_finite(1.0, "loss")
    with pytest.raises(FloatingPointError, match="loss"):
        check_finite(math.nan, "loss")
    with pytest.raises(FloatingPointError):
        check_finite(math.inf, "loss")


def test_metrics_writer_none_dir_is_noop():
    w = MetricsWriter(None)
    w.write({"a": 1})  # no crash, nothing written
    assert w.path is None


def test_json_sanitize_nonfinite_to_null():
    """Strict-JSON contract (ISSUE 3 satellite): non-finite floats become
    None recursively; finite values and non-float types pass through."""
    rec = {
        "a": math.nan,
        "b": math.inf,
        "c": -math.inf,
        "d": 1.5,
        "e": "nan",  # strings are never touched
        "f": [math.nan, 2, {"g": math.inf}],
        "h": None,
        "i": 3,
    }
    out = json_sanitize(rec)
    assert out["a"] is None and out["b"] is None and out["c"] is None
    assert out["d"] == 1.5 and out["e"] == "nan" and out["i"] == 3
    assert out["f"] == [None, 2, {"g": None}]
    # and the sanitized record survives the strictest dumps
    json.dumps(out, allow_nan=False)


def test_json_sanitize_numpy_scalars_round_trip():
    """ISSUE 4 satellite: a stray device/numpy scalar in a record fails into
    a clean Python value — never a non-JSON repr, never a bare NaN token."""
    rec = {
        "f32": np.float32(1.5),
        "f64_nan": np.float64("nan"),
        "f32_inf": np.float32("inf"),
        "i64": np.int64(7),
        "i32": np.int32(-3),
        "bool": np.bool_(True),
        "zero_d": np.array(2.25),
        "zero_d_nan": np.array(np.nan),
        "zero_d_int": np.array(9, dtype=np.int64),
        "nested": [np.float32(0.5), {"x": np.int64(1), "y": np.bool_(False)}],
    }
    out = json_sanitize(rec)
    assert out["f32"] == 1.5 and isinstance(out["f32"], float)
    assert out["f64_nan"] is None and out["f32_inf"] is None
    assert out["i64"] == 7 and isinstance(out["i64"], int)
    assert out["i32"] == -3 and out["bool"] is True
    assert out["zero_d"] == 2.25 and out["zero_d_nan"] is None
    assert out["zero_d_int"] == 9
    assert out["nested"] == [0.5, {"x": 1, "y": False}]
    # the full round trip: dumps(strict) -> loads recovers plain values
    back = json.loads(json.dumps(out, allow_nan=False))
    assert back == out
    # jax device scalars fetch as numpy and sanitize the same way
    dev = jax.device_get(jnp.float32(3.5))
    assert json_sanitize({"v": dev})["v"] == 3.5
    json.dumps(json_sanitize({"v": dev}), allow_nan=False)


def test_metrics_writer_emits_null_not_nan(tmp_path, monkeypatch):
    """history.jsonl stays parseable by strict JSON consumers even when an
    epoch's metrics blew up."""
    w = MetricsWriter(str(tmp_path))
    w.write({"epoch": 0, "train_loss": math.nan, "test_loss": math.inf})
    w.close()
    raw = open(os.path.join(str(tmp_path), "history.jsonl")).read()
    assert "NaN" not in raw and "Infinity" not in raw
    row = json.loads(raw, parse_constant=lambda t: pytest.fail(f"bare {t}"))
    assert row["train_loss"] is None and row["test_loss"] is None


def test_metrics_writer_line_buffered_and_synced(tmp_path):
    """ISSUE 4 satellite: every completed write is a whole line on disk
    immediately (line-buffered append), and close() fsyncs."""
    w = MetricsWriter(str(tmp_path))
    w.write({"a": 1})
    # visible to an independent reader BEFORE any flush/close call
    raw = open(os.path.join(str(tmp_path), "history.jsonl")).read()
    assert raw == '{"a": 1}\n'
    w.write({"b": 2})
    w.sync()  # flush + fsync: must not error, file stays whole-line
    raw = open(os.path.join(str(tmp_path), "history.jsonl")).read()
    assert raw.endswith('{"b": 2}\n') and raw.count("\n") == 2
    w.close()
    w.close()  # idempotent


def test_profiler_env_toggle(monkeypatch, tmp_path, mesh):
    monkeypatch.setenv("TPUDDP_PROFILE", str(tmp_path / "trace"))
    small_run(mesh, str(tmp_path / "run"), num_epochs=1)
    # a trace directory with at least one artifact was produced
    trace_dir = tmp_path / "trace"
    assert trace_dir.exists()
    assert any(trace_dir.rglob("*"))


# ------------------------------------------------------------- new: schema --


def test_comm_bytes_counter_zero_is_not_none():
    """ISSUE 4 satellite: bytes_per_update=0 (a hookless/no-grad-comm config)
    is a true zero-byte measurement, not a disabled counter."""
    c = CommBytesCounter(0)
    c.add_updates(7)
    assert c.bytes_per_update == 0
    assert c.total_bytes == 0
    snap = c.snapshot(7)
    assert snap == {
        "grad_comm_bytes_per_update": 0,
        "grad_comm_bytes_total": 0,
        "grad_comm_bytes_epoch": 0,
    }
    # None still degrades to the inert counter (pre-init_state ddp objects)
    inert = CommBytesCounter(None)
    inert.add_updates(3)
    assert inert.total_bytes is None and inert.snapshot(3) == {}


def test_schema_validator_accepts_writer_output(mesh, tmp_path):
    """Every native-driver writer path produces records the validator (the
    same code tpuddp_inspect --validate runs) accepts; run_meta is present
    and FIRST."""
    small_run(mesh, str(tmp_path), num_epochs=2, step_stats_every=2, n=256)
    path = str(tmp_path / "history.jsonl")
    errors, n = schema_mod.validate_history_file(path)
    assert errors == [] and n >= 3
    records = read_history(path)
    assert records[0]["type"] == "run_meta"
    types = {r["type"] for r in records}
    assert {"run_meta", "epoch", "step_stats"} <= types
    meta = records[0]
    assert meta["world_size"] == 8 and meta["mesh_shape"] == {"data": 8}
    assert meta["jax_version"] and meta["tpuddp_version"]
    assert meta["api"] == "native"


_BARRIER = {"enabled": False, "segments": None, "reason": "barrier step"}


def test_run_meta_records_the_one_step_program(mesh, tmp_path):
    """A run's header says what its exchange is, and since the step is one
    program that is a constant: from the wrap, through the epoch driver, into
    ``run_meta.comm.overlap``; the managed wrapper records the same."""
    from tpuddp.accelerate import Accelerator

    ddp, _ = small_run(mesh, str(tmp_path), num_epochs=1)
    assert ddp.comm_overlap_meta == _BARRIER
    meta = read_history(tmp_path / "history.jsonl")[0]
    assert meta["type"] == "run_meta" and meta["comm"] == {"overlap": _BARRIER}
    assert Accelerator().comm_overlap_meta == _BARRIER


@pytest.mark.parametrize("comm,valid", [
    ({"overlap": _BARRIER}, True),
    # a history written while the segmented step existed still reads
    ({"overlap": {"enabled": True, "segments": 3, "reason": None}}, True),
    (None, True),  # meshless and serving headers
    ({"something": 1}, False),  # a v10 header's comm block needs its overlap member
    (7, False),
], ids=["barrier", "stored_segmented", "null", "no_overlap_member", "not_a_block"])
def test_schema_v10_comm_block(comm, valid):
    rec = schema_mod.make_run_meta(world_size=8, comm=comm)
    assert (schema_mod.validate_record(rec) == []) == valid


def test_schema_rejects_unknown_type_and_missing_header(tmp_path):
    good_meta = schema_mod.make_run_meta(comm_hook="none")
    good_event = stamp("event", {"event": "x"})
    # unknown type
    errs = schema_mod.validate_history_records(
        [good_meta, {"type": "telemetry", "schema_version": 1}]
    )
    assert any("unknown type" in e for e in errs)
    # header missing / not first
    errs = schema_mod.validate_history_records([good_event, good_meta])
    assert any("must start with a run_meta" in e for e in errs)
    # empty file
    assert any("empty" in e for e in schema_mod.validate_history_records([]))
    # missing required epoch fields
    errs = schema_mod.validate_history_records(
        [good_meta, stamp("epoch", {"epoch": 0})]
    )
    assert any("missing required field" in e for e in errs)
    # newer schema version than this reader
    errs = schema_mod.validate_history_records(
        [dict(good_meta, schema_version=schema_mod.SCHEMA_VERSION + 1)]
    )
    assert any("newer" in e for e in errs)
    # a valid stream has no errors
    assert schema_mod.validate_history_records([good_meta, good_event]) == []
    # stamp refuses unknown types at write time too
    with pytest.raises(ValueError, match="unknown record type"):
        stamp("metrics", {})
    # non-strict JSON on disk is a validation error
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps(good_meta) + "\n{\"type\": \"event\", \"schema_version\": 1, \"event\": \"e\", \"v\": NaN}\n")
    errors, _ = schema_mod.validate_history_file(str(p))
    assert any("invalid JSON" in e for e in errors)


def test_schema_v4_requires_comm_topology(tmp_path):
    """Comm-compression-v2 schema bump: a run_meta stamped at v4+ without
    ``comm_topology`` is drift and must be rejected; older headers (v3 and
    below, which predate the field) keep validating at their own version —
    and the shared make_run_meta always carries the field."""
    meta = schema_mod.make_run_meta(comm_hook="int8_ef", comm_topology="flat")
    assert meta["schema_version"] >= 4
    assert meta["comm_topology"] == "flat"
    assert schema_mod.validate_history_records([meta]) == []
    # null is legal (e.g. serving headers have no gradient comm)...
    assert schema_mod.validate_history_records(
        [schema_mod.make_run_meta(comm_hook=None)]
    ) == []
    # ...but ABSENCE at v4 is drift
    dropped = {k: v for k, v in meta.items() if k != "comm_topology"}
    errs = schema_mod.validate_history_records([dropped])
    assert any("comm_topology" in e for e in errs), errs
    # a v3 header without the field stays valid (its version's contract)
    v3 = dict(dropped, schema_version=3)
    assert schema_mod.validate_history_records([v3]) == []
    # the drift also fails through the file validator (the gate's path)
    p = tmp_path / "drift.jsonl"
    p.write_text(json.dumps(dropped) + "\n")
    errors, _ = schema_mod.validate_history_file(str(p))
    assert any("comm_topology" in e for e in errors)


def test_inspect_cli_validates_and_summarizes(mesh, tmp_path):
    """tools/tpuddp_inspect.py end to end: --validate accepts a real run's
    history, the summary renders, and a corrupted stream is refused."""
    import subprocess
    import sys

    small_run(mesh, str(tmp_path), num_epochs=1)
    path = str(tmp_path / "history.jsonl")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "tpuddp_inspect.py")
    ok = subprocess.run(
        [sys.executable, tool, "--validate", path],
        capture_output=True, text=True, cwd=repo,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "OK:" in ok.stdout
    summary = subprocess.run(
        [sys.executable, tool, path], capture_output=True, text=True, cwd=repo,
    )
    assert summary.returncode == 0, summary.stdout + summary.stderr
    assert "run_meta" in summary.stdout and "epochs (1)" in summary.stdout

    bad = tmp_path / "drifted.jsonl"
    with open(path) as f:
        lines = f.read().splitlines()
    lines.append(json.dumps({"type": "mystery", "schema_version": 1}))
    bad.write_text("\n".join(lines) + "\n")
    refused = subprocess.run(
        [sys.executable, tool, "--validate", str(bad)],
        capture_output=True, text=True, cwd=repo,
    )
    assert refused.returncode == 1
    assert "unknown type" in refused.stderr


def test_bench_payload_validator(tmp_path):
    payload = {
        "metric": "m", "value": 1.0, "unit": "u", "vs_baseline": 1.0,
        "device": "cpu",
        "configs": {"row": {"samples_per_sec_per_chip": 1.0, "ms_per_step": 2.0}},
    }
    assert schema_mod.validate_bench_payload(payload) == []
    p = tmp_path / "bench_results.json"
    p.write_text(json.dumps(payload, indent=2))
    errors, n = schema_mod.validate_bench_file(str(p))
    assert errors == [] and n == 1
    # a decode row satisfies the rate requirement with tokens_per_sec alone
    tok = dict(payload)
    tok["configs"] = {"row": {"tokens_per_sec": 9.0, "ms_per_step": 2.0}}
    assert schema_mod.validate_bench_payload(tok) == []
    bad = dict(payload)
    bad["configs"] = {"row": {"ms_per_step": 2.0}}
    assert any("needs one of" in e for e in schema_mod.validate_bench_payload(bad))
    bad["configs"] = {"row": {"samples_per_sec_per_chip": 1.0}}
    assert any("missing field" in e for e in schema_mod.validate_bench_payload(bad))
    del bad["metric"]
    assert any("'metric'" in e for e in schema_mod.validate_bench_payload(bad))


# ------------------------------------------------- new: the step recorder --


def test_step_stats_percentiles_match_synthetic_sequence(monkeypatch):
    """Percentile correctness against a known timing sequence: drive the
    recorder with a fake clock whose laps are exactly 1..100 ms and check the
    emitted fields against numpy's own percentiles of that sequence."""
    laps_ms = list(range(1, 101))  # 1, 2, ..., 100 ms — one step per lap
    clock = {"t": 0.0}

    def fake_clock():
        return clock["t"]

    import tpuddp.observability.recorder as rec_mod

    monkeypatch.setattr(rec_mod.time, "perf_counter", fake_clock)
    written = []

    class W:
        def write(self, r):
            written.append(r)

    r = StepStatsRecorder(writer=W(), window=50, peak_flops=None)
    r.start_epoch(0)
    for ms in laps_ms:
        clock["t"] += ms / 1e3
        r.record(1, 8)
    fields = r.epoch_summary()

    expect = np.asarray(laps_ms, np.float64)
    assert fields["train_steps"] == 100
    assert fields["step_time_ms_p50"] == pytest.approx(np.percentile(expect, 50), rel=1e-6)
    assert fields["step_time_ms_p95"] == pytest.approx(np.percentile(expect, 95), rel=1e-6)
    assert fields["step_time_ms_p99"] == pytest.approx(np.percentile(expect, 99), rel=1e-6)
    assert fields["step_time_ms_max"] == pytest.approx(100.0, rel=1e-6)
    # two window rows of 50 steps each, each with ITS OWN slice's percentiles
    assert [w["steps"] for w in written] == [50, 50]
    assert written[0]["step_start"] == 0 and written[1]["step_start"] == 50
    first = np.asarray(laps_ms[:50], np.float64)
    assert written[0]["step_time_ms_p50"] == pytest.approx(
        np.percentile(first, 50), rel=1e-6
    )
    assert written[0]["step_time_ms_max"] == pytest.approx(50.0, rel=1e-6)
    # throughput: 100 steps x 8 samples over 5.050 s (writer rounds to 2dp)
    assert fields["train_samples_per_sec"] == pytest.approx(
        800 / (sum(laps_ms) / 1e3), abs=0.01
    )
    # fused dispatches split their lap evenly across n_steps
    r2 = StepStatsRecorder(window=0, peak_flops=None)
    r2.start_epoch(0)
    clock["t"] += 0.064
    r2.record(64, 64)
    f2 = r2.epoch_summary()
    assert f2["train_steps"] == 64
    assert f2["step_time_ms_p50"] == pytest.approx(1.0, rel=1e-6)


def test_step_stats_mfu_fields():
    """MFU = flops / step-time / peak at the matching percentile; null
    without a known peak."""
    import tpuddp.observability.recorder as rec_mod

    fields = rec_mod.step_time_fields(
        [0.01, 0.01, 0.02], flops_per_step=1e9, peak_flops=1e12
    )
    # p50 step time is 10 ms -> 1e9 / 0.01 / 1e12 = 0.1
    assert fields["mfu_p50"] == pytest.approx(0.1, rel=1e-3)
    assert fields["mfu_p95"] is not None and fields["mfu_p95"] < fields["mfu_p50"]
    null = rec_mod.step_time_fields([0.01], flops_per_step=None, peak_flops=1e12)
    assert null["mfu_p50"] is None
    assert rec_mod.percentiles([]) == {
        "p50": None, "p95": None, "p99": None, "max": None
    }


def test_percentiles_helper_shared_with_bench():
    pct = percentiles([0.001, 0.002, 0.003, 0.010])
    assert pct["max"] == pytest.approx(0.010)
    assert pct["p50"] == pytest.approx(np.percentile([1, 2, 3, 10], 50) / 1e3)


def test_epoch_rows_carry_step_fields_and_no_recompilation(mesh, tmp_path):
    """ISSUE 4 acceptance: telemetry-on epoch rows carry step-time
    percentiles + MFU fields, the step program is HLO-identical with
    telemetry on or off, and no recompilation happens across epochs."""
    # scan_steps pinned: "auto" runs a pass of four batches batch by batch
    # (a quarter of the pass a dispatch), and this test wants the scan step
    ddp, (state, history) = small_run(
        mesh, str(tmp_path), num_epochs=2, step_stats_every=2, n=256,
        scan_steps=4,
    )
    for row in history:
        assert row["type"] == "epoch"
        assert row["step_time_ms_p50"] > 0
        assert row["step_time_ms_p95"] >= row["step_time_ms_p50"]
        assert row["train_steps"] == 4  # 256 samples / 64 global batch
        assert "mfu_p50" in row  # null on CPU (unknown peak), but present
    # one compiled scan step object across both epochs — telemetry added no
    # retrace (the guard test's no-recompilation contract, held here too)
    jitted = ddp._scan_step
    assert jitted is not None

    def lower_text(d, st):
        b = d.shard((
            np.zeros((64, 8, 8, 3), np.float32),
            np.zeros((64,), np.int32),
            np.ones((64,), np.float32),
        ))
        return jax.jit(lambda s, x: d.train_step(s, x)).lower(st, b).as_text()

    # telemetry is host-side only: the driven wrap's single-step program is
    # byte-identical to a fresh, never-telemetered build's
    fresh = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    fresh_state = fresh.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    assert lower_text(ddp, fresh_state) == lower_text(fresh, fresh_state)


def test_flops_probe_is_per_chip(cpu_devices):
    """The probe reads the COMPILED program, which is the per-device one:
    the same per-chip batch gives the same figure on 1 and on 8 devices (it
    is not a whole-program figure to divide by the world), and a probe that
    cannot resolve returns None."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpuddp.observability import estimate_step_flops

    def flops(devices):
        sh = NamedSharding(make_mesh(devices), P("data"))
        x = jax.ShapeDtypeStruct((16 * len(devices), 64), jnp.float32, sharding=sh)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        return estimate_step_flops(lambda: jax.jit(lambda a, b: a @ b).lower(x, w))

    one, eight = flops(cpu_devices[:1]), flops(cpu_devices)
    assert one == pytest.approx(2 * 16 * 64 * 64, rel=0.1)
    assert eight == pytest.approx(one, rel=0.1)
    assert estimate_step_flops(lambda: 1 / 0) is None


def test_mfu_populates_when_chip_peak_known(mesh, tmp_path, monkeypatch):
    """End-to-end MFU plumbing: with the device kind in the peak table (as
    on a real TPU), the FLOPs probe resolves and the epoch row's MFU fields
    are real numbers — on the CPU world they are null only because 'cpu'
    has no table entry, so teach the table 'cpu' and assert the full path."""
    import tpuddp.observability.recorder as rec_mod

    monkeypatch.setitem(rec_mod.PEAK_FLOPS, "cpu", 1e9)
    _, (state, history) = small_run(mesh, str(tmp_path), num_epochs=1, n=256)
    row = history[0]
    assert row["mfu_p50"] is not None and row["mfu_p50"] > 0
    assert row["mfu_p95"] is not None
    records = read_history(tmp_path / "history.jsonl")
    assert epoch_rows(records)[0]["mfu_p50"] == row["mfu_p50"]
    assert records[0]["device_kind"] == "cpu"  # the MESH device's kind


def test_step_stats_window_rows_inside_epoch(mesh, tmp_path):
    """step_stats_every=N emits intra-epoch rows at the N-step cadence with
    the window's own step range."""
    small_run(
        mesh, str(tmp_path), num_epochs=1, step_stats_every=2, scan_steps=2,
        n=512,
    )
    records = read_history(tmp_path / "history.jsonl")
    windows = [r for r in records if r["type"] == "step_stats"]
    # 512 samples / 64 global batch = 8 steps -> 4 windows of 2
    assert len(windows) == 4
    assert [w["step_start"] for w in windows] == [0, 2, 4, 6]
    assert all(w["steps"] == 2 and w["epoch"] == 0 for w in windows)
    assert all(w["samples_per_sec"] > 0 for w in windows)
    # cadence off -> no window rows, epoch percentiles still present
    small_run(mesh, str(tmp_path / "off"), num_epochs=1, n=512)
    records = read_history(tmp_path / "off" / "history.jsonl")
    assert not any(r["type"] == "step_stats" for r in records)
    assert epoch_rows(records)[0]["step_time_ms_p50"] is not None


# ------------------------------------------------- new: profiling triggers --


def test_profile_steps_env_parsing():
    assert profiling_mod.parse_profile_steps("10:20") == (10, 20)
    assert profiling_mod.parse_profile_steps("") is None
    for bad in ("10", "a:b", "5:5", "-1:3", "7:2"):
        with pytest.raises(ValueError):
            profiling_mod.parse_profile_steps(bad)


def test_profile_steps_window_trace(monkeypatch, tmp_path, mesh):
    """TPUDDP_PROFILE_STEPS=<a>:<b> produces a trace dir named for exactly
    the requested window, with artifacts, and releases the trace latch."""
    monkeypatch.setenv("TPUDDP_PROFILE_STEPS", "2:4")
    profiling_mod.reset_profiling_state()
    try:
        small_run(mesh, str(tmp_path), num_epochs=1, scan_steps=1, n=512)
    finally:
        profiling_mod.reset_profiling_state()
    trace_dir = tmp_path / "trace_steps_2_4"
    assert trace_dir.is_dir()
    assert any(trace_dir.rglob("*"))
    assert not profiling_mod._profiling["active"]
    # the first-epoch mode stands down while the step window owns the trace
    monkeypatch.setenv("TPUDDP_PROFILE", str(tmp_path / "unused"))
    assert profiling_mod.maybe_start_profiler(str(tmp_path)) is False


def test_sigusr1_epoch_trace(monkeypatch, tmp_path, mesh):
    """A SIGUSR1 received mid-run traces the NEXT epoch into
    trace_sigusr1_e<N> and records a profile_epoch event."""
    profiling_mod.reset_profiling_state()
    profiling_mod._sigusr1["requested"] = True  # as the signal handler would
    try:
        small_run(mesh, str(tmp_path), num_epochs=1)
    finally:
        profiling_mod.reset_profiling_state()
    trace_dir = tmp_path / "trace_sigusr1_e0"
    assert trace_dir.is_dir()
    assert any(trace_dir.rglob("*"))
    records = read_history(tmp_path / "history.jsonl")
    assert any(
        r.get("event") == "profile_epoch" and r["epoch"] == 0 for r in records
    )
    errors, _ = schema_mod.validate_history_file(str(tmp_path / "history.jsonl"))
    assert errors == []


def test_managed_fused_profile_window_covers_queued_group(
    monkeypatch, tmp_path, mesh
):
    """A TPUDDP_PROFILE_STEPS window falling INSIDE a not-yet-flushed fused
    group must still be traced: the managed driver arms the profiler with
    the queued-group size, so the flush carrying the window is captured."""
    import train_accelerate as ta
    from tpuddp import nn as tnn
    from tpuddp import optim as topt
    from tpuddp.accelerate import Accelerator
    from tpuddp.data import DataLoader

    monkeypatch.setenv("TPUDDP_PROFILE_STEPS", "2:3")  # inside group [0, 4)
    profiling_mod.reset_profiling_state()
    ds = SyntheticClassification(n=256, shape=(8, 8, 3), seed=0)
    acc = Accelerator(mesh=mesh, seed=0, fuse_steps=4)
    model, opt, loader = acc.prepare(
        ToyMLP(hidden=(16,)), topt.Adam(1e-2),
        DataLoader(ds, batch_size=4, shuffle=True),
    )
    test_loader = DataLoader(
        SyntheticClassification(n=64, shape=(8, 8, 3), seed=1), batch_size=32
    )
    try:
        ta.run_training_loop(
            model, loader, test_loader, tnn.CrossEntropyLoss(), opt,
            str(tmp_path), acc, jax.jit(lambda r, i, x: x),
            jax.jit(lambda x: x), num_epochs=1, checkpoint_epoch=5,
            deferred_metrics=True,
        )
    finally:
        profiling_mod.reset_profiling_state()
    trace_dir = tmp_path / "trace_steps_2_3"
    assert trace_dir.is_dir(), "window inside a fused group was not traced"
    assert any(trace_dir.rglob("*"))


def test_watchdog_stale_event_headers_empty_history(tmp_path):
    """A watchdog firing before ANY driver wrote run_meta (process 0 died in
    rendezvous) must still leave a history that validates: it prepends a
    minimal header to its fsync'd stale-peer event."""
    from tpuddp.resilience import watchdog as wd

    writer = MetricsWriter(str(tmp_path), main_only=False)
    fired = []
    w = wd.Watchdog(
        str(tmp_path / "hb"), process_id=1, num_processes=2, timeout=0.1,
        action=lambda stale: fired.append(stale), event_writer=writer,
    )
    w._fire([(0, 5.0)])
    assert fired
    records = read_history(tmp_path / "history.jsonl")
    assert records[0]["type"] == "run_meta" and records[0]["api"] == "watchdog"
    ev = records[1]
    assert ev["event"] == "watchdog_stale"
    assert ev["stale_peers"] == [{"process": 0, "lag_s": 5.0}]
    assert schema_mod.validate_history_records(records) == []
    # with a header already present (the normal mid-training case), no
    # second run_meta is injected
    w._fire([(0, 6.0)])
    records = read_history(tmp_path / "history.jsonl")
    assert [r["type"] for r in records].count("run_meta") == 1


def test_sigusr1_handler_installs_and_fires():
    import signal

    assert profiling_mod.install_sigusr1_trigger() is True
    profiling_mod._sigusr1["requested"] = False
    os.kill(os.getpid(), signal.SIGUSR1)
    # the handler runs on the main thread at the next bytecode boundary
    deadline = 200
    while not profiling_mod._sigusr1["requested"] and deadline:
        deadline -= 1
    assert profiling_mod.consume_sigusr1_request() is True
    assert profiling_mod.consume_sigusr1_request() is False


# ------------------------------------------------ ISSUE 10: live telemetry --


def _scrape(port: int, path: str = "/metrics") -> str:
    import urllib.request

    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ).read().decode()


def _prom_value(text: str, name: str, labels: str = ""):
    needle = f"{name}{labels} " if labels else f"{name} "
    for line in text.splitlines():
        if line.startswith(needle):
            return float(line.split()[-1])
    return None


def test_observability_config_resolution():
    from tpuddp import config as cfg_lib

    # defaults: exporter OFF, aggregation + flight recorder on
    cfg = cfg_lib.resolve_observability(None)
    assert cfg["exporter"] is False
    assert cfg["aggregate"] is True and cfg["flight_recorder"] is True
    # false turns the whole plane off
    off = cfg_lib.resolve_observability(False)
    assert not off["exporter"] and not off["aggregate"]
    assert not off["flight_recorder"]
    # the exporter dict shorthand expands to host/port knobs
    cfg = cfg_lib.resolve_observability(
        {"exporter": {"host": "0.0.0.0", "port": 9100}}
    )
    assert cfg["exporter"] is True
    assert cfg["exporter_host"] == "0.0.0.0" and cfg["exporter_port"] == 9100
    # unknown keys refused, both levels
    with pytest.raises(ValueError, match="unknown observability key"):
        cfg_lib.resolve_observability({"straggler_ration": 2.0})
    with pytest.raises(ValueError, match="observability.exporter"):
        cfg_lib.resolve_observability({"exporter": {"prot": 1}})


def test_exporter_ephemeral_bind_and_endpoints(tmp_path):
    """Port-0 binds ephemerally (two exporters coexist), the port file is
    published and removed, and all three endpoints answer."""
    from tpuddp.observability.exporter import (
        MetricsExporter, PORT_FILENAME, counter,
    )

    a = MetricsExporter(port=0, run_dir=str(tmp_path)).start()
    b = MetricsExporter(port=0).start()
    try:
        assert a.port and b.port and a.port != b.port
        port_file = tmp_path / PORT_FILENAME
        assert int(port_file.read_text().splitlines()[0]) == a.port
        a.register_source("t", lambda: {"x_total": counter(3, "x")})
        assert _prom_value(_scrape(a.port), "tpuddp_x_total") == 3
        health = json.loads(_scrape(a.port, "/healthz"))
        assert health["status"] == "ok" and health["uptime_s"] >= 0
        snap = json.loads(_scrape(a.port, "/snapshot"))
        assert snap["series"]["x_total"]["value"] == 3
        with pytest.raises(Exception):  # 404 on unknown paths
            _scrape(a.port, "/nope")
        # a failing source is skipped, the scrape survives
        def boom():
            raise RuntimeError("broken feeder")
        a.register_source("bad", boom)
        assert "tpuddp_x_total 3" in _scrape(a.port)
    finally:
        a.stop()
        b.stop()
    assert not (tmp_path / PORT_FILENAME).exists()
    # stop is idempotent
    a.stop()


def test_exporter_scrape_matches_recorder_state(monkeypatch):
    """ISSUE 10 acceptance (training side): /metrics values equal the
    recorder's last flushed window exactly — the live plane can never
    disagree with history.jsonl beyond one window."""
    import tpuddp.observability.recorder as rec_mod
    from tpuddp.observability.exporter import MetricsExporter
    from tpuddp.observability.telemetry import RunTelemetry

    clock = {"t": 0.0}
    monkeypatch.setattr(rec_mod.time, "perf_counter", lambda: clock["t"])
    tel = RunTelemetry(writer=None, step_stats_every=4)
    exporter = MetricsExporter(port=0).start()
    try:
        tel.attach_live(exporter=exporter)
        tel.start_epoch(0)
        for ms in (1, 2, 3, 4):  # one window of laps 1..4 ms
            clock["t"] += ms / 1e3
            tel.post_dispatch(1, 8)
        tel.update_live(skipped_steps=2, train_loss=0.5)
        text = _scrape(exporter.port)
        win = tel.recorder.last_window
        assert win is not None
        assert _prom_value(text, "tpuddp_train_steps_total") == 4
        assert _prom_value(text, "tpuddp_train_samples_total") == 32
        assert _prom_value(
            text, "tpuddp_step_time_ms", '{quantile="0.5"}'
        ) == pytest.approx(win["step_time_ms_p50"])
        assert _prom_value(
            text, "tpuddp_step_time_ms", '{quantile="0.99"}'
        ) == pytest.approx(win["step_time_ms_p99"])
        assert _prom_value(
            text, "tpuddp_train_samples_per_sec"
        ) == pytest.approx(win["samples_per_sec"])
        assert _prom_value(text, "tpuddp_skipped_steps") == 2
        assert _prom_value(text, "tpuddp_train_loss") == 0.5
    finally:
        exporter.stop()
        tel.finish()


def test_loop_live_plane_on_records_port_and_hlo_identical(mesh, tmp_path):
    """The whole plane on (exporter + flight + aggregation enabled) changes
    ZERO device semantics: run_meta records the bound endpoint, the step
    program lowers byte-identical to a never-telemetered build, and a clean
    exit leaves no flight recording and no port file."""
    ddp, (state, history) = small_run(
        mesh, str(tmp_path), num_epochs=1, step_stats_every=2, n=256,
        observability={"exporter": True, "exporter_port": 0},
    )
    records = read_history(tmp_path / "history.jsonl")
    meta = records[0]
    obs = meta["observability"]
    assert obs["exporter"]["port"] > 0
    assert obs["flight_recorder"] == {"capacity": 64}
    assert obs["straggler_ratio"] == 1.5 and obs["straggler_windows"] == 3
    assert schema_mod.validate_history_records(records) == []
    # clean exit: endpoint torn down, no crash artifact
    assert not (tmp_path / "exporter.port").exists()
    assert not list(tmp_path.glob("flightrec_*.json"))

    def lower_text(d, st):
        b = d.shard((
            np.zeros((64, 8, 8, 3), np.float32),
            np.zeros((64,), np.int32),
            np.ones((64,), np.float32),
        ))
        return jax.jit(lambda s, x: d.train_step(s, x)).lower(st, b).as_text()

    fresh = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    fresh_state = fresh.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    assert lower_text(ddp, fresh_state) == lower_text(fresh, fresh_state)


def test_serving_engine_live_scrape_matches_stats(mesh, tmp_path):
    """Serving acceptance: a live /metrics scrape during traffic reports the
    engine's own counters and the LAST flushed serving_stats window; drain
    tears the endpoint down."""
    import urllib.error

    from tpuddp.serving import ServingEngine

    cfg = {
        "model": "toy_mlp", "num_classes": 10, "input_shape": [8, 8, 3],
        "checkpoint_dir": None, "checkpoint_prefix": "auto",
        "num_replicas": 2, "max_batch_size": 8, "max_queue_depth": 64,
        "per_tenant_quota": None, "batch_timeout_ms": 0.5,
        "stats_window": 8, "unhealthy_after": 3, "seed": 0,
    }
    engine = ServingEngine.from_config(
        cfg, out_dir=str(tmp_path),
        observability={"exporter": True, "exporter_port": 0},
    )
    engine.start()
    port = engine.exporter.port
    try:
        rng = np.random.RandomState(0)
        results = [
            engine.submit(f"tenant{i % 2}", rng.randn(2, 8, 8, 3).astype(np.float32))
            for i in range(24)
        ]
        for r in results:
            r.result(timeout=120)
        text = _scrape(port)
        assert _prom_value(text, "tpuddp_serving_completed_total") == 24
        assert _prom_value(text, "tpuddp_serving_requests_total") == 24
        assert _prom_value(text, "tpuddp_serving_replicas_healthy") == 2
        win = engine.stats.last_window
        assert win is not None  # 24 completed / window 8 -> windows flushed
        assert _prom_value(
            text, "tpuddp_serving_e2e_ms", '{quantile="0.5"}'
        ) == pytest.approx(win["e2e_ms_p50"])
        assert _prom_value(
            text, "tpuddp_serving_throughput_rps"
        ) == pytest.approx(win["throughput_rps"])
        assert _prom_value(
            text, "tpuddp_serving_tenant_completed_total", '{tenant="tenant0"}'
        ) == 12
        # and the flushed history agrees with the scrape (same record)
        records = read_history(tmp_path / "history.jsonl")
        flushed = [r for r in records if r["type"] == "serving_stats"]
        assert flushed[-1]["e2e_ms_p50"] == win["e2e_ms_p50"]
    finally:
        engine.drain()
    with pytest.raises(Exception):  # endpoint down after drain
        _scrape(port, "/healthz")
    errors, _ = schema_mod.validate_history_file(str(tmp_path / "history.jsonl"))
    assert errors == []


# ---------------------------------------------- shard channel + aggregator --


def test_heartbeat_shard_channel_round_trip(tmp_path):
    """The heartbeat file carries the telemetry shard on line 2; liveness
    reads (line 1) are indifferent, and a torn JSON line is skipped with a
    warning, never an exception."""
    from tpuddp.observability import aggregate
    from tpuddp.resilience import watchdog

    shard = {"window_index": 3, "step_time_ms_p50": 1.5, "skipped_steps": 0}
    aggregate.publish_shard(str(tmp_path), 1, shard)
    assert watchdog.read_heartbeat(str(tmp_path), 1) is not None
    assert aggregate.read_shard(str(tmp_path), 1) == shard
    # payload-free beats still read as alive, shard None
    watchdog.write_heartbeat(str(tmp_path), 2, now=123.0)
    assert watchdog.read_heartbeat(str(tmp_path), 2) == 123.0
    assert aggregate.read_shard(str(tmp_path), 2) is None
    # a torn mid-write line: liveness survives, shard read returns None
    with open(tmp_path / "hb_3", "w") as f:
        f.write("456.0\n{\"window_index\": 9, \"step_time")  # torn
    assert watchdog.read_heartbeat(str(tmp_path), 3) == 456.0
    assert aggregate.read_shard(str(tmp_path), 3) is None
    # absent peer
    assert aggregate.read_shard(str(tmp_path), 7) is None


def test_purge_stale_peers_preserves_live_shards(tmp_path):
    """ISSUE 10 satellite: the elastic-resume purge removes ONLY the old
    larger world's hb files — live peers' shard payloads survive."""
    from tpuddp.observability import aggregate
    from tpuddp.resilience import watchdog

    for pid in range(4):
        aggregate.publish_shard(
            str(tmp_path), pid, {"window_index": pid, "step_time_ms_p50": 1.0}
        )
    removed = watchdog.purge_stale_peers(str(tmp_path), 2)
    assert removed == 2
    assert not os.path.exists(tmp_path / "hb_2")
    assert not os.path.exists(tmp_path / "hb_3")
    for pid in (0, 1):  # the live world keeps both liveness AND shards
        assert watchdog.read_heartbeat(str(tmp_path), pid) is not None
        assert aggregate.read_shard(str(tmp_path), pid)["window_index"] == pid


def _shard_dir(tmp_path, p50s, window=1):
    from tpuddp.observability import aggregate

    for pid, p50 in enumerate(p50s):
        aggregate.publish_shard(str(tmp_path), pid, {
            "window_index": window, "epoch": 0, "step": window * 4,
            "step_time_ms_p50": p50, "host_stall_ms": 1.0,
            "skipped_steps": 0, "samples_per_sec": 100.0,
        })


def test_pod_aggregator_percentiles_match_numpy(tmp_path):
    from tpuddp.observability.aggregate import PodAggregator

    p50s = [1.0, 2.0, 3.0, 10.0]
    _shard_dir(tmp_path, p50s)
    agg = PodAggregator(str(tmp_path), 4)
    merged = agg.update()
    assert merged["hosts_reporting"] == 4
    assert merged["pod_step_time_ms_p50"] == pytest.approx(
        np.median(p50s), rel=1e-6
    )
    assert merged["pod_step_time_ms_p95"] == pytest.approx(
        np.percentile(p50s, 95), rel=1e-6
    )
    assert merged["pod_step_time_ms_max"] == 10.0
    assert merged["pod_host_stall_ms"] == pytest.approx(4.0)
    assert merged["hosts"]["3"]["step_time_ms_p50"] == 10.0
    # empty dir -> None, never a crash
    empty = PodAggregator(str(tmp_path / "none"), 2)
    assert empty.update() is None


def test_straggler_fires_at_exact_ratio_and_window(tmp_path):
    """The detector's contract: a host over ratio x pod-median for EXACTLY
    `straggler_windows` consecutive fresh windows produces exactly ONE typed
    event naming it; uniform hosts never fire; a recovered host can fire
    again on relapse; a stalled (non-fresh) shard never extends a streak."""
    from tpuddp.observability.aggregate import PodAggregator

    written = []

    class W:
        def write(self, r):
            written.append(r)

    agg = PodAggregator(
        str(tmp_path), 4, writer=W(),
        straggler_ratio=1.5, straggler_windows=3,
    )
    # uniform pod: many windows, zero events
    for w in range(1, 5):
        _shard_dir(tmp_path, [1.0, 1.0, 1.1, 0.9], window=w)
        agg.update()
    assert written == [] and agg.straggler_events == 0

    # host 3 goes slow: 2.0 vs median ~1.0 -> ratio 2.0 > 1.5
    for w in range(5, 8):  # exactly 3 consecutive slow fresh windows
        _shard_dir(tmp_path, [1.0, 1.0, 1.0, 2.0], window=w)
        merged = agg.update()
        if w < 7:
            assert written == []  # not yet: needs 3 consecutive
    assert len(written) == 1
    ev = written[0]
    assert ev["type"] == "event" and ev["event"] == "straggler"
    assert ev["host"] == 3 and ev["windows"] == 3
    assert ev["ratio"] == pytest.approx(2.0)
    assert merged["stragglers"] == [3]
    # still slow: the SAME episode never re-fires
    _shard_dir(tmp_path, [1.0, 1.0, 1.0, 2.0], window=8)
    agg.update()
    assert len(written) == 1
    # a stalled shard (same window index) cannot extend/refire either
    agg2 = PodAggregator(
        str(tmp_path / "stall"), 2, writer=W(),
        straggler_ratio=1.5, straggler_windows=2,
    )
    os.makedirs(tmp_path / "stall", exist_ok=True)
    from tpuddp.observability import aggregate as agg_mod

    for pid, p50 in ((0, 1.0), (1, 5.0)):
        agg_mod.publish_shard(str(tmp_path / "stall"), pid, {
            "window_index": 1, "step_time_ms_p50": p50,
        })
    before = len(written)
    for _ in range(5):  # window never advances -> streak frozen at 1
        agg2.update()
    assert len(written) == before
    # recovery then relapse: a SECOND event is legitimate
    _shard_dir(tmp_path, [1.0, 1.0, 1.0, 1.0], window=9)
    agg.update()  # recovered
    for w in range(10, 13):
        _shard_dir(tmp_path, [1.0, 1.0, 1.0, 3.0], window=w)
        agg.update()
    assert len(written) == 2 and written[1]["host"] == 3
    # knob validation
    with pytest.raises(ValueError, match="straggler_ratio"):
        PodAggregator(str(tmp_path), 2, straggler_ratio=1.0)
    with pytest.raises(ValueError, match="straggler_windows"):
        PodAggregator(str(tmp_path), 2, straggler_windows=0)


# -------------------------------------------------------- flight recorder --


def test_flight_ring_bound_and_dump_validates(tmp_path):
    from tpuddp.observability.flight import FlightRecorder

    rec = FlightRecorder(str(tmp_path), capacity=3, process_index=0)
    rec.observe(schema_mod.make_run_meta(comm_hook="none"))
    for i in range(7):
        rec.observe(stamp("step_stats", {
            "epoch": 0, "step_start": i * 2, "steps": 2,
            "step_time_ms_p50": 1.0, "step_time_ms_p95": 1.0,
            "step_time_ms_p99": 1.0, "step_time_ms_max": 1.0,
            "samples_per_sec": 10.0, "host_stall_ms": 0.0,
            "inflight_depth": 0, "staging_queue_depth": 0,
        }))
    rec.observe(stamp("event", {"event": "preempt", "epoch": 0, "step": 14}))
    rec.note(emergency_step=14)
    path = rec.dump("preempt")
    assert path and os.path.basename(path) == "flightrec_preempt.json"
    errors, n = schema_mod.validate_flight_file(path)
    assert errors == [] and n == 4  # 3-capped step_stats ring + 1 event
    payload = json.load(open(path))
    assert payload["counts"]["step_stats"] == 3  # ring bound respected
    assert payload["records"]["step_stats"][-1]["step_start"] == 12
    assert payload["notes"]["emergency_step"] == 14
    assert payload["observed_records"] == 9
    # idempotent per reason
    assert rec.dump("preempt") == path
    # no save_dir -> None, never a crash
    assert FlightRecorder(None).dump("exception") is None


def test_flight_payload_drift_rejected():
    from tpuddp.observability.flight import FlightRecorder

    rec = FlightRecorder(None, capacity=4)
    rec.observe(stamp("event", {"event": "x"}))
    good = rec.payload("exception")
    assert schema_mod.validate_flight_payload(good) == []
    # unknown reason
    errs = schema_mod.validate_flight_payload(dict(good, reason="mystery"))
    assert any("unknown reason" in e for e in errs)
    # missing envelope field
    dropped = {k: v for k, v in good.items() if k != "counts"}
    assert any("counts" in e for e in schema_mod.validate_flight_payload(dropped))
    # a ring holding a record of the wrong type
    bad = json.loads(json.dumps(good))
    bad["records"]["step_stats"] = [stamp("event", {"event": "y"})]
    errs = schema_mod.validate_flight_payload(bad)
    assert any("does not belong" in e for e in errs)
    # newer-version reject
    errs = schema_mod.validate_flight_payload(
        dict(good, schema_version=schema_mod.SCHEMA_VERSION + 1)
    )
    assert any("newer" in e for e in errs)
    # wrong type marker
    errs = schema_mod.validate_flight_payload(dict(good, type="history"))
    assert any("flight_recording" in e for e in errs)


def test_flight_dump_on_loop_exception(mesh, tmp_path):
    """An unhandled exception in the native epoch driver leaves a validated
    flightrec_exception.json holding the run header and the records written
    before the crash."""
    class PoisonedLoader:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def __len__(self):
            return len(self.inner)

        def __iter__(self):
            it = iter(self.inner)
            yield next(it)
            raise RuntimeError("injected loader failure")

    ds = SyntheticClassification(n=256, shape=(8, 8, 3), seed=0)
    loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    test_loader = ShardedDataLoader(ds, 8, mesh, shuffle=True)
    ddp = DistributedDataParallel(
        ToyMLP(hidden=(16,)), optim.Adam(1e-2), CrossEntropyLoss(), mesh=mesh
    )
    state = ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    with pytest.raises(RuntimeError, match="injected loader failure"):
        run_training_loop(
            ddp, state, PoisonedLoader(loader), test_loader, str(tmp_path),
            num_epochs=2, checkpoint_epoch=1, step_stats_every=2,
            log=lambda *_: None,
        )
    path = tmp_path / "flightrec_exception.json"
    assert path.exists()
    errors, _ = schema_mod.validate_flight_file(str(path))
    assert errors == []
    payload = json.load(open(path))
    assert payload["reason"] == "exception"
    assert payload["run_meta"]["api"] == "native"
    # the recorder registry is clean after the loop's finally
    from tpuddp.observability import flight as flight_mod

    assert flight_mod._registry == []


@pytest.mark.slow
def test_flight_dump_on_exit75_matches_emergency_checkpoint(tmp_path):
    """ISSUE 10 acceptance (chaos leg): an injected preempt drains to exit
    75 and leaves a tpuddp_inspect-valid flight recording whose emergency
    note and preempt event agree with the emergency checkpoint's step."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "TPUDDP_BACKEND": "cpu",
        "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        "TPUDDP_FAULT": "preempt@epoch=1",
        "TPUDDP_CHAOS_TRAINING": '{"step_stats_every": 2}',
    })
    proc = subprocess.run(
        [sys.executable, "-u",
         os.path.join(repo, "tests", "_chaos_train_worker.py"),
         str(tmp_path), "3"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 75, proc.stdout + proc.stderr
    path = tmp_path / "flightrec_preempt.json"
    assert path.exists()
    # the CLI validates it (the gate's path)
    check = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "tpuddp_inspect.py"),
         "--validate", str(path)],
        capture_output=True, text=True, cwd=repo,
    )
    assert check.returncode == 0, check.stdout + check.stderr
    payload = json.load(open(path))
    assert payload["reason"] == "preempt"
    preempts = [
        e for e in payload["records"]["event"] if e["event"] == "preempt"
    ]
    assert len(preempts) == 1
    # the recording's last window ends at (or before) the emergency step,
    # and the notes name the checkpoint the drain wrote
    notes = payload["notes"]
    assert notes["emergency_step"] == preempts[0]["step"]
    assert os.path.exists(notes["emergency_checkpoint"])
    windows = payload["records"]["step_stats"]
    assert windows, "no step_stats windows retained"
    last = windows[-1]
    assert last["step_start"] + last["steps"] <= notes["emergency_step"]
    # the emergency checkpoint is the newest on disk and restores at the
    # epoch the preempt event names
    from tpuddp.training import checkpoint as _ckpt

    newest = _ckpt.latest(str(tmp_path))
    assert newest is not None
    assert os.path.basename(newest[0]) == os.path.basename(
        notes["emergency_checkpoint"]
    )


# --------------------------------------------------------- schema v5 drift --


def test_schema_v5_requires_observability_field(tmp_path):
    """Live-plane schema bump: a run_meta stamped v5+ without the
    ``observability`` key is drift; v4 headers keep validating at their own
    version; the shared make_run_meta always carries the key (null = plane
    off)."""
    meta = schema_mod.make_run_meta(
        comm_hook="none", observability={"exporter": False}
    )
    assert meta["schema_version"] >= 5
    assert schema_mod.validate_history_records([meta]) == []
    # null is legal (a minimal watchdog header)...
    assert schema_mod.validate_history_records(
        [schema_mod.make_run_meta(comm_hook=None)]
    ) == []
    # ...but ABSENCE at v5 is drift
    dropped = {k: v for k, v in meta.items() if k != "observability"}
    errs = schema_mod.validate_history_records([dropped])
    assert any("observability" in e for e in errs), errs
    # a v4 header without the field stays valid (its version's contract)
    v4 = dict(dropped, schema_version=4)
    assert schema_mod.validate_history_records([v4]) == []
    # the drift also fails through the file validator (the gate's path)
    p = tmp_path / "drift5.jsonl"
    p.write_text(json.dumps(dropped) + "\n")
    errors, _ = schema_mod.validate_history_file(str(p))
    assert any("observability" in e for e in errors)


# ------------------------------------- inspect: resumed-run attribution fix --


def test_inspect_attributes_rows_to_latest_header(tmp_path):
    """ISSUE 10 satellite: after an elastic shrink-resume the summary's
    per-epoch table marks which header owns each row and the grad-comm
    savings line uses ONLY the latest run segment — pre- and post-resume
    worlds never mix."""
    import subprocess
    import sys

    # a realistic shrink-resume stream: world 4 (16 B/update) then a resumed
    # world 2 (8 B/update, resumed_from_world=4), built from the real
    # make_run_meta/stamp writers so it validates at v5
    records = [
        schema_mod.make_run_meta(
            world_size=4, comm_hook="bf16_ef", comm_topology="flat",
            extra={
                "api": "native",
                "grad_comm_bytes_per_update": 16,
                "grad_comm_bytes_per_update_f32": 32,
            },
        ),
    ]

    def epoch_row(epoch, total):
        return stamp("epoch", {
            "epoch": epoch, "train_loss": 1.0, "test_loss": 1.0,
            "test_accuracy": 50.0, "train_samples": 256, "test_samples": 64,
            "epoch_time_s": 1.0, "samples_per_sec": 320.0,
            "step_time_ms_p50": 1.0, "step_time_ms_p95": 1.0,
            "step_time_ms_p99": 1.0, "step_time_ms_max": 1.0,
            "mfu_p50": None, "grad_comm_bytes_total": total,
        })

    records += [epoch_row(0, 160), epoch_row(1, 320)]
    records.append(schema_mod.make_run_meta(
        world_size=2, comm_hook="bf16_ef", comm_topology="flat",
        extra={
            "api": "native",
            "resumed_from_world": 4,
            "grad_comm_bytes_per_update": 8,
            "grad_comm_bytes_per_update_f32": 16,
        },
    ))
    records.append(stamp("event", {
        "event": "topology_change", "from_world": 4, "to_world": 2,
    }))
    records += [epoch_row(2, 80)]
    path = tmp_path / "history.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in records))
    assert schema_mod.validate_history_records(records) == []

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "tpuddp_inspect.py")
    out = subprocess.run(
        [sys.executable, tool, str(path)],
        capture_output=True, text=True, cwd=repo,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    # the table names the owning run per row
    assert "epochs (3 across 2 runs" in out.stdout
    lines = out.stdout.splitlines()
    run_col = [
        line.split() for line in lines
        if line.strip() and line.split()[0] in ("0", "1", "2")
        and len(line.split()) > 5
    ]
    by_epoch = {cells[1]: cells[0] for cells in run_col}
    assert by_epoch["0"] == "0" and by_epoch["1"] == "0"
    assert by_epoch["2"] == "1"  # the resumed epoch belongs to header 1
    # grad-comm savings come from the LATEST segment: 8 B/update vs 16 B
    # f32 and the resumed run's own 80 B total — not the old world's 320
    assert "8 B/update on the wire vs 16 B" in out.stdout
    assert "80 B total this run (latest of 2)" in out.stdout
    assert "320 B total" not in out.stdout
    # resumed provenance is surfaced in the header block
    assert "resumed_from_world: 4" in out.stdout


def test_inspect_real_resumed_history_gains_run_column(mesh, tmp_path):
    """The same attribution over a REAL resumed run (double-header history
    from the actual writers)."""
    import subprocess
    import sys

    ddp, (state, _) = small_run(mesh, str(tmp_path), num_epochs=1)
    restored, start = ckpt.restore_latest(
        str(tmp_path), ddp.init_state(jax.random.key(0), jnp.zeros((1, 8, 8, 3)))
    )
    small_run(
        mesh, str(tmp_path), num_epochs=2, start_epoch=start, state=restored
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "tpuddp_inspect.py"),
         str(tmp_path / "history.jsonl")],
        capture_output=True, text=True, cwd=repo,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "across 2 runs" in out.stdout


def test_inspect_validates_and_summarizes_flight_recording(tmp_path):
    """The CLI's flight kind: --validate accepts a real dump, the summary
    renders, and drift (bad reason) is refused."""
    import subprocess
    import sys

    from tpuddp.observability.flight import FlightRecorder

    rec = FlightRecorder(str(tmp_path), capacity=4)
    rec.observe(schema_mod.make_run_meta(comm_hook="none", extra={"api": "native"}))
    rec.observe(stamp("event", {"event": "preempt", "epoch": 1, "step": 8}))
    path = rec.dump("preempt")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(repo, "tools", "tpuddp_inspect.py")
    ok = subprocess.run(
        [sys.executable, tool, "--validate", path],
        capture_output=True, text=True, cwd=repo,
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "flight record" in ok.stdout
    summary = subprocess.run(
        [sys.executable, tool, path], capture_output=True, text=True, cwd=repo,
    )
    assert summary.returncode == 0
    assert "reason=preempt" in summary.stdout
    assert "preempt" in summary.stdout
    bad = tmp_path / "flightrec_bogus.json"
    payload = json.load(open(path))
    payload["reason"] = "mystery"
    bad.write_text(json.dumps(payload))
    refused = subprocess.run(
        [sys.executable, tool, "--validate", str(bad)],
        capture_output=True, text=True, cwd=repo,
    )
    assert refused.returncode == 1
    assert "unknown reason" in refused.stderr


def test_supervisor_summarizes_flight_before_restart(tmp_path, caplog):
    """tools/supervise.py pickup: the supervisor logs the child's flight
    recording after an abnormal exit, BEFORE deciding the restart."""
    import logging as _logging

    from tpuddp.observability.flight import FlightRecorder
    from tpuddp.resilience.supervisor import RestartSupervisor, SupervisorPolicy

    calls = {"n": 0}

    def runner(argv, env):
        calls["n"] += 1
        if calls["n"] == 1:
            rec = FlightRecorder(str(tmp_path), capacity=4)
            rec.observe(schema_mod.make_run_meta(
                comm_hook="none", extra={"api": "native"}
            ))
            rec.observe(stamp("event", {"event": "preempt", "epoch": 0}))
            rec.dump("preempt")
            return 75
        return 0

    sup = RestartSupervisor(
        ["cmd"], policy=SupervisorPolicy(max_restarts=3),
        runner=runner, sleep=lambda s: None, flight_dir=str(tmp_path),
    )
    with caplog.at_level(_logging.WARNING, logger="tpuddp"):
        rc = sup.run()
    assert rc == 0 and calls["n"] == 2
    flight_lines = [
        r.message for r in caplog.records if "flight recording" in r.message
    ]
    assert flight_lines, "supervisor never summarized the recording"
    assert any("reason=preempt" in m for m in flight_lines)
    # the same recording is not re-summarized on later exits
    assert len([m for m in flight_lines if "reason=preempt" in m]) == 1


def test_exporter_escapes_label_values():
    """A caller-supplied label value (tenant id!) containing quotes,
    backslashes, or newlines must not corrupt the exposition page."""
    from tpuddp.observability.exporter import MetricsExporter

    e = MetricsExporter(port=0)
    e.register_source("t", lambda: {
        "serving_tenant_completed_total": {
            "type": "counter", "help": "h",
            "values": [({"tenant": 'acme"prod\\x\ny'}, 3)],
        },
    })
    text = e.render_prometheus()
    line = [l for l in text.splitlines() if l.startswith(
        "tpuddp_serving_tenant_completed_total{")][0]
    assert line == (
        'tpuddp_serving_tenant_completed_total'
        '{tenant="acme\\"prod\\\\x\\ny"} 3'
    )
    assert "\n\n" not in text  # no raw newline leaked mid-sample


def test_flight_dump_per_process_qualified(tmp_path):
    """On a shared save_dir, non-zero processes dump under their own name —
    a pod-wide death must not be last-rename-wins."""
    from tpuddp.observability.flight import FlightRecorder, find_recordings

    for pid in (0, 1, 2):
        rec = FlightRecorder(str(tmp_path), capacity=2, process_index=pid)
        rec.observe(stamp("event", {"event": "watchdog_stale", "process": pid}))
        rec.dump("watchdog")
    names = sorted(os.path.basename(p) for p in find_recordings(str(tmp_path)))
    assert names == [
        "flightrec_watchdog.json",
        "flightrec_watchdog_p1.json",
        "flightrec_watchdog_p2.json",
    ]
    for path in find_recordings(str(tmp_path)):
        errors, _ = schema_mod.validate_flight_file(path)
        assert errors == []


def test_exporter_port_file_per_process_name(tmp_path, monkeypatch):
    """exporter_from_config qualifies the discovery file by process index —
    the shared run dir must hold one file per serving host."""
    import jax as _jax

    from tpuddp.observability import exporter as exp_mod

    monkeypatch.setattr(_jax, "process_index", lambda: 2)
    e = exp_mod.exporter_from_config(
        {"exporter": True, "exporter_port": 0}, run_dir=str(tmp_path)
    )
    assert e.port_filename == "exporter_p2.port"
    e.start()
    try:
        assert int((tmp_path / "exporter_p2.port").read_text().splitlines()[0]) == e.port
        assert not (tmp_path / "exporter.port").exists()
    finally:
        e.stop()
    assert not (tmp_path / "exporter_p2.port").exists()
