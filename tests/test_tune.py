"""The autotuning plane: advisor rule table, A/B probe arithmetic, the
TUNE_r*.json schema contract, the fleet tuner's apply/measure/revert state
machine, and the advisor-off identity guarantee.

The advisor tests craft run directories (history.jsonl / trace_*.json /
*.writer.json) with exactly the evidence each rule keys on — thresholds come
from the advisor's own module constants so the tests track the boundaries,
not copies of them.
"""

import json
import os

import pytest

from tpuddp import config as cfg_lib
from tpuddp.observability import advisor
from tpuddp.observability import schema
from tpuddp.tune import (
    FleetTuner,
    TunePolicy,
    endorsed_rules_from_report,
    probe,
)


# ------------------------------------------------------------ run builders --


def _write_history(run_dir, records):
    os.makedirs(run_dir, exist_ok=True)
    with open(os.path.join(run_dir, "history.jsonl"), "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def _run_meta(**overrides):
    """A minimal-but-plausible v12 training header the advisor reads."""
    meta = {
        "type": "run_meta",
        "schema_version": schema.SCHEMA_VERSION,
        "world_size": 4,
        "process_count": 1,
        "comm_hook": "bf16_ef",
        "comm_topology": "hierarchical",
        "pipeline": {"depth": 2, "host_workers": 2, "sync_readback": False},
        "scan_steps": 8,
        "comm": {"overlap": {"enabled": False, "segments": None, "reason": "barrier step"}},
        "snapshot": False,
        "tuning": None,
        "grad_comm_bytes_per_update": 0,
    }
    meta.update(overrides)
    return meta


def _epoch(samples_per_sec=100.0, epoch_time_s=10.0, host_stall_ms=0.0,
           step_time_ms_p50=5.0):
    return {
        "type": "epoch",
        "schema_version": schema.SCHEMA_VERSION,
        "samples_per_sec": samples_per_sec,
        "epoch_time_s": epoch_time_s,
        "host_stall_ms": host_stall_ms,
        "step_time_ms_p50": step_time_ms_p50,
    }


def _write_trace(run_dir, shares, total_us=100_000.0):
    """One trace artifact whose span durations realize ``shares`` of the
    traced step-phase time (dispatch/stage/readback/collective)."""
    events = []
    t = 0.0
    for cat, share in shares.items():
        dur = total_us * share
        events.append({"ph": "X", "cat": cat, "name": f"{cat}.0",
                       "ts": t, "dur": dur})
        t += dur
    payload = {"traceEvents": events, "tpuddp": {"dropped": 0}}
    with open(os.path.join(run_dir, "trace_r0.json"), "w") as f:
        json.dump(payload, f)


def _write_sidecar(run_dir, **stats):
    base = {"snapshots": 3, "skipped_queue_full": 0, "write_s": 0.01,
            "bytes": 4096, "mode": "async"}
    base.update(stats)
    with open(os.path.join(run_dir, "ckpt.writer.json"), "w") as f:
        json.dump(base, f)


def _clean_run(run_dir):
    """Healthy evidence: every rule's predicate is false."""
    _write_history(run_dir, [
        _run_meta(),
        _epoch(), _epoch(), _epoch(),
    ])
    _write_trace(run_dir, {"dispatch": 0.1, "stage": 0.3, "readback": 0.1,
                           "collective": 0.5})


# One builder per rule: arrange exactly the evidence that rule fires on
# (against an otherwise-clean run so only the targeted predicate is true).
def _arm_pipeline_sync(d):
    _write_history(d, [
        _run_meta(pipeline={"depth": 1, "host_workers": 0,
                            "sync_readback": True}),
        _epoch(host_stall_ms=3000.0),
    ])


def _arm_pipeline_stall(d):
    stall = advisor.HOST_STALL_SHARE_THRESHOLD + 0.1
    _write_history(d, [
        _run_meta(),
        _epoch(epoch_time_s=10.0, host_stall_ms=stall * 10.0 * 1000.0),
    ])


def _arm_span_readback(d):
    share = advisor.READBACK_SHARE_THRESHOLD + 0.1
    _write_history(d, [_run_meta(), _epoch()])
    _write_trace(d, {"dispatch": 0.1, "stage": 0.9 - share,
                     "readback": share})


def _arm_span_dispatch(d):
    share = advisor.DISPATCH_SHARE_THRESHOLD + 0.1
    _write_history(d, [_run_meta(scan_steps=1), _epoch()])
    _write_trace(d, {"dispatch": share, "stage": 0.9 - share,
                     "readback": 0.1})


def _arm_comm_hook(d):
    _write_history(d, [
        _run_meta(comm_hook="none",
                  grad_comm_bytes_per_update=advisor.COMM_BYTES_FLOOR * 64),
        _epoch(),
    ])


def _arm_comm_topology(d):
    _write_history(d, [
        _run_meta(comm_topology="flat", process_count=2, world_size=8,
                  grad_comm_bytes_inter_host=1 << 20),
        _epoch(),
    ])


def _arm_snapshot_backlog(d):
    _write_history(d, [
        _run_meta(snapshot={"every_steps": 50, "inflight": 1}),
        _epoch(),
    ])
    _write_sidecar(d, skipped_queue_full=4)


def _arm_snapshot_cadence(d):
    _write_history(d, [
        _run_meta(snapshot={"every_steps": advisor.SNAPSHOT_HOT_EVERY_STEPS,
                            "inflight": 2}),
        _epoch(),
    ])
    _write_sidecar(d, write_s=1.5)


def _serving_window(**overrides):
    row = {
        "type": "serving_stats",
        "schema_version": schema.SCHEMA_VERSION,
        "batch_occupancy": 0.9,
        "queue_ms_p50": 1.0,
        "device_ms_p50": 5.0,
        "e2e_ms_p50": 7.0,
        "throughput_rps": 100.0,
        "shed": 0,
        "rejected": 0,
    }
    row.update(overrides)
    return row


def _arm_serving_linger(d):
    _write_history(d, [
        _run_meta(),
        _serving_window(batch_occupancy=advisor.OCCUPANCY_FLOOR - 0.1,
                        queue_ms_p50=20.0, device_ms_p50=4.0,
                        e2e_ms_p50=25.0),
    ])


def _arm_serving_shed(d):
    _write_history(d, [_run_meta(), _serving_window(shed=7)])


def _arm_decode_kv(d):
    _write_history(d, [
        _run_meta(),
        {
            "type": "decode_stats",
            "schema_version": schema.SCHEMA_VERSION,
            "tokens_per_sec": 50.0,
            "ttft_ms_p50": 10.0,
            "itl_ms_p50": 4.0,
            "itl_ms_p95": 20.0,
            "kv_occupancy": advisor.KV_PRESSURE_THRESHOLD + 0.05,
            "shed": 0,
            "failovers": 0,
        },
    ])


_RULE_BUILDERS = {
    "pipeline_sync_readback": _arm_pipeline_sync,
    "pipeline_host_stall_depth": _arm_pipeline_stall,
    "span_readback_share": _arm_span_readback,
    "span_dispatch_share": _arm_span_dispatch,
    "comm_hook_uncompressed": _arm_comm_hook,
    "comm_topology_flat_multihost": _arm_comm_topology,
    "snapshot_writer_backlog": _arm_snapshot_backlog,
    "snapshot_cadence_hot": _arm_snapshot_cadence,
    "serving_low_occupancy_linger": _arm_serving_linger,
    "serving_shed_pressure": _arm_serving_shed,
    "decode_kv_pressure": _arm_decode_kv,
}


# --------------------------------------------------------------- the rules --


def test_rule_table_is_fully_covered():
    assert {rid for rid, _, _, _ in advisor.RULES} == set(_RULE_BUILDERS)


@pytest.mark.parametrize("rule_id", sorted(_RULE_BUILDERS))
def test_every_rule_fires_on_crafted_evidence(tmp_path, rule_id):
    d = str(tmp_path / rule_id)
    os.makedirs(d)
    _RULE_BUILDERS[rule_id](d)
    report = advisor.advise(d)
    by_rule = {r["rule"]: r for r in report["recommendations"]}
    assert rule_id in by_rule, (
        f"{rule_id} did not fire; got {sorted(by_rule)}; "
        f"insufficient={report['insufficient']}"
    )
    rec = by_rule[rule_id]
    assert rec["rule_class"] in advisor.RULE_CLASSES
    assert rec["predicted_delta_pct"] > 0
    assert isinstance(rec["diff"], dict) and rec["diff"]
    assert rec["evidence"], "a recommendation must cite its evidence"
    for c in rec["evidence"]:
        assert set(c) == {"source", "field", "value"}


def test_clean_run_yields_no_recommendations(tmp_path):
    d = str(tmp_path / "clean")
    _clean_run(d)
    report = advisor.advise(d)
    assert report["recommendations"] == []
    # with a trace present, even the span rules had their evidence and
    # declined — nothing lands in insufficient either
    assert report["insufficient"] == []


def test_traceless_history_degrades_gracefully(tmp_path):
    """A v11-era history (no trace artifact) still runs the metric rules;
    the span rules report insufficient_evidence instead of guessing."""
    d = str(tmp_path / "v11")
    _write_history(d, [
        _run_meta(schema_version=11, comm_hook="none",
                  grad_comm_bytes_per_update=1 << 20),
        _epoch(),
    ])
    meta_path = os.path.join(d, "history.jsonl")
    with open(meta_path) as f:
        head = json.loads(f.readline())
    head.pop("tuning", None)  # v11 headers predate the tuning key
    rest = open(meta_path).readlines()[1:]
    with open(meta_path, "w") as f:
        f.write(json.dumps(head) + "\n")
        f.writelines(rest)

    report = advisor.advise(d)
    fired = {r["rule"] for r in report["recommendations"]}
    assert "comm_hook_uncompressed" in fired
    missing = {m["rule"]: m for m in report["insufficient"]}
    assert set(missing) == {"span_readback_share", "span_dispatch_share"}
    for m in missing.values():
        assert m["needs"] == "trace"
        assert "insufficient_evidence" in m["reason"]


def test_overlay_from_merges_without_clobbering():
    recs = [
        {"section": "training", "diff": {"pipeline": {"depth": 4}}},
        {"section": "training", "diff": {"pipeline": True}},
        {"section": "training", "diff": {"scan_steps": 8}},
        {"section": "serving", "diff": {"batch_timeout_ms": 1}},
        {"section": "training", "diff": {"pipeline": {"host_workers": 4}}},
    ]
    overlay = advisor.overlay_from(recs)
    # a bare enable never erases a sibling rule's dict refinement
    assert overlay["training"]["pipeline"] == {"depth": 4, "host_workers": 4}
    assert overlay["training"]["scan_steps"] == 8
    assert overlay["serving"] == {"batch_timeout_ms": 1}


def test_pending_summary_top_recommendation(tmp_path):
    d = str(tmp_path / "pending")
    _arm_comm_hook(d)
    pending = advisor.pending_summary(d)
    assert pending is not None
    assert pending["rule"] == "comm_hook_uncompressed"
    assert pending["endorsed"] is False
    assert "comm_hook_uncompressed" in pending["pending_rules"]

    clean = str(tmp_path / "pending_clean")
    _clean_run(clean)
    assert advisor.pending_summary(clean) is None
    # and a nonexistent dir must never raise (crash-path contract)
    assert advisor.pending_summary(str(tmp_path / "nope")) is None


def test_measure_run_reads_train_metrics(tmp_path):
    d = str(tmp_path / "measure")
    _write_history(d, [
        _run_meta(grad_comm_bytes_per_update=2048),
        _epoch(samples_per_sec=100.0),
        _epoch(samples_per_sec=200.0),
    ])
    metrics = advisor.measure_run(d, mode="train")
    assert metrics["samples_per_sec"] == pytest.approx(150.0)
    assert metrics["grad_comm_bytes"] == 2048


# --------------------------------------------------------- probe arithmetic --


def test_delta_pct_sign_convention():
    # higher-better: raw relative change
    assert probe.delta_pct("samples_per_sec", 100.0, 150.0) == pytest.approx(50.0)
    assert probe.delta_pct("samples_per_sec", 100.0, 80.0) == pytest.approx(-20.0)
    # lower-better: the REDUCTION is the improvement
    assert probe.delta_pct("step_time_ms_p50", 10.0, 5.0) == pytest.approx(50.0)
    assert probe.delta_pct("grad_comm_bytes", 100.0, 150.0) == pytest.approx(-50.0)


def test_delta_pct_zero_baseline_and_unknowns():
    assert probe.delta_pct("shed", 0.0, 0.0) == 0.0
    assert probe.delta_pct("shed", 0.0, 3.0) == -100.0  # left zero: regression
    assert probe.delta_pct("samples_per_sec", 0.0, 3.0) == 100.0
    assert probe.delta_pct("shed", None, 3.0) is None
    assert probe.delta_pct("shed", 3.0, None) is None
    assert probe.delta_pct("not_a_metric", 1.0, 2.0) is None


def test_endorse_refuses_regressions_and_no_data():
    assert probe.endorse(5.0)
    assert probe.endorse(0.0)
    assert not probe.endorse(-0.1)
    assert not probe.endorse(None), "no data is not a pass"
    assert not probe.endorse(0.5, min_improvement_pct=1.0)


def _rec_fixture(metric="samples_per_sec"):
    return {
        "rule": "comm_hook_uncompressed",
        "rule_class": "comm",
        "section": "training",
        "knob": "comm_hook",
        "diff": {"comm_hook": "bf16_ef"},
        "metric": metric,
        "predicted_delta_pct": 50.0,
        "reason": "test",
        "evidence": [advisor.cite("history.jsonl#run_meta", "comm_hook", None)],
    }


def test_make_result_row_endorsement():
    rec = _rec_fixture()
    good = probe.make_result_row(rec, {"samples_per_sec": 100.0},
                                 {"samples_per_sec": 120.0})
    assert good["measured_delta_pct"] == pytest.approx(20.0)
    assert good["endorsed"] is True
    bad = probe.make_result_row(rec, {"samples_per_sec": 100.0},
                                {"samples_per_sec": 90.0})
    assert bad["endorsed"] is False
    unmeasured = probe.make_result_row(rec, {}, {})
    assert unmeasured["measured_delta_pct"] is None
    assert unmeasured["endorsed"] is False


def test_build_tune_report_round_trips_validation():
    rec = _rec_fixture()
    row = probe.make_result_row(rec, {"samples_per_sec": 100.0},
                                {"samples_per_sec": 120.0})
    payload = probe.build_tune_report(
        device="cpu", mode="train",
        baseline_metrics={"samples_per_sec": 100.0}, results=[row],
    )
    assert payload["type"] == "tune_report"
    assert payload["schema_version"] == schema.SCHEMA_VERSION
    assert schema.validate_tune_payload(payload) == []


def test_build_tune_report_refuses_endorsed_regression():
    rec = _rec_fixture()
    row = probe.make_result_row(rec, {"samples_per_sec": 100.0},
                                {"samples_per_sec": 90.0})
    row["endorsed"] = True  # forge the verdict the probe refused to give
    with pytest.raises(ValueError, match="refus"):
        probe.build_tune_report(
            device="cpu", mode="train",
            baseline_metrics={"samples_per_sec": 100.0}, results=[row],
        )


def test_next_tune_path_numbers_the_artifact_family(tmp_path):
    root = str(tmp_path)
    assert probe.next_tune_path(root).endswith("TUNE_r01.json")
    open(os.path.join(root, "TUNE_r01.json"), "w").close()
    open(os.path.join(root, "TUNE_r07.json"), "w").close()
    assert probe.next_tune_path(root).endswith("TUNE_r08.json")


# ------------------------------------------------------------- schema v12 --


def test_validate_tune_payload_field_contract():
    errors = schema.validate_tune_payload({"type": "tune_report"})
    assert any("schema_version" in e for e in errors)
    assert any("'results'" in e or "results" in e for e in errors)

    payload = {
        "type": "tune_report", "schema_version": 12, "device": "cpu",
        "mode": "train", "baseline_metrics": {},
        "results": [{
            "rule": "x", "rule_class": "comm", "knob": "k", "diff": {},
            "metric": "m", "predicted_delta_pct": 1.0,
            "measured_delta_pct": -4.0, "endorsed": True, "evidence": [],
        }],
    }
    errors = schema.validate_tune_payload(payload)
    assert any("endorsed=true" in e and "regress" in e for e in errors)
    payload["results"][0]["endorsed"] = False
    assert schema.validate_tune_payload(payload) == []
    payload["mode"] = "decode"
    assert any("mode" in e for e in schema.validate_tune_payload(payload))


def test_run_meta_requires_tuning_key_at_v12():
    meta = schema.make_run_meta(world_size=4)
    assert "tuning" in meta and meta["tuning"] is None
    assert schema.validate_record(meta) == []

    stripped = dict(meta)
    del stripped["tuning"]
    assert any("tuning" in e for e in schema.validate_record(stripped))

    # an older header that predates the key keeps validating under this
    # reader — requirements apply at the version a record CARRIES
    stripped["schema_version"] = 11
    assert not any("tuning" in e for e in schema.validate_record(stripped))


def test_run_meta_carries_tuning_provenance():
    prov = {"source": "fleet", "rule": "comm_hook_uncompressed",
            "generation": 2, "applied": {"training": {"comm_hook": "bf16_ef"}},
            "section": "training"}
    meta = schema.make_run_meta(world_size=4, tuning=prov)
    assert meta["tuning"] == prov
    assert schema.validate_record(meta) == []


# -------------------------------------------------------------- fleet tuner --


def _fake_edges(rec, epoch_rows):
    """Injectable advise/reader pair: a fixed recommendation + a mutable
    list of history rows (append to simulate the job's live stream)."""
    def fake_advise(run_dir):
        return {"recommendations": [dict(rec)] if rec else [],
                "insufficient": []}

    def fake_reader(run_dir):
        return list(epoch_rows)

    return fake_advise, fake_reader


def _epoch_row(sps):
    return {"type": "epoch", "samples_per_sec": sps}


def _make_tuner(rec, rows, endorsed=None, **policy):
    policy.setdefault("cooldown_s", 0.0)
    policy.setdefault("baseline_rows", 2)
    policy.setdefault("measure_rows", 2)
    fake_advise, fake_reader = _fake_edges(rec, rows)
    return FleetTuner(
        TunePolicy(**policy),
        endorsed_rules=endorsed,
        advise=fake_advise,
        reader=fake_reader,
    )


def test_fleet_tuner_apply_measure_keep(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rec = _rec_fixture()
    rows = [_epoch_row(100.0), _epoch_row(100.0)]
    tuner = _make_tuner(rec, rows, endorsed={rec["rule"]})

    decision = tuner.observe_and_decide("job", "training", run_dir, now=0.0)
    assert decision["action"] == "apply"
    assert decision["generation"] == 1
    assert decision["baseline_value"] == pytest.approx(100.0)
    env = decision["overlay_env"]
    assert env["source"] == "fleet"
    assert env["rule"] == rec["rule"]
    assert env["training"] == {"comm_hook": "bf16_ef"}
    tuner.mark_applied("job", run_dir, decision, now=0.0)
    assert tuner.counters["applied"] == 1

    # not enough post-change rows yet: the tuner waits, makes no new move
    rows.append(_epoch_row(130.0))
    assert tuner.observe_and_decide("job", "training", run_dir, 1.0) is None

    rows.append(_epoch_row(130.0))
    verdict = tuner.observe_and_decide("job", "training", run_dir, 2.0)
    assert verdict["action"] == "keep"
    assert verdict["measured_delta_pct"] == pytest.approx(30.0)
    assert verdict["overlay_env"] is None, "keep = no drain"
    tuner.mark_applied("job", run_dir, verdict, now=2.0)
    assert tuner.counters["kept"] == 1

    # the kept rule is never re-proposed on this job
    assert tuner.observe_and_decide("job", "training", run_dir, 100.0) is None

    # typed audit: both actions landed as tune_action events in the history
    with open(os.path.join(run_dir, "history.jsonl")) as f:
        events = [json.loads(line) for line in f]
    assert [e["action"] for e in events] == ["apply", "keep"]
    for e in events:
        assert e["type"] == "event" and e["event"] == "tune_action"
        assert e["rule"] == rec["rule"]
        assert schema.validate_record(e) == []


def test_fleet_tuner_reverts_on_regression(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rec = _rec_fixture()
    rows = [_epoch_row(100.0), _epoch_row(100.0)]
    tuner = _make_tuner(rec, rows, endorsed={rec["rule"]})

    decision = tuner.observe_and_decide("job", "training", run_dir, 0.0)
    assert decision["action"] == "apply"
    tuner.mark_applied("job", run_dir, decision, 0.0)

    rows += [_epoch_row(80.0), _epoch_row(80.0)]  # injected regression
    verdict = tuner.observe_and_decide("job", "training", run_dir, 1.0)
    assert verdict["action"] == "revert"
    assert verdict["measured_delta_pct"] == pytest.approx(-20.0)
    # nothing was kept before this apply: revert clears the overlay entirely
    assert verdict["overlay_env"] is None
    tuner.mark_applied("job", run_dir, verdict, 1.0)
    assert tuner.counters["reverted"] == 1

    # the refuted rule is never retried on this job (cooldown is 0)
    assert tuner.observe_and_decide("job", "training", run_dir, 50.0) is None

    with open(os.path.join(run_dir, "history.jsonl")) as f:
        actions = [json.loads(line)["action"] for line in f]
    assert actions == ["apply", "revert"]


def test_fleet_tuner_revert_restores_kept_overlay(tmp_path):
    """A regression on change N rolls back to the overlay kept after
    change N-1, not to bare defaults."""
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rec_a = _rec_fixture()
    rows = [_epoch_row(100.0), _epoch_row(100.0)]
    tuner = _make_tuner(rec_a, rows, endorsed=None)  # trust-advisor mode

    d1 = tuner.observe_and_decide("job", "training", run_dir, 0.0)
    tuner.mark_applied("job", run_dir, d1, 0.0)
    rows += [_epoch_row(150.0), _epoch_row(150.0)]
    keep = tuner.observe_and_decide("job", "training", run_dir, 1.0)
    assert keep["action"] == "keep"
    tuner.mark_applied("job", run_dir, keep, 1.0)

    # second rule proposed; its overlay stacks on the kept one
    rec_b = dict(_rec_fixture(), rule="span_dispatch_share",
                 rule_class="pipeline", knob="scan_steps",
                 diff={"scan_steps": 16})
    tuner.advise, tuner.reader = _fake_edges(rec_b, rows)
    d2 = tuner.observe_and_decide("job", "training", run_dir, 2.0)
    assert d2["action"] == "apply" and d2["generation"] == 2
    assert d2["overlay_env"]["training"] == {
        "comm_hook": "bf16_ef", "scan_steps": 16,
    }
    tuner.mark_applied("job", run_dir, d2, 2.0)

    rows += [_epoch_row(60.0), _epoch_row(60.0)]
    tuner.advise, tuner.reader = _fake_edges(rec_b, rows)
    verdict = tuner.observe_and_decide("job", "training", run_dir, 3.0)
    assert verdict["action"] == "revert"
    # the restore target is the kept generation-1 overlay
    assert verdict["overlay_env"]["training"] == {"comm_hook": "bf16_ef"}


def test_fleet_tuner_endorsement_gating(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rec = _rec_fixture()
    rows = [_epoch_row(100.0), _epoch_row(100.0)]

    inert = _make_tuner(rec, rows, endorsed=set())
    assert inert.observe_and_decide("job", "training", run_dir, 0.0) is None

    trusting = _make_tuner(rec, rows, endorsed=None)
    assert trusting.observe_and_decide(
        "job", "training", run_dir, 0.0
    )["action"] == "apply"


def test_fleet_tuner_respects_cooldown_and_prediction_floor(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rows = [_epoch_row(100.0), _epoch_row(100.0)]

    weak = dict(_rec_fixture(), predicted_delta_pct=0.5)
    floor = _make_tuner(weak, rows, endorsed=None, min_improvement_pct=1.0)
    assert floor.observe_and_decide("job", "training", run_dir, 0.0) is None

    rec = _rec_fixture()
    tuner = _make_tuner(rec, rows, endorsed=None, cooldown_s=300.0)
    d = tuner.observe_and_decide("job", "training", run_dir, 0.0)
    tuner.mark_applied("job", run_dir, d, 0.0)
    rows += [_epoch_row(150.0), _epoch_row(150.0)]
    keep = tuner.observe_and_decide("job", "training", run_dir, 10.0)
    tuner.mark_applied("job", run_dir, keep, 10.0)
    # inside the cooldown window nothing new is proposed; after it, idle
    # decisions are possible again (here: same rule, already kept -> None,
    # but the cooldown gate itself must be what blocks at t=20)
    assert not tuner._cooled("job", 20.0)
    assert tuner._cooled("job", 311.0)


def test_fleet_tuner_needs_a_baseline(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    tuner = _make_tuner(_rec_fixture(), [], endorsed=None)
    assert tuner.observe_and_decide("job", "training", run_dir, 0.0) is None
    assert tuner.counters["applied"] == 0


def test_fleet_tuner_export_source_shape(tmp_path):
    run_dir = str(tmp_path / "job")
    os.makedirs(run_dir)
    rows = [_epoch_row(100.0), _epoch_row(100.0)]
    tuner = _make_tuner(_rec_fixture(), rows, endorsed=None)
    d = tuner.observe_and_decide("job", "training", run_dir, 0.0)
    tuner.mark_applied("job", run_dir, d, 0.0)

    series = tuner.export_source()
    assert series["tpuddp_tune_applied_total"] == {
        "type": "counter",
        "help": series["tpuddp_tune_applied_total"]["help"],
        "value": 1,
    }
    assert series["tpuddp_tune_reverted_total"]["value"] == 0
    assert series["tpuddp_tune_kept_total"]["value"] == 0
    assert series["tpuddp_tune_measuring"]["type"] == "gauge"
    assert series["tpuddp_tune_measuring"]["value"] == 1


def test_endorsed_rules_from_report(tmp_path):
    path = str(tmp_path / "TUNE_r01.json")
    with open(path, "w") as f:
        json.dump({"type": "tune_report", "results": [
            {"rule": "a", "endorsed": True},
            {"rule": "b", "endorsed": False},
            {"rule": "c", "endorsed": True},
            {"endorsed": True},  # no rule name: ignored
        ]}, f)
    assert endorsed_rules_from_report(path) == {"a", "c"}
    assert endorsed_rules_from_report(str(tmp_path / "missing.json")) == set()


# ------------------------------------------------- overlay + off-identity --


def test_tune_overlay_env_resolves_into_config(monkeypatch):
    overlay = {"source": "advisor", "rule": "comm_hook_uncompressed",
               "generation": 1,
               "training": {"comm_hook": "bf16_ef", "scan_steps": 16}}
    monkeypatch.setenv(cfg_lib.TUNE_OVERLAY_ENV, json.dumps(overlay))
    cfg = cfg_lib.training_config({"training": {"num_epochs": 3}})
    assert cfg["comm_hook"] == "bf16_ef"
    assert cfg["scan_steps"] == 16
    assert cfg["num_epochs"] == 3  # settings survive around the overlay

    prov = cfg_lib.tuning_provenance_from_env()
    assert prov["source"] == "advisor"
    assert prov["rule"] == "comm_hook_uncompressed"
    assert prov["generation"] == 1
    assert prov["applied"]["training"] == {"comm_hook": "bf16_ef",
                                           "scan_steps": 16}


def test_tune_overlay_refuses_unknown_knobs(monkeypatch):
    monkeypatch.setenv(cfg_lib.TUNE_OVERLAY_ENV, json.dumps(
        {"training": {"not_a_knob": 1}}
    ))
    with pytest.raises(ValueError, match="not_a_knob"):
        cfg_lib.training_config({})
    monkeypatch.setenv(cfg_lib.TUNE_OVERLAY_ENV, "{not json")
    with pytest.raises(ValueError):
        cfg_lib.training_config({})


def test_advisor_off_identity(monkeypatch):
    """With no overlay armed the tuning plane is invisible: configs resolve
    identically to a build that never had it, and provenance is None."""
    monkeypatch.delenv(cfg_lib.TUNE_OVERLAY_ENV, raising=False)
    settings = {"training": {"num_epochs": 3, "scan_steps": 4}}
    cfg = cfg_lib.training_config(settings)
    untouched, prov = cfg_lib.apply_tune_overlay(dict(cfg), section="training")
    assert untouched == cfg
    assert prov is None
    assert cfg_lib.tuning_provenance_from_env() is None
    assert cfg_lib.tuning_provenance_from_env("serving") is None
    # and a run_meta built off that provenance carries tuning: null
    assert schema.make_run_meta(world_size=4, tuning=None)["tuning"] is None
