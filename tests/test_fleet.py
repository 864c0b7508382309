"""Fleet control plane unit suite (ISSUE 11) — fast tier.

The pure pieces are tested without any process tree: the gang-placement /
priority-preemption / rebalance planner (the acceptance criterion is that
placement decisions are deterministic functions of (pool, specs,
arrivals/exits)), the autoscaler's hysteresis/cooldown/straggler policy
matrix over synthetic observations, the Prometheus-scrape parsing, and the
stale-``exporter.port`` discovery contract. The controller lifecycle tests
use trivial python children (prints/sleeps) — the full jax chaos proof
lives in tests/test_chaos.py and ``tools/fleet.py chaos-demo``.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from tpuddp.fleet.autoscale import (
    Autoscaler,
    AutoscalePolicy,
    metric_value,
    parse_prometheus,
)
from tpuddp.fleet.controller import (
    FleetController,
    escalate_drain,
)
from tpuddp.fleet.scheduler import JobView, plan_fleet
from tpuddp.fleet.spec import FleetAdmissionError, JobSpec, spec_from_dict
from tpuddp.observability.exporter import MetricsExporter, read_live_port
from tpuddp.resilience.supervisor import (
    RestartSupervisor,
    SupervisorPolicy,
    classify_exit,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- specs --
def test_jobspec_validation_matrix():
    ok = JobSpec(name="a", argv=("python", "x.py"))
    assert ok.min_world == ok.max_world == 1
    with pytest.raises(FleetAdmissionError) as e:
        JobSpec(name="bad/name", argv=("x",))
    assert e.value.reason == "bad_spec"
    with pytest.raises(FleetAdmissionError):
        JobSpec(name="a", argv=("x",), kind="batch")
    with pytest.raises(FleetAdmissionError):
        JobSpec(name="a", argv=())
    with pytest.raises(FleetAdmissionError):
        JobSpec(name="a", argv=("x",), min_world=0)
    with pytest.raises(FleetAdmissionError):
        JobSpec(name="a", argv=("x",), min_world=4, max_world=2)
    with pytest.raises(FleetAdmissionError):
        JobSpec(name="a", argv=("x",), max_restarts=-1)


def test_jobspec_run_dir_substitution():
    spec = JobSpec(
        name="a",
        argv=("python", "w.py", "{run_dir}", "3"),
        env={"OUT": "{run_dir}/sub", "K": "v"},
    )
    assert spec.resolved_argv("/tmp/j/a") == ["python", "w.py", "/tmp/j/a", "3"]
    assert spec.resolved_env("/tmp/j/a") == {"OUT": "/tmp/j/a/sub", "K": "v"}


def test_jobspec_initial_desired_by_kind():
    t = JobSpec(name="t", argv=("x",), kind="training", min_world=1, max_world=4)
    s = JobSpec(name="s", argv=("x",), kind="serving", min_world=1, max_world=4)
    assert t.initial_desired() == 4  # training soaks spare capacity
    assert s.initial_desired() == 1  # serving earns replicas from SLO pressure


def test_spec_from_dict_refuses_unknown_keys():
    with pytest.raises(FleetAdmissionError) as e:
        spec_from_dict({"name": "a", "argv": ["x"], "wat": 1})
    assert "wat" in str(e.value)
    with pytest.raises(FleetAdmissionError):
        spec_from_dict({"name": "a", "argv": "not-a-list"})
    spec = spec_from_dict(
        {"name": "a", "argv": ["x"], "priority": 3, "kind": "serving"}
    )
    assert spec.priority == 3 and spec.kind == "serving"


def test_spec_env_none_normalizes_and_non_mapping_refused():
    """A YAML `env:` key with no value parses to None — that is an empty
    mapping, not a start-time AttributeError inside the controller tick;
    a non-mapping env is refused AT ADMISSION (bad_spec)."""
    spec = spec_from_dict(
        {"name": "a", "argv": ["x"], "env": None, "first_attempt_env": None}
    )
    assert spec.env == {} and spec.first_attempt_env == {}
    assert spec.resolved_env("/tmp/a") == {}
    with pytest.raises(FleetAdmissionError) as e:
        JobSpec(name="a", argv=("x",), env=["not", "a", "mapping"])
    assert e.value.reason == "bad_spec"
    with pytest.raises(FleetAdmissionError):
        spec_from_dict({"name": "a", "argv": ["x"], "first_attempt_env": "x=1"})


# ----------------------------------------------------------------- planner --
def V(name, **kw):
    return JobView(name=name, **kw)


def test_plan_is_deterministic_and_input_order_free():
    jobs = [
        V("a", priority=1, arrival=0, min_world=1, max_world=4),
        V("b", priority=1, arrival=1, min_world=2, max_world=2),
        V("c", priority=5, arrival=2, min_world=1, max_world=8),
    ]
    p1 = plan_fleet(8, jobs)
    p2 = plan_fleet(8, list(reversed(jobs)))
    assert p1 == p2
    # priority first, then arrival: c gets its growth headroom first
    assert [p.name for p in p1.placements] == ["c", "a", "b"]
    assert p1.alloc == {"c": 5, "a": 1, "b": 2}
    assert p1.free == 0


def test_plan_gang_admission_is_all_or_nothing_with_backfill():
    jobs = [
        V("big", priority=10, arrival=0, min_world=6, max_world=6),
        V("small", priority=1, arrival=1, min_world=2, max_world=2),
    ]
    plan = plan_fleet(4, jobs)
    # big cannot gang-place at 6 on a 4-pool; small backfills behind it
    assert plan.alloc == {"small": 2}
    assert plan.action("big") == "queued"
    assert plan.free == 2


def test_plan_priority_preempts_running_lower_priority():
    jobs = [
        V("low", priority=1, arrival=0, min_world=3, max_world=4,
          running=True, current_world=4),
        V("high", priority=9, arrival=1, min_world=3, max_world=3),
    ]
    plan = plan_fleet(4, jobs)
    assert plan.alloc == {"high": 3}
    assert plan.action("low") == "preempt"
    assert plan.action("high") == "start"


def test_plan_resize_actions_on_membership_change():
    # a finishes -> b grows back toward desired
    before = plan_fleet(4, [
        V("a", priority=9, arrival=1, min_world=2, max_world=2,
          running=True, current_world=2),
        V("b", priority=1, arrival=0, min_world=1, max_world=4,
          running=True, current_world=2),
    ])
    assert before.alloc == {"a": 2, "b": 2}
    after = plan_fleet(4, [
        V("b", priority=1, arrival=0, min_world=1, max_world=4,
          running=True, current_world=2),
    ])
    assert after.alloc == {"b": 4}
    assert after.action("b") == "resize"


def test_plan_desired_is_clamped_to_spec_bounds():
    jobs = [V("a", min_world=2, max_world=4, desired=99)]
    assert plan_fleet(16, jobs).alloc == {"a": 4}
    jobs = [V("a", min_world=2, max_world=4, desired=1)]
    assert plan_fleet(16, jobs).alloc == {"a": 2}
    jobs = [V("a", min_world=2, max_world=4, desired=3)]
    assert plan_fleet(16, jobs).alloc == {"a": 3}


def test_plan_slices_are_disjoint_and_packed():
    jobs = [
        V("a", priority=2, arrival=0, min_world=2, max_world=2),
        V("b", priority=1, arrival=1, min_world=3, max_world=3),
        V("c", priority=3, arrival=2, min_world=1, max_world=1),
    ]
    plan = plan_fleet(8, jobs)
    slices = plan.slices
    assert slices == {"c": (0, 1), "a": (1, 3), "b": (3, 6)}
    spans = sorted(slices.values())
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        assert e0 <= s1  # disjoint
    assert all(0 <= s < e <= 8 for s, e in spans)


def test_plan_rejects_bad_inputs():
    with pytest.raises(ValueError):
        plan_fleet(0, [])
    with pytest.raises(ValueError):
        plan_fleet(4, [V("a"), V("a")])


def test_plan_keep_action_when_nothing_changes():
    jobs = [V("a", min_world=2, max_world=2, running=True, current_world=2)]
    plan = plan_fleet(4, jobs)
    assert plan.action("a") == "keep"


# -------------------------------------------------------------- autoscaler --
def OBS(p99=None, occ=None, stragglers=None, shed=None, cursor=0):
    return {
        "p99_ms": p99,
        "occupancy": occ,
        "straggler_events": stragglers,
        "shed_total": shed,
        "fresh_cursor": cursor,
    }


def test_autoscale_policy_validation():
    with pytest.raises(ValueError):
        AutoscalePolicy(hysteresis=0)
    with pytest.raises(ValueError):
        AutoscalePolicy(cooldown_s=-1)
    with pytest.raises(ValueError):
        AutoscalePolicy(scale_down_below=1.0)
    with pytest.raises(ValueError):
        AutoscalePolicy(shrink_factor=1)
    with pytest.raises(ValueError):
        AutoscalePolicy(shed_high=0)


def test_autoscale_serving_scale_up_needs_hysteresis_of_fresh_windows():
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=2,
                                   cooldown_s=0.0))
    # breach 1 (fresh): no action yet
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=500, cursor=1), 0.0) is None
    # same cursor re-scraped: STALE — must not extend the streak
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=500, cursor=1), 1.0) is None
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=500, cursor=1), 2.0) is None
    # breach 2 (fresh): act
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=500, cursor=2), 3.0) == 2
    assert a.actions[-1]["action"] == "scale_up"


def test_autoscale_cooldown_bounds_one_action_per_window():
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=1,
                                   cooldown_s=30.0))
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=500, cursor=1), 0.0) == 2
    # still breached on fresh windows, but inside the cooldown
    assert a.propose("s", "serving", 2, 1, 4, OBS(p99=500, cursor=2), 10.0) is None
    assert a.propose("s", "serving", 2, 1, 4, OBS(p99=500, cursor=3), 29.0) is None
    # cooldown over (and the streak rebuilt post-action)
    assert a.propose("s", "serving", 2, 1, 4, OBS(p99=500, cursor=4), 31.0) == 3


def test_autoscale_serving_scale_down_when_far_under_slo():
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, scale_down_below=0.25,
                                   hysteresis=2, cooldown_s=0.0))
    assert a.propose("s", "serving", 3, 1, 4, OBS(p99=10, cursor=1), 0.0) is None
    assert a.propose("s", "serving", 3, 1, 4, OBS(p99=10, cursor=2), 1.0) == 2
    assert a.actions[-1]["action"] == "scale_down"
    # at min_world: never below
    a2 = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=1,
                                    cooldown_s=0.0))
    assert a2.propose("s", "serving", 1, 1, 4, OBS(p99=1, cursor=1), 0.0) is None


def test_autoscale_clamps_at_max_world():
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=1,
                                   cooldown_s=0.0))
    assert a.propose("s", "serving", 4, 1, 4, OBS(p99=500, cursor=1), 0.0) is None


def test_autoscale_occupancy_breach_also_scales_up():
    a = Autoscaler(AutoscalePolicy(occupancy_high=0.9, hysteresis=1,
                                   cooldown_s=0.0))
    assert a.propose("s", "serving", 1, 1, 4, OBS(occ=0.97, cursor=1), 0.0) == 2


def test_autoscale_shed_rate_breach_scales_up_with_hysteresis():
    """The survivability rule (schema v7): >= shed_high NEWLY shed requests
    per fresh window is overload evidence — sustained for the hysteresis,
    it scales serving up even with p99/occupancy silent."""
    a = Autoscaler(AutoscalePolicy(shed_high=2, hysteresis=2, cooldown_s=0.0))
    # first observation is the baseline counter — never a breach, whatever
    # the cumulative total already is
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=10, cursor=1), 0.0) is None
    # +3 shed in a fresh window: breach 1 of 2
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=13, cursor=2), 1.0) is None
    # +3 again: hysteresis met -> scale up
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=16, cursor=3), 2.0) == 2
    assert a.actions[-1]["action"] == "scale_up"
    assert "shed" in a.actions[-1]["why"]


def test_autoscale_shed_stale_window_is_not_evidence():
    """A re-scraped window (cursor unmoved) must not extend the shed streak
    — and the baseline only advances on FRESH windows, so the deferred
    delta still convicts once the engine makes progress."""
    a = Autoscaler(AutoscalePolicy(shed_high=2, hysteresis=1, cooldown_s=0.0))
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=10, cursor=1), 0.0) is None
    # shed_total climbed but the window is STALE: no action, baseline held
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=20, cursor=1), 1.0) is None
    # the same total on a fresh window: delta +10 vs the held baseline
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=20, cursor=2), 2.0) == 2


def test_autoscale_shed_below_threshold_never_acts():
    a = Autoscaler(AutoscalePolicy(shed_high=5, hysteresis=1, cooldown_s=0.0))
    assert a.propose("s", "serving", 1, 1, 4, OBS(shed=0, cursor=1), 0.0) is None
    for i in range(2, 6):  # +1 shed per window, under the threshold
        assert a.propose(
            "s", "serving", 1, 1, 4, OBS(shed=i - 1, cursor=i), float(i)
        ) is None
    assert a.actions == []


def test_autoscale_shed_rule_disabled_without_knob():
    # shed evidence flows through the observation, but shed_high=None
    # (the default) never arms the rule
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=1,
                                   cooldown_s=0.0))
    assert a.propose("s", "serving", 1, 1, 4, OBS(p99=5, shed=0, cursor=1), 0.0) is None
    assert a.propose("s", "serving", 1, 1, 4,
                     OBS(p99=5, shed=1000, cursor=2), 1.0) is None
    assert a.actions == []


def test_autoscale_training_shrinks_on_new_straggler_conviction():
    a = Autoscaler(AutoscalePolicy(cooldown_s=0.0, shrink_factor=2))
    # first observation establishes the baseline counter — no action
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=0, cursor=1), 0.0) is None
    # counter unchanged: no conviction
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=0, cursor=2), 1.0) is None
    # a NEW conviction shrinks by the factor
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=1, cursor=3), 2.0) == 2
    assert a.actions[-1]["action"] == "shrink"
    # already at min: convicted again, but nowhere to go
    assert a.propose("t", "training", 1, 1, 4, OBS(stragglers=2, cursor=4), 3.0) is None


def test_autoscale_straggler_conviction_survives_cooldown():
    """A conviction landing INSIDE the cooldown is evidence deferred, not
    evidence destroyed: the shrink fires once the cooldown ends."""
    a = Autoscaler(AutoscalePolicy(cooldown_s=30.0, shrink_factor=2))
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=0, cursor=1), 0.0) is None
    a._last_action["t"] = 1.0  # a prior action opened the cooldown window
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=1, cursor=2), 5.0) is None
    # same counter, cooldown over: the pending conviction still shrinks
    assert a.propose("t", "training", 4, 1, 4, OBS(stragglers=1, cursor=3), 32.0) == 2


def test_autoscale_dead_endpoint_is_no_evidence():
    a = Autoscaler(AutoscalePolicy(slo_p99_ms=100.0, hysteresis=1,
                                   cooldown_s=0.0))
    assert a.propose("s", "serving", 1, 1, 4, None, 0.0) is None
    assert a.actions == []


def test_autoscale_scraper_is_injectable_end_to_end():
    feed = [OBS(p99=900, cursor=1), OBS(p99=900, cursor=2)]
    a = Autoscaler(
        AutoscalePolicy(slo_p99_ms=100.0, hysteresis=2, cooldown_s=0.0),
        scraper=lambda run_dir: feed.pop(0),
    )
    assert a.observe_and_propose("s", "serving", "/x", 1, 1, 4, 0.0) is None
    assert a.observe_and_propose("s", "serving", "/x", 1, 1, 4, 1.0) == 2


# ------------------------------------------------------- prometheus parsing --
def test_parse_prometheus_families_and_labels():
    text = "\n".join([
        "# HELP tpuddp_serving_e2e_ms last-window end-to-end latency",
        "# TYPE tpuddp_serving_e2e_ms summary",
        'tpuddp_serving_e2e_ms{quantile="0.5"} 3.25',
        'tpuddp_serving_e2e_ms{quantile="0.99"} 17.5',
        "tpuddp_serving_completed_total 128",
        'tpuddp_serving_tenant_completed_total{tenant="a\\"b"} 7',
        "garbage line that is not a sample",
        "tpuddp_bad_value nan_is_not_here_but_text_is_skipped x",
    ])
    fam = parse_prometheus(text)
    assert metric_value(fam, "tpuddp_serving_e2e_ms", quantile="0.99") == 17.5
    assert metric_value(fam, "tpuddp_serving_completed_total") == 128
    assert metric_value(
        fam, "tpuddp_serving_tenant_completed_total", tenant='a"b'
    ) == 7
    assert metric_value(fam, "tpuddp_serving_e2e_ms", quantile="0.75") is None
    assert metric_value(fam, "tpuddp_absent_total") is None


# ------------------------------------------- stale exporter.port discovery --
def test_read_live_port_rejects_dead_port_file(tmp_path):
    """Satellite regression (ISSUE 11): a SIGKILLed run leaves exporter.port
    behind — readers must treat a port as live ONLY after /healthz answers,
    within a short timeout."""
    # bind-then-close: a real port that is guaranteed dead
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    (tmp_path / "exporter.port").write_text(f"{dead_port}\n")
    t0 = time.monotonic()
    assert read_live_port(str(tmp_path), probe_timeout=0.5) is None
    assert time.monotonic() - t0 < 5.0  # a short probe, not a hang


def test_read_live_port_missing_or_garbled_file(tmp_path):
    assert read_live_port(str(tmp_path)) is None
    (tmp_path / "exporter.port").write_text("not-a-port\n")
    assert read_live_port(str(tmp_path)) is None


def test_read_live_port_accepts_live_exporter(tmp_path):
    exporter = MetricsExporter(port=0, run_dir=str(tmp_path)).start()
    try:
        assert read_live_port(str(tmp_path), probe_timeout=2.0) == exporter.port
    finally:
        exporter.stop()


def test_read_live_port_probes_recorded_host_line(tmp_path):
    """The port file's line 2 names the BOUND host (legacy single-line files
    fall back to loopback, as do bind-all hosts): a non-loopback-bound
    exporter must be probed where it actually lives, not assumed dead."""
    exporter = MetricsExporter(port=0, run_dir=str(tmp_path)).start()
    try:
        port_file = tmp_path / "exporter.port"
        lines = port_file.read_text().splitlines()
        assert lines == [str(exporter.port), exporter.host]
        # legacy single-line file: loopback fallback still finds the server
        port_file.write_text(f"{exporter.port}\n")
        assert read_live_port(str(tmp_path), probe_timeout=2.0) == exporter.port
        # bind-all recorded host maps onto loopback for the probe
        port_file.write_text(f"{exporter.port}\n0.0.0.0\n")
        assert read_live_port(str(tmp_path), probe_timeout=2.0) == exporter.port
        # an explicit host override wins over the recorded line
        port_file.write_text(f"{exporter.port}\n127.0.0.1\n")
        assert (
            read_live_port(str(tmp_path), host="127.0.0.1", probe_timeout=2.0)
            == exporter.port
        )
    finally:
        exporter.stop()


def test_exporter_start_removes_stale_port_file_before_binding(tmp_path):
    """The writer half of the hardening: a leftover port file is cleared at
    start (pre-bind) and replaced by the LIVE port after bind."""
    stale = tmp_path / "exporter.port"
    stale.write_text("59999\n")
    exporter = MetricsExporter(port=0, run_dir=str(tmp_path))
    exporter.start()
    try:
        assert int(stale.read_text().splitlines()[0]) == exporter.port != 59999
    finally:
        exporter.stop()
    assert not stale.exists()


# ----------------------------------------------- supervisor fleet extensions --
def test_classify_exit_names_signals_and_contract_codes():
    assert classify_exit(-9) == "killed by SIGKILL"
    assert classify_exit(-15) == "killed by SIGTERM"
    assert classify_exit(75) == "preemption drain"
    assert classify_exit(76) == "stale peer"
    assert classify_exit(77) == "replica desync"
    assert classify_exit(1) == "crash"
    assert "signal" in classify_exit(-250)  # out-of-range signum still labels


def test_supervisor_request_stop_prevents_restart():
    calls = []

    def runner(argv, env):
        calls.append(dict(env))
        sup.request_stop()  # the controller preempts mid-flight
        return 75

    sup = RestartSupervisor(
        ["x"], runner=runner, sleep=lambda s: None,
        policy=SupervisorPolicy(backoff_base=0.01, backoff_cap=0.02),
    )
    assert sup.run() == 75  # surfaced, never relaunched
    assert len(calls) == 1


def test_supervisor_stop_before_first_launch_never_spawns():
    """A preemption landing before the FIRST child spawns must not run the
    job even once — preempted work holds no pool capacity."""
    calls = []
    sup = RestartSupervisor(
        ["x"], runner=lambda argv, env: calls.append(1) or 0,
    )
    sup.request_stop()
    assert sup.run() == 0
    assert calls == []


def test_supervisor_world_env_var_override_for_serving():
    calls = []

    def runner(argv, env):
        calls.append(dict(env))
        return 0

    sup = RestartSupervisor(
        ["x"], runner=runner, world_size=3,
        world_env_var="TPUDDP_SERVING_REPLICAS",
    )
    assert sup.run() == 0
    assert calls[0]["TPUDDP_SERVING_REPLICAS"] == "3"
    assert "TPUDDP_WORLD_SIZE" not in calls[0] or not os.environ.get(
        "TPUDDP_WORLD_SIZE"
    )


def test_supervisor_set_world_retargets_next_attempt():
    calls = []

    def runner(argv, env):
        calls.append(env.get("TPUDDP_WORLD_SIZE"))
        if len(calls) == 1:
            sup.set_world(2)  # the fleet rebalance lever
            return 75  # drain: relaunch immediately at the new world
        return 0

    sup = RestartSupervisor(["x"], runner=runner, world_size=4,
                            sleep=lambda s: None)
    assert sup.run() == 0
    assert calls == ["4", "2"]


def test_supervisor_popen_runner_exposes_live_child(tmp_path):
    sup = RestartSupervisor(
        [sys.executable, "-c", "import time; time.sleep(30)"],
        policy=SupervisorPolicy(max_restarts=0),
    )
    import threading

    t = threading.Thread(target=sup.run, daemon=True)
    t.start()
    deadline = time.monotonic() + 30
    while sup.child is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert sup.child is not None
    sup.request_stop()
    assert sup.signal_child(signal.SIGKILL)
    t.join(timeout=30)
    assert not t.is_alive()
    assert sup.history[-1][1] == -signal.SIGKILL


# -------------------------------------------------------------- controller --
def _trivial_spec(name, seconds=0.0, rc=0, **kw):
    code = f"import time; time.sleep({seconds}); raise SystemExit({rc})"
    return JobSpec(name=name, argv=(sys.executable, "-c", code), **kw)


def test_controller_admission_bounds(tmp_path):
    c = FleetController(2, fleet_dir=str(tmp_path), max_jobs=1)
    c.submit(_trivial_spec("a"))
    with pytest.raises(FleetAdmissionError) as e:
        c.submit(_trivial_spec("a"))
    assert e.value.reason == "duplicate_name"
    with pytest.raises(FleetAdmissionError) as e:
        c.submit(_trivial_spec("b"))
    assert e.value.reason == "fleet_full"
    with pytest.raises(FleetAdmissionError) as e:
        FleetController(2, fleet_dir=str(tmp_path)).submit(
            _trivial_spec("c", min_world=3, max_world=3)
        )
    assert e.value.reason == "bad_spec"


def test_controller_runs_trivial_jobs_to_done_with_namespaced_dirs(tmp_path):
    c = FleetController(2, fleet_dir=str(tmp_path))
    c.submit(_trivial_spec("a"))
    c.submit(_trivial_spec("b"))
    assert c.run_until(lambda ctl: ctl.training_complete(), poll=0.05,
                       timeout=60)
    status = {s["name"]: s for s in c.status()}
    assert status["a"]["state"] == "done"
    assert status["b"]["state"] == "done"
    assert status["a"]["run_dir"] == os.path.join(str(tmp_path), "jobs", "a")
    assert os.path.isdir(status["a"]["run_dir"])
    assert status["a"]["run_dir"] != status["b"]["run_dir"]


def test_controller_failed_job_reports_rc(tmp_path):
    c = FleetController(
        1, fleet_dir=str(tmp_path),
        supervisor_policy=SupervisorPolicy(backoff_base=0.01,
                                           backoff_cap=0.02),
    )
    c.submit(_trivial_spec("bad", rc=3, max_restarts=1))
    assert c.run_until(lambda ctl: ctl.training_complete(), poll=0.05,
                       timeout=60)
    s = c.status()[0]
    assert s["state"] == "failed" and s["exit_code"] == 3


def test_controller_stop_queued_job_without_spawn(tmp_path):
    c = FleetController(1, fleet_dir=str(tmp_path))
    c.submit(_trivial_spec("big", seconds=30.0))
    c.submit(_trivial_spec("waiting"))
    c.step()
    assert c.jobs["waiting"].state == "queued"  # gang-blocked behind big
    c.stop_job("waiting")
    assert c.jobs["waiting"].state == "preempted"
    c.stop_job("big")
    c.shutdown(timeout=60)
    assert c.jobs["big"].state == "preempted"


class _StubChild:
    """A 'live' Popen stand-in: poll() None until signalled/released."""

    def __init__(self):
        self.alive = True
        self.pid = -1

    def poll(self):
        return None if self.alive else 0

    def send_signal(self, sig):
        self.alive = False  # drains instantly

    def kill(self):
        self.alive = False


class _StubSupervisor:
    """Just the surface the controller's capacity/resize/drain machinery
    reads: the launched world (current_world), the retargeted next world
    (world_size), the live child, and the set_world/request_stop levers."""

    def __init__(self, current, target, child_alive=True):
        self._current_world = current
        self.world_size = target
        self.child = _StubChild() if child_alive else None
        self.set_world_calls = []
        self.stop_requested = False

    @property
    def current_world(self):
        return self._current_world

    def set_world(self, world):
        self.set_world_calls.append(world)
        self.world_size = world

    def request_stop(self):
        self.stop_requested = True


def test_controller_defers_start_while_drain_holds_devices(tmp_path):
    """Oversubscription regression: the plan's capacity math assumes a
    resize has LANDED, but the draining child still holds its launched
    world — a new gang must not start until the pool can really seat it."""
    c = FleetController(3, fleet_dir=str(tmp_path))
    # job-a was launched at 3 and is mid-drain down to 1: its child still
    # holds all 3 devices even though the supervisor is retargeted
    a = c.submit(_trivial_spec("a"))
    a.state = "running"
    a.supervisor = _StubSupervisor(current=3, target=1, child_alive=True)
    new = c.submit(_trivial_spec("new", min_world=2, max_world=2))
    c.step()
    assert c.last_plan.action("new") == "start"  # the PLAN seats it...
    assert new.state == "queued" and new.supervisor is None  # ...we defer
    # the drain lands: job-a's child exits, its supervisor holds world 1
    a.supervisor.child = None
    c.step()
    assert new.state == "running" and new.supervisor is not None
    c.shutdown(timeout=60)


def test_controller_defers_grow_while_drain_holds_devices(tmp_path):
    """Same invariant for a GROW resize: the grown job relaunches the
    moment its own (fast) drain lands — a neighbor's unfinished shrink must
    complete before the extra devices are claimed."""
    c = FleetController(4, fleet_dir=str(tmp_path))
    x = c.submit(_trivial_spec("x", min_world=2, max_world=3, priority=1))
    x.state = "running"
    x.supervisor = _StubSupervisor(current=2, target=2, child_alive=True)
    y = c.submit(_trivial_spec("y", min_world=1, max_world=2))
    y.state = "running"
    y.supervisor = _StubSupervisor(current=2, target=2, child_alive=True)
    y.desired = 1  # the autoscaler shrank y; x grows into the freed device
    c.step()
    assert c.last_plan.alloc == {"x": 3, "y": 1}
    assert y.supervisor.set_world_calls == [1]  # shrink proceeds
    assert x.supervisor.set_world_calls == []  # grow deferred: y holds 2
    y.supervisor.child = None  # y's drain lands (relaunches at 1)
    c.step()
    assert x.supervisor.set_world_calls == [3]


def test_controller_shutdown_cancels_queued_jobs(tmp_path):
    """shutdown() must not gang-place NEW work into the capacity its own
    preemptions free: queued jobs are cancelled, not started."""
    c = FleetController(1, fleet_dir=str(tmp_path))
    c.submit(_trivial_spec("long", seconds=30.0))
    c.step()
    waiting = c.submit(_trivial_spec("waiting"))
    c.shutdown(timeout=60)
    assert waiting.state == "preempted"
    assert waiting.supervisor is None  # never spawned
    assert c.jobs["long"].state == "preempted"


def test_escalate_drain_sigkills_only_after_grace(tmp_path):
    """Satellite (ISSUE 11): a child that ignores SIGTERM is SIGKILLed only
    after the grace window — never SIGKILL-first."""
    proc = subprocess.Popen(
        [
            sys.executable, "-u",
            os.path.join(REPO, "tests", "_chaos_wedge_worker.py"),
            str(tmp_path), "ignore-sigterm",
        ],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        assert "armed" in proc.stdout.readline()
        t0 = time.monotonic()
        rc = escalate_drain(proc, grace=1.5, poll=0.05)
        elapsed = time.monotonic() - t0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    assert rc == -signal.SIGKILL
    assert elapsed >= 1.5  # the drain window was honored before escalation
    assert classify_exit(rc) == "killed by SIGKILL"


def test_escalate_drain_returns_clean_drain_rc(tmp_path):
    proc = subprocess.Popen(
        [
            sys.executable, "-c",
            "import signal, sys, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: sys.exit(75))\n"
            "print('up', flush=True)\n"
            "time.sleep(60)\n",
        ],
        stdout=subprocess.PIPE, text=True,
    )
    try:
        assert proc.stdout.readline().strip() == "up"
        t0 = time.monotonic()
        rc = escalate_drain(proc, grace=30.0, poll=0.05)
        elapsed = time.monotonic() - t0
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    assert rc == 75
    assert elapsed < 25.0  # a draining child is never made to wait out grace


# ------------------------------------------------------------------ config --
def test_serving_config_honors_replica_env_override(monkeypatch):
    from tpuddp import config as config_lib

    monkeypatch.delenv("TPUDDP_SERVING_REPLICAS", raising=False)
    cfg = config_lib.serving_config({"serving": {"num_replicas": 1}})
    assert cfg["num_replicas"] == 1
    monkeypatch.setenv("TPUDDP_SERVING_REPLICAS", "3")
    cfg = config_lib.serving_config({"serving": {"num_replicas": 1}})
    assert cfg["num_replicas"] == 3
