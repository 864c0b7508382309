"""The four readers of the program's own pass-boundary spans on a small
hand-made run: two threads' overlapping spans, a window that closes before an
abandoned pass, a cell without the spans."""

import pytest

from benchmark import cells, trace_reduce as tr
from benchmark.layer_metrics import _program_spans

READERS = (
    "stage_stack_ms_per_step", "stage_put_ms_per_step",
    "loader_assemble_ms_per_step", "idle_under_program_span_pct",
)


def _reader(name):
    return cells.load_module("layer_metrics", name)


def _op(ts, dur, name="fusion.1"):
    return {"ph": "X", "pid": 1, "tid": 1, "ts": ts, "dur": dur, "name": name, "args": {}}


def _events(host_spans):
    """A device plane with two operations and the harness's host spans laid
    on the capture's clock as ``capture_events`` lays them."""
    return [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name", "args": {"name": tr.DEVICE_THREAD}},
        _op(400, 300), _op(700, 100, "fusion.2"),
    ] + tr.host_span_events(
        [[name, 1000 * t0, 1000 * t1] for name, t0, t1 in host_spans], start_ns=0
    )


def _run(host_spans, steps=4, window_s=1000e-6):
    events = _events(host_spans)
    return {
        "events": events, "trace": tr.reduce_events(events),
        "window": {"steps": steps, "window_s": window_s, "counters": {}},
        "spans": {"seconds": {}, "counts": {}},
    }


# one pass of a loader-fed cell in 1,000 us; the device runs 400-800. The
# runner waits 0-200 on two workers whose gathers overlap, stages 200-390
# (stack, then put), dispatches, and reads back; the clock then stops a
# second pass in its first wait (1,000-1,200: after the last value fetch)
PASS = [
    ("window", 0, 1200),
    ("input_wait", 0, 100), ("loader_next", 5, 95), ("input_wait", 100, 200),
    ("loader_order", 0, 10),
    ("loader_gather", 10, 90), ("loader_gather", 20, 120),  # two threads
    ("loader_pad", 90, 100), ("loader_pad", 120, 150),
    ("stage", 200, 390), ("stage_stack", 200, 280), ("stage_put", 280, 388),
    ("dispatch", 390, 400), ("readback", 800, 1000),
    ("input_wait", 1000, 1200), ("loader_gather", 1010, 1100),
]


def test_the_three_span_totals_over_steps():
    run = _run(PASS)
    assert _reader("stage_stack_ms_per_step").read(run) == pytest.approx(0.080 / 4)
    assert _reader("stage_put_ms_per_step").read(run) == pytest.approx(0.108 / 4)
    # both threads' gathers count whole (80 + 100), the abandoned pass's does not
    assert _reader("loader_assemble_ms_per_step").read(run) == pytest.approx(
        (0.010 + 0.080 + 0.100 + 0.010 + 0.030) / 4
    )


def test_a_span_that_straddles_the_last_value_fetch_counts_up_to_it():
    run = _run([("window", 0, 1200), ("stage_put", 900, 1100)])
    assert _reader("stage_put_ms_per_step").read(run) == pytest.approx(0.100 / 4)


def test_idle_time_under_the_programs_spans():
    # idle: 0-400 and 800-1200. Covered: 0-200 (waits), 200-400 (stage,
    # dispatch), 800-1200 (readback, the second pass's wait): all of it
    assert _reader("idle_under_program_span_pct").read(_run(PASS)) == pytest.approx(100.0)
    # the harness's own wrapper and a gap under nothing do not count
    bare = [
        ("window", 0, 1200), ("loader_next", 0, 200), ("stage", 200, 400),
        ("between_passes", 800, 900), ("readback", 900, 1000),
    ]
    assert _reader("idle_under_program_span_pct").read(_run(bare)) == pytest.approx(
        100.0 * (200 + 100) / 800
    )
    assert tr.first_plane(_run(bare)["trace"])["idle_by_host_activity_s"]["loader_next"] == (
        pytest.approx(200e-6)
    )


@pytest.mark.parametrize("name", READERS)
def test_a_run_without_the_spans_reads_nothing(name):
    """A resident cell, or a program built before the spans existed, under
    these readers: the harness's own spans only, or no traced window."""
    resident = _run([("window", 0, 1200), ("loader_next", 0, 200), ("between_passes", 800, 900)])
    assert _reader(name).read(resident) is None
    untraced = dict(resident, events=None, trace=None)
    assert _reader(name).read(untraced) is None
    no_window = _run(PASS)
    no_window["events"] = [e for e in no_window["events"] if e.get("name") != "window"]
    assert _reader(name).read(no_window) is None
    assert _reader(name).read(dict(_run(PASS), window={"steps": 0, "window_s": 0.0})) is None or (
        name == "idle_under_program_span_pct"
    )


def test_the_window_and_the_intervals():
    events = _events(PASS)
    assert _program_spans.window(events) == (0.0, 1200.0)
    assert sorted(_program_spans.intervals(events, ("loader_pad", "no_such"))) == [
        (90.0, 100.0), (120.0, 150.0)
    ]
    assert _program_spans.window(_events([("stage", 0, 1)])) is None
