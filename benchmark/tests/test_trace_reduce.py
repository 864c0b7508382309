"""``trace_reduce`` on a recorded chip capture (every number fixed) and on
hand-made events (the interval arithmetic, containers, collectives)."""

import json
import os

import pytest

from benchmark import scope_reduce as sr, trace_reduce as tr
from benchmark.tests import xspace

DATA = os.path.join(os.path.dirname(__file__), "data")
ALEXNET_SHAPES = {
    "11,11,3,64", "64", "5,5,64,192", "192", "3,3,192,384", "384", "3,3,384,256",
    "256", "3,3,256,256", "9216,4096", "4096", "4096,4096", "4096,10", "10",
}


def _close(got, want, path="result"):
    if isinstance(want, dict):
        assert set(got) == set(want), path
        for key in want:
            _close(got[key], want[key], f"{path}.{key}")
    elif isinstance(want, list):
        assert len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            _close(g, w, f"{path}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=1e-9, abs=1e-12), path
    else:
        assert got == want, path


def test_recorded_capture_reduces_to_fixed_numbers():
    """``recorded.expected.json`` is what this reduction yielded when the
    capture was cut (see ``data/README.md``); any change to the yardstick
    shows here."""
    got = tr.reduce_capture(os.path.join(DATA, "recorded.trace.json.gz"), ALEXNET_SHAPES)
    with open(os.path.join(DATA, "recorded.expected.json")) as f:
        want = json.load(f)
    _close(json.loads(json.dumps(got)), want)
    # and what must hold of any reduction
    for plane in got["planes"].values():
        assert plane["busy_s"] <= got["window_s"] + 1e-12
        assert plane["collective_exposed_s"] <= plane["collective_s"] + 1e-12
        assert sum(plane["buckets_s"].values()) == pytest.approx(plane["op_s"])
    shown = tr.breakdown(got)
    assert len(shown["device_ops"]) <= 10 and len(shown["idle_gaps"]) <= 10


@pytest.mark.parametrize("fixture,reduce,shapes", [
    ("recorded", tr.reduce_capture, (ALEXNET_SHAPES,)),
    ("scoped", sr.reduce_capture, ()),
])
def test_the_profilers_own_file_reduces_to_the_same_numbers(tmp_path, fixture, reduce, shapes):
    """The fixtures are the trace viewer's JSON; the chip's captures are read
    from the ``.xplane.pb``. The same device events in that file (names and
    stats on the event metadata, times in picoseconds from the line's start)
    with the annotations as host spans beside it give the fixture's numbers,
    through ``find_capture``'s choice of file."""
    events = tr.load_events(os.path.join(DATA, fixture + ".trace.json.gz"))
    profile = tmp_path / "plugins" / "profile" / "t"
    xspace.write_capture(events, str(profile))
    (profile / "host.trace.json.gz").write_bytes(b"not read where there is an .xplane.pb")
    assert tr.find_capture(str(tmp_path)).endswith("host.xplane.pb")
    with open(os.path.join(DATA, fixture + ".expected.json")) as f:
        want = json.load(f)
    _close(json.loads(json.dumps(reduce(str(tmp_path), *shapes))), want)


def test_a_capture_above_the_viewers_cap_still_reduces(tmp_path):
    """Staging one 157 MB chunk logs about a million host "Transpose"
    slices, and the trace viewer's file stops at a million events, before
    the first device row: the loader-fed cell's traced run died there (PR
    22). The ``.xplane.pb`` has no cap, a host plane in it is passed over
    whole, and what the host did is in the harness's own spans."""
    cap = 1_000_000
    flood = [
        {"ph": "X", "pid": 9, "tid": 1, "ts": 10 + i * 1e-3, "dur": 5e-4, "name": "Transpose", "args": {}}
        for i in range(cap + 1)
    ]
    events = _meta() + [_host("bench:window", 0, 2000), _host("bench:stage", 0, 1500)] + flood + [
        _op("fusion.1", 1500, 400, tf_op="jit(f)/tpuddp.forward/0_Conv2d/conv_general_dilated:"),
    ]
    kept = tr.capture_events(xspace.write_capture(events, str(tmp_path)))
    assert len(kept) < 20  # the flood is passed over, not copied
    r = tr.reduce_events(kept)
    plane = tr.first_plane(r)
    assert plane["busy_s"] == pytest.approx(400e-6) and r["window_s"] == pytest.approx(2000e-6)
    assert plane["idle_by_host_activity_s"] == {"stage": pytest.approx(1500e-6), "none": pytest.approx(100e-6)}
    assert sr.reduce_events(kept)["layers_s"]["0_Conv2d"]["forward"] == pytest.approx(400e-6)


def test_a_file_that_is_no_capture_is_an_error(tmp_path):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(b"\x0a\xff\xff\xff\x7f truncated")
    with pytest.raises(tr.TraceError, match="not an XSpace"):
        tr.load_events(str(path))
    with pytest.raises(tr.TraceError, match="no \\*.xplane.pb"):
        tr.find_capture(str(tmp_path / "nowhere"))


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]


def _meta():
    return [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name", "args": {"name": "Steps"}},
        {"ph": "M", "pid": 9, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name", "args": {"name": "main"}},
    ]


def _op(name, ts, dur, tid=1, **args):
    return {"ph": "X", "pid": 1, "tid": tid, "ts": ts, "dur": dur, "name": name, "args": args}


def _host(name, ts, dur):
    return {"ph": "X", "pid": 9, "tid": 1, "ts": ts, "dur": dur,
            "name": name.split(":")[-1], "args": {"long_name": name}}


def test_busy_idle_containers_and_gap_attribution():
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _host("bench:dispatch", 0, 100),
        _host("bench:readback", 100, 900),
        _host("bench:loader_next", 600, 50),  # the innermost wins
        _op("while.1", 100, 400),  # encloses the two below: not work itself
        _op("fusion.1", 100, 150, tf_op="jit(f)/conv_general_dilated:"),
        _op("fusion.2", 300, 200, source="/x/tpuddp/optim.py:216"),
        _op("copy.3", 700, 100),
        _op("step-marker", 0, 1000, tid=2),  # another thread of the device
        _op("fusion.9", 2000, 50),  # outside the window
    ]
    r = tr.reduce_events(events)
    plane = r["planes"]["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(1000e-6)
    assert plane["busy_s"] == pytest.approx(450e-6) and plane["n_ops"] == 3
    assert r["idle_pct"] == pytest.approx(55.0)
    assert plane["buckets_s"]["fwd/input-grad conv+matmul"] == pytest.approx(150e-6)
    assert plane["buckets_s"]["weight-grad + optimizer (fused)"] == pytest.approx(200e-6)
    assert plane["buckets_s"]["copies/slices"] == pytest.approx(100e-6)
    # gaps: 0-100 under dispatch; 250-300, 500-700 and 800-1000 under
    # readback, but for 600-650 of the longest, which the loader's span takes
    assert plane["longest_gaps"][0] == ["readback", pytest.approx(200e-6)]
    assert plane["idle_by_host_activity_s"] == {
        "readback": pytest.approx(400e-6), "dispatch": pytest.approx(100e-6),
        "loader_next": pytest.approx(50e-6),
    }


def test_collectives_synchronous_and_asynchronous():
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("fusion.1", 0, 300),
        _op("all-reduce.1", 300, 100),  # synchronous: wholly exposed
        _op("all-gather-start.2", 400, 10),
        _op("fusion.2", 410, 140),  # hides most of the asynchronous one
        _op("all-gather-done.2", 550, 50),
        _op("psum.161", 900, 50, hlo_category="all-reduce"),  # named after the primitive
    ]
    plane = tr.reduce_events(events)["planes"]["/device:TPU:0"]
    assert plane["collective_s"] == pytest.approx((100 + 200 + 50) * 1e-6)
    assert plane["collective_exposed_s"] == pytest.approx((100 + 10 + 50 + 50) * 1e-6)
    assert plane["buckets_s"]["collective"] == pytest.approx((100 + 10 + 50 + 50) * 1e-6)
    assert plane["busy_s"] == pytest.approx(650e-6)  # waiting in a collective is busy


@pytest.mark.parametrize("shape,params,expected", [
    ("(f32[9216,4096]{1,0}, f32[9216,4096]{1,0}, f32[9216,4096]{1,0})", None, True),
    ("(f32[9216,4096]{1,0}, bf16[9216,4096]{1,0}, bf16[9216,4096]{1,0})", None, True),
    ("(f32[256]{0}, f32[256]{0}, f32[256]{0}, bf16[256,56,56,64]{3,2,1,0})", None, False),
    ("(bf16[256,56,56,64]{3,2,1,0}, bf16[256,56,56,64]{3,2,1,0}, bf16[256,56,56,64]{3,2,1,0})",
     {"9216,4096"}, False),
    ("(f32[3,3,64,64]{3,2,1,0}, f32[3,3,64,64]{3,2,1,0}, f32[3,3,64,64]{3,2,1,0})",
     {"3,3,64,64"}, True),
    ("f32[9216,4096]{1,0}", None, False),
])
def test_optimizer_update_rule(shape, params, expected):
    assert tr._looks_like_optimizer_update(shape, params) is expected


def test_a_capture_without_a_device_or_a_window_is_an_error():
    with pytest.raises(tr.TraceError, match="no device plane"):
        tr.reduce_events(_meta()[3:] + [_host("bench:window", 0, 10)])
    with pytest.raises(tr.TraceError, match="bench:window"):
        tr.reduce_events(_meta() + [_op("fusion.1", 0, 5)])
    with pytest.raises(tr.TraceError, match="no operation ran"):
        tr.reduce_events(_meta() + [_host("bench:window", 0, 10), _op("fusion.1", 50, 5)])
