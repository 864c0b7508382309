"""``scope_reduce`` on this PR's recorded chip capture (every number fixed),
on the older capture that has no scope (absent metrics and a reason, never a
zero), and on hand-made events (the phase rule, the layer path, the readers).
"""

import json
import os
import types

import pytest

from benchmark import cells, scope_reduce as sr, trace_reduce as tr
from benchmark.tests import xspace
from benchmark.tests.test_trace_reduce import _close, _host, _meta, _op

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPED = os.path.join(DATA, "scoped.trace.json.gz")
UNSCOPED = os.path.join(DATA, "recorded.trace.json.gz")
READERS = (
    "forward_ms_per_step", "backward_ms_per_step", "update_ms_per_step",
    "augment_ms_per_step", "scoped_device_time_pct",
)
PRE = "jit(multi)/while/body/closed_call/"


def test_recorded_scoped_capture_reduces_to_fixed_numbers():
    """``scoped.expected.json`` is what this reduction yielded when the
    capture was cut (``data/README.md``); and the phases account for every
    operation ``trace_reduce`` counts on the same plane."""
    got = sr.reduce_capture(SCOPED)
    with open(os.path.join(DATA, "scoped.expected.json")) as f:
        want = json.load(f)
    _close(json.loads(json.dumps(got)), want)
    assert got["scoped"]
    plane = tr.first_plane(tr.reduce_capture(SCOPED))
    assert got["op_s"] == pytest.approx(plane["op_s"], rel=1e-12)
    assert sum(got["phases_s"].values()) == pytest.approx(plane["op_s"], rel=1e-9)
    by_layer = sum(sum(v.values()) for v in got["layers_s"].values())
    assert by_layer == pytest.approx(
        sr.phase_seconds(got, "forward", sr.BACKWARD, sr.RECOMPUTE), rel=1e-9
    )
    # every convolution and matrix product of AlexNet under its own layer,
    # forward and backward
    for layer in ("0_Conv2d", "3_Conv2d", "6_Conv2d", "8_Conv2d", "10_Conv2d",
                  "16_Linear", "19_Linear", "21_Linear"):
        assert got["layers_s"][layer]["forward"] > 0, layer
        assert got["layers_s"][layer][sr.BACKWARD] > 0, layer


@pytest.mark.parametrize("tf_op,phase,layer", [
    (PRE + "jvp(tpuddp.forward)/3_Conv2d/conv_general_dilated:", "forward", "3_Conv2d"),
    (PRE + "transpose(jvp(tpuddp.forward))/3_Conv2d/conv_general_dilated:", "backward", "3_Conv2d"),
    # the form with the layer inside the group reads the same
    (PRE + "transpose(jvp(tpuddp.forward/3_Conv2d))/conv_general_dilated:", "backward", "3_Conv2d"),
    (PRE + "jvp(tpuddp.forward)/12_Bottleneck/conv2/conv_general_dilated", "forward", "12_Bottleneck/conv2"),
    (PRE + "transpose(jvp(tpuddp.forward))/12_Bottleneck/bn3/reduce_sum", "backward", "12_Bottleneck/bn3"),
    # a jitted helper inside a layer is not a layer
    (PRE + "jvp(tpuddp.forward)/12_Bottleneck/jit(relu)/max", "forward", "12_Bottleneck"),
    ("jit(f)/tpuddp.forward/5_Linear/dot_general", "forward", "5_Linear"),  # eval
    ("jit(f)/tpuddp.forward/add", "forward", sr.NO_LAYER),
    # jax.checkpoint: the backward proper, and the forward it recomputes
    ("jit(f)/transpose(jvp(tpuddp.forward))/jvp(tpuddp.forward)/checkpoint/1_Linear/dot_general",
     "backward", "1_Linear"),
    ("jit(f)/transpose(jvp(tpuddp.forward))/jvp(tpuddp.forward)/checkpoint/rematted_computation/1_Linear/dot_general",
     "recompute", "1_Linear"),
    (PRE + "jvp(tpuddp.loss)/jit(take_along_axis)/gather:", "loss", None),
    (PRE + "transpose(jvp(tpuddp.loss))/mul:", "loss", None),
    (PRE + "tpuddp.augment/jit(_resize)/dot_general:", "augment", None),
    (PRE + "tpuddp.optimizer/mul:", "optimizer", None),
    # the first scope names the phase: the update inside the firewall's cond
    (PRE + "tpuddp.guard/cond/branch_1_fun/tpuddp.optimizer/mul:", "guard", None),
    (PRE + "tpuddp.clip/sqrt:", "clip", None),
    (PRE + "tpuddp.exchange/psum:", "exchange", None),
    (PRE + "tpuddp.buffers/psum:", "buffers", None),
    (PRE + "tpuddp.metrics/mul:", "metrics", None),
    (PRE + "tpuddp.some_later_phase/mul:", "other_scoped", None),
    ("jit(multi)/while/body/dynamic_slice:", "unscoped", None),
    (PRE + "transpose(jvp())/conv_general_dilated:", "unscoped", None),  # the parent's
    ("", "unscoped", None),
])
def test_phase_and_layer_of_an_operation(tf_op, phase, layer):
    assert sr.attribute(tf_op) == (phase, layer)


def _scoped_events():
    return _meta() + [
        _host("bench:window", 0, 1000),
        _op("while.1", 0, 900),  # a container: its children are the work
        _op("fusion.1", 0, 100, tf_op=PRE + "tpuddp.augment/jit(_resize)/dot_general:"),
        _op("fusion.2", 100, 200, tf_op=PRE + "jvp(tpuddp.forward)/0_Conv2d/conv_general_dilated:"),
        _op("fusion.3", 300, 50, tf_op=PRE + "jvp(tpuddp.loss)/reduce_sum:"),
        _op("fusion.4", 350, 300, tf_op=PRE + "transpose(jvp(tpuddp.forward))/0_Conv2d/conv_general_dilated:"),
        _op("fusion.5", 650, 100, tf_op=PRE + "transpose(jvp(tpuddp.forward))/2_Linear/dot_general:"),
        _op("fusion.6", 750, 40, tf_op=PRE + "tpuddp.optimizer/mul:"),
        _op("fusion.7", 790, 10, tf_op=PRE + "tpuddp.guard/cond/branch_1_fun/tpuddp.clip/mul:"),
        _op("copy.8", 800, 50, tf_op="jit(multi)/while/body/dynamic_update_slice:"),
        _op("copy.8", 850, 50, tf_op="jit(multi)/while/body/dynamic_update_slice:"),
        _op("fusion.9", 2000, 50, tf_op=PRE + "tpuddp.optimizer/mul:"),  # outside the window
    ]


def test_reduction_of_hand_made_events():
    r = sr.reduce_events(_scoped_events())
    assert r["scoped"] and r["op_s"] == pytest.approx(900e-6)
    assert r["op_s"] == pytest.approx(tr.first_plane(tr.reduce_events(_scoped_events()))["op_s"])
    want = {"augment": 100, "forward": 200, "loss": 50, "backward": 400,
            "optimizer": 40, "guard": 10, "unscoped": 100}
    for phase in sr.PHASES:
        assert r["phases_s"][phase] == pytest.approx(want.get(phase, 0) * 1e-6), phase
    assert sr.phase_seconds(r, *sr.UPDATE_SCOPES) == pytest.approx(50e-6)
    assert list(r["layers_s"]) == ["0_Conv2d", "2_Linear"]  # model order
    assert r["layers_s"]["0_Conv2d"] == {
        "forward": pytest.approx(200e-6), "backward": pytest.approx(300e-6), "recompute": 0,
    }
    assert r["top_unscoped"] == [["copy.8", pytest.approx(100e-6)]]


def test_layers_come_in_model_order():
    names = ["10_Conv2d", "2_MaxPool2d", "12_Bottleneck/conv2", "12_Bottleneck/bn1",
             "0_Conv2d", sr.NO_LAYER, "12_Bottleneck"]
    assert sorted(names, key=sr._model_order) == [
        "0_Conv2d", "2_MaxPool2d", "10_Conv2d", "12_Bottleneck", "12_Bottleneck/bn1",
        "12_Bottleneck/conv2", sr.NO_LAYER,
    ]


def test_achieved_tflops_per_layer():
    r = sr.reduce_events(_scoped_events())
    r["layers_s"]["2_Linear"]["forward"] = 0.0  # fused into a neighbour: no time of its own
    got = sr.achieved_tflops(r, [(1e9, False), (5e8, True)], samples=10)
    assert got["0_Conv2d"]["forward"] == pytest.approx(2e10 / 200e-6 / 1e12)
    assert got["0_Conv2d"]["backward"] == pytest.approx(2e10 / 300e-6 / 1e12)  # no input gradient
    assert got["2_Linear"] == {"forward": None, "backward": pytest.approx(2e10 / 100e-6 / 1e12)}
    with pytest.raises(tr.TraceError, match="2 convolution/matrix-product layers"):
        sr.achieved_tflops(r, [(1e9, False)], samples=10)


def _run_with_capture(tmp_path, events, steps=2):
    """What ``run.py`` hands a reader, with ``events`` written as the
    profiler writes a capture under ``<root>/.bench_out/<cell>/trace`` and
    read back from there."""
    trace_dir = tmp_path / ".bench_out" / "a_cell" / "trace"
    xspace.write_capture(events, str(trace_dir / "plugins" / "profile" / "t"))
    events = tr.capture_events(str(trace_dir))
    return {
        "cell": types.SimpleNamespace(root=str(tmp_path), name="a_cell"),
        "trace": tr.reduce_events(events), "events": events, "window": {"steps": steps},
    }


def _read_all(run):
    return {name: cells.load_module("layer_metrics", name).read(run) for name in READERS}


def test_the_five_readers_share_one_reduction(tmp_path, capsys):
    run = _run_with_capture(tmp_path, _scoped_events())
    got = _read_all(run)
    assert got == {
        "forward_ms_per_step": pytest.approx(0.125),  # forward + loss
        "backward_ms_per_step": pytest.approx(0.2),
        "update_ms_per_step": pytest.approx(0.025),  # clip + guard + optimizer
        "augment_ms_per_step": pytest.approx(0.05),
        "scoped_device_time_pct": pytest.approx(100 * 800 / 900),
    }
    # both tables once, as one JSON line on stderr
    lines = [line for line in capsys.readouterr().err.splitlines() if line]
    assert len(lines) == 1
    tables = json.loads(lines[0])["scope_reduce"]
    assert tables["phases_s"]["backward"] == pytest.approx(400e-6)
    assert "0_Conv2d" in tables["layers_s"]


@pytest.mark.parametrize("events", [
    "the chip capture PR 22 recorded, before the scopes",
    "hand-made, every operation unnamed",
])
def test_a_capture_with_no_scope_yields_no_value_and_says_why(tmp_path, capsys, events):
    """A stale compile cache, or the parent commit's program: absent, not
    zero, and one line on stderr with the reason."""
    if events.startswith("the chip capture"):
        events = tr.load_events(UNSCOPED)
    else:
        events = [
            dict(e, args={"tf_op": PRE + "transpose(jvp())/conv_general_dilated:"})
            if e.get("pid") == 1 and e.get("ph") == "X" else e
            for e in _scoped_events()
        ]
    assert not sr.reduce_events(events)["scoped"]
    run = _run_with_capture(tmp_path, events)
    assert _read_all(run) == dict.fromkeys(READERS)
    lines = [line for line in capsys.readouterr().err.splitlines() if line]
    assert len(lines) == 1
    assert "no 'tpuddp.' scope" in lines[0] and "compile cache" in lines[0]
    assert "absent, not zero" in lines[0]


def test_no_device_plane_and_no_traced_window_yield_nothing(tmp_path, capsys):
    run = _run_with_capture(tmp_path, _scoped_events())
    run["events"] = [e for e in run["events"] if e.get("pid") == -1]  # the host's spans alone
    assert _read_all(run) == dict.fromkeys(READERS)
    assert "no device plane" in capsys.readouterr().err
    untraced = dict(run, trace=None, events=None)
    untraced.pop(sr._KEY)
    assert _read_all(untraced) == dict.fromkeys(READERS)
    assert capsys.readouterr().err == ""


def test_the_command_prints_both_tables(capsys):
    assert sr.main([SCOPED, "--steps", "2", "--flops", "alexnet_cifar224", "--batch", "2048"]) == 0
    out = capsys.readouterr().out
    assert "ms/step" in out and "backward" in out and "update = clip + guard + optimizer" in out
    assert "3_Conv2d" in out and "fwd TFLOP/s" in out
    assert sr.main([UNSCOPED]) == 2
    assert "no 'tpuddp.' scope" in capsys.readouterr().err
