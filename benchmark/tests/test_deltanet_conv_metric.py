"""``deltanet_conv_ms_per_step`` and ``deltanet_conv_kernel_ms_per_step`` on
hand-made events: the time under the mixers' ``conv`` scope in whichever
lowering, and of it the fused kernels' own operations, by the name the
capture gives them or by their scope; 0.0 in a program whose scope runs
plain; nothing untraced."""

import pytest

from benchmark import cells
from benchmark.tests.test_token_moe_lm import BWD, FWD, PRE, REMAT, _run_with
from benchmark.tests.test_trace_reduce import _host, _meta, _op

MIXER = "1_GatedDeltaNet/while/body/closed_call/checkpoint/"
CONV, SCAN = MIXER + "conv/", MIXER + "scan/"
READERS = ("deltanet_conv_ms_per_step", "deltanet_conv_kernel_ms_per_step", "deltanet_kernel_ms_per_step")


def _read(ops, steps=2):
    events, ts = _meta() + [_host("bench:window", 0, 100_000.0)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * 1000.0, tf_op=tf_op))
        ts += ms * 1000.0
    run_ = _run_with(events, steps=steps)
    return tuple(cells.load_module("layer_metrics", metric).read(run_) for metric in READERS)


def test_the_scope_and_its_kernels_are_counted_and_the_scans_are_not():
    fwd, bwd = CONV + "deltanet_conv_fwd/", CONV + "deltanet_conv_bwd/"
    scope, kernels, scan_kernels = _read([
        ("deltanet_conv_fwd.5", FWD + fwd + "pallas_call:", 2),
        ("deltanet_conv_fwd.6", REMAT + fwd + "pallas_call:", 2),
        ("deltanet_conv_bwd.2", BWD + bwd + "pallas_call:", 3),
        ("fusion.12", BWD + CONV + "convert_element_type:", 1),  # what stands round the kernels
        ("custom-call.9", BWD + bwd + "pallas_call:", 4),  # a kernel under another name: by its scope
        ("deltanet_conv_bwd.3", "deltanet_conv_bwd", 5),  # one that lost its scope: by name
        ("deltanet_chunk_fwd.7", FWD + SCAN + "deltanet_chunk_fwd/pallas_call:", 6),  # the scan's: another metric's
        ("f2", FWD + MIXER + "in_proj/dot_general:", 7),
        ("f3", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernels == pytest.approx((2 + 2 + 3 + 4 + 5) / 2)
    assert scope == pytest.approx((2 + 2 + 3 + 1 + 4) / 2)  # the scope's: not the unscoped kernel
    assert scan_kernels == pytest.approx(6 / 2)


def test_a_plain_scope_reads_its_time_and_no_kernel():
    """The parent's program and every CPU-sized one: the family's scopes and
    no kernel of this name."""
    scope, kernels, _ = _read([
        ("f9", FWD + CONV + "mul:", 7),
        ("f10", BWD + CONV + "pad:", 5),
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernels == 0.0 and scope == pytest.approx(6.0)


@pytest.mark.parametrize("metric", READERS[:2])
def test_nothing_where_there_is_no_capture_or_no_scope(metric):
    run_ = _run_with(None)
    run_["trace"] = None
    reader = cells.load_module("layer_metrics", metric)
    assert reader.read(run_) is None
    bare = _run_with(_meta() + [_host("bench:window", 0, 1000), _op("f1", 0, 500, tf_op="jit(f)/mul:")])
    assert reader.read(bare) is None
