"""``deltanet_kernel_ms_per_step`` on hand-made events: the scan's fused
kernels' own operations, by the name the capture gives them or by their
scope; 0.0 in a program whose scan runs plain; nothing untraced."""

import pytest

from benchmark import cells
from benchmark.tests.test_token_moe_lm import BWD, FWD, PRE, REMAT, _run_with
from benchmark.tests.test_trace_reduce import _host, _meta, _op

SCAN = "1_GatedDeltaNet/while/body/closed_call/checkpoint/scan/"


def _read(ops, steps=2):
    events, ts = _meta() + [_host("bench:window", 0, 100_000.0)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * 1000.0, tf_op=tf_op))
        ts += ms * 1000.0
    run_ = _run_with(events, steps=steps)
    read = lambda metric: cells.load_module("layer_metrics", metric).read(run_)
    return read("deltanet_kernel_ms_per_step"), read("linear_attention_ms_per_step")


def test_the_kernels_are_counted_and_what_stands_round_them_is_not():
    fwd, bwd = SCAN + "deltanet_chunk_fwd/", SCAN + "deltanet_chunk_bwd/"
    kernel, linear = _read([
        ("deltanet_chunk_fwd.5", FWD + fwd + "pallas_call:", 2),
        ("deltanet_chunk_fwd.6", REMAT + fwd + "pallas_call:", 2),
        ("deltanet_chunk_bwd.2", BWD + bwd + "pallas_call:", 3),
        ("fusion.12", BWD + SCAN + "reduce_sum:", 1),  # the shared key heads' gradients summed
        ("custom-call.9", BWD + bwd + "pallas_call:", 4),  # a kernel under another name: by its scope
        ("deltanet_chunk_bwd.3", "deltanet_chunk_bwd", 5),  # one that lost its scope: by name
        ("f1", FWD + SCAN + "while/body/dot_general:", 6),  # the carry
        ("f2", FWD + "1_GatedDeltaNet/while/body/closed_call/checkpoint/in_proj/dot_general:", 7),
        ("splash_mha_fwd_residuals.19", FWD + "3_GatedAttention/attention/splash_mha_fwd/pallas_call:", 8),
        ("f3", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernel == pytest.approx((2 + 2 + 3 + 4 + 5) / 2)
    assert linear == pytest.approx((2 + 2 + 3 + 1 + 4 + 6 + 7) / 2)  # the scope's: not the unscoped kernel


def test_a_plain_scan_reads_zero():
    """The parent's program and every CPU-sized one: the family's scopes and
    no kernel."""
    kernel, linear = _read([
        ("f9", FWD + SCAN + "dot_general:", 7),
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernel == 0.0 and linear == pytest.approx(3.5)


def test_nothing_where_there_is_no_capture_or_no_scope():
    run_ = _run_with(None)
    run_["trace"] = None
    reader = cells.load_module("layer_metrics", "deltanet_kernel_ms_per_step")
    assert reader.read(run_) is None
    bare = _run_with(_meta() + [_host("bench:window", 0, 1000), _op("f1", 0, 500, tf_op="jit(f)/mul:")])
    assert reader.read(bare) is None
