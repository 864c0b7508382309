"""``attention_kernel_ms_per_step`` on hand-made events: the fused attention
kernels' own operations, by the name the capture gives them or by their
scope; 0.0 in a program whose attention runs blockwise; nothing untraced."""

import pytest

from benchmark import cells
from benchmark.tests.test_token_moe_lm import BWD, FWD, PRE, REMAT, _run_with
from benchmark.tests.test_trace_reduce import _host, _meta, _op

KERNEL = "3_GatedAttention/while/body/closed_call/checkpoint/attention/vmap(jit(_splash_attention))/"


def _read(ops, steps=2):
    events, ts = _meta() + [_host("bench:window", 0, 100_000.0)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * 1000.0, tf_op=tf_op))
        ts += ms * 1000.0
    run_ = _run_with(events, steps=steps)
    read = lambda metric: cells.load_module("layer_metrics", metric).read(run_)
    return read("attention_kernel_ms_per_step"), read("attention_ms_per_step")


def test_the_kernels_are_counted_and_what_stands_round_them_is_not():
    fwd = KERNEL + "splash_mha_fwd_residuals/splash_mha_fwd_residuals/"
    dkv = KERNEL + "splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/"
    kernel, attention = _read([
        ("splash_mha_fwd_residuals.19", FWD + fwd + "pallas_call:", 4),
        ("splash_mha_fwd_residuals.20", REMAT + fwd + "pallas_call:", 4),
        ("splash_mha_dkv_no_residuals.11", BWD + dkv + "pallas_call:", 9),
        ("fusion.3446", BWD + dkv + "squeeze:", 1),  # inside the library's scope, not a kernel
        ("reduce.1532", BWD + KERNEL + "reduce_sum:", 1),  # the partial dq's summed
        ("custom-call.7", BWD + dkv + "pallas_call:", 2),  # a kernel under another name: by its scope
        ("splash_mha_dq_no_residuals.3", "splash_mha_dq_no_residuals", 3),  # one that lost its scope: by name
        ("f1", FWD + "3_GatedAttention/while/body/closed_call/checkpoint/qkv/dot_general:", 5),
        ("f2", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernel == pytest.approx((4 + 4 + 9 + 2 + 3) / 2)
    assert attention == pytest.approx((4 + 4 + 9 + 1 + 1 + 2 + 5) / 2)  # the scope's: not the unscoped kernel


def test_a_blockwise_program_reads_zero():
    """The parent's program and every CPU-sized one: the family's scopes and
    no kernel."""
    kernel, attention = _read([
        ("f9", FWD + "3_GatedAttention/while/body/checkpoint/attention/checkpoint/dot_general:", 7),
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert kernel == 0.0 and attention == pytest.approx(3.5)


def test_nothing_where_there_is_no_capture_or_no_scope():
    run_ = _run_with(None)
    run_["trace"] = None
    reader = cells.load_module("layer_metrics", "attention_kernel_ms_per_step")
    assert reader.read(run_) is None
    bare = _run_with(_meta() + [_host("bench:window", 0, 1000), _op("f1", 0, 500, tf_op="jit(f)/mul:")])
    assert reader.read(bare) is None
