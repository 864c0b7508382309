"""``narrow_head_attention_kernel_ms_per_step`` on hand-made events: the fused
attention kernels of the convolution-and-attention model, a group of heads a
call included; what stands round them under the ``attention`` scope is the
scope reader's and not this one's; nothing in another family's capture."""

import pytest

from benchmark import cells
from benchmark.tests.test_token_conv_moe_lm import BWD, FWD, PRE, REMAT, SPLASH, _events, _run_with
from benchmark.tests.test_trace_reduce import _host, _meta, _op

ATTENTION = "1_FullAttention/while/body/checkpoint/attention/"
# the backward's one kernel, a group of heads a call: under the groups' loop
SPLASH_DKV = "while/body/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals/splash_mha_dkv_no_residuals/pallas_call:"


def _read(events, name="narrow_head_attention_kernel_ms_per_step", **run):
    return cells.load_module("layer_metrics", name).read(_run_with(events, **run))


def _with(ops, start=200_000.0):
    """The cell's hand-made events with ``ops`` (name, scope, ms) after them, inside the 400 ms window."""
    events, ts = _events(), start
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * 1000.0, tf_op=tf_op))
        ts += ms * 1000.0
    return events


def test_the_kernels_are_split_from_what_stands_round_them():
    """Forward, recomputed forward and the grouped backward's kernel count;
    the partials' sum and the groups' slices, under the same scope, do not."""
    events = _with([
        ("splash_mha_fwd_residuals.22", REMAT + ATTENTION + SPLASH, 6),
        ("splash_mha_dkv_no_residuals.7", BWD + ATTENTION + SPLASH_DKV, 5),
        ("reduce.88", BWD + ATTENTION + "reduce_sum:", 2),  # the partial dq's summed
        ("fusion.412", BWD + ATTENTION + "while/body/dynamic_slice:", 1),  # a group's heads cut out
    ])
    assert _read(events) == pytest.approx((7 + 6 + 5) / 2)
    scope = _read(events, "narrow_head_attention_ms_per_step")
    assert scope == pytest.approx((7 + 3 + 11 + 6 + 5 + 2 + 1) / 2)
    # the accepted reader, which this one asks once the model is known, reads the same
    assert _read(events, "attention_kernel_ms_per_step") == pytest.approx((7 + 6 + 5) / 2)


def test_the_two_kernel_backward_is_counted_under_both_names():
    """The parent's program in this cell: ``dkv`` and ``dq`` apart."""
    lib = "vmap(jit(_splash_attention))/"
    events = _with([
        ("splash_mha_dkv_no_residuals.7", BWD + ATTENTION + lib + "splash_mha_dkv_no_residuals/pallas_call:", 9),
        ("splash_mha_dq_no_residuals.7", BWD + ATTENTION + lib + "splash_mha_dq_no_residuals/pallas_call:", 8),
    ])
    assert _read(events) == pytest.approx((7 + 9 + 8) / 2)


def test_a_blockwise_program_of_this_model_reads_zero():
    """Every CPU-sized run: the model's scopes and no kernel."""
    events = _meta() + [
        _host("bench:window", 0, 100_000.0),
        _op("f1", 0, 4000.0, tf_op=FWD + "0_ShortConv/while/body/checkpoint/conv/mul:"),
        _op("f2", 4000.0, 6000.0, tf_op=FWD + ATTENTION + "checkpoint/dot_general:"),
    ]
    assert _read(events) == 0.0


def test_nothing_in_another_familys_capture_nor_untraced():
    """Another token cell's fused kernels are the accepted reader's, not this
    model's; no capture, or one with no scope, gives nothing and raises
    nothing."""
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("splash_mha_fwd_residuals.3", 0, 500, tf_op=FWD + "3_FullAttention/checkpoint/attention/" + SPLASH),
        _op("f4", 500, 100, tf_op=PRE + "tpuddp.optimizer/mul:"),
    ]
    assert _read(events) is None
    assert _read(events, "attention_kernel_ms_per_step") == pytest.approx(0.5 / 2)
    reader = cells.load_module("layer_metrics", "narrow_head_attention_kernel_ms_per_step")
    untraced = _run_with(None)
    untraced["trace"] = None
    assert reader.read(untraced) is None
    bare = _run_with(_meta() + [_host("bench:window", 0, 1000), _op("f1", 0, 500, tf_op="jit(f)/mul:")])
    assert reader.read(bare) is None
