"""``deltanet_carry_kernel_ms_per_step`` on hand-made events: the carry's
fused kernels' own operations, by the name the capture gives them or by their
scope; 0.0 in a program whose carry is a loop; the chunk-local and the short
convolution's kernels are not the carry's, nor the carry's theirs; nothing
untraced."""

import pytest

from benchmark import cells
from benchmark.tests.test_token_moe_lm import BWD, FWD, PRE, REMAT, _run_with
from benchmark.tests.test_trace_reduce import _host, _meta, _op

MIXER = "1_GatedDeltaNet/while/body/closed_call/checkpoint/"
CONV, SCAN = MIXER + "conv/", MIXER + "scan/"
READERS = (
    "deltanet_carry_kernel_ms_per_step", "deltanet_kernel_ms_per_step", "deltanet_conv_kernel_ms_per_step",
    "linear_attention_ms_per_step",
)


def _read(ops, steps=2):
    events, ts = _meta() + [_host("bench:window", 0, 100_000.0)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * 1000.0, tf_op=tf_op))
        ts += ms * 1000.0
    run_ = _run_with(events, steps=steps)
    return tuple(cells.load_module("layer_metrics", metric).read(run_) for metric in READERS)


def test_the_carrys_kernels_are_counted_and_the_other_kernels_are_not():
    fwd, bwd = SCAN + "deltanet_carry_fwd/", SCAN + "deltanet_carry_bwd/"
    carry, chunk_local, conv, linear = _read([
        ("deltanet_carry_fwd.5", FWD + fwd + "pallas_call:", 2),
        ("deltanet_carry_fwd.6", REMAT + fwd + "pallas_call:", 2),
        ("deltanet_carry_bwd.2", BWD + bwd + "pallas_call:", 3),
        ("fusion.12", BWD + SCAN + "reduce_sum:", 1),  # what stands round the kernels
        ("custom-call.9", BWD + bwd + "pallas_call:", 4),  # a kernel under another name: by its scope
        ("deltanet_carry_bwd.3", "deltanet_carry_bwd", 5),  # one that lost its scope: by name
        ("deltanet_chunk_fwd.7", FWD + SCAN + "deltanet_chunk_fwd/pallas_call:", 6),  # the chunk-local pair's
        ("deltanet_chunk_bwd.8", BWD + SCAN + "deltanet_chunk_bwd/pallas_call:", 7),
        ("deltanet_conv_fwd.9", FWD + CONV + "deltanet_conv_fwd/pallas_call:", 8),  # the short convolution's
        ("f2", FWD + MIXER + "in_proj/dot_general:", 9),
        ("f3", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert carry == pytest.approx((2 + 2 + 3 + 4 + 5) / 2)
    assert chunk_local == pytest.approx((6 + 7) / 2) and conv == pytest.approx(8 / 2)
    assert linear == pytest.approx((2 + 2 + 3 + 1 + 4 + 6 + 7 + 8 + 9) / 2)  # the scope's: not the unscoped kernel


def test_a_carry_in_a_loop_reads_zero():
    """The parent's program and every CPU-sized one: the family's scopes, the
    chunk-local kernels perhaps, and no kernel of this name."""
    carry, chunk_local, _, linear = _read([
        ("deltanet_chunk_fwd.7", FWD + SCAN + "deltanet_chunk_fwd/pallas_call:", 6),
        ("f1", FWD + SCAN + "while/body/dot_general:", 4),  # the loop's state products
        ("bitcast_dynamic-update-slice_fusion.41", FWD + SCAN + "while/body/dynamic_update_slice:", 2),
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
    ])
    assert carry == 0.0 and chunk_local == pytest.approx(3.0) and linear == pytest.approx(6.0)


def test_nothing_where_there_is_no_capture_or_no_scope():
    run_ = _run_with(None)
    run_["trace"] = None
    reader = cells.load_module("layer_metrics", READERS[0])
    assert reader.read(run_) is None
    bare = _run_with(_meta() + [_host("bench:window", 0, 1000), _op("f1", 0, 500, tf_op="jit(f)/mul:")])
    assert reader.read(bare) is None
