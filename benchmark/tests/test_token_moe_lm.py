"""The token mixture-of-experts cell at its shrunk size on the CPU, and its
six readers on hand-made events that carry the family's scope names."""

import json
import os
import types

import pytest

from benchmark import cells, run, trace_reduce
from benchmark.tests import tiny
from benchmark.tests.test_trace_reduce import _host, _meta, _op

WORKLOAD = "qwen3next_ep16_t8k_fused"
NEW_READERS = (
    "linear_attention_ms_per_step", "attention_ms_per_step", "moe_ms_per_step",
    "deltanet_scan_roofline_pct", "expert_matmul_roofline_pct", "moe_load_imbalance",
)
PRE = "jit(multi)/while/body/closed_call/"
FWD = PRE + "jvp(tpuddp.forward)/"
BWD = PRE + "transpose(jvp(tpuddp.forward))/"
REMAT = BWD + "jvp(tpuddp.forward)/"


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.fake_devices(monkeypatch, run)
    from tpuddp.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    return tiny.make_root(tmp_path)


def _said(capfd):
    return [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')][-1]


@pytest.mark.parametrize("seed", [2600000501, 2147483649])
def test_the_shrunk_cell_is_correct_and_counts_its_experts(root, capfd, seed):
    """Untraced, on seeds above 2**31 as the driver's are: ``correct``, no
    program lowered in the window, tokens counted, and the expert layer's
    counters summed over the window by the cell's own feed."""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["sample_unit"] == "token" and cell.traffic["feed"] == "resident_counted"
    result = run.run_cell(WORKLOAD, seed=seed, seconds=0.5, trace=False, root=root)
    assert result["correct"] is True and result["failed"] == 0
    said = _said(capfd)
    tokens = cell.traffic["batch_per_chip"] * cell.config["tokens"]["seq_len"]
    assert said["samples"] == result["attempted"] * tokens
    assert said["compiles_in_window"] == 0
    assert said["reference"]["loss_rel_err"] < 2e-3 and said["reference"]["update_norm_rel_err"] < 2e-2
    counters = said["counters"]
    assignments = said["samples"] * cell.config["num_experts_per_tok"] * cell.config["num_hidden_layers"]
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assignments
    assert counters["moe_dropped_assignments"] == 0
    assert counters["moe_expert_tokens_max"] >= counters["moe_expert_tokens_held"] / cell.config["num_experts"]


def test_the_next_precision_down_fails_the_cells_own_limits(root, capfd):
    """The control of the comparison with the reference, through the harness's
    own comparison (``check.against_reference``) and against the limits the
    configuration's file states. The 8-bit float nearest to the stated
    bfloat16 (``float8_e4m3fn``: most mantissa) as the products' input type is
    not ``correct``: its loss stays inside the limit and the norm of its
    parameter change misses by far, one limit and not each. (``float8_e5m2``
    keeps bfloat16's range and reads 3 to 10 times bfloat16's errors at this
    size, inside limits that were set at the cell's: tests/
    test_hybrid_moe_training.py holds it to those ratios.)"""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["compute_dtype"] == "bfloat16"
    entry = next(c for c in cells.load_benchmark(root)["configs"] if c["name"] == cell.config_name)
    published = cells.load_cell(WORKLOAD).config["check"]  # the shrunk root loosens every cell's limits
    with open(os.path.join(root, entry["file"]), "w") as f:
        json.dump({**cell.config, "compute_dtype": "float8_e4m3fn",
                   "check": {**cell.config["check"], "loss_rtol": published["loss_rtol"],
                             "update_norm_rtol": published["update_norm_rtol"]}}, f)
    result = run.run_cell(WORKLOAD, seed=2600000501, seconds=0.3, trace=False, root=root)
    assert result["correct"] is False
    reference = _said(capfd)["reference"]
    assert reference["loss_rtol"] == published["loss_rtol"]
    assert reference["update_norm_rtol"] == published["update_norm_rtol"]
    assert reference["ok"] is False
    assert reference["update_norm_rel_err"] > 10 * reference["update_norm_rtol"]


def test_the_traced_line_reports_the_counter_reader(root, monkeypatch):
    """A CPU capture has no device plane, so the recorded AlexNet capture
    stands in: the scope readers find none of this family's layers in it and
    report nothing (as they do on a parent that lacks the scopes); the
    counter's reader reads the window's own counters."""
    from benchmark.tests.test_harness import RECORDED

    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )
    result = run.run_cell(WORKLOAD, seed=3000000011, seconds=5, trace=True, root=root)
    assert result["correct"] is True
    assert {"compile_s", "device_ms_per_step", "moe_load_imbalance"} <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["moe_load_imbalance"]["value"] <= result["attempted"] * 1e3
    assert not set(NEW_READERS[:5]) & set(result["metrics"])


def _run_with(events, steps=2, tokens=32768, counters=None):
    cell = cells.load_cell(WORKLOAD)
    return {
        "cell": cell, "trace": {"planes": []}, "events": events, "setup": {},
        "window": {"steps": steps, "samples": tokens, "counters": counters or {}},
        "spans": {"seconds": {}, "counts": {}}, "counters": {},
        "flops_per_sample": 1.0, "peaks": cells.load_peaks("TPU v5e"),
    }


def _events():
    us = 1000.0  # one millisecond
    ops = [
        ("f1", FWD + "0_GatedDeltaNet/while/body/checkpoint/in_proj/dot_general:", 4),
        ("f2", FWD + "0_GatedDeltaNet/while/body/checkpoint/scan/while/body/dot_general:", 6),
        ("f3", REMAT + "0_GatedDeltaNet/while/body/checkpoint/rematted_computation/scan/dot_general:", 6),
        ("f4", BWD + "0_GatedDeltaNet/while/body/checkpoint/scan/while/body/dot_general:", 12),
        ("f5", BWD + "1_GatedDeltaNet/while/body/checkpoint/conv/mul:", 2),
        ("f6", FWD + "1_GatedDeltaNet/checkpoint/moe/experts/ragged_dot:", 3),
        ("f7", BWD + "1_GatedDeltaNet/checkpoint/moe/while/body/experts/ragged_dot:", 5),
        ("f8", FWD + "3_GatedAttention/checkpoint/moe/router/dot_general:", 1),
        ("f9", FWD + "3_GatedAttention/while/body/checkpoint/attention/checkpoint/dot_general:", 7),
        ("f10", BWD + "3_GatedAttention/while/body/checkpoint/qkv/dot_general:", 3),
        ("ragged-dot-none.7", "ragged-dot-none", 9),  # the compiler's own name: no scope
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
        ("f12", PRE + "jvp(tpuddp.loss)/while/body/checkpoint/dot_general:", 4),
    ]
    events, ts = _meta() + [_host("bench:window", 0, 100 * us)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * us, tf_op=tf_op))
        ts += ms * us
    return events


def test_the_scope_readers_on_the_familys_names():
    """Device time by layer kind and part, forward, backward and recomputation
    together; a roofline share is the kernel's least time over that."""
    counters = {"moe_expert_tokens_max": 2.0 * 4 * 400, "moe_expert_tokens_held": 2.0 * 4 * 10240}
    run_ = _run_with(_events(), counters=counters)
    read = lambda name: cells.load_module("layer_metrics", name).read(run_)
    assert read("linear_attention_ms_per_step") == pytest.approx((4 + 6 + 6 + 12 + 2) / 2)
    assert read("attention_ms_per_step") == pytest.approx((7 + 3) / 2)
    assert read("moe_ms_per_step") == pytest.approx((3 + 5 + 1 + 9) / 2)
    assert read("moe_load_imbalance") == pytest.approx(32 * 400 / 10240)
    cfg, peaks = run_["cell"].config, run_["peaks"]
    flops = cells.load_module("flops", run_["cell"].config_name)
    ops, nbytes = flops.scan_cost(cfg, 32768)
    least = 3 * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert read("deltanet_scan_roofline_pct") == pytest.approx(100 * least / 24e-3)
    ops, nbytes = flops.expert_matmul_cost(cfg, 10240)
    least = 2 * 4 * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert read("expert_matmul_roofline_pct") == pytest.approx(100 * least / 17e-3)


def test_the_scope_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, or an image cell's: scopes, but none of this
    family's layers. Every reader returns nothing and none raises."""
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("f1", 0, 500, tf_op=FWD + "3_Conv2d/conv_general_dilated:"),
        _op("f2", 500, 400, tf_op=PRE + "tpuddp.optimizer/mul:"),
    ]
    run_ = _run_with(events)
    for name in NEW_READERS:
        assert cells.load_module("layer_metrics", name).read(run_) is None, name


def test_analytic_counts_of_the_published_cut():
    """234.07M multiply-accumulates a token: three DeltaNet layers of 36.57M
    (25.17M + 0.13M in, 8.39M out, 2.88M in the chunked scan), one attention
    layer of 60.82M (33.56M of it scores and values over 4096.5 keys a
    query), four expert layers of 6.16M (router 1.05M, shared 3.15M, held
    share of the routed 1.97M) and the head's 38.90M."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", cfg["name"])
    assert flops.scan_macs_per_token(cfg) == 32 * (64 * 5 * 128 + 3 * 128 * 128) == 2_883_584
    assert sum(m for m, _ in flops.products(cfg)) == 234_074_112
    assert flops.train_flops_per_sample(cfg) == 6.0 * 234_074_112
