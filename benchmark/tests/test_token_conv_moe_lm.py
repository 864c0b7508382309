"""The convolution-and-attention cell at its shrunk size on the CPU, and its
nine readers on hand-made events that carry the two layer types' scope names."""

import json
import os

import pytest

from benchmark import cells, run, trace_reduce
from benchmark.tests import tiny
from benchmark.tests.test_trace_reduce import _host, _meta, _op

WORKLOAD = "lfm2_ep4_t32k_fused"
SCOPE_READERS = (
    "short_conv_ms_per_step", "short_conv_roofline_pct", "narrow_head_attention_ms_per_step",
    "narrow_head_attention_roofline_pct", "dense_ffn_ms_per_step", "biased_moe_ms_per_step",
    "biased_moe_matmul_roofline_pct",
)
COUNTER_READERS = ("biased_moe_load_imbalance", "biased_moe_held_share_pct")
PRE = "jit(multi)/while/body/closed_call/"
FWD = PRE + "jvp(tpuddp.forward)/"
BWD = PRE + "transpose(jvp(tpuddp.forward))/"
REMAT = BWD + "jvp(tpuddp.forward)/"
SPLASH = "vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call:"


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
    counters (the one over all the router's experts among them) summed over
    the window by the cell's feed, from the four sparse layers alone."""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["sample_unit"] == "token" and cell.traffic["feed"] == "resident_counted"
    assert cell.config["system"] == "token_conv_moe_lm"
    result = run.run_cell(WORKLOAD, seed=seed, seconds=0.5, trace=False, root=root)
    assert result["correct"] is True and result["failed"] == 0
    said = _said(capfd)
    tokens = cell.traffic["batch_per_chip"] * cell.config["tokens"]["seq_len"]
    assert said["samples"] == result["attempted"] * tokens
    assert said["compiles_in_window"] == 0
    assert said["reference"]["loss_rel_err"] < 2e-3 and said["reference"]["update_norm_rel_err"] < 2e-2
    counters, cfg = said["counters"], cell.config
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assignments = said["samples"] * cfg["num_experts_per_tok"] * sparse
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assignments
    assert counters["moe_dropped_assignments"] == 0
    assert counters["moe_router_tokens_max"] >= assignments / cfg["deployment"]["experts_published"]
    assert counters["moe_router_tokens_max"] >= counters["moe_expert_tokens_max"]


def test_the_next_precision_down_fails_the_cells_own_limits(root, capfd):
    """The control of the comparison with the reference, through a whole run
    of the harness and against the limits the configuration's file states:
    ``float8_e4m3fn`` products are not ``correct``."""
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
    assert reference["ok"] is False
    assert max(reference["loss_rel_err"] / published["loss_rtol"],
               reference["update_norm_rel_err"] / published["update_norm_rtol"]) > 1.5


def test_a_program_without_the_model_fails_the_cell_at_once(root, monkeypatch):
    """The parent commit under this benchmark: its registry has no such model,
    so the cell ends in a ``BenchmarkError`` (``run.py``: exit 2, no result
    line) before a state is made or a program compiled."""
    import tpuddp.models as zoo

    monkeypatch.setattr(zoo, "_REGISTRY", {k: v for k, v in zoo._REGISTRY.items() if not k.startswith("lfm2")})
    with pytest.raises(cells.BenchmarkError, match="cannot build 'lfm2_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_a_trunk_without_the_new_arguments_fails_the_cell_at_once(root, monkeypatch):
    """A registry that knows the name but whose trunk takes none of this
    model's arguments (a ``TypeError`` at construction) ends the same way."""
    import tpuddp.models as zoo

    monkeypatch.setitem(zoo._REGISTRY, "lfm2_tiny", lambda num_classes, hidden_size: None)
    with pytest.raises(cells.BenchmarkError, match="cannot build 'lfm2_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_the_traced_line_reports_the_counter_readers(root, monkeypatch):
    """A CPU capture has no device plane, so the recorded AlexNet capture
    stands in: the scope readers find none of this family's layers in it and
    report nothing (as they do on a parent that lacks the scopes); the
    counters' readers read the window's own counters, and the readers without
    a list of cells report as they do everywhere."""
    from benchmark.tests.test_harness import RECORDED

    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )
    result = run.run_cell(WORKLOAD, seed=3000000011, seconds=5, trace=True, root=root)
    assert result["correct"] is True
    assert {"compile_s", "device_ms_per_step", "device_mfu_pct", *COUNTER_READERS} <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["biased_moe_load_imbalance"]["value"] <= 8.0
    assert 0.0 <= result["metrics"]["biased_moe_held_share_pct"]["value"] <= 100.0
    assert not set(SCOPE_READERS) & set(result["metrics"])
    assert not {"moe_load_imbalance", "routed_moe_load_imbalance", "attention_ms_per_step"} & set(result["metrics"])


def _run_with(events, steps=2, tokens=65536, counters=None, workload=WORKLOAD):
    cell = cells.load_cell(workload)
    return {
        "cell": cell, "trace": {"planes": []}, "events": events, "setup": {},
        "window": {"steps": steps, "samples": tokens, "counters": counters or {}},
        "spans": {"seconds": {}, "counts": {}}, "counters": {},
        "flops_per_sample": 1.0, "peaks": cells.load_peaks("TPU v5e"),
    }


def _events():
    us = 1000.0  # one millisecond
    ops = [
        ("f1", FWD + "0_ShortConv/while/body/checkpoint/in_proj/dot_general:", 4),
        ("f2", FWD + "0_ShortConv/while/body/checkpoint/conv/mul:", 3),
        ("f3", REMAT + "2_ShortConv/while/body/checkpoint/rematted_computation/conv/mul:", 3),
        ("f4", BWD + "3_ShortConv/while/body/checkpoint/conv/mul:", 6),
        ("f5", BWD + "4_ShortConv/while/body/checkpoint/out_proj/dot_general:", 2),
        ("f5b", FWD + "4_ShortConv/while/body/mul:", 1),  # the layer's norm: no operator's scope
        ("f6", FWD + "0_ShortConv/while/body/checkpoint/mlp/dot_general:", 8),
        ("f7", BWD + "0_ShortConv/while/body/checkpoint/mlp/dot_general:", 12),
        ("f8", FWD + "1_FullAttention/checkpoint/moe/router/dot_general:", 1),
        ("f8b", FWD + "1_FullAttention/moe/router/sign:", 1),  # the bias's update
        ("f9", FWD + "1_FullAttention/while/body/checkpoint/attention/" + SPLASH, 7),
        ("f10", BWD + "1_FullAttention/while/body/checkpoint/qkv/dot_general:", 3),
        ("f10b", BWD + "1_FullAttention/while/body/checkpoint/attention/transpose:", 11),
        ("ragged-dot-none.7", "ragged-dot-none", 9),  # the compiler's own name: no scope
        # the Pallas lowering: the forward's kernel under ``experts``, the layer's own backward's beside it
        ("gmm.4", FWD + "2_ShortConv/checkpoint/moe/while/body/experts/jit(gmm)/pallas_call:", 2),
        ("gmm.9", BWD + "2_ShortConv/checkpoint/moe/while/body/jvp(experts)/jit(gmm)/pallas_call:", 2),
        ("tgmm.2", BWD + "2_ShortConv/checkpoint/moe/while/body/transpose(jvp(experts))/jit(tgmm)/pallas_call:", 4),
        ("f11", PRE + "tpuddp.optimizer/mul:", 10),
        ("f12", PRE + "jvp(tpuddp.loss)/while/body/checkpoint/dot_general:", 4),
        ("f13", FWD + "0_GatedDeltaNet/while/body/checkpoint/conv/dot_general:", 50),  # another family's
        ("f14", FWD + "0_SlidingAttention/while/body/checkpoint/attention/dot_general:", 50),
    ]
    events, ts = _meta() + [_host("bench:window", 0, 400 * us)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * us, tf_op=tf_op))
        ts += ms * us
    return events


def test_the_scope_readers_on_the_layer_types_names():
    """Device time by layer type and part, forward, backward and recomputation
    together; a roofline share is the kernel's least time over that."""
    counters = {
        "moe_router_tokens_max": 2.0 * 4 * 5000, "moe_expert_tokens_held": 2.0 * 4 * 32768,
        "moe_absent_assignments": 2.0 * 4 * 3 * 32768,
    }
    run_ = _run_with(_events(), counters=counters)
    read = lambda name: cells.load_module("layer_metrics", name).read(run_)
    assert read("short_conv_ms_per_step") == pytest.approx((4 + 3 + 3 + 6 + 2) / 2)
    assert read("narrow_head_attention_ms_per_step") == pytest.approx((7 + 3 + 11) / 2)
    assert read("dense_ffn_ms_per_step") == pytest.approx((8 + 12) / 2)
    assert read("biased_moe_ms_per_step") == pytest.approx((1 + 1 + 9 + 2 + 2 + 4) / 2)
    assert read("biased_moe_load_imbalance") == pytest.approx(5000 / (32768 * 4 / 32))
    assert read("biased_moe_held_share_pct") == pytest.approx(25.0)
    cfg, peaks = run_["cell"].config, run_["peaks"]
    flops = cells.load_module("flops", run_["cell"].config_name)
    ops, nbytes = flops.short_conv_cost(cfg, 65536)
    assert nbytes / peaks["hbm_bytes_per_s"] > 50 * ops / peaks["bf16_flops_per_s"]  # an HBM bound
    assert read("short_conv_roofline_pct") == pytest.approx(100 * 4 * nbytes / peaks["hbm_bytes_per_s"] / 12e-3)
    ops, nbytes = flops.attention_cost(cfg, 65536)
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]  # a compute bound
    assert read("narrow_head_attention_roofline_pct") == pytest.approx(100 * ops / peaks["bf16_flops_per_s"] / 18e-3)
    ops, nbytes = flops.expert_matmul_cost(cfg, 32768)
    least = 2 * 4 * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert read("biased_moe_matmul_roofline_pct") == pytest.approx(100 * least / (2e-3 + 15e-3))
    # the fused lowering engaged: the accepted reader finds its kernel by name
    kernels = cells.load_module("layer_metrics", "attention_kernel_ms_per_step").read(run_)
    assert kernels == pytest.approx(7 / 2)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, another token cell's or an image cell's: scopes,
    but none of this model's convolution layers (the window-and-full model
    has ``<i>_FullAttention`` layers of its own: they are not read as this
    model's). Every scope reader returns nothing and none raises; the
    counters' readers return nothing where the step carries no such counters
    out."""
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("f1", 0, 500, tf_op=FWD + "3_Conv2d/conv_general_dilated:"),
        _op("f2", 500, 200, tf_op=FWD + "3_FullAttention/while/body/checkpoint/attention/dot_general:"),
        _op("f3", 700, 100, tf_op=FWD + "3_FullAttention/checkpoint/moe/experts/ragged_dot:"),
        _op("f4", 800, 100, tf_op=PRE + "tpuddp.optimizer/mul:"),
    ]
    held_only = {"moe_expert_tokens_held": 100.0, "moe_expert_tokens_max": 10.0}  # the parent's counters, partly
    run_ = _run_with(events, counters=held_only)
    for name in SCOPE_READERS + COUNTER_READERS:
        assert cells.load_module("layer_metrics", name).read(run_) is None, name


def test_analytic_counts_of_the_published_cut():
    """266.60M multiply-accumulates a token: the operators' projections 77.6M
    (four convolution operators of 16.78M, attention's 10.49M), scores and
    values 67.11M (16,384.5 keys a query at 32,768 tokens), the dense
    feed-forward 44.04M, four expert layers of 11.08M (router 0.07M, the held
    share of the routed 11.01M at uniform routing's 1 held expert a token),
    the tied head's 33.55M."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", cfg["name"])
    assert flops.built_layer_types(cfg) == ["conv", "full_attention", "conv", "conv", "conv"]
    assert flops.visible_pairs(32768) == 32768 * 32769 // 2
    per_product = [m for m, _ in flops.products(cfg)]
    assert per_product[:3] == [2048 * 6144, 2048 * 2048, 3 * 2048 * 7168]  # the dense layer
    assert per_product[6] == 32 * 64 * 32769 == 67_110_912  # the triangle
    assert per_product[8:10] == [2048 * 32, 3 * 2048 * 1792]  # a router, one held expert a token
    assert per_product[-1] == 2048 * 16384
    assert sum(per_product) == 266_602_496
    assert flops.train_flops_per_sample(cfg) == 6.0 * 266_602_496
    ops, nbytes = flops.attention_cost(cfg, 32768)
    assert ops == 6.0 * 67_110_912 * 32768 and nbytes == 3 * 32768 * 64 * (64 + 16) * 2
    ops, nbytes = flops.short_conv_cost(cfg, 32768)
    assert nbytes == 32768 * 2048 * 2 * 11 and ops == 32768 * 2048 * 30
