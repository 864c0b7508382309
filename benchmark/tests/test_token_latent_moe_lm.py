"""The latent-attention cell at its shrunk size on the CPU, the planted faults
of its comparison with the reference, and its eight readers on hand-made
events that carry the scope names only this model opens."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, check, run, trace_reduce
from benchmark.tests import tiny
from benchmark.tests.test_token_looped_lm import _SeededBatches, e4m3  # what the check asks of a feed; 8-bit operands
from benchmark.tests.test_trace_reduce import _host, _meta, _op
from tpuddp.parallel import make_mesh

WORKLOAD, CONFIG = "glm47flash_ep8_t16k_fused", "glm_4_7_flash_ep8"
SCOPE_READERS = (
    "latent_attention_ms_per_step", "latent_projection_ms_per_step", "latent_attention_roofline_pct",
    "mtp_ms_per_step", "shared_biased_moe_ms_per_step", "shared_biased_moe_matmul_roofline_pct",
)
COUNTER_READERS = ("mtp_loss_per_token", "shared_biased_moe_load_imbalance")
PRE = "jit(multi)/while/body/closed_call/"
FWD = PRE + "jvp(tpuddp.forward)/"
BWD = PRE + "transpose(jvp(tpuddp.forward))/"
REMAT = BWD + "jvp(tpuddp.forward)/"
LOSS, LOSS_BWD = PRE + "jvp(tpuddp.loss)/", PRE + "transpose(jvp(tpuddp.loss))/"
SPLASH = "vmap(jit(_splash_attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call:"


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.fake_devices(monkeypatch, run)
    from tpuddp.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    return tiny.make_root(tmp_path)


def _said(capfd):
    return [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')][-1]


@pytest.mark.parametrize("seed", [2600000501, 2147483659])
def test_the_shrunk_cell_is_correct_and_counts_both_its_heads(root, capfd, seed):
    """Untraced, on seeds above 2**31 as the driver's are: ``correct``, no
    program lowered in the window, tokens counted, the expert layers' counters
    summed over the window by the cell's feed from the two sparse layers and
    the module's, and the second head's loss over the positions that have a
    token after next."""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["sample_unit"] == "token" and cell.traffic["feed"] == "resident_counted"
    assert cell.config["system"] == "token_latent_moe_lm"
    result = run.run_cell(WORKLOAD, seed=seed, seconds=0.5, trace=False, root=root)
    assert result["correct"] is True and result["failed"] == 0
    said = _said(capfd)
    sequences, t = cell.traffic["batch_per_chip"], cell.config["tokens"]["seq_len"]
    assert said["samples"] == result["attempted"] * sequences * t
    assert said["compiles_in_window"] == 0
    assert said["reference"]["loss_rel_err"] < 2e-3 and said["reference"]["update_norm_rel_err"] < 2e-2
    counters, cfg = said["counters"], cell.config
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] + cfg["num_nextn_predict_layers"]
    assignments = said["samples"] * cfg["num_experts_per_tok"] * sparse
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assignments
    assert counters["moe_dropped_assignments"] == 0
    assert counters["moe_router_tokens_max"] >= assignments / cfg["deployment"]["experts_published"]
    assert counters["mtp_tokens"] == result["attempted"] * sequences * (t - 1)
    per_token = counters["mtp_loss_sum"] / counters["mtp_tokens"]
    assert 0.8 * np.log(cfg["vocab_size"]) < per_token < 1.1 * np.log(cfg["vocab_size"])  # a fresh head's


def test_a_program_without_the_model_fails_the_cell_at_once(root, monkeypatch):
    """The parent commit under this benchmark: its registry has no such model,
    so the cell ends in a ``BenchmarkError`` (``run.py``: exit 2, no result
    line) before a state is made or a program compiled."""
    import tpuddp.models as zoo

    monkeypatch.setattr(zoo, "_REGISTRY", {k: v for k, v in zoo._REGISTRY.items() if not k.startswith("glm")})
    with pytest.raises(cells.BenchmarkError, match="cannot build 'glm_4_7_flash_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_a_trunk_without_the_new_arguments_fails_the_cell_at_once(root, monkeypatch):
    """A registry that knows the name but whose trunk takes none of this
    model's arguments (a ``TypeError`` at construction) ends the same way."""
    import tpuddp.models as zoo

    monkeypatch.setitem(zoo._REGISTRY, "glm_4_7_flash_tiny", lambda num_classes, hidden_size: None)
    with pytest.raises(cells.BenchmarkError, match="cannot build 'glm_4_7_flash_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_the_traced_line_reports_the_counter_readers(root, monkeypatch):
    """A CPU capture has no device plane, so the recorded AlexNet capture
    stands in: the scope readers find none of this model's layers in it and
    report nothing (as they do on a program without the scopes); the
    counters' readers read the window's own counters, and the readers without
    a list of cells report as they do everywhere."""
    from benchmark.tests.test_harness import RECORDED

    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )
    result = run.run_cell(WORKLOAD, seed=3000000011, seconds=5, trace=True, root=root)
    assert result["correct"] is True
    assert {"compile_s", "device_ms_per_step", "device_mfu_pct", *COUNTER_READERS} <= set(result["metrics"])
    assert 1.0 <= result["metrics"]["shared_biased_moe_load_imbalance"]["value"] <= 8.0
    assert 3.0 < result["metrics"]["mtp_loss_per_token"]["value"] < 5.0  # ln 96 = 4.56
    assert not set(SCOPE_READERS) & set(result["metrics"])
    assert not {"moe_load_imbalance", "biased_moe_load_imbalance", "attention_ms_per_step"} & set(result["metrics"])


# -- the planted faults, through the harness's own comparison -------------------------------

FAULTS = ("no_latent_norm", "no_rotary_key", "unshifted_target", "no_second_loss", "unscaled_routes")


def plant(fault: str, monkeypatch) -> None:
    """Plant one of the program's five faults (``benchmark/configs``: the
    configuration's ``check.reason`` has what each reads at the cell's size)."""
    from tpuddp.models.hybrid_moe import HybridMoELM
    from tpuddp.nn import sequence as seq

    def built_with(**over):
        real = HybridMoELM.__init__
        monkeypatch.setattr(HybridMoELM, "__init__", lambda self, *a, **kw: real(self, *a, **{**kw, **over}))

    if fault == "no_latent_norm":  # (a) the norm inside the key/value pair left out
        real_norm = HybridMoELM._norm
        monkeypatch.setattr(
            HybridMoELM, "_norm",
            lambda self, x, w: x if w.shape == (self.kv_lora_rank,) else real_norm(self, x, w),
        )
    elif fault == "no_rotary_key":  # (b) the rotary part dropped from the keys: the one key with a head axis of 1
        real_rotary = seq.rotary
        monkeypatch.setattr(
            seq, "rotary", lambda x, *a, **kw: jnp.zeros_like(x) if x.shape[2] == 1 else real_rotary(x, *a, **kw)
        )
    elif fault == "unshifted_target":  # (c) the second head held to the next token, the first head's own target
        real_targets = seq.targets_after_next
        monkeypatch.setattr(seq, "targets_after_next", lambda labels, weights: (labels, real_targets(labels, weights)[1]))
    elif fault == "no_second_loss":  # (d) lambda = 0: the second head's loss out of the gradient
        built_with(next_token_loss_weight=0.0)
    elif fault == "unscaled_routes":  # (e) the routed weights renormalised and not scaled: 1 for 1.8
        built_with(routed_scale=1.0)
    else:
        raise ValueError(fault)


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_latent_moe_lm")


def _held_to_the_files_limits(system, published, seed, **over):
    shrunk = system.shrunk(published)
    limits = {k: published["check"][k] for k in ("loss_rtol", "update_norm_rtol")}
    config = {**shrunk, **over, "check": {**shrunk["check"], "batch": 4, **limits}}
    cell = cells.Cell(
        name="t", chips=1, config_name=CONFIG, config=config, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )
    mesh = make_mesh(jax.devices()[:1], {"data": 1})
    return check.against_reference(cell, mesh, seed, _SeededBatches(system, config, seed)), limits


@pytest.mark.parametrize("seed", [11, 12, 2600000501])
def test_the_sound_program_passes_the_configurations_limits(system, published, seed):
    got, limits = _held_to_the_files_limits(system, published, seed)
    assert got["ok"] is True
    assert got["loss_rel_err"] < limits["loss_rtol"] / 2 and got["update_norm_rel_err"] < limits["update_norm_rtol"] / 2


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "unscaled_routes"])
@pytest.mark.parametrize("seed", [11, 2600000501])
def test_a_planted_fault_fails_the_configurations_limits(system, published, fault, seed, monkeypatch):
    """Four of the program's five planted faults are not ``correct`` under
    the limits the configuration's file states, with room, at this size too
    (the configuration's ``check.reason`` has the readings at the cell's
    size). Here the update's norm reads each: leaves that lose their gradient
    stand still from the first step (the norm's weight, the rotary columns of
    two projections, the whole module), and another target turns the module's
    gradients from the second step on."""
    plant(fault, monkeypatch)
    got, limits = _held_to_the_files_limits(system, published, seed)
    assert got["ok"] is False, got
    assert got["update_norm_rel_err"] > 1.5 * limits["update_norm_rtol"], got


@pytest.mark.parametrize("seed", [11, 2600000501])
def test_unscaled_routes_are_read_where_the_experts_weigh(system, published, seed, monkeypatch):
    """(e) Routed weights renormalised and not scaled by 1.8: no leaf loses
    its gradient, so only the size of what the experts add tells. At the
    cell's size the loss reads it 11 times over its limit (``check.reason``);
    two 32-wide experts a token beside a 64-wide stream add too little for
    that, and what is held here is that the fault shows at all: in float32
    products, where the sound program stands within 5e-5 of the reference on
    the update's norm, it reads twenty times that and more."""
    sound, _ = _held_to_the_files_limits(system, published, seed, compute_dtype="float32")
    plant("unscaled_routes", monkeypatch)
    got, _ = _held_to_the_files_limits(system, published, seed, compute_dtype="float32")
    assert sound["update_norm_rel_err"] < 5e-5 and got["update_norm_rel_err"] > 20 * sound["update_norm_rel_err"], (got, sound)


@pytest.mark.parametrize("seed", [11, 12])
def test_the_reference_in_8_bit_operands_fails_the_configurations_limits(system, published, seed, monkeypatch):
    """(f) The next precision down, read through the reference side (no 8-bit
    type, no program): the reference with every product's operands and their
    cotangents rounded to ``float8_e4m3fn``, put in the program's place and
    held against the reference as it is by the comparison's own measure."""
    from tpuddp.models import load_model

    reference = cells.load_module("reference", CONFIG)
    config = system.shrunk(published)
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    init = jax.device_get(system.init_variables(model, config, seed))
    batches = _SeededBatches(system, config, seed).sample_batches(3, 4)
    plain = reference.train_steps(config, *init, batches)
    monkeypatch.setattr(reference, "_operand", e4m3)
    rounded = reference.train_steps(config, *init, batches)
    worst = lambda ours, theirs: max(abs(a - b) / abs(b) for a, b in zip(ours, theirs))
    limits = published["check"]
    assert max(worst(rounded[0], plain[0]) / limits["loss_rtol"],
               worst(rounded[1], plain[1]) / limits["update_norm_rtol"]) > 1.5


def test_the_reference_reports_the_second_heads_loss(system, published):
    """``with_mtp``: a third list, the loss of the token after next a step,
    which a fresh head reads at the logarithm of the vocabulary; the program's
    counters carry the same number out."""
    from tpuddp.models import load_model

    reference = cells.load_module("reference", CONFIG)
    config = {**system.shrunk(published), "compute_dtype": "float32"}
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    init = jax.device_get(system.init_variables(model, config, 7))
    batches = _SeededBatches(system, config, 7).sample_batches(1, 2)
    losses, norms, second = reference.train_steps(config, *init, batches, with_mtp=True)
    assert len(losses) == len(norms) == len(second) == 1
    assert abs(second[0] - np.log(96)) < 0.2
    from tpuddp import nn
    from tpuddp.nn.core import Context

    out, _ = model.apply(*init, jnp.asarray(batches[0][0]), Context(train=True))
    loss = nn.CrossEntropyLoss()(out, jnp.asarray(batches[0][1]), jnp.ones(batches[0][1].shape, jnp.float32))
    assert float(loss) == pytest.approx(losses[0], rel=1e-5)
    assert float(out.counters["mtp_loss_sum"] / out.counters["mtp_tokens"]) == pytest.approx(second[0], rel=1e-5)


def test_the_built_tree_is_the_tables_count(system, published):
    """The cell-size tree, as shapes only: 706,518,528 parameters, part by
    part as the configuration's table has them, and a selection bias a sparse
    layer with the module's last."""
    from tpuddp.models import load_model

    model = load_model(published["model"]["registry_name"], published["vocab_size"], **system.model_kwargs(published))
    preset = load_model("glm_4_7_flash_ep8", published["vocab_size"])
    ours = {"compute_dtype": None}  # the file's own choice (`assumed`)
    assert {**vars(model), **ours} == {**vars(preset), **ours}
    shapes, state = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))
    attention = 21_759_232
    assert count(shapes["layers"][0]["mixer"]) == attention
    assert count(shapes["layers"][0]) == attention + 4096 + 62_914_560 == 84_677_888
    sparse = attention + 4096 + 131_072 + 9_437_184 + 8 * 9_437_184
    assert [count(l) for l in shapes["layers"][1:]] == [sparse] * 4 and sparse == 106_829_056
    assert count(shapes["embed"]) + count(shapes["head"]) == 2 * 19360 * 2048 == 79_298_560
    assert count(shapes["mtp"]) == 2 * 2048 + 8_388_608 + sparse + 2048 == 115_223_808
    assert count(shapes) == published["parameters"] == 706_518_528
    assert "shared_gate" not in shapes["layers"][1]["moe"] and "shared" in shapes["mtp"]["layer"]["moe"]
    assert [jax.tree_util.tree_map(lambda a: a.shape, s) for s in state] == [()] + [{"expert_bias": (64,)}] * 5
    assert published["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880}


# -- the readers ---------------------------------------------------------------------------

def _run_with(events, steps=2, tokens=32768, counters=None, workload=WORKLOAD):
    cell = cells.load_cell(workload)
    return {
        "cell": cell, "trace": {"planes": []}, "events": events, "setup": {},
        "window": {"steps": steps, "samples": tokens, "counters": counters or {}},
        "spans": {"seconds": {}, "counts": {}}, "counters": {},
        "flops_per_sample": 1.0, "peaks": cells.load_peaks("TPU v5e"),
    }


def _events():
    us = 1000.0  # one millisecond
    mixer = "while/body/checkpoint/"
    ops = [
        ("f1", FWD + "0_LatentAttention/" + mixer + "q_latent/dot_general:", 4),
        ("f2", FWD + "0_LatentAttention/" + mixer + "kv_latent/dot_general:", 3),
        ("f3", FWD + "0_LatentAttention/" + mixer + "kv_latent/broadcast_in_dim:", 1),
        ("f4", FWD + "1_LatentAttention/" + mixer + "attention/" + SPLASH, 7),
        ("f5", REMAT + "1_LatentAttention/" + mixer + "rematted_computation/attention/" + SPLASH, 7),
        ("f6", BWD + "1_LatentAttention/" + mixer + "attention/transpose:", 16),
        ("f7", BWD + "4_LatentAttention/" + mixer + "o_proj/dot_general:", 2),
        ("f8", BWD + "4_LatentAttention/" + mixer + "q_latent/dot_general:", 5),
        ("f9", FWD + "4_LatentAttention/while/body/mul:", 1),  # the layer's norm: no part's scope
        ("f10", FWD + "0_LatentAttention/while/body/checkpoint/mlp/dot_general:", 8),
        ("f11", FWD + "2_LatentAttention/checkpoint/moe/router/dot_general:", 1),
        ("f12", FWD + "2_LatentAttention/moe/router/sign:", 1),  # the bias's update
        ("f13", FWD + "2_LatentAttention/checkpoint/moe/shared_expert/dot_general:", 3),
        ("f14", FWD + "3_LatentAttention/checkpoint/moe/while/body/experts/mul:", 2),
        ("ragged-dot-none.7", "ragged-dot-none", 9),  # the compiler's own name: no scope
        # the module: its projection, its layer under the same names, its norm; the second head in the loss phase
        ("m1", FWD + "mtp/proj/dot_general:", 2),
        ("m2", FWD + "mtp/5_LatentAttention/" + mixer + "kv_latent/dot_general:", 3),
        ("m3", BWD + "mtp/5_LatentAttention/" + mixer + "attention/transpose:", 16),
        ("m4", FWD + "mtp/5_LatentAttention/checkpoint/moe/shared_expert/dot_general:", 3),
        ("m5", FWD + "mtp/mul:", 1),
        ("m6", LOSS + "mtp/while/body/checkpoint/dot_general:", 5),
        ("m7", LOSS_BWD + "mtp/while/body/checkpoint/rematted_computation/dot_general:", 5),
        ("m8", LOSS_BWD + "mtp/while/body/checkpoint/dot_general:", 10),
        ("o1", PRE + "tpuddp.optimizer/mul:", 10),
        ("o2", LOSS + "while/body/checkpoint/dot_general:", 50),  # the first head: no mtp
        ("o3", FWD + "1_FullAttention/while/body/checkpoint/attention/dot_general:", 50),  # another family's
        ("o4", FWD + "1_FullAttention/checkpoint/moe/shared_expert/dot_general:", 50),
    ]
    events, ts = _meta() + [_host("bench:window", 0, 400 * us)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * us, tf_op=tf_op))
        ts += ms * us
    return events


def test_the_scope_readers_on_the_models_names():
    """Device time by part under the latent layers, the module's among them,
    forward, backward and recomputation together; the module's own time under
    both ``mtp`` scopes; a roofline share is the need's least time over the
    time."""
    counters = {
        "moe_router_tokens_max": 2.0 * 5 * 1500, "moe_expert_tokens_held": 2.0 * 5 * 8000,
        "mtp_loss_sum": 9.5 * 32766, "mtp_tokens": 32766.0,
    }
    run_ = _run_with(_events(), counters=counters)
    read = lambda name: cells.load_module("layer_metrics", name).read(run_)
    assert read("latent_attention_ms_per_step") == pytest.approx((4 + 3 + 1 + 7 + 7 + 16 + 2 + 5 + 3 + 16) / 2)
    assert read("latent_projection_ms_per_step") == pytest.approx((4 + 3 + 1 + 5 + 3) / 2)
    assert read("mtp_ms_per_step") == pytest.approx((2 + 3 + 16 + 3 + 1 + 5 + 5 + 10) / 2)
    assert read("shared_biased_moe_ms_per_step") == pytest.approx((1 + 1 + 3 + 2 + 3 + 9) / 2)
    assert read("mtp_loss_per_token") == pytest.approx(9.5)
    assert read("shared_biased_moe_load_imbalance") == pytest.approx(1500 / (16384 * 4 / 64))
    cfg, peaks = run_["cell"].config, run_["peaks"]
    flops = cells.load_module("flops", CONFIG)
    ops, nbytes = flops.attention_cost(cfg, 32768)
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]  # a compute bound
    assert read("latent_attention_roofline_pct") == pytest.approx(100 * 6 * ops / peaks["bf16_flops_per_s"] / 46e-3)
    ops, nbytes = flops.expert_matmul_cost(cfg, 8000)
    least = 2 * 5 * max(ops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert read("shared_biased_moe_matmul_roofline_pct") == pytest.approx(100 * least / (2e-3 + 9e-3))


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, another token cell's or an image cell's: scopes,
    but no ``<i>_LatentAttention`` layer and no ``mtp``. Every scope reader
    returns nothing and none raises; the counters' readers return nothing
    where the step carries no such counters out. On an empty capture the
    same."""
    events = _meta() + [
        _host("bench:window", 0, 1000),
        _op("f1", 0, 500, tf_op=FWD + "3_Conv2d/conv_general_dilated:"),
        _op("f2", 500, 200, tf_op=FWD + "3_FullAttention/while/body/checkpoint/attention/dot_general:"),
        _op("f3", 700, 100, tf_op=FWD + "3_FullAttention/checkpoint/moe/experts/ragged_dot:"),
        _op("f4", 800, 100, tf_op=LOSS + "while/body/checkpoint/dot_general:"),
    ]
    for run_ in (_run_with(events, counters={"moe_expert_tokens_held": 100.0}), {**_run_with([]), "trace": None}):
        for name in SCOPE_READERS + COUNTER_READERS:
            assert cells.load_module("layer_metrics", name).read(run_) is None, name


def test_analytic_counts_of_the_published_cut():
    """855,930,880 multiply-accumulates a token: six latent mixers of
    105,649,152 (the low-rank pairs 11,272,192, scores and values 83,891,200
    over 8,192.5 keys a query at 16,384 tokens, the output projection
    10,485,760), the dense feed-forward 62,914,560, five sparse ones of
    14,286,848 (router 131,072, the shared expert 9,437,184, the held share of
    the routed 4,718,592 at uniform routing's half a held expert a token), the
    module's projection 8,388,608 and the head's 39,649,280 twice."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", CONFIG)
    assert flops.attention_layers(cfg) == 6 and flops.sparse_layers(cfg) == 5
    assert flops.visible_pairs(16384) == 16384 * 16385 // 2
    per_product = [m for m, _ in flops.products(cfg)]
    assert per_product[:4] == [11_272_192, 20 * 512 * 16385 // 2, 5120 * 2048, 3 * 2048 * 10240]  # the dense layer
    assert per_product[4:10] == [11_272_192, 83_891_200, 10_485_760, 2048 * 64, 3 * 2048 * 1536, 3 * 2048 * 1536 // 2]
    assert per_product[28] == 2 * 2048 * 2048 and per_product[-2:] == [2048 * 19360] * 2
    assert sum(per_product) == 6 * 105_649_152 + 62_914_560 + 5 * 14_286_848 + 8_388_608 + 2 * 39_649_280 == 855_930_880
    assert flops.train_flops_per_sample(cfg) == 6.0 * 855_930_880
    ops, nbytes = flops.attention_cost(cfg, 16384)
    assert ops == 6.0 * 83_891_200 * 16384 and nbytes == 3 * 16384 * 20 * 1024 * 2
    ops, nbytes = flops.latent_projection_cost(cfg, 16384)
    assert ops == 6.0 * 11_272_192 * 16384
    assert nbytes == 11_272_192 * 10 + 2 * 16384 * 2 * (2048 + 768 + 768 + 5120 + 2048 + 576 + 512 + 8960)
    ops, nbytes = flops.expert_matmul_cost(cfg, 8192)
    assert ops == 6.0 * 9_437_184 * 8192 and nbytes == 8 * 9_437_184 * 10 + 8192 * 4 * 2048 * 2
