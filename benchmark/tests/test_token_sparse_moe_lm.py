"""The sparse-attention cell at its shrunk size on the CPU, the planted faults
of its comparison with the reference, the eight shares that add up to the
uncut layer, and its six readers on hand-made events that carry the scope
names only this model opens."""

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

WORKLOAD, CONFIG = "keye2_ep8_t32k_fused", "keye_vl_2_0_30b_a3b_ep8"
SCOPE_READERS = (
    "indexer_ms_per_step", "index_select_ms_per_step", "sparse_attention_ms_per_step",
    "sparse_attention_roofline_pct", "index_scores_roofline_pct",
)
COUNTER_READERS = ("indexer_kl_per_row",)
PRE = "jit(multi)/while/body/closed_call/"
FWD = PRE + "jvp(tpuddp.forward)/"
BWD = PRE + "transpose(jvp(tpuddp.forward))/"
REMAT = BWD + "jvp(tpuddp.forward)/"
LOSS = PRE + "jvp(tpuddp.loss)/"


@pytest.fixture
def root(tmp_path, monkeypatch):
    tiny.fake_devices(monkeypatch, run)
    from tpuddp.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    return tiny.make_root(tmp_path)


def _said(capfd):
    return [json.loads(l) for l in capfd.readouterr().err.splitlines() if l.startswith('{"workload"')][-1]


@pytest.mark.parametrize("seed", [2600000501, 2147483659])
def test_the_shrunk_cell_is_correct_and_counts_its_selections(root, capfd, seed):
    """Untraced, on seeds above 2**31 as the driver's are: ``correct``, no
    program lowered in the window, tokens counted, the expert layers' counters
    and the indexers' summed over the window by the cell's feed: a row a token
    a layer, and the pairs an exact selection holds."""
    cell = cells.load_cell(WORKLOAD, root)
    assert cell.config["sample_unit"] == "token" and cell.traffic["feed"] == "resident_counted"
    assert cell.config["system"] == "token_sparse_moe_lm"
    result = run.run_cell(WORKLOAD, seed=seed, seconds=0.5, trace=False, root=root)
    assert result["correct"] is True and result["failed"] == 0
    said = _said(capfd)
    sequences, t = cell.traffic["batch_per_chip"], cell.config["tokens"]["seq_len"]
    assert said["samples"] == result["attempted"] * sequences * t
    assert said["compiles_in_window"] == 0
    assert said["reference"]["loss_rel_err"] < 2e-3 and said["reference"]["update_norm_rel_err"] < 2e-2
    counters, cfg = said["counters"], cell.config
    layers, top_k = cfg["num_hidden_layers"], cfg["sa_config"]["topk"]
    assignments = said["samples"] * cfg["num_experts_per_tok"] * layers
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assignments
    assert counters["moe_dropped_assignments"] == 0
    assert counters["indexer_rows"] == said["samples"] * layers
    a_sequence = sum(min(i + 1, top_k) for i in range(t))
    assert counters["index_selected_pairs"] == result["attempted"] * sequences * layers * a_sequence
    flops = cells.load_module("flops", CONFIG, root)
    assert flops.selected_pairs(t, top_k) == a_sequence
    assert 0 < counters["indexer_kl_sum"] / counters["indexer_rows"] < 0.5  # a fresh indexer: near uniform


def test_a_program_without_the_model_fails_the_cell_at_once(root, monkeypatch):
    """The parent commit under this benchmark: its registry has no such model,
    so the cell ends in a ``BenchmarkError`` (``run.py``: exit 2, no result
    line) before a state is made or a program compiled."""
    import tpuddp.models as zoo

    monkeypatch.setattr(zoo, "_REGISTRY", {k: v for k, v in zoo._REGISTRY.items() if not k.startswith("keye")})
    with pytest.raises(cells.BenchmarkError, match="cannot build 'keye_vl_2_0_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_a_trunk_without_the_new_arguments_fails_the_cell_at_once(root, monkeypatch):
    """A registry that knows the name but whose trunk takes none of this
    model's arguments (a ``TypeError`` at construction) ends the same way."""
    import tpuddp.models as zoo

    monkeypatch.setitem(zoo._REGISTRY, "keye_vl_2_0_tiny", lambda num_classes, hidden_size: None)
    with pytest.raises(cells.BenchmarkError, match="cannot build 'keye_vl_2_0_tiny'"):
        run.run_cell(WORKLOAD, seed=1, seconds=0.1, trace=False, root=root)


def test_the_traced_line_reports_the_counter_reader(root, monkeypatch):
    """A CPU capture has no device plane, so the recorded AlexNet capture
    stands in: the scope readers find none of this model's layers in it and
    report nothing (as they do on a program without the scopes); the
    counters' reader reads the window's own counters, and the readers without
    a list of cells report as they do everywhere."""
    from benchmark.tests.test_harness import RECORDED

    monkeypatch.setattr(
        trace_reduce, "capture_events", lambda trace_dir: trace_reduce.load_events(RECORDED)
    )
    result = run.run_cell(WORKLOAD, seed=3000000011, seconds=5, trace=True, root=root)
    assert result["correct"] is True
    assert {"compile_s", "device_ms_per_step", "device_mfu_pct", *COUNTER_READERS} <= set(result["metrics"])
    assert 0 < result["metrics"]["indexer_kl_per_row"]["value"] < 0.5
    assert not set(SCOPE_READERS) & set(result["metrics"])
    assert not {"routed_moe_load_imbalance", "mtp_loss_per_token", "attention_ms_per_step"} & set(result["metrics"])


# -- the planted faults, through the harness's own comparison -------------------------------

FAULTS = ("selection_ignored", "half_the_keys", "no_relu", "no_indexer_loss", "indexer_reads_through")


def plant(fault: str, monkeypatch) -> None:
    """Plant one of the program's five faults (``benchmark/configs``: the
    configuration's ``check.reason`` has what each reads at the cell's size)."""
    from tpuddp.models.hybrid_moe import HybridMoELM
    from tpuddp.nn import sequence as seq

    def built_with(change):
        real = HybridMoELM.__init__
        monkeypatch.setattr(HybridMoELM, "__init__", lambda self, *a, **kw: real(self, *a, **change(kw)))

    if fault == "selection_ignored":  # (a) every earlier key attended: the mask is the causal one
        monkeypatch.setattr(seq, "top_k_mask", lambda scores, visible, k, **lowering: visible)
    elif fault == "half_the_keys":  # (b) 1,024 keys for 2,048
        built_with(lambda kw: {**kw, "index_top_k": kw["index_top_k"] // 2})
    elif fault == "no_relu":  # (c) the ReLU left out of I: the heads' products summed as they are
        from tpuddp.nn import sparse_attention_kernels as kernels

        @jax.custom_vjp
        def linear(qi, ki, wi):
            return jnp.matmul(jnp.einsum("gj,gjd->gd", wi, qi.astype(jnp.float32)).astype(qi.dtype), ki.T,
                              preferred_element_type=jnp.float32)

        plain = lambda qi, ki, wi: jnp.matmul(jnp.einsum("gj,gjd->gd", wi, qi.astype(jnp.float32)), ki.T.astype(jnp.float32))
        linear.defvjp(lambda *a: (linear(*a), a), lambda saved, d: tuple(
            g.astype(a.dtype) for g, a in zip(jax.vjp(plain, *saved)[1](d), saved)))
        monkeypatch.setattr(seq, "index_scores", linear)  # either lowering's
        monkeypatch.setattr(kernels, "index_scores", lambda qi, ki, wi, start, interpret=False: linear(qi, ki, wi))
    elif fault == "no_indexer_loss":  # (d) lambda_I = 0: the indexers' 11.3M elements stand still
        built_with(lambda kw: {**kw, "indexer_loss_weight": 0.0})
    elif fault == "indexer_reads_through":  # (e) a stop-gradient missing: L_I reaches norms, embedding and the layers below through h
        monkeypatch.setattr(seq, "indexer_input", lambda h: h)
    else:
        raise ValueError(fault)


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_sparse_moe_lm")


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
    """Under the file's limits, which were set at the cell's size from the
    chip's readings (``check.reason``): in float32 products with half of each
    limit to spare; in bfloat16, whose rounding 64 tokens do not average out
    as 32,768 do, within twice the limit on the update's norm."""
    got, limits = _held_to_the_files_limits(system, published, seed, compute_dtype="float32")
    assert got["ok"] is True
    assert got["loss_rel_err"] < limits["loss_rtol"] / 2 and got["update_norm_rel_err"] < limits["update_norm_rtol"] / 2
    got, _ = _held_to_the_files_limits(system, published, seed)
    assert got["loss_rel_err"] < limits["loss_rtol"] / 2 and got["update_norm_rel_err"] < 2 * limits["update_norm_rtol"]


# the faults the file's limits read at the tests' size too; the ReLU left out (c) turns the indexers' gradients
# alone and reads under the limit at 64 tokens: its reading at the cell's size is in check.reason
_READ_AT_THE_TESTS_SIZE = ("selection_ignored", "half_the_keys", "no_indexer_loss")


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "indexer_reads_through"])
@pytest.mark.parametrize("seed", [11, 2600000501])
def test_a_planted_fault_fails_the_configurations_limits(system, published, fault, seed, monkeypatch):
    """Four of the program's five planted faults, in float32 products (where
    the sound program stands within 2e-4 of the reference on the update's
    norm): three come out NOT correct under the file's limits, by the update's
    norm (leaves that lose their gradient stand still from the first step, the
    indexers' under (d); the others turn the gradients of the leaves they
    touch), and the fourth reads ten times the sound program's own reading and
    more. What each reads at the cell's size, on the chip, is in the file's
    ``check.reason``."""
    sound, limits = _held_to_the_files_limits(system, published, seed, compute_dtype="float32")
    plant(fault, monkeypatch)
    got, _ = _held_to_the_files_limits(system, published, seed, compute_dtype="float32")
    assert sound["ok"] is True and sound["update_norm_rel_err"] < 2e-4, sound
    assert got["update_norm_rel_err"] > 10 * sound["update_norm_rel_err"], (got, sound)
    if fault in _READ_AT_THE_TESTS_SIZE:
        assert got["ok"] is False and got["update_norm_rel_err"] > limits["update_norm_rtol"], (got, limits)


@pytest.mark.parametrize("planted", [False, True])
def test_a_missing_stop_gradient_is_held_by_the_gradient_itself(system, published, planted, monkeypatch):
    """(e) The indexer reading its input with no stop-gradient: ``L_I`` then
    reaches the layer's norm, the embedding and every layer below through
    ``h``. A fresh indexer's objective is small beside the language model's
    loss and Adam's first steps move an element by the rate whatever its
    gradient's size, so the comparison with the reference cannot read it (at
    this size it reads what the sound program reads; the configuration's
    ``check.reason`` names it as a fault both limits pass). What holds it is
    the gradient itself, exactly: ``dL_I / d(embedding)`` is 0 in every
    element of the sound program and not of the planted one (tier-1:
    tests/test_sparse_moe_training.py, leaf by leaf)."""
    from tpuddp.models import load_model
    from tpuddp.nn.core import Context

    if planted:
        plant("indexer_reads_through", monkeypatch)
    config = {**system.shrunk(published), "compute_dtype": "float32", "aux_loss_weight": 0.0}
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    params, state = model.init(jax.random.key(3), None)
    tokens = jnp.asarray(_SeededBatches(system, config, 7).sample_batches(1, 2)[0][0])
    of_index = jax.grad(lambda p: model.apply(p, state, tokens, Context(train=True))[0].aux_loss)(params)
    assert bool(np.any(np.asarray(of_index["embed"]["weight"]))) is planted
    assert np.any(np.asarray(of_index["layers"][1]["mixer"]["indexer"]["q_proj"]))


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


def test_the_references_stretches_change_no_arithmetic(system, published, monkeypatch):
    """A block of the reference's queries meets the keys up to the end of its
    stretch of the sequence and no later one: the same selection to the pair
    and the same three losses as whole rows give."""
    from tpuddp.models import load_model

    reference = cells.load_module("reference", CONFIG)
    config = system.shrunk(published)
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    params = jax.tree_util.tree_map(  # perturbed: the indexer's choice then differs from the first keys
        lambda a: a * 8.0 if a.ndim > 1 else a, jax.device_get(system.init_variables(model, config, 5))[0])
    tokens, targets = _SeededBatches(system, config, 5).sample_batches(1, 2)[0]
    monkeypatch.setattr(reference, "_QUERY_BLOCK", 8)  # 64 tokens: four stretches of two blocks
    with jax.default_matmul_precision("highest"):
        stretches = reference.losses(config, params, tokens, targets)
        monkeypatch.setattr(reference, "_STRETCHES", 1)
        whole_rows = reference.losses(config, params, tokens, targets)
    assert float(stretches[3]) == float(whole_rows[3]) > 0
    np.testing.assert_allclose(np.asarray(stretches[:3]), np.asarray(whole_rows[:3]), rtol=2e-6)
    assert float(stretches[2]) > 1e-3  # the indexers' objective is there to differ


def test_the_reference_reports_the_indexers_objective(system, published):
    """``with_index``: a third list, ``L_I`` a step, which the program's
    counters carry out as a sum over rows and layers."""
    from tpuddp import nn
    from tpuddp.models import load_model
    from tpuddp.nn.core import Context

    reference = cells.load_module("reference", CONFIG)
    config = {**system.shrunk(published), "compute_dtype": "float32"}
    model = load_model(config["model"]["registry_name"], config["vocab_size"], **system.model_kwargs(config))
    init = jax.device_get(system.init_variables(model, config, 7))
    batches = _SeededBatches(system, config, 7).sample_batches(1, 2)
    losses, norms, index = reference.train_steps(config, *init, batches, with_index=True)
    assert len(losses) == len(norms) == len(index) == 1 and 0 < index[0] < 1.0
    out, _ = model.apply(*init, jnp.asarray(batches[0][0]), Context(train=True))
    loss = nn.CrossEntropyLoss()(out, jnp.asarray(batches[0][1]), jnp.ones(batches[0][1].shape, jnp.float32))
    assert float(loss) == pytest.approx(losses[0], rel=1e-5)
    per_row = float(out.counters["indexer_kl_sum"] / out.counters["indexer_rows"])
    assert per_row * config["num_hidden_layers"] == pytest.approx(index[0], rel=2e-4)


# -- the share and the tree -------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(system, published):
    """The deployment's shares (four of 2 of 8 experts here, eight of 16 of
    128 in the cell), all under one router: their routed parts add up to the
    uncut reference's expert layer, every assignment is some share's, and what
    every chip computes alike (attention, the indexer, its selection and its
    objective: no expert in them) is the same whatever the share, so it counts
    once: the uncut reference's whole layer is attention's half plus the sum
    of the shares' routed parts."""
    from tpuddp.models import load_model
    from tpuddp.nn import moe as moe_lib

    reference = cells.load_module("reference", CONFIG)
    config = {**system.shrunk(published), "compute_dtype": "float32"}
    n_all, held = config["deployment"]["experts_published"], config["num_experts"]
    uncut = {**config, "num_experts": n_all, "num_local_experts": n_all}
    model = load_model(uncut["model"]["registry_name"], uncut["vocab_size"], **system.model_kwargs(uncut))
    params, _ = model.init(jax.random.key(3), None)
    p = jax.tree_util.tree_map(lambda a: a * 8.0 if a.ndim > 1 else a, params["layers"][1])
    x = jnp.asarray(np.random.RandomState(4).randn(2, 40, config["hidden_size"]), jnp.float32)
    eps = config["rms_norm_eps"]
    rms = lambda x, w: x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w
    with jax.default_matmul_precision("highest"):
        mixed, kl, pairs = reference.sparse_mixer(uncut, p["mixer"], rms(x, p["input_norm"]))
        a = x + mixed  # what every chip computes alike
        normed = rms(a, p["post_norm"])
        whole, _ = reference.moe(uncut, p["moe"], normed)
        flat = normed.reshape(-1, normed.shape[-1])
        total, seen = 0.0, 0.0
        for share in range(n_all // held):
            mine = {**p["moe"], "experts": jax.tree_util.tree_map(lambda w: w[share * held:(share + 1) * held], p["moe"]["experts"])}
            y, _, counters, _ = moe_lib.expert_share_moe(
                mine, flat, top_k=model.top_k, first_expert=share * held, compute_dtype=jnp.float32,
            )
            theirs = {**config, "deployment": {**config["deployment"], "first_expert": share * held}}
            np.testing.assert_allclose(y, reference.moe(theirs, mine, normed)[0].reshape(flat.shape), rtol=2e-4, atol=2e-5)
            # the share's model computes the same attention half: no expert is in it
            ours = model._sparse_mix({**p, "moe": mine}, x[0], False)
            np.testing.assert_allclose(ours[0], a[0], rtol=2e-4, atol=2e-5)
            total = total + y
            seen += float(counters["moe_expert_tokens_held"])
        np.testing.assert_allclose(total, whole.reshape(flat.shape), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(a.reshape(flat.shape) + total, (a + whole).reshape(flat.shape), rtol=2e-4, atol=2e-5)
    assert seen == flat.shape[0] * model.top_k and n_all // held == 4
    assert published["deployment"]["experts_published"] // published["num_experts"] == 8


def test_the_built_tree_is_the_tables_count(system, published):
    """The cell-size tree, as shapes only: 562,290,560 parameters, part by
    part as the issue's table has them."""
    from tpuddp.models import load_model

    model = load_model(published["model"]["registry_name"], published["vocab_size"], **system.model_kwargs(published))
    preset = load_model("keye_vl_2_0_ep8", published["vocab_size"])
    ours = {"compute_dtype": None}  # the file's own choice (`assumed`)
    assert {**vars(model), **ours} == {**vars(preset), **ours}
    shapes, state = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    count = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64
    assert count(shapes["layers"][0]["mixer"]["indexer"]) == indexer == 2_261_120
    assert count(shapes["layers"][0]["mixer"]) == 18_874_368 + 256 + indexer
    assert count(shapes["layers"][0]["moe"]) == 262_144 + 16 * 4_718_592
    layer = 18_874_368 + 256 + indexer + 262_144 + 4096 + 75_497_472
    assert [count(l) for l in shapes["layers"]] == [layer] * 5 and layer == 96_899_456
    assert count(shapes["embed"]) + count(shapes["head"]) == 2 * 18992 * 2048 == 77_791_232
    assert count(shapes) == published["parameters"] == 5 * layer + 77_791_232 + 2048 == 562_290_560
    assert state == () and "shared" not in shapes["layers"][0]["moe"]
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 48, "num_experts": 128, "num_local_experts": 128, "vocab_size": 151936}
    assert 5 * indexer == 11_305_600  # what stands still under lambda_I = 0


# -- the readers ---------------------------------------------------------------------------

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
    rows = "while/body/closed_call/checkpoint/"
    ops = [
        ("f1", FWD + "0_SparseAttention/checkpoint/qkv/dot_general:", 4),
        ("f2", FWD + "0_SparseAttention/checkpoint/index_proj/dot_general:", 3),
        ("f3", FWD + "0_SparseAttention/" + rows + "index_proj/dot_general:", 5),
        ("f4", FWD + "0_SparseAttention/" + rows + "index_scores/while/body/dot_general:", 20),
        ("f5", BWD + "0_SparseAttention/" + rows + "index_scores/while/body/dot_general:", 40),
        ("f6", REMAT + "1_SparseAttention/" + rows + "rematted_computation/index_select/while/body/reduce_sum:", 30),
        ("f7", FWD + "1_SparseAttention/" + rows + "index_select/while/body/reduce_sum:", 30),
        ("f8", FWD + "2_SparseAttention/" + rows + "attention/pallas_call:", 70),
        ("f9", BWD + "2_SparseAttention/" + rows + "attention/pallas_call:", 170),
        ("f10", FWD + "2_SparseAttention/" + rows + "indexer_loss/while/body/dot_general:", 60),
        ("f11", BWD + "4_SparseAttention/" + rows + "o_proj/dot_general:", 2),
        ("f12", FWD + "4_SparseAttention/" + rows + "mul:", 1),  # the layer's norm: no part's scope
        ("f13", FWD + "3_SparseAttention/checkpoint/moe/router/dot_general:", 1),
        ("f14", FWD + "3_SparseAttention/checkpoint/moe/while/body/experts/attention/mul:", 2),  # inside the expert layer
        ("o1", PRE + "tpuddp.optimizer/mul:", 10),
        ("o2", LOSS + "while/body/checkpoint/dot_general:", 50),
        ("o3", FWD + "1_FullAttention/while/body/checkpoint/attention/dot_general:", 50),  # another family's
        ("o4", FWD + "1_LatentAttention/while/body/checkpoint/attention/dot_general:", 50),
    ]
    events, ts = _meta() + [_host("bench:window", 0, 700 * us)], 0.0
    for name, tf_op, ms in ops:
        events.append(_op(name, ts, ms * us, tf_op=tf_op))
        ts += ms * us
    return events


def test_the_scope_readers_on_the_models_names():
    """Device time by part under the sparse-attention layers, forward,
    backward and recomputation together, the expert layer left out; a
    roofline share is the need's least time over the time, attention's need
    the SELECTED pairs the counters carried out."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", CONFIG)
    pairs = 2.0 * 5 * flops.selected_pairs(32768, 2048)
    counters = {"indexer_kl_sum": 0.25 * 2 * 5 * 32768, "indexer_rows": 2.0 * 5 * 32768, "index_selected_pairs": pairs}
    run_ = _run_with(_events(), counters=counters)
    read = lambda name: cells.load_module("layer_metrics", name).read(run_)
    assert read("indexer_ms_per_step") == pytest.approx((3 + 5 + 20 + 40) / 2)
    assert read("index_select_ms_per_step") == pytest.approx((30 + 30) / 2)
    assert read("sparse_attention_ms_per_step") == pytest.approx((70 + 170 + 60) / 2)
    assert read("indexer_kl_per_row") == pytest.approx(0.25)
    peaks = run_["peaks"]
    ops, nbytes = flops.sparse_attention_cost(cfg, 5 * 65536, pairs)
    assert ops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]  # a compute bound
    assert ops == 3 * 4 * 128 * 32 * pairs
    assert read("sparse_attention_roofline_pct") == pytest.approx(100 * ops / peaks["bf16_flops_per_s"] / 300e-3)
    ops, nbytes = flops.index_scores_cost(cfg, 65536)
    assert ops == 6.0 * 16 * 64 * 2 * flops.visible_pairs(32768)
    assert read("index_scores_roofline_pct") == pytest.approx(100 * 5 * ops / peaks["bf16_flops_per_s"] / 60e-3)
    # whole blocks scored under the mask: the need is an eighth of the causal triangle's, so the share reads low
    assert flops.selected_pairs(32768, 2048) / flops.visible_pairs(32768) == pytest.approx(0.121, abs=1e-3)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, another token cell's or an image cell's: scopes,
    but no ``<i>_SparseAttention`` layer. Every scope reader returns nothing
    and none raises; the counter's reader returns nothing where the step
    carries no such counters out. On an empty capture the same."""
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
    # the scopes without the counter (a capture alone): the time readers read, the pairs' reader has nothing to read
    run_ = _run_with(_events())
    assert cells.load_module("layer_metrics", "sparse_attention_ms_per_step").read(run_) == pytest.approx(150.0)
    assert cells.load_module("layer_metrics", "sparse_attention_roofline_pct").read(run_) is None


def test_analytic_counts_of_the_published_cut():
    """A token's multiply-accumulates by the mathematics: a layer's attention
    projections 18,874,368, the indexer's 2,260,992 (no gradient goes on to
    their input), the index scores 1,024 wide over 16,384.5 keys a query, the
    sparse product 8,192 wide over the 1,984.03 keys a query selected on
    average at 32,768 tokens, the router 262,144 and the held share of the
    routed experts (one expert a token at uniform routing) 4,718,592; the head
    38,895,616 once."""
    cfg = cells.load_cell(WORKLOAD).config
    flops = cells.load_module("flops", CONFIG)
    assert flops.visible_pairs(32768) == 32768 * 32769 // 2
    assert flops.selected_pairs(32768, 2048) == 2048 * 2049 // 2 + (32768 - 2048) * 2048 == 65_012_736
    per_product = [m for m, _ in flops.products(cfg)]
    layer = [18_874_368, 2_260_992, 1024 * 32769 // 2, 8192 * 65_012_736 // 32768, 262_144, 4_718_592]
    assert per_product[:6] == layer and per_product[-1] == 2048 * 18992 and len(per_product) == 5 * 6 + 1
    assert [needs for _, needs in flops.products(cfg)[:6]] == [True, False, True, True, True, True]
    total = flops.train_flops_per_sample(cfg)
    assert total == 6.0 * (5 * (sum(layer) - layer[1]) + per_product[-1]) + 4.0 * 5 * layer[1]
    # scoring, selecting and attending are over half a layer's products
    assert (layer[2] + layer[3]) / sum(layer) > 0.5
    ops, nbytes = flops.sparse_attention_cost(cfg, 32768, 65_012_736)
    assert ops == 12.0 * 128 * 32 * 65_012_736 and nbytes == 3 * 32768 * 72 * 128 * 2
    assert not hasattr(flops, "expert_matmul_cost")  # no metric of this cell reads the expert products (ROADMAP W0)
