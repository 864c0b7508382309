"""The sparse-attention family trained: whole steps of the tiny preset through
``DistributedDataParallel`` (``train_step`` and ``train_step_many``) against
the plain reference's (benchmark/reference/keye_vl_2_0_30b_a3b_ep8.py), the
two stop-gradients that keep the language model's loss and the indexers'
objective on leaves of their own, the indexers' counters summed over steps, a
checkpoint, and ``train_native.py``'s worker on the token stream. The layers'
own tests are in tests/test_sparse_moe.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from test_conv_moe_training import _update_norm
from test_window_moe_training import _batches
from tpuddp import nn
from tpuddp.models import load_model
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context
from tpuddp.parallel import make_mesh
from tpuddp.training import checkpoint as ckpt

CONFIG_NAME = "keye_vl_2_0_30b_a3b_ep8"
WORKLOAD = "keye2_ep8_t32k_fused"
VOCAB = 96


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_sparse_moe_lm")


@pytest.fixture(scope="module")
def tiny(system):
    """The configuration at the tiny preset's sizes, in float32 and at a rate
    at which three steps visibly move a 64-wide model (the cell's 1e-5 is the
    foot of a warm-up)."""
    config = system.shrunk(cells.load_cell(WORKLOAD).config)
    return {**config, "compute_dtype": "float32", "optimizer": {**config["optimizer"], "lr": 3e-3}}


def _cell(config, devices=1):
    return cells.Cell(
        name="t", chips=devices, config_name=CONFIG_NAME, config=config, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )


def _built(system, config, devices):
    cell = _cell(config, devices)
    mesh = make_mesh(jax.devices()[:devices], {"data": devices})
    model, ddp = system.build_ddp(cell, mesh)
    variables = system.init_variables(model, cell.config, 11)
    init = jax.device_get(variables)
    return ddp, init, system.init_state(model, ddp, cell.config, 11, variables)


def _is_indexer(path) -> bool:
    return "indexer" in jax.tree_util.keystr(path)


# -- the two stop-gradients ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def both_gradients(system, tiny):
    """``(grad L_LM, grad L_I)`` of the tiny model on its seeded weights,
    each taken alone: the language model's loss with both other losses'
    weights at 0, and what enters the gradient beside it with the routers'
    weight at 0."""
    kwargs = {**system.model_kwargs(tiny), "aux_loss_weight": 0.0}
    tokens, targets = _batches(system, tiny, 1, 2)[0]
    ones = jnp.ones(targets.shape, jnp.float32)

    def grads(indexer_loss_weight, which):
        model = load_model(tiny["model"]["registry_name"], VOCAB, **{**kwargs, "indexer_loss_weight": indexer_loss_weight})
        params, state = model.init(jax.random.key(11), None)

        def loss(params):
            out, _ = model.apply(params, state, jnp.asarray(tokens), Context(train=True))
            return nn.CrossEntropyLoss()(out, jnp.asarray(targets), ones) if which == "lm" else out.aux_loss

        return jax.jit(jax.grad(loss))(params)

    return grads(0.0, "lm"), grads(1.0, "index")


def test_the_language_models_loss_never_reaches_the_indexer(both_gradients):
    """``dL_LM / d(indexer leaves)`` is exactly 0, every element, and every
    other leaf has a gradient: the selection is no function the loss can be
    differentiated through, and the indexer reads its input behind a
    stop-gradient."""
    of_lm, _ = both_gradients
    for path, g in jax.tree_util.tree_leaves_with_path(of_lm):
        if _is_indexer(path):
            assert not np.any(np.asarray(g)), jax.tree_util.keystr(path)
        else:
            assert np.any(np.asarray(g)), jax.tree_util.keystr(path)


def test_the_indexers_objective_reaches_the_indexer_alone(both_gradients):
    """``dL_I / d(every other leaf)`` is exactly 0, the embedding and the
    layer's own norm among them (the indexer's input is behind a
    stop-gradient, its target, attention's distribution, behind another), and
    each of the indexer's five leaves has a gradient."""
    _, of_index = both_gradients
    moved = 0
    for path, g in jax.tree_util.tree_leaves_with_path(of_index):
        if _is_indexer(path):
            assert np.any(np.asarray(g)), jax.tree_util.keystr(path)
            moved += 1
        else:
            assert not np.any(np.asarray(g)), jax.tree_util.keystr(path)
    assert moved == 2 * 5


# -- whole steps ---------------------------------------------------------------------------------

@pytest.mark.parametrize("devices", [1, 2])
def test_three_training_steps_through_ddp_match_the_reference(reference, system, tiny, devices):
    """The whole model through ``DistributedDataParallel`` (one device, and
    two with the batch split) against the reference's float32 steps on one
    worker: the reported loss is the language model's alone, the norm of each
    Adam update carries the indexers' gradient, and the counters carry their
    objective. Each replica takes the load-balancing loss over its own tokens,
    and a product of two means is not linear in the tokens: the two-replica
    case is compared with that term off; the indexers' objective is a mean
    over rows and stays on."""
    config = tiny if devices == 1 else {**tiny, "aux_loss_weight": 0.0}
    batches = _batches(system, config, 3, 4)
    ddp, init, state = _built(system, config, devices)
    ones = system.unit_weights(config, 4)
    losses, norms, index, prev = [], [], [], init[0]
    layers, t, top_k = config["num_hidden_layers"], config["tokens"]["seq_len"], config["sa_config"]["topk"]
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        index.append(layers * float(np.sum(m["indexer_kl_sum"]) / np.sum(m["indexer_rows"])))
        norms.append(_update_norm(new, prev))
        prev = new
        assert set(m) == {"loss_sum", "n", *moe_lib.COUNTERS, *seq.SPARSE_COUNTERS}
        assert np.sum(m["indexer_rows"]) == 4 * t * layers
        assert np.sum(m["index_selected_pairs"]) == 4 * layers * sum(min(i + 1, top_k) for i in range(t))
        held, absent = np.sum(m["moe_expert_tokens_held"]), np.sum(m["moe_absent_assignments"])
        assert held + absent == batch[0].size * config["num_experts_per_tok"] * layers
        assert np.sum(m["moe_dropped_assignments"]) == 0
    ref_losses, ref_norms, ref_index = reference.train_steps(config, *init, batches, with_index=True)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(index, ref_index, rtol=2e-4)
    np.testing.assert_allclose(norms, ref_norms, rtol=5e-3)
    assert losses[-1] < losses[0]


def test_without_its_objective_the_indexer_does_not_move(system, tiny):
    """``indexer_loss_weight`` 0 takes the objective out of the gradient: the
    indexers' leaves stay where they were (Adam moves nothing that has no
    gradient) while the rest moves, and the counters still carry the
    objective."""
    batch = _batches(system, tiny, 1, 4)[0]
    ones = system.unit_weights(tiny, 4)
    ddp, init, state = _built(system, {**tiny, "indexer_loss_weight": 0.0}, 1)
    state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
    new = jax.device_get(state.params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(init[0])):
        assert np.array_equal(a, b) == _is_indexer(path), jax.tree_util.keystr(path)
    assert float(np.sum(jax.device_get(m)["indexer_kl_sum"])) > 0


def test_counters_ride_through_train_step_many_as_through_single_steps(system, tiny):
    """``train_step_many``'s K fused steps are K calls of ``train_step``:
    parameters and each step's metrics, the indexers' among them, are the
    same."""
    batches = _batches(system, tiny, 3, 4)
    ones = system.unit_weights(tiny, 4)
    ddp, _, state = _built(system, tiny, 2)
    singles = []
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        singles.append(jax.device_get(m))
    one_by_one = jax.device_get(state.params)
    ddp, _, state = _built(system, tiny, 2)
    stacked = tuple(np.stack(a) for a in zip(*[(*batch, ones) for batch in batches]))
    state, many = ddp.train_step_many(state, ddp.shard_stacked(stacked))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state.params)), jax.tree_util.tree_leaves(one_by_one)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    many = jax.device_get(many)
    for name in ("loss_sum", "n", "moe_expert_tokens_held", *seq.SPARSE_COUNTERS):
        np.testing.assert_allclose(np.sum(many[name]), sum(np.sum(m[name]) for m in singles), rtol=1e-5)


def test_the_indexer_comes_back_from_a_checkpoint(system, tiny, tmp_path):
    """The indexers' leaves are parameters like any others: after two steps
    they have moved, are written and read back onto the mesh as they were, and
    the restored state steps on as the saved one does."""
    batches = _batches(system, tiny, 2, 4)
    ones = system.unit_weights(tiny, 4)
    ddp, init, state = _built(system, tiny, 2)
    for batch in batches:
        state, _ = ddp.train_step(state, ddp.shard((*batch, ones)))
    ckpt.save_on_main(str(tmp_path), epoch=0, tree=state)
    _, _, fresh = _built(system, tiny, 2)
    restored, next_epoch = ckpt.restore_latest(str(tmp_path), fresh)
    assert next_epoch == 1
    saved, back = jax.device_get((state.params, restored.params))
    for (path, a), b, start in zip(jax.tree_util.tree_leaves_with_path(saved), jax.tree_util.tree_leaves(back),
                                   jax.tree_util.tree_leaves(init[0])):
        np.testing.assert_array_equal(a, b)
        if _is_indexer(path):
            assert np.any(a != start), jax.tree_util.keystr(path)
    batch = _batches(system, tiny, 1, 4, seed=5)[0]
    _, m_saved = ddp.train_step(state, ddp.shard((*batch, ones)))
    _, m_back = ddp.train_step(restored, ddp.shard((*batch, ones)))
    np.testing.assert_allclose(np.sum(m_back["loss_sum"]), np.sum(m_saved["loss_sum"]), rtol=1e-6)
    np.testing.assert_allclose(np.sum(m_back["indexer_kl_sum"]), np.sum(m_saved["indexer_kl_sum"]), rtol=1e-5)


def test_train_native_trains_the_tiny_preset_on_the_token_stream(tmp_path):
    """``train_native.py``'s worker: the registry's tiny preset on
    ``markov_tokens`` through the loader, ``DistributedDataParallel`` and the
    epoch driver on the 8-device CPU world; the expert counters and the
    indexers' reach the epoch's row, and the loss falls."""
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel import backend
    from tpuddp.parallel.spawn import run_ddp_training

    training = {
        "model": "keye_vl_2_0_tiny", "dataset": "markov_tokens", "num_classes": VOCAB, "seq_len": 48,
        "synthetic_n": [256, 64], "train_batch_size": 8, "test_batch_size": 8, "learning_rate": 0.003,
        "num_epochs": 2, "checkpoint_epoch": 2, "image_size": None, "seed": 0, "mode": "shard_map",
        "sync_bn": False, "scan_steps": 4,
    }
    backend.cleanup()
    try:
        run_ddp_training(
            partial(basic_ddp_training_loop, training=training), world_size=8,
            save_dir=str(tmp_path), optional_args={"set_epoch": True, "print_rand": False}, backend="cpu",
        )
    finally:
        backend.cleanup()
    rows = [json.loads(l) for l in open(os.path.join(tmp_path, "history.jsonl"))]
    epochs = [r for r in rows if "train_loss" in r]
    assert len(epochs) == 2 and epochs[1]["train_loss"] < epochs[0]["train_loss"]
    assert epochs[0]["train_samples"] == 256
    assert epochs[0]["moe_dropped_assignments"] == 0 and epochs[0]["moe_expert_tokens_held"] > 0
    assert epochs[0]["indexer_rows"] == 256 * 48 * 2
    assert epochs[0]["index_selected_pairs"] == 256 * 2 * sum(min(i + 1, 8) for i in range(48))
    assert all(e["indexer_kl_sum"] > 0 for e in epochs)
