"""The latent-attention family trained: whole steps of the tiny preset through
``DistributedDataParallel`` (``train_step`` and ``train_step_many``) against
the plain reference's (benchmark/reference/glm_4_7_flash_ep8.py), the
selection biases (the prediction module's among them) riding in the model
state and through a checkpoint, the second head's counters summed over steps,
and ``train_native.py``'s worker on the token stream. The layers' own tests
are in tests/test_latent_moe.py."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import cells
from test_conv_moe_training import _update_norm
from test_window_moe_training import _batches
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.parallel import make_mesh
from tpuddp.training import checkpoint as ckpt

CONFIG_NAME = "glm_4_7_flash_ep8"
WORKLOAD = "glm47flash_ep8_t16k_fused"
VOCAB = 96


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_latent_moe_lm")


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


@pytest.mark.parametrize("devices", [1, 2])
def test_three_training_steps_through_ddp_match_the_reference(reference, system, tiny, devices):
    """The whole model through ``DistributedDataParallel`` (one device, and
    two with the batch split) against the reference's float32 steps on one
    worker: the reported loss is the next token's alone, the norm of each Adam
    update carries the second head's gradient, the counters carry its loss,
    and the biases (the module's too) come out moved by the counts of every
    replica's tokens."""
    batches = _batches(system, tiny, 3, 4)
    ddp, init, state = _built(system, tiny, devices)
    ones = system.unit_weights(tiny, 4)
    losses, norms, second, prev = [], [], [], init[0]
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        second.append(float(np.sum(m["mtp_loss_sum"]) / np.sum(m["mtp_tokens"])))
        norms.append(_update_norm(new, prev))
        prev = new
        assert set(m) == {"loss_sum", "n", *moe_lib.COUNTERS, *seq.NEXT_COUNTERS}
        assert np.sum(m["mtp_tokens"]) == 4 * (tiny["tokens"]["seq_len"] - 1)
        held, absent = np.sum(m["moe_expert_tokens_held"]), np.sum(m["moe_absent_assignments"])
        sparse = tiny["num_hidden_layers"] - tiny["first_k_dense_replace"] + tiny["num_nextn_predict_layers"]
        assert held + absent == batch[0].size * tiny["num_experts_per_tok"] * sparse
        assert np.sum(m["moe_dropped_assignments"]) == 0
    ref_losses, ref_norms, ref_second = reference.train_steps(tiny, *init, batches, with_mtp=True)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(second, ref_second, rtol=2e-5)
    np.testing.assert_allclose(norms, ref_norms, rtol=5e-3)
    assert losses[-1] < losses[0] and second[-1] < second[0]
    # three steps of the rule: every bias within three steps of where it started (0), and some moved
    final = jax.device_get(state.model_state)
    assert final[0] == () and len(final) == tiny["num_hidden_layers"] + 1
    for new in final[1:]:
        steps = new["expert_bias"] / tiny["expert_bias_update_rate"]
        assert np.all(np.abs(steps) < 3.001) and np.any(np.abs(steps) > 0.999)
        np.testing.assert_allclose(steps, np.round(steps), atol=2e-3)


def test_without_the_second_loss_the_module_does_not_move(system, tiny):
    """``mtp_loss_weight`` 0 takes the second head out of the gradient: the
    module's leaves stay where they were (Adam moves nothing that has no
    gradient) and the trunk moves as a model without the module would; the
    counters still carry the second loss."""
    batch = _batches(system, tiny, 1, 4)[0]
    ones = system.unit_weights(tiny, 4)
    ddp, init, state = _built(system, {**tiny, "mtp_loss_weight": 0.0}, 1)
    state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
    new = jax.device_get(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(new["mtp"]), jax.tree_util.tree_leaves(init[0]["mtp"])):
        np.testing.assert_array_equal(a, b)
    assert _update_norm(new["layers"], init[0]["layers"]) > 0
    assert float(np.sum(jax.device_get(m)["mtp_loss_sum"])) > 0


def test_state_and_counters_ride_through_train_step_many_as_through_single_steps(system, tiny):
    """``train_step_many``'s K fused steps carry the model state from step to
    step as K calls of ``train_step`` do: parameters, biases and each step's
    metrics, the second head's among them, are the same."""
    batches = _batches(system, tiny, 3, 4)
    ones = system.unit_weights(tiny, 4)
    ddp, _, state = _built(system, tiny, 2)
    singles = []
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        singles.append(jax.device_get(m))
    one_by_one = jax.device_get((state.params, state.model_state))
    ddp, _, state = _built(system, tiny, 2)
    stacked = tuple(np.stack(a) for a in zip(*[(*batch, ones) for batch in batches]))
    state, many = ddp.train_step_many(state, ddp.shard_stacked(stacked))
    fused = jax.device_get((state.params, state.model_state))
    for a, b in zip(jax.tree_util.tree_leaves(fused), jax.tree_util.tree_leaves(one_by_one)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    many = jax.device_get(many)
    for name in ("loss_sum", "n", "moe_router_tokens_max", "moe_expert_tokens_held", "mtp_loss_sum", "mtp_tokens"):
        np.testing.assert_allclose(np.sum(many[name]), sum(np.sum(m[name]) for m in singles), rtol=1e-5)
    assert all(np.any(np.abs(b["expert_bias"]) > 0) for b in fused[1][1:])


def test_the_biases_come_back_from_a_checkpoint(system, tiny, tmp_path):
    """The model state is part of the train state: after two steps the moved
    biases, the prediction module's last among them, are written and read back
    onto the mesh as they were."""
    batches = _batches(system, tiny, 2, 4)
    ones = system.unit_weights(tiny, 4)
    ddp, _, state = _built(system, tiny, 2)
    for batch in batches:
        state, _ = ddp.train_step(state, ddp.shard((*batch, ones)))
    ckpt.save_on_main(str(tmp_path), epoch=0, tree=state)
    _, _, fresh = _built(system, tiny, 2)
    restored, next_epoch = ckpt.restore_latest(str(tmp_path), fresh)
    assert next_epoch == 1
    saved, back = jax.device_get((state.model_state, restored.model_state))
    assert len(back) == tiny["num_hidden_layers"] + 1 and back[0] == ()
    for a, b in zip(saved[1:], back[1:]):
        np.testing.assert_array_equal(a["expert_bias"], b["expert_bias"])
    assert np.any(back[-1]["expert_bias"] != 0)  # the module's, moved by its own router's counts
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state.params["mtp"])),
                    jax.tree_util.tree_leaves(jax.device_get(restored.params["mtp"]))):
        np.testing.assert_array_equal(a, b)
    # and the restored state steps on as the saved one does
    batch = _batches(system, tiny, 1, 4, seed=5)[0]
    _, m_saved = ddp.train_step(state, ddp.shard((*batch, ones)))
    _, m_back = ddp.train_step(restored, ddp.shard((*batch, ones)))
    np.testing.assert_allclose(np.sum(m_back["loss_sum"]), np.sum(m_saved["loss_sum"]), rtol=1e-6)


def test_train_native_trains_the_tiny_preset_on_the_token_stream(tmp_path):
    """``train_native.py``'s worker: the registry's tiny preset on
    ``markov_tokens`` through the loader, ``DistributedDataParallel`` and the
    epoch driver on the 8-device CPU world; the expert counters and the
    second head's reach the epoch's row, and its loss falls."""
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel import backend
    from tpuddp.parallel.spawn import run_ddp_training

    training = {
        "model": "glm_4_7_flash_tiny", "dataset": "markov_tokens", "num_classes": VOCAB, "seq_len": 48,
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
    assert epochs[0]["mtp_tokens"] == 256 * 47
    per_token = [e["mtp_loss_sum"] / e["mtp_tokens"] for e in epochs]
    assert per_token[1] < per_token[0] < 1.2 * np.log(VOCAB)
