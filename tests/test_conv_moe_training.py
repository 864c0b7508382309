"""The convolution-and-attention family trained: whole steps of the tiny
preset through ``DistributedDataParallel`` (``train_step`` and
``train_step_many``) against the plain reference's
(benchmark/reference/lfm2_8b_a1b_ep4.py), the bias riding in the model state
as BatchNorm's running statistics do, the controls of ``correct`` through the
harness's own comparison (the bias left out of the choice, the bias let into
the weights, the next precision down), and ``train_native.py``'s worker on the
token stream. The layers' own tests are in tests/test_conv_moe.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, check
from test_window_moe_training import _batches, _SeededBatches, e4m3  # e4m3: an operand and its cotangent in 8 bits
from tpuddp.nn import moe as moe_lib
from tpuddp.parallel import make_mesh

CONFIG_NAME = "lfm2_8b_a1b_ep4"
WORKLOAD = "lfm2_ep4_t32k_fused"
VOCAB = 96


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_conv_moe_lm")


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def tiny(system, published):
    """The configuration at the tiny preset's sizes, as the reference reads it."""
    return system.shrunk(published)


def _cell(config, devices=1, **over):
    return cells.Cell(
        name="t", chips=devices, config_name=CONFIG_NAME, config={**config, **over}, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )


def _update_norm(new, old):
    return float(np.sqrt(sum(
        np.sum(np.square(a - b)) for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old))
    )))


def _built(system, config, devices):
    cell = _cell(config, devices)
    mesh = make_mesh(jax.devices()[:devices], {"data": devices})
    model, ddp = system.build_ddp(cell, mesh, check=True)  # the biases drawn, so that they decide choices
    variables = system.init_variables(model, cell.config, 11)
    init = jax.device_get(variables)
    return ddp, init, system.init_state(model, ddp, cell.config, 11, variables)


@pytest.mark.parametrize("devices", [1, 2])
def test_three_training_steps_through_ddp_match_the_reference(reference, system, tiny, devices):
    """The whole model through ``DistributedDataParallel`` (one device, and
    two with the batch split) against the reference's float32 steps on one
    worker: loss and the norm of each Adam update; the biases ride in the
    model state and come out moved by the counts of every replica's tokens;
    the counters come out with the step's metrics and nothing is dropped."""
    # the cell's 1e-5 is the foot of a warm-up; three steps of it move a
    # 64-wide model's loss by less than its noise, so the test takes a rate
    # at which the loss visibly falls
    config = {**tiny, "compute_dtype": "float32", "optimizer": {**tiny["optimizer"], "lr": 3e-3}}
    batches = _batches(system, config, 3, 4)
    ddp, init, state = _built(system, config, devices)
    ones = system.unit_weights(config, 4)
    losses, norms, prev = [], [], init[0]
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        norms.append(_update_norm(new, prev))
        prev = new
        assert set(m) == {"loss_sum", "n", *moe_lib.COUNTERS}
        held, absent = np.sum(m["moe_expert_tokens_held"]), np.sum(m["moe_absent_assignments"])
        sparse = config["num_hidden_layers"] - config["num_dense_layers"]
        assert held + absent == batch[0].size * config["num_experts_per_tok"] * sparse
        assert np.sum(m["moe_dropped_assignments"]) == 0
    ref_losses, ref_norms = reference.train_steps(config, *init, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(norms, ref_norms, rtol=5e-3)
    assert losses[-1] < losses[0]
    # three steps of the rule: every bias within three steps of where it started, and some moved
    final = jax.device_get(state.model_state)
    assert final[0] == ()
    for new, old in zip(final[1:], init[1][1:]):
        steps = (new["expert_bias"] - old["expert_bias"]) / config["expert_bias_update_rate"]
        assert np.all(np.abs(steps) < 3.001) and np.any(np.abs(steps) > 0.999)
        np.testing.assert_allclose(steps, np.round(steps), atol=2e-3)


def test_the_bias_rides_through_train_step_many_as_through_single_steps(system, tiny):
    """``train_step_many``'s K fused steps carry the model state from step to
    step as K calls of ``train_step`` do: parameters, biases and each step's
    metrics are the same."""
    config = {**tiny, "compute_dtype": "float32", "optimizer": {**tiny["optimizer"], "lr": 3e-3}}
    batches = _batches(system, config, 3, 4)
    ones = system.unit_weights(config, 4)
    ddp, _, state = _built(system, config, 2)
    singles = []
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        singles.append(jax.device_get(m))
    one_by_one = jax.device_get((state.params, state.model_state))
    ddp, _, state = _built(system, config, 2)
    stacked = tuple(np.stack(a) for a in zip(*[(*batch, ones) for batch in batches]))
    state, many = ddp.train_step_many(state, ddp.shard_stacked(stacked))
    fused = jax.device_get((state.params, state.model_state))
    for a, b in zip(jax.tree_util.tree_leaves(fused), jax.tree_util.tree_leaves(one_by_one)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    many = jax.device_get(many)
    for name in ("loss_sum", "n", "moe_router_tokens_max", "moe_expert_tokens_held"):  # summed over the K steps
        np.testing.assert_allclose(np.sum(many[name]), sum(np.sum(m[name]) for m in singles), rtol=1e-5)
    moved = [b["expert_bias"] for b in fused[1][1:]]
    assert all(np.any(np.abs(b) > 0) for b in moved)


def _no_bias_in_the_choice(real):
    def route(x, router, *, top_k, bias=None, **scale):
        return real(x, router, top_k=top_k, bias=None if bias is None else jnp.zeros_like(bias), **scale)
    return route


def _bias_in_the_weights(real):
    def route(x, router, *, top_k, bias=None, scale=1.0):
        assert scale == 1.0  # this family's routes are not scaled
        _, experts, scores = real(x, router, top_k=top_k, bias=bias)
        chosen = jnp.take_along_axis(scores + bias, experts, axis=-1)
        return chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6), experts, scores
    return route


def _held_to_the_files_limits(system, tiny, published, seed, **over):
    limits = {k: published["check"][k] for k in ("loss_rtol", "update_norm_rtol")}
    config = {**tiny, **over, "check": {**tiny["check"], "batch": 4, **limits}}
    mesh = make_mesh(jax.devices()[:1], {"data": 1})
    got = check.against_reference(_cell(config), mesh, seed, _SeededBatches(system, config, seed))
    assert (got["loss_rtol"], got["update_norm_rtol"]) == (limits["loss_rtol"], limits["update_norm_rtol"])
    return got, limits


@pytest.mark.parametrize("seed", [11, 12, 2600000501])
def test_the_sound_program_passes_the_configurations_limits(system, tiny, published, seed):
    """The program with the stated bfloat16 products, through the harness's
    own comparison against the limits the configuration's file states, with
    room to spare."""
    got, limits = _held_to_the_files_limits(system, tiny, published, seed)
    assert got["ok"] is True
    assert got["loss_rel_err"] < limits["loss_rtol"] / 3 and got["update_norm_rel_err"] < limits["update_norm_rtol"] / 3


@pytest.mark.parametrize("seed", [11, 12, 2600000501])
def test_the_bias_left_out_of_the_choice_fails_the_configurations_limits(system, tiny, published, seed, monkeypatch):
    """Planted: a router that chooses by its scores alone. The check's model
    draws its biases at the file's scale, so they decide who is chosen: a
    held expert that the bias keeps every token from gets tokens in the
    faulty program and moves, and the update's norm is not the reference's."""
    monkeypatch.setattr(moe_lib, "route", _no_bias_in_the_choice(moe_lib.route))
    got, limits = _held_to_the_files_limits(system, tiny, published, seed)
    assert got["ok"] is False and got["update_norm_rel_err"] > 1.5 * limits["update_norm_rtol"]


@pytest.mark.parametrize("seed", [11, 2600000501])
def test_the_bias_let_into_the_weights_is_read_by_the_loss_alone(system, tiny, published, seed, monkeypatch):
    """Planted: weights from score plus bias. Every chosen expert stays
    chosen and moves whatever its weight (Adam's first steps move an element
    by the rate wherever its gradient points), so the update's norm is blind
    to it by construction. The loss reads it: in float32 products, where the
    sound program stands within 1e-6 of the reference, hundreds of times
    that. (In bfloat16 at this size the reading is of the size of the
    rounding's own; what the cell's size reads is in the configuration's
    file.)"""
    sound, _ = _held_to_the_files_limits(system, tiny, published, seed, compute_dtype="float32")
    monkeypatch.setattr(moe_lib, "route", _bias_in_the_weights(moe_lib.route))
    got, limits = _held_to_the_files_limits(system, tiny, published, seed, compute_dtype="float32")
    assert sound["loss_rel_err"] < 1e-6 and got["loss_rel_err"] > 5e-5
    assert got["update_norm_rel_err"] < limits["update_norm_rtol"] / 10


@pytest.mark.parametrize("dtype", ["float8_e4m3fn"])
def test_the_next_precision_down_fails_the_configurations_limits(system, tiny, published, dtype):
    """The program with its products' inputs rounded to ``float8_e4m3fn``, the
    8-bit float nearest to bfloat16 (most mantissa), is not ``correct``."""
    got, limits = _held_to_the_files_limits(system, tiny, published, 11, compute_dtype=dtype)
    assert got["ok"] is False
    assert max(got["loss_rel_err"] / limits["loss_rtol"], got["update_norm_rel_err"] / limits["update_norm_rtol"]) > 1.5


@pytest.mark.parametrize("seed", [11, 12])
def test_the_reference_in_8_bit_operands_fails_the_configurations_limits(reference, system, tiny, published, seed, monkeypatch):
    """The control read through the reference side (what PERF.md's reading at
    the cell's size is: no 8-bit type, no program): the reference with every
    product's operands rounded to ``float8_e4m3fn``, put in the program's
    place and held against the reference as it is by the comparison's own
    measure. Not ``correct``."""
    from tpuddp.models import load_model

    model = load_model(
        tiny["model"]["registry_name"], tiny["vocab_size"], **system.model_kwargs(tiny),
        expert_bias_std=tiny["check"]["expert_bias_std"],
    )
    init = jax.device_get(system.init_variables(model, tiny, seed))
    batches = _batches(system, tiny, 3, 4, seed)
    plain = reference.train_steps(tiny, *init, batches)
    monkeypatch.setattr(reference, "_operand", e4m3)
    rounded = reference.train_steps(tiny, *init, batches)
    worst = lambda ours, theirs: max(abs(a - b) / abs(b) for a, b in zip(ours, theirs))
    limits = published["check"]
    assert max(worst(rounded[0], plain[0]) / limits["loss_rtol"], worst(rounded[1], plain[1]) / limits["update_norm_rtol"]) > 1.5


def test_train_native_trains_the_tiny_preset_on_the_token_stream(tmp_path):
    """``train_native.py``'s worker: the registry's tiny preset on
    ``markov_tokens`` through the loader, ``DistributedDataParallel`` and the
    epoch driver on the 8-device CPU world; the expert counters reach the
    epoch's row, and the biases the checkpoint."""
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel import backend
    from tpuddp.parallel.spawn import run_ddp_training

    training = {
        "model": "lfm2_tiny", "dataset": "markov_tokens", "num_classes": VOCAB, "seq_len": 48,
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
    assert epochs[0]["moe_router_tokens_max"] > 0
