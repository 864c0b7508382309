"""The hybrid mixture-of-experts family trained: whole steps of the tiny
preset through ``DistributedDataParallel`` against the plain reference's
(benchmark/reference/qwen3_next_80b_a3b_ep16.py), the control of ``correct``,
the seeded token stream and ``train_native.py``'s worker on it. The layers'
own tests are in tests/test_hybrid_moe.py."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import cells
from tpuddp.nn import moe as moe_lib
from tpuddp.parallel import make_mesh

CONFIG_NAME = "qwen3_next_80b_a3b_ep16"
WORKLOAD = "qwen3next_ep16_t8k_fused"
VOCAB = 96


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_moe_lm")


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def tiny(system, published):
    """The configuration at the tiny preset's sizes, as the reference reads it."""
    return system.shrunk(published)


# -- training -------------------------------------------------------------------

def _ddp_steps(system, config, batches, devices=1, **over):
    cell = cells.Cell(
        name="t", chips=devices, config_name=CONFIG_NAME, config={**config, **over}, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )
    mesh = make_mesh(jax.devices()[:devices], {"data": devices})
    model, ddp = system.build_ddp(cell, mesh)
    variables = system.init_variables(model, cell.config, 11)
    init = jax.device_get(variables)
    state = system.init_state(model, ddp, cell.config, 11, variables)
    ones = system.unit_weights(cell.config, batches[0][0].shape[0])
    losses, norms, metrics, prev = [], [], [], init[0]
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        norms.append(float(np.sqrt(sum(
            np.sum(np.square(a - b)) for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(prev))
        ))))
        metrics.append(m)
        prev = new
    return init, losses, norms, metrics


def _batches(system, config, n, batch, seed=3):
    tokens, targets = system.make_batches(config, seed, n, batch)
    return [(np.asarray(tokens[i]), np.asarray(targets[i])) for i in range(n)]


@pytest.mark.parametrize("devices", [1, 2])
def test_three_training_steps_through_ddp_match_the_reference(reference, system, tiny, devices):
    """The whole model through ``DistributedDataParallel`` (one device, and
    two with the batch split) against the reference's float32 steps on one
    worker: loss and the norm of each Adam update; the counters come out with
    the step's metrics and nothing is dropped. Each replica takes the
    load-balancing loss over its own tokens, as every data-parallel trainer
    does, and a product of two means is not linear in the tokens: the
    two-replica case is compared with that term off, the one-replica case
    with it on."""
    # the cell's 1e-5 is the foot of a warm-up; three steps of it move a
    # 64-wide model's loss by less than its noise, so the test takes a rate
    # at which the loss visibly falls
    config = {**tiny, "compute_dtype": "float32", "optimizer": {**tiny["optimizer"], "lr": 3e-3}}
    if devices > 1:
        config["aux_loss_weight"] = 0.0
    batches = _batches(system, config, 3, 4)
    init, losses, norms, metrics = _ddp_steps(system, config, batches, devices)
    ref_losses, ref_norms = reference.train_steps(config, *init, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(norms, ref_norms, rtol=5e-3)
    assert losses[-1] < losses[0]
    tokens = batches[0][0].size
    for m in metrics:
        assert set(m) == {"loss_sum", "n", *moe_lib.COUNTERS}
        assert np.sum(m["n"]) == tokens
        held, absent = np.sum(m["moe_expert_tokens_held"]), np.sum(m["moe_absent_assignments"])
        assert held + absent == tokens * config["num_experts_per_tok"] * config["num_hidden_layers"]
        assert np.sum(m["moe_dropped_assignments"]) == 0


def test_the_next_precision_down_fails_the_configurations_limits(reference, system, tiny, published):
    """The control of ``correct``. Against the float32 reference the program
    with bfloat16 products passes the limits the configuration's file sets.
    With its products' inputs rounded one precision down it does not:
    ``float8_e4m3fn`` (whose range flushes the 0.02-wide weights' small
    products) fails the cell's own update-norm limit by far; ``float8_e5m2``
    (range kept, two bits of mantissa) is 12 times bfloat16's loss error and
    5 times its update-norm error at this size, and fails limits that stand
    over this size's bfloat16 errors as the cell's stand over the chip's
    (3 times; PERF.md section 6 has the chip's readings)."""
    limits = published["check"]
    batches = _batches(system, tiny, 3, 4)
    worst = lambda ours, theirs: max(abs(a - b) / abs(b) for a, b in zip(ours, theirs))

    def errors(dtype):
        _, losses, norms, _ = _ddp_steps(system, tiny, batches, compute_dtype=dtype)
        return worst(losses, ref_losses), worst(norms, ref_norms)

    init, _, _, _ = _ddp_steps(system, tiny, batches[:1], compute_dtype="bfloat16")
    ref_losses, ref_norms = reference.train_steps(tiny, *init, batches)
    loss_err, norm_err = errors("bfloat16")
    assert loss_err <= limits["loss_rtol"] and norm_err <= limits["update_norm_rtol"]
    e4m3 = errors("float8_e4m3fn")
    assert e4m3[0] > limits["loss_rtol"] or e4m3[1] > 3 * limits["update_norm_rtol"]
    e5m2 = errors("float8_e5m2")
    assert e5m2[0] > 3 * loss_err and e5m2[1] > 3 * norm_err


def test_the_token_stream_is_seeded_and_learnable(system, tiny):
    a = system.make_batches(tiny, 2600000501, 2, 3)
    b = system.make_batches(tiny, 2600000501, 2, 3)
    c = system.make_batches(tiny, 2147483649, 2, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b)) and not np.array_equal(a[0], c[0])
    tokens, targets = (np.asarray(x) for x in a)
    assert tokens.shape == (2, 3, tiny["tokens"]["seq_len"]) and tokens.dtype == np.int32
    assert np.array_equal(tokens[..., 1:], targets[..., :-1])  # the target is the next token
    assert 0 <= tokens.min() and tokens.max() < tiny["vocab_size"]
    big = np.asarray(system.make_batches({**tiny, "tokens": {"seq_len": 2000}}, 5, 1, 4)[0]).reshape(-1)
    followers = {}
    for cur, nxt in zip(big[:-1], big[1:]):
        followers.setdefault(int(cur), set()).add(int(nxt))
    assert max(len(f) for f in followers.values()) <= 5  # 4 successors (+1 across a sequence's end)
    assert np.mean(big < 10) > 0.3  # Zipf: the ten most frequent ids are a third of the stream


def test_train_native_trains_the_tiny_preset_on_the_token_stream(tmp_path, capsys):
    """``train_native.py``'s worker: the registry's tiny preset on
    ``markov_tokens`` through the loader, ``DistributedDataParallel`` and the
    epoch driver on the 8-device CPU world; the expert counters reach the
    epoch's row."""
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel import backend
    from tpuddp.parallel.spawn import run_ddp_training

    training = {
        "model": "qwen3_next_tiny", "dataset": "markov_tokens", "num_classes": VOCAB, "seq_len": 24,
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
