"""The looped dense model on the hybrid family's trunk (models/hybrid_moe.py:
a stack walked several times over the same leaves, every pass an exit;
nn/sequence.py: the exits' deferred head and loss) against its plain reference
(benchmark/reference/ouro_2_6b_loop4.py) at the tiny preset on the CPU:
seeded random weights, float32 unless a test says otherwise."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from test_window_moe_training import _batches
from tpuddp import nn
from tpuddp.models import load_model
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context
from tpuddp.parallel import make_mesh

CONFIG_NAME = "ouro_2_6b_loop4"
WORKLOAD = "ouro_loop4_t16k_fused"
VOCAB = 96
PASSES = 4


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_looped_lm")


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def tiny(system, published):
    """The configuration at the tiny preset's sizes, as the reference reads it."""
    return system.shrunk(published)


def _model(system, config, **over):
    return load_model(
        config["model"]["registry_name"], config["vocab_size"],
        **{**system.model_kwargs(config), "compute_dtype": "float32", **over},
    )


def _perturbed(params, scale=0.3):
    """Norm weights off their initial 1, the gate off its initial 0 (so the
    exits' shares differ from token to token), projections large enough that
    attention and the SwiGLU are away from their flat middle: a mistake in
    any of them then shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        scale * jax.random.normal(k, l.shape) if "exit_gate" in jax.tree_util.keystr(path)
        else l + scale * jax.random.normal(k, l.shape) if l.ndim == 1 else l * 8.0
        for (path, l), k in zip(leaves, keys)
    ])


def _tokens(batch=2, t=44, seed=5):
    stream = np.random.RandomState(seed).randint(0, VOCAB, (batch, t + 1))
    return jnp.asarray(stream[:, :-1], jnp.int32), jnp.asarray(stream[:, 1:], jnp.int32)


@pytest.fixture(scope="module")
def seeded(system, tiny):
    model = _model(system, tiny)
    tokens, targets = _tokens()
    return model, _perturbed(model.init(jax.random.key(3), tokens)[0]), tokens, targets


def _loss(model, params, tokens, targets, weights=None):
    out, _ = model.apply(params, (), tokens, Context(train=True))
    return nn.CrossEntropyLoss()(out, targets, weights), out


def _close(ours, theirs, rtol):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours), jax.tree_util.tree_leaves(theirs)):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-12)
        assert err <= rtol, (jax.tree_util.keystr(path), err)


def _value_and_grad(model, tokens, targets):
    """Jitted: ``params -> ((loss, (exit states, counters)), gradients)``."""
    def loss(params):
        value, out = _loss(model, params, tokens, targets)
        return value, (out.hidden, out.counters)
    return jax.jit(jax.value_and_grad(loss, has_aux=True))


@pytest.fixture(scope="module")
def ours(seeded):
    """The program once: loss, exit states, counters and every gradient."""
    model, params, tokens, targets = seeded
    (loss, (hidden, counters)), grads = _value_and_grad(model, tokens, targets)(params)
    return {"loss": loss, "hidden": hidden, "counters": counters, "grads": grads}


@pytest.fixture(scope="module")
def theirs(reference, tiny, seeded):
    """The reference once: exit states, distribution, a token's loss at each
    exit, the objective with the loss it reports, and every gradient."""
    _, params, tokens, targets = seeded

    @jax.jit
    def run(params):
        exits = reference.exit_states(tiny, params, tokens)
        losses = jnp.stack([reference.exit_losses(params, h, targets) for h in exits])
        (_, reported), grads = jax.value_and_grad(
            lambda q: reference.objective(tiny, q, tokens, targets), has_aux=True
        )(params)
        return {"exits": jnp.stack(exits), "p": reference.exit_probabilities(params, exits), "losses": losses,
                "reported": reported, "grads": grads}

    return run(params)


# -- the program against the reference ------------------------------------------------

@pytest.mark.parametrize("exit_", range(PASSES))
def test_each_exits_logits_match_the_reference(tiny, seeded, ours, theirs, exit_):
    params, hidden = seeded[1], ours["hidden"]
    assert hidden.shape == theirs["exits"].shape == (PASSES, 2, 44, tiny["hidden_size"])
    np.testing.assert_allclose(
        hidden[exit_] @ params["head"]["weight"], theirs["exits"][exit_] @ params["head"]["weight"],
        rtol=2e-4, atol=2e-4,
    )
    # every exit's state differs from the one before it: the passes do work
    assert float(jnp.abs(hidden[exit_] - hidden[exit_ - 1]).max()) > 1e-2


@pytest.mark.parametrize("what", ["distribution", "exit_losses", "reported"])
def test_the_exits_loss_matches_the_reference(tiny, seeded, ours, theirs, what):
    params, targets = seeded[1], seeded[3]
    rows = ours["hidden"].reshape(PASSES, -1, tiny["hidden_size"])
    if what == "distribution":
        gate = params["exit_gate"]
        log_p, p = seq.exit_distribution(jnp.sum(rows * gate["weight"][:, 0], -1) + gate["bias"])
        np.testing.assert_allclose(p, theirs["p"], rtol=1e-4, atol=1e-7)
        np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
        assert float(jnp.std(p[0])) > 0.05  # the perturbed gate tells tokens apart
    elif what == "exit_losses":
        losses = seq.token_cross_entropies(
            rows.reshape(-1, tiny["hidden_size"]), params["head"]["weight"], jnp.tile(targets.reshape(-1), PASSES),
            compute_dtype=jnp.float32, chunk=64,
        ).reshape(PASSES, -1)
        np.testing.assert_allclose(losses, theirs["losses"], rtol=2e-4, atol=2e-5)
        sums = [float(ours["counters"][f"loop_exit_loss_{t}"]) for t in range(1, PASSES + 1)]
        np.testing.assert_allclose(sums, jnp.sum(theirs["losses"], axis=1), rtol=1e-4)
    else:
        np.testing.assert_allclose(ours["loss"], theirs["reported"], rtol=1e-5)


@pytest.mark.parametrize("group", ["embed", "layers", "final_norm", "head", "exit_gate"])
def test_gradients_match_the_reference(ours, theirs, group):
    """The program's gradient is the objective's: the entropy term is in it,
    and each of a shared leaf's uses."""
    _close(ours["grads"][group], theirs["grads"][group], 2e-3)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(ours["grads"][group]))


@pytest.mark.parametrize("group", ["embed", "layers", "final_norm", "head", "exit_gate"])
def test_the_references_passes_one_at_a_time_change_no_arithmetic(reference, tiny, seeded, theirs, group):
    """The reference's training steps differentiate the passes one at a time
    (for memory): the same loss and gradients as the whole model in one
    expression."""
    _, params, tokens, targets = seeded
    loss, grads = reference.loss_and_gradients(tiny)(params, tokens, targets)
    np.testing.assert_allclose(loss, theirs["reported"], rtol=1e-6)
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(theirs["grads"])
    _close(grads[group], theirs["grads"][group], 1e-5)


def _cell(config, devices=1):
    return cells.Cell(
        name="t", chips=devices, config_name=CONFIG_NAME, config=config, traffic_name="t",
        traffic={"ddp": {}}, end_to_end=(), per_layer=(), root=cells.ROOT,
    )


def _built(system, config, devices):
    cell = _cell(config, devices)
    mesh = make_mesh(jax.devices()[:devices], {"data": devices})
    model, ddp = system.build_ddp(cell, mesh)
    variables = system.init_variables(model, config, 11)
    init = jax.device_get(variables)
    return ddp, init, system.init_state(model, ddp, config, 11, variables)


@pytest.mark.parametrize("devices", [1, 2])
def test_three_training_steps_through_ddp_match_the_reference(reference, system, tiny, devices):
    """The whole model through ``DistributedDataParallel`` (one device, and
    two with the batch split) against the reference's float32 steps on one
    worker: loss and the norm of each Adam update; the exits' counters come
    out with the step's metrics and their masses add up to the token count."""
    # the cell's 1e-5 is the foot of a warm-up; the test takes a rate at which the loss visibly falls
    config = {**tiny, "compute_dtype": "float32", "optimizer": {**tiny["optimizer"], "lr": 3e-3}}
    batches = _batches(system, config, 3, 4)
    ddp, init, state = _built(system, config, devices)
    ones = system.unit_weights(config, 4)
    losses, norms, prev = [], [], init[0]
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        m, new = jax.device_get((m, state.params))
        losses.append(float(np.sum(m["loss_sum"]) / np.sum(m["n"])))
        norms.append(float(np.sqrt(sum(
            np.sum(np.square(a - b)) for a, b in zip(jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(prev))
        ))))
        prev = new
        assert set(m) == {"loss_sum", "n", *seq.exit_counter_names(PASSES)}
        masses = [np.sum(m[f"loop_exit_mass_{t}"]) for t in range(1, PASSES + 1)]
        np.testing.assert_allclose(sum(masses), batch[0].size, rtol=1e-5)
        weighted = sum(np.sum(m[f"loop_exit_loss_{t}"]) for t in range(1, PASSES + 1))
        assert 0.9 * PASSES * losses[-1] < weighted / batch[0].size < 1.1 * PASSES * losses[-1]
    ref_losses, ref_norms = reference.train_steps(config, *init, batches)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-5)
    np.testing.assert_allclose(norms, ref_norms, rtol=5e-3)
    assert losses[-1] < losses[0]
    # the gate has left its fresh 0.5, 0.25, 0.125, 0.125
    assert abs(masses[0] / batches[0][0].size - 0.5) > 1e-3


def test_the_counters_ride_through_train_step_many_as_through_single_steps(system, tiny):
    config = {**tiny, "compute_dtype": "float32", "optimizer": {**tiny["optimizer"], "lr": 3e-3}}
    batches = _batches(system, config, 3, 4)
    ones = system.unit_weights(config, 4)
    ddp, _, state = _built(system, config, 2)
    singles = []
    for batch in batches:
        state, m = ddp.train_step(state, ddp.shard((*batch, ones)))
        singles.append(jax.device_get(m))
    one_by_one = jax.device_get(state.params)
    ddp, _, state = _built(system, config, 2)
    stacked = tuple(np.stack(a) for a in zip(*[(*batch, ones) for batch in batches]))
    state, many = ddp.train_step_many(state, ddp.shard_stacked(stacked))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state.params)), jax.tree_util.tree_leaves(one_by_one)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    many = jax.device_get(many)
    for name in ("loss_sum", "n", *seq.exit_counter_names(PASSES)):  # summed over the K steps
        np.testing.assert_allclose(np.sum(many[name]), sum(np.sum(m[name]) for m in singles), rtol=1e-5)


# -- the loop ---------------------------------------------------------------------------

def _unrolled_exits(model, passes_params, tokens):
    """The passes as a Python loop, pass ``t`` over its own copy of the
    parameters: what the rolled loop computes when all copies are one."""
    h = jnp.take(passes_params[0]["embed"]["weight"], tokens, axis=0).astype(model.compute_dtype)
    exits = []
    for params in passes_params:
        h, exit_ = model._pass(params, (), h, Context(train=True))
        exits.append(exit_)
    return jnp.stack(exits)


def _exits_loss(model, params, exits, targets):
    out = seq.DeferredExits(
        exits, params["head"]["weight"], params["exit_gate"], entropy_weight=model.exit_entropy_weight,
        compute_dtype=model.compute_dtype, chunk=model.loss_chunk,
    )
    return nn.CrossEntropyLoss()(out, targets)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_rolled_passes_equal_unrolled_ones(system, tiny, seeded, compute_dtype):
    _, params, tokens, targets = seeded
    model = _model(system, tiny, compute_dtype=compute_dtype)
    (rolled, _), rolled_grads = _value_and_grad(model, tokens, targets)(params)
    unrolled, unrolled_grads = jax.jit(jax.value_and_grad(
        lambda p: _exits_loss(model, p, _unrolled_exits(model, [p] * PASSES, tokens), targets)
    ))(params)
    tol = 1e-5 if compute_dtype == "float32" else 2e-2
    np.testing.assert_allclose(rolled, unrolled, rtol=tol)
    _close(rolled_grads, unrolled_grads, tol)


@pytest.mark.parametrize("group", ["layers", "final_norm"])
def test_a_shared_leafs_gradient_is_the_sum_over_its_four_uses(seeded, ours, group):
    """Each pass over a copy of its own: the gradient of the one shared leaf
    is the sum of the four copies' gradients, and every copy's is its own."""
    model, params, tokens, targets = seeded

    def unshared(copies):
        passes = [{**params, group: copy} for copy in copies]
        return _exits_loss(model, params, _unrolled_exits(model, passes, tokens), targets)

    each = jax.jit(jax.grad(unshared))([params[group]] * PASSES)
    _close(jax.tree_util.tree_map(lambda *g: sum(g), *each), ours["grads"][group], 1e-5)
    norms = [sum(float(jnp.sum(jnp.square(l))) for l in jax.tree_util.tree_leaves(g)) for g in each]
    assert len({round(n, 9) for n in norms}) == PASSES and min(norms) > 0


@pytest.mark.parametrize("passes", [2, 4, 8])
def test_the_step_program_does_not_grow_with_the_passes(system, tiny, passes):
    """The lowered gradient of a model of ``passes`` passes is as long as the
    two-pass model's, to the counters' few lines: one loop, not copies."""
    tokens, targets = _tokens()

    def lowered(n):
        model = _model(system, tiny, loop_steps=n)
        params = model.init(jax.random.key(0), tokens)[0]
        return jax.jit(jax.grad(lambda p: _loss(model, p, tokens, targets)[0])).lower(params).as_text()

    assert len(lowered(passes)) < 1.05 * len(lowered(2))


@pytest.mark.parametrize("name", ["qwen3_next_tiny", "mellum2_tiny", "lfm2_tiny"])
def test_one_pass_without_a_gate_is_the_parents_walk_to_the_bit(name):
    """The trunk's forward as it stood before a stack could be looped (one
    walk, one final norm, ``DeferredLogits``), written out here: the three
    older presets lower to the same text and give ``DeferredLogits``' loss and
    every gradient to the bit."""
    model = load_model(name, VOCAB)
    tokens, targets = _tokens(t=48)
    params, state = model.init(jax.random.key(1), tokens)
    assert model.loop_steps == 1 and "exit_gate" not in params

    def parents_apply(params, state, x, ctx):
        h = seq.round_to(jnp.take(params["embed"]["weight"], x.astype(jnp.int32), axis=0), model.compute_dtype)
        aux_total = jnp.zeros((), jnp.float32)
        totals = {name: jnp.zeros((), jnp.float32) for name in moe_lib.COUNTERS}
        new_state = list(state)
        for i, p in enumerate(params["layers"]):
            kind = model.layer_kind(i)
            bias = state[i]["expert_bias"] if state and state[i] else None
            with jax.named_scope(f"{i}_{kind}"):
                h, aux, counters, router_counts = model._layer(kind, p, bias, h, ctx.train)
                if bias is not None and ctx.train:
                    with jax.named_scope("moe"), jax.named_scope("router"):
                        new_state[i] = {"expert_bias": moe_lib.balanced_bias(
                            bias, router_counts, model.bias_update_rate, ctx.axis_name
                        )}
            if counters is not None:
                aux_total = aux_total + aux
                totals = {name: totals[name] + counters[name] for name in totals}
        h = model._norm(h, params["final_norm"])
        head = params["head"]["weight"] if "head" in params else params["embed"]["weight"].T
        return seq.DeferredLogits(
            h, head, model.aux_loss_weight * aux_total, totals,
            compute_dtype=model.compute_dtype, chunk=model.loss_chunk,
        ), tuple(new_state)

    def loss_of(apply):
        def loss(params):
            out, new_state = apply(params, state, tokens, Context(train=True))
            assert isinstance(out, nn.DeferredLogits)
            return nn.CrossEntropyLoss()(out, targets), (new_state, out.counters)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))

    ours, theirs = loss_of(model.apply), loss_of(parents_apply)
    assert ours.lower(params).as_text() == theirs.lower(params).as_text()
    for a, b in zip(jax.tree_util.tree_leaves(ours(params)), jax.tree_util.tree_leaves(theirs(params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_loop_without_a_gate_takes_its_loss_from_the_last_pass(system, tiny, seeded):
    _, params, tokens, targets = seeded
    model = _model(system, tiny, exit_gate=False)
    params = {k: v for k, v in params.items() if k != "exit_gate"}  # the tree says whether the passes are exits

    @jax.jit
    def run(params):
        loss, out = _loss(model, params, tokens, targets)
        assert isinstance(out, nn.DeferredLogits) and set(out.counters) == set(moe_lib.COUNTERS)
        return loss, out.hidden, _unrolled_exits(model, [params] * PASSES, tokens)[-1]

    loss, hidden, last = run(params)
    np.testing.assert_allclose(hidden, last, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(loss, nn.CrossEntropyLoss()(last @ params["head"]["weight"], targets), rtol=1e-5)


# -- the exits ----------------------------------------------------------------------------

@pytest.mark.parametrize("passes", [2, 4])
@pytest.mark.parametrize("scale", [0.0, 1.0, 30.0])
def test_the_exit_distribution_sums_to_one_and_the_last_exit_takes_the_rest(passes, scale):
    logits = scale * jax.random.normal(jax.random.key(passes), (passes, 50))
    log_p, p = seq.exit_distribution(logits)
    lam = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1.0 - lam[:-1], axis=0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    # the last gate is not read: what it says changes nothing
    np.testing.assert_array_equal(seq.exit_distribution(logits.at[-1].set(7.0))[1], p)
    assert bool(jnp.all(jnp.isfinite(log_p))) and bool(jnp.all(jnp.isfinite(p * log_p)))
    if scale == 0.0:  # a fresh gate halves what is left at every exit
        fresh = [0.5 ** t for t in range(1, passes)]
        np.testing.assert_allclose(p[:, 0], fresh + [fresh[-1]], rtol=1e-6)


@pytest.mark.parametrize("masked", [False, True])
def test_the_counters_add_up_to_the_token_count(seeded, masked):
    model, params, tokens, targets = seeded
    weights = jnp.asarray([1.0, 0.0]) if masked else None  # per-sequence weights: the loaders' padding mask

    @jax.jit
    def run(params, tokens, targets, weights):
        loss, out = _loss(model, params, tokens, targets, weights)
        return loss, out.counters

    loss, counters = run(params, tokens, targets, weights)
    counted = tokens.size // 2 if masked else tokens.size
    assert set(counters) == set(seq.exit_counter_names(PASSES)) == set(model.counter_names)
    masses = np.asarray([counters[f"loop_exit_mass_{t}"] for t in range(1, PASSES + 1)])
    np.testing.assert_allclose(masses.sum(), counted, rtol=1e-6)
    assert masses.min() > 0
    if masked:
        np.testing.assert_allclose(loss, run(params, tokens[:1], targets[:1], None)[0], rtol=1e-6)


def test_one_exit_is_the_deferred_logits_loss(seeded, ours):
    """A single exit takes all of every token: the exits' loss is then the
    plain deferred head's, and the gate has no gradient."""
    params, targets = seeded[1], seeded[3]
    last, head = ours["hidden"][-1], params["head"]["weight"]
    one = jax.jit(jax.value_and_grad(lambda gate: nn.CrossEntropyLoss()(seq.DeferredExits(
        last[None], head, gate, entropy_weight=0.05, compute_dtype=jnp.float32, chunk=64,
    ), targets)))
    loss, gate_grads = one(params["exit_gate"])
    plain = seq.DeferredLogits(last, head, compute_dtype=jnp.float32, chunk=64)
    np.testing.assert_allclose(loss, nn.CrossEntropyLoss()(plain, targets), rtol=1e-6)
    assert all(float(jnp.abs(g).max()) == 0 for g in jax.tree_util.tree_leaves(gate_grads))


def test_the_entropy_term_is_in_the_gradient_and_not_in_the_loss(system, tiny, seeded, ours):
    _, params, tokens, targets = seeded
    without = _model(system, tiny, exit_entropy_weight=0.0)
    a, grad_a = ours["loss"], ours["grads"]  # the preset's 0.05
    (b, _), grad_b = _value_and_grad(without, tokens, targets)(params)
    assert float(a) == float(b)
    moved = lambda g: float(jnp.linalg.norm(g["exit_gate"]["weight"]))
    assert abs(moved(grad_a) - moved(grad_b)) > 1e-3 * moved(grad_b)


def test_evaluation_returns_the_last_passs_logits(seeded, theirs):
    model, params, tokens, _ = seeded
    logits, state = jax.jit(lambda p: model.apply(p, (), tokens, Context(train=False)))(params)
    assert logits.shape == (2, 44, VOCAB) and state == ()
    np.testing.assert_allclose(logits, theirs["exits"][-1] @ params["head"]["weight"], rtol=2e-4, atol=2e-4)


# -- the registry ---------------------------------------------------------------------------

def test_registry_builds_the_published_cut_and_the_tiny_preset(system, published):
    """``ouro_2_6b_l6`` is the configuration's file to the number: 509,661,185
    parameters, of which a layer 51,388,416 with its four norms and no
    per-head norm, the embedding and the head 100,663,296 each, the gate
    2,049."""
    model = load_model("ouro_2_6b_l6", published["vocab_size"])
    for key, value in system.model_kwargs(published).items():
        if key not in ("compute_dtype", "partial_rotary_factor", "zero_centred_norms"):
            assert getattr(model, key) == value, key
    assert model.rotary_dim == model.head_dim == 128 and not model.zero_centred_norms
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))[0], jax.random.key(0))
    count = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == published["parameters"] == 509_661_185
    assert count(shapes["layers"][0]) == 4 * 2048**2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert count(shapes["embed"]) == count(shapes["head"]) == 100_663_296 and count(shapes["exit_gate"]) == 2049
    assert set(shapes["layers"][0]) == {"input_norm", "mixer_out_norm", "post_norm", "ff_out_norm", "mixer", "mlp"}
    assert set(shapes["layers"][0]["mixer"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert (model.loop_steps, model.n_layers, model.counter_names) == (4, 6, seq.exit_counter_names(4))
    tiny_model = load_model("ouro_tiny", VOCAB)
    assert tiny_model.hidden_size <= 64 and (tiny_model.loop_steps, tiny_model.n_layers) == (4, 3)


@pytest.mark.parametrize("bad", [
    dict(loop_steps=1), dict(loop_steps=0, exit_gate=False), dict(dense_layers=2), dict(expert_bias=True),
])
def test_the_constructor_refuses_what_a_looped_stack_cannot_be(bad):
    with pytest.raises(ValueError):
        load_model("ouro_tiny", VOCAB, **bad)


def test_train_native_trains_the_tiny_preset_on_the_token_stream(tmp_path):
    """``train_native.py``'s worker: the registry's tiny preset on
    ``markov_tokens`` through the loader, ``DistributedDataParallel`` and the
    epoch driver on the 8-device CPU world; the exits' counters reach the
    epoch's row and their masses add up to the epoch's tokens."""
    from functools import partial

    from train_native import basic_ddp_training_loop
    from tpuddp.parallel import backend
    from tpuddp.parallel.spawn import run_ddp_training

    training = {
        "model": "ouro_tiny", "dataset": "markov_tokens", "num_classes": VOCAB, "seq_len": 48,
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
    masses = [epochs[0][f"loop_exit_mass_{t}"] for t in range(1, PASSES + 1)]
    np.testing.assert_allclose(sum(masses), 256 * 48, rtol=1e-5)
    assert all(epochs[0][f"loop_exit_loss_{t}"] > 0 for t in range(1, PASSES + 1))
