"""Latent attention and the second prediction head in one model
(models/hybrid_moe.py's ``LatentAttention`` layers and the module after the
stack; nn/sequence.py's second deferred head; nn/moe.py's scaled routes and
ungated shared expert) against attention written down a head at a time and
against the plain reference (benchmark/reference/glm_4_7_flash_ep8.py) at the
tiny preset on the CPU: seeded random weights, float32 unless a test says
otherwise. Whole training steps are in tests/test_latent_moe_training.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from test_window_moe import _close, _hidden, _model, _perturbed  # the hybrid family's tests share them
from tpuddp import nn
from tpuddp.models import load_model
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context

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
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def tiny(system, published):
    """The configuration at the tiny preset's sizes, as the reference reads it."""
    return system.shrunk(published)


def _variables(model, perturb=True):
    params, state = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return (_perturbed(params), _perturbed(state)) if perturb else (params, state)


# -- the mixer against attention a head at a time -----------------------------------------

def _rotate_half(x, theta):
    """``x (T, d)`` rotated by position: dimension ``m`` pairs with ``m + d / 2``."""
    t, d = x.shape
    angles = np.arange(t)[:, None] * theta ** (-2.0 * np.arange(d // 2) / d)[None, :]
    a, b = x[:, :d // 2], x[:, d // 2:]
    return np.concatenate([a * np.cos(angles) - b * np.sin(angles), b * np.cos(angles) + a * np.sin(angles)], axis=-1)


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _a_head_at_a_time(model, p, x):
    """The equations of the mixer for one sequence ``x (T, E)`` in float64
    numpy: a head's queries and keys built from their two parts, the rotary
    key computed once and handed to every head, a ``T x T`` score block a
    head, the heads' values side by side through ``W_o``."""
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    x = np.asarray(x, np.float64)
    h, rank, dn, dr, dv = model.n_heads, model.kv_lora_rank, model.qk_nope_dim, model.qk_rope_dim, model.v_head_dim
    t = x.shape[0]
    c_q = _rms(x @ p["q_a_proj"], p["q_a_norm"], model.rms_eps)
    q = (c_q @ p["q_b_proj"]).reshape(t, h, dn + dr)
    latent = x @ p["kv_a_proj"]
    kv = (_rms(latent[:, :rank], p["kv_a_norm"], model.rms_eps) @ p["kv_b_proj"]).reshape(t, h, dn + dv)
    shared_key = _rotate_half(latent[:, rank:], model.rope_theta)
    seen = np.tril(np.ones((t, t), bool))
    heads = []
    for i in range(h):
        q_i = np.concatenate([q[:, i, :dn], _rotate_half(q[:, i, dn:], model.rope_theta)], axis=-1)
        k_i = np.concatenate([kv[:, i, :dn], shared_key], axis=-1)
        scores = np.where(seen, q_i @ k_i.T * (dn + dr) ** -0.5, -np.inf)
        probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
        heads.append(probs / probs.sum(axis=-1, keepdims=True) @ kv[:, i, dn:])
    return np.concatenate(heads, axis=-1) @ p["o_proj"]


def test_the_latent_mixer_is_its_equations_a_head_at_a_time(system, tiny):
    """The blockwise lowering (a sequence of 50 in query blocks of 16 and
    what is left) against the equations written down in float64."""
    model = _model(system, tiny)
    assert set(model.layer_types) == {"LatentAttention"} and model.qk_nope_dim + model.qk_rope_dim == model.v_head_dim
    p = _variables(model)[0]["layers"][1]["mixer"]
    assert set(p) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    assert p["kv_a_proj"].shape == (64, 16 + 4) and p["kv_b_proj"].shape == (16, 4 * (12 + 16))
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours = model._latent_attention(p, x)
    for b in range(2):
        np.testing.assert_allclose(ours[b], _a_head_at_a_time(model, p, x[b]), rtol=2e-4, atol=2e-5)


def test_the_latent_mixer_matches_the_reference(reference, system, tiny):
    model = _model(system, tiny)
    p = _variables(model)[0]["layers"][2]["mixer"]
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours, theirs = model._latent_attention, lambda p, x: reference.latent_mixer(tiny, p, x)
    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    _close(jax.grad(loss(ours), argnums=(0, 1))(p, x), jax.grad(loss(theirs), argnums=(0, 1))(p, x), 5e-4)


@pytest.fixture(scope="module")
def wide_heads():
    """Two heads of 128 = 96 + 32, the published 3:1 of 192 + 64, over 512
    tokens: the smallest shapes the fused kernel's rule takes."""
    model = load_model(
        "glm_4_7_flash_tiny", VOCAB, n_heads=2, n_kv_heads=2, qk_nope_dim=96, qk_rope_dim=32, v_head_dim=128,
        q_lora_rank=48, kv_lora_rank=32, compute_dtype="float32",
    )
    p = _variables(model)[0]["layers"][1]["mixer"]
    x = jnp.asarray(np.random.RandomState(5).randn(1, 512, 64), jnp.float32)
    return model, p, x


def test_the_latent_mixer_in_the_fused_lowering_under_the_interpreter(wide_heads, monkeypatch):
    """The library's kernel under Pallas's interpreter at a head of 128 (the
    kernel's lane rule is met; scores and values one width) against the
    equations a head at a time, output and gradients; the blockwise lowering
    at the same shapes agrees with both."""
    model, p, x = wide_heads
    assert seq.fused_attention_blocks(512, 128, None, hq=2, hkv=2) is not None
    blockwise = model._latent_attention(p, x)
    fused_calls = []

    def fused(q, k, v, *, scale, compute_dtype, q_block=None, window=None):
        fused_calls.append(q.shape)
        return seq._fused_causal_attention(q, k, v, scale=scale, compute_dtype=compute_dtype, window=window, interpret=True)

    monkeypatch.setattr(seq, "causal_attention", fused)
    fused_out = model._latent_attention(p, x)
    assert fused_calls == [(1, 512, 2, 128)]
    want = _a_head_at_a_time(model, p, x[0])
    np.testing.assert_allclose(fused_out[0], want, rtol=2e-4, atol=2e-5 * np.abs(want).max())
    np.testing.assert_allclose(blockwise[0], want, rtol=2e-4, atol=2e-5 * np.abs(want).max())
    loss = lambda p, x: jnp.sum(jnp.sin(model._latent_attention(p, x)))
    fused_grads = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.undo()
    _close(fused_grads, jax.grad(loss, argnums=(0, 1))(p, x), 5e-4)


def test_one_rotary_key_serves_every_head(system, tiny):
    """``W_kva``'s last ``qk_rope_dim`` columns make the one rotary key:
    moving them moves every head's output, and moves nothing before the
    position it is moved at; the latent's columns beside them make keys and
    values head by head."""
    model = _model(system, tiny)
    p = _variables(model)[0]["layers"][1]["mixer"]
    x = _hidden(np.random.RandomState(3), tiny, 1, 40)
    h, dv, rank = model.n_heads, model.v_head_dim, model.kv_lora_rank

    def head(i, p):  # head i's output alone: an output projection that picks its dv columns out
        pick = jnp.zeros_like(p["o_proj"]).at[i * dv:(i + 1) * dv, :dv].set(jnp.eye(dv))
        return model._latent_attention({**p, "o_proj": pick}, x)[0, :, :dv]

    moved = {**p, "kv_a_proj": p["kv_a_proj"].at[:, rank:].multiply(-1.5)}
    for i in range(h):
        assert float(jnp.max(jnp.abs(head(i, moved) - head(i, p)))) > 1e-3, i
    # the rotary key of position 0 is not rotated and a first query sees it alone: softmax over one key is 1
    np.testing.assert_allclose(head(0, moved)[0], head(0, p)[0], rtol=1e-5, atol=1e-6)
    # a query's rotary slice, by contrast, is a head's own
    cols = (model.qk_nope_dim + model.qk_rope_dim)
    one_head = {**p, "q_b_proj": p["q_b_proj"].at[:, model.qk_nope_dim:cols].multiply(-1.5)}  # head 0's rotary slice
    assert float(jnp.max(jnp.abs(head(0, one_head) - head(0, p)))) > 1e-3
    np.testing.assert_array_equal(head(1, one_head), head(1, p))


# -- the router's scale and the ungated shared expert (nn/moe.py) ------------------------

def _router_case():
    keys = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(keys[0], (64, 16), jnp.float32)
    router = 0.5 * jax.random.normal(keys[1], (16, 8), jnp.float32)
    return x, router, jax.random.normal(keys[2], (8,), jnp.float32)


@pytest.mark.parametrize("biased", [True, False])
def test_route_scales_the_renormalised_weights_and_nothing_else(biased):
    x, router, bias = _router_case()
    bias = bias if biased else None
    plain_w, plain_e, plain_s = moe_lib.route(x, router, top_k=3, bias=bias)
    w, e, s = moe_lib.route(x, router, top_k=3, bias=bias, scale=1.8)
    np.testing.assert_array_equal(e, plain_e)
    np.testing.assert_array_equal(s, plain_s)
    np.testing.assert_allclose(w, 1.8 * plain_w, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.8, rtol=1e-5)
    # the default is today's program: no multiplication is traced
    text = lambda **kw: str(jax.make_jaxpr(lambda x: moe_lib.route(x, router, top_k=3, bias=bias, **kw)[0])(x))
    assert text() == text(scale=1.0) != text(scale=1.8)


def _moe_params(gated: bool):
    keys = jax.random.split(jax.random.key(2), 6)
    e, f, n, held = 16, 8, 8, 4
    draw = lambda k, *shape: 0.3 * jax.random.normal(k, shape, jnp.float32)
    return {
        "router": draw(keys[0], e, n),
        "experts": {"gate_up": draw(keys[1], held, e, 2 * f), "down": draw(keys[2], held, f, e)},
        "shared": {"gate_up": draw(keys[3], e, 2 * f), "down": draw(keys[4], f, e)},
        **({"shared_gate": draw(keys[5], e, 1)} if gated else {}),
    }


def test_the_shared_expert_is_gated_where_the_tree_has_a_gate_and_added_whole_where_not():
    x = _router_case()[0]
    layer = lambda p: moe_lib.expert_share_moe(p, x, top_k=2, first_expert=0, compute_dtype=jnp.float32)[0]
    gated, ungated = _moe_params(True), _moe_params(False)
    routed = layer({k: v for k, v in ungated.items() if k != "shared"})
    shared = seq.swiglu(x, ungated["shared"]["gate_up"], ungated["shared"]["down"], jnp.float32)
    np.testing.assert_allclose(layer(ungated), routed + shared, rtol=1e-5, atol=1e-6)
    gate = jax.nn.sigmoid(x @ gated["shared_gate"])
    np.testing.assert_allclose(layer(gated), routed + gate * shared, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(layer(gated) - layer(ungated)))) > 1e-3
    # the scale is on the routed weights alone
    scaled = moe_lib.expert_share_moe(ungated, x, top_k=2, first_expert=0, compute_dtype=jnp.float32, scale=1.8)[0]
    np.testing.assert_allclose(scaled, 1.8 * routed + shared, rtol=1e-5, atol=1e-6)


def test_the_sparse_layer_matches_the_reference(reference, system, tiny):
    """Sigmoid scores, the bias in the choice alone, weights renormalised and
    scaled by 1.8, the held experts' part and the shared expert ungated."""
    model = _model(system, tiny)
    params, state = _variables(model)
    p, bias = params["layers"][1]["moe"], state[1]["expert_bias"]
    assert set(p) == {"router", "experts", "shared"} and model.routed_scale == 1.8
    x = _hidden(np.random.RandomState(4), tiny, 2, 33)
    flat = x.reshape(-1, x.shape[-1])

    def ours(p, flat):
        return moe_lib.expert_share_moe(
            p, flat, top_k=model.top_k, first_expert=0, compute_dtype=jnp.float32, bias=bias, scale=model.routed_scale
        )[0]

    theirs = lambda p, flat: reference.moe(tiny, p, bias, flat[None])[0][0]
    np.testing.assert_allclose(ours(p, flat), theirs(p, flat), rtol=2e-4, atol=2e-5)
    loss = lambda f: lambda p, flat: jnp.sum(jnp.sin(f(p, flat)))
    _close(jax.grad(loss(ours), argnums=(0, 1))(p, flat), jax.grad(loss(theirs), argnums=(0, 1))(p, flat), 5e-4)


def test_the_shares_add_up_to_the_uncut_layer(reference, system, tiny):
    """Four shares of 2 of the 8 experts each, all under one router and one
    bias: their routed parts, with the shared expert, which every chip
    computes alike, counted once, are the uncut reference's layer output, and
    every assignment is some share's."""
    n_all, held = tiny["deployment"]["experts_published"], tiny["n_routed_experts"]
    uncut = {**tiny, "n_routed_experts": n_all}
    model = _model(system, uncut)
    params, state = _variables(model)
    p, bias = params["layers"][1]["moe"], state[1]["expert_bias"]
    x = _hidden(np.random.RandomState(4), tiny, 2, 33)
    flat = x.reshape(-1, x.shape[-1])
    whole, whole_counts = reference.moe(uncut, p, bias, x)
    shared = reference.shared_part(p, flat)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2
    total, seen = 0.0, 0.0
    for share in range(n_all // held):
        mine = {**p, "experts": jax.tree_util.tree_map(lambda w: w[share * held:(share + 1) * held], p["experts"])}
        y, _, counters, router_counts = moe_lib.expert_share_moe(
            mine, flat, top_k=model.top_k, first_expert=share * held, compute_dtype=jnp.float32, bias=bias,
            scale=model.routed_scale,
        )
        theirs = {**tiny, "deployment": {**tiny["deployment"], "first_expert": share * held}}
        np.testing.assert_allclose(y, reference.moe(theirs, mine, bias, x)[0].reshape(flat.shape), rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(router_counts, whole_counts)  # every share routes over all experts alike
        total = total + (y - shared)  # a share's routed part
        seen += float(counters["moe_expert_tokens_held"])
    np.testing.assert_allclose(total + shared, whole.reshape(flat.shape), rtol=2e-4, atol=2e-5)
    assert seen == flat.shape[0] * model.top_k


# -- the second head (nn/sequence.py, models/hybrid_moe.py) -----------------------------

def test_the_second_heads_targets_are_the_labels_one_on():
    labels = jnp.arange(10).reshape(2, 5)
    weights = jnp.asarray([[1, 1, 0, 1, 1], [1, 0, 1, 1, 1]], jnp.float32)
    after, theirs = seq.targets_after_next(labels, weights)
    np.testing.assert_array_equal(after[:, :-1], labels[:, 1:])
    np.testing.assert_array_equal(theirs, [[1, 0, 1, 1, 0], [0, 1, 1, 1, 0]])


def test_the_deferred_second_head_is_its_loss_written_down():
    """``L_main`` is what comes out; ``L_main + lambda L_mtp`` is what is
    differentiated; the counters carry ``L_mtp``'s sum and its tokens."""
    keys = jax.random.split(jax.random.key(0), 5)
    hidden, after = (jax.random.normal(k, (2, 7, 8), jnp.float32) for k in keys[:2])
    head = jax.random.normal(keys[2], (8, 11), jnp.float32)
    labels = jax.random.randint(keys[3], (2, 7), 0, 11)
    weights = (jax.random.uniform(keys[4], (2, 7)) > 0.3).astype(jnp.float32)

    def written_down(hidden, after, head):
        ce = lambda h, y: -jnp.take_along_axis(jax.nn.log_softmax(h @ head, axis=-1), y[..., None], axis=-1)[..., 0]
        main = jnp.sum(weights * ce(hidden, labels)) / jnp.sum(weights)
        w2 = weights[:, 1:]
        mtp = jnp.sum(w2 * ce(after[:, :-1], labels[:, 1:])) / jnp.sum(w2)
        return main, mtp, jnp.sum(w2)

    def ours(hidden, after, head):
        out = seq.DeferredLogits(hidden, head, None, {}, after, compute_dtype=jnp.float32, chunk=4, next_weight=0.3)
        return nn.CrossEntropyLoss()(out, labels, weights), out.counters

    (loss, counters), grads = jax.value_and_grad(ours, argnums=(0, 1, 2), has_aux=True)(hidden, after, head)
    main, mtp, count = written_down(hidden, after, head)
    np.testing.assert_allclose(loss, main, rtol=1e-5)
    np.testing.assert_allclose(counters["mtp_loss_sum"], mtp * count, rtol=1e-5)
    assert float(counters["mtp_tokens"]) == float(count)
    _close(grads, jax.grad(lambda *a: written_down(*a)[0] + 0.3 * written_down(*a)[1], argnums=(0, 1, 2))(hidden, after, head), 1e-4)
    # without second states it is the head it was: no counters of its own
    plain = seq.DeferredLogits(hidden, head, None, {}, compute_dtype=jnp.float32, chunk=4)
    np.testing.assert_allclose(nn.CrossEntropyLoss()(plain, labels, weights), main, rtol=1e-5)
    assert plain.counters == {} and jax.tree_util.tree_structure(plain).num_leaves == 2
    # a pytree: through jit, the weight among the static parts
    again = jax.jit(lambda out: out)(seq.DeferredLogits(hidden, head, None, {}, after, compute_dtype=jnp.float32, next_weight=0.3))
    assert again.next_weight == 0.3 and again.next_hidden.shape == after.shape


def test_the_model_matches_the_reference_on_both_heads(reference, system, tiny):
    """The whole forward at the tiny preset: the reported loss is the
    reference's ``L_main``, the counters' loss its ``L_mtp``, the gradient
    that of ``L_main + 0.3 L_mtp``, and the biases, the module's among them,
    move by the reference's rule."""
    model = _model(system, tiny)
    params, state = _variables(model)
    assert len(state) == tiny["num_hidden_layers"] + 1 and state[0] == ()
    rng = np.random.RandomState(6)
    tokens, targets = (jnp.asarray(rng.randint(0, VOCAB, (2, 44)), jnp.int32) for _ in range(2))

    def ours(params):
        out, new_state = model.apply(params, state, tokens, Context(train=True))
        return nn.CrossEntropyLoss()(out, targets, jnp.ones(targets.shape, jnp.float32)), (out.counters, new_state)

    (loss, (counters, new_state)), grads = jax.value_and_grad(ours, has_aux=True)(params)
    (_, (main, mtp, counts)), ref_grads = jax.value_and_grad(
        lambda p: reference.losses_and_counts(tiny, p, state, tokens, targets), has_aux=True
    )(params)
    np.testing.assert_allclose(loss, main, rtol=2e-5)
    np.testing.assert_allclose(counters["mtp_loss_sum"] / counters["mtp_tokens"], mtp, rtol=2e-5)
    assert float(counters["mtp_tokens"]) == 2 * 43
    _close(grads, ref_grads, 2e-3)
    for new, old, c in zip(new_state[1:], state[1:], counts[1:]):
        np.testing.assert_allclose(new["expert_bias"], reference.balanced(tiny, old["expert_bias"], c), rtol=0, atol=1e-7)
    sparse = len(state) - 1
    assert float(counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"]) == 2 * 44 * model.top_k * sparse
    # evaluation: the first head's logits, the module not run, the state as it was
    logits, same = model.apply(params, state, tokens, Context(train=False))
    assert logits.shape == (2, 44, VOCAB) and jax.tree_util.tree_structure(same) == jax.tree_util.tree_structure(state)
    np.testing.assert_array_equal(same[-1]["expert_bias"], state[-1]["expert_bias"])


def test_the_last_position_reaches_no_other_positions_second_state(system, tiny):
    """A sequence's last position has no token after it and is fed the first
    token's embedding (the ids rolled by one): with the trunk's states held
    fixed, another first token moves the module's state at the last position
    and at no other, because attention is causal; the loss gives that
    position weight 0."""
    model = _model(system, tiny)
    params, state = _variables(model)
    tokens = jnp.asarray(np.random.RandomState(8).randint(0, VOCAB, (1, 20)), jnp.int32)
    h = _hidden(np.random.RandomState(9), tiny, 1, 20)
    states = lambda t: model._next_token_module(
        params["mtp"], state[-1], params["embed"]["weight"], t, h, Context(train=True)
    )[0]
    a, b = states(tokens), states(tokens.at[0, 0].set((tokens[0, 0] + 1) % VOCAB))
    np.testing.assert_allclose(a[0, :-1], b[0, :-1], rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(a[0, -1] - b[0, -1]))) > 1e-3


def test_registry_and_constructor_hold_the_models_shape():
    model = load_model("glm_4_7_flash_tiny", VOCAB)
    assert model.counter_names == moe_lib.COUNTERS + seq.NEXT_COUNTERS
    assert load_model("lfm2_tiny", VOCAB).counter_names == moe_lib.COUNTERS
    with pytest.raises(ValueError, match="one width"):
        load_model("glm_4_7_flash_tiny", VOCAB, v_head_dim=32)
    with pytest.raises(ValueError, match="walked once"):
        load_model("glm_4_7_flash_tiny", VOCAB, next_token_modules=2)
    with pytest.raises(ValueError, match="walked once"):
        load_model("ouro_tiny", VOCAB, next_token_modules=1)
    with pytest.raises(ValueError, match="one scale"):  # a tied head is the embedding
        load_model("lfm2_tiny", VOCAB, embed_std=1.0)
    # the embedding at a scale of its own, every projection and the head at init_std; others as they were
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[0]
    assert model.embed_std == 1.0 and 0.9 < float(jnp.std(params["embed"]["weight"])) < 1.1
    assert 0.015 < float(jnp.std(params["head"]["weight"])) < 0.025
    plain = load_model("mellum2_tiny", VOCAB)
    assert plain.embed_std == plain.init_std == 0.02
    np.testing.assert_array_equal(
        plain.init(jax.random.key(0), None)[0]["embed"]["weight"],
        0.02 * jax.random.normal(jax.random.split(jax.random.key(0), 3)[0], (VOCAB, 64), jnp.float32),
    )
    # a model of another layer type with the module: the module's layer is of the last layer's type
    other = load_model("lfm2_tiny", VOCAB, next_token_modules=1)
    params, state = other.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    assert set(params["mtp"]["layer"]["mixer"]) == {"in_proj", "conv", "out_proj"} and len(state) == 6
