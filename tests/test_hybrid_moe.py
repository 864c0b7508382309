"""The hybrid linear-attention mixture-of-experts family (models/hybrid_moe.py,
nn/deltanet.py, nn/moe.py, nn/sequence.py) against its plain reference
(benchmark/reference/qwen3_next_80b_a3b_ep16.py) at the tiny preset on the
CPU: seeded random weights, float32 unless a test says otherwise."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from benchmark import cells
from tpuddp import nn
from tpuddp.models import QWEN3_NEXT_EP16, load_model
from tpuddp.nn import deltanet
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context
from tpuddp.nn.deltanet import _invert_unit_lower, chunk_gated_delta_rule

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


def _model(system, config, **over):
    return load_model(
        config["model"]["registry_name"], config["vocab_size"],
        **{**system.model_kwargs(config), "compute_dtype": "float32", **over},
    )


def _perturbed(params, scale=0.3):
    """Norm weights and decay parameters off their initial 0/1, projections
    large enough that every gate and the router are away from their flat
    middle: a mistake in any of them then shows."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        l + scale * jax.random.normal(k, l.shape) if l.ndim == 1 else l * 8.0
        for l, k in zip(leaves, keys)
    ])


def _close(ours, theirs, rtol):
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(ours), jax.tree_util.tree_leaves(theirs)
    ):
        err = float(jnp.linalg.norm(a - b)) / (float(jnp.linalg.norm(b)) + 1e-12)
        assert err <= rtol, (jax.tree_util.keystr(path), err)


def _hidden(rng, tiny, batch, t, scale=1.0):
    return jnp.asarray(scale * rng.randn(batch, t, tiny["hidden_size"]), jnp.float32)


# -- the scan --------------------------------------------------------------------

@pytest.mark.parametrize("t,chunk", [(37, 16), (64, 16), (100, 64), (5, 16)])
def test_chunked_delta_rule_matches_token_by_token(reference, t, chunk):
    """Forward and every input's gradient, at lengths that are not multiples
    of the chunk, with decays from nearly none to nearly all."""
    rng = np.random.RandomState(t)
    b, h, dk, dv = 2, 3, 16, 8
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = jnp.asarray(unit(rng.randn(b, t, h, dk)) * dk ** -0.5, jnp.float32)
    k = jnp.asarray(unit(rng.randn(b, t, h, dk)), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    g = jnp.asarray(-np.exp(rng.uniform(-7, 1.5, (b, t, h))), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.02, 0.98, (b, t, h)), jnp.float32)
    probe = jnp.asarray(rng.randn(b, t, h, dv), jnp.float32)
    ours = lambda *a: jnp.sum(chunk_gated_delta_rule(*a, chunk=chunk) * probe)
    theirs = lambda *a: jnp.sum(reference.delta_rule(*a) * probe)
    np.testing.assert_allclose(
        chunk_gated_delta_rule(q, k, v, g, beta, chunk=chunk), reference.delta_rule(q, k, v, g, beta),
        rtol=2e-4, atol=2e-5,
    )
    args = (q, k, v, g, beta)
    _close(jax.grad(ours, argnums=range(5))(*args), jax.grad(theirs, argnums=range(5))(*args), 2e-4)


def test_unit_lower_inverse_with_identical_keys():
    """The worst case for a series in powers of ``a``: every entry below the
    diagonal is 1 (identical keys, beta 1, no decay). Forward substitution
    and the joins by halves stay exact."""
    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    inverse = _invert_unit_lower(a[None])[0]
    np.testing.assert_allclose(inverse @ (jnp.eye(64) + a), np.eye(64), atol=1e-5)


def test_chunked_delta_rule_in_bfloat16_keeps_its_sums_in_float32():
    """bfloat16 product inputs over 512 tokens of slow decay: the state and
    the decay sums stay float32, so the result stays within bfloat16's own
    rounding of the float32 one and does not drift with length."""
    rng = np.random.RandomState(0)
    b, t, h, d = 1, 512, 2, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q, k = (jnp.asarray(unit(rng.randn(b, t, h, d)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    g = jnp.full((b, t, h), -0.002, jnp.float32)
    beta = jnp.full((b, t, h), 0.5, jnp.float32)
    exact = chunk_gated_delta_rule(q, k, v, g, beta, chunk=64)
    rounded = chunk_gated_delta_rule(q, k, v, g, beta, chunk=64, compute_dtype=jnp.bfloat16)
    err = jnp.linalg.norm(rounded - exact, axis=-1) / jnp.linalg.norm(exact, axis=-1)
    assert float(jnp.max(err)) < 0.03 and float(jnp.mean(err[:, -64:])) < 2 * float(jnp.mean(err[:, :64])) + 0.01


# -- the mixers and the expert layer ----------------------------------------------

def _layer_params(model, kind_index):
    params, _ = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return _perturbed(params)["layers"][kind_index]


def test_deltanet_mixer_matches_the_reference(reference, system, tiny):
    model = _model(system, tiny)
    p = _layer_params(model, 0)["mixer"]
    x = _hidden(np.random.RandomState(1), tiny, 2, 37)
    ours = lambda p, x: jnp.sum(jnp.sin(model._deltanet(p, x)))
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.deltanet_mixer(tiny, p, x)))
    np.testing.assert_allclose(model._deltanet(p, x), reference.deltanet_mixer(tiny, p, x), rtol=2e-4, atol=2e-5)
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


def test_the_tiny_hybrid_is_bit_equal_to_the_mixers_old_expression(system, tiny, monkeypatch):
    """``deltanet.short_conv``'s plain path is what ``_deltanet`` wrote out
    under its ``conv`` scope before the function existed, moved and not
    rewritten: with that expression put back in the function's place, the
    tiny hybrid's loss and every gradient are the same to the bit, in float32
    and with bfloat16 products."""
    def old(qkv, taps, *, key_width, head_dim, q_scale):
        b, t = qkv.shape[:2]
        qkv = jax.nn.silu(seq.causal_conv1d(qkv, taps))
        q = qkv[..., :key_width].reshape(b, t, -1, head_dim)
        k = qkv[..., key_width: 2 * key_width].reshape(b, t, -1, head_dim)
        v = qkv[..., 2 * key_width:]
        q = seq.l2_normalise(q) * q_scale
        k = seq.l2_normalise(k)
        return q.reshape(b, t, -1), k.reshape(b, t, -1), v

    tokens = jnp.asarray(np.random.RandomState(5).randint(0, VOCAB, (2, 40)), jnp.int32)
    for compute_dtype in ("float32", "bfloat16"):
        model = _model(system, tiny, compute_dtype=compute_dtype)
        params = _perturbed(model.init(jax.random.key(3), tokens)[0])

        def loss(params):
            out, _ = model.apply(params, (), tokens[:, :-1], Context(train=True))
            return nn.CrossEntropyLoss()(out, tokens[:, 1:])

        ours = jax.value_and_grad(loss)(params)
        monkeypatch.setattr(deltanet, "short_conv", old)
        theirs = jax.value_and_grad(loss)(params)
        monkeypatch.undo()
        for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(ours[0]) > 0 and all(float(jnp.abs(g).max()) > 0 for g in jax.tree_util.tree_leaves(ours[1]["layers"][0]["mixer"]))


def test_gated_attention_mixer_matches_the_reference(reference, system, tiny):
    """Partial rotary (a quarter of the head), one key/value head serving
    eight query heads as published, the per-head norms and the output gate;
    queries in blocks of 16 over 50 positions."""
    config = {**tiny, "num_attention_heads": 8, "num_key_value_heads": 1}
    model = _model(system, config, attention_q_block=16)
    p = _layer_params(model, 3)["mixer"]
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours = lambda p, x: jnp.sum(jnp.sin(model._attention(p, x)))
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.attention_mixer(config, p, x)))
    np.testing.assert_allclose(model._attention(p, x), reference.attention_mixer(config, p, x), rtol=2e-4, atol=2e-5)
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


def _moe_ours(model, p, x, **kw):
    y, aux, counters, _ = moe_lib.expert_share_moe(
        p, x.reshape(-1, x.shape[-1]), top_k=model.top_k, first_expert=model.first_expert,
        compute_dtype=jnp.float32, **kw,
    )
    return y.reshape(x.shape), aux, counters


def test_expert_layer_matches_the_reference(reference, system, tiny):
    model = _model(system, tiny)
    p = _layer_params(model, 1)["moe"]
    x = _hidden(np.random.RandomState(3), tiny, 2, 40)
    y, aux, counters = _moe_ours(model, p, x)
    ref_y, ref_aux = reference.moe(tiny, p, x)
    np.testing.assert_allclose(y, ref_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-5)
    assigned = x.shape[0] * x.shape[1] * model.top_k
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assigned
    assert counters["moe_dropped_assignments"] == 0
    ours = lambda p, x: jnp.sum(jnp.sin(_moe_ours(model, p, x)[0])) + _moe_ours(model, p, x)[1]
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.moe(tiny, p, x)[0])) + reference.moe(tiny, p, x)[1]
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


def test_the_shares_add_up_to_the_uncut_layer(reference, system, tiny):
    """Four shares of 2 of the 8 experts each: their routed parts, plus the
    shared expert counted once, are the uncut reference's layer output."""
    n_all, held = tiny["deployment"]["experts_published"], tiny["num_experts"]
    uncut = {**tiny, "num_experts": n_all}
    model = _model(system, uncut)
    p = _layer_params(model, 0)["moe"]
    x = _hidden(np.random.RandomState(4), tiny, 2, 33)
    flat = x.reshape(-1, x.shape[-1])
    whole, _ = reference.moe(uncut, p, x)
    shared = whole.reshape(flat.shape) - reference.routed_part(uncut, p, flat, 0)[0]
    total, seen = shared, 0.0
    for share in range(n_all // held):
        mine = {**p, "experts": jax.tree_util.tree_map(lambda w: w[share * held:(share + 1) * held], p["experts"])}
        y, _, counters, _ = moe_lib.expert_share_moe(
            mine, flat, top_k=model.top_k, first_expert=share * held, compute_dtype=jnp.float32
        )
        ref_y, _ = reference.moe({**tiny, "deployment": {**tiny["deployment"], "first_expert": share * held}}, mine, x)
        np.testing.assert_allclose(y, ref_y.reshape(flat.shape), rtol=2e-4, atol=2e-5)
        total = total + (y - shared)
        seen += float(counters["moe_expert_tokens_held"])
    np.testing.assert_allclose(total, whole.reshape(flat.shape), rtol=2e-4, atol=2e-5)
    assert seen == flat.shape[0] * model.top_k  # every assignment is some share's


@pytest.mark.parametrize("round_rows", [None, 16])
def test_no_token_is_dropped_when_every_token_chooses_held_experts(reference, system, tiny, round_rows):
    """A router forced to send every token to the two held experts: N k rows,
    many rounds of the grouped product, none dropped, result and gradients
    the reference's."""
    model = _model(system, tiny)
    p = _layer_params(model, 0)["moe"]
    router = jnp.zeros_like(p["router"]).at[:, :2].set(jnp.abs(p["router"][:, :2]) + 1.0)
    p = {**p, "router": router}
    x = jnp.abs(_hidden(np.random.RandomState(5), tiny, 1, 40)) + 0.1  # positive: logits 0 and 1 lead
    y, _, counters = _moe_ours(model, p, x, round_rows=round_rows)
    assert counters["moe_expert_tokens_held"] == 40 * model.top_k
    assert counters["moe_absent_assignments"] == 0 and counters["moe_dropped_assignments"] == 0
    assert counters["moe_expert_tokens_max"] == 40
    np.testing.assert_allclose(y, reference.moe(tiny, p, x)[0], rtol=2e-4, atol=2e-5)
    ours = lambda p, x: jnp.sum(jnp.sin(_moe_ours(model, p, x, round_rows=round_rows)[0]))
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.moe(tiny, p, x)[0]))
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


# -- the model -------------------------------------------------------------------

def test_registry_builds_the_published_cut_and_the_tiny_preset(system, published):
    """The published cut, as shapes only: its registry preset is the
    configuration file's numbers, its parameters the file's count, and every
    published width is unchanged in the file."""
    model = load_model("qwen3_next_ep16", published["vocab_size"])
    from_file = _model(system, published)
    ours = {"compute_dtype": None, "aux_loss_weight": None}  # the file's own choices (`assumed`)
    assert {**vars(from_file), **ours} == {**vars(model), **ours}
    assert all(hasattr(model, name) or name == "partial_rotary_factor" for name in QWEN3_NEXT_EP16)
    assert [model.layer_kind(i) for i in range(4)] == ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    shapes, _ = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    count = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert count == published["parameters"]
    assert shapes["layers"][0]["moe"]["router"].shape == (2048, 512)
    assert shapes["layers"][0]["moe"]["experts"]["gate_up"].shape == (32, 2048, 1024)
    assert shapes["layers"][0]["mixer"]["in_proj_qkvz"].shape == (2048, 12288)
    assert shapes["layers"][3]["mixer"]["q_proj"].shape == (2048, 8192)
    assert shapes["head"]["weight"].shape == (2048, 18992)
    catalog = {
        "head_dim": 256, "hidden_size": 2048, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32, "moe_intermediate_size": 512,
        "num_attention_heads": 16, "num_key_value_heads": 2, "num_experts_per_tok": 10,
        "shared_expert_intermediate_size": 512, "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
    }
    assert {k: published[k] for k in catalog} == catalog
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    tiny_model = load_model("qwen3_next_tiny", VOCAB)
    assert tiny_model.hidden_size <= 64 and (tiny_model.n_experts, tiny_model.top_k) == (8, 2)
    assert tiny_model.n_layers == tiny_model.full_attention_interval  # one period


def test_evaluation_returns_logits_and_training_defers_them(system, tiny):
    model = _model(system, tiny)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, 20)))
    params, state = model.init(jax.random.key(0), tokens)
    logits, _ = model.apply(params, state, tokens, Context(train=False))
    deferred, _ = model.apply(params, state, tokens, Context(train=True))
    assert logits.shape == (2, 20, VOCAB) and isinstance(deferred, nn.DeferredLogits)
    np.testing.assert_allclose(deferred.logits(), logits, rtol=1e-5, atol=1e-6)
    labels = jnp.roll(tokens, -1, axis=1)
    criterion = nn.CrossEntropyLoss()
    np.testing.assert_allclose(criterion(deferred, labels), criterion(logits, labels), rtol=1e-6)
    # per-sequence weights (the loaders' padding mask) cover their tokens
    mask = jnp.asarray([1.0, 0.0])
    np.testing.assert_allclose(
        criterion(deferred, labels, mask), criterion(logits[:1], labels[:1]), rtol=1e-6
    )
    np.testing.assert_allclose(criterion(logits, labels, mask), criterion(logits[:1], labels[:1]), rtol=1e-6)


def test_auxiliary_loss_is_in_the_gradient_and_not_in_the_loss(system, tiny):
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (2, 24)))
    labels = jnp.roll(tokens, -1, axis=1)
    with_aux, without = _model(system, tiny, aux_loss_weight=0.5), _model(system, tiny, aux_loss_weight=0.0)
    params, state = with_aux.init(jax.random.key(0), tokens)

    def loss(model, p):
        return nn.CrossEntropyLoss()(model.apply(p, state, tokens, Context(train=True))[0], labels)

    a, grad_a = jax.value_and_grad(lambda p: loss(with_aux, p))(params)
    b, grad_b = jax.value_and_grad(lambda p: loss(without, p))(params)
    assert float(a) == float(b)
    moved = lambda g: float(jnp.linalg.norm(g["layers"][0]["moe"]["router"]))
    assert abs(moved(grad_a) - moved(grad_b)) > 1e-3 * moved(grad_b)


def test_the_references_blocks_change_no_arithmetic(reference, system, tiny, monkeypatch):
    """The reference takes attention and the loss in blocks for memory only:
    with blocks short enough that the rolled loop over whole blocks and the
    call for what is left both run (44 tokens: two of 16 and one of 12), loss,
    auxiliary loss and gradients are those of one block over everything."""
    model = _model(system, tiny)
    params, _ = model.init(jax.random.key(3), None)
    params = _perturbed(params)
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))
    objective = lambda p: sum(reference.loss_and_aux(tiny, p, tokens, targets))
    whole = jax.value_and_grad(objective)(params)
    monkeypatch.setattr(reference, "_QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "_LOSS_BLOCK", 16)
    blocked = jax.value_and_grad(objective)(params)
    _close(blocked[0], whole[0], 1e-6)
    _close(blocked[1], whole[1], 1e-4)


# -- softmax attention's two lowerings (nn/sequence.py) -----------------------

# kernel-eligible and small: three blocks of 512 (the diagonal's, whole and
# skipped blocks all occur), two query heads a key/value head
_T, _HQ, _HKV, _D = 1536, 4, 2, 128


def _qkvw(dtype, t=_T):
    keys = jax.random.split(jax.random.key(11), 4)
    shapes = ((1, t, _HQ, _D), (1, t, _HKV, _D), (1, t, _HKV, _D), (1, t, _HQ, _D))
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(keys, shapes)]


def _lowerings(dtype):
    kw = dict(scale=_D ** -0.5, compute_dtype=dtype)
    return {
        "fused": lambda q, k, v: seq._fused_causal_attention(q, k, v, interpret=True, **kw),
        "blockwise": lambda q, k, v: seq._blockwise_causal_attention(q, k, v, q_block=512, **kw),
    }


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2 ** -6)])
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_fused_attention_agrees_with_the_blockwise_path(dtype, tol, what):
    """The kernel in interpret mode against the blockwise path: float32
    inputs to float32 tolerance, bfloat16 inputs to bfloat16's rounding (8
    bits: half a unit in the last place of values up to 4, and a sum of
    two)."""
    q, k, v, w = _qkvw(dtype)
    got = {}
    for name, f in _lowerings(jnp.dtype(dtype)).items():
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
        got[name] = f(q, k, v) if what == "out" else jax.grad(loss, argnums="qkv".index(what[1]))(q, k, v)
    assert got["fused"].dtype == got["blockwise"].dtype == jnp.dtype(dtype)
    a, b = (np.asarray(got[n], np.float32) for n in ("fused", "blockwise"))
    assert np.abs(b).max() > 1.0  # the tolerance is against values of this size
    np.testing.assert_allclose(a, b, rtol=0, atol=tol * np.abs(b).max())


@pytest.mark.parametrize("lowering", ["fused", "blockwise"])
@pytest.mark.parametrize("cut", [511, 512, 1100])
def test_attention_is_causal_to_the_bit(lowering, cut):
    """Other tokens after position ``cut`` leave the outputs up to it as they
    were, bit for bit."""
    q, k, v, _ = _qkvw(jnp.bfloat16)
    q2, k2, v2, _ = (jnp.concatenate([a[:, :cut + 1], -2.0 * a[:, cut + 1:]], axis=1) for a in _qkvw(jnp.bfloat16))
    f = jax.jit(_lowerings(jnp.bfloat16)[lowering])
    before, after = f(q, k, v), f(q2, k2, v2)
    np.testing.assert_array_equal(np.asarray(before[:, :cut + 1]), np.asarray(after[:, :cut + 1]))
    assert not np.array_equal(np.asarray(before[:, cut + 1:]), np.asarray(after[:, cut + 1:]))


@pytest.mark.parametrize("backend,head_dim,t,per_replica,want", [
    ("tpu", 256, 8192, True, "fused"),       # the published widths at the cell's length
    ("tpu", 128, 1536, True, "fused"),       # blocks of 512
    ("tpu", 256, 8192, False, "blockwise"),  # mode="auto": GSPMD cannot partition a custom call
    ("cpu", 256, 8192, True, "blockwise"),
    ("gpu", 256, 8192, True, "blockwise"),
    ("tpu", 16, 8192, True, "blockwise"),    # the tiny preset's heads
    ("tpu", 192, 8192, True, "blockwise"),   # no whole number of lane registers
    ("tpu", 512, 8192, True, "blockwise"),   # wider than the blocks were sized for
    ("tpu", 256, 8000, True, "blockwise"),   # ragged lengths
    ("tpu", 256, 8192 + 256, True, "blockwise"),
    ("tpu", 256, 48, True, "blockwise"),
])
def test_attention_lowering_rule(backend, head_dim, t, per_replica, want):
    assert seq.attention_lowering(backend, head_dim, t, per_replica=per_replica) == want


@pytest.mark.parametrize("t,block", [(8192, 1024), (3072, 1024), (1536, 512), (512, 512)])
def test_fused_attention_blocks_divide_the_sequence(t, block):
    blocks = seq.fused_attention_blocks(t, 256)
    assert {blocks.block_q, blocks.block_kv, blocks.block_q_dkv, blocks.block_kv_dkv} == {block}
    assert blocks.use_fused_bwd_kernel and blocks.has_backward_blocks
    assert block % blocks.block_kv_compute == 0 and block % blocks.block_kv_dkv_compute == 0


@pytest.mark.parametrize("mode,want", [("shard_map", True), ("auto", False), ("shard_map_unchecked", True)])
def test_a_call_knows_whether_it_is_traced_per_replica(mode, want):
    """Inside the wrap's ``shard_map`` (with and without its replication
    check) the traced code sees a mesh whose every axis is manual; under
    ``jit`` over the same mesh of eight it sees none, and eight devices."""
    mesh = Mesh(np.array(jax.devices()), ("data",))
    seen = []

    def f(x):
        seen.append(seq.traced_per_replica())
        return jax.lax.map(jax.checkpoint(lambda row: seen.append(seq.traced_per_replica()) or 2 * row), x)

    x = jnp.ones((len(jax.devices()), 4))
    if mode == "auto":
        jax.jit(f, in_shardings=NamedSharding(mesh, P("data")))(x)
    else:
        wrapped = jax.shard_map(
            f, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=mode == "shard_map"
        )
        jax.jit(wrapped)(x)
    assert len(jax.devices()) > 1 and seen and all(s is want for s in seen)


def test_the_tiny_preset_and_the_cpu_stay_on_the_blockwise_path(system, tiny, monkeypatch):
    """Whatever the model, a CPU run never reaches the kernel."""
    monkeypatch.setattr(seq, "_fused_causal_attention", lambda *a, **k: pytest.fail("the kernel on the CPU"))
    model = _model(system, tiny)
    params, _ = model.init(jax.random.key(0), jnp.zeros((1, 48), jnp.int32))
    model._attention(params["layers"][3]["mixer"], jnp.ones((1, 48, tiny["hidden_size"])))


def test_the_kernel_runs_inside_the_wraps_shard_map():
    """A ``pallas_call`` inside ``shard_map`` over the data axis as the step
    builders wrap it (``check_vma=False``: the library's kernels declare no
    ``vma``, so the replication check refuses them while a step is traced, and
    the wraps have it off), forward and backward: each device attends to its
    own sequence."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    q, k, v, _ = (jnp.concatenate([a, a[:, ::-1]]) for a in _qkvw(jnp.float32, t=512))
    fused = _lowerings(jnp.float32)["fused"]
    grad = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(fused(q, k, v))), argnums=(0, 1, 2))
    wrapped = jax.jit(jax.shard_map(
        grad, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False
    ))
    for got, want in zip(wrapped(q, k, v), grad(q, k, v)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# -- the fused lowering compiled for the chip, with no chip attached ----------

@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip: the TPU's compiler is installed, and compiles
    for a chip that is described and not attached."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("t,hq,hkv,d,window", [
    (8192, 16, 2, 256, None), (1536, 4, 2, 128, None), (3072, 8, 8, 256, None),
    (16384, 32, 4, 128, None), (16384, 32, 4, 128, 1024),  # the window-and-full cell's two layer types
    (1536, 4, 2, 128, 400),
    (32768, 32, 8, 64, None),  # the convolution-and-attention cell's: heads of 64, a key/value head's group a call
    (65536, 8, 2, 64, None),  # one group past the bound a call: the two-kernel backward
    (1536, 4, 2, 64, None),  # heads of 64 under the one-kernel backward
    (16384, 20, 20, 256, None),  # the latent-attention cell's: ungrouped heads of 192 + 64, every head in one call
])
def test_the_fused_lowering_compiles_for_a_v5e(v5e, t, hq, hkv, d, window):
    """Forward and backward kernels at the blocks the rule picks (the first
    shape is the DeltaNet hybrid's cell's): Mosaic refuses here what it would
    refuse on the chip, a block that does not fit VMEM first of all. Past the
    bound a head on the one-kernel backward's partial ``dq`` the heads go a
    group a call and the program's scratch stays under 3 GB; where a group
    is past the bound a call the query gradient has a kernel of its own."""
    sds = lambda h: jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16, sharding=v5e)
    fused = lambda q, k, v: seq._fused_causal_attention(
        q, k, v, scale=d ** -0.5, compute_dtype=jnp.bfloat16, window=window
    )
    grad = jax.grad(lambda q, k, v: jnp.sum(jax.checkpoint(fused)(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(sds(hq), sds(hkv), sds(hkv)).compile()
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text and "tpu_custom_call" in text
    assert ("splash_mha_dq" in text) == (not seq.fused_attention_blocks(t, d, window, hq=hq, hkv=hkv).use_fused_bwd_kernel)
    assert ("splash_mha_dq" in text) == (t > 32768)
    if t > 16384:
        assert compiled.memory_analysis().temp_size_in_bytes < 3e9
    if (hq, d) == (20, 256):  # the widest call of any cell: 16 partial dq of 20 heads x 256, 2,855,433,728 bytes
        assert compiled.memory_analysis().temp_size_in_bytes < 2.9e9


def test_a_group_of_queries_under_a_selection_compiles_for_a_v5e(v5e):
    """The sparse-attention cell's group at its last stretch (1,024 queries
    against 32,768 keys, 32/4 heads of 128, an indexer of 16 heads of 64,
    2,048 keys a query), forward and backward: the index scores' kernel pair,
    the threshold's, the library's attention kernels under a mask that is
    data with their one-kernel backward, and the mean probability's."""
    g, t = seq.SPARSE_GROUP, 32768
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    f32 = jnp.float32

    def loss(q, qi, wi, k, v, ki, start):
        out, kl, _ = seq.sparse_attention_rows(
            q, qi, wi, k, v, ki, start, scale=128 ** -0.5, top_k=2048, compute_dtype=jnp.bfloat16, lowering="fused"
        )
        return jnp.sum(out.astype(f32)) + kl

    args = (sds(g, 32, 128), sds(g, 16, 64, dtype=f32), sds(g, 16, dtype=f32), sds(t, 4, 128, dtype=f32),
            sds(t, 4, 128, dtype=f32), sds(t, 64, dtype=f32), sds(dtype=jnp.int32))
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    for kernel in ("sparse_index_scores_fwd", "sparse_index_scores_bwd", "sparse_kth_largest",
                   "sparse_mean_probabilities", "splash_mha_fwd", "splash_mha_dkv"):
        assert kernel in text, kernel
    assert "splash_mha_dq" not in text  # 32 partial dq of a group's 1,024 queries: the one-kernel backward
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


@pytest.mark.parametrize("t,hk,hv,d", [(8192, 16, 32, 128), (512, 2, 2, 256)])
def test_the_fused_scan_compiles_for_a_v5e(v5e, t, hk, hv, d):
    """Forward and backward kernels of the scan's chunk-local phase and of
    its carry over chunks (the first shape is the token cell's) through
    Mosaic."""
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct((1, t, *shape), dtype, sharding=v5e)
    fused = lambda *a: deltanet._chunked_rule(*a, chunk=64, compute_dtype=jnp.bfloat16, fused=True)
    grad = jax.grad(lambda *a: jnp.sum(jax.checkpoint(fused)(*a).astype(jnp.float32)), argnums=range(5))
    vectors = sds(hv, dtype=jnp.float32)
    text = jax.jit(grad).lower(sds(hk, d), sds(hk, d), sds(hv, d), vectors, vectors).compile().as_text()
    assert "deltanet_chunk_fwd" in text and "deltanet_chunk_bwd" in text and "tpu_custom_call" in text
    assert "deltanet_carry_fwd" in text and "deltanet_carry_bwd" in text


@pytest.mark.parametrize("t,channels,key_width,d,taps", [(8192, 8192, 2048, 128, 4), (512, 1024, 256, 256, 8)])
def test_the_fused_conv_compiles_for_a_v5e(v5e, t, channels, key_width, d, taps):
    """Forward and backward kernels of the DeltaNet mixer's short convolution
    (the first shape is the token cell's) through Mosaic, the forward once an
    output."""
    from tpuddp.nn import deltanet_conv_kernels

    rows = jax.ShapeDtypeStruct((1, t, channels), jnp.bfloat16, sharding=v5e)
    filt = jax.ShapeDtypeStruct((taps, channels), jnp.float32, sharding=v5e)
    fused = lambda x, w: deltanet_conv_kernels.short_conv(x, w, key_width, d, d ** -0.5, 1e-6, False)

    def loss(x, w):
        first, again = fused(x, w), jax.checkpoint(fused)(x, w)  # the outputs are used, so the forward stays
        return sum(jnp.sum(jnp.sin(a.astype(jnp.float32)) * b.astype(jnp.float32)) for a, b in zip(first, again))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(rows, filt).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 4  # three forwards, one backward at least
    assert "deltanet_conv_fwd" in text and "deltanet_conv_bwd" in text


@pytest.mark.slow
@pytest.mark.parametrize("workload,sequences,method", [
    (WORKLOAD, 1, "train_step"), (WORKLOAD, 2, "train_step_many"),
    ("mellum2_ep4_t16k_fused", 1, "train_step"), ("mellum2_ep4_t16k_fused", 1, "train_step_many"),
    ("lfm2_ep4_t32k_fused", 1, "train_step"), ("lfm2_ep4_t32k_fused", 1, "train_step_many"),
    ("ouro_loop4_t16k_fused", 1, "train_step"), ("ouro_loop4_t16k_fused", 1, "train_step_many"),  # four rolled passes
    # the largest state of any cell beside the widest attention call
    ("glm47flash_ep8_t16k_fused", 1, "train_step"), ("glm47flash_ep8_t16k_fused", 1, "train_step_many"),
    # attention under a mask that is data, a group of 1,024 queries a call of the kernel, three stretches a layer
    ("keye2_ep8_t32k_fused", 1, "train_step"), ("keye2_ep8_t32k_fused", 1, "train_step_many"),
])
def test_the_token_cells_step_compiles_for_a_v5e(v5e, monkeypatch, workload, sequences, method):
    """The whole step at published widths (the check's single step at one
    sequence, the timed K-step program at the cell's batch) with its fused
    lowerings, attention's (full and banded), the scan's two pairs (what a
    chunk computes alone, and the carry over chunks) and the short
    convolution's: inside it XLA
    keeps buffers of its own in VMEM, and a block that compiled alone did not
    fit (PERF.md, PR 29). About a minute each."""
    from tpuddp.training.train_state import create_train_state

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # this process sees the CPU
    mesh = Mesh(np.array(list(v5e.device_set)), ("data",))
    cell = cells.load_cell(workload)
    model, ddp = cells.load_system(cell).build_ddp(cell, mesh)
    t = cell.config["tokens"]["seq_len"]
    assert sequences == (1 if method == "train_step" else cell.traffic["batch_per_chip"])
    state = jax.eval_shape(
        lambda k: create_train_state(model, ddp.optimizer, k, jnp.zeros((1, t), jnp.int32)), jax.random.key(0)
    )
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(mesh, P())), state)
    lead, spec = ((), P("data")) if method == "train_step" else ((cell.traffic["scan_steps"],), P(None, "data"))
    rows = lambda dtype: jax.ShapeDtypeStruct((*lead, sequences, t), dtype, sharding=NamedSharding(mesh, spec))
    batch = (rows(jnp.int32), rows(jnp.int32), rows(jnp.float32))
    compiled = jax.jit(getattr(ddp, method)).lower(state, batch).compile()
    text = compiled.as_text()
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert "splash_mha_dq" not in text  # the one-kernel backward, past 16,384 tokens a key/value head's group a call
    memory = compiled.memory_analysis()  # nothing donated here: the state once as argument, once as result
    assert memory.temp_size_in_bytes + memory.argument_size_in_bytes < 14.5e9
    if "LatentAttention" in model.layer_types:  # 8.48 GB of arguments: the scratch stays where it compiled (3.64 GB)
        assert memory.temp_size_in_bytes < 3.8e9
    if "SparseAttention" in model.layer_types:  # 6.75 GB of arguments beside the scratch of three stretches a layer (2.90 GB; six: 3.87, PR 49)
        assert memory.temp_size_in_bytes < 3.1e9
    elif t > 16384 and method == "train_step_many":  # the timed program: no more scratch than with the two kernels (PR 42)
        assert memory.temp_size_in_bytes <= 7_031_718_912
    deltanet_layers = "GatedDeltaNet" in model.layer_types
    assert ("deltanet_chunk_fwd" in text and "deltanet_chunk_bwd" in text) == deltanet_layers
    assert ("deltanet_carry_fwd" in text) == deltanet_layers and ("deltanet_carry_bwd" in text) == deltanet_layers
    assert ("deltanet_conv_fwd" in text and "deltanet_conv_bwd" in text) == deltanet_layers  # the short convolution's
    # the grouped products: Pallas where an expert's rows are many (nn/moe.py: grouped_tiles)
    many_rows = cell.traffic["batch_per_chip"] * t * model.top_k // model.n_experts >= 1024
    if many_rows:  # the cell's round, and the tiles the rule answers its gate-and-up call: forward, rows', matrices'
        rows = moe_lib._round_rows(
            cell.traffic["batch_per_chip"] * t, model.top_k, model.experts_held, model.n_experts
        )
        assert (workload, rows, moe_lib.grouped_tiles(
            "tpu", rows, model.experts_held, model.hidden_size, 2 * model.expert_width, per_replica=True
        )) in (
            # 1,664 rows a group, 52 whole row tiles: one triple, swapped for the rows' gradient (PR 45)
            ("glm47flash_ep8_t16k_fused", 13312, ((256, 2048, 512), (256, 512, 2048), (256, 2048, 512))),
            # long groups: all that is contracted in one tile, a triple a kernel (PR 47)
            ("mellum2_ep4_t16k_fused", 52480, ((256, 2304, 896), (256, 1792, 1152), (256, 1152, 896))),
            ("lfm2_ep4_t32k_fused", 52480, ((256, 2048, 896), (256, 3584, 512), (256, 2048, 512))),
            ("keye2_ep8_t32k_fused", 52480, ((256, 2048, 768), (256, 1536, 1024), (256, 2048, 512))),  # PR 49
        )
    # forward kernel, weight-gradient kernel; by the instruction's name (a kernel's serialised body is
    # base64, in which three letters turn up by chance)
    named = lambda kernel: re.search(rf"%{kernel}(\.\d+)? = ", text) is not None
    assert named("gmm") == many_rows and named("tgmm") == many_rows
