"""Gated short convolutions beside full attention in one model
(models/hybrid_moe.py's ``ShortConv`` layers, a dense or a sparse feed-forward
a layer, a tied head, the router's selection bias as the trunk's state;
nn/sequence.py's gated convolution and heads of 64 in the fused lowering;
nn/moe.py's sigmoid router) against its plain reference
(benchmark/reference/lfm2_8b_a1b_ep4.py) at the tiny preset on the CPU: seeded
random weights, float32 unless a test says otherwise. Whole training steps are
in tests/test_conv_moe_training.py."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from benchmark import cells, scope_reduce
from test_window_moe import _close, _hidden, _model, _perturbed  # the hybrid family's tests share them
from tpuddp import nn
from tpuddp.models import load_model
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context
from tpuddp.observability import profiling

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


def _variables(model, perturb=True):
    params, state = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return (_perturbed(params), _perturbed(state)) if perturb else (params, state)


# -- the gated convolution (nn/sequence.py) -------------------------------------------

def test_gated_short_conv_is_its_equation_written_down():
    """``z_t = c_t * sum_j w_j (b * u)_{t - 2 + j}`` token by token, zeros
    before the sequence; the three streams lie ``b | c | u``."""
    rng = np.random.RandomState(0)
    bcu, w = rng.randn(2, 9, 3 * 5).astype(np.float32), rng.randn(3, 5).astype(np.float32)
    b, c, u = bcu[..., :5], bcu[..., 5:10], bcu[..., 10:]
    want = np.zeros((2, 9, 5), np.float32)
    for t in range(9):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += w[j] * (b * u)[:, t - 2 + j]
    np.testing.assert_allclose(seq.gated_short_conv(jnp.asarray(bcu), jnp.asarray(w)), c * want, rtol=1e-5, atol=1e-6)
    # causal to the bit: other tokens after a position leave it as it was
    moved = bcu.copy()
    moved[:, 6:] *= -2.0
    np.testing.assert_array_equal(
        np.asarray(seq.gated_short_conv(jnp.asarray(moved), jnp.asarray(w)))[:, :6],
        np.asarray(seq.gated_short_conv(jnp.asarray(bcu), jnp.asarray(w)))[:, :6],
    )
    half = seq.gated_short_conv(jnp.asarray(bcu, jnp.bfloat16), jnp.asarray(w))
    assert half.dtype == jnp.bfloat16  # the result in the rows' type, the arithmetic in float32
    np.testing.assert_allclose(np.asarray(half, np.float32), c * want, rtol=0, atol=2 ** -6 * np.abs(c * want).max())


# -- the operators and the feed-forwards ------------------------------------------------

def test_short_conv_mixer_matches_the_reference(reference, system, tiny):
    model = _model(system, tiny)
    assert model.layer_kind(0) == model.layer_kind(2) == "ShortConv"
    p = _variables(model)[0]["layers"][2]["mixer"]
    assert set(p) == {"in_proj", "conv", "out_proj"} and p["conv"].shape == (3, tiny["hidden_size"])
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours, theirs = model._short_conv, lambda p, x: reference.conv_mixer(tiny, p, x)
    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-4)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    _close(jax.grad(loss(ours), argnums=(0, 1))(p, x), jax.grad(loss(theirs), argnums=(0, 1))(p, x), 5e-4)


def test_attention_mixer_matches_the_reference(reference, system, tiny):
    """The trunk's ``FullAttention`` with no YaRN table: the per-head norm,
    plain rotary on the whole head, two query heads a key/value head."""
    model = _model(system, tiny)
    assert model.layer_kind(1) == "FullAttention" and model.yarn is None
    p = _variables(model)[0]["layers"][1]["mixer"]
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours = lambda p, x: model._attention(p, x, kind="FullAttention")
    theirs = lambda p, x: reference.attention_mixer(tiny, p, x)
    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    _close(jax.grad(loss(ours), argnums=(0, 1))(p, x), jax.grad(loss(theirs), argnums=(0, 1))(p, x), 5e-4)


@pytest.mark.parametrize("tokens", [50, 32, 20])  # a chunk of 32 and what is left, one chunk, less than one
def test_dense_feed_forward_matches_the_reference(reference, system, tiny, tokens):
    """The leading layer's tree has ``mlp`` and no ``moe``: the SwiGLU over
    chunks of tokens is the reference's over all of them, with and without
    the chunks' recomputation."""
    model = _model(system, tiny)
    p = _variables(model)[0]["layers"][0]
    assert "mlp" in p and "moe" not in p and p["mlp"]["gate_up"].shape == (64, 2 * tiny["intermediate_size"])
    h = _hidden(np.random.RandomState(5), tiny, 2, tokens // 2)
    theirs = lambda p, h: h + reference.dense(p["mlp"], reference._rms(h, p["post_norm"], tiny["norm_eps"]))
    for remat in (False, True):
        ours = lambda p, h: model._dense(p, h, remat)
        np.testing.assert_allclose(ours(p, h), theirs(p, h), rtol=2e-4, atol=2e-4)
        loss = lambda f: lambda p, h: jnp.sum(jnp.sin(f(p, h)))
        got = jax.grad(loss(ours), argnums=(0, 1))(p, h)
        want = jax.grad(loss(theirs), argnums=(0, 1))(p, h)
        _close((got[0]["mlp"], got[0]["post_norm"], got[1]), (want[0]["mlp"], want[0]["post_norm"], want[1]), 5e-4)


def _moe_ours(model, p, bias, x, **kw):
    y, aux, counters, router_counts = moe_lib.expert_share_moe(
        p, x.reshape(-1, x.shape[-1]), top_k=model.top_k, first_expert=model.first_expert,
        compute_dtype=jnp.float32, bias=bias, **kw,
    )
    return y.reshape(x.shape), aux, counters, router_counts


def test_biased_expert_layer_matches_the_reference(reference, system, tiny):
    """Sigmoid scores, the choice by score plus bias, the weights by the
    scores alone: the layer's part for the held experts, the counts over all
    the router's experts, and every gradient; no auxiliary loss."""
    model = _model(system, tiny)
    params, state = _variables(model)
    p, bias = params["layers"][2]["moe"], state[2]["expert_bias"]
    assert set(p) == {"router", "experts"} and bias.shape == (8,) and state[0] == ()
    x = _hidden(np.random.RandomState(3), tiny, 2, 40)
    y, aux, counters, router_counts = _moe_ours(model, p, bias, x)
    ref_y, ref_counts = reference.moe(tiny, p, bias, x)
    np.testing.assert_allclose(y, ref_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(router_counts, ref_counts)
    assert float(aux) == 0.0
    assigned = x.shape[0] * x.shape[1] * model.top_k
    assert float(jnp.sum(router_counts)) == assigned
    assert counters["moe_expert_tokens_held"] + counters["moe_absent_assignments"] == assigned
    assert counters["moe_expert_tokens_held"] == float(jnp.sum(router_counts[:2]))  # experts 0 and 1 are held
    assert counters["moe_router_tokens_max"] == float(jnp.max(router_counts)) >= counters["moe_expert_tokens_max"]
    assert counters["moe_dropped_assignments"] == 0
    ours = lambda p, x: jnp.sum(jnp.sin(_moe_ours(model, p, bias, x)[0]))
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.moe(tiny, p, bias, x)[0]))
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


def test_the_shares_add_up_to_the_uncut_layer(reference, system, tiny):
    """Four shares of 2 of the 8 experts each, all under one router and one
    bias: their routed parts (nothing in a sparse layer is computed by every
    chip alike: no shared expert) are the uncut reference's layer output, and
    every assignment is some share's. The dense layer is every chip's alike:
    it is the uncut reference's once, whatever the share."""
    n_all, held = tiny["deployment"]["experts_published"], tiny["num_experts"]
    uncut = {**tiny, "num_experts": n_all}
    model = _model(system, uncut)
    params, state = _variables(model)
    p, bias = params["layers"][1]["moe"], state[1]["expert_bias"]
    x = _hidden(np.random.RandomState(4), tiny, 2, 33)
    flat = x.reshape(-1, x.shape[-1])
    whole, whole_counts = reference.moe(uncut, p, bias, x)
    total, seen = 0.0, 0.0
    for share in range(n_all // held):
        mine = {**p, "experts": jax.tree_util.tree_map(lambda w: w[share * held:(share + 1) * held], p["experts"])}
        y, _, counters, router_counts = moe_lib.expert_share_moe(
            mine, flat, top_k=model.top_k, first_expert=share * held, compute_dtype=jnp.float32, bias=bias,
        )
        theirs = {**tiny, "deployment": {**tiny["deployment"], "first_expert": share * held}}
        np.testing.assert_allclose(y, reference.moe(theirs, mine, bias, x)[0].reshape(flat.shape), rtol=2e-4, atol=2e-5)
        np.testing.assert_array_equal(router_counts, whole_counts)  # every share routes over all experts alike
        total = total + y
        seen += float(counters["moe_expert_tokens_held"])
    np.testing.assert_allclose(total, whole.reshape(flat.shape), rtol=2e-4, atol=2e-5)
    assert seen == flat.shape[0] * model.top_k
    share_model, dense = _model(system, tiny), params["layers"][0]
    assert jax.tree.structure(share_model.init(jax.random.key(3), None)[0]["layers"][0]) == jax.tree.structure(dense)
    np.testing.assert_allclose(
        share_model._dense(dense, x, False),
        x + reference.dense(dense["mlp"], reference._rms(x, dense["post_norm"], tiny["norm_eps"])),
        rtol=2e-4, atol=2e-4,
    )


# -- the router (nn/moe.py) -----------------------------------------------------------

def _router_case():
    keys = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(keys[0], (64, 16), jnp.float32)
    router = 0.5 * jax.random.normal(keys[1], (16, 8), jnp.float32)
    return x, router, jax.random.normal(keys[2], (8,), jnp.float32)


def test_a_bias_changes_who_is_chosen_and_never_a_weight():
    x, router, bias = _router_case()
    scores = jax.nn.sigmoid(x @ router)
    plain_w, plain_e, plain_s = moe_lib.route(x, router, top_k=2, bias=jnp.zeros(8))
    w, e, s = moe_lib.route(x, router, top_k=2, bias=bias)
    np.testing.assert_allclose(s, scores, rtol=1e-6)
    np.testing.assert_array_equal(s, plain_s)
    # the choice: the two largest of score plus bias, which are not the two largest scores for most tokens
    np.testing.assert_array_equal(np.sort(e, -1), np.sort(np.argsort(-(scores + bias), -1)[:, :2], -1))
    assert np.mean(np.sort(e, -1) != np.sort(plain_e, -1)) > 0.3
    # the weights: the chosen experts' own scores over their sum, no bias in them
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(e), -1)
    np.testing.assert_allclose(w, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # a bias that is the same for every expert changes nothing at all
    same_w, same_e, _ = moe_lib.route(x, router, top_k=2, bias=jnp.full(8, 3.0))
    np.testing.assert_array_equal(same_e, plain_e)
    np.testing.assert_array_equal(same_w, plain_w)
    # without a bias the router is the softmax it was
    soft_w, soft_e, probs = moe_lib.route(x, router, top_k=2)
    np.testing.assert_allclose(probs, jax.nn.softmax(x @ router, -1), rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(soft_w, -1), 1.0, rtol=1e-6)


def test_the_bias_has_no_gradient():
    x, router, bias = _router_case()
    experts = {
        "gate_up": 0.2 * jax.random.normal(jax.random.key(4), (3, 16, 8)),
        "down": 0.2 * jax.random.normal(jax.random.key(5), (3, 4, 16)),
    }

    def loss(bias, router):
        y = moe_lib.expert_share_moe(
            {"router": router, "experts": experts}, x, top_k=2, first_expert=0, compute_dtype=jnp.float32, bias=bias
        )[0]
        return jnp.sum(jnp.sin(y))

    d_bias, d_router = jax.grad(loss, argnums=(0, 1))(bias, router)
    assert not np.any(np.asarray(d_bias)) and np.any(np.asarray(d_router))


def test_the_sign_rule_moves_the_bias_towards_the_mean_count():
    counts = jnp.asarray([10.0, 0.0, 4.0, 2.0, 4.0, 4.0, 8.0, 0.0])  # mean 4
    bias = jnp.linspace(-1.0, 1.0, 8)
    moved = moe_lib.balanced_bias(bias, counts, 1e-3)
    np.testing.assert_allclose(moved - bias, 1e-3 * np.asarray([-1, 1, 0, 1, 0, 0, -1, 1.0]), atol=1e-7)
    np.testing.assert_array_equal(moe_lib.balanced_bias(bias, jnp.full(8, 7), 1e-3), bias)  # an even load moves nothing
    # a fixed step whatever the gap: repeated, it turns an uneven choice even
    x, router, _ = _router_case()
    bias = jnp.zeros(8)
    spread = lambda b: float(jnp.max(moe_lib.expert_share_moe(
        {"router": router, "experts": {"gate_up": jnp.zeros((1, 16, 8)), "down": jnp.zeros((1, 4, 16))}},
        x, top_k=2, first_expert=0, compute_dtype=jnp.float32, bias=b)[3]))
    before = spread(bias)
    for _ in range(200):
        counts = moe_lib.route(x, router, top_k=2, bias=bias)[1]
        bias = moe_lib.balanced_bias(bias, jnp.sum(counts.reshape(-1, 1) == jnp.arange(8), 0), 1e-2)
    assert spread(bias) < before and spread(bias) <= 64 * 2 / 8 + 4


def test_counts_are_summed_over_the_data_axis():
    """On a mesh of two, each replica with its own tokens: the bias moves by
    the counts of both (as synchronised BatchNorm sums its statistics), alike
    on both, and not by either's own."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    counts = jnp.asarray([[6.0, 0.0, 2.0, 0.0], [0.0, 2.0, 2.0, 4.0]])  # summed: 6 2 4 4, mean 4
    bias = jnp.zeros(4)
    f = jax.shard_map(
        lambda c: moe_lib.balanced_bias(bias, c[0], 1e-3, "data")[None], mesh=mesh, in_specs=P("data"), out_specs=P("data"),
    )
    both = jax.jit(f)(counts)
    np.testing.assert_allclose(both[0], 1e-3 * np.asarray([-1, 1, 0, 0.0]), atol=1e-9)
    np.testing.assert_array_equal(both[0], both[1])
    alone = moe_lib.balanced_bias(bias, counts[0], 1e-3)
    assert not np.allclose(alone, both[0])


def test_the_model_hands_the_axis_to_the_rule(system, tiny):
    """Through the model: two replicas of one sequence each under
    ``shard_map`` return the state that one replica of both sequences
    returns."""
    model = _model(system, tiny, expert_bias_std=0.5)
    params, state = model.init(jax.random.key(3), None)
    tokens = jnp.asarray(system.make_batches(tiny, 5, 1, 2)[0][0])
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    whole = model.apply(params, state, tokens, Context(train=True))[1]
    split = jax.jit(jax.shard_map(
        lambda t: model.apply(params, state, t, Context(train=True, axis_name="data"))[1],
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False,
    ))(tokens)
    moved = [np.asarray(w["expert_bias"] - s["expert_bias"]) for w, s in zip(whole[1:], state[1:])]
    assert whole[0] == () and all(np.all(np.isin(np.round(m / 1e-3), (-1, 0, 1))) and np.any(m) for m in moved)
    for a, b in zip(whole[1:], split[1:]):
        np.testing.assert_array_equal(a["expert_bias"], b["expert_bias"])
    alone = model.apply(params, state, tokens[:1], Context(train=True))[1]
    assert any(not np.array_equal(a["expert_bias"], b["expert_bias"]) for a, b in zip(alone[1:], whole[1:]))
    # evaluation chooses by the bias and leaves it as it was
    logits, same = model.apply(params, state, tokens, Context(train=False))
    assert jax.tree.all(jax.tree.map(lambda a, b: np.array_equal(a, b), same, state))
    zeroed = jax.tree.map(jnp.zeros_like, state)
    assert not np.allclose(logits, model.apply(params, zeroed, tokens, Context(train=False))[0])


# -- heads of 64 in the fused lowering (nn/sequence.py) ------------------------------

@pytest.mark.parametrize("backend,head_dim,t,per_replica,want", [
    ("tpu", 64, 32768, True, "fused"),      # the published widths at the cell's length
    ("tpu", 64, 1536, True, "fused"),       # blocks of 512
    ("tpu", 64, 32768, False, "blockwise"),  # mode="auto"
    ("cpu", 64, 32768, True, "blockwise"),
    ("tpu", 64, 32768 + 256, True, "blockwise"),  # ragged lengths
    ("tpu", 32, 32768, True, "blockwise"),  # a quarter of a register: not the library's
    ("tpu", 96, 32768, True, "blockwise"),
    ("tpu", 16, 44, True, "blockwise"),     # the tiny preset
])
def test_the_lowering_rule_for_heads_of_64(backend, head_dim, t, per_replica, want):
    assert seq.attention_lowering(backend, head_dim, t, per_replica=per_replica) == want


@pytest.mark.parametrize("t,head_dim,window,heads,sequences,groups", [
    (32768, 64, None, (32, 8), 1, 8),     # this cell: 32 partial dq of 32,768 rows a head, one key/value head a call
    (32768, 128, None, (32, 4), 1, 0),    # 8 query heads a key/value head: one group is past the bound a call
    (32768, 64, None, (32, 8), 2, 0),     # two sequences a call, likewise
    (32768, 64, None, (8, 8), 1, 2),      # ungrouped heads: 4 a call
    (32768, 256, None, (16, 2), 1, 0),    # a row of 256 fills two lane registers
    (65536, 64, None, (32, 8), 1, 0),     # the library's two kernels
    (16384, 128, None, (32, 4), 1, 1),    # the window-and-full cell's full layer: as it was
    (16384, 128, 1024, (32, 4), 1, 1),    # and its band
    (16384, 128, None, (16, 16), 1, 1),   # the looped cell
    (16384, 256, None, (20, 20), 1, 1),   # the latent-attention cell: 20 ungrouped heads of 192 + 64, one call
    (16384, 64, None, (32, 8), 1, 1),
    (8192, 256, None, (16, 2), 2, 1),     # the DeltaNet hybrid's cell
    (32768, 128, 1024, (32, 4), 1, 1),    # a band's calls see 2,048 keys whatever the sequence
    (17408, 64, None, (32, 8), 1, 4),     # the first length past the bound a head: two key/value heads a call
    (17408, 64, None, (6, 3), 1, 1),      # past it and few heads: one call still
    (17408, 64, None, (36, 3), 1, 3),     # groups divide the key/value heads: 3, not 2
])
def test_the_backward_pass_is_one_kernel_up_to_its_partials_bound(t, head_dim, window, heads, sequences, groups):
    """``groups``: the calls the one kernel's heads go in, 0 where the
    backward pass is the library's two kernels."""
    shapes = dict(hq=heads[0], hkv=heads[1], sequences=sequences)
    assert seq.fused_attention_head_groups(t, head_dim, window, **shapes) == groups
    blocks = seq.fused_attention_blocks(t, head_dim, window, **shapes)
    assert blocks.use_fused_bwd_kernel is (groups > 0) and blocks.has_backward_blocks
    assert blocks.block_q == blocks.block_kv == blocks.block_q_dkv == blocks.block_kv_dkv == 1024
    if not groups:
        assert blocks.block_q_dq == blocks.block_kv_dq == 1024


def test_the_bound_a_call_is_one_key_value_head_of_this_cell():
    """Stated in rows, beside the bound a head: what one key/value head's
    four query heads write at 32,768 tokens, and less than two write."""
    a_head = 4 * (32768 // 1024) * 32768
    assert a_head <= seq._MOST_DQ_PARTIALS_A_CALL < 2 * a_head
    assert seq._MOST_DQ_PARTIALS == 16 * 16384  # the bound a head, as it was


@pytest.mark.parametrize("sequences,t,heads,head_dim,window,loops", [
    (2, 8192, (16, 2), 256, None, 0),     # the DeltaNet hybrid's cell
    (1, 16384, (32, 4), 128, None, 0),    # the window-and-full cell's full layer
    (1, 16384, (32, 4), 128, 1024, 0),    # and its band
    (1, 16384, (16, 16), 128, None, 0),   # the looped cell
    (1, 16384, (20, 20), 256, None, 0),   # the latent-attention cell
    (1, 32768, (32, 8), 64, None, 1),     # this cell: the groups' loop
    (1, 65536, (8, 2), 64, None, 0),      # the two kernels take every head at once
])
def test_only_heads_that_go_in_groups_are_walked_by_a_loop(sequences, t, heads, head_dim, window, loops):
    """Traced, not run: within the bound a head the kernel is called once
    over all heads, the program the other token cells had before there were
    groups; past it the one call stands in a loop over the groups."""
    q, k = (jax.ShapeDtypeStruct((sequences, t, h, head_dim), jnp.bfloat16) for h in heads)
    jaxpr = jax.make_jaxpr(functools.partial(
        seq._fused_causal_attention, scale=head_dim ** -0.5, compute_dtype=jnp.bfloat16, window=window
    ))(q, k, k)
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns].count("scan") == loops


# kernel-eligible and small: three blocks of 512, heads of 64, two query heads a key/value head
_T, _D = 1536, 64


def _qkvw(hq=4, hkv=2):
    keys = jax.random.split(jax.random.key(11), 4)
    shapes = ((1, _T, hq, _D), (1, _T, hkv, _D), (1, _T, hkv, _D), (1, _T, hq, _D))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _value_and_grads(f, q, k, v, w):
    """``(out, (dq, dk, dv))`` of ``sum(f(q, k, v) * w)``."""
    return f(q, k, v), jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(q, k, v)


def _fused(qkvw, **patched):
    """Through the kernel in interpret mode, with the names of ``nn.sequence``
    in ``patched`` set while it is traced."""
    kw = dict(scale=_D ** -0.5, compute_dtype=jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        for name, value in patched.items():
            patch.setattr(seq, name, value)
        return _value_and_grads(lambda q, k, v: seq._fused_causal_attention(q, k, v, interpret=True, **kw), *qkvw)


def _blockwise(qkvw):
    kw = dict(scale=_D ** -0.5, compute_dtype=jnp.float32)
    return _value_and_grads(lambda q, k, v: seq._blockwise_causal_attention(q, k, v, q_block=512, **kw), *qkvw)


def _two_kernels(t, head_dim, window=None, *, real=seq.fused_attention_blocks, **shapes):
    blocks = real(t, head_dim, window, **shapes)
    return type(blocks)(**{
        **{f: getattr(blocks, f) for f in blocks.__dataclass_fields__},
        "use_fused_bwd_kernel": False, "block_q_dq": blocks.block_q, "block_kv_dq": blocks.block_kv,
    })


def _a_kv_head_a_call(hq, hkv):
    """The bounds at which ``_T`` tokens are past the bound a head and one
    key/value head's query heads fill the bound a call."""
    return dict(_MOST_DQ_PARTIALS=0, _MOST_DQ_PARTIALS_A_CALL=(hq // hkv) * (_T // 512) * _T)


@pytest.fixture(scope="module", params=["one_kernel", "two_kernels", "grouped"])
def narrow(request):
    """Output and gradients at heads of 64 of the kernel in interpret mode,
    with the backward pass it has at this length (one kernel, every head in
    one call), the one it has past the partials' bound a head (one kernel, a
    key/value head's group a call: the bounds patched so that two groups
    form) and the one it has where a group is past the bound a call (the
    library's two), and of the blockwise path."""
    qkvw = _qkvw()
    patched = {
        "one_kernel": {}, "two_kernels": dict(fused_attention_blocks=_two_kernels), "grouped": _a_kv_head_a_call(4, 2),
    }[request.param]
    return _fused(qkvw, **patched), _blockwise(qkvw)


def _pick(got, what):
    return np.asarray(got[0] if what == "out" else got[1]["qkv".index(what[1])])


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_heads_of_64_through_the_kernel_agree_with_the_blockwise_path(narrow, what):
    fused, blockwise = narrow
    a, b = _pick(fused, what), _pick(blockwise, what)
    assert a.shape == b.shape and a.shape[-1] == 64
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * np.abs(b).max())


@pytest.fixture(scope="module")
def three_a_group():
    """Six query heads over two key/value heads, three a group: a cut that is
    not at a key/value head gives a query head another head's keys. All heads
    in one call, a key/value head's group a call, and the blockwise path."""
    qkvw = _qkvw(6, 2)
    with pytest.MonkeyPatch.context() as patch:
        for name, value in _a_kv_head_a_call(6, 2).items():
            patch.setattr(seq, name, value)
        assert seq.fused_attention_head_groups(_T, _D, hq=6, hkv=2) == 2
        assert seq.fused_attention_head_groups(_T, _D, hq=6, hkv=2, sequences=2) == 0
    assert seq.fused_attention_head_groups(_T, _D, hq=6, hkv=2) == 1
    return _fused(qkvw), _fused(qkvw, **_a_kv_head_a_call(6, 2)), _blockwise(qkvw)


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_a_group_is_a_key_value_head_and_its_query_heads(three_a_group, what):
    """Grouped, the kernel computes a head as it does with every head in one
    call (the same blocks in the same order: to rounding, and the output to
    the bit), and both agree with the blockwise path."""
    one_call, grouped, blockwise = (_pick(got, what) for got in three_a_group)
    assert grouped.shape == blockwise.shape == (1, _T, 6 if what in ("out", "dq") else 2, 64)
    np.testing.assert_allclose(grouped, blockwise, rtol=0, atol=2e-5 * np.abs(blockwise).max())
    np.testing.assert_allclose(grouped, one_call, rtol=0, atol=1e-6 * np.abs(one_call).max())
    if what == "out":
        np.testing.assert_array_equal(grouped, one_call)


# -- the model -------------------------------------------------------------------------

def test_registry_builds_the_published_cut_and_the_tiny_preset(system, published, tiny):
    """The published cut, as shapes only: its registry preset is the
    configuration file's numbers, its parameters the file's count, and every
    published width is unchanged in the file."""
    model = load_model("lfm2_ep4", published["vocab_size"])
    from_file = _model(system, published)
    ours = {"compute_dtype": None}  # the file's own choice (`assumed`)
    assert {**vars(from_file), **ours} == {**vars(model), **ours}
    assert model.layer_types == ("ShortConv", "FullAttention", "ShortConv", "ShortConv", "ShortConv")
    shapes, state = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    count = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert count == published["parameters"] == 507_820_160
    assert "head" not in shapes and shapes["embed"]["weight"].shape == (16384, 2048)  # tied
    dense, attention, conv = shapes["layers"][0], shapes["layers"][1], shapes["layers"][2]
    assert set(dense) == {"input_norm", "mixer", "post_norm", "mlp"} and set(conv) == {"input_norm", "mixer", "post_norm", "moe"}
    assert dense["mlp"]["gate_up"].shape == (2048, 14336) and dense["mlp"]["down"].shape == (7168, 2048)
    assert conv["mixer"]["in_proj"].shape == (2048, 6144) and conv["mixer"]["conv"].shape == (3, 2048)
    assert conv["mixer"]["out_proj"].shape == (2048, 2048)
    assert attention["mixer"]["q_proj"].shape == (2048, 2048) and attention["mixer"]["k_proj"].shape == (2048, 512)
    assert attention["mixer"]["q_norm"].shape == (64,)
    assert set(conv["moe"]) == {"router", "experts"} and conv["moe"]["router"].shape == (2048, 32)
    assert conv["moe"]["experts"]["gate_up"].shape == (8, 2048, 3584) and conv["moe"]["experts"]["down"].shape == (8, 1792, 2048)
    assert jax.tree.structure(attention["moe"]) == jax.tree.structure(conv["moe"])
    assert [s if s == () else s["expert_bias"].shape for s in state] == [(), (32,), (32,), (32,), (32,)]
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    }
    assert {k: published[k] for k in catalog} == catalog
    types = published["layer_types"]
    assert len(types) == 24 and types.count("full_attention") == 6 and types[:2] == ["conv", "conv"]
    assert types[1:6] == ["conv", "full_attention", "conv", "conv", "conv"] and published["deployment"]["first_layer"] == 1
    assert published["reduced"] == ["num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32, "vocab_size": 65536}
    assert published["deployment"]["experts_published"] == 32 and published["num_experts"] == 8
    tiny_model = load_model("lfm2_tiny", VOCAB)
    assert {**vars(_model(system, tiny)), **ours} == {**vars(tiny_model), **ours}
    assert tiny_model.hidden_size <= 64 and (tiny_model.n_experts, tiny_model.experts_held, tiny_model.top_k) == (8, 2, 2)
    assert tiny_model.layer_types == model.layer_types and tiny_model.dense_layers == model.dense_layers == 1
    assert tiny_model.mlp_chunk < tiny["tokens"]["seq_len"]  # the dense chunks' loop runs


@pytest.mark.parametrize("bad", [
    dict(layer_types=("ShortConv",) * 4),        # a type a layer
    dict(dense_layers=1, dense_width=0),         # a dense feed-forward has a width
    dict(first_expert=7),                        # the held experts are among the router's
])
def test_the_presets_arguments_are_checked(bad):
    with pytest.raises(ValueError):
        load_model("lfm2_tiny", VOCAB, **bad)


def test_the_other_presets_build_what_they_built():
    """One trunk: the other families' registry entries keep their untied
    head, their sparse feed-forward in every layer, their softmax router and
    no state."""
    for name in ("qwen3_next_tiny", "mellum2_tiny"):
        model = load_model(name, VOCAB)
        params, state = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        assert state == () and "head" in params and all("moe" in p and "mlp" not in p for p in params["layers"])
        out, new_state = model.apply(params, state, jnp.zeros((1, 16), jnp.int32), Context(train=True))
        assert new_state == () and set(out.counters) == set(moe_lib.COUNTERS)


def _loss_pair(reference, system, tiny, model, params, state, tokens, targets):
    def ours(p):
        return nn.CrossEntropyLoss()(model.apply(p, state, tokens, Context(train=True))[0], targets)

    def theirs(p):
        return reference.loss_and_counts(tiny, p, state, tokens, targets)[0]

    return ours, theirs


def test_the_whole_model_matches_the_reference(reference, system, tiny):
    """Loss and every parameter's gradient on the seeded stream at 44 tokens,
    and the state after the step: every bias moved by the rule from the
    reference's counts."""
    model = _model(system, tiny)
    params, state = _variables(model)
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))
    ours, theirs = _loss_pair(reference, system, tiny, model, params, state, tokens, targets)
    (loss, grads), (ref_loss, ref_grads) = jax.value_and_grad(ours)(params), jax.value_and_grad(theirs)(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    _close(grads, ref_grads, 5e-4)
    out, new_state = model.apply(params, state, tokens, Context(train=True))
    counts = reference.loss_and_counts(tiny, params, state, tokens, targets)[1]
    assert counts[0] is None and new_state[0] == ()
    for new, old, c in zip(new_state[1:], state[1:], counts[1:]):
        np.testing.assert_array_equal(new["expert_bias"], reference.balanced(tiny, old["expert_bias"], c))
    assert float(out.counters["moe_router_tokens_max"]) == sum(float(jnp.max(c)) for c in counts[1:])
    logits, _ = model.apply(params, state, tokens, Context(train=False))
    assert logits.shape == (2, tiny["tokens"]["seq_len"], VOCAB)


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(reference, system, tiny):
    """One leaf, two uses: with the head untied into a leaf of its own that
    holds the embedding's transpose, the tied model's gradient is the
    embedding's plus the head's transposed; and it is the reference's."""
    tied, untied = _model(system, tiny), _model(system, tiny, tied_head=False)
    params, state = _variables(tied)
    assert "head" not in params and "head" in untied.init(jax.random.key(3), None)[0]
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))
    loss_of = lambda model: lambda p: nn.CrossEntropyLoss()(
        model.apply(p, state, tokens, Context(train=True))[0], targets
    )
    g_tied = jax.grad(loss_of(tied))(params)["embed"]["weight"]
    g = jax.grad(loss_of(untied))({**params, "head": {"weight": params["embed"]["weight"].T}})
    assert np.any(np.asarray(g["embed"]["weight"])) and np.any(np.asarray(g["head"]["weight"]))
    np.testing.assert_allclose(g_tied, g["embed"]["weight"] + g["head"]["weight"].T, rtol=1e-5, atol=1e-7)
    _, theirs = _loss_pair(reference, system, tiny, tied, params, state, tokens, targets)
    _close(g_tied, jax.grad(theirs)(params)["embed"]["weight"], 5e-4)


def test_the_references_blocks_change_no_arithmetic(reference, system, tiny, monkeypatch):
    """The reference takes attention, the dense feed-forward and the loss in
    blocks for memory only: with blocks short enough that the rolled loop
    over whole blocks and the call for what is left both run (44 tokens: two
    of 16 and one of 12), loss and gradients are those of one block over
    everything."""
    model = _model(system, tiny)
    params, state = _variables(model)
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))
    objective = lambda p: reference.loss_and_counts(tiny, p, state, tokens, targets)[0]
    whole = jax.value_and_grad(objective)(params)
    for name in ("_QUERY_BLOCK", "_MLP_BLOCK", "_LOSS_BLOCK"):
        monkeypatch.setattr(reference, name, 16)
    blocked = jax.value_and_grad(objective)(params)
    _close(blocked[0], whole[0], 1e-6)
    _close(blocked[1], whole[1], 1e-4)


def test_the_step_carries_the_layer_types_scopes(system, tiny):
    """``<i>_ShortConv`` with ``in_proj``, ``conv`` and ``out_proj`` inside,
    ``<i>_FullAttention`` with ``qkv``, ``attention`` and ``o_proj``, the
    leading layer's ``mlp`` and the others' ``moe`` with its parts, forward
    and backward: what the benchmark's readers sum."""
    model = _model(system, tiny)
    tokens = jnp.zeros((1, 44), jnp.int32)
    params, state = model.init(jax.random.key(0), tokens)

    def loss(p):
        with profiling.scope(profiling.FORWARD):  # as the step opens it
            out = model.apply(p, state, tokens, Context(train=True))[0]
        return nn.CrossEntropyLoss()(out, tokens)

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    control = cells.load_module("layer_metrics", "_token_layers")._CONTROL
    seen = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        phase, path = scope_reduce.attribute(name + ":")
        if path is not None:
            seen.add((phase, "/".join(c for c in path.split("/") if c not in control)))
    operators = {"ShortConv": ("in_proj", "conv", "out_proj"), "FullAttention": ("qkv", "attention", "o_proj")}
    for i, kind in enumerate(model.layer_types):
        layer = f"{i}_{kind}"
        feed_forward = ("mlp",) if i < model.dense_layers else ("moe/router", "moe/dispatch", "moe/combine")
        for part in operators[kind] + feed_forward:
            for phase in ("forward", "backward"):
                assert (phase, f"{layer}/{part}") in seen, (phase, layer, part)
        assert ("recompute", f"{layer}/{operators[kind][1]}") in seen
        if i >= model.dense_layers:
            assert ("forward", f"{layer}/moe/experts") in seen and ("backward", f"{layer}/moe") in seen
    assert ("recompute", "0_ShortConv/mlp") in seen and not any(p.startswith("0_ShortConv/moe") for _, p in seen)
    layers = {path.split("/")[0] for _, path in seen} - {scope_reduce.NO_LAYER}
    assert layers == {f"{i}_{kind}" for i, kind in enumerate(model.layer_types)}
