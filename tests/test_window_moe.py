"""Window and full attention in one model (models/hybrid_moe.py's
``SlidingAttention`` and ``FullAttention`` layers, nn/sequence.py's window and
YaRN table, nn/moe.py without a shared expert) against its plain reference
(benchmark/reference/mellum2_12b_a2_5b_ep4.py) at the tiny preset on the CPU:
seeded random weights, float32 unless a test says otherwise. Whole training
steps are in tests/test_window_moe_training.py."""

import contextlib
import math
import re
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, scope_reduce
from tpuddp import nn
from tpuddp.models import MELLUM2_EP4, load_model
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context
from tpuddp.observability import profiling

CONFIG_NAME = "mellum2_12b_a2_5b_ep4"
WORKLOAD = "mellum2_ep4_t16k_fused"
VOCAB = 96


@pytest.fixture(scope="module")
def reference():
    return cells.load_module("reference", CONFIG_NAME)


@pytest.fixture(scope="module")
def system():
    return cells.load_module("systems", "token_window_moe_lm")


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
    """Norm weights off their initial 1, projections large enough that the
    softmax and the router are away from their flat middle: a mistake in any
    of them then shows."""
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


# -- the window (nn/sequence.py) ---------------------------------------------------

def _dense_masked(q, k, v, *, scale, window):
    """Every score of the ``T x T`` block, those a query does not see at
    ``-inf``: the mask written down, nothing else."""
    hq, hkv = q.shape[2], k.shape[2]
    k, v = jnp.repeat(k, hq // hkv, axis=2), jnp.repeat(v, hq // hkv, axis=2)
    behind = jnp.arange(q.shape[1])[:, None] - jnp.arange(q.shape[1])[None, :]  # i - j
    visible = (behind >= 0) & (behind < (q.shape[1] if window is None else window))
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k, precision="highest") * scale
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v, precision="highest")


def _qkvw(t, hq=4, hkv=2, d=8, batch=2, seed=11):
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = ((batch, t, hq, d), (batch, t, hkv, d), (batch, t, hkv, d), (batch, t, hq, d))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _value_and_grads(f, q, k, v, w):
    return f(q, k, v), jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("t,q_block,window", [
    (100, 16, 37),   # the band's loop: six whole blocks and what is left, no multiple of anything
    (77, 32, 45),    # the band as wide as it may be before the groups take over
    (64, 16, 16),    # a window of exactly one block
    (48, 16, 1),     # a query sees its own key alone
    (44, 16, 20),    # the tiny preset's
    (40, 16, 30),    # a window below the sequence whose band does not fit under it: the groups, masked
    (40, 64, 7),     # one ragged block
])
def test_blockwise_windowed_attention_is_the_dense_mask(t, q_block, window):
    q, k, v, w = _qkvw(t)
    kw = dict(scale=8 ** -0.5, compute_dtype=jnp.float32)
    ours = lambda q, k, v: seq.causal_attention(q, k, v, q_block=q_block, window=window, **kw)
    theirs = lambda q, k, v: _dense_masked(q, k, v, scale=8 ** -0.5, window=window)
    (out, grads), (ref_out, ref_grads) = _value_and_grads(ours, q, k, v, w), _value_and_grads(theirs, q, k, v, w)
    np.testing.assert_allclose(out, ref_out, rtol=2e-5, atol=2e-6)
    _close(grads, ref_grads, 2e-5)


# kernel-eligible and small: three blocks of 512. A window of 400 goes in
# three chunks of 512, the first alone and two against the chunk before; one
# of 1100 in one chunk, the whole sequence. (One of 700 would go in chunks of
# 1024, which do not divide the sequence: the rule sends it blockwise.)
_T, _D = 1536, 128


def _lowerings(window):
    kw = dict(scale=_D ** -0.5, compute_dtype=jnp.float32)
    return {
        "fused": lambda q, k, v: seq._fused_causal_attention(q, k, v, window=window, interpret=True, **kw),
        "blockwise": lambda q, k, v: seq._blockwise_causal_attention(q, k, v, q_block=512, window=window, **kw),
        "dense": lambda q, k, v: _dense_masked(q, k, v, scale=_D ** -0.5, window=window),
    }


@pytest.fixture(scope="module", params=[400, 1100])
def windowed(request):
    """Output and gradients of the three ways to the windowed result."""
    q, k, v, w = _qkvw(_T, d=_D, batch=2)
    return {name: _value_and_grads(f, q, k, v, w) for name, f in _lowerings(request.param).items()}


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("lowering", ["fused", "blockwise"])
def test_windowed_lowerings_agree_with_the_dense_mask(windowed, lowering, what):
    """The kernel in interpret mode (its band a ``LocalMask``, a chunk at a
    time) and the blockwise path (a block's keys sliced out) against the mask
    written down, forward and every gradient."""
    pick = lambda got: got[0] if what == "out" else got[1]["qkv".index(what[1])]
    a, b = np.asarray(pick(windowed[lowering])), np.asarray(pick(windowed["dense"]))
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * np.abs(b).max())


def test_the_band_goes_in_chunks_where_they_divide_the_sequence(monkeypatch):
    """At the cell's shapes: chunks of 1,024 queries, the first against its
    own 1,024 keys and fifteen against 2,048, none against all 16,384."""
    seen = []

    def attend(mask, q, k, v):
        seen.append((mask.shape, q.shape, k.shape))
        return jnp.zeros(q.shape, q.dtype)

    monkeypatch.setattr(seq._splash()[0], "make_splash_mha", lambda mask, **kw: (
        lambda q, k, v: attend(mask, q, k, v)))
    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    out = jax.eval_shape(
        lambda q, k, v: seq._fused_causal_attention(q, k, v, scale=1.0, compute_dtype=jnp.bfloat16, window=1024),
        q, k, k,
    )
    assert out.shape == q.shape and out.dtype == q.dtype
    assert seen == [
        ((32, 1024, 1024), (32, 1024, 128), (4, 1024, 128)), ((32, 1024, 2048), (32, 1024, 128), (4, 2048, 128)),
    ]


@pytest.mark.parametrize("lowering", ["fused", "blockwise"])
def test_a_window_that_holds_the_sequence_is_full_causal_attention(lowering):
    """``causal_attention`` drops such a window before it picks a lowering;
    each lowering, handed it all the same, gives the full causal result."""
    q, k, v, _ = _qkvw(_T, d=_D, batch=1)
    full, wide = _lowerings(None)[lowering](q, k, v), _lowerings(_T)[lowering](q, k, v)
    np.testing.assert_allclose(wide, full, rtol=0, atol=1e-6)
    kw = dict(scale=_D ** -0.5, compute_dtype=jnp.float32, q_block=512)
    for window in (_T, _T + 5):
        np.testing.assert_array_equal(
            seq.causal_attention(q, k, v, window=window, **kw), seq.causal_attention(q, k, v, **kw)
        )


@pytest.mark.parametrize("lowering", ["fused", "blockwise"])
def test_a_query_sees_its_window_and_nothing_older_to_the_bit(lowering):
    """Other keys and values more than ``window - 1`` before a query, or
    after it, leave its output as it was, bit for bit; the oldest key inside
    the window does not."""
    window, at = 400, 1100  # the third of the fused lowering's three chunks
    q, k, v, _ = (a.astype(jnp.bfloat16) for a in _qkvw(_T, d=_D, batch=1))
    kw = dict(scale=_D ** -0.5, compute_dtype=jnp.bfloat16, window=window)
    f = jax.jit({
        "fused": lambda q, k, v: seq._fused_causal_attention(q, k, v, interpret=True, **kw),
        "blockwise": lambda q, k, v: seq._blockwise_causal_attention(q, k, v, q_block=512, **kw),
    }[lowering])
    oldest = at - window + 1  # the first key the query at ``at`` sees
    outside = jnp.arange(_T)[None, :, None, None]
    outside = (outside < oldest) | (outside > at)
    before = f(q, k, v)
    after = f(q, jnp.where(outside, -2.0 * k, k), jnp.where(outside, -2.0 * v, v))
    np.testing.assert_array_equal(np.asarray(before[:, at]), np.asarray(after[:, at]))
    inside = jnp.arange(_T)[None, :, None, None] == oldest
    moved = f(q, k, jnp.where(inside, v + 4.0, v))
    assert not np.array_equal(np.asarray(before[:, at]), np.asarray(moved[:, at]))
    assert np.array_equal(np.asarray(before[:, at + 1]), np.asarray(moved[:, at + 1]))  # one query on, it is out


@pytest.mark.parametrize("backend,head_dim,t,per_replica,window,want", [
    ("tpu", 128, 16384, True, 1024, "fused"),      # the published widths at the cell's length: chunks of 1024
    ("tpu", 128, 16384, True, 1025, "fused"),      # chunks of 2048
    ("tpu", 128, 16384, True, 3000, "blockwise"),  # chunks of 3072 do not divide the sequence
    ("tpu", 128, 1536, True, 400, "fused"),        # chunks of 512
    ("tpu", 128, 1536, True, 700, "blockwise"),    # chunks of 1024 do not
    ("tpu", 128, 16384, False, 1024, "blockwise"),  # mode="auto"
    ("cpu", 128, 16384, True, 1024, "blockwise"),
    ("tpu", 16, 44, True, 20, "blockwise"),        # the tiny preset
])
def test_the_lowering_rule_with_a_window(backend, head_dim, t, per_replica, window, want):
    assert seq.attention_lowering(backend, head_dim, t, per_replica=per_replica, window=window) == want


# -- the rotary tables (nn/sequence.py) ---------------------------------------------

def test_yarn_table_is_the_closed_form_at_the_published_parameters(reference, published):
    """``low`` 18 and ``high`` 35 of 64 frequencies: below the ramp the plain
    ``theta^(-2m/128)``, above it that over 16, linear between; 1.2772... on
    cos and sin. The reference's table, written independently, is the same."""
    yarn, theta, d = MELLUM2_EP4["yarn"], MELLUM2_EP4["rope_theta"], MELLUM2_EP4["head_dim"]
    assert published["rope_parameters"]["full_attention"] == {"rope_type": "yarn", "rope_theta": theta, **yarn}
    assert seq.yarn_ramp(d, theta, yarn) == (18, 35)
    inv_freq, factor = seq.rotary_frequencies(d, theta, yarn)
    plain = np.asarray(theta, np.float64) ** (-2.0 * np.arange(64) / d)
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    np.testing.assert_allclose(inv_freq[:19], plain[:19], rtol=1e-5)  # m <= low: extrapolated, untouched
    np.testing.assert_allclose(inv_freq[35:], plain[35:] / 16, rtol=1e-5)  # m >= high: interpolated
    m = 27  # inside the ramp
    np.testing.assert_allclose(inv_freq[m], plain[m] * (1 - 9 / 17) + plain[m] / 16 * 9 / 17, rtol=1e-5)
    assert np.all(np.diff(np.asarray(inv_freq)) < 0)  # still falling with the dimension
    ref_freq, ref_factor = reference.rotary_table(d, published["rope_parameters"]["full_attention"])
    np.testing.assert_array_equal(np.asarray(inv_freq), np.asarray(ref_freq))
    assert ref_factor == factor
    plain_freq, one = seq.rotary_frequencies(d, theta)
    np.testing.assert_allclose(plain_freq, plain, rtol=1e-5)
    assert one == 1.0


def test_yarn_puts_its_factor_on_cos_and_sin():
    """Position 0 turns nothing: the rotated vector is the factor times the
    vector. Elsewhere its length is the factor times the vector's, and the
    angle between two positions depends on their distance alone."""
    yarn = MELLUM2_EP4["yarn"]
    x = jax.random.normal(jax.random.key(0), (1, 9000, 2, 128), jnp.float32)
    rot = seq.rotary(x, jnp.arange(9000), rotary_dim=128, theta=5e5, yarn=yarn)
    a = yarn["attention_factor"]
    np.testing.assert_allclose(rot[:, 0], a * x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(rot, axis=-1), a * jnp.linalg.norm(x, axis=-1), rtol=1e-4
    )
    same = jnp.broadcast_to(x[:, :1], x.shape)  # one vector at every position
    turned = seq.rotary(same, jnp.arange(9000), rotary_dim=128, theta=5e5, yarn=yarn)
    dots = lambda i, j: float(jnp.sum(turned[0, i, 0] * turned[0, j, 0]))
    assert dots(10, 110) == pytest.approx(dots(8500, 8600), rel=1e-3)
    plain = seq.rotary(same, jnp.arange(9000), rotary_dim=128, theta=5e5)
    assert abs(float(jnp.sum(plain[0, 10, 0] * plain[0, 8510, 0])) - dots(10, 8510) / a ** 2) > 1e-2  # another table


# -- the mixers and the expert layer --------------------------------------------------

def _layer_params(model, index):
    params, _ = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return _perturbed(params)["layers"][index]


@pytest.mark.parametrize("index,layer_type,kind", [
    (0, "sliding_attention", "SlidingAttention"), (3, "full_attention", "FullAttention"),
])
def test_attention_mixers_match_the_reference(reference, system, tiny, index, layer_type, kind):
    """The per-head norm, rotary on the whole head from the type's own table,
    two query heads a key/value head and no gate; 50 positions, past the
    window of 20 and the YaRN table's original 32, queries in blocks of 16."""
    model = _model(system, tiny)
    assert model.layer_kind(index) == kind
    p = _layer_params(model, index)["mixer"]
    x = _hidden(np.random.RandomState(2), tiny, 2, 50)
    ours = lambda p, x: model._attention(p, x, kind=kind)
    theirs = lambda p, x: reference.attention_mixer(tiny, p, x, layer_type)
    np.testing.assert_allclose(ours(p, x), theirs(p, x), rtol=2e-4, atol=2e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    _close(jax.grad(loss(ours), argnums=(0, 1))(p, x), jax.grad(loss(theirs), argnums=(0, 1))(p, x), 5e-4)
    other = "full_attention" if layer_type == "sliding_attention" else "sliding_attention"
    assert float(jnp.max(jnp.abs(ours(p, x) - reference.attention_mixer(tiny, p, x, other)))) > 1e-2


def _moe_ours(model, p, x, **kw):
    y, aux, counters, _ = moe_lib.expert_share_moe(
        p, x.reshape(-1, x.shape[-1]), top_k=model.top_k, first_expert=model.first_expert,
        compute_dtype=jnp.float32, **kw,
    )
    return y.reshape(x.shape), aux, counters


def test_expert_layer_without_a_shared_expert_matches_the_reference(reference, system, tiny):
    """The tree says whether a layer has a shared expert: this family's has
    no ``shared`` leaf, and nothing under a ``shared_expert`` scope runs."""
    model = _model(system, tiny)
    p = _layer_params(model, 1)["moe"]
    assert set(p) == {"router", "experts"}
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
    text = jax.jit(lambda p, x: _moe_ours(model, p, x)[0]).lower(p, x).as_text(debug_info=True)
    assert "router/" in text and "shared_expert/" not in text


def test_the_shares_add_up_to_the_uncut_layer(reference, system, tiny):
    """Four shares of 2 of the 8 experts each: their routed parts (there is
    nothing that every chip computes alike) are the uncut reference's layer
    output, and every assignment is some share's."""
    n_all, held = tiny["deployment"]["experts_published"], tiny["num_experts"]
    uncut = {**tiny, "num_experts": n_all}
    model = _model(system, uncut)
    p = _layer_params(model, 0)["moe"]
    x = _hidden(np.random.RandomState(4), tiny, 2, 33)
    flat = x.reshape(-1, x.shape[-1])
    whole, _ = reference.moe(uncut, p, x)
    total, seen = 0.0, 0.0
    for share in range(n_all // held):
        mine = {**p, "experts": jax.tree_util.tree_map(lambda w: w[share * held:(share + 1) * held], p["experts"])}
        y, _, counters, _ = moe_lib.expert_share_moe(
            mine, flat, top_k=model.top_k, first_expert=share * held, compute_dtype=jnp.float32
        )
        ref_y, _ = reference.moe({**tiny, "deployment": {**tiny["deployment"], "first_expert": share * held}}, mine, x)
        np.testing.assert_allclose(y, ref_y.reshape(flat.shape), rtol=2e-4, atol=2e-5)
        total = total + y
        seen += float(counters["moe_expert_tokens_held"])
    np.testing.assert_allclose(total, whole.reshape(flat.shape), rtol=2e-4, atol=2e-5)
    assert seen == flat.shape[0] * model.top_k


def test_rows_that_are_no_experts_never_reach_the_result(reference, system, tiny, monkeypatch):
    """A round has more rows than the routing fills, and a grouped product
    need not write the rows past its last group: the TPU's leaves them as the
    buffer was, in the product and in the cotangent that its transpose makes
    (PERF.md, PR 35: that garbage, scattered into the layer's input gradient,
    grew from layer to layer). With such rows poisoned in both, result and
    gradients are the reference's all the same."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        out = real(lhs, rhs, sizes, preferred_element_type=jnp.float32)
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None], out, jnp.nan)

    def fwd(lhs, rhs, sizes):
        return unwritten(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(saved, d_out):
        lhs, rhs, sizes = saved
        d_lhs, d_rhs = jax.vjp(lambda a, b: real(a, b, sizes, preferred_element_type=jnp.float32), lhs, rhs)[1](d_out)
        return jnp.where((jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None], d_lhs, jnp.nan), d_rhs, None

    unwritten.defvjp(fwd, bwd)
    monkeypatch.setattr(moe_lib.jax.lax, "ragged_dot", lambda lhs, rhs, sizes, **kw: unwritten(lhs, rhs, sizes))
    model = _model(system, tiny)
    p = _layer_params(model, 1)["moe"]
    x = _hidden(np.random.RandomState(3), tiny, 2, 40)
    y, _, counters = _moe_ours(model, p, x)
    assert moe_lib._round_rows(80, model.top_k, model.experts_held, model.n_experts) > counters["moe_expert_tokens_held"]
    np.testing.assert_allclose(y, reference.moe(tiny, p, x)[0], rtol=2e-4, atol=2e-5)
    ours = lambda p, x: jnp.sum(jnp.sin(_moe_ours(model, p, x)[0]))
    theirs = lambda p, x: jnp.sum(jnp.sin(reference.moe(tiny, p, x)[0]))
    _close(jax.grad(ours, argnums=(0, 1))(p, x), jax.grad(theirs, argnums=(0, 1))(p, x), 5e-4)


def test_a_quarter_of_the_experts_held_goes_through_one_round_when_routing_is_even():
    """The cell's shapes: 16,384 tokens, 8 a token, 16 of 64 held: rounds of
    52,480 rows, so uniform routing's 32,768 held assignments are one round
    and every token at held experts three."""
    rows = moe_lib._round_rows(16384, 8, 16, 64)
    assert rows == 52480 and 16384 * 8 * 16 // 64 <= rows
    assert -(-16384 * 8 // rows) == 3


def _same(tiles):
    """One triple for the three kernels, as the short groups' rounds keep it:
    the forward's, swapped for the rows' gradient, the forward's again."""
    tm, tk, tn = tiles
    return tiles, (tm, tn, tk), tiles


@pytest.mark.parametrize("backend,rows,groups,k,n,per_replica,want", [
    # the window-and-full cell, 3,280 rows a group: forward, rows' gradient, matrices' gradient, each its own
    ("tpu", 52480, 16, 2304, 1792, True, ((256, 2304, 896), (256, 1792, 1152), (256, 1152, 896))),  # its gate and up
    ("tpu", 52480, 16, 896, 2304, True, ((256, 896, 1152), (256, 2304, 896), (256, 896, 1152))),  # and its down
    ("tpu", 16384, 32, 2048, 1024, True, None),  # the DeltaNet hybrid's cell: 512 rows an expert, out of the kernel's scope (ROADMAP S16)
    ("tpu", 52480, 16, 2304, 1792, False, None),  # mode="auto": GSPMD cannot partition the call
    ("cpu", 52480, 16, 2304, 1792, True, None),
    ("tpu", 52352, 16, 2304, 1792, True, None),  # no whole row tiles
    ("tpu", 52480, 16, 2304, 1800, True, None),  # no whole lane registers
    # the latent-attention cell, 1,664 rows a group: what is contracted in one tile, 512 columns, one triple for all
    ("tpu", 13312, 8, 2048, 3072, True, _same((256, 2048, 512))),  # its gate and up
    ("tpu", 13312, 8, 1536, 2048, True, _same((256, 1536, 512))),  # and its down
    ("tpu", 13184, 8, 2048, 3072, True, None),  # that cell's round as a multiple of 128: 51.5 row tiles
    # 2,048 rows a group: the long groups' rule, 13 MiB of blocks to the byte
    ("tpu", 16384, 8, 2048, 3072, True, ((256, 2048, 1024), (256, 3072, 512), (256, 1024, 1024))),
    # short groups, but more contracted than was swept: a 2,304 x 512 result block would pass 4 MiB
    ("tpu", 13312, 8, 2304, 3072, True, ((256, 2304, 768), (256, 3072, 384), (256, 1152, 768))),
    ("tpu", 13312, 8, 2048, 1792, True, ((256, 2048, 896), (256, 1792, 1024), (256, 1024, 896))),  # and columns that 512 does not divide
    # the convolution-and-attention cell, 6,560 rows a group
    ("tpu", 52480, 8, 2048, 3584, True, ((256, 2048, 896), (256, 3584, 512), (256, 2048, 512))),  # its gate and up
    ("tpu", 52480, 8, 1792, 2048, True, ((256, 1792, 1024), (256, 2048, 896), (256, 896, 1024))),  # and its down
    ("tpu", 16128, 8, 2048, 3072, True, _same((256, 2048, 512))),  # 2,016 rows a group (63 tiles in 8): still the short groups' tiles
    ("tpu", 8192, 8, 2048, 3072, True, _same((256, 2048, 512))),  # 1,024 rows a group: the first the kernel serves
    ("tpu", 7936, 8, 2048, 3072, True, None),  # 992 rows a group: the compiler's
    ("tpu", 52224, 8, 2048, 3584, True, ((256, 2048, 896), (256, 3584, 512), (256, 2048, 512))),  # 204 whole row tiles
    ("tpu", 52352, 8, 2048, 3584, True, None),  # 204.5 of them
    # a matrix whose whole contraction fits no block: the widest divisors up to 896 for gmm, tgmm's largest block
    ("tpu", 65536, 8, 16384, 2048, True, ((256, 512, 512), (256, 2048, 1024), (256, 1024, 1024))),
])
def test_the_grouped_products_lowering_rule(backend, rows, groups, k, n, per_replica, want):
    assert moe_lib.grouped_tiles(backend, rows, groups, k, n, per_replica=per_replica) == want


@pytest.mark.parametrize("k,n,itemsize", [
    (2048, 3584, 2), (1792, 2048, 2), (2304, 1792, 2), (896, 2304, 2), (2048, 3072, 2), (2048, 3584, 4), (896, 2304, 4),
    (4096, 1024, 2), (128, 128, 2), (16384, 2048, 2),
])
def test_the_long_groups_tiles_fit_the_chips_fast_memory(k, n, itemsize):
    """What the rule answers for a round of long groups, from the blocks the
    kernels keep in VMEM: ``gmm``'s rows and matrix double-buffered, its
    float32 result double-buffered beside the accumulator, within 13 MiB with
    all that is contracted in one tile (or, where that fits with no columns,
    the widest divisors up to 896); ``tgmm``'s float32 result block within
    4 MiB and no other whole-register block larger."""
    forward, rows_gradient, matrices_gradient = moe_lib.grouped_tiles(
        "tpu", 4096 * 8, 8, k, n, per_replica=True, itemsize=itemsize
    )
    for (tm, tk, tn), (contracted, columns) in ((forward, (k, n)), (rows_gradient, (n, k))):
        assert tm == moe_lib._ROW_TILE and contracted % tk == 0 and columns % tn == 0 and tk % 128 == 0 and tn % 128 == 0
        blocks = lambda tn: 2 * itemsize * (tm * tk + tk * tn) + 3 * 4 * tm * tn
        if tk == contracted:
            assert blocks(tn) <= 13 * 2**20
            wider = [t for t in range(tn + 128, columns + 1, 128) if columns % t == 0]
            assert all(blocks(t) > 13 * 2**20 for t in wider)  # the widest that fits
        else:
            assert 2 * itemsize * (tm * contracted + contracted * 128) + 3 * 4 * tm * 128 > 13 * 2**20
            assert tk <= 896 and tn <= 896
    tm, tk, tn = matrices_gradient
    assert tm == moe_lib._ROW_TILE and k % tk == 0 and n % tn == 0 and 4 * tk * tn <= 4 * 2**20
    whole = lambda d: [t for t in range(128, d + 1, 128) if d % t == 0]
    assert tk * tn == max(a * b for a in whole(k) for b in whole(n) if 4 * a * b <= 4 * 2**20)


@pytest.mark.parametrize("n_tokens,top_k,held,n_experts,want", [
    (16384, 4, 8, 64, 13312),  # the latent-attention cell: 52 row tiles (13,184, 51.5 of them, as a multiple of 128)
    (16384, 8, 16, 64, 52480),  # the window-and-full cell: 205 tiles, as it was
    (32768, 4, 8, 32, 52480),  # the convolution-and-attention cell, as it was
    (16384, 10, 32, 512, 16384),  # the DeltaNet hybrid's cell: 64 tiles, as it was
    (4096, 2, 2, 8, 3328),  # 1.6 times 2,048 is 3,276.8: up to 13 tiles
    (80, 2, 2, 8, 160),  # tiny: one tile would be more than every assignment
    (256, 2, 1, 64, 256),  # eight expected rows: one whole tile at the least
])
def test_a_round_is_whole_row_tiles_or_every_assignment(n_tokens, top_k, held, n_experts, want):
    rows = moe_lib._round_rows(n_tokens, top_k, held, n_experts)
    assert rows == want and (rows % moe_lib._ROW_TILE == 0 or rows == n_tokens * top_k)
    assert rows >= min(n_tokens * top_k, 1.6 * n_tokens * top_k * held / n_experts - 1)


@contextlib.contextmanager
def _time_limit(seconds: int):
    """Raises in the test after ``seconds``: the interpreter walks a kernel's
    grid a step at a time, and a case that grew must fail here, not eat the
    tier's time."""
    def late(signum, frame):
        raise TimeoutError(f"over {seconds} s in the Pallas interpreter")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(scope="module", params=[
    # rows, contracted, columns, the groups' sizes, the kernels' tiles: forward, rows' gradient, matrices' gradient
    (512, 256, 384, (150, 0, 200, 70), _same((128, 128, 128))),  # tiles that several groups share
    # the latent-attention cell's kind of tiles (all that is contracted in one, 512 columns), at what its round looks
    # like under an imbalance of 3.5: a group shorter than one row tile, an empty group beside one of several tiles
    (1280, 512, 1024, (180, 0, 900, 60), _same((256, 512, 512))),
    (1280, 768, 512, (60, 900, 0, 180), _same((256, 768, 512))),
    # the long groups' kind, a triple a kernel, at an eighth and less of the cells' sizes with the tiles' ratios kept:
    # groups of several row tiles that end inside one, an empty group, two whole tiles of dead rows past the last.
    # The convolution-and-attention cell's gate and up (2,048 x 3,584 in 256 x 2,048 x 896, 256 x 3,584 x 512,
    # 256 x 2,048 x 512): all that is contracted in one tile in all three, four, two and four column tiles
    (1536, 256, 512, (500, 0, 610, 170), ((128, 256, 128), (128, 512, 128), (128, 256, 128))),
    # the window-and-full cell's gate and up (2,304 x 1,792 in 256 x 2,304 x 896, 256 x 1,792 x 1,152,
    # 256 x 1,152 x 896): two column tiles each way, the matrices' gradient in half of what is contracted
    (1536, 768, 512, (610, 170, 0, 500), ((128, 768, 256), (128, 512, 384), (128, 384, 256))),
    # its down (896 x 2,304 in 256 x 896 x 1,152, 256 x 2,304 x 896, 256 x 896 x 1,152): the rows' gradient's
    # columns in one tile, so one fetch of a group's whole matrix serves its rows
    (1536, 384, 768, (170, 610, 500, 0), ((128, 384, 384), (128, 768, 384), (128, 384, 384))),
], ids=["128x128x128", "256x512x512", "256x768x512", "long-2048x3584", "long-2304x1792", "long-896x2304"])
def grouped(request):
    """Both lowerings' value and gradients at one shape: an empty group among
    the groups and rows past the last group."""
    rows, k, n, sizes, tiles = request.param
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (rows, k), jnp.float32).astype(jnp.bfloat16)
    w = (0.1 * jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32)).astype(jnp.bfloat16)
    probe = jax.random.normal(ks[2], (rows, n), jnp.float32).astype(jnp.bfloat16).astype(jnp.float32)
    live = (jnp.arange(rows) < sum(sizes))[:, None]
    empty = sizes.index(0)
    sizes = jnp.asarray(sizes, jnp.int32)
    plain = lambda x, w: jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    pallas = lambda x, w: moe_lib._pallas_grouped_product(x, w, sizes, tiles, True)

    def of(product):
        def masked(x, w):
            y = jnp.where(live, product(x, w), 0)
            return jnp.sum(y * probe), y
        (_, y), (dx, dw) = jax.value_and_grad(masked, argnums=(0, 1), has_aux=True)(x, w)
        return {"y": y, "dx": jnp.where(live, dx, 0), "dw": dw}

    with _time_limit(60):
        return of(plain), of(pallas), empty


@pytest.mark.parametrize("what", ["y", "dx", "dw"])
def test_the_grouped_products_lowerings_agree(grouped, what):
    """The Pallas kernel in the interpreter against ``ragged_dot``: the
    product, and the gradients that its own backward makes from the cotangent
    (which here holds bfloat16 values, so rounding it changes nothing)."""
    plain, pallas, empty = grouped
    assert pallas[what].dtype == plain[what].dtype
    np.testing.assert_allclose(
        np.asarray(pallas[what], np.float32), np.asarray(plain[what], np.float32), rtol=1e-2, atol=1e-2
    )
    if what == "dw":
        assert not np.any(np.asarray(pallas[what][empty], np.float32))  # the empty group's matrix gets no gradient


# -- the model -------------------------------------------------------------------

def test_registry_builds_the_published_cut_and_the_tiny_preset(system, published, tiny):
    """The published cut, as shapes only: its registry preset is the
    configuration file's numbers, its parameters the file's count, and every
    published width is unchanged in the file."""
    model = load_model("mellum2_ep4", published["vocab_size"])
    from_file = _model(system, published)
    ours = {"compute_dtype": None, "aux_loss_weight": None}  # the file's own choices (`assumed`)
    assert {**vars(from_file), **ours} == {**vars(model), **ours}
    assert [model.layer_kind(i) for i in range(4)] == ["SlidingAttention"] * 3 + ["FullAttention"]
    shapes, _ = jax.eval_shape(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)), jax.random.key(0))
    count = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert count == published["parameters"] == 595_154_176
    layer = shapes["layers"][0]
    assert set(layer["moe"]) == {"router", "experts"}
    assert layer["moe"]["router"].shape == (2304, 64)
    assert layer["moe"]["experts"]["gate_up"].shape == (16, 2304, 1792)
    assert layer["moe"]["experts"]["down"].shape == (16, 896, 2304)
    assert layer["mixer"]["q_proj"].shape == (2304, 4096) and layer["mixer"]["k_proj"].shape == (2304, 512)
    assert layer["mixer"]["q_norm"].shape == (128,)
    assert jax.tree.structure(shapes["layers"][3]) == jax.tree.structure(layer)  # the types differ in no leaf
    assert shapes["head"]["weight"].shape == (2304, 24576)
    catalog = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168, "moe_intermediate_size": 896,
        "num_attention_heads": 32, "num_key_value_heads": 4, "num_experts_per_tok": 8, "sliding_window": 1024,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-6, "norm_topk_prob": True,
        "tie_word_embeddings": False, "use_sliding_window": True, "max_window_layers": 0,
    }
    assert {k: published[k] for k in catalog} == catalog
    assert published["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert published["mlp_layer_types"] == ["sparse"] * 28
    assert published["rope_parameters"]["sliding_attention"] == {"rope_type": "default", "rope_theta": 500000}
    assert published["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert published["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert published["deployment"]["experts_published"] == 64 and published["num_experts"] == 16
    tiny_model = load_model("mellum2_tiny", VOCAB)
    assert {**vars(_model(system, tiny)), **ours} == {**vars(tiny_model), **ours}
    assert tiny_model.hidden_size <= 64 and (tiny_model.n_experts, tiny_model.experts_held, tiny_model.top_k) == (8, 2, 2)
    assert tiny_model.layer_types == model.layer_types  # the same pattern
    window, block, t = tiny_model.sliding_window, tiny_model.attention_q_block, tiny["tokens"]["seq_len"]
    assert window < t and window % block and block + window - 1 < t  # the band's loop runs


@pytest.mark.parametrize("bad", [
    dict(layer_types=("SlidingAttention",) * 3),              # a type a layer
    dict(layer_types=("SlidingAttention",) * 3 + ("Mamba",)),  # of the four the trunk has
    dict(sliding_window=None),                                # a sliding layer has a window
])
def test_layer_types_are_checked(bad):
    with pytest.raises(ValueError):
        load_model("mellum2_tiny", VOCAB, **bad)


def test_the_qwen_presets_build_what_they_built():
    """One trunk: the DeltaNet hybrid's registry entries still derive their
    pattern from the interval, keep their zero-centred norms (weights from 0)
    and their shared expert."""
    model = load_model("qwen3_next_tiny", VOCAB)
    assert model.layer_types == ("GatedDeltaNet",) * 3 + ("GatedAttention",)
    params, _ = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    assert set(params["layers"][0]["moe"]) == {"router", "experts", "shared", "shared_gate"}
    assert float(jnp.max(jnp.abs(params["final_norm"]))) == 0.0
    assert params["layers"][3]["mixer"]["q_proj"].shape[1] == 2 * model.n_heads * model.head_dim  # query | gate
    mellum, _ = load_model("mellum2_tiny", VOCAB).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    assert float(jnp.min(mellum["final_norm"])) == 1.0  # a plain norm's weight starts at 1


def test_the_whole_model_matches_the_reference(reference, system, tiny):
    """Loss and every parameter's gradient, the load-balancing loss in the
    gradient and not in the loss, on the seeded stream at 44 tokens."""
    model = _model(system, tiny)
    params, state = model.init(jax.random.key(3), None)
    params = _perturbed(params)
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))

    def ours(p):
        return nn.CrossEntropyLoss()(model.apply(p, state, tokens, Context(train=True))[0], targets)

    def theirs(p):
        loss, aux = reference.loss_and_aux(tiny, p, tokens, targets)
        return loss + tiny["aux_loss_weight"] * aux, loss

    (loss, grads), ((_, ref_loss), ref_grads) = (
        jax.value_and_grad(ours)(params), jax.value_and_grad(theirs, has_aux=True)(params)
    )
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    _close(grads, ref_grads, 5e-4)
    logits, _ = model.apply(params, state, tokens, Context(train=False))
    assert logits.shape == (2, tiny["tokens"]["seq_len"], VOCAB)


def test_the_references_blocks_change_no_arithmetic(reference, system, tiny, monkeypatch):
    """The reference takes attention and the loss in blocks for memory only:
    with blocks short enough that the rolled loop over whole blocks and the
    call for what is left both run (44 tokens: two of 16 and one of 12), loss,
    auxiliary loss and gradients are those of one block over everything."""
    model = _model(system, tiny)
    params = _perturbed(model.init(jax.random.key(3), None)[0])
    tokens, targets = (jnp.asarray(a[0]) for a in system.make_batches(tiny, 5, 1, 2))
    objective = lambda p: sum(reference.loss_and_aux(tiny, p, tokens, targets))
    whole = jax.value_and_grad(objective)(params)
    monkeypatch.setattr(reference, "_QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "_LOSS_BLOCK", 16)
    blocked = jax.value_and_grad(objective)(params)
    _close(blocked[0], whole[0], 1e-6)
    _close(blocked[1], whole[1], 1e-4)


def test_the_step_carries_the_layer_types_scopes(system, tiny):
    """``<i>_SlidingAttention`` and ``<i>_FullAttention`` with ``qkv``,
    ``attention`` and ``o_proj`` inside and ``moe`` with its four parts,
    forward and backward: what the benchmark's readers sum. No
    ``shared_expert``, no DeltaNet scope."""
    model = _model(system, tiny)
    tokens = jnp.zeros((1, 44), jnp.int32)
    params, state = model.init(jax.random.key(0), tokens)

    def loss(p):
        with profiling.scope(profiling.FORWARD):  # as the step opens it
            out = model.apply(p, state, tokens, Context(train=True))[0]
        return nn.CrossEntropyLoss()(out, tokens)

    # the compiled program's operation names, what a capture's events carry,
    # through the benchmark's own attribution to phase and layer path
    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    control = cells.load_module("layer_metrics", "_token_layers")._CONTROL
    seen = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        phase, path = scope_reduce.attribute(name + ":")
        if path is not None:
            seen.add((phase, "/".join(c for c in path.split("/") if c not in control)))
    for layer in ("0_SlidingAttention", "1_SlidingAttention", "2_SlidingAttention", "3_FullAttention"):
        for part in ("qkv", "attention", "o_proj", "moe/router", "moe/dispatch", "moe/combine"):
            for phase in ("forward", "backward"):
                assert (phase, f"{layer}/{part}") in seen, (phase, layer, part)
        assert {("recompute", f"{layer}/qkv"), ("recompute", f"{layer}/attention")} <= seen
        # the expert layer's own backward pass names its products transpose(jvp(experts)),
        # which the attribution leaves at ``moe``, as it does for the DeltaNet hybrid
        assert ("forward", f"{layer}/moe/experts") in seen and ("backward", f"{layer}/moe") in seen
    layers = {path.split("/")[0] for _, path in seen} - {scope_reduce.NO_LAYER}  # the embedding, the final norm
    assert layers == {"0_SlidingAttention", "1_SlidingAttention", "2_SlidingAttention", "3_FullAttention"}
    assert not any("shared_expert" in path for _, path in seen)
