"""Learned sparse attention in one model (models/hybrid_moe.py's
``SparseAttention`` layers; nn/sequence.py's index scores, exact selection,
attention under it and the indexer's objective) against a dense softmax under
the explicit mask, against a sort, and against the plain reference
(benchmark/reference/keye_vl_2_0_30b_a3b_ep8.py) at the tiny preset on the
CPU: seeded random weights, float32 unless a test says otherwise. Whole
training steps are in tests/test_sparse_moe_training.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from test_window_moe import _close, _hidden, _model, _perturbed  # the hybrid family's tests share them
from tpuddp import nn
from tpuddp.models import load_model
from tpuddp.models.hybrid_moe import SPARSE
from tpuddp.nn import moe as moe_lib
from tpuddp.nn import sequence as seq
from tpuddp.nn.core import Context

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
def published():
    return cells.load_cell(WORKLOAD).config


@pytest.fixture(scope="module")
def tiny(system, published):
    """The configuration at the tiny preset's sizes, as the reference reads it."""
    return system.shrunk(published)


def _variables(model, perturb=True):
    params, state = model.init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
    return (_perturbed(params), state) if perturb else (params, state)


# -- the selection (nn/sequence.py) ---------------------------------------------------------

def _sorted_selection(scores, visible, k):
    """``S_t`` by a sort, in numpy: a row's visible entries by falling score,
    equal scores by rising index (a stable sort of the negated scores), the
    first ``k`` of them."""
    scores, visible = np.asarray(scores, np.float32), np.asarray(visible)
    chosen = np.zeros(scores.shape, bool)
    for t in range(scores.shape[0]):
        seen = np.flatnonzero(visible[t])
        order = seen[np.argsort(-(scores[t, seen] + 0.0), kind="stable")]  # + 0.0: -0 and +0 are one score
        chosen[t, order[:k]] = True
    return chosen


@pytest.mark.parametrize("levels", [None, 5, 2])
@pytest.mark.parametrize("k", [1, 8, 40])
def test_the_selection_is_a_sort(levels, k):
    """``top_k_mask`` against a stable sort: real-valued scores (no two
    equal), scores on 5 and on 2 levels (ties at the threshold in most rows,
    zeros of both signs among them), under a causal mask whose first rows
    see fewer than ``k``."""
    rng = np.random.RandomState(k)
    scores = rng.randn(48, 64).astype(np.float32)
    if levels:
        scores = np.round(scores * (levels - 1) / 4) * np.where(rng.rand(48, 64) < 0.5, 1.0, -1.0).astype(np.float32)
        assert np.any(np.signbit(scores) & (scores == 0)) and np.any(~np.signbit(scores) & (scores == 0))
    visible = np.arange(64)[None, :] <= (16 + np.arange(48))[:, None]
    canon = jnp.where(jnp.asarray(scores) == 0, 0.0, jnp.asarray(scores))  # as sparse_attention_rows hands them over
    ours = np.asarray(seq.top_k_mask(canon, jnp.asarray(visible), k))
    np.testing.assert_array_equal(ours, _sorted_selection(scores, visible, k))
    np.testing.assert_array_equal(ours.sum(axis=1), np.minimum(visible.sum(axis=1), k))


def test_the_selection_finds_extreme_scores_and_more_keys_than_there_are():
    """Infinite and denormal scores are ordered as floats are; ``k`` past the
    row's length selects what is visible."""
    scores = jnp.asarray([[np.inf, -np.inf, 1e-42, -1e-42, 0.0, 3e38, -3e38, 1.0]], jnp.float32)
    visible = jnp.ones((1, 8), bool)
    for k in range(1, 9):
        np.testing.assert_array_equal(seq.top_k_mask(scores, visible, k), _sorted_selection(scores, visible, k))
    np.testing.assert_array_equal(seq.top_k_mask(scores, visible.at[0, 2].set(False), 20), visible.at[0, 2].set(False))


def test_the_row_groups_cover_a_sequence_once():
    for t, group in [(64, 16), (56, 16), (32768, 1024), (16, 16), (10, 16), (100, 7)]:
        spans = seq.sparse_row_groups(t, group)
        covered = [row for first, n, _ in spans for row in range(first, first + n)]
        assert covered == list(range(t))
        assert all(end >= first + n and end <= t for first, n, end in spans)
        assert all(n % group == 0 or n < group for _, n, _ in spans)
    assert len(seq.sparse_row_groups(32768, 1024)) == seq.SPARSE_STRETCHES == 3  # a rolled loop a stretch, not a copy a group


def test_the_attention_librarys_own_rule_has_the_layout_the_fused_lowering_calls():
    """``_fused_selected_attention`` calls the library's forward and backward
    functions as the library's own rule does (the public call keeps the
    log-sum-exp to itself): their arguments by name, the residuals and the
    results by place, read from jax 0.9.0. A library that moves any of them
    fails here, not in a training run."""
    import inspect
    import re

    kernels, _ = seq._splash()
    assert list(inspect.signature(kernels._splash_attention_forward).parameters) == [
        "fwd_mask_info", "q", "k", "v", "segment_ids", "sinks", "mask_value", "is_mqa", "block_sizes",
        "residual_checkpoint_name", "save_residuals", "mask_function", "attn_logits_soft_cap", "interpret"]
    assert list(inspect.signature(kernels._splash_attention_bwd).parameters) == [
        "save_residuals", "mask_value", "is_mqa", "block_sizes", "residual_checkpoint_name", "mask_function",
        "attn_logits_soft_cap", "interpret", "res", "do"]
    kernel = seq._selected_kernel(jnp.ones((128, 128), bool), True)
    assert set(kernel.kwargs) == {"mask_value", "is_mqa", "block_sizes", "residual_checkpoint_name", "save_residuals",
                                  "mask_function", "attn_logits_soft_cap", "interpret"}
    source = inspect.getsource(kernels._splash_attention_bwd)
    assert re.search(r"q,\s*k,\s*v,\s*segment_ids,\s*sinks,\s*o,\s*logsumexp,\s*dq_mask_info,\s*dkv_mask_info,?\s*\)\s*=\s*res", source)
    returned = re.sub(r"#[^\n]*", "", source[source.rindex("return ("):])  # the results by place: three mask infos, dq, dk, dv
    assert re.match(r"return \(\s*None,\s*None,\s*None,\s*dq,\s*dk,\s*dv,", returned)


def test_the_lowering_follows_backend_and_shapes():
    rule = seq.sparse_attention_lowering
    assert rule("tpu", 128, 32768, per_replica=True) == "fused"
    assert rule("tpu", 128, 32768, per_replica=False) == "blockwise"  # a custom call GSPMD cannot partition
    assert rule("cpu", 128, 32768, per_replica=True) == "blockwise"
    assert rule("tpu", 16, 32768, per_replica=True) == "blockwise"
    assert rule("tpu", 128, 1000, per_replica=True) == "blockwise"


# -- a group of queries against a dense softmax under the explicit mask ------------------------

def _inputs(t, hq=4, hkv=2, d=16, hi=2, di=16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    return (normal(ks[0], t, hq, d), normal(ks[1], t, hkv, d), normal(ks[2], t, hkv, d),
            normal(ks[3], t, hi, di), normal(ks[4], t, di), 0.3 * normal(ks[5], t, hi))


def _dense(q, k, v, qi, ki, wi, *, top_k):
    """Every pair of the ``T x T`` block written down: the index scores, the
    mask from a sort, a softmax a head under it, the heads' mean against the
    indexer's softmax."""
    t, (hq, hkv) = q.shape[0], (q.shape[1], k.shape[1])
    scores_i = jnp.einsum("tj,tjs->ts", wi, jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki, precision="highest")))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    chosen = jnp.asarray(_sorted_selection(jax.lax.stop_gradient(scores_i), causal, top_k))
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision="highest") * q.shape[-1] ** -0.5
    alpha = jax.nn.softmax(jnp.where(chosen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", alpha, v, precision="highest")
    p = jax.lax.stop_gradient(jnp.mean(alpha, axis=0))
    log_r = jax.nn.log_softmax(jnp.where(chosen, scores_i, -jnp.inf), axis=-1)
    kl = jnp.sum(jnp.where(chosen, p * (jnp.log(jnp.where(chosen, p, 1.0)) - jnp.where(chosen, log_r, 0.0)), 0.0))
    return out, kl, chosen


@functools.partial(jax.jit, static_argnames=("top_k", "group", "lowering", "interpret"))
def _in_groups(q, k, v, qi, ki, wi, *, top_k, group, lowering="blockwise", interpret=False):
    outs, kl, pairs = [], 0.0, 0.0
    for first, n, end in seq.sparse_row_groups(q.shape[0], group):
        for start in range(first, first + n, group):
            rows = slice(start, min(start + group, first + n))
            out, kl_rows, pairs_rows = seq.sparse_attention_rows(
                q[rows], qi[rows], wi[rows], k[:end], v[:end], ki[:end], start, scale=q.shape[-1] ** -0.5,
                top_k=top_k, compute_dtype=jnp.float32, lowering=lowering, interpret=interpret,
            )
            outs.append(out)
            kl, pairs = kl + kl_rows, pairs + pairs_rows
    return jnp.concatenate(outs), kl, pairs


@pytest.mark.parametrize("t,group,top_k", [(64, 16, 8), (56, 16, 8), (40, 64, 5), (64, 16, 100)])
def test_rows_are_a_dense_softmax_under_the_explicit_mask(t, group, top_k):
    """Output, the objective and the selected pairs of the groups against the
    whole block written down, and every gradient: the objective's reaches the
    indexer's inputs alone, the output's never does."""
    args = _inputs(t)
    with jax.default_matmul_precision("highest"):
        want_out, want_kl, chosen = _dense(*args, top_k=top_k)
        out, kl, pairs = _in_groups(*args, top_k=top_k, group=group)
        np.testing.assert_allclose(out, want_out, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(kl, want_kl, rtol=1e-4)
        assert float(pairs) == float(jnp.sum(chosen)) == sum(min(i + 1, top_k) for i in range(t))
        both = lambda f: lambda *a: (lambda out, kl, _: jnp.sum(jnp.sin(out)) + 3.0 * kl)(*f(*a))
        ours = jax.grad(both(lambda *a: _in_groups(*a, top_k=top_k, group=group)), argnums=tuple(range(6)))(*args)
        theirs = jax.grad(both(lambda *a: _dense(*a, top_k=top_k)), argnums=tuple(range(6)))(*args)
        _close(ours, theirs, 2e-4)
        of_out = jax.grad(lambda *a: jnp.sum(jnp.sin(_in_groups(*a, top_k=top_k, group=group)[0])), argnums=(3, 4, 5))(*args)
        of_kl = jax.grad(lambda *a: _in_groups(*a, top_k=top_k, group=group)[1], argnums=(0, 1, 2))(*args)
    for leaf in (*of_out, *of_kl):
        assert not np.any(np.asarray(leaf))


def test_every_key_is_attended_while_there_are_top_k_or_fewer():
    """Queries before position ``top_k`` attend every earlier key: their rows
    are plain causal attention's, whatever the indexer says."""
    q, k, v, qi, ki, wi = _inputs(48, seed=2)
    out, _, pairs = _in_groups(q, k, v, qi, ki, wi, top_k=24, group=16)
    causal = seq.causal_attention(q[None], k[None], v[None], scale=16 ** -0.5, compute_dtype=jnp.float32, q_block=16)[0]
    np.testing.assert_allclose(out[:24], causal[:24], rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(out[24:] - causal[24:]))) > 1e-2  # past it a query leaves keys out
    everything, _, _ = _in_groups(q, k, v, 0 * qi, ki, wi, top_k=48, group=16)
    np.testing.assert_allclose(everything, causal, rtol=1e-4, atol=1e-5)


def test_the_index_scores_bring_their_own_backward_pass():
    """``index_scores`` a head at a time, forward and backward, against the
    one einsum and its automatic gradient."""
    _, _, _, qi, ki, wi = _inputs(40, hi=3, seed=4)
    plain = lambda qi, ki, wi: jnp.einsum("tj,tjs->ts", wi, jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki)))
    weights = jnp.asarray(np.random.RandomState(0).randn(40, 40), jnp.float32)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(seq.index_scores(qi, ki, wi), plain(qi, ki, wi), rtol=1e-5, atol=1e-5)
        loss = lambda f: lambda *a: jnp.sum(weights * f(*a))
        _close(jax.grad(loss(seq.index_scores), argnums=(0, 1, 2))(qi, ki, wi),
               jax.grad(loss(plain), argnums=(0, 1, 2))(qi, ki, wi), 1e-5)


def test_the_fused_lowering_under_the_interpreter():
    """The library's kernel under a mask that is data, in Pallas's
    interpreter at heads of 128 (one mask serves every head; its log-sum-exp
    comes out for the objective): output, objective and every gradient against
    the blockwise lowering at the same shapes."""
    args = _inputs(256, d=128, seed=5)
    run = lambda lowering: jax.value_and_grad(
        lambda *a: (lambda out, kl, _: jnp.sum(jnp.sin(out)) + 3.0 * kl)(
            *_in_groups(*a, top_k=32, group=128, lowering=lowering, interpret=True)),
        argnums=tuple(range(6)))(*args)
    with jax.default_matmul_precision("highest"):
        (fused, fused_grads), (blockwise, blockwise_grads) = run("fused"), run("blockwise")
    assert float(fused) == pytest.approx(float(blockwise), rel=1e-5)
    _close(fused_grads, blockwise_grads, 2e-4)


def test_the_index_scores_kernel_pair_under_the_interpreter():
    """``sparse_index_scores_fwd`` / ``_bwd`` against the loop a head at a
    time: the scores in every block that holds a key at or before its last
    query, 0 in the blocks above the diagonal, and the three gradients of a
    cotangent that is 0 where no query sees a key."""
    from tpuddp.nn import sparse_attention_kernels as kernels

    _, _, _, qi, ki, wi = _inputs(512, hi=3, di=64, seed=6)
    qi, start = qi[:256], jnp.int32(128)
    visible = jnp.arange(512)[None, :] <= (start + jnp.arange(256))[:, None]
    in_seen_blocks = (jnp.arange(512)[None, :] // 512 * 512) <= (start + (jnp.arange(256)[:, None] // 256 + 1) * 256 - 1)
    assert kernels.blocks(256, 512) == (256, 512) and kernels.blocks(250, 512) is None
    with jax.default_matmul_precision("highest"):
        want, got = seq.index_scores(qi, ki, wi[:256]), kernels.index_scores(qi, ki, wi[:256], start, True)
        np.testing.assert_allclose(got, jnp.where(in_seen_blocks, want, 0.0), rtol=1e-5, atol=1e-5)
        cot = jnp.asarray(np.random.RandomState(0).randn(256, 512), jnp.float32) * visible
        loop = jax.grad(lambda *a: jnp.sum(cot * seq.index_scores(*a)), argnums=(0, 1, 2))(qi, ki, wi[:256])
        pair = jax.grad(lambda *a: jnp.sum(cot * kernels.index_scores(*a, start, True)), argnums=(0, 1, 2))(qi, ki, wi[:256])
    _close(pair, loop, 1e-5)
    # keys in blocks of 128: the blocks past the group's last query are written as zeros
    narrow = kernels.index_scores(qi[:128], ki[:384], wi[:128], jnp.int32(0), True)
    assert not np.any(np.asarray(narrow[:, 128:])) and np.any(np.asarray(narrow[:, :128]))


def test_the_mean_probability_kernel_under_the_interpreter():
    """``sparse_mean_probabilities`` against the softmax written down: the
    heads' mean of ``exp(score - lse)`` on the selection, 0 off it, a row
    summing to 1."""
    from tpuddp.nn import sparse_attention_kernels as kernels

    q, k, _, _, _, _ = _inputs(512, d=128, seed=7)
    q, start = q[128:384], jnp.int32(128)
    visible = jnp.arange(512)[None, :] <= (start + jnp.arange(256))[:, None]
    selected = visible & (jnp.asarray(np.random.RandomState(1).rand(256, 512)) < 0.3)
    selected = selected | (jnp.arange(512)[None, :] == (start + jnp.arange(256))[:, None])
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("ghd,shd->hgs", q, jnp.repeat(k, 2, axis=1))
        alpha = jax.nn.softmax(jnp.where(selected, scores, -jnp.inf), axis=-1)
        lse = jax.scipy.special.logsumexp(jnp.where(selected, scores, -jnp.inf), axis=-1)
        got = kernels.mean_probabilities(q, k, lse, selected, start, True)
    np.testing.assert_allclose(got, jnp.mean(alpha, axis=0), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(got, axis=1), 1.0, rtol=1e-5)
    assert not np.any(np.asarray(got)[~np.asarray(selected)])


@pytest.mark.parametrize("levels,k", [(None, 33), (3, 7), (3, 100), (2, 512)])
def test_the_threshold_kernel_is_the_loop(levels, k):
    """``sparse_kth_largest`` under the interpreter: the selection with the
    threshold from the kernel is the selection with the loop's, with ties at
    the threshold and without."""
    rng = np.random.RandomState(k)
    scores = rng.randn(64, 512).astype(np.float32)
    if levels:
        scores = np.round(scores * (levels - 1) / 2)
    visible = jnp.arange(512)[None, :] <= (200 + 4 * jnp.arange(64))[:, None]
    scores = jnp.where(jnp.asarray(scores) == 0, 0.0, jnp.asarray(scores))
    loop, kernel = seq.top_k_mask(scores, visible, k), seq.top_k_mask(scores, visible, k, fused=True, interpret=True)
    np.testing.assert_array_equal(kernel, loop)
    np.testing.assert_array_equal(loop, _sorted_selection(scores, visible, k))


# -- the mixer, the layer and the model against the reference ----------------------------------

def test_the_mixer_matches_the_reference(reference, system, tiny):
    """``x + Attn(RMSNorm(x))``, the objective and the pairs of one layer on
    perturbed weights (the indexer's choice then differs from layer to layer
    and from the first keys), and the gradients of both, the stop-gradient on
    the indexer's input among them."""
    model = _model(system, tiny)
    p = _variables(model)[0]["layers"][1]
    x = _hidden(np.random.RandomState(2), tiny, 1, 56)[0]
    eps = tiny["rms_norm_eps"]

    def theirs(p, x):
        h = x[None] / jnp.sqrt(jnp.mean(x[None] ** 2, axis=-1, keepdims=True) + eps) * p["input_norm"]
        mixed, kl, pairs = reference.sparse_mixer(tiny, p["mixer"], h)
        return x + mixed[0], kl, pairs

    ours = jax.jit(lambda p, x: model._sparse_mix(p, x, False))
    theirs = jax.jit(theirs)
    with jax.default_matmul_precision("highest"):
        for a, b in zip(ours(p, x), theirs(p, x)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
        loss = lambda f: lambda p, x: (lambda y, kl, _: jnp.sum(jnp.sin(y)) + 2.0 * kl)(*f(p, x))
        grads = lambda f: jax.jit(jax.grad(loss(f), argnums=(0, 1)))({k: p[k] for k in ("mixer", "input_norm")}, x)
        mine = grads(ours)
        _close(mine, grads(theirs), 5e-4)
        remat = grads(lambda p, x: model._sparse_mix(p, x, True))
    _close(remat, mine, 1e-5)  # the layer's own checkpoints change nothing


def test_the_references_selection_is_a_sort(reference, tiny):
    scores = jnp.asarray(np.round(np.random.RandomState(1).randn(2, 24, 40) * 2), jnp.float32)
    got = reference.selection(jnp.where(scores == 0, 0.0, scores), 16, 8)
    visible = np.arange(40)[None, :] <= (16 + np.arange(24))[:, None]
    for b in range(2):
        np.testing.assert_array_equal(got[b], _sorted_selection(scores[b], visible, 8))


def test_the_model_matches_the_reference_on_every_loss(reference, system, tiny):
    """The language model's loss, the routers' and the indexers' losses and
    the selected pairs of the whole model on perturbed weights; the reported
    loss is the language model's alone, and the counters carry the indexers'
    out."""
    model = _model(system, tiny)
    params, state = _variables(model)
    tokens = jnp.asarray(np.random.RandomState(5).randint(0, VOCAB, (2, 56)))
    targets = jnp.roll(tokens, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        out, _ = jax.jit(lambda p, x: model.apply(p, state, x, Context(train=True)))(params, tokens)
        reported = nn.CrossEntropyLoss()(out, targets, jnp.ones(targets.shape, jnp.float32))
        loss, aux, index_loss, pairs = jax.jit(functools.partial(reference.losses, tiny))(params, tokens, targets)
    assert float(reported) == pytest.approx(float(loss), rel=2e-5)
    assert float(out.aux_loss) == pytest.approx(
        float(tiny["aux_loss_weight"] * aux + tiny["indexer_loss_weight"] * index_loss), rel=2e-4)
    per_layer_row = out.counters["indexer_kl_sum"] / out.counters["indexer_rows"]
    assert float(per_layer_row) * tiny["num_hidden_layers"] == pytest.approx(float(index_loss), rel=2e-4)
    assert float(out.counters["indexer_rows"]) == 2 * 56 * tiny["num_hidden_layers"]
    top_k = tiny["sa_config"]["topk"]
    assert float(out.counters["index_selected_pairs"]) == float(pairs) == (
        2 * tiny["num_hidden_layers"] * sum(min(i + 1, top_k) for i in range(56)))
    assert float(index_loss) > 1e-3  # perturbed: attention and indexer disagree
    logits, _ = model.apply(params, state, tokens, Context(train=False))
    assert logits.shape == (2, 56, VOCAB) and logits.dtype == jnp.float32


def test_registry_and_constructor_hold_the_models_shape():
    model = load_model("keye_vl_2_0_tiny", VOCAB)
    assert model.layer_types == (SPARSE,) * 2
    assert model.counter_names == moe_lib.COUNTERS + seq.SPARSE_COUNTERS
    assert load_model("mellum2_tiny", VOCAB).counter_names == moe_lib.COUNTERS
    params, state = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    indexer = params["layers"][0]["mixer"]["indexer"]
    assert jax.tree_util.tree_map(lambda a: a.shape, indexer) == {
        "q_proj": (64, 32), "k_proj": (64, 16), "k_norm": {"weight": (16,), "bias": (16,)}, "w_proj": (64, 2)}
    assert state == () and "shared" not in params["layers"][0]["moe"]
    assert set(params["layers"][0]["mixer"]) == {"q_proj", "k_proj", "v_proj", "q_norm", "k_norm", "o_proj", "indexer"}
    for bad in (dict(loop_steps=2, dense_layers=2, dense_width=32), dict(next_token_modules=1),
                dict(sandwich_norms=True), dict(index_top_k=0)):
        with pytest.raises(ValueError, match="plain stack"):
            load_model("keye_vl_2_0_tiny", VOCAB, **bad)
    # one sparse-attention layer among others: the counters are the model's, the other layers add nothing to them
    mixed = load_model("keye_vl_2_0_tiny", VOCAB, layer_types=("FullAttention", SPARSE), compute_dtype="float32")
    params, state = mixed.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    out, _ = mixed.apply(params, state, jnp.zeros((1, 24), jnp.int32), Context(train=True))
    assert float(out.counters["indexer_rows"]) == 24 and "indexer" not in params["layers"][0]["mixer"]
