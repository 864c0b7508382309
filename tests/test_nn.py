"""nn layer correctness, with torch (CPU) as the numerical oracle where the
reference stack defines the semantics (BatchNorm buffers, adaptive pooling,
cross-entropy)."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from tpuddp import nn

KEY = jax.random.key(0)


def ctx_train(rng=None, axis_name=None):
    return nn.Context(train=True, rng=rng, axis_name=axis_name)


def test_linear_shapes_and_math():
    x = jnp.ones((4, 16))
    layer = nn.Linear(8)
    params, state = layer.init(KEY, x)
    assert params["weight"].shape == (16, 8)
    y, _ = layer.apply(params, state, x, nn.Context())
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ params["weight"] + params["bias"]), rtol=1e-6
    )


def test_linear_init_bound_matches_torch_scheme():
    x = jnp.ones((2, 100))
    params, _ = nn.Linear(50).init(KEY, x)
    bound = 1 / np.sqrt(100)
    w = np.asarray(params["weight"])
    assert w.min() >= -bound and w.max() <= bound
    assert w.std() == pytest.approx(bound / np.sqrt(3), rel=0.1)


def test_conv2d_matches_torch():
    x = np.random.RandomState(0).randn(2, 8, 8, 3).astype(np.float32)
    layer = nn.Conv2d(5, kernel_size=3, strides=2, padding=1)
    params, state = layer.init(KEY, jnp.asarray(x))
    y, _ = layer.apply(params, state, jnp.asarray(x), nn.Context())
    # torch oracle: NCHW / OIHW
    w = np.asarray(params["weight"]).transpose(3, 2, 0, 1)  # HWIO -> OIHW
    ref = F.conv2d(
        torch.from_numpy(x.transpose(0, 3, 1, 2)),
        torch.from_numpy(w),
        torch.from_numpy(np.asarray(params["bias"])),
        stride=2,
        padding=1,
    ).numpy().transpose(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)


def test_maxpool_matches_torch():
    x = np.random.RandomState(1).randn(2, 9, 9, 4).astype(np.float32)
    layer = nn.MaxPool2d(3, strides=2)
    y, _ = layer.apply((), (), jnp.asarray(x), nn.Context())
    ref = F.max_pool2d(torch.from_numpy(x.transpose(0, 3, 1, 2)), 3, 2).numpy()
    np.testing.assert_allclose(np.asarray(y), ref.transpose(0, 2, 3, 1), rtol=1e-6)


@pytest.mark.parametrize("in_hw,out_hw", [(13, 6), (7, 7), (8, 4), (5, 3), (1, 2)])
def test_adaptive_avg_pool_matches_torch(in_hw, out_hw):
    x = np.random.RandomState(2).randn(2, in_hw, in_hw, 3).astype(np.float32)
    layer = nn.AdaptiveAvgPool2d(out_hw)
    y, _ = layer.apply((), (), jnp.asarray(x), nn.Context())
    ref = F.adaptive_avg_pool2d(
        torch.from_numpy(x.transpose(0, 3, 1, 2)), out_hw
    ).numpy().transpose(0, 2, 3, 1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("in_hw,out_hw", [(13, 6), (5, 3)])
def test_adaptive_avg_pool_gradient_matches_torch(in_hw, out_hw):
    # (13,6) takes the uniform-bin reduce_window fast path, (5,3) the ragged
    # integral-image path (nn/layers.py) — both backwards must match torch
    x = np.random.RandomState(3).randn(2, in_hw, in_hw, 3).astype(np.float32)
    layer = nn.AdaptiveAvgPool2d(out_hw)
    g = jax.grad(
        lambda v: jnp.sum(layer.apply((), (), v, nn.Context())[0] ** 2)
    )(jnp.asarray(x))
    xt = torch.from_numpy(x.transpose(0, 3, 1, 2)).requires_grad_(True)
    F.adaptive_avg_pool2d(xt, out_hw).pow(2).sum().backward()
    np.testing.assert_allclose(
        np.asarray(g), xt.grad.numpy().transpose(0, 2, 3, 1), rtol=1e-4, atol=1e-5
    )


def test_dropout_train_eval_and_rng():
    x = jnp.ones((100, 100))
    layer = nn.Dropout(0.5)
    y_eval, _ = layer.apply((), (), x, nn.Context())
    np.testing.assert_array_equal(np.asarray(y_eval), np.ones((100, 100)))
    y_train, _ = layer.apply((), (), x, ctx_train(jax.random.key(1)))
    kept = np.asarray(y_train) != 0
    assert 0.4 < kept.mean() < 0.6
    assert np.allclose(np.asarray(y_train)[kept], 2.0)  # inverted scaling
    with pytest.raises(ValueError):
        layer.apply((), (), x, ctx_train(rng=None))


def test_batchnorm_matches_torch_train_and_eval():
    x = np.random.RandomState(3).randn(8, 4, 4, 5).astype(np.float32) * 3 + 1
    layer = nn.BatchNorm()
    params, state = layer.init(KEY, jnp.asarray(x))
    y, new_state = layer.apply(params, state, jnp.asarray(x), ctx_train())

    bn = torch.nn.BatchNorm2d(5)
    bn.train()
    ref = bn(torch.from_numpy(x.transpose(0, 3, 1, 2))).detach().numpy()
    np.testing.assert_allclose(np.asarray(y), ref.transpose(0, 2, 3, 1), rtol=1e-3, atol=1e-4)
    # running buffers (torch keeps unbiased var in the buffer)
    np.testing.assert_allclose(np.asarray(new_state["mean"]), bn.running_mean.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state["var"]), bn.running_var.numpy(), rtol=1e-4, atol=1e-5)

    # eval mode uses the buffers
    bn.eval()
    y2, same_state = layer.apply(params, new_state, jnp.asarray(x), nn.Context())
    ref2 = bn(torch.from_numpy(x.transpose(0, 3, 1, 2))).detach().numpy()
    np.testing.assert_allclose(np.asarray(y2), ref2.transpose(0, 2, 3, 1), rtol=1e-3, atol=1e-4)
    assert same_state is new_state  # eval must not touch buffers


def test_sync_batchnorm_equals_global_batch_stats(mesh):
    """The SyncBatchNorm contract (SURVEY §2b #16): per-shard BN with sync=True
    must equal single-device BN over the full global batch."""
    from jax.sharding import PartitionSpec as P

    x = np.random.RandomState(4).randn(16, 2, 2, 3).astype(np.float32)
    layer = nn.BatchNorm(sync=True)
    params, state = layer.init(KEY, jnp.asarray(x))

    def per_shard(p, s, xs):
        y, ns = layer.apply(p, s, xs, ctx_train(axis_name="data"))
        return y, ns

    y_sync, st_sync = jax.jit(
        shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()),
            check_vma=False,
        )
    )(params, state, jnp.asarray(x))

    layer_local = nn.BatchNorm()
    y_full, st_full = layer_local.apply(params, state, jnp.asarray(x), ctx_train())
    np.testing.assert_allclose(np.asarray(y_sync), np.asarray(y_full), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_sync["mean"]), np.asarray(st_full["mean"]), rtol=1e-4, atol=1e-6)
    # unbiased-var correction uses the GLOBAL count when synced
    np.testing.assert_allclose(np.asarray(st_sync["var"]), np.asarray(st_full["var"]), rtol=1e-4, atol=1e-6)


def test_convert_sync_batchnorm_walks_tree():
    model = nn.Sequential(
        nn.Conv2d(4, 3, padding=1),
        nn.BatchNorm(),
        nn.Sequential(nn.BatchNorm(), nn.ReLU()),
    )
    nn.convert_sync_batchnorm(model)
    assert model[1].sync is True
    assert model[2][0].sync is True


def test_cross_entropy_matches_torch():
    logits = np.random.RandomState(5).randn(10, 7).astype(np.float32)
    labels = np.random.RandomState(6).randint(0, 7, 10)
    ours = nn.cross_entropy(jnp.asarray(logits), jnp.asarray(labels))
    ref = F.cross_entropy(torch.from_numpy(logits), torch.from_numpy(labels)).item()
    assert float(ours) == pytest.approx(ref, rel=1e-5)
    ours_sum = nn.cross_entropy(jnp.asarray(logits), jnp.asarray(labels), "sum")
    assert float(ours_sum) == pytest.approx(ref * 10, rel=1e-5)


def test_cross_entropy_weighted_mask_ignores_padding():
    logits = np.random.RandomState(7).randn(6, 3).astype(np.float32)
    labels = np.array([0, 1, 2, 0, 1, 2])
    w = jnp.array([1, 1, 1, 1, 0, 0], jnp.float32)
    masked = nn.cross_entropy(jnp.asarray(logits), jnp.asarray(labels), "mean", w)
    unpadded = nn.cross_entropy(jnp.asarray(logits[:4]), jnp.asarray(labels[:4]))
    assert float(masked) == pytest.approx(float(unpadded), rel=1e-6)


def test_sequential_threads_state_and_shapes():
    x = jnp.ones((2, 8, 8, 3))
    model = nn.Sequential(
        nn.Conv2d(4, 3, padding=1),
        nn.BatchNorm(),
        nn.ReLU(),
        nn.MaxPool2d(2),
        nn.Flatten(),
        nn.Linear(10),
    )
    params, state = model.init(KEY, x)
    y, new_state = model.apply(params, state, x, ctx_train())
    assert y.shape == (2, 10)
    assert len(new_state) == 6
    # BN state updated in train mode
    assert not np.allclose(np.asarray(new_state[1]["mean"]), 0.0)


def test_batchnorm_sample_weight_excludes_padding():
    """Padded (weight-0) rows must not bias BN batch statistics: a padded
    batch with a mask must produce the same output rows and running stats as
    the unpadded batch (the torch ragged-last-batch behavior, without the
    ragged recompile)."""
    rng = np.random.RandomState(5)
    real = rng.randn(6, 2, 2, 3).astype(np.float32) * 2 + 4
    padded = np.concatenate([real, np.repeat(real[:1], 2, axis=0)])
    w = np.array([1, 1, 1, 1, 1, 1, 0, 0], np.float32)

    layer = nn.BatchNorm()
    params, state = layer.init(KEY, jnp.asarray(padded))
    y_ref, st_ref = layer.apply(params, state, jnp.asarray(real), ctx_train())
    y_pad, st_pad = layer.apply(
        params, state, jnp.asarray(padded),
        nn.Context(train=True, sample_weight=jnp.asarray(w)),
    )
    np.testing.assert_allclose(
        np.asarray(y_pad)[:6], np.asarray(y_ref), rtol=1e-4, atol=1e-5
    )
    for k in ("mean", "var"):
        np.testing.assert_allclose(
            np.asarray(st_pad[k]), np.asarray(st_ref[k]), rtol=1e-4, atol=1e-6
        )


def test_sync_batchnorm_weighted_equals_global_masked(mesh):
    """sync=True + sample_weight: sharded weighted stats == full-batch stats
    over only the real rows."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(6)
    real = rng.randn(13, 2, 2, 3).astype(np.float32)
    padded = np.concatenate([real, np.repeat(real[:1], 3, axis=0)])
    w = np.concatenate([np.ones(13), np.zeros(3)]).astype(np.float32)

    layer = nn.BatchNorm(sync=True)
    params, state = layer.init(KEY, jnp.asarray(padded))

    def per_shard(p, s, xs, ws):
        ctx = nn.Context(train=True, axis_name="data", sample_weight=ws)
        return layer.apply(p, s, xs, ctx)

    y_sync, st_sync = jax.jit(
        shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P("data"), P()),
            check_vma=False,
        )
    )(params, state, jnp.asarray(padded), jnp.asarray(w))

    y_ref, st_ref = nn.BatchNorm().apply(params, state, jnp.asarray(real), ctx_train())
    np.testing.assert_allclose(
        np.asarray(y_sync)[:13], np.asarray(y_ref), rtol=1e-4, atol=1e-5
    )
    for k in ("mean", "var"):
        np.testing.assert_allclose(
            np.asarray(st_sync[k]), np.asarray(st_ref[k]), rtol=1e-4, atol=1e-6
        )


def test_batchnorm_stable_var_matches_and_survives_large_mean():
    x = np.random.RandomState(7).randn(8, 4, 4, 5).astype(np.float32)
    a = nn.BatchNorm()
    b = nn.BatchNorm(stable_var=True)
    params, state = a.init(KEY, jnp.asarray(x))
    ya, _ = a.apply(params, state, jnp.asarray(x), ctx_train())
    yb, _ = b.apply(params, state, jnp.asarray(x), ctx_train())
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb), rtol=1e-4, atol=1e-5)

    # large-mean activations: E[x^2]-E[x]^2 cancels catastrophically; the
    # two-pass path keeps the true variance
    big = (x + 300.0).astype(np.float32)  # unit variance at mean 300
    yb2, st2 = b.apply(params, state, jnp.asarray(big), ctx_train())
    np.testing.assert_allclose(
        np.asarray(yb2).reshape(-1, 5).var(axis=0), np.ones(5), rtol=2e-2
    )
    assert np.all(np.asarray(st2["var"]) > 0)
    # the single-pass path visibly degrades on the same input (that's the
    # reason stable_var exists); don't assert a hard bound, just the contrast
    ya2, _ = a.apply(params, state, jnp.asarray(big), ctx_train())
    err_stable = np.abs(np.asarray(yb2).reshape(-1, 5).var(axis=0) - 1).max()
    err_fast = np.abs(np.asarray(ya2).reshape(-1, 5).var(axis=0) - 1).max()
    assert err_stable <= err_fast


def test_batchnorm_all_padded_batch_leaves_running_stats():
    """A fully-padded (all weight-0) shard must leave the running buffers
    untouched rather than decaying them toward mean=0/var=0."""
    x = np.random.RandomState(8).randn(4, 2, 2, 3).astype(np.float32)
    layer = nn.BatchNorm()
    params, _ = layer.init(KEY, jnp.asarray(x))
    state = {"mean": jnp.full((3,), 2.0), "var": jnp.full((3,), 3.0)}
    w = jnp.zeros(4, jnp.float32)
    _, new_state = layer.apply(
        params, state, jnp.asarray(x),
        nn.Context(train=True, sample_weight=w),
    )
    np.testing.assert_array_equal(np.asarray(new_state["mean"]), np.full(3, 2.0))
    np.testing.assert_array_equal(np.asarray(new_state["var"]), np.full(3, 3.0))


def test_divergent_state_protocol():
    """sync_buffers='none' validation holds by construction (Module.
    divergent_state): an UNDECLARED custom stateful leaf counts as divergent;
    declaring divergent_state() -> False vouches replica-invariance."""
    from tpuddp.nn.core import Module
    from tpuddp.nn.norm import has_divergent_buffers

    class Counter(Module):
        def init(self, key, x):
            return (), {"count": jnp.zeros(())}

        def apply(self, params, state, x, ctx):
            return x, {"count": state["count"] + 1.0}

    class InvariantCounter(Counter):
        def divergent_state(self):
            return False

    assert has_divergent_buffers(Counter())
    assert not has_divergent_buffers(InvariantCounter())
    assert has_divergent_buffers(nn.Sequential(nn.Linear(4), Counter()))
    assert not has_divergent_buffers(nn.Sequential(nn.Linear(4), InvariantCounter()))

    class StatefulContainer(Module):
        """Container with its OWN buffer beside clean children — must not
        escape the check just because its children are fine."""

        def __init__(self):
            self.inner = nn.Linear(4)

        def children(self):
            return (self.inner,)

        def init(self, key, x):
            p, s = self.inner.init(key, x)
            return {"inner": p}, {"inner": s, "ema": jnp.zeros(x.shape[-1])}

        def apply(self, params, state, x, ctx):
            y, s = self.inner.apply(params["inner"], state["inner"], x, ctx)
            new = dict(state, inner=s, ema=0.9 * state["ema"])
            return y, new

    assert has_divergent_buffers(StatefulContainer())  # undeclared own init
    assert not has_divergent_buffers(nn.Sequential(nn.Linear(4)))  # declared container
    # the built-in declarations
    assert has_divergent_buffers(nn.BatchNorm())
    assert not has_divergent_buffers(nn.BatchNorm(sync=True))
    assert not has_divergent_buffers(nn.BatchNorm(track_running_stats=False))
    assert not has_divergent_buffers(nn.Sequential(nn.Conv2d(4, 3), nn.ReLU()))


# --------------------------------------------- transformer-family layers --


def test_layernorm_matches_torch():
    x = np.random.RandomState(7).randn(4, 9, 32).astype(np.float32) * 3 + 1
    layer = nn.LayerNorm()
    params, state = layer.init(KEY, jnp.asarray(x))
    assert params["scale"].shape == (32,) and params["bias"].shape == (32,)
    # non-trivial affine so the test covers scale/bias application too
    params = {
        "scale": jnp.asarray(np.random.RandomState(8).randn(32), jnp.float32),
        "bias": jnp.asarray(np.random.RandomState(9).randn(32), jnp.float32),
    }
    y, state2 = layer.apply(params, state, jnp.asarray(x), ctx_train())
    assert state2 == state  # no buffers, nothing diverges
    ref = F.layer_norm(
        torch.from_numpy(x), (32,),
        torch.from_numpy(np.asarray(params["scale"])),
        torch.from_numpy(np.asarray(params["bias"])),
    ).numpy()
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)
    # train and eval are the same math (per-sample statistics)
    y_eval, _ = layer.apply(params, state, jnp.asarray(x), nn.Context())
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_eval))


def test_layernorm_no_affine_and_no_divergent_buffers():
    x = jnp.asarray(np.random.RandomState(10).randn(2, 8).astype(np.float32))
    layer = nn.LayerNorm(affine=False)
    params, _ = layer.init(KEY, x)
    assert params == {}
    y, _ = layer.apply(params, (), x, nn.Context())
    out = np.asarray(y)
    np.testing.assert_allclose(out.mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.std(-1), 1.0, atol=1e-3)
    assert not layer.divergent_state()


def test_embedding_lookup_and_shape():
    layer = nn.Embedding(10, 6)
    params, state = layer.init(KEY, jnp.zeros((2, 3), jnp.int32))
    assert params["weight"].shape == (10, 6)  # torch (num_embeddings, dim)
    ids = jnp.asarray([[1, 4], [9, 0]], jnp.int32)
    y, _ = layer.apply(params, state, ids, nn.Context())
    assert y.shape == (2, 2, 6)
    np.testing.assert_array_equal(
        np.asarray(y[1, 0]), np.asarray(params["weight"][9])
    )
    assert not layer.divergent_state()
