"""FusedAdam Pallas kernel — must match tpuddp.optim.Adam (== torch.optim.Adam)
exactly. These tests pass ``interpret=True`` themselves: the kernel never
guesses the interpreter from the platform."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuddp.ops import FusedAdam
from tpuddp.optim import Adam


def tree_maxdiff(a, b):
    return max(
        float(jnp.abs(x - y).max())
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


@pytest.fixture()
def problem():
    rng = np.random.RandomState(0)
    params = {
        "w": jnp.asarray(rng.randn(37, 50).astype(np.float32)),
        "b": jnp.asarray(rng.randn(5).astype(np.float32)),  # < one lane
        "big": jnp.asarray(rng.randn(700, 130).astype(np.float32)),  # multi-block
    }
    grads = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)), params
    )
    return params, grads


def test_fused_matches_adam_over_steps(problem):
    params, grads = problem
    ref = Adam(1e-2)
    fused = FusedAdam(1e-2, impl="pallas", interpret=True)
    rs, fs = ref.init(params), fused.init(params)
    rp, fp = params, params
    for _ in range(3):
        rp, rs = ref.update(grads, rs, rp)
        fp, fs = fused.update(grads, fs, fp)
    assert tree_maxdiff(rp, fp) < 1e-5
    assert tree_maxdiff(rs.m, fs.m) < 1e-6
    assert tree_maxdiff(rs.v, fs.v) < 1e-6
    assert int(fs.step) == 3


def test_impl_xla_inherits_adam(problem):
    params, grads = problem
    a, b = Adam(1e-3), FusedAdam(1e-3, impl="xla")
    pa, _ = a.update(grads, a.init(params), params)
    pb, _ = b.update(grads, b.init(params), params)
    assert tree_maxdiff(pa, pb) == 0.0


def test_impl_auto_falls_back_off_tpu(problem):
    params, grads = problem
    opt = FusedAdam(1e-3, impl="auto")
    # on CPU default backend this must route to XLA math and still be correct
    p, s = opt.update(grads, opt.init(params), params)
    ref = Adam(1e-3)
    rp, _ = ref.update(grads, ref.init(params), params)
    assert tree_maxdiff(p, rp) < 1e-6


def test_invalid_impl():
    with pytest.raises(ValueError):
        FusedAdam(impl="cuda")


def test_fused_in_jitted_train_step(problem):
    """The kernel must compose with jit + value_and_grad like any optimizer."""
    params, _ = problem
    fused = FusedAdam(1e-2, impl="pallas", interpret=True)
    state = fused.init(params)

    def loss_fn(p):
        return sum(jnp.sum(l**2) for l in jax.tree_util.tree_leaves(p))

    @jax.jit
    def step(p, s):
        g = jax.grad(loss_fn)(p)
        return fused.update(g, s, p)

    p1, s1 = step(params, state)
    assert float(loss_fn(p1)) < float(loss_fn(params))
