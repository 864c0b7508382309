"""Collectives over the 8-device CPU mesh — the dist.all_reduce/barrier/broadcast
contracts (SURVEY.md §2b #11, reference multi-GPU-training-torch.py:194-204,245)."""

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpuddp.parallel import collectives as col
from tpuddp.parallel.mesh import DATA_AXIS


def shmap(mesh, fn, in_specs, out_specs):
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    )


def test_all_reduce_sum_matches_dist_all_reduce(mesh):
    x = jnp.arange(8.0)
    out = shmap(mesh, lambda v: col.psum(v), P(DATA_AXIS), P())(x)
    np.testing.assert_allclose(out, np.full((1,), 28.0))


def test_pmean_is_ddp_grad_average(mesh):
    x = jnp.arange(8.0)
    out = shmap(mesh, lambda v: col.pmean(v), P(DATA_AXIS), P())(x)
    np.testing.assert_allclose(out, np.full((1,), 3.5))


def test_all_reduce_pytree_and_ops(mesh):
    tree = {"a": jnp.arange(8.0), "b": jnp.ones(8)}
    out = shmap(mesh, lambda t: col.all_reduce(t, "max"), P(DATA_AXIS), P())(tree)
    np.testing.assert_allclose(out["a"], [7.0])
    np.testing.assert_allclose(out["b"], [1.0])
    out = shmap(mesh, col.pmax, P(DATA_AXIS), P())(jnp.arange(8.0))
    np.testing.assert_allclose(out, [7.0])
    with pytest.raises(ValueError):
        col.all_reduce(jnp.ones(8), "median")


def test_all_gather(mesh):
    x = jnp.arange(8.0)
    out = shmap(mesh, lambda v: col.all_gather(v, tiled=True), P(DATA_AXIS), P(DATA_AXIS))(x)
    # every shard holds the full gathered vector; global shape is 8*8
    assert out.shape == (64,)
    np.testing.assert_allclose(np.asarray(out)[:8], np.arange(8.0))


def test_reduce_scatter(mesh):
    x = jnp.ones((8, 8))
    out = shmap(
        mesh, lambda v: col.reduce_scatter(v.sum(0)), P(DATA_AXIS), P(DATA_AXIS)
    )(x)
    np.testing.assert_allclose(out, np.full(8, 8.0))


def test_ppermute_ring(mesh):
    x = jnp.arange(8.0)
    perm = [(i, (i + 1) % 8) for i in range(8)]
    out = shmap(mesh, lambda v: col.ppermute(v, perm), P(DATA_AXIS), P(DATA_AXIS))(x)
    np.testing.assert_allclose(out, np.roll(np.arange(8.0), 1))


def test_broadcast_from_root(mesh):
    x = jnp.arange(8.0) + 100.0
    out = shmap(mesh, lambda v: col.broadcast(v, root=3), P(DATA_AXIS), P(DATA_AXIS))(x)
    np.testing.assert_allclose(out, np.full(8, 103.0))


def test_axis_index_is_rank(mesh):
    out = shmap(
        mesh,
        lambda: col.axis_index().reshape(1),
        (),
        P(DATA_AXIS),
    )()
    np.testing.assert_array_equal(out, np.arange(8))


def test_finalize_metrics_aggregates_sharded_metrics(mesh):
    # per-device partial sums, as the shard_map train step emits them; the
    # epoch-end path (the reference's five dist.all_reduce calls,
    # multi-GPU-training-torch.py:198-204) is finalize_metrics
    from tpuddp.training.step import finalize_metrics

    parts = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P(DATA_AXIS)))
    assert finalize_metrics({"loss_sum": parts})["loss_sum"] == 28.0


def test_barrier_single_host_noop(mesh):
    col.barrier("test", wait_for=jnp.ones(3))  # must not raise


def test_broadcast_one_to_all_single_process_identity():
    tree = {"w": np.ones(3)}
    assert col.broadcast_one_to_all(tree) is tree
