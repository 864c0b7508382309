"""Mesh abstraction: named axes, N-D tiling (the TP/PP-ready design from
SURVEY.md §2c's build consequence), batch placement."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpuddp.parallel import DATA_AXIS, data_sharded, make_mesh, replicated
from tpuddp.parallel.mesh import replicate, shard_batch


def test_default_mesh_is_1d_data(cpu_devices):
    mesh = make_mesh(cpu_devices)
    assert mesh.axis_names == (DATA_AXIS,)
    assert mesh.devices.shape == (8,)


def test_nd_mesh_axes(cpu_devices):
    mesh = make_mesh(cpu_devices, axes={"data": 4, "model": 2})
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.shape == (4, 2)
    assert mesh.shape["model"] == 2


def test_mesh_axes_must_tile_devices(cpu_devices):
    with pytest.raises(ValueError, match="do not tile"):
        make_mesh(cpu_devices, axes={"data": 3})


def test_sharding_helpers(cpu_devices):
    mesh = make_mesh(cpu_devices)
    assert replicated(mesh).spec == P()
    assert data_sharded(mesh).spec == P(DATA_AXIS)
    assert data_sharded(mesh, ndim=3).spec == P(DATA_AXIS, None, None)


def test_shard_batch_places_disjoint_shards(cpu_devices):
    mesh = make_mesh(cpu_devices)
    x = np.arange(16 * 2, dtype=np.float32).reshape(16, 2)
    placed = shard_batch(mesh, x)
    assert placed.sharding.spec == P(DATA_AXIS, None)
    shards = placed.addressable_shards
    assert len(shards) == 8
    np.testing.assert_array_equal(np.asarray(shards[0].data), x[:2])
    np.testing.assert_array_equal(np.asarray(shards[7].data), x[14:])


def test_replicate_places_full_copy_everywhere(cpu_devices):
    mesh = make_mesh(cpu_devices[:4])
    tree = {"w": jnp.arange(6.0)}
    placed = replicate(mesh, tree)
    assert placed["w"].sharding.spec == P()
    assert len(placed["w"].addressable_shards) == 4
    for s in placed["w"].addressable_shards:
        np.testing.assert_array_equal(np.asarray(s.data), np.arange(6.0))


def test_replicate_leaves_replicated_leaves_where_they_are(cpu_devices):
    """A leaf born replicated on the mesh is not copied (a state of several GB
    must not peak at twice its size); host arrays beside it are placed; and
    under ``jax.eval_shape`` tracers, which have no sharding to ask, pass."""
    import jax

    mesh = make_mesh(cpu_devices[:2])
    born = jax.device_put(jnp.arange(6.0), replicated(mesh))
    placed = replicate(mesh, {"born": born, "host": np.ones(3, np.float32)})
    assert placed["born"] is born
    assert placed["host"].sharding == replicated(mesh)
    shapes = jax.eval_shape(lambda t: replicate(mesh, t), {"w": jnp.zeros((2, 3))})
    assert shapes["w"].shape == (2, 3)
