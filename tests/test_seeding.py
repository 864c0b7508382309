"""Rank-aware seeding parity (reference multi-GPU-training-torch.py:54-69) —
the RNG-state probe (reference :180-183) turned into asserts."""

import random

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import PartitionSpec as P

from tpuddp import seeding
from tpuddp.parallel.mesh import DATA_AXIS


def test_ranks_get_distinct_keys():
    k0, base = seeding.set_seed_based_on_rank(rank=0, base_seed=1234)
    k1, _ = seeding.set_seed_based_on_rank(rank=1, base_seed=1234)
    assert base == 1234
    assert not np.array_equal(jax.random.key_data(k0), jax.random.key_data(k1))


def test_python_numpy_seeded_in_reduced_range():
    seeding.set_seed_based_on_rank(rank=2, base_seed=2**40)
    py_draw = random.random()
    np_draw = np.random.rand()
    # replay: same reduced seed + rank must reproduce
    expected_seed = (2**40) % (2**32 - 1) + 2
    random.seed(expected_seed)
    np.random.seed(expected_seed % 2**32)
    assert random.random() == py_draw
    assert np.random.rand() == np_draw


def test_fresh_base_seed_per_run():
    _, a = seeding.set_seed_based_on_rank(rank=0)
    _, b = seeding.set_seed_based_on_rank(rank=0)
    assert a != b  # analog of torch initial_seed varying per spawn


def test_probe_string_mentions_base_seed():
    seeding.set_seed_based_on_rank(rank=0, base_seed=42)
    s = seeding.rng_probe_string()
    assert "base seed: 42" in s
    assert seeding.last_base_seed() == 42


def test_fold_in_axis_index_diverges_per_replica(mesh):
    key, _ = seeding.set_seed_based_on_rank(rank=0, base_seed=0)

    def draw(k):
        k = seeding.fold_in_axis_index(k, DATA_AXIS)
        return jax.random.uniform(k, (1,))

    out = jax.jit(
        shard_map(draw, mesh=mesh, in_specs=None, out_specs=P(DATA_AXIS))
    )(key)
    vals = np.asarray(out)
    assert len(set(vals.tolist())) == 8  # every replica drew differently
