"""Device mesh + sharding helpers.

The reference's notion of a "world" is N single-GPU processes joined by NCCL
(multi-GPU-training-torch.py:269-279). The TPU-native notion is a
``jax.sharding.Mesh`` over all chips with a named ``"data"`` axis; data
parallelism = batch sharded over that axis, parameters replicated. The axis is
*named* so that later tensor/pipeline axes can be added to the same mesh
without redesign (SURVEY.md §2c build consequence).
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpuddp.parallel import backend as _backend

DATA_AXIS = "data"

# The factored data mesh (comm_topology="hierarchical", parallel/comm.py):
# the SAME replica set, with the axis split ("host", "local") so collectives
# can address the intra-host (ICI) and inter-host (DCN) hops separately —
# outer axis first, so consecutive local devices stay adjacent in the mesh.
HOST_AXIS = "host"
LOCAL_AXIS = "local"


def data_axes(mesh: "Mesh"):
    """The axis name(s) forming ``mesh``'s data-parallel dimension: the flat
    ``"data"`` axis when present, else the full factored axis tuple (the
    hierarchical ``("host", "local")`` split). Every mesh tpuddp builds is
    data-parallel over ALL its axes, so the tuple is the whole name list;
    ``jax.lax`` collectives, ``PartitionSpec`` entries, and ``axis_index``
    all accept the tuple wherever the flat name went."""
    names = tuple(mesh.axis_names)
    if DATA_AXIS in names:
        return DATA_AXIS
    return names if len(names) > 1 else names[0]


def local_mesh_devices(
    world_size: Optional[int] = None, backend: Optional[str] = None
) -> Sequence[jax.Device]:
    """Devices forming the data-parallel world (see backend.resolve_devices)."""
    return _backend.resolve_devices(world_size, backend)


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    axes: Optional[Mapping[str, int]] = None,
    backend: Optional[str] = None,
) -> Mesh:
    """Create a mesh. Default: 1-D mesh over all resolved devices, axis "data".

    ``axes`` maps axis names to sizes, e.g. ``{"data": 4, "model": 2}``; sizes
    must multiply to the device count. Data parallelism only needs the default,
    but the mesh abstraction is N-D from day one.
    """
    if devices is None:
        devices = local_mesh_devices(backend=backend)
    devices = np.asarray(devices, dtype=object)
    if axes is None:
        axes = {DATA_AXIS: devices.size}
    names = tuple(axes.keys())
    sizes = tuple(axes.values())
    if int(np.prod(sizes)) != devices.size:
        raise ValueError(f"mesh axes {dict(axes)} do not tile {devices.size} devices")
    return Mesh(devices.reshape(sizes), names)


def data_mesh(world_size: Optional[int] = None, backend: Optional[str] = None) -> Mesh:
    """1-D data-parallel mesh — the DP world the reference builds with mp.spawn."""
    return make_mesh(local_mesh_devices(world_size, backend))


def hierarchical_mesh(
    world_size: Optional[int] = None,
    hosts: Optional[int] = None,
    backend: Optional[str] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """The factored ``("host", "local")`` data mesh for
    ``comm_topology="hierarchical"``: the same replica set as
    :func:`data_mesh`, with the axis split so the comm hooks can run the
    intra-host f32 reduce-scatter / compressed inter-host exchange /
    all-gather pipeline (parallel/comm.py ``reduce_hierarchical``).

    ``hosts`` (the outer-axis size) defaults to ``jax.process_count()`` on a
    real pod; on a single process (the CPU test rung, or one multi-chip
    host) it defaults to 2 — a SIMULATED host split that keeps the factored
    collectives and the intra/inter byte accounting testable without DCN.
    The world must factor: ``hosts`` has to divide it."""
    if devices is None:
        devices = local_mesh_devices(world_size, backend)
    world = len(devices)
    if hosts is None:
        hosts = jax.process_count() if jax.process_count() > 1 else 2
    hosts = int(hosts)
    if hosts < 2 or world % hosts:
        raise ValueError(
            f"comm_topology='hierarchical' needs a factorable world: "
            f"{hosts} host group(s) do not tile {world} device(s); pick a "
            "world size divisible by the host count (or >= 2 devices on the "
            "simulated single-host split)"
        )
    return make_mesh(devices, axes={HOST_AXIS: hosts, LOCAL_AXIS: world // hosts})


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for parameters/optimizer state: replicated on every device
    (the DDP contract: replica-identical params, multi-GPU-training-torch.py:245)."""
    return NamedSharding(mesh, P())


def replicate(mesh: Mesh, tree):
    """Place a pytree replicated on every mesh device. Works multi-process
    (where a plain device_put cannot target non-addressable devices): a jitted
    identity with replicated out_shardings lets each process contribute its
    (identical — broadcast first!) local copy to the global array."""
    target = NamedSharding(mesh, P())
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    # a leaf that was made replicated on this mesh stays where it is: the
    # jitted identity would copy it, and a state of several GB then peaks at
    # twice its size before the first step (a tracer has no sharding to ask)
    todo = [i for i, leaf in enumerate(leaves) if getattr(leaf, "sharding", None) != target]
    if todo:
        placed = jax.jit(lambda t: t, out_shardings=target)([leaves[i] for i in todo])
        for i, leaf in zip(todo, placed):
            leaves[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, leaves)


def place_like(template, tree):
    """Put ``tree``'s leaves (host arrays out of a checkpoint) where the
    matching mesh-placed leaves of ``template`` live. A resumed run then
    dispatches the very programs a fresh run compiled — same input shardings,
    same compile-cache keys — instead of lowering each step once more for
    uncommitted host inputs. Same jitted identity as :func:`replicate`, so it
    works multi-process; template leaves that are not on a mesh pass through."""
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    leaves = treedef.flatten_up_to(tree)
    on_mesh = [
        i for i, t in enumerate(t_leaves)
        if isinstance(t, jax.Array) and isinstance(t.sharding, NamedSharding)
    ]
    if on_mesh:
        placed = jax.jit(
            lambda xs: xs, out_shardings=[t_leaves[i].sharding for i in on_mesh]
        )([leaves[i] for i in on_mesh])
        for i, leaf in zip(on_mesh, placed):
            leaves[i] = leaf
    return treedef.unflatten(leaves)


def data_sharded(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Sharding for a batch: leading axis split over the data mesh axis
    (the factored axis tuple on a hierarchical mesh)."""
    axis = data_axes(mesh)
    spec = P(axis, *([None] * (ndim - 1))) if ndim > 1 else P(axis)
    return NamedSharding(mesh, spec)


def shard_batch(mesh: Mesh, batch):
    """Place a host batch onto the mesh, split over the data axis.

    Single-process: a plain ``device_put`` with a data-sharded NamedSharding.
    Multi-process: each process passes its *local* shard (what its sampler
    loaded) and the global array is assembled across hosts — the TPU-native
    replacement for N dataloaders feeding N processes.
    """
    axis = data_axes(mesh)

    def _put(x):
        sharding = NamedSharding(mesh, P(axis, *([None] * (x.ndim - 1))))
        if (
            isinstance(x, jax.Array)
            and x.sharding.is_equivalent_to(sharding, x.ndim)
        ):
            return x  # already laid out correctly: no copy, no dispatch
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, np.asarray(x))
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(_put, batch)
