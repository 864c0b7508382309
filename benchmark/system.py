"""Build the system under test from a cell's files: the registry model, the
device-side augment, Adam and the ``DistributedDataParallel`` wrap on the
cell's mesh, exactly as ``train_native.py`` builds them, and a train state
whose parameters are made on the device from the seed in one jitted call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import nn, optim
from tpuddp.data.transforms import make_train_augment
from tpuddp.models import load_model
from tpuddp.parallel import make_mesh
from tpuddp.parallel.ddp import DistributedDataParallel


def make_mesh_for(cell, devices):
    axes = {k: int(v) for k, v in cell.traffic["mesh"].items()}
    if int(np.prod(list(axes.values()))) != cell.chips:
        raise ValueError(
            f"traffic {cell.traffic_name!r} lays out a mesh {axes} but the "
            f"cell has {cell.chips} chip(s)"
        )
    return make_mesh(list(devices)[: cell.chips], axes)


def model_input_hw(config) -> int:
    return config["input"]["resize_to"] or config["input"]["shape"][0]


def build_ddp(cell, mesh, *, check: bool = False):
    """The DDP wrap of the cell's configuration. ``check=True`` builds the
    variant the correctness check steps through: the configuration's
    ``check.model_kwargs`` (dropout off; same parameter shapes) and no
    random flip, so that the plain reference sees the same inputs."""
    cfg = cell.config
    kwargs = dict(cfg["model"]["kwargs"])
    if check:
        kwargs.update(cfg["check"]["model_kwargs"])
    model = load_model(
        cfg["model"]["registry_name"], cfg["model"]["num_classes"], **kwargs
    )
    inp = cfg["input"]
    augment = make_train_augment(
        size=inp["resize_to"],
        flip=cfg["check"]["flip"] if check else inp["flip"],
        mean=inp["mean"], std=inp["std"],
        compute_dtype=jnp.dtype(cfg["compute_dtype"]),
    )
    opt = cfg["optimizer"]
    if opt["name"] != "adam":
        raise ValueError(f"optimizer {opt['name']!r}: the benchmark builds adam only")
    optimizer = optim.Adam(
        opt["lr"], betas=tuple(opt["betas"]), eps=opt["eps"],
        state_dtype=None if opt["state_dtype"] == "float32" else opt["state_dtype"],
    )
    ddp = DistributedDataParallel(
        model, optimizer, nn.CrossEntropyLoss(), mesh=mesh, mode="shard_map",
        augment=augment, **cell.traffic.get("ddp", {}),
    )
    return model, ddp


def init_variables(model, config, seed: int):
    """``(params, model_state)`` made on the device from ``seed`` in one
    jitted call, in the parameter type the configuration states. Calling it
    again with the same seed gives the same values, which is how the
    correctness check gets its copy of the initialisation without holding
    one through the window."""
    hw = model_input_hw(config)
    sample = jax.ShapeDtypeStruct((1, hw, hw, config["input"]["shape"][2]), jnp.float32)

    def init_model_variables(key):
        return model.init(key, sample)

    params, mstate = jax.jit(init_model_variables)(jax.random.key(seed))
    want = jnp.dtype(config["param_dtype"])
    for leaf in jax.tree_util.tree_leaves(params):
        if leaf.dtype != want:
            raise ValueError(f"parameter of dtype {leaf.dtype}, configuration says {want}")
    return params, mstate


def init_state(model, ddp, config, seed: int, variables=None):
    """The replicated train state on ``ddp``'s mesh; ``variables`` where the
    caller has already made them (the check keeps a host copy first)."""
    params, mstate = variables or init_variables(model, config, seed)
    hw = model_input_hw(config)
    return ddp.init_state(
        jax.random.key(seed), jnp.zeros((1, hw, hw, config["input"]["shape"][2])),
        params=params, model_state=mstate,
    )
