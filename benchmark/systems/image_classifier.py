"""The image-classifier system under test, built from a cell's files: the
registry model, the device-side augment, Adam and the
``DistributedDataParallel`` wrap on the cell's mesh, exactly as
``train_native.py`` builds them; a train state whose parameters are made on
the device from the seed in one jitted call; and its seeded, learnable uint8
image batches, made on the device too. A configuration names this file as
its ``"system"``; everything the harness asks of a system is below, and
nothing outside ``benchmark/systems/`` knows what a batch looks like.

What the harness asks of a system's file: ``build_ddp``, ``init_variables``
and ``init_state``; ``make_batches`` (the step's arrays but the weights,
stacked, laid out as asked); ``unit_weights`` (one per counted unit: a sample
here, a token for a sequence model); ``shrunk`` (the configuration at a size
the CPU tests run).

The batches are the class-cluster generator of ``tpuddp/data/synthetic.py``
(a copy of its arithmetic, so a later PR that edits the program cannot move
the yardstick): ``x = mean[label] + 0.5 * noise``, then ``clip(40 x + 128)``
to uint8. The original draws on the host with numpy, image by image of
float32; this one draws with ``jax.random`` on the device, batch by batch, so
that set-up does not pay seconds of host random numbers. Class means are
drawn at no more than 32x32 and repeated up to the image size, which keeps
1000 classes of 224x224 means at 12 MB and leaves the set as separable as
the 32x32 one.
"""

from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import nn, optim
from tpuddp.data.transforms import make_train_augment
from tpuddp.models import load_model
from tpuddp.parallel.ddp import DistributedDataParallel


_MEAN_HW = 32
_NOISE = 0.5


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


def make_seeded_batches(key, *, n_batches, batch, shape, num_classes):
    h, w, c = shape
    mh, mw = min(h, _MEAN_HW), min(w, _MEAN_HW)
    if h % mh or w % mw:
        raise ValueError(f"image size {shape} is not a multiple of {mh}x{mw}")
    mean_key, label_key, noise_key = jax.random.split(key, 3)
    means = jax.random.normal(mean_key, (num_classes, mh, mw, c), jnp.float32)
    labels = jax.random.randint(label_key, (n_batches, batch), 0, num_classes, jnp.int32)

    def one(args):
        k, y = args
        mean = jnp.repeat(jnp.repeat(means[y], h // mh, axis=1), w // mw, axis=2)
        x = mean + _NOISE * jax.random.normal(k, (batch, *shape), jnp.float32)
        return jnp.clip(x * 40 + 128, 0, 255).astype(jnp.uint8)

    images = jax.lax.map(one, (jax.random.split(noise_key, n_batches), labels))
    return images, labels


def make_batches(config, seed: int, n_batches: int, batch: int, layout=None):
    """``(images, labels)`` of shapes ``(n_batches, batch, *shape)`` uint8 and
    ``(n_batches, batch)`` int32, a function of ``seed`` alone. ``layout``
    (array rank -> sharding) lays them out as the step wants them."""
    shape = tuple(config["input"]["shape"])
    make = functools.partial(
        make_seeded_batches, n_batches=n_batches, batch=batch, shape=shape,
        num_classes=config["model"]["num_classes"],
    )
    out = {"out_shardings": (layout(2 + len(shape)), layout(2))} if layout else {}
    return jax.jit(make, **out)(jax.random.key(seed))


def unit_weights(config, *leading: int):
    """Weight 1 for every unit the step counts: one a sample."""
    del config
    return np.ones(leading, np.float32)


def shrunk(config):
    """The configuration at a size the CPU runs in seconds: same model, same
    code paths, small images (AlexNet's stem needs 63 pixels at least)."""
    cfg = copy.deepcopy(config)
    if cfg["input"]["resize_to"]:
        cfg["input"]["resize_to"] = 64
    else:
        cfg["input"]["shape"] = [32, 32, 3]
    return cfg
