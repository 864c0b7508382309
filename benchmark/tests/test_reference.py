"""The plain references against the system's own layers at tiny widths, on
the CPU in float32: same parameters, same batch, same loss and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from benchmark.reference import _plain
from tpuddp import nn
from tpuddp.models.resnet import Bottleneck, _resnet
from tpuddp.nn.core import Context


def _system_loss(model, params, mstate, x, y):
    logits, _ = model.apply(params, mstate, x, Context(train=True))
    return nn.CrossEntropyLoss()(logits, y)


def _compare(model, config, forward, x_uint8, y, dtype=jnp.float32, grad_rtol=1e-4):
    x = _plain.preprocess(config, x_uint8).astype(dtype)
    params, mstate = model.init(jax.random.key(0), x)
    ours, ours_grad = jax.value_and_grad(
        lambda p: _system_loss(model, p, mstate, x, y)
    )(params)
    ref, ref_grad = jax.value_and_grad(
        lambda p: _plain.cross_entropy_mean(forward(p, x), y)
    )(params)
    np.testing.assert_allclose(ours, ref, rtol=1e-5)
    # leaf by leaf, the distance between the gradients against their size
    for a, b in zip(jax.tree_util.tree_leaves(ours_grad), jax.tree_util.tree_leaves(ref_grad)):
        assert float(jnp.linalg.norm(a - b)) <= grad_rtol * float(jnp.linalg.norm(b)) + 1e-7


def _config(name):
    workload = next(
        w["name"] for w in cells.load_benchmark()["workloads"] if w["config"] == name
    )
    return cells.load_cell(workload).config


def test_alexnet_reference_matches_the_layers_at_tiny_widths():
    """AlexNet's layer pattern with 8-16-24-16-16 channels and a 64-64
    classifier at 80x80 (a 1x1 map before the adaptive pool, so the pool's
    bin arithmetic is exercised, not only its identity case)."""
    config = _config("alexnet_cifar224")
    channels = (8, 16, 24, 16, 16)
    config["widths"]["conv"] = [
        {**spec, "out": c} for spec, c in zip(config["widths"]["conv"], channels)
    ]
    config["widths"]["classifier"] = [64, 64]
    config["input"]["resize_to"] = 80
    layers = []
    for spec in config["widths"]["conv"]:
        layers += [
            nn.Conv2d(spec["out"], spec["kernel"], strides=spec["stride"], padding=spec["pad"]),
            nn.ReLU(),
        ]
        if spec["pool"]:
            layers.append(nn.MaxPool2d(spec["pool"][0], strides=spec["pool"][1]))
    layers += [
        nn.AdaptiveAvgPool2d((6, 6)), nn.Flatten(), nn.Linear(64), nn.ReLU(),
        nn.Linear(64), nn.ReLU(), nn.Linear(10),
    ]
    model = nn.Sequential(*layers)
    rng = np.random.RandomState(0)
    x = rng.randint(0, 256, (4, 32, 32, 3)).astype(np.uint8)
    y = jnp.asarray(rng.randint(0, 10, 4))
    forward = cells.load_module("reference", "alexnet_cifar224").make_forward(config)
    _compare(model, config, forward, x, y)


def _widen(module):
    if hasattr(module, "dtype"):
        module.dtype = jnp.float64
    for child in module.children():
        _widen(child)


def test_resnet_reference_matches_the_layers_at_one_block_a_stage():
    """Published bottleneck widths, one block a stage, 64x64 input: every
    kind of block (projected, strided) once, BatchNorm in training mode.

    In float64: the last stage normalises over 2x2 positions of 16 samples,
    and in float32 the rounding that point amplifies moves every earlier
    gradient by the same 1.3%, the same formulas notwithstanding (they agree
    to 4e-8 here)."""
    config = _config("resnet50_imagenet224")
    config["widths"]["stages"] = [{**s, "blocks": 1} for s in config["widths"]["stages"]]
    config["model"]["num_classes"] = 10
    rng = np.random.RandomState(1)
    x = rng.randint(0, 256, (16, 64, 64, 3)).astype(np.uint8)
    forward = cells.load_module("reference", "resnet50_imagenet224").make_forward(config)
    with jax.enable_x64(True):
        model = _resnet((1, 1, 1, 1), 10, False, False, block=Bottleneck)
        _widen(model)
        y = jnp.asarray(rng.randint(0, 10, 16))
        _compare(model, config, forward, x, y, dtype=jnp.float64, grad_rtol=1e-6)


def test_adam_is_the_textbook_update():
    opt = {"lr": 0.1, "betas": [0.9, 0.999], "eps": 1e-8}
    p, g = {"w": jnp.asarray([1.0, -2.0])}, {"w": jnp.asarray([0.5, -0.25])}
    zeros = {"w": jnp.zeros(2)}
    new, m, v = _plain.adam_step(p, g, zeros, zeros, 1, opt)
    # after one step m-hat = g and v-hat = g^2: the update is lr * sign(g)
    np.testing.assert_allclose(new["w"], [0.9, -1.9], rtol=1e-6)
    np.testing.assert_allclose(m["w"], [0.05, -0.025], rtol=1e-6)
    np.testing.assert_allclose(v["w"], [0.00025, 0.0000625], rtol=1e-6)


@pytest.mark.parametrize("n_in,n_out", [(13, 6), (6, 6), (1, 6), (7, 3)])
def test_adaptive_pool_bins(n_in, n_out):
    module = cells.load_module("reference", "alexnet_cifar224")
    x = jnp.arange(n_in * n_in, dtype=jnp.float32).reshape(1, n_in, n_in, 1)
    ours, _ = nn.AdaptiveAvgPool2d((n_out, n_out)).apply((), (), x, Context())
    np.testing.assert_allclose(module._adaptive_avg_pool(x, n_out), ours, rtol=1e-6)
