"""Plain reference for ``alexnet_cifar224``: torchvision's AlexNet
(Krizhevsky 2014, arXiv:1404.5997) in NHWC, five convolutions with ReLU and
three 3x3/2 max-pools, a 6x6 adaptive average pool (the identity at 224,
where the map is already 6x6), and a 9216-4096-4096-classes classifier. Dropout is
off: the check steps run without it on both sides.

Departure from torchvision: the 6x6x256 map is flattened in NHWC order, as
the system lays it out, not NCHW; with weights drawn from a seed that is a
fixed permutation of the first classifier matrix's rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import _plain


def _weights(params):
    """The system hands its parameters as a tuple over its layers, empty for
    layers without any; in order they are the five convolutions and the three
    linear layers."""
    leaves = [p for p in params if isinstance(p, dict) and "weight" in p]
    return leaves[:5], leaves[5:]


def _adaptive_avg_pool(x, out: int):
    """torch's AdaptiveAvgPool2d: output bin i averages input rows
    floor(i*N/out) up to ceil((i+1)*N/out). The identity on a 6x6 map."""
    _, h, w, _ = x.shape
    edges = lambda i, n: ((i * n) // out, -(-((i + 1) * n) // out))
    rows = []
    for i in range(out):
        (h0, h1), cols = edges(i, h), []
        for j in range(out):
            w0, w1 = edges(j, w)
            cols.append(jnp.mean(x[:, h0:h1, w0:w1, :], axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)


def make_forward(config):
    widths = config["widths"]

    def forward(params, x):
        convs, linears = _weights(params)
        for spec, p in zip(widths["conv"], convs):
            x = _plain.conv(x, p["weight"], spec["stride"], spec["pad"]) + p["bias"]
            x = jax.nn.relu(x)
            if spec["pool"]:
                x = _plain.max_pool(x, *spec["pool"])
        x = _adaptive_avg_pool(x, widths["avgpool_to"])
        x = x.reshape(x.shape[0], -1)
        for i, p in enumerate(linears):
            x = x @ p["weight"] + p["bias"]
            if i < len(linears) - 1:
                x = jax.nn.relu(x)
        return x

    return forward


def train_steps(config, params, model_state, batches):
    del model_state  # AlexNet has no buffers
    return _plain.train_steps(config, make_forward(config), params, batches)
