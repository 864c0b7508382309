"""Plain reference for ``resnet50_imagenet224``: ResNet-50 (He et al. 2015,
arXiv:1512.03385, Table 1) in NHWC. 7x7/2 stem, BatchNorm, ReLU, 3x3/2
max-pool; four stages of bottleneck blocks (1x1 reduce, 3x3, 1x1 expand x4,
each followed by BatchNorm, ReLU after the first two and after the residual
sum); global average pool; linear head. BatchNorm is in training mode:
each layer normalises with its batch's mean and biased variance (eps from
the configuration), in float32.

Departures: the 3x3 convolution of a downsampling block carries the stride
(torchvision's v1.5; the paper strides the first 1x1). Running statistics
are not updated: they do not enter the loss or the gradients of a training
step, which is all the check compares.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import _plain


def _batchnorm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride, eps):
    h = jax.nn.relu(_batchnorm(_plain.conv(x, p["conv1"]["weight"], 1, 0), p["bn1"], eps))
    h = jax.nn.relu(_batchnorm(_plain.conv(h, p["conv2"]["weight"], stride, 1), p["bn2"], eps))
    h = _batchnorm(_plain.conv(h, p["conv3"]["weight"], 1, 0), p["bn3"], eps)
    if "down_conv" in p:
        x = _batchnorm(_plain.conv(x, p["down_conv"]["weight"], stride, 0), p["down_bn"], eps)
    return jax.nn.relu(h + x)


def make_forward(config):
    widths, eps = config["widths"], config["batchnorm"]["eps"]
    stem = widths["stem"]
    strides = [
        s["stride"] if b == 0 else 1
        for s in widths["stages"] for b in range(s["blocks"])
    ]

    def forward(params, x):
        # the system's parameters: a tuple over its layers, empty for the
        # ones without any: stem conv, stem BatchNorm, the blocks, the head
        layers = [p for p in params if p]
        conv1, bn1, blocks, head = layers[0], layers[1], layers[2:-1], layers[-1]
        if len(blocks) != len(strides):
            raise ValueError(f"{len(blocks)} blocks of parameters, configuration has {len(strides)}")
        x = _plain.conv(x, conv1["weight"], stem["stride"], stem["pad"])
        x = jax.nn.relu(_batchnorm(x, bn1, eps))
        x = _plain.max_pool(x, *stem["pool"])
        for p, stride in zip(blocks, strides):
            x = _bottleneck(x, p, stride, eps)
        x = jnp.mean(x, axis=(1, 2))
        return x @ head["weight"] + head["bias"]

    return forward


def train_steps(config, params, model_state, batches):
    del model_state  # running statistics: see the module's note
    return _plain.train_steps(config, make_forward(config), params, batches)
