"""Seeded, learnable image batches made on the device in one jitted call.

The class-cluster generator of ``tpuddp/data/synthetic.py`` (a copy of its
arithmetic, so a later PR that edits the program cannot move the yardstick):
``x = mean[label] + 0.5 * noise``, then ``clip(40 x + 128)`` to uint8. The
original draws on the host with numpy, image by image of float32; this one
draws with ``jax.random`` on the device, batch by batch, so that set-up does
not pay seconds of host random numbers. Class means are drawn at no more
than 32x32 and repeated up to the image size, which keeps 1000 classes of
224x224 means at 12 MB and leaves the set as separable as the 32x32 one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_MEAN_HW = 32
_NOISE = 0.5


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


def make_batches(seed: int, n_batches: int, batch: int, shape, num_classes: int,
                 shardings=None):
    """``(images, labels)`` of shapes ``(n_batches, batch, *shape)`` uint8 and
    ``(n_batches, batch)`` int32, a function of ``seed`` alone. ``shardings``
    (a pair, for images and labels) lays them out as the step wants them."""
    make = functools.partial(
        make_seeded_batches, n_batches=n_batches, batch=batch, shape=tuple(shape),
        num_classes=num_classes,
    )
    out = {"out_shardings": tuple(shardings)} if shardings else {}
    return jax.jit(make, **out)(jax.random.key(seed))
