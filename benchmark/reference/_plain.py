"""What the plain references share: the input pipeline, the loss, textbook
Adam and the loop that steps them. Straightforward ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bfloat16 passes); nothing here comes from ``tpuddp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def preprocess(config, x_uint8):
    """uint8 NHWC -> float32 in [0, 1], normalised per channel, then
    resized bilinearly (half-pixel centres, as torchvision's Resize and
    ``jax.image.resize`` have it) where the configuration says so. No flip:
    the check switches the random flip off on both sides."""
    inp = config["input"]
    x = x_uint8.astype(jnp.float32) / 255.0
    x = (x - jnp.asarray(inp["mean"], jnp.float32)) / jnp.asarray(inp["std"], jnp.float32)
    size = inp["resize_to"]
    if size is not None and x.shape[1] != size:
        n, _, _, c = x.shape
        x = jax.image.resize(x, (n, size, size, c), method="bilinear")
    return x


def conv(x, w, stride: int, pad: int):
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def max_pool(x, window: int, stride: int, pad: int = 0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)],
    )


def cross_entropy_mean(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def adam_step(params, grads, m, v, t, opt):
    """Kingma & Ba 2015, Algorithm 1, with torch's placement of epsilon
    (outside the square root of the bias-corrected second moment)."""
    b1, b2 = opt["betas"]
    tmap = jax.tree_util.tree_map
    m = tmap(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = tmap(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    mhat = tmap(lambda m_: m_ / (1 - b1 ** t), m)
    vhat = tmap(lambda v_: v_ / (1 - b2 ** t), v)
    new = tmap(
        lambda p, mh, vh: p - opt["lr"] * mh / (jnp.sqrt(vh) + opt["eps"]),
        params, mhat, vhat,
    )
    return new, m, v


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree_util.tree_leaves(tree)))


def train_steps(config, forward, params, batches):
    """Step ``params`` through ``batches`` (a list of ``(x_uint8, labels)``)
    on one worker: per step the loss before the update and the global norm of
    the parameter change. ``forward(params, x) -> logits`` is the
    configuration's plain forward pass in training mode."""
    opt = config["optimizer"]

    def loss_of(p, x, y):
        return cross_entropy_mean(forward(p, preprocess(config, x)), y)

    @jax.jit
    def run(params, xs, ys):
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, norms = [], []
        for t in range(len(batches)):
            loss, grads = jax.value_and_grad(loss_of)(params, xs[t], ys[t])
            new, m, v = adam_step(params, grads, m, v, t + 1, opt)
            losses.append(loss)
            norms.append(_global_norm(jax.tree_util.tree_map(jnp.subtract, new, params)))
            params = new
        return jnp.stack(losses), jnp.stack(norms)

    with jax.default_matmul_precision("highest"):
        losses, norms = run(
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params),
            jnp.stack([jnp.asarray(x) for x, _ in batches]),
            jnp.stack([jnp.asarray(y) for _, y in batches]),
        )
    return [float(a) for a in losses], [float(a) for a in norms]
