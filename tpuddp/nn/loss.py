"""Losses. CrossEntropyLoss matches the reference's criterion
(multi-GPU-training-torch.py:248): softmax cross-entropy on integer labels,
default mean reduction. A ``weights`` argument supports masked (padded) final
batches so eval shapes stay static on TPU while the sample-weighted metric math
of the reference (:129-132,198-206) stays exact.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    reduction: str = "mean",
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Softmax cross-entropy. logits: (N, C) float, labels: (N,) int.

    reduction: 'mean' (weighted mean), 'sum', or 'none'.
    weights: optional per-sample weights/mask (N,).

    Higher-rank logits (e.g. a language model's ``(B, T, V)`` with ``(B, T)``
    labels/weights) flatten to per-token rows first — the token IS the sample
    in that regime, so the weighted metric math applies unchanged
    (``reduction='none'`` then returns the flattened per-token losses).
    """
    logits = logits.astype(jnp.float32)  # stable softmax even for bf16 nets
    if logits.ndim > 2:
        if weights is not None:
            from tpuddp.nn.sequence import per_token_weights

            weights = per_token_weights(weights, labels.shape).reshape(-1)
        logits = logits.reshape(-1, logits.shape[-1])
        labels = labels.reshape(-1)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    losses = logz - true_logit
    if weights is not None:
        losses = losses * weights
    if reduction == "none":
        return losses
    if reduction == "sum":
        return jnp.sum(losses)
    if reduction == "mean":
        if weights is not None:
            # an all-padding (weight-0) batch means 0 loss, not 0/0 — the
            # grad-accumulation tail pads whole micro-batches to a static
            # cycle length (training/loop.py) and their grads must vanish
            denom = jnp.sum(weights)
            denom = jnp.where(denom == 0, 1.0, denom)
        else:
            denom = losses.shape[0]
        return jnp.sum(losses) / denom
    raise ValueError(f"unknown reduction {reduction!r}")


class CrossEntropyLoss:
    """Callable criterion object, mirroring ``nn.CrossEntropyLoss()``."""

    def __init__(self, reduction: str = "mean"):
        self.reduction = reduction

    def __call__(self, logits, labels, weights=None):
        # Managed-API hook: applied to a prepared model's deferred outputs
        # (tpuddp.accelerate.LazyForward), return a deferred loss that
        # Accelerator.backward executes as one fused fwd+bwd.
        bind = getattr(logits, "_tpuddp_bind_loss", None)
        if bind is not None:
            return bind(self, labels, weights)
        return cross_entropy(logits, labels, self.reduction, weights)
