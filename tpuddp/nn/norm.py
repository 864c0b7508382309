"""BatchNorm with optional cross-replica statistic synchronization.

This owns the SyncBatchNorm contract the reference documents but does not code
(README.md:79-81; SURVEY.md §2b #16): under data parallelism, per-device batch
statistics are biased toward the local shard, so ``sync=True`` computes the
batch mean / mean-of-squares with ``lax.pmean`` over the ``"data"`` mesh axis
before normalizing — every replica then normalizes with *global*-batch
statistics, exactly what ``torch.nn.SyncBatchNorm`` does with its CUDA kernels,
here as two fused XLA collectives over ICI.

torch-parity details kept: momentum 0.1 (new-stat weight), eps 1e-5, biased
variance for normalization but **unbiased** variance for the running buffer.

Two honesty details beyond torch:

- **Padded rows are excluded from batch statistics.** tpuddp pads the final
  partial batch to a static shape with weight-0 rows (TPU-first: no ragged
  recompiles); when the forward ``Context`` carries ``sample_weight``, the
  batch mean/var are weighted sums so padding cannot bias the running stats
  (torch never sees padded rows because it feeds a ragged last batch).
- ``stable_var=True`` computes the variance two-pass (``E[(x-mean)^2]``)
  instead of the single-pass ``E[x^2]-E[x]^2``, which is cancellation-prone
  for large-mean activations; sync mode then costs a second ``pmean``.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from tpuddp.nn.core import Context, Module, Sequential


class BatchNorm(Module):
    """Batch normalization over all axes except the last (features).

    ``sync``: if True, batch statistics are averaged across the data-parallel
    axis (``ctx.axis_name``) — the SyncBatchNorm behavior. If False (default,
    matching plain ``nn.BatchNorm2d``), statistics are local to the replica.
    """

    def __init__(
        self,
        momentum: float = 0.1,
        eps: float = 1e-5,
        affine: bool = True,
        track_running_stats: bool = True,
        sync: bool = False,
        stable_var: bool = False,
        dtype=jnp.float32,
    ):
        self.momentum = momentum
        self.eps = eps
        self.affine = affine
        self.track_running_stats = track_running_stats
        self.sync = sync
        self.stable_var = stable_var
        self.dtype = dtype

    def init(self, key, x):
        features = x.shape[-1]
        params = (
            {
                "scale": jnp.ones((features,), self.dtype),
                "bias": jnp.zeros((features,), self.dtype),
            }
            if self.affine
            else {}
        )
        state = (
            {
                "mean": jnp.zeros((features,), self.dtype),
                "var": jnp.ones((features,), self.dtype),
            }
            if self.track_running_stats
            else {}
        )
        return params, state

    def apply(self, params, state, x, ctx: Context):
        reduce_axes = tuple(range(x.ndim - 1))
        use_batch_stats = ctx.train or not self.track_running_stats

        if use_batch_stats:
            xs = x.astype(self.dtype)  # stats accumulate in f32 even for bf16
            ax = ctx.axis_name if self.sync else None
            w = ctx.sample_weight
            if w is not None:
                # padded (weight-0) rows are excluded from the statistics
                wb = jnp.reshape(
                    w.astype(self.dtype), (-1,) + (1,) * (x.ndim - 1)
                )
                spatial = x.size // (x.shape[0] * x.shape[-1])
                count = jnp.sum(wb) * spatial
                sum_x = jnp.sum(xs * wb, axis=reduce_axes)
            else:
                wb = None
                count = jnp.asarray(float(x.size // x.shape[-1]), self.dtype)
                sum_x = jnp.sum(xs, axis=reduce_axes)

            if self.stable_var:
                # two-pass: mean first, then E[(x-mean)^2] — no cancellation
                if ax is not None:
                    sum_x, count = lax.pmean((sum_x, count), ax)
                denom = jnp.maximum(count, 1.0)
                mean = sum_x / denom
                dev = jnp.square(xs - mean)
                sum_dev = jnp.sum(
                    dev * wb if wb is not None else dev, axis=reduce_axes
                )
                if ax is not None:
                    sum_dev = lax.pmean(sum_dev, ax)
                var = sum_dev / denom  # biased, used for normalization
            else:
                xsq = jnp.square(xs)
                sum_x2 = jnp.sum(
                    xsq * wb if wb is not None else xsq, axis=reduce_axes
                )
                if ax is not None:
                    sum_x, sum_x2, count = lax.pmean((sum_x, sum_x2, count), ax)
                denom = jnp.maximum(count, 1.0)
                mean = sum_x / denom
                var = sum_x2 / denom - jnp.square(mean)  # biased

            new_state = state
            if self.track_running_stats and ctx.train:
                m = self.momentum
                # total element count behind the stats (all replicas when sync)
                n = denom * (lax.axis_size(ax) if ax is not None else 1)
                unbiased = var * (n / jnp.maximum(n - 1.0, 1.0))
                # a fully-padded (count==0) shard must leave the running
                # buffers untouched, not decay them toward mean=0/var=0
                has_data = count > 0
                new_state = {
                    "mean": jnp.where(
                        has_data, (1 - m) * state["mean"] + m * mean, state["mean"]
                    ),
                    "var": jnp.where(
                        has_data, (1 - m) * state["var"] + m * unbiased, state["var"]
                    ),
                }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
            xs = x.astype(self.dtype)

        inv = lax.rsqrt(var + self.eps)
        y = (xs - mean) * inv
        if self.affine:
            y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), new_state

    def divergent_state(self) -> bool:
        # running statistics accumulate the LOCAL shard's batches unless
        # cross-replica synced — the canonical divergent buffer
        return self.track_running_stats and not self.sync


class LayerNorm(Module):
    """Layer normalization over the last (feature) axis — the transformer
    family's norm (tpuddp/models/transformer.py). Per-sample statistics, so
    unlike :class:`BatchNorm` there are no running buffers, nothing diverges
    across replicas, and train/eval are the same math.

    torch parity: ``nn.LayerNorm(features)`` defaults — eps 1e-5, elementwise
    affine, biased variance. Statistics accumulate in f32 even for bf16
    activations (the BatchNorm convention above)."""

    def __init__(self, eps: float = 1e-5, affine: bool = True, dtype=jnp.float32):
        self.eps = eps
        self.affine = affine
        self.dtype = dtype

    def init(self, key, x):
        features = x.shape[-1]
        params = (
            {
                "scale": jnp.ones((features,), self.dtype),
                "bias": jnp.zeros((features,), self.dtype),
            }
            if self.affine
            else {}
        )
        return params, ()

    def apply(self, params, state, x, ctx: Context):
        xs = x.astype(self.dtype)
        mean = jnp.mean(xs, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xs - mean), axis=-1, keepdims=True)  # biased
        y = (xs - mean) * lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["scale"] + params["bias"]
        return y.astype(x.dtype), state

    def divergent_state(self) -> bool:
        return False  # parameters only, no buffers


def has_divergent_buffers(module: Module) -> bool:
    """True when the module tree contains a buffer that *diverges across
    replicas* under data parallelism. Used by the DDP step builder to refuse
    ``sync_buffers="none"`` configs that would publish per-replica-divergent
    buffers as replicated.

    The judgment is the :meth:`Module.divergent_state` protocol, so it holds
    by construction: ``divergent_state`` speaks for a module's OWN buffers
    (children are always walked separately), and ANY module that creates
    variables (overrides ``init``) — leaf or container — without declaring
    its divergence is conservatively treated as divergent. A future stateful
    layer cannot silently bypass the validation by not being special-cased
    here; built-in variable-creating modules (Linear, Conv2d, Sequential,
    BasicBlock, BatchNorm) all declare."""
    own = module.divergent_state()
    if own:
        return True
    if own is None and type(module).init is not Module.init:
        # undeclared variable-creating module: could hold divergent state
        return True
    return any(has_divergent_buffers(c) for c in module.children())


def convert_sync_batchnorm(module: Module) -> Module:
    """Flip every BatchNorm in a module tree to ``sync=True`` — API parity with
    ``torch.nn.SyncBatchNorm.convert_sync_batchnorm`` (reference README.md:79-81).
    Mutates hyperparameters in place (parameters/state are unaffected) and
    returns the module for chaining."""
    if isinstance(module, BatchNorm):
        module.sync = True
    for child in module.children():
        convert_sync_batchnorm(child)
    return module
