"""Shared shape-bucketing and static-batch padding helpers.

One implementation for every consumer that turns ragged host data into the
static device shapes XLA compiles once:

- the data loaders pad final partial batches (:func:`pad_batch` — the 0/1
  sample-weight convention consumed by the masked loss/metric math and
  BatchNorm, tpuddp/data/loader.py);
- the managed ``FusedEvaluator`` and train-side ``fuse_steps="auto"`` key
  their queues and depth caps by :func:`shape_key` / :func:`resolve_fuse`
  (tpuddp/accelerate.py);
- the native epoch driver's ``scan_steps: auto`` caps its staged super-chunk
  by the same :data:`STAGE_BYTES_BUDGET` (tpuddp/training/loop.py);
- the serving scheduler coalesces variable-size requests into
  power-of-two-bucketed padded batches (:func:`bucket_for`,
  tpuddp/serving/scheduler.py) so the compile cache stays warm: at most
  ``log2(max_batch) + 1`` programs per sample shape, compile storms by
  construction impossible.

These used to live as private helpers inside their consumers; serving made a
second copy inevitable, so they were lifted here instead of diverging.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

# Bound on one staged (K, batch, ...) chunk / one K-deep device queue. The
# number every auto depth policy caps against (depth amortizes the
# per-dispatch latency; this budget is what keeps depth from staging past
# HBM).
STAGE_BYTES_BUDGET = 256 * 1024 * 1024


def shape_key(x) -> Tuple[Tuple[int, ...], str]:
    """Bucketing key of a batch: (shape, dtype-string). Metadata-only — never
    converts ``x`` (it may be a staged device array; ``np.asarray`` on it
    would force a host transfer)."""
    return (tuple(np.shape(x)), str(getattr(x, "dtype", "untyped")))


def resolve_fuse(batch_nbytes: Optional[int], cap: int = 32) -> int:
    """Depth of a device-side batch queue: ``cap``, bounded by the staging
    budget over one batch's input bytes when they are known — the queue holds
    K such batches on device before each flush, so depth x batch bytes is
    real HBM."""
    cap = max(1, int(cap))
    if batch_nbytes:
        cap = max(1, min(cap, STAGE_BYTES_BUDGET // int(batch_nbytes)))
    return cap


def pad_batch(x: np.ndarray, y: Optional[np.ndarray], batch_size: int):
    """Pad ``(x, y)`` along axis 0 to the static ``batch_size``; returns
    ``(x, y, w)`` where the 0/1 float32 weight vector ``w`` marks real rows.
    Padding repeats row 0 (a real sample, so no NaN/denormal surprises reach
    the compiled program) and zero-labels it; every masked consumer (loss,
    metrics, BatchNorm, the serving scheduler's row slicing) ignores w==0
    rows. ``y=None`` (an unlabeled inference batch) pads x alone and returns
    ``y=None``."""
    n = len(x) if y is None else len(y)
    if n > batch_size:
        raise ValueError(f"batch of {n} rows cannot pad down to {batch_size}")
    w = np.ones(batch_size, np.float32)
    if n < batch_size:
        pad = batch_size - n
        x = np.concatenate([x, np.repeat(x[:1], pad, axis=0)])
        if y is not None:
            y = np.concatenate([y, np.zeros(pad, y.dtype)])
        w[n:] = 0.0
    return x, y, w


def bucket_sizes(max_batch: int):
    """The power-of-two ladder up to ``max_batch`` (inclusive; ``max_batch``
    itself is always the top rung even when it is not a power of two)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return sizes


def bucket_for(n: int, max_batch: int) -> int:
    """Smallest bucket that holds ``n`` rows. Bounds the set of compiled
    batch shapes: every dispatched batch is one of :func:`bucket_sizes`."""
    if n < 1:
        raise ValueError(f"cannot bucket {n} rows")
    if n > max_batch:
        raise ValueError(f"{n} rows exceed max_batch={max_batch}")
    for b in bucket_sizes(max_batch):
        if n <= b:
            return b
    return max_batch  # unreachable: the ladder always ends at max_batch
