"""Layers of a token model: RMSNorm (plain, zero-centred and gated), rotary
positions over part or all of a head from a plain or a YaRN-scaled frequency
table, a causal depthwise 1-D convolution, plain and between two gates, SwiGLU,
causal attention over
grouped key/value heads, full, within a sliding window or over the keys a
learned indexer chose for each query, that never holds a
``T x T`` score block, and a head-plus-cross-entropy that never holds
``(B, T, V)`` logits, over one final state (beside it, a second head's states
for the token after next) or over the exits of a looped stack.

Attention under a static mask has one mask with one parameter (a query sees
itself and the keys before it, all of them or the ``window - 1`` nearest); under
a mask that is data (:func:`sparse_attention_rows`: which keys a query sees is
decided in the step, by an indexer's exact top-k over its scores of every
earlier key) it goes a group of queries at a time. Either has two lowerings, and
picks one from what a call can see (:func:`attention_lowering`): on a TPU,
for heads of 64, 128 or 256, sequences that are a multiple of 512 and a window
whose chunks divide them, traced once a device (the default
``mode="shard_map"`` step, a one-device process), the library's fused kernel,
which keeps score blocks, softmax statistics and the accumulator in VMEM,
skips the blocks no query of which sees a key, and has its own backward
kernels; everywhere else (the CPU, the tiny presets' heads of 16, ragged
lengths, ``mode="auto"``) query blocks in plain XLA, each a score block of
``q_block`` queries by the keys they can see in HBM.

Functions over explicit arrays (the family file ``models/hybrid_moe.py``
owns the parameter tree). Products take their inputs in ``compute_dtype``
and accumulate in float32 (:func:`matmul`); statistics, softmax, the loss and
whatever a recurrence carries stay float32.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from tpuddp.observability import profiling as _prof

_NEG_INF = -1e30  # masked-score fill: finite, so a fully masked row stays NaN-free


def round_to(x, dtype):
    """``x`` rounded to ``dtype`` as a product's input. An 8-bit float is
    rounded to and widened again to bfloat16: backends without 8-bit products
    then compute what a backend with them would be fed."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 1:
        return x.astype(dtype).astype(jnp.bfloat16)
    return x.astype(dtype)


def matmul(x, w, compute_dtype, out_dtype=None):
    """``x @ w`` with both inputs rounded to ``compute_dtype`` and float32
    accumulation; the result in ``out_dtype`` (default: the rounded type)."""
    x, w = round_to(x, compute_dtype), round_to(w, compute_dtype)
    y = jnp.matmul(x, w, preferred_element_type=jnp.float32)
    return y.astype(out_dtype or x.dtype)


def rms_norm(x, weight, eps: float = 1e-6, *, zero_centred: bool = False, gate=None):
    """``x / sqrt(mean(x^2) + eps) * w`` over the last axis, statistics in
    float32. ``zero_centred``: the scale is ``1 + w`` (``w`` starts at 0).
    ``gate``: the gated form, the normalised value times ``SiLU(gate)``."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    y = y * (1.0 + w if zero_centred else w)
    if gate is not None:
        y = y * jax.nn.silu(gate.astype(jnp.float32))
    return y.astype(x.dtype)


def l2_normalise(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)).astype(x.dtype)


def rotary_frequencies(rotary_dim: int, theta: float, yarn=None):
    """``(inv_freq, factor)``: the ``rotary_dim / 2`` angular frequencies a
    position is multiplied by, and what cos and sin are multiplied by.
    Without ``yarn``: ``theta^(-2m/d)`` and 1. With it (a mapping: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): YaRN's blend. Dimension ``m`` turns
    ``L theta^(-2m/d) / 2 pi`` times over the original ``L`` positions; those
    that turn ``beta_fast`` times or more keep their frequency, those that
    turn ``beta_slow`` times or fewer are slowed by ``factor``, and between
    the two (whole dimensions ``low`` to ``high``) a linear ramp blends."""
    half = rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim))
    if yarn is None:
        return inv_freq, 1.0
    low, high = yarn_ramp(rotary_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * inv_freq + ramp * inv_freq / yarn["factor"], float(yarn["attention_factor"])


def yarn_ramp(rotary_dim: int, theta: float, yarn) -> tuple:
    """``(low, high)``: the dimensions between which YaRN's ramp runs, the
    dimension that turns ``beta`` times over the original positions being
    ``d ln(L / (2 pi beta)) / (2 ln theta)``, rounded outwards."""
    turns = lambda beta: rotary_dim * math.log(
        yarn["original_max_position_embeddings"] / (2 * math.pi * beta)
    ) / (2 * math.log(theta))
    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), rotary_dim - 1)
    return low, max(high, low + 1e-3)  # never a ramp of no width


def rotary(x, positions, *, rotary_dim: int, theta: float, yarn=None):
    """Rotate-half rotary positions on the first ``rotary_dim`` of the last
    axis; the rest passes. ``x``: ``(B, T, H, D)``, ``positions``: ``(T,)``.
    ``yarn``: :func:`rotary_frequencies`' scaling, or none."""
    half = rotary_dim // 2
    inv_freq, factor = rotary_frequencies(rotary_dim, theta, yarn)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (T, half)
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    if factor != 1.0:
        cos, sin = factor * cos, factor * sin
    rot, rest = x[..., :rotary_dim].astype(jnp.float32), x[..., rotary_dim:]
    a, b = rot[..., :half], rot[..., half:]
    rot = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), rest], axis=-1)


def causal_conv1d(x, kernel):
    """Depthwise causal convolution along time: ``y[t] = sum_j kernel[j] *
    x[t - (K - 1) + j]`` with zeros before the sequence. ``x``: ``(B, T, C)``,
    ``kernel``: ``(K, C)``. Written as ``K`` shifted multiply-adds, which is
    what a depthwise kernel of 4 is on a vector unit."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = kernel.astype(jnp.float32)
    y = sum(padded[:, j:j + t].astype(jnp.float32) * w[j] for j in range(k))
    return y.astype(x.dtype)


def gated_short_conv(bcu, kernel):
    """A short convolution between two gates: ``bcu`` ``(B, T, 3C)`` holds
    three streams side by side, ``b | c | u``, and the result is ``c *
    causal_conv1d(b * u)``: no activation, no norm, no scan. The gates and the
    taps are float32 elementwise work on rows that are read once and written
    once (the compiler fuses the chain); the result is in ``bcu``'s type."""
    b, c, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return (c * causal_conv1d(b * u, kernel)).astype(bcu.dtype)


def swiglu(x, gate_up, down, compute_dtype):
    """``down(SiLU(x W_gate) * (x W_up))`` with gate and up joined column-wise
    in ``gate_up`` (``(E, 2F)``: gate first)."""
    h = matmul(x, gate_up, compute_dtype, jnp.float32)
    f = h.shape[-1] // 2
    return matmul(jax.nn.silu(h[..., :f]) * h[..., f:], down, compute_dtype)


def _attend_block(q, q_start, k, v, k_start=None, *, scale, compute_dtype, window=None):
    """One block of queries, the first at position ``q_start``, against keys
    from position ``k_start`` (0 if ``None``) that reach at least to the
    block's end. ``q``: ``(B, Q, Hkv, G, D)``; ``k``, ``v``: ``(B, S, Hkv, D)``."""
    scores = jnp.einsum(
        "bqhgd,bshd->bhgqs", round_to(q, compute_dtype), round_to(k, compute_dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    q_pos = q_start + jnp.arange(q.shape[1])
    k_pos = jnp.arange(k.shape[1])
    if k_start is not None:
        k_pos = k_start + k_pos
    visible = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        visible &= q_pos[:, None] - k_pos[None, :] < window
    probs = jax.nn.softmax(jnp.where(visible, scores, _NEG_INF), axis=-1)
    out = jnp.einsum(
        "bhgqs,bshd->bqhgd", round_to(probs, compute_dtype), round_to(v, compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def causal_attention(q, k, v, *, scale: float, compute_dtype, q_block: int = 512, window=None):
    """Causal softmax attention, each key/value head serving ``Hq / Hkv``
    query heads. ``q``: ``(B, T, Hq, D)``; ``k``, ``v``: ``(B, T, Hkv, D)``.
    One contract (product inputs in ``compute_dtype``, float32 accumulation
    and softmax statistics, the exact mask, the output in ``q``'s type): query
    ``i`` sees key ``j`` where ``j <= i`` and, with a ``window``, ``i - j <
    window`` (its own key and the ``window - 1`` before it). Two lowerings,
    chosen by :func:`attention_lowering` from the backend, the shapes and
    where the call is traced: one fused kernel that keeps every score block
    in VMEM, or ``q_block`` queries at a time in plain XLA
    (:func:`_blockwise_causal_attention`). Both compute only blocks in which
    some query sees some key."""
    t = q.shape[1]
    if window is not None and window >= t:
        window = None  # every key before a query is within it
    lowering = attention_lowering(
        jax.default_backend(), q.shape[-1], t, per_replica=traced_per_replica(), window=window
    )
    if lowering == "fused":
        return _fused_causal_attention(q, k, v, scale=scale, compute_dtype=compute_dtype, window=window)
    return _blockwise_causal_attention(
        q, k, v, scale=scale, compute_dtype=compute_dtype, q_block=q_block, window=window
    )


def attention_lowering(backend: str, head_dim: int, t: int, *, per_replica: bool, window=None) -> str:
    """``"fused"`` or ``"blockwise"``. The kernel is written for the TPU's
    tiles: a head fills whole 128-lane registers, the sequence whole blocks
    and, under a ``window``, whole chunks of the band
    (:func:`fused_attention_blocks`); it is a custom call, which GSPMD cannot
    partition, so it serves only a call that is traced once a device
    (``per_replica``). Everything else, the CPU first, takes the blockwise
    path."""
    if backend == "tpu" and per_replica and fused_attention_blocks(t, head_dim, window) is not None:
        return "fused"
    return "blockwise"


def traced_per_replica() -> bool:
    """Whether what is being traced runs once a device: inside ``shard_map``
    (every axis of the mesh manual: the default ``mode="shard_map"`` step), or
    in a process that has one device. Under ``jit`` over a mesh of several
    (``mode="auto"``) XLA partitions the program after the fact."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.empty:
        return set(mesh.manual_axes) == set(mesh.axis_names)
    return jax.device_count() == 1


def _blockwise_causal_attention(q, k, v, *, scale, compute_dtype, q_block, window=None):
    """Queries go ``q_block`` at a time and each block is recomputed in the
    backward pass, so no block's probabilities are kept.

    Without a window the largest score block is ``q_block x T`` a head. About
    ``sqrt(T / q_block)`` neighbouring blocks form a group that shares the
    keys up to the group's end and one rolled loop, so the program holds a
    loop a group and not a copy a block; what a group computes above its
    blocks' diagonals is masked (at 16 blocks, 160 block products where 136
    are needed).

    With a window shorter than the sequence a block reads only the
    ``q_block + window - 1`` keys that end with its own last (from the first
    key on where the sequence's start cuts them short), so every whole block
    has one shape and all of them share one rolled loop."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    q = q.reshape(b, t, hkv, hq // hkv, d)
    block = jax.checkpoint(
        functools.partial(_attend_block, scale=scale, compute_dtype=compute_dtype, window=window)
    )
    if window is not None and q_block + window - 1 < t:
        return _banded_blocks(block, q, k, v, q_block, window).reshape(b, t, hq, d)
    span = _stretch(t, q_block)
    outs = []
    for start in range(0, t, span):
        end = min(start + span, t)
        keys, values = k[:, :end], v[:, :end]
        whole = (end - start) // q_block
        if whole:
            queries = q[:, start:start + whole * q_block].reshape(b, whole, q_block, *q.shape[2:])
            out = jax.lax.map(
                lambda args: block(*args, keys, values),
                (jnp.moveaxis(queries, 1, 0), start + q_block * jnp.arange(whole)),
            )
            outs.append(jnp.moveaxis(out, 0, 1).reshape(b, whole * q_block, *q.shape[2:]))
        if start + whole * q_block < end:  # what is left of a sequence that is no multiple
            outs.append(block(q[:, start + whole * q_block:end], start + whole * q_block, keys, values))
    return jnp.concatenate(outs, axis=1).reshape(b, t, hq, d)


def _stretch(t: int, block: int) -> int:
    """Tokens a group of neighbouring blocks that share their keys and one
    rolled loop: about ``sqrt(t / block)`` blocks."""
    return block * (math.isqrt(max(-(-t // block) - 1, 0)) + 1)


def _banded_blocks(block, q, k, v, q_block: int, window: int):
    """``block`` over query blocks of ``q_block``, each against the
    ``q_block + window - 1`` keys that end with the block's last query (the
    first that many keys for the blocks the sequence's start cuts short: the
    mask is by position, so what a slice holds beyond the band is masked)."""
    b, t = q.shape[:2]
    seen = q_block + window - 1

    def one(queries, start, end):
        first = jnp.clip(end - seen, 0, t - seen)
        keys, values = (jax.lax.dynamic_slice_in_dim(a, first, seen, axis=1) for a in (k, v))
        return block(queries, start, keys, values, first)

    whole, outs = t // q_block, []
    queries = q[:, :whole * q_block].reshape(b, whole, q_block, *q.shape[2:])
    out = jax.lax.map(
        lambda args: one(args[0], args[1], args[1] + q_block),
        (jnp.moveaxis(queries, 1, 0), q_block * jnp.arange(whole)),
    )
    outs.append(jnp.moveaxis(out, 0, 1).reshape(b, whole * q_block, *q.shape[2:]))
    if whole * q_block < t:
        outs.append(one(q[:, whole * q_block:], whole * q_block, t))
    return jnp.concatenate(outs, axis=1)


# -- the fused lowering -----------------------------------------------------------

_LANES = 128  # a vector register's minor width
_WIDEST_HEAD = 256  # the blocks below fill VMEM at this width; a wider head does not compile with them
# The one-kernel backward writes a copy of ``dq`` a key block, and what bounds it is rows of that copy
# (a row: a head's ``dq`` at one query for one key block, in the inputs' type):
_MOST_DQ_PARTIALS = 16 * 16384  # a head: up to it every head goes in one call
_MOST_DQ_PARTIALS_A_CALL = 4 * 32 * 32768  # past it, a call over all its heads and sequences, lanes filled


def fused_attention_blocks(t: int, head_dim: int, window=None, *, hq: int = 1, hkv: int = 1, sequences: int = 1):
    """The fused kernels' block sizes for ``sequences`` sequences of ``t``
    tokens and ``hq`` query heads of ``head_dim`` over ``hkv`` key/value
    heads, or ``None`` where the kernel is not for the shapes: a
    head that is neither half a lane register (64: the library's kernel takes
    it as it is, and zeros to fill the register would double q, k and v in
    HBM for the same products) nor a whole number of them, or wider than the
    blocks were sized for, a sequence that is no multiple of 512 (at blocks of
    256 and 128 the kernel is slower than the blockwise path: 6.3 and 18.8 ms
    a forward against 12.3 at 8,192 tokens; PERF.md, PR 29), or a ``window``
    whose chunks (:func:`_banded_chunks`) do not divide the sequence. The
    heads and the sequences decide the backward pass only.

    Constants from that sweep, on a v5e at ``(8192, 256)``: blocks of 1024
    queries and keys (512 where 1024 does not divide ``t``), the forward's
    inner key step 256; the backward pass as one kernel (5 products where the
    library's two kernels make 7), which writes a partial ``dq`` a key block
    and sums them. Its key block stays 1024: 2048 read 0.3 ms a sequence
    faster alone and then did not fit VMEM inside the whole step, where XLA
    keeps buffers of its own there. Those partials are a copy of ``dq`` a key block of a
    call (``t / block`` of them without a window) in the inputs' type, lanes filled: 64 MiB a head at 16,384 tokens
    (2 GiB over 32 heads) and 256 MiB a head at 32,768, so what a call writes
    is bounded (:func:`fused_attention_head_groups`): past 16,384 tokens the
    one kernel goes a group of whole key/value heads at a time, and where one
    key/value head's query heads are past the bound a call (65,536 tokens at
    32/8 heads) the backward pass is the library's two kernels, 7 products
    and no partials. The widest call of any cell is latent attention's 20
    ungrouped heads of 192 + 64 at 16,384 tokens: attention's gradient alone,
    compiled for a described v5e, has 2,855,433,728 bytes of scratch in the
    one call the rule gives it (2,438,981,120 in 2 groups of 10 heads,
    1,516,232,704 in 4 of 5), and the whole step compiles to 3.64 GB of
    scratch beside its 8.48 GB of state, so the one call stays (PERF.md,
    PR 44)."""
    if (head_dim % _LANES and head_dim != _LANES // 2) or head_dim > _WIDEST_HEAD or t % 512:
        return None
    block = _fused_block(t)
    if window is not None and t % _band_chunk(window, block):
        return None
    if fused_attention_head_groups(t, head_dim, window, hq=hq, hkv=hkv, sequences=sequences):
        backward = dict(use_fused_bwd_kernel=True)
    else:
        backward = dict(use_fused_bwd_kernel=False, block_q_dq=block, block_kv_dq=block)
    return _splash()[0].BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=256,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=512, **backward,
    )


def _fused_block(t: int) -> int:
    """Queries and keys a block of the fused kernels, forward and backward."""
    return 1024 if t % 1024 == 0 else 512


def fused_attention_head_groups(t: int, head_dim: int, window=None, *, hq: int, hkv: int, sequences: int = 1) -> int:
    """How many calls the one-kernel backward's heads go in, one after
    another, at shapes :func:`fused_attention_blocks` has blocks for: 1 where a
    head's partial ``dq`` is within ``_MOST_DQ_PARTIALS`` rows (every head and
    sequence in one call: 2 GiB at 16,384 tokens and 32 heads); past it the
    fewest groups of whole key/value heads, each with its ``hq / hkv`` query
    heads, that divide the heads and keep what a call writes (the group's
    query heads over all sequences, a row counted once a lane register it
    fills) within ``_MOST_DQ_PARTIALS_A_CALL``; 0 where one key/value head's
    group is past that, and the backward pass is the library's two kernels.
    At 32,768 tokens and 32/8 heads of 64: 8 calls of 4 query heads, 1.07 GB
    of partials alive at a time where one call's 8.6 GB did not fit; the
    whole step's scratch, compiled for a described v5e, is 7.03 GB with the
    two kernels, 6.88 at one key/value head a call and 8.00 at two (PERF.md,
    PR 43), so the bound a call is one head's group there and no more."""
    block = _fused_block(t)
    keys = t if window is None else 2 * _band_chunk(window, block)  # a kernel call's, at most
    rows_a_head = (keys // block) * t
    if rows_a_head <= _MOST_DQ_PARTIALS:
        return 1
    rows_a_kv_head = sequences * (hq // hkv) * rows_a_head * -(-head_dim // _LANES)
    for groups in range(1, hkv + 1):
        if hkv % groups == 0 and (hkv // groups) * rows_a_kv_head <= _MOST_DQ_PARTIALS_A_CALL:
            return groups
    return 0


def _splash():
    """The library's kernel and mask modules, imported where they are used:
    they pull in Pallas and Mosaic, which nothing else of the package needs."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel, splash_attention_mask,
    )

    return splash_attention_kernel, splash_attention_mask


def _fused_causal_attention(q, k, v, *, scale, compute_dtype, window=None, interpret: bool = False):
    """The library's splash attention (``jax.experimental.pallas.ops.tpu``):
    scores, mask, running softmax and the value product of a block in one
    kernel, whose statistics and accumulator stay in VMEM, which skips the
    blocks above the diagonal and, with a ``window``, those below the band,
    in the forward and the backward pass alike, and which brings its own
    backward kernels (they recompute the scores from the saved log-sum-exp).
    Grouped heads are the kernel's own: query head ``h`` reads key/value head
    ``h // (Hq / Hkv)``. Where the rule cuts the heads into groups
    (:func:`fused_attention_head_groups`: past 16,384 tokens) the kernel is
    called a group of whole key/value heads at a time, one after another
    (``lax.map``: one kernel instance, one group's partial ``dq`` alive at a
    time), and within a group the heads are the kernel's own still. The
    kernel applies no scale, so ``q`` carries it in.
    ``interpret`` runs the kernels in Pallas's interpreter: the CPU tests'
    way in."""
    kernels, masks = _splash()
    (b, t, hq, d), hkv = q.shape, k.shape[2]
    shapes = dict(hq=hq, hkv=hkv, sequences=b)
    blocks = fused_attention_blocks(t, d, window, **shapes)
    groups = fused_attention_head_groups(t, d, window, **shapes) or 1  # the two kernels take every head at once

    def attend(mask, q, k, v):
        """``q (N, Tq, Hq, D)`` against ``k``, ``v (N, Tk, Hkv, D)`` under ``mask (Tq, Tk)``."""
        kernel = jax.vmap(kernels.make_splash_mha(
            masks.MultiHeadMask([mask] * (hq // groups)), block_sizes=blocks, head_shards=1, q_seq_shards=1,
            interpret=interpret,
        ))
        qkv = (round_to(q, compute_dtype) * scale, round_to(k, compute_dtype), round_to(v, compute_dtype))
        if groups == 1:
            heads_first = lambda a: jnp.swapaxes(a, 1, 2)  # (N, H, T, D)
            return heads_first(kernel(*map(heads_first, qkv))).astype(q.dtype)
        # (groups, N, H / groups, T, D): neighbouring heads share a group, as they share a key/value head
        groups_first = lambda a: a.reshape(*a.shape[:2], groups, -1, d).transpose(2, 0, 3, 1, 4)
        out = jax.lax.map(lambda group: kernel(*group), tuple(map(groups_first, qkv)))
        return out.transpose(1, 3, 0, 2, 4).reshape(q.shape).astype(q.dtype)

    if window is None:
        return attend(masks.CausalMask((t, t)), q, k, v)
    # ``window - 1`` keys to the left of a query's own, none to the right;
    # ``offset``: where the first query stands among the keys it is given
    band = lambda keys, offset: masks.LocalMask((keys - offset, keys), (window - 1, 0), offset)
    return _banded_chunks(attend, band, q, k, v, chunk=_band_chunk(window, blocks.block_q))


def _band_chunk(window: int, block: int) -> int:
    """Queries a chunk of the band: whole blocks, at least ``window - 1``."""
    return -(-(window - 1) // block) * block


def _banded_chunks(attend, band, q, k, v, *, chunk: int):
    """Banded attention a chunk of queries at a time, each against its own
    keys and the chunk's before; a chunk is at least ``window - 1`` long, so
    no query sees further back, and the first, which has no chunk before it,
    goes alone. One kernel over the whole sequence would compute the same
    blocks, but its backward pass writes a partial ``dq`` of the whole
    sequence for every key block and sums them (:func:`fused_attention_blocks`):
    16 of them at 16,384 tokens, of which two are not zero, 2.1 GB a layer
    and sequence; a chunk's kernel writes its own two (20.9 against 17.0 ms a
    layer, forward and backward, on a v5e; PERF.md, PR 35). Chunks are cut
    along ``T`` of ``(B, T, H, D)``, which moves nothing."""
    b, t = q.shape[:2]
    n = t // chunk
    head = attend(band(chunk, 0), *(a[:, :chunk] for a in (q, k, v)))
    if n == 1:
        return head
    chunks = lambda a: a.reshape(b, n, chunk, *a.shape[2:])
    flat = lambda a: a.reshape(b * (n - 1), -1, *a.shape[3:])  # chunks as sequences of their own
    with_before = lambda a: flat(jnp.concatenate([chunks(a)[:, :-1], chunks(a)[:, 1:]], axis=2))
    rest = attend(band(2 * chunk, chunk), flat(chunks(q)[:, 1:]), with_before(k), with_before(v))
    return jnp.concatenate([head, rest.reshape(b, t - chunk, *q.shape[2:])], axis=1)


# -- attention over the keys a query's indexer chose --------------------------------

SPARSE_COUNTERS = (  # additive, as an expert layer's: summed over layers here and over steps by whoever reads them
    "indexer_kl_sum",  # the indexer's objective summed over rows: KL(attention's distribution || the indexer's) over a row's selection
    "indexer_rows",  # the rows it was summed over, a layer each: their quotient is the objective a row a layer
    "index_selected_pairs",  # (query, key) pairs the selections hold, summed over layers: what the sparse product needs
)
SPARSE_GROUP = 1024  # queries a call of the fused kernel under a selection: the mask and the index scores are (group, keys)
# Stretches a sequence's groups go in. Every stretch is one more copy of a group's program in the step (the
# mask's processing, six kernels, the passes over rows by keys), and the copies are what the step's trace, compile
# time and executable are made of; a group of three stretches meets 0.67 of the sequence's keys on average, of six
# 0.59, of one all: the kernels skip the blocks no query sees, what is elementwise pays for them. Measured at
# 32,768 tokens (PERF.md section 6, PR 49): three for six cost 3.0% of the step and took 80 s off its set-up.
SPARSE_STRETCHES = 3


def layer_norm(x, weight, bias, eps: float = 1e-6):
    """``(x - mean) / sqrt(var + eps) * w + b`` over the last axis, statistics
    in float32."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(jnp.mean(jnp.square(centred), axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def indexer_input(h):
    """What a layer's indexer reads: the layer's normed input, which no
    gradient leaves through. The indexer's objective moves the indexer's own
    leaves and nothing below them."""
    return jax.lax.stop_gradient(h)


def sparse_attention_lowering(backend: str, head_dim: int, t: int, *, per_replica: bool) -> str:
    """``"fused"`` or ``"blockwise"`` for attention under a selection, by
    :func:`attention_lowering`'s reasons: the library's kernel under a mask
    that is data, ``SPARSE_GROUP`` queries a call, on a TPU where the
    sequence is whole groups and the call is traced once a device; query
    blocks in plain XLA everywhere else."""
    if backend == "tpu" and per_replica and head_dim % _LANES == 0 and head_dim <= _WIDEST_HEAD and t % SPARSE_GROUP == 0:
        return "fused"
    return "blockwise"


def sparse_row_groups(t: int, group: int) -> list:
    """``(first row, rows, keys)`` of the stretches a sequence's queries go
    in: whole groups of ``group`` queries that share the keys up to the
    stretch's end and one rolled loop, ``SPARSE_STRETCHES`` stretches of as
    many groups each, and what is left of a sequence that is no multiple as
    a stretch of its own."""
    groups = -(-t // group)
    span, out = group * -(-groups // SPARSE_STRETCHES), []
    for start in range(0, t, span):
        end = min(start + span, t)
        whole = (end - start) // group * group
        if whole:
            out.append((start, whole, end))
        if start + whole < end:
            out.append((start + whole, end - start - whole, end))
    return out


@jax.custom_vjp
def index_scores(qi, ki, wi):
    """``I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s])``, ``(G, S)`` float32,
    of ``qi (G, H, D)`` and ``ki (S, D)`` as they are given (the products'
    inputs, already rounded) and ``wi (G, H)`` float32. A head at a time, in
    the forward and the backward pass alike: the ``(G, S)`` block of one head
    is all that lives beside the sum, and nothing of it is kept."""
    def head(total, args):
        q_j, w_j = args
        z = jnp.matmul(q_j, ki.T, preferred_element_type=jnp.float32)
        return total + w_j[:, None] * jax.nn.relu(z), None

    total, _ = jax.lax.scan(
        head, jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32), (jnp.moveaxis(qi, 1, 0), wi.T)
    )
    return total


def _index_scores_fwd(qi, ki, wi):
    return index_scores(qi, ki, wi), (qi, ki, wi)


def _index_scores_bwd(saved, d):
    qi, ki, wi = saved

    def head(d_ki, args):
        q_j, w_j = args
        z = jnp.matmul(q_j, ki.T, preferred_element_type=jnp.float32)
        d_w = jnp.sum(d * jax.nn.relu(z), axis=1)
        d_z = jnp.where(z > 0, d * w_j[:, None], 0.0).astype(qi.dtype)  # a product's input, as the forward's are
        d_q = jnp.matmul(d_z, ki, preferred_element_type=jnp.float32)
        return d_ki + jnp.matmul(d_z.T, q_j, preferred_element_type=jnp.float32), (d_q, d_w)

    d_ki, (d_qi, d_wi) = jax.lax.scan(
        head, jnp.zeros(ki.shape, jnp.float32), (jnp.moveaxis(qi, 1, 0), wi.T)
    )
    return jnp.moveaxis(d_qi, 0, 1).astype(qi.dtype), d_ki.astype(ki.dtype), d_wi.T.astype(wi.dtype)


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


def _ordered_bits(x):
    """float32 as uint32 in the floats' order (``-inf`` lowest, above 0)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def top_k_mask(scores, visible, k: int, *, fused: bool = False, interpret: bool = False):
    """``(G, S)`` bool: a row's ``k`` ``visible`` entries of largest score,
    equal scores to the lower index; every visible entry where they are ``k``
    or fewer. Exact, and no sort: the ``k``-th largest score is found a bit
    at a time on the floats' ordered bit patterns (32 counts over the row;
    ``fused``: in a kernel that keeps the row in VMEM for all of them),
    entries above it are in, and of those equal to it the first that fill the
    ``k``. Only where some row has more equal to it than it needs (a tie at
    the threshold: rare) are they counted along the row."""
    keys = jnp.where(visible, _ordered_bits(scores), jnp.uint32(0))  # what is not visible lies under every score
    if keys.shape[1] < k:
        threshold = jnp.zeros((keys.shape[0], 1), jnp.uint32)  # fewer keys than k: all that is visible
    elif fused:
        from tpuddp.nn import sparse_attention_kernels as kernels

        threshold = kernels.kth_largest(keys, k, interpret)[:, None]
    else:
        def bit(i, found):
            candidate = found | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
            enough = jnp.sum(keys >= candidate[:, None], axis=1, dtype=jnp.int32) >= k
            return jnp.where(enough, candidate, found)

        threshold = jax.lax.fori_loop(0, 32, bit, jnp.zeros((keys.shape[0],), jnp.uint32))[:, None]
    above, equal = keys > threshold, keys == threshold
    need = k - jnp.sum(above, axis=1, dtype=jnp.int32)
    surplus = (jnp.sum(equal, axis=1, dtype=jnp.int32) > need) & (threshold[:, 0] > 0)
    equal = jax.lax.cond(
        jnp.any(surplus),
        lambda: equal & (jnp.cumsum(equal, axis=1, dtype=jnp.int32) <= need[:, None]),
        lambda: equal,
    )
    return (above | equal) & visible


def _attend_selected_block(q, k, v, selected, *, scale, compute_dtype):
    """Plain XLA: ``q (G, Hkv, g, D)`` against ``k``, ``v (S, Hkv, D)`` under
    ``selected (G, S)``: the output, and the heads' mean probabilities
    ``(G, S)`` float32."""
    scores = jnp.einsum(
        "qhgd,shd->hgqs", round_to(q, compute_dtype), round_to(k, compute_dtype), preferred_element_type=jnp.float32
    ) * scale
    probs = jax.nn.softmax(jnp.where(selected, scores, _NEG_INF), axis=-1)
    out = jnp.einsum(
        "hgqs,shd->qhgd", round_to(probs, compute_dtype), round_to(v, compute_dtype), preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype), jnp.mean(jax.lax.stop_gradient(probs), axis=(0, 1))


def _selected_kernel(selected, interpret: bool):
    """The library's kernel under ``selected (G, S)``, a mask that is data:
    one mask for all heads (the kernel reads a one-head mask's blocks for
    every head), its blocks whole groups of queries where they divide."""
    kernels, _ = _splash()
    g, s = selected.shape
    bq = next(b for b in (1024, 512, 256, 128) if g % b == 0)
    bkv = next(b for b in (1024, 512, 256, 128) if s % b == 0)
    blocks = kernels.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=min(bkv, 256),
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=min(bkv, 512), use_fused_bwd_kernel=True,
    )
    return kernels.make_splash_mha(selected[None], block_sizes=blocks, head_shards=1, q_seq_shards=1, interpret=interpret)


def _fused_selected_attention(q, k, v, selected, *, scale, compute_dtype, interpret: bool = False):
    """The fused lowering of one group: ``q (G, Hq, D)``, ``k``, ``v (S, Hkv,
    D)`` under ``selected (G, S)``. The output ``(G, Hq, D)`` and each
    head's log-sum-exp ``(Hq, G)`` float32, which the kernel computes for its
    own backward pass and the indexer's objective reads (the public call
    keeps it to itself, so the kernel's forward and backward functions are
    called as its own rule calls them: ``_splash_attention_forward`` and
    ``_splash_attention_bwd`` of jax 0.9.0, their arguments by name, the
    residuals ``(q, k, v, segment_ids, sinks, out, lse, dq_mask_info,
    dkv_mask_info)`` and the results ``(three mask infos, dq, dk, dv, ...)``
    by place; ``tests/test_sparse_moe.py`` holds both layouts, so a library
    that changes them fails a test before a step). Blocks with no selected
    pair are skipped, in the forward and the backward pass."""
    kernels, _ = _splash()
    kernel = _selected_kernel(selected, interpret)
    static = {name: value for name, value in kernel.kwargs.items() if name != "save_residuals"}
    flat = lambda info: info if info is None else info._replace(
        partial_mask_blocks=info.partial_mask_blocks.reshape(-1, *info.partial_mask_blocks.shape[-2:])
    )
    infos = tuple(flat(info) for info in (kernel.fwd_mask_info, kernel.dq_mask_info, kernel.dkv_mask_info))

    @jax.custom_vjp
    def attend(infos, q, k, v):
        out, (lse,) = kernels._splash_attention_forward(
            infos[0], q, k, v, None, None, save_residuals=True, **static
        )
        return out, lse

    def forward(infos, q, k, v):
        out, lse = attend(infos, q, k, v)
        return (out, lse), (infos, q, k, v, out, lse)

    def backward(saved, cotangents):
        infos, q, k, v, out, lse = saved
        grads = kernels._splash_attention_bwd(  # by name: a library that moves an argument then says so
            save_residuals=False, **static, res=(q, k, v, None, None, out, lse, infos[1], infos[2]),
            do=cotangents[0],  # none reaches the log-sum-exp: its reader stops it
        )
        return (None, *grads[3:6])

    attend.defvjp(forward, backward)
    heads_first = lambda a: jnp.swapaxes(a, 0, 1)
    out, lse = attend(
        infos, heads_first(round_to(q, compute_dtype) * scale), heads_first(round_to(k, compute_dtype)),
        heads_first(round_to(v, compute_dtype)),
    )
    return heads_first(out).astype(q.dtype), lse


def sparse_attention_rows(q, qi, wi, k, v, ki, start, *, scale, top_k: int, compute_dtype,
                          lowering: str = "blockwise", interpret: bool = False):
    """A group of queries under learned sparse attention (DeepSeek Sparse
    Attention's indexer and its sparse training objective): ``q (G, Hq, D)``,
    the first at position ``start``, against ``k``, ``v (S, Hkv, D)`` from
    position 0 that reach at least to the group's end, with the indexer's
    queries ``qi (G, Hi, Di)``, their weights ``wi (G, Hi)`` (float32) and
    its keys ``ki (S, Di)``.

    ``I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s])`` in float32 (the
    products' inputs in ``compute_dtype``); ``S_t``: the ``top_k`` keys ``s <=
    t`` of largest ``I[t, s]`` (all of them while ``t < top_k``; equal scores
    to the earlier key; exact: :func:`top_k_mask`); every head attends ``S_t``
    alone. Returns ``(out (G, Hq, D), kl, pairs)``: ``kl`` the sum over the
    rows of ``sum_{s in S_t} p (log p - log r)`` with ``p`` the heads' mean
    attention probability (no gradient through it) and ``r = softmax_{S_t}
    I``, so its gradient reaches ``qi``, ``ki`` and ``wi`` alone; ``pairs``
    the selected pairs. Scopes ``index_scores``, ``index_select``,
    ``attention``, ``indexer_loss``.

    Two lowerings (:func:`sparse_attention_lowering`). Fused: the library's
    kernel under the selection as a mask that is data, and the two sums over
    heads that are rows by keys (the index scores with their backward pass,
    the heads' mean probability) as kernels that hold a block of the sum in
    VMEM and walk the heads inside (``nn/sparse_attention_kernels.py``).
    Blockwise: the group's score block, and a head's at a time of the index
    scores, in plain XLA. Either scores whole blocks under the mask."""
    g, s = q.shape[0], k.shape[0]
    fused = lowering == "fused"
    if fused:
        from tpuddp.nn import sparse_attention_kernels as kernels  # pulls in Pallas, as _splash does
    with _prof.scope("index_scores"):
        operands = round_to(qi, compute_dtype), round_to(ki, compute_dtype), wi.astype(jnp.float32)
        scores = kernels.index_scores(*operands, start, interpret) if fused else index_scores(*operands)
        scores = jnp.where(scores == 0, 0.0, scores)  # one zero: -0 and +0 are equal scores
    with _prof.scope("index_select"):
        visible = jnp.arange(s)[None, :] <= (start + jnp.arange(g))[:, None]
        selected = top_k_mask(jax.lax.stop_gradient(scores), visible, top_k, fused=fused, interpret=interpret)
    with _prof.scope("attention"):
        if fused:
            out, lse = _fused_selected_attention(
                q, k, v, selected, scale=scale, compute_dtype=compute_dtype, interpret=interpret
            )
        else:
            hkv = k.shape[1]
            out, p = _attend_selected_block(
                q.reshape(g, hkv, -1, q.shape[-1]), k, v, selected, scale=scale, compute_dtype=compute_dtype
            )
            out = out.reshape(q.shape)
    with _prof.scope("indexer_loss"):
        if fused:  # no gradient: the indexer's target; the queries scaled as the attention kernel was given them
            p = kernels.mean_probabilities(*jax.lax.stop_gradient((
                round_to(q, compute_dtype) * scale, round_to(k, compute_dtype), lse
            )), selected, start, interpret)
        log_r = jax.nn.log_softmax(jnp.where(selected, scores, -jnp.inf), axis=-1)
        counted = selected & (p > 0)  # a probability that underflowed adds nothing
        kl = jnp.sum(jnp.where(counted, p * (jnp.log(jnp.where(counted, p, 1.0)) - jnp.where(counted, log_r, 0.0)), 0.0))
    return out, kl, jnp.sum(selected, dtype=jnp.float32)


# -- the head and its loss ------------------------------------------------------

def _chunk_cross_entropies(h, y, head, compute_dtype):
    """A token's cross-entropy for one chunk of rows ``h`` against the rounded
    ``head``: the chunk's logits live in float32 for their own logsumexp only."""
    logits = jnp.matmul(round_to(h, compute_dtype), head, preferred_element_type=jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    true = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return logz - true


def linear_cross_entropy(hidden, head, labels, weights, *, compute_dtype, chunk: int = 2048):
    """Sum over tokens of ``weights * cross_entropy(hidden @ head, labels)``
    without the ``(N, V)`` logits: tokens go ``chunk`` at a time, each chunk's
    logits live in float32 for its own logsumexp only and are recomputed in
    the backward pass. ``hidden``: ``(N, E)``, ``head``: ``(E, V)``."""
    n = hidden.shape[0]
    chunk = min(chunk, n)
    pad = -n % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels, weights = jnp.pad(labels, (0, pad)), jnp.pad(weights, (0, pad))
    head = round_to(head, compute_dtype)

    @jax.checkpoint
    def chunk_loss(h, y, w):
        return jnp.sum(_chunk_cross_entropies(h, y, head, compute_dtype) * w)

    def body(total, args):
        return total + chunk_loss(*args), None

    split = lambda a: a.reshape(-1, chunk, *a.shape[1:])
    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (split(hidden), split(labels), split(weights.astype(jnp.float32))),
    )
    return total


def targets_after_next(labels, weights):
    """A second head's targets and their weights, ``(B, T)`` each: position
    ``i`` predicts the label of position ``i + 1`` under that label's weight;
    a sequence's last position has no such label and weight 0."""
    last = jnp.arange(labels.shape[-1]) == labels.shape[-1] - 1
    return jnp.roll(labels, -1, axis=-1), jnp.where(last, 0.0, jnp.roll(weights, -1, axis=-1))


NEXT_COUNTERS = ("mtp_loss_sum", "mtp_tokens")  # what a second head adds to the counters: its loss summed over its weighted tokens, and their count


@jax.tree_util.register_pytree_node_class
class DeferredLogits:
    """What a token model's training forward returns in place of ``(B, T, V)``
    logits: the final hidden states and the head, for the criterion to take
    the loss from in chunks (``nn.CrossEntropyLoss`` binds itself through
    ``_tpuddp_bind_loss``, as it does to the managed path's lazy forward); ``aux_loss``, which enters the gradient and not
    the reported loss; and ``counters``, additive program counters that the
    step carries out beside its metrics (``training/step.py``).

    ``next_hidden``: the states of a second head over the same matrix, which
    predicts the token after next (multi-token prediction at depth 1,
    arXiv:2412.19437 section 2.2). Position ``i``'s target is the label of
    position ``i + 1`` under that label's weight, the last position of a
    sequence has none (weight 0), and the head's loss is a mean over its own
    weighted tokens; ``next_weight`` times it enters the gradient and not the
    reported loss, as ``aux_loss`` does, and :data:`NEXT_COUNTERS` carry it
    out, filled when the loss is taken (which is before the step reads
    ``counters``)."""

    def __init__(self, hidden, head, aux_loss=None, counters=None, next_hidden=None, *,
                 compute_dtype, chunk=2048, next_weight: float = 0.0):
        self.hidden, self.head = hidden, head
        self.aux_loss = aux_loss
        self.counters = counters or {}
        self.next_hidden, self.next_weight = next_hidden, float(next_weight)
        self.compute_dtype, self.chunk = jnp.dtype(compute_dtype), chunk

    def tree_flatten(self):
        children = (self.hidden, self.head, self.aux_loss, self.counters, self.next_hidden)
        return children, (self.compute_dtype, self.chunk, self.next_weight)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, compute_dtype=aux[0], chunk=aux[1], next_weight=aux[2])

    def logits(self):
        """The logits whole, in float32: for evaluation and small sizes."""
        return matmul(self.hidden, self.head, self.compute_dtype, jnp.float32)

    # hook consumed by tpuddp criterions (see nn/loss.py)
    def _tpuddp_bind_loss(self, criterion, labels, weights=None):
        return self.cross_entropy(labels, weights, criterion.reduction)

    def _reduced(self, hidden, labels, weights, reduction: str):
        """``(loss, its sum, its weights' sum)`` of one head's states."""
        total = linear_cross_entropy(
            hidden.reshape(-1, hidden.shape[-1]), self.head, labels, weights,
            compute_dtype=self.compute_dtype, chunk=self.chunk,
        )
        denom = jnp.sum(weights)
        if reduction == "sum":
            return total, total, denom
        if reduction == "mean":
            return total / jnp.where(denom == 0, 1.0, denom), total, denom
        raise ValueError(f"deferred logits reduce to 'mean' or 'sum', not {reduction!r}")

    def cross_entropy(self, labels, weights: Optional[jax.Array], reduction: str = "mean"):
        tokens = self.hidden.shape[:-1]
        if weights is None:
            weights = jnp.ones(tokens, jnp.float32)
        else:
            weights = per_token_weights(weights, tokens)
        labels = labels.reshape(tokens)
        loss = self._reduced(self.hidden, labels.reshape(-1), weights.reshape(-1), reduction)[0]
        extra = self.aux_loss
        if self.next_hidden is not None:
            with _prof.scope("mtp"):
                after, their_weights = targets_after_next(labels, weights)
                next_loss, total, count = self._reduced(
                    self.next_hidden, after.reshape(-1), their_weights.reshape(-1), reduction
                )
            self.counters = {
                **self.counters, **dict(zip(NEXT_COUNTERS, jax.lax.stop_gradient((total, count)))),
            }
            extra = self.next_weight * next_loss + (0.0 if extra is None else extra)
        if extra is not None:
            # value of the cross-entropy alone, gradient of the sum
            loss = loss + (extra - jax.lax.stop_gradient(extra))
        return loss


def token_cross_entropies(hidden, head, labels, *, compute_dtype, chunk: int = 2048):
    """``cross_entropy(hidden @ head, labels)`` a token, ``(N,)`` float32,
    without the ``(N, V)`` logits: :func:`linear_cross_entropy`'s chunks (each
    chunk's logits live in float32 for its own logsumexp only and are
    recomputed in the backward pass), kept apart a token for a caller whose
    weights depend on the losses' own inputs."""
    n = hidden.shape[0]
    chunk = min(chunk, n)
    pad = -n % chunk
    if pad:
        hidden, labels = jnp.pad(hidden, ((0, pad), (0, 0))), jnp.pad(labels, (0, pad))
    head = round_to(head, compute_dtype)

    chunk_losses = jax.checkpoint(lambda rows: _chunk_cross_entropies(*rows, head, compute_dtype))
    split = lambda a: a.reshape(-1, chunk, *a.shape[1:])
    return jax.lax.map(chunk_losses, (split(hidden), split(labels))).reshape(-1)[:n]


def exit_distribution(gate_logits):
    """``(log p, p)`` over the ``R`` exits of a looped stack from the gate's
    logits ``(R, N)`` (the last row is not read): exit ``t`` takes
    ``sigmoid(z_t)`` of what the exits before it left, the last exit all that
    is left, so a token's ``p`` sums to 1. In logarithms, so that a gate that
    has saturated still has an entropy."""
    stay = jax.nn.log_sigmoid(-gate_logits[:-1])  # log(1 - lambda_t)
    left = jnp.concatenate([jnp.zeros_like(gate_logits[:1]), jnp.cumsum(stay, axis=0)])  # log of what reaches exit t
    log_p = left + jnp.concatenate([jax.nn.log_sigmoid(gate_logits[:-1]), jnp.zeros_like(gate_logits[:1])])
    return log_p, jnp.exp(log_p)


def exit_counter_names(exits: int) -> tuple:
    """The counters :class:`DeferredExits` carries out: over a step's tokens
    the sum of ``p_t`` and the sum of the exit's own loss, an exit each."""
    return tuple(f"loop_exit_{what}_{t}" for what in ("mass", "loss") for t in range(1, exits + 1))


@jax.tree_util.register_pytree_node_class
class DeferredExits:
    """What a looped stack with an exit gate returns in training: the
    ``(R, B, T, E)`` normalised states after each of its ``R`` passes, the one
    head they share and the gate ``{"weight": (E, 1), "bias": (1,)}``. The
    criterion binds itself as to :class:`DeferredLogits`. Per token: exit
    ``t``'s cross-entropy ``l_t`` (chunked: no exit's logits exist whole),
    ``lambda_t = sigmoid(h_t . w + b)`` in float32, ``p`` as
    :func:`exit_distribution` has it, and the loss ``sum_t p_t l_t``; the
    entropy term ``-entropy_weight * H(p)`` enters the gradient and not the
    reported loss, as ``aux_loss`` does. ``counters`` is filled when the loss
    is taken (:func:`exit_counter_names`), which is before the step reads it."""

    def __init__(self, hidden, head, gate, *, entropy_weight, compute_dtype, chunk=2048):
        self.hidden, self.head, self.gate = hidden, head, gate
        self.counters = {}
        self.entropy_weight = float(entropy_weight)
        self.compute_dtype, self.chunk = jnp.dtype(compute_dtype), chunk

    def tree_flatten(self):
        return (self.hidden, self.head, self.gate), (self.entropy_weight, self.compute_dtype, self.chunk)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, entropy_weight=aux[0], compute_dtype=aux[1], chunk=aux[2])

    def _tpuddp_bind_loss(self, criterion, labels, weights=None):
        return self.cross_entropy(labels, weights, criterion.reduction)

    def cross_entropy(self, labels, weights: Optional[jax.Array], reduction: str = "mean"):
        if reduction not in ("mean", "sum"):
            raise ValueError(f"deferred exits reduce to 'mean' or 'sum', not {reduction!r}")
        exits, width = self.hidden.shape[0], self.hidden.shape[-1]
        labels = labels.reshape(-1)
        if weights is None:
            weights = jnp.ones(labels.shape, jnp.float32)
        else:
            weights = per_token_weights(weights, self.hidden.shape[1:-1]).reshape(-1)
        rows = self.hidden.reshape(exits, -1, width)
        with _prof.scope("exits"):
            losses = token_cross_entropies(
                rows.reshape(-1, width), self.head, jnp.tile(labels, exits),
                compute_dtype=self.compute_dtype, chunk=self.chunk,
            ).reshape(exits, -1)
            with _prof.scope("gate"):
                # an elementwise sum: float32 whatever a backend makes of a float32 product
                logits = jnp.sum(
                    rows.astype(jnp.float32) * self.gate["weight"][:, 0].astype(jnp.float32), axis=-1
                ) + self.gate["bias"].astype(jnp.float32)
                log_p, p = exit_distribution(logits)
            total = jnp.sum(weights * jnp.sum(p * losses, axis=0))
            entropy = -jnp.sum(weights * jnp.sum(p * log_p, axis=0))
            masses, sums = jnp.sum(weights * p, axis=1), jnp.sum(weights * losses, axis=1)
        self.counters = dict(zip(exit_counter_names(exits), jax.lax.stop_gradient(jnp.concatenate([masses, sums]))))
        denom = 1.0
        if reduction == "mean":
            denom = jnp.sum(weights)
            denom = jnp.where(denom == 0, 1.0, denom)
        aux = -self.entropy_weight * entropy / denom
        # value of the expected cross-entropy alone, gradient of the sum
        return total / denom + (aux - jax.lax.stop_gradient(aux))


def per_token_weights(weights, token_shape):
    """Per-sequence weights ``(B,)`` (the loaders' padding mask) spread over
    the sequence's tokens; per-token weights pass."""
    weights = weights.astype(jnp.float32)
    if weights.ndim < len(token_shape):
        weights = weights.reshape(weights.shape + (1,) * (len(token_shape) - weights.ndim))
    return jnp.broadcast_to(weights, token_shape)
