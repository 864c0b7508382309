"""The DeltaNet mixer's short convolution as a TPU kernel pair: one pass over
the rows each way.

What :func:`tpuddp.nn.deltanet.short_conv` computes, per channel ``c`` and
token ``t``, from rows ``x`` of ``(B, T, C)`` and taps ``w`` of ``(K, C)``::

    y[t] = sum_j w[j] x[t - (K - 1) + j]      (zeros before the sequence)
    a = y sigmoid(y)
    q, k = a / sqrt(sum_head(a^2) + eps)      (q times q_scale; v = a)

is elementwise but for two neighbourhoods: ``K - 1`` rows back in time and a
head's channels. Both fit a tile, so nothing has to pass through HBM between
the row that is read and the ``q``, ``k``, ``v`` that are written. Arithmetic
is float32 from unpacked rows, and every output is rounded once.

*Forward* (``deltanet_conv_fwd``, one call for each of ``q``, ``k``, ``v``
over its own columns of the rows, so each is an array of its own and nothing
is sliced afterwards): the grid walks channel tiles of whole heads and row
tiles; a step also gets the 16-row block that ends where its tile starts (one
packed bfloat16 register; the nearest 8 rows are used, zeros at the
sequence's start). Inside, a head's lanes go a chunk of rows at a time through
registers: the ``K`` shifted copies are sublane rotations of one block that
starts 8 rows early.

*Backward* (``deltanet_conv_bwd``, one call): reads the rows, the taps and
the three cotangents, each by its own index map (a step reads the one whose
columns it is in; the other two stay where they are and are not fetched
again), recomputes ``y`` and the norms' statistics, forms ``d_y`` and writes
``d_x[t] = sum_j w[j] d_y[t + K - 1 - j]`` summed in float32 and rounded
once. The ``d_y`` of the ``K - 1`` rows after a block are recomputed from the
rows there (the 16-row block after the tile, nothing past the sequence's
end), not exchanged. The taps' gradient ``sum_t d_y[t] x[t - (K - 1) + j]`` is
summed over row tiles in one resident float32 block a channel tile.

Both kernels are bound by the vector unit, not by HBM (66% and 45% of their
HBM bounds in the token cell on a v5e; PERF.md, PR 36), so what they
compute is written for few vector operations: ``SiLU`` is ``h + h tanh(h)``
at ``h = y / 2`` (the same function, one pass through the transcendental unit
and no division; the taps are halved once a head), and the norms' inverse
root goes through that unit too (:func:`_rsqrt`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuddp.nn.sequence import _LANES

_F32 = jnp.float32
# Tile sizes, chosen on a v5e at (1, 8192, 8192) bfloat16 rows and 4 taps
# (PERF.md, PR 36): a grid step takes 512 rows of 512 channels; the inner loop
# 64 rows of a head forward and 32 backward (the backward keeps the K shifted
# copies for the taps' gradient: at 64 rows they spill), 4 chunks side by side
# (forward 9.8 bundles a register against 8.2 at 8 and 13.9 at 2, backward
# 16.7 against 17.5 at 8; every copy is lowered again wherever a layer calls
# the kernels, which a job's set-up pays: 2 s of 44 at 4, 3 s at 8).
_ROWS = 512
_COLS = 512
_STEP_FWD = 64
_STEP_BWD = 32
_UNROLL = 4
_HALO = 16  # rows of the block that brings a tile's neighbours: one packed bfloat16 register
_NEAR = 8  # of them the rows used: one float32 register, which bounds the taps
MAX_TAPS = _NEAR


def channel_tile(channels: int, key_width: int, head_dim: int):
    """Channels a grid step: the most whole heads, ``_COLS`` wide at most,
    that divide the key columns and the value columns alike; ``None`` where
    heads are no whole lane registers or no such tile exists."""
    value_width = channels - 2 * key_width
    if head_dim <= 0 or head_dim % _LANES or value_width <= 0:
        return None
    for tile in range(_COLS - _COLS % head_dim, 0, -head_dim):
        if key_width % tile == 0 and value_width % tile == 0:
            return tile
    return None


def row_tile(t: int):
    """Rows a grid step, or ``None`` for a length that is no whole number of them."""
    return _ROWS if t > 0 and t % _ROWS == 0 else None


def _rows32(ref, start, n, lanes):
    """Rows ``[start, start + n)`` of a head's lanes of a ``(1, rows, channels)`` block, unpacked."""
    return ref[0, pl.ds(start, n), lanes].astype(_F32)


def _shifted(block, taps):
    """``block``: rows ``[r0 - 8, r0 + n)`` of a head. Copy ``j`` holds, at
    row ``r`` of ``n``, the block's row ``r0 + r - (K - 1) + j``."""
    return [block[_NEAR:] if j == taps - 1 else pltpu.roll(block, taps - 1 - j, 0)[_NEAR:] for j in range(taps)]


def _over_chunks(n, one, carry):
    """``one(c, carry)`` for the ``n`` chunks of a head in turn, ``_UNROLL``
    of them side by side: a chunk is one long chain (convolution, activation,
    a sum over lanes, the norm), and only chunks next to each other fill the
    vector unit's slots. The body is traced once, and Mosaic unrolls a whole
    loop or none, so the unrolled loop is the inner of two."""
    together = lambda g, carry: jax.lax.fori_loop(
        0, _UNROLL, lambda j, carry: one(g * _UNROLL + j, carry), carry, unroll=True
    )
    return jax.lax.fori_loop(0, n // _UNROLL, together, carry)


def _rsqrt(s):
    """``s ** -0.5`` through the transcendental unit (a logarithm and an
    exponential, either to float32's last bits; the output is rounded to the
    rows' type): ``lax.rsqrt`` refines its estimate on the vector unit, which
    is what bounds these kernels, nine operations a register of statistics."""
    return jnp.exp(-0.5 * jnp.log(s))


def _forward_kernel(before_ref, x_ref, w_ref, o_ref, *, width, norm, scale, eps):
    taps, rows, step = w_ref.shape[0], x_ref.shape[1], _STEP_FWD
    starts_sequence = pl.program_id(2) == 0

    def head(h, carry):
        lanes = pl.ds(pl.multiple_of(h * width, width), width)
        half_w = 0.5 * w_ref[:, lanes]

        def chunk(base, before):
            block = jnp.concatenate([before, _rows32(x_ref, base, step, lanes)], axis=0)
            half = sum(copy * half_w[j:j + 1] for j, copy in enumerate(_shifted(block, taps)))  # y / 2
            a = half + half * jnp.tanh(half)
            if norm:
                r = _rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)
                a = a * (r if scale == 1.0 else r * scale)
            o_ref[0, pl.ds(base, step), lanes] = a.astype(o_ref.dtype)

        first_before = jnp.where(starts_sequence, 0.0, _rows32(before_ref, 0, _HALO, lanes)[_HALO - _NEAR:])

        def one(c, carry):
            base = pl.multiple_of(c * step, step)
            behind = pl.multiple_of(jnp.maximum(base - _HALO, 0), _HALO)  # inside the tile: chunk 0 takes first_before
            near = _rows32(x_ref, behind, _HALO, lanes)[_HALO - _NEAR:]
            chunk(base, jnp.where(c == 0, first_before, near))
            return carry

        return _over_chunks(rows // step, one, carry)

    jax.lax.fori_loop(0, x_ref.shape[2] // width, head, 0)


def _backward_kernel(
    before_ref, x_ref, after_ref, w_ref, gq_ref, gq_after_ref, gk_ref, gk_after_ref, gv_ref, gv_after_ref,
    dx_ref, dw_ref, *, width, key_tiles, q_scale, eps,
):
    taps, rows, step = w_ref.shape[0], x_ref.shape[1], _STEP_BWD
    tile = pl.program_id(0)
    starts_sequence = pl.program_id(2) == 0
    ends_sequence = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, starts_sequence))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def section(g_ref, g_after_ref, norm, scale):
        def head(h, carry):
            lanes = pl.ds(pl.multiple_of(h * width, width), width)
            w = w_ref[:, lanes]
            half_w = 0.5 * w

            def chunk(base, before, after, g_after, sums):
                # rows [base - 8, base + step + 8) of x and [base, base + step + 8) of the cotangent
                block = jnp.concatenate([before, _rows32(x_ref, base, step, lanes), after], axis=0)
                g = jnp.concatenate([_rows32(g_ref, base, step, lanes), g_after], axis=0)
                copies = _shifted(block, taps)
                half = sum(copy * half_w[j:j + 1] for j, copy in enumerate(copies))  # y / 2
                tanh = jnp.tanh(half)
                sig, a = 0.5 + 0.5 * tanh, half + half * tanh
                if norm:
                    r = _rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)
                    unit = a * r
                    g = (g - unit * jnp.sum(g * unit, axis=-1, keepdims=True)) * (r if scale == 1.0 else r * scale)
                d_y = g * (sig + a * (1.0 - sig))  # rows [base, base + step + 8)
                n = step + _NEAR
                d_x = sum(
                    (d_y[:step] if j == taps - 1 else pltpu.roll(d_y, n - (taps - 1 - j), 0)[:step]) * w[j:j + 1]
                    for j in range(taps)
                )
                dx_ref[0, pl.ds(base, step), lanes] = d_x.astype(dx_ref.dtype)
                per_tap = (d_y[:step] * copy[:step] for copy in copies)
                # down to one register of 8 rows a tap; the rows are summed once a head
                return tuple(
                    s + sum(p[m:m + _NEAR] for m in range(0, step, _NEAR)) for s, p in zip(sums, per_tap)
                )

            near_before = lambda ref, start: _rows32(ref, start - _HALO, _HALO, lanes)[_HALO - _NEAR:]
            near_after = lambda ref, start: _rows32(ref, start, _HALO, lanes)[:_NEAR]
            first_before = jnp.where(starts_sequence, 0.0, near_before(before_ref, _HALO))
            last_after = near_after(after_ref, 0)
            last_g_after = jnp.where(ends_sequence, 0.0, near_after(g_after_ref, 0))
            chunks = rows // step

            def one(c, sums):
                base = pl.multiple_of(c * step, step)
                behind = pl.multiple_of(jnp.maximum(base, _HALO), _HALO)  # inside the tile: chunk 0 takes first_before
                ahead = pl.multiple_of(jnp.minimum(base + step, rows - _HALO), _HALO)  # and the last last_after
                last = c == chunks - 1
                return chunk(
                    base, jnp.where(c == 0, first_before, near_before(x_ref, behind)),
                    jnp.where(last, last_after, near_after(x_ref, ahead)),
                    jnp.where(last, last_g_after, near_after(g_ref, ahead)), sums,
                )

            sums = _over_chunks(chunks, one, (jnp.zeros((_NEAR, width), _F32),) * taps)
            dw_ref[:, lanes] += jnp.concatenate([jnp.sum(s, axis=0, keepdims=True) for s in sums], axis=0)
            return carry

        jax.lax.fori_loop(0, x_ref.shape[2] // width, head, 0)

    pl.when(tile < key_tiles)(lambda: section(gq_ref, gq_after_ref, True, q_scale))
    pl.when(jnp.logical_and(tile >= key_tiles, tile < 2 * key_tiles))(
        lambda: section(gk_ref, gk_after_ref, True, 1.0)
    )
    pl.when(tile >= 2 * key_tiles)(lambda: section(gv_ref, gv_after_ref, False, 1.0))


def _neighbours(t, rows):
    """Index of the 16-row block that ends where row tile ``i`` starts, and of
    the one that starts where it ends; held inside the sequence at its two
    ends, where the kernels put zeros in their place."""
    per_tile, blocks = rows // _HALO, t // _HALO
    return (lambda i: jnp.maximum(i * per_tile - 1, 0)), (lambda i: jnp.minimum((i + 1) * per_tile, blocks - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def short_conv(x, w, key_width, head_dim, q_scale, eps, interpret):
    """``q, k`` of ``(B, T, key_width)`` and ``v`` of the remaining columns
    from rows ``x`` of ``(B, T, C)`` and taps ``w`` of ``(K, C)``; ``T`` a
    whole number of :func:`row_tile` and the widths of :func:`channel_tile`."""
    return _forward(x, w, key_width, head_dim, q_scale, eps, interpret)[0]


def _forward(x, w, key_width, head_dim, q_scale, eps, interpret):
    b, t, channels = x.shape
    rows, cols = row_tile(t), channel_tile(channels, key_width, head_dim)
    before, _ = _neighbours(t, rows)

    def columns(first, width, norm, scale):
        first //= cols
        return pl.pallas_call(
            functools.partial(_forward_kernel, width=head_dim, norm=norm, scale=scale, eps=eps),
            name="deltanet_conv_fwd", grid=(width // cols, b, t // rows),
            in_specs=[
                pl.BlockSpec((1, _HALO, cols), lambda c, b_, i: (b_, before(i), first + c)),
                pl.BlockSpec((1, rows, cols), lambda c, b_, i: (b_, i, first + c)),
                pl.BlockSpec((w.shape[0], cols), lambda c, b_, i: (0, first + c)),
            ],
            out_specs=pl.BlockSpec((1, rows, cols), lambda c, b_, i: (b_, i, c)),
            out_shape=jax.ShapeDtypeStruct((b, t, width), x.dtype),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret,
        )(x, x, w)

    q = columns(0, key_width, True, q_scale)
    k = columns(key_width, key_width, True, 1.0)
    v = columns(2 * key_width, channels - 2 * key_width, False, 1.0)
    return (q, k, v), (x, w)


def _backward(key_width, head_dim, q_scale, eps, interpret, saved, cotangents):
    x, w = saved
    b, t, channels = x.shape
    rows, cols = row_tile(t), channel_tile(channels, key_width, head_dim)
    before, after = _neighbours(t, rows)
    key_tiles = key_width // cols
    here = lambda rows_, index: pl.BlockSpec((1, rows_, cols), lambda c, b_, i: (b_, index(i), c))

    def cotangent(first, count):
        """A cotangent's tile and the block after it where the step is in its
        columns; one block that is never left where it is not."""
        inside = lambda c: jnp.logical_and(c >= first, c < first + count)
        at = lambda index: lambda c, b_, i: (
            jnp.where(inside(c), b_, 0), jnp.where(inside(c), index(i), 0), jnp.clip(c - first, 0, count - 1)
        )
        return [pl.BlockSpec((1, rows, cols), at(lambda i: i)), pl.BlockSpec((1, _HALO, cols), at(after))]

    taps_block = pl.BlockSpec((w.shape[0], cols), lambda c, b_, i: (0, c))
    d_x, d_w = pl.pallas_call(
        functools.partial(
            _backward_kernel, width=head_dim, key_tiles=key_tiles, q_scale=q_scale, eps=eps
        ),
        name="deltanet_conv_bwd", grid=(channels // cols, b, t // rows),
        in_specs=[
            here(_HALO, before), here(rows, lambda i: i), here(_HALO, after), taps_block,
            *cotangent(0, key_tiles), *cotangent(key_tiles, key_tiles),
            *cotangent(2 * key_tiles, channels // cols - 2 * key_tiles),
        ],
        out_specs=[here(rows, lambda i: i), taps_block],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(w.shape, _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, x, x, w, *(g for g in cotangents for _ in range(2)))
    return d_x, d_w.astype(w.dtype)


short_conv.defvjp(_forward, _backward)
