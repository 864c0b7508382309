"""The chunk-local phase of the gated delta rule as a TPU kernel pair.

Everything :func:`tpuddp.nn.deltanet.chunk_gated_delta_rule` computes before
its carry depends on one chunk only. With ``G`` the running decay sum inside
the chunk and ``D[i, j] = exp(G_i - G_j)`` for ``i >= j``::

    A = strictly_lower((beta k) k^T * D);  T = (I + A)^-1
    U = T (beta v);  W = T (beta k * exp(G))
    K~ = k * exp(G_C - G);  Q~ = q * exp(G);  P = lower((q k^T) * D)

One grid step takes ``block`` chunks of one value head: it reads their rows
of ``q``, ``k`` (the key head the value head shares: the grid's last axis
walks the value heads of one key head, so its rows are fetched once) and
``v`` straight from the ``(B, T, heads * width)`` arrays, keeps ``D``, ``A``,
the inverse's levels and ``q k^T`` in VMEM, and writes ``U``, ``W``, ``K~``,
``Q~``, ``P`` rounded to the products' input type, which is where the plain
path rounds them, laid out chunks first, as the carry's kernel
(``nn/deltanet_carry_kernels.py``) reads them. ``W`` and
``P`` are stored in that type: their cotangents only ever enter products,
which round them anyway. ``U``, ``K~`` and ``Q~`` are stored in float32,
because their cotangents are used elementwise and a cotangent takes its
primal's type: through a bfloat16 ``K~`` and ``Q~`` the token cell's update
parted from its float32 reference twice as far (PERF.md, PR 31). ``T`` goes
out too, for the backward kernel: the inverse's derivative is ``-T^T (.) T^T``
below the diagonal and never inverts again. The backward kernel adds the
gradients of the value heads that share a key head in its output block.

The inverse is the plain path's: the four 16-row diagonal blocks by forward
substitution (the 15 steps run for every block of every chunk of the grid
step at once), then two joins by halves, each two products in float32
(:func:`_product32`). Vectors of a chunk arrive as rows ``(1, C)`` and are
turned into columns ``(C, 1)`` through the diagonal of their broadcast, which
is exact and needs no transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuddp.nn.sequence import round_to

_BASE = 16  # rows of a diagonal block inverted by substitution
_F32 = jnp.float32


def _column(row, eye):
    """``(R, 1, C)`` to ``(R, C, 1)``: the diagonal of the row's broadcast."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _row(column, eye):
    """``(R, C, 1)`` to ``(R, 1, C)``."""
    return jnp.sum(jnp.where(eye, column, 0.0), axis=1, keepdims=True)


def _nt(x, y):  # (R, I, D), (R, J, D) -> (R, I, J)
    return jnp.einsum("rid,rjd->rij", x, y, preferred_element_type=_F32)


def _nn(x, y):  # (R, I, J), (R, J, D) -> (R, I, D)
    return jnp.einsum("rij,rjd->rid", x, y, preferred_element_type=_F32)


def _tn(x, y):  # (R, I, J), (R, I, D) -> (R, J, D)
    return jnp.einsum("rij,rid->rjd", x, y, preferred_element_type=_F32)


def _product32(x, y):
    """``x @ y`` of float32 matrices to float32's accuracy on a matrix unit
    that multiplies bfloat16: each factor as a bfloat16 head and a bfloat16
    rest, and the three products that matter (relative error 2^-16 of the
    factors' size, a hundredth of the rounding the products' inputs get
    anyway; the compiler's own float32 product takes six passes)."""
    split = lambda a: (a.astype(jnp.bfloat16), (a - a.astype(jnp.bfloat16).astype(_F32)).astype(jnp.bfloat16))
    (x_head, x_rest), (y_head, y_rest) = split(x), split(y)
    return _nn(x_head, y_head) + (_nn(x_head, y_rest) + _nn(x_rest, y_head))


def _invert_unit_lower(a, i, j):
    """``(I + a)^-1`` for strictly lower ``a`` of ``(R, C, C)`` float32."""
    r, c, _ = a.shape
    base = min(_BASE, c)
    eye = (i == j).astype(_F32)
    inv = jnp.broadcast_to(eye, a.shape)
    # the diagonal blocks side by side: row i holds a[i, base * (i // base):][:base]
    diagonal = sum(
        jnp.where(i[:, :base] // base == blk, a[:, :, blk * base:(blk + 1) * base], 0.0) for blk in range(c // base)
    )
    for step in range(base - 1):
        # column `step` of every diagonal block, as a factor a row; row `step`
        # of every block, final since the step before, under the block's rows
        factor = diagonal[:, :, step:step + 1]
        done = inv.reshape(r, c // base, base, c)[:, :, step:step + 1, :]
        done = jnp.broadcast_to(done, (r, c // base, base, c)).reshape(r, c, c)
        inv = inv - factor * done
    size = base
    while size < c:
        below = jnp.where(((i // size) % 2 == 1) & (j // size == i // size - 1), a, 0.0)
        inv = inv - _product32(_product32(inv, below), inv)
        size *= 2
    return inv


def _prelude(q_ref, k_ref, v_ref, g_ref, b_ref, chunk):
    """The grid step's rows of ``q``, ``k``, ``v`` a chunk apart, ``(R, C,
    width)``, and what both kernels make of its decay sums and ``beta``."""
    rows = g_ref.shape[2]
    take = lambda ref: ref[0].reshape(rows, chunk, ref.shape[-1])
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = i == j
    g_row, b_row = g_ref[0, 0], b_ref[0, 0]  # (R, 1, C)
    g_col, b_col = _column(g_row, eye), _column(b_row, eye)
    decay = jnp.exp(jnp.where(i >= j, g_col - g_row, -jnp.inf))
    return take(q_ref), take(k_ref), take(v_ref), g_row, g_col, b_col, decay, i, j


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, u_ref, w_ref, kt_ref, qd_ref, p_ref, t_ref, *, chunk, dtype):
    q, k, v, g_row, g_col, b_col, decay, i, j = _prelude(q_ref, k_ref, v_ref, g_ref, b_ref, chunk)
    rt = lambda x: round_to(x, dtype)
    k32 = k.astype(_F32)
    k_beta = k32 * b_col
    grown = jnp.exp(g_col)
    a = jnp.where(i > j, _nt(rt(k_beta), rt(k)) * decay, 0.0)
    t_inv = _invert_unit_lower(a, i, j)
    t_ref[0, :, 0] = t_inv
    u_ref[0, :, 0] = _nn(rt(t_inv), rt(v.astype(_F32) * b_col))
    w_ref[0, :, 0] = rt(_nn(rt(t_inv), rt(k_beta * grown))).astype(w_ref.dtype)
    g_last = g_row[:, :, chunk - 1:]
    kt_ref[0, :, 0] = rt(k32 * jnp.exp(g_last - g_col)).astype(kt_ref.dtype)
    qd_ref[0, :, 0] = rt(q.astype(_F32) * grown).astype(qd_ref.dtype)
    p_ref[0, :, 0] = rt(_nt(rt(q), rt(k)) * decay).astype(p_ref.dtype)


def _backward_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, t_ref, du_ref, dw_ref, dkt_ref, dqd_ref, dp_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *, chunk, dtype,
):
    q, k, v, g_row, g_col, b_col, decay, i, j = _prelude(q_ref, k_ref, v_ref, g_ref, b_ref, chunk)
    rt = lambda x: round_to(x, dtype)
    eye = i == j
    q32, k32, v32 = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    k_beta, v_beta = k32 * b_col, v32 * b_col
    grown = jnp.exp(g_col)
    k_grown = k_beta * grown
    tail = jnp.exp(g_row[:, :, chunk - 1:] - g_col)
    du, dw, dkt, dqd, dp = (ref[0, :, 0].astype(_F32) for ref in (du_ref, dw_ref, dkt_ref, dqd_ref, dp_ref))

    t_t = jnp.swapaxes(t_ref[0, :, 0], 1, 2)
    d_t = _nt(rt(du), rt(v_beta)) + _nt(rt(dw), rt(k_grown))
    d_a = -jnp.where(i > j, _product32(_product32(t_t, d_t), t_t), 0.0)
    dv_beta, dk_grown = _nn(rt(t_t), rt(du)), _nn(rt(t_t), rt(dw))

    s1 = _nt(rt(k_beta), rt(k))  # above the diagonal d_a and decay are zero
    s2 = _nt(rt(q), rt(k))
    d_s1, d_s2 = d_a * decay, dp * decay
    through_decay = d_s1 * s1 + d_s2 * s2  # dD * D

    dk_beta = _nn(rt(d_s1), rt(k)) + dk_grown * grown
    dq = (_nn(rt(d_s2), rt(k)) + dqd * grown).reshape(dq_ref.shape[1:])
    dk = _tn(rt(d_s1), rt(k_beta)) + _tn(rt(d_s2), rt(q)) + dkt * tail + dk_beta * b_col
    dk = dk.reshape(dk_ref.shape[1:])
    first = pl.program_id(3) == 0  # of the value heads that share this key head: their gradients add up

    @pl.when(first)
    def _():
        dq_ref[0] = dq
        dk_ref[0] = dk

    @pl.when(jnp.logical_not(first))
    def _():
        dq_ref[0] += dq
        dk_ref[0] += dk

    dv_ref[0] = (dv_beta * b_col).reshape(dv_ref.shape[1:]).astype(dv_ref.dtype)
    along = lambda x: jnp.sum(x, axis=2, keepdims=True)  # over a token's width: (R, C, 1)
    db_ref[0, 0] = _row(along(dk_beta * k32) + along(dv_beta * v32), eye)
    from_tail = along(dkt * k32) * tail  # K~ = k exp(G_C - G): minus at the token, plus at the chunk's last
    dg_col = along(through_decay) + (along(dk_grown * k_beta) + along(dqd * q32)) * grown - from_tail
    at_last = jnp.where(j[:1] == chunk - 1, jnp.sum(from_tail, axis=1, keepdims=True), 0.0)
    dg_ref[0, 0] = _row(dg_col, eye) - jnp.sum(through_decay, axis=1, keepdims=True) + at_last


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )


def _layout(q, k, v, gsum, beta, block, chunk):
    """The arrays as the kernels read them and the specifications they share.
    ``q``, ``k``: ``(B, T, Hk, Dk)``; ``v``: ``(B, T, Hv, Dv)``; ``gsum``,
    ``beta``: ``(B, Hv, N, C)``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n = t // chunk
    share = hv // hk
    rows = block * chunk
    flat = lambda a: a.reshape(b, t, -1)
    vector = lambda a: a.astype(_F32).reshape(b, hv, n, 1, chunk)
    arrays = (flat(q), flat(k), flat(v), vector(gsum), vector(beta))
    # grid: (B, key heads, chunk blocks, value heads a key head); value head h reads key head h // share
    key_rows = pl.BlockSpec((1, rows, dk), lambda b_, h, c, s: (b_, c, h))
    tokens = lambda width: pl.BlockSpec((1, rows, width), lambda b_, h, c, s: (b_, c, h * share + s))
    vectors = pl.BlockSpec((1, 1, block, 1, chunk), lambda b_, h, c, s: (b_, h * share + s, c, 0, 0))
    # what the carry reads chunk by chunk is laid out chunks first: (B, N, Hv, C, width)
    per_chunk = lambda width: pl.BlockSpec((1, block, 1, chunk, width), lambda b_, h, c, s: (b_, c, h * share + s, 0, 0))
    return arrays, (key_rows, tokens, vectors, per_chunk), (b, hk, n // block, share), (b, t, hk, dk, hv, dv, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def chunk_local(q, k, v, gsum, beta, chunk, block, dtype, interpret):
    """``U, W, K~, Q~, P`` of ``(B, N, Hv, C, .)``, chunks first as the
    carry's kernel (``nn/deltanet_carry_kernels.py``) reads them, from ``q``,
    ``k`` of ``(B, T, Hk, Dk)``, ``v`` of ``(B, T, Hv, Dv)`` and ``gsum`` (the
    decay's running sum inside each chunk), ``beta`` of ``(B, Hv, N, C)``
    float32. ``T`` is ``N * chunk`` and ``N`` a multiple of ``block``;
    ``dtype`` is the products' input type."""
    return _forward(q, k, v, gsum, beta, chunk, block, dtype, interpret)[0]


def _forward(q, k, v, gsum, beta, chunk, block, dtype, interpret):
    arrays, (key_rows, tokens, vectors, per_chunk), grid, (b, t, hk, dk, hv, dv, n) = _layout(
        q, k, v, gsum, beta, block, chunk
    )
    rounded = round_to(jnp.zeros((), _F32), dtype).dtype
    out = lambda width, dt: jax.ShapeDtypeStruct((b, n, hv, chunk, width), dt)
    *outs, t_inv = _call(
        functools.partial(_forward_kernel, chunk=chunk, dtype=dtype), "deltanet_chunk_fwd", grid,
        [key_rows, key_rows, tokens(dv), vectors, vectors],
        [per_chunk(dv), per_chunk(dk), per_chunk(dk), per_chunk(dk), per_chunk(chunk), per_chunk(chunk)],
        [out(dv, _F32), out(dk, rounded), out(dk, _F32), out(dk, _F32), out(chunk, rounded), out(chunk, _F32)],
        interpret,
    )(*arrays)
    return tuple(outs), (q, k, v, gsum, beta, t_inv)


def _backward(chunk, block, dtype, interpret, saved, cotangents):
    q, k, v, gsum, beta, t_inv = saved
    arrays, (key_rows, tokens, vectors, per_chunk), grid, (b, t, hk, dk, hv, dv, n) = _layout(
        q, k, v, gsum, beta, block, chunk
    )
    vector = jax.ShapeDtypeStruct((b, hv, n, 1, chunk), _F32)
    dq, dk_, dv_, dg, db = _call(
        functools.partial(_backward_kernel, chunk=chunk, dtype=dtype), "deltanet_chunk_bwd", grid,
        [key_rows, key_rows, tokens(dv), vectors, vectors,
         per_chunk(chunk), per_chunk(dv), per_chunk(dk), per_chunk(dk), per_chunk(dk), per_chunk(chunk)],
        [key_rows, key_rows, tokens(dv), vectors, vectors],
        [jax.ShapeDtypeStruct((b, t, hk * dk), _F32), jax.ShapeDtypeStruct((b, t, hk * dk), _F32),
         jax.ShapeDtypeStruct((b, t, hv * dv), v.dtype), vector, vector],
        interpret,
    )(*arrays, t_inv, *cotangents)
    return (
        dq.reshape(q.shape).astype(q.dtype), dk_.reshape(k.shape).astype(k.dtype), dv_.reshape(v.shape),
        dg.reshape(gsum.shape).astype(gsum.dtype), db.reshape(beta.shape).astype(beta.dtype),
    )


chunk_local.defvjp(_forward, _backward)
