"""The gated delta rule's carry over chunks as a TPU kernel pair.

After the chunk-local phase (``nn/deltanet_kernels.py``: ``U``, ``W``, ``K~``,
``Q~``, ``P`` a chunk) what is left of
:func:`tpuddp.nn.deltanet.chunk_gated_delta_rule` walks the chunks in order
with the ``Dk x Dv`` state ``S``, zero before the first::

    V' = U - W round(S);  O = Q~ round(S) + P round(V')
    S <- exp(G_C) S + K~^T round(V')

where ``round`` is the rounding to the products' input type that the plain
path's carry gives ``S`` and ``V'`` at the same places; the state itself is
float32 throughout and every product accumulates in float32.

One grid step takes ``block`` chunks of ``heads`` value heads (independent
chains side by side, so that one's products fill the other's waits); the
grid's last axis walks a head's chunk blocks in order and the state stays in a
VMEM scratch along it. The forward kernel reads the five arrays in the ``(B,
N, Hv, C, width)`` layout the chunk-local kernel writes and the decay sums in
the layout that kernel reads them in (the chunk's decay is the exponential of
its last), and writes the output rows straight into ``(B, T, Hv * Dv)`` in the
values' type, and for the backward pass every chunk's rounded start state and
``round(V')`` in the products' input type. The backward kernel walks the
blocks last to first through its index maps with the state's cotangent in the
scratch, and writes each cotangent in its primal's type (``dW``, ``dP`` in the
products' input type, ``dU``, ``dK~``, ``dQ~`` float32; PERF.md, PR 31) and
the decay's at each chunk's last token. Cotangents are rounded where they
enter a product, as a TPU's default precision rounds them in the plain path;
the decay's cotangent ``sum(S * dS')`` reads the rounded start state, the only
one kept.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuddp.nn.sequence import round_to

_F32 = jnp.float32
_BLOCK = 4  # chunks a grid step, at most (a divisor of the chunk-local kernels')
_HEADS = 4  # value heads a grid step, at most
_VMEM = 10 << 20  # bytes a grid step's blocks may take, both buffers, of the 16 MiB a kernel is given


def tile(chunk: int, dk: int, dv: int, hv: int, block: int, itemsize: int = 4, out_itemsize: int = 4):
    """``(chunks, heads)`` a grid step: as many as :data:`_BLOCK` and
    :data:`_HEADS` allow, divisors of ``block`` and ``hv``, whose blocks in
    the backward kernel (the larger of the two) fit :data:`_VMEM` twice over;
    ``None`` where one chunk of one head does not (a state of 1,024 x 1,024).
    ``itemsize``: bytes of the products' input type, ``out_itemsize`` of the
    output rows'; the defaults are the widest."""
    rows, square, state = chunk * max(dk, dv), chunk * chunk, dk * dv
    read = rows * (itemsize + 8) + square * itemsize + state * itemsize + rows * (itemsize + out_itemsize)
    written = rows * (12 + itemsize) + square * itemsize
    room = _VMEM // (2 * (read + written))
    if room < 1:
        return None
    largest = lambda n, most: next(size for size in range(min(n, most), 0, -1) if n % size == 0)
    heads = largest(hv, min(_HEADS, room))
    return largest(block, min(_BLOCK, room // heads)), heads


def _nn(x, y):  # (H, I, J), (H, J, D) -> (H, I, D)
    return jnp.einsum("hij,hjd->hid", x, y, preferred_element_type=_F32)


def _nt(x, y):  # (H, I, D), (H, J, D) -> (H, I, J)
    return jnp.einsum("hid,hjd->hij", x, y, preferred_element_type=_F32)


def _tn(x, y):  # (H, I, J), (H, I, D) -> (H, J, D)
    return jnp.einsum("hij,hid->hjd", x, y, preferred_element_type=_F32)


def _held(ref, r, like):
    """Chunk ``r`` of a float32 array that holds rounded values, in the type they were rounded to."""
    return ref[0, r].astype(like.dtype)


def _decay(g_ref, r, chunk):
    """``exp`` of the chunk's last decay sum: ``(H, 1, 1)``."""
    return jnp.exp(g_ref[0, :, r][:, :, chunk - 1:])


def _forward_kernel(u_ref, w_ref, kt_ref, qd_ref, p_ref, g_ref, o_ref, *rest, chunk, dtype):
    *saved, state = rest  # the start states and round(V'), where the backward pass will want them
    heads, dv = state.shape[0], state.shape[2]
    rt = lambda x: round_to(x, dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one_chunk(r, carry):
        s = state[...]
        s_in = rt(s)
        v_in = rt(u_ref[0, r] - _nn(w_ref[0, r], s_in))
        o = _nn(_held(qd_ref, r, w_ref), s_in) + _nn(p_ref[0, r], v_in)
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        for h in range(heads):
            o_ref[0, rows, h * dv:(h + 1) * dv] = o[h].astype(o_ref.dtype)
        state[...] = s * _decay(g_ref, r, chunk) + _tn(_held(kt_ref, r, w_ref), v_in)
        if saved:
            saved[0][0, r], saved[1][0, r] = s_in.astype(saved[0].dtype), v_in.astype(saved[1].dtype)
        return carry

    jax.lax.fori_loop(0, u_ref.shape[1], one_chunk, 0)


def _backward_kernel(
    w_ref, kt_ref, qd_ref, p_ref, g_ref, s_ref, v_ref, do_ref,
    du_ref, dw_ref, dkt_ref, dqd_ref, dp_ref, dg_ref, d_state, *, chunk, dtype,
):
    heads, dv = d_state.shape[0], d_state.shape[2]
    block = w_ref.shape[1]
    rt = lambda x: round_to(x, dtype)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2) == chunk - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    def one_chunk(i, carry):
        r = block - 1 - i
        ds_next = d_state[...]  # the cotangent of the state the chunk leaves
        ds_in = rt(ds_next)
        s_in, v_in, w, kt = s_ref[0, r], v_ref[0, r], w_ref[0, r], _held(kt_ref, r, w_ref)
        rows = pl.ds(pl.multiple_of(r * chunk, chunk), chunk)
        do = jnp.stack([do_ref[0, rows, h * dv:(h + 1) * dv] for h in range(heads)])
        if do.dtype != w.dtype:
            do = rt(do.astype(_F32))
        d_v = _tn(p_ref[0, r], do) + _nn(kt, ds_in)  # of V', through its rounding
        dv_in = rt(d_v)
        du_ref[0, r] = d_v
        dw_ref[0, r] = (-_nt(dv_in, s_in)).astype(dw_ref.dtype)
        dqd_ref[0, r] = _nt(do, s_in)
        dp_ref[0, r] = _nt(do, v_in).astype(dp_ref.dtype)
        dkt_ref[0, r] = _nt(v_in, ds_in)
        decay = _decay(g_ref, r, chunk)
        through = jnp.sum(jnp.sum(s_in.astype(_F32) * ds_next, axis=2, keepdims=True), axis=1, keepdims=True)
        dg_ref[0, :, r] = jnp.where(last, through * decay, 0.0)  # exp's derivative, at the chunk's last token
        d_state[...] = ds_next * decay + (_tn(_held(qd_ref, r, w_ref), do) - _tn(w, dv_in))
        return carry

    jax.lax.fori_loop(0, block, one_chunk, 0)


def _layout(u, w, gsum, block, out_itemsize, reverse):
    """Grid and specifications both kernels share. ``u``: ``(B, N, Hv, C,
    Dv)``; ``w``: ``(B, N, Hv, C, Dk)``; ``gsum``: ``(B, Hv, N, C)``."""
    b, n, hv, chunk, dv = u.shape
    dk = w.shape[-1]
    block, heads = tile(chunk, dk, dv, hv, block, w.dtype.itemsize, out_itemsize)
    blocks = n // block
    at = (lambda c: blocks - 1 - c) if reverse else (lambda c: c)
    per_chunk = lambda width: pl.BlockSpec((1, block, heads, chunk, width), lambda b_, h, c: (b_, at(c), h, 0, 0))
    states = pl.BlockSpec((1, block, heads, dk, dv), lambda b_, h, c: (b_, at(c), h, 0, 0))
    vectors = pl.BlockSpec((1, heads, block, 1, chunk), lambda b_, h, c: (b_, h, at(c), 0, 0))
    rows = pl.BlockSpec((1, block * chunk, heads * dv), lambda b_, h, c: (b_, at(c), h))
    grid = (b, hv // heads, blocks)
    return grid, (per_chunk, states, vectors, rows), pltpu.VMEM((heads, dk, dv), _F32), (b, n, hv, chunk, dk, dv)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, interpret):
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[scratch],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def carry(u, w, k_tail, q_grown, scores, gsum, block, dtype, out_dtype, interpret):
    """The output rows ``(B, N * C, Hv * Dv)`` in ``out_dtype`` from ``U``,
    ``W``, ``K~``, ``Q~``, ``P`` of ``(B, N, Hv, C, .)`` as
    :func:`tpuddp.nn.deltanet_kernels.chunk_local` returns them and ``gsum``
    of ``(B, Hv, N, C)`` float32, the decay's running sum inside each chunk.
    ``N`` is a multiple of ``block``; ``dtype`` is the products' input type."""
    return _forward(u, w, k_tail, q_grown, scores, gsum, block, dtype, out_dtype, interpret, save=False)[0]


def _forward(u, w, k_tail, q_grown, scores, gsum, block, dtype, out_dtype, interpret, save=True):
    grid, (per_chunk, states, vectors, rows), scratch, (b, n, hv, chunk, dk, dv) = _layout(
        u, w, gsum, block, jnp.dtype(out_dtype).itemsize, reverse=False
    )
    kept = lambda *shape: jax.ShapeDtypeStruct((b, n, hv, *shape), w.dtype)
    o, *saved = _call(
        functools.partial(_forward_kernel, chunk=chunk, dtype=dtype), "deltanet_carry_fwd", grid,
        [per_chunk(dv), per_chunk(dk), per_chunk(dk), per_chunk(dk), per_chunk(chunk), vectors],
        [rows] + [states, per_chunk(dv)] * save,
        [jax.ShapeDtypeStruct((b, n * chunk, hv * dv), out_dtype)] + [kept(dk, dv), kept(chunk, dv)] * save,
        scratch, interpret,
    )(u, w, k_tail, q_grown, scores, gsum.reshape(b, hv, n, 1, chunk))
    return o, (w, k_tail, q_grown, scores, gsum, *saved)


def _backward(block, dtype, out_dtype, interpret, saved, d_o):
    w, k_tail, q_grown, scores, gsum, starts, v_new = saved
    grid, (per_chunk, states, vectors, rows), scratch, (b, n, hv, chunk, dk, dv) = _layout(
        v_new, w, gsum, block, d_o.dtype.itemsize, reverse=True
    )
    out = lambda width, dt: jax.ShapeDtypeStruct((b, n, hv, chunk, width), dt)
    *grads, d_g = _call(
        functools.partial(_backward_kernel, chunk=chunk, dtype=dtype), "deltanet_carry_bwd", grid,
        [per_chunk(dk), per_chunk(dk), per_chunk(dk), per_chunk(chunk), vectors, states, per_chunk(dv), rows],
        [per_chunk(dv), per_chunk(dk), per_chunk(dk), per_chunk(dk), per_chunk(chunk), vectors],
        [out(dv, _F32), out(dk, w.dtype), out(dk, k_tail.dtype), out(dk, q_grown.dtype), out(chunk, scores.dtype),
         jax.ShapeDtypeStruct((b, hv, n, 1, chunk), _F32)],
        scratch, interpret,
    )(w, k_tail, q_grown, scores, gsum.reshape(b, hv, n, 1, chunk), starts, v_new, d_o)
    return (*grads, d_g.reshape(gsum.shape).astype(gsum.dtype))


carry.defvjp(_forward, _backward)
