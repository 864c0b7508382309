"""What learned sparse attention computes rows by keys beside attention
itself, as TPU kernels: a block of the result is made where its operands are
and written once.

For a group of ``G`` queries (the first at position ``start``) against ``S``
keys, :func:`tpuddp.nn.sequence.sparse_attention_rows` needs two ``(G, S)``
float32 arrays that are sums over heads of a product's elementwise function:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])                 (the index scores)
    p[t, s] = (1 / H) sum_h exp(q[t, h] . k[s, h // g] - lse[t, h]) on the selection  (the indexer's target)

In plain XLA each head's ``(G, S)`` product goes out to HBM and comes back to
be summed; here a grid step holds one ``(rows, keys)`` block of the sum in
VMEM, walks the heads inside, and writes the block once. Blocks that lie
wholly above the diagonal (no key at or before the block's last query) are
written as zeros and cost nothing else. The index scores bring their backward
pass (``d qI``, ``d w`` summed over the key blocks in blocks that stay
resident, ``d kI`` a partial a block of rows that the caller adds up): a head's
product is recomputed from the same operands, so nothing is kept between the
passes but the inputs.

Products take their inputs as they are given (``compute_dtype``, already
rounded) and accumulate in float32; the ReLU, the weights, the exponential
and every sum are float32. Kernels ``sparse_index_scores_fwd``,
``sparse_index_scores_bwd`` and ``sparse_mean_probabilities``.

The selection's threshold, a row's ``k``-th largest score, is no product at
all: 32 counts over the row, one a bit of the answer
(:func:`tpuddp.nn.sequence.top_k_mask`). ``sparse_kth_largest`` keeps a few
whole rows of ordered bit patterns in VMEM for all 32, where plain XLA reads
the rows from HBM for each.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))  # a product whose right side is given rows by columns transposed
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _block(n: int, most: int) -> int:
    """Rows or keys a grid step: the largest of 512, 256 and 128 that divides
    ``n`` and is at most ``most``."""
    return next(b for b in (512, 256, 128) if b <= most and n % b == 0)


def blocks(g: int, s: int):
    """``(rows, keys)`` of a grid step for a group of ``g`` queries against
    ``s`` keys, or ``None`` where they are no whole blocks of 128."""
    if g % 128 or s % 128:
        return None
    return _block(g, 512), _block(s, 512)


def _seen(start_ref, rows: int, keys: int):
    """Whether the grid step's block holds a key at or before its last query."""
    i, j = pl.program_id(0), pl.program_id(1)
    return j * keys <= start_ref[0] + (i + 1) * rows - 1


def _grid_spec(g, s, rows, keys, in_specs, out_specs):
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(g // rows, s // keys), in_specs=in_specs, out_specs=out_specs,
    )


# -- the index scores -------------------------------------------------------------------

def _scores_fwd_kernel(start_ref, qi_ref, ki_ref, wi_ref, out_ref, *, heads, rows, keys):
    seen = _seen(start_ref, rows, keys)

    @pl.when(seen)
    def _():
        k = ki_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)
        for h in range(heads):
            z = jax.lax.dot_general(qi_ref[h], k, _NT, preferred_element_type=_F32)
            out_ref[...] += wi_ref[:, h:h + 1] * jnp.maximum(z, 0.0)

    @pl.when(jnp.logical_not(seen))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _scores_bwd_kernel(start_ref, qi_ref, ki_ref, wi_ref, d_ref, d_qi_ref, d_wi_ref, d_ki_ref, *, heads, rows, keys):
    seen = _seen(start_ref, rows, keys)

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_qi_ref[...] = jnp.zeros_like(d_qi_ref)
        d_wi_ref[...] = jnp.zeros_like(d_wi_ref)

    @pl.when(seen)
    def _():
        k, d = ki_ref[...], d_ref[...]
        d_ki_ref[0] = jnp.zeros_like(d_ki_ref[0])
        for h in range(heads):
            q = qi_ref[h]
            z = jax.lax.dot_general(q, k, _NT, preferred_element_type=_F32)
            live = z > 0.0
            d_wi_ref[:, h:h + 1] += jnp.sum(jnp.where(live, d * z, 0.0), axis=1, keepdims=True)
            d_z = jnp.where(live, d * wi_ref[:, h:h + 1], 0.0).astype(q.dtype)  # a product's input, as the forward's are
            d_qi_ref[h] += jax.lax.dot_general(d_z, k, _NN, preferred_element_type=_F32)
            d_ki_ref[0] += jax.lax.dot_general(d_z, q, _TN, preferred_element_type=_F32)

    @pl.when(jnp.logical_not(seen))
    def _():
        d_ki_ref[0] = jnp.zeros_like(d_ki_ref[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def index_scores(qi, ki, wi, start, interpret=False):
    """``I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s])``, ``(G, S)`` float32,
    of ``qi (G, H, D)`` and ``ki (S, D)`` as they are given and ``wi (G, H)``
    float32, for queries from position ``start`` on: blocks no query of which
    sees a key are 0."""
    return _index_scores_fwd(qi, ki, wi, start, interpret)[0]


def _index_scores_fwd(qi, ki, wi, start, interpret):
    (g, heads, d), s = qi.shape, ki.shape[0]
    rows, keys = blocks(g, s)
    heads_first = jnp.moveaxis(qi, 1, 0)
    out = pl.pallas_call(
        functools.partial(_scores_fwd_kernel, heads=heads, rows=rows, keys=keys),
        name="sparse_index_scores_fwd",
        grid_spec=_grid_spec(g, s, rows, keys, [
            pl.BlockSpec((heads, rows, d), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec((keys, d), lambda i, j, start: (j, 0)),
            pl.BlockSpec((rows, heads), lambda i, j, start: (i, 0)),
        ], pl.BlockSpec((rows, keys), lambda i, j, start: (i, j))),
        out_shape=jax.ShapeDtypeStruct((g, s), _F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), heads_first, ki, wi)
    return out, (qi, ki, wi, start)


def _index_scores_bwd(interpret, saved, d):
    qi, ki, wi, start = saved
    (g, heads, width), s = qi.shape, ki.shape[0]
    rows, keys = blocks(g, s)
    rows = min(rows, 256)  # the queries and their float32 gradient, every head's, lie in VMEM at once
    d_qi, d_wi, d_ki = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, heads=heads, rows=rows, keys=keys),
        name="sparse_index_scores_bwd",
        grid_spec=_grid_spec(g, s, rows, keys, [
            pl.BlockSpec((heads, rows, width), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec((keys, width), lambda i, j, start: (j, 0)),
            pl.BlockSpec((rows, heads), lambda i, j, start: (i, 0)),
            pl.BlockSpec((rows, keys), lambda i, j, start: (i, j)),
        ], [
            pl.BlockSpec((heads, rows, width), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec((rows, heads), lambda i, j, start: (i, 0)),
            pl.BlockSpec((1, keys, width), lambda i, j, start: (i, j, 0)),
        ]),
        out_shape=[
            jax.ShapeDtypeStruct((heads, g, width), _F32), jax.ShapeDtypeStruct((g, heads), _F32),
            jax.ShapeDtypeStruct((g // rows, s, width), _F32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), jnp.moveaxis(qi, 1, 0), ki, wi, d.astype(_F32))
    return jnp.moveaxis(d_qi, 0, 1).astype(qi.dtype), jnp.sum(d_ki, axis=0).astype(ki.dtype), d_wi.astype(wi.dtype), None


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


# -- the indexer's target ---------------------------------------------------------------

def _mean_probabilities_kernel(start_ref, q_ref, k_ref, lse_ref, selected_ref, out_ref, *, heads, group, rows, keys):
    seen = _seen(start_ref, rows, keys)

    @pl.when(seen)
    def _():
        selected = selected_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)
        for h in range(heads):
            scores = jax.lax.dot_general(q_ref[h], k_ref[h // group], _NT, preferred_element_type=_F32)
            out_ref[...] += jnp.exp(jnp.where(selected, scores - lse_ref[:, h:h + 1], -jnp.inf))
        out_ref[...] *= 1.0 / heads

    @pl.when(jnp.logical_not(seen))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def mean_probabilities(q, k, lse, selected, start, interpret=False):
    """``(1 / H) sum_h exp(q[:, h] . k[:, h // g] - lse[h])`` where
    ``selected``, 0 elsewhere: ``(G, S)`` float32 of ``q (G, H, D)`` (scaled
    as the attention kernel was given it) and ``k (S, Hkv, D)`` as they are
    given, each head's log-sum-exp ``lse (H, G)`` and ``selected (G, S)``
    bool. No gradient."""
    (g, heads, d), (s, kv_heads, _) = q.shape, k.shape
    rows, keys = blocks(g, s)
    rows = min(rows, 256)  # every head's queries of a block lie in VMEM at once
    return pl.pallas_call(
        functools.partial(_mean_probabilities_kernel, heads=heads, group=heads // kv_heads, rows=rows, keys=keys),
        name="sparse_mean_probabilities",
        grid_spec=_grid_spec(g, s, rows, keys, [
            pl.BlockSpec((heads, rows, d), lambda i, j, start: (0, i, 0)),
            pl.BlockSpec((kv_heads, keys, d), lambda i, j, start: (0, j, 0)),
            pl.BlockSpec((rows, heads), lambda i, j, start: (i, 0)),
            pl.BlockSpec((rows, keys), lambda i, j, start: (i, j)),
        ], pl.BlockSpec((rows, keys), lambda i, j, start: (i, j))),
        out_shape=jax.ShapeDtypeStruct((g, s), _F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.reshape(start, (1,)).astype(jnp.int32), jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0), lse.T.astype(_F32), selected)


# -- the selection's threshold ------------------------------------------------------------

_SIGN = -2 ** 31  # flips an unsigned order into a signed one, bit pattern for bit pattern


def _kth_largest_kernel(keys_ref, out_ref, *, k):
    keys = keys_ref[...] ^ jnp.int32(_SIGN)  # compared as signed: the same order

    def bit(i, found):
        candidate = found | jnp.left_shift(jnp.int32(1), 31 - i)
        count = jnp.sum((keys >= (candidate ^ jnp.int32(_SIGN))).astype(jnp.int32), axis=1, keepdims=True)
        return jnp.where(count >= k, candidate, found)

    found = jax.lax.fori_loop(0, 32, bit, jnp.zeros((keys.shape[0], 1), jnp.int32))
    out_ref[...] = jnp.broadcast_to(found, out_ref.shape)


def kth_largest(keys, k: int, interpret=False):
    """A row's ``k``-th largest of ``keys (G, S)`` uint32, ``S >= k``, as
    ``(G,)`` uint32: the largest value that ``k`` of the row's keys are at or
    above, found a bit at a time from the top."""
    g, s = keys.shape
    rows = next(b for b in (32, 16, 8) if g % b == 0)
    out = pl.pallas_call(
        functools.partial(_kth_largest_kernel, k=k), name="sparse_kth_largest", grid=(g // rows,),
        in_specs=[pl.BlockSpec((rows, s), lambda i: (i, 0))], out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(keys, jnp.int32))
    return jax.lax.bitcast_convert_type(out[:, 0], jnp.uint32)
