"""The gated delta rule in chunks, and the short convolution before it.

Per head and token ``t``, with a state ``S`` of ``Dk x Dv`` (key by value)
that starts at zero::

    S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

A chunk of ``C`` tokens is one block step (Yang et al., "Gated Delta
Networks", 2024; the WY form of "Parallelizing Linear Transformers with the
Delta Rule", 2024). With ``G`` the running sum of ``g`` inside the chunk and
``D[i, j] = exp(G_i - G_j)`` for ``i >= j``::

    A = strictly_lower((beta k) k^T * D);  T = (I + A)^-1
    U = T (beta v);  W = T (beta k * exp(G))
    V' = U - W S;  O = (q * exp(G)) S + lower((q k^T) * D) V'
    S <- exp(G_C) S + (k * exp(G_C - G))^T V'

Only the last line and ``V'`` depend on the chunk before, so the scan over
chunks carries ``S`` through two small products a chunk and everything else
is batched over chunks. Both phases have two lowerings, picked together by
:func:`scan_lowering` from what a call can see. On a TPU, at chunk 64 and
widths that are multiples of 128, traced once a device: one Pallas kernel
pair (``nn/deltanet_kernels.py``) for what a chunk computes alone (the first
two lines, the decayed keys, queries and score block), which keeps the ``C x
C`` blocks in VMEM, and a second (``nn/deltanet_carry_kernels.py``) for the
carry, which walks a head's chunks with ``S`` in VMEM, rounds where the loop
below rounds and writes the output rows as the mixer's projection reads them.
Everywhere else the plain XLA below (the carry a ``lax.scan`` that stacks
every chunk's start state, then two batched products that read them), which
the kernels are tested against.
Every exponent is a difference of running sums with
the later one first, so none is positive. Decay sums, ``T`` and the state are
float32; products take ``compute_dtype`` inputs and accumulate in float32.

What stands between a mixer's input projection and this scan
(:func:`short_conv`: a depthwise causal convolution over time, SiLU, the l2
norms of queries and keys) is the family's third shape-selected lowering
(after attention's, ``nn/sequence.py``, and the scan's): :func:`conv_lowering`
picks, under the scan's conditions on backend and tracing, heads of whole
lane registers, at most 8 taps and a length of whole 512-row tiles, a Pallas
kernel pair with its own backward rule (``nn/deltanet_conv_kernels.py``) that
reads the rows once each way, computes in float32 and rounds each output
once; everywhere else (the CPU, the tiny preset's heads of 16, ragged
lengths, ``mode="auto"`` over several devices) the plain XLA the mixer always
ran, which the kernels are tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpuddp.nn.sequence import _LANES, causal_conv1d, l2_normalise, round_to, traced_per_replica

_BASE = 16  # block inverted by forward substitution; larger blocks by halves
_FUSED_CHUNK = 64  # the chunk the kernels' blocks were laid out for
_FUSED_BLOCK = 8  # chunks a grid step of theirs
_EXACT = jax.lax.Precision.HIGHEST


def _forward_substitute(a):
    """``(I + a)^-1`` row by row: row ``i`` is ``e_i - a[i] @ rows`` (exact,
    no powers of ``a``). Rows not yet done still hold the identity's, and
    ``a[i, j]`` is zero for ``j >= i``, so every step is the same product
    over all rows and the loop is rolled."""
    n = a.shape[-1]
    eye = jnp.eye(n, dtype=a.dtype)

    def row(i, inv):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)  # (..., n)
        new = eye[i] - jnp.sum(a_i[..., :, None] * inv, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, axis=-2)

    return jax.lax.fori_loop(1, n, row, jnp.broadcast_to(eye, a.shape))


@jax.custom_vjp
def _invert_unit_lower(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` of ``(..., n, n)``,
    float32. The diagonal blocks of at most 16 go through forward
    substitution together; two halves ``[[P, 0], [R, Q]]`` join as
    ``[[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, which is all matrix products, level
    by level until one block is left. The backward pass is the inverse's own
    derivative, ``-T^T g T^T`` below the diagonal: it keeps ``T`` and nothing
    of the loop."""
    n, lead = a.shape[-1], a.shape[:-2]
    size = n
    while size > _BASE and size % 2 == 0:
        size //= 2
    blocks = n // size
    tiles = a.reshape(*lead, blocks, size, blocks, size)
    inv = _forward_substitute(jnp.stack([tiles[..., i, :, i, :] for i in range(blocks)], axis=-3))
    while blocks > 1:
        blocks //= 2
        tiles = a.reshape(*lead, blocks, 2, size, blocks, 2, size)
        below = jnp.stack([tiles[..., i, 1, :, i, 0, :] for i in range(blocks)], axis=-3)
        p, q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        r = -jnp.matmul(jnp.matmul(q, below, precision=_EXACT), p, precision=_EXACT)
        top = jnp.concatenate([p, jnp.zeros_like(p)], axis=-1)
        inv = jnp.concatenate([top, jnp.concatenate([r, q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _invert_fwd(a):
    inv = _invert_unit_lower(a)
    return inv, inv


def _invert_bwd(inv, g):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.tril(jnp.matmul(jnp.matmul(inv_t, g, precision=_EXACT), inv_t, precision=_EXACT), -1),)


_invert_unit_lower.defvjp(_invert_fwd, _invert_bwd)


def scan_lowering(backend: str, chunk: int, dk: int, dv: int, t: int, *, per_replica: bool) -> str:
    """``"fused"`` or ``"plain"``: where the scan runs, its chunk-local phase
    and its carry alike. The two kernel pairs (``nn/deltanet_kernels.py``,
    ``nn/deltanet_carry_kernels.py``) are written for the TPU's tiles: chunks
    of 64, key and value widths that fill whole 128-lane registers, a length
    that is a whole number of their grid steps (:func:`fused_scan_block`), a
    state narrow enough that one chunk's blocks fit VMEM beside it
    (``deltanet_carry_kernels.tile``: 512 x 512 does, 1,024 x 1,024 does not);
    they are custom calls, which GSPMD cannot partition, so they serve only a
    call that is traced once a device (``per_replica``). Everything else, the
    CPU first, takes the plain path."""
    if backend == "tpu" and per_replica and chunk == _FUSED_CHUNK and dk % _LANES == 0 and dv % _LANES == 0:
        block = fused_scan_block(t, chunk)
        if block is not None and _carry_kernels().tile(chunk, dk, dv, 1, block) is not None:
            return "fused"
    return "plain"


def _carry_kernels():
    from tpuddp.nn import deltanet_carry_kernels  # pulls in Pallas and Mosaic, which nothing else here needs

    return deltanet_carry_kernels


def fused_scan_block(t: int, chunk: int):
    """Chunks a grid step of the kernels, or ``None`` for a length that is no
    whole number of them."""
    return _FUSED_BLOCK if t > 0 and t % (_FUSED_BLOCK * chunk) == 0 else None


def conv_lowering(
    backend: str, channels: int, key_width: int, head_dim: int, taps: int, t: int, *, per_replica: bool
) -> str:
    """``"fused"`` or ``"plain"``: where :func:`short_conv` runs. The kernel
    pair (``nn/deltanet_conv_kernels.py``) is written for the TPU's tiles:
    heads that fill whole 128-lane registers, key and value columns that are
    whole channel tiles of them, taps that reach no further back than one
    float32 register's 8 rows, a length that is a whole number of row tiles;
    like the scan's it is a custom call and serves only a call that is traced
    once a device (``per_replica``). Everything else, the CPU first, takes
    the plain path."""
    if backend == "tpu" and per_replica:
        kernels = _conv_kernels()
        if (
            0 < taps <= kernels.MAX_TAPS and kernels.row_tile(t) is not None
            and kernels.channel_tile(channels, key_width, head_dim) is not None
        ):
            return "fused"
    return "plain"


def _conv_kernels():
    from tpuddp.nn import deltanet_conv_kernels  # pulls in Pallas and Mosaic, which nothing else here needs

    return deltanet_conv_kernels


def short_conv(qkv, taps, *, key_width: int, head_dim: int, q_scale: float, eps: float = 1e-6):
    """What stands between a DeltaNet mixer's input projection and its scan.
    ``qkv``: ``(B, T, C)`` rows, queries | keys | values along ``C``, the
    first two ``key_width`` wide in heads of ``head_dim``; ``taps``:
    ``(K, C)``. A depthwise causal convolution over time
    (:func:`~tpuddp.nn.sequence.causal_conv1d`), SiLU, then for queries and
    keys the l2 norm over each head (:func:`~tpuddp.nn.sequence.l2_normalise`)
    and for queries ``q_scale``. Returns ``q``, ``k`` of ``(B, T, key_width)``
    and ``v`` of the rest, in ``qkv``'s type.

    One contract (rows in ``qkv``'s type, arithmetic in float32) and two
    lowerings, chosen by :func:`conv_lowering` from the backend, the shapes
    and where the call is traced: a fused kernel pair with its own backward
    rule that passes over the rows once each way and rounds each output once,
    or plain XLA, which rounds after the convolution, the activation, the
    norm and the scale, as its backward pass does after each tap."""
    lowering = conv_lowering(
        jax.default_backend(), qkv.shape[-1], key_width, head_dim, taps.shape[0], qkv.shape[1],
        per_replica=traced_per_replica(),
    )
    if lowering == "fused":
        return _conv_kernels().short_conv(qkv, taps, key_width, head_dim, q_scale, eps, False)
    return _plain_short_conv(qkv, taps, key_width, head_dim, q_scale, eps)


def _plain_short_conv(qkv, taps, key_width, head_dim, q_scale, eps):
    qkv = jax.nn.silu(causal_conv1d(qkv, taps))
    heads = lambda a: a.reshape(*a.shape[:-1], -1, head_dim)
    flat = lambda a: a.reshape(*a.shape[:-2], key_width)
    q, k, v = qkv[..., :key_width], qkv[..., key_width: 2 * key_width], qkv[..., 2 * key_width:]
    return flat(l2_normalise(heads(q), eps) * q_scale), flat(l2_normalise(heads(k), eps)), v


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64, compute_dtype=jnp.float32):
    """``q``, ``k``: ``(B, T, Hk, Dk)`` (already normalised and scaled);
    ``v``: ``(B, T, H, Dv)``, each key head serving ``H / Hk`` value heads in
    turn; ``g`` (log decay, <= 0) and ``beta``: ``(B, T, H)`` float32. Returns
    ``o`` of ``(B, T, H, Dv)`` in ``v``'s type. ``T`` need not be a multiple
    of ``chunk``: the tail is padded with tokens that leave the state as it
    is (``beta`` 0, ``g`` 0).

    One contract and two lowerings, chosen by :func:`scan_lowering` from the
    backend, the shapes and where the call is traced: two fused kernel pairs,
    one that keeps a chunk's ``C x C`` blocks in VMEM and one that keeps the
    state there while it walks the chunks, or plain XLA, which holds the
    blocks in HBM, carries the state through a loop of ``T / chunk`` steps and
    reads the stacked states back. Both round the same values at the same
    places."""
    lowering = scan_lowering(
        jax.default_backend(), chunk, q.shape[-1], v.shape[-1], q.shape[1], per_replica=traced_per_replica()
    )
    return _chunked_rule(q, k, v, g, beta, chunk=chunk, compute_dtype=compute_dtype, fused=lowering == "fused")


def _chunked_rule(q, k, v, g, beta, *, chunk, compute_dtype, fused: bool, interpret: bool = False):
    """``interpret`` runs the kernels in Pallas's interpreter: the CPU tests'
    way in."""
    b, t, h, dv = v.shape
    dk = q.shape[-1]
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunked(a):  # (B, T, H, ...) -> (B, H, N, C, ...)
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    g, beta = chunked(g.astype(jnp.float32)), chunked(beta.astype(jnp.float32))
    f32 = jnp.float32
    product = lambda spec, x, y: jnp.einsum(
        spec, round_to(x, compute_dtype), round_to(y, compute_dtype), preferred_element_type=f32
    )

    gsum = jnp.cumsum(g, axis=-1)  # (B, H, N, C)
    g_last = gsum[..., -1]  # (B, H, N)
    if fused:
        from tpuddp.nn import deltanet_kernels  # pulls in Pallas and Mosaic, which nothing else here needs

        block, dtype = fused_scan_block(n * chunk, chunk), jnp.dtype(compute_dtype)
        local = deltanet_kernels.chunk_local(q, k, v, gsum, beta, chunk, block, dtype, interpret)
        o = _carry_kernels().carry(*local, gsum, block, dtype, v.dtype, interpret)  # (B, N C, H Dv)
        return o.reshape(b, n * chunk, h, dv)[:, :t]
    else:
        # each key head serves h / hk value heads
        q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))
        q, k, v = chunked(q), chunked(k), chunked(v)
        i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(i >= j, gsum[..., :, None] - gsum[..., None, :], -jnp.inf))
        k_beta = k.astype(f32) * beta[..., None]
        a = jnp.where(i > j, product("bhnid,bhnjd->bhnij", k_beta, k) * decay, 0.0)
        t_inv = _invert_unit_lower(a)
        u = product("bhnij,bhnjd->bhnid", t_inv, v.astype(f32) * beta[..., None])
        w = product("bhnij,bhnjd->bhnid", t_inv, k_beta * jnp.exp(gsum)[..., None])
        k_tail = k.astype(f32) * jnp.exp(g_last[..., None] - gsum)[..., None]
        q_grown = q.astype(f32) * jnp.exp(gsum)[..., None]
        scores = product("bhnid,bhnjd->bhnij", q, k) * decay

    def step(state, xs):
        w_i, u_i, k_i, decay_i = xs
        v_new = u_i - product("bhcd,bhde->bhce", w_i, state)
        nxt = state * decay_i[..., None, None] + product("bhcd,bhce->bhde", k_i, v_new)
        return nxt, (round_to(state, compute_dtype), round_to(v_new, compute_dtype))

    over_chunks = lambda x: jnp.moveaxis(x, 2, 0)
    _, (starts, v_new) = jax.lax.scan(
        step, jnp.zeros((b, h, dk, dv), f32),
        (over_chunks(w), over_chunks(u), over_chunks(k_tail), over_chunks(jnp.exp(g_last))),
    )
    starts, v_new = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(v_new, 0, 2)
    inter = product("bhncd,bhnde->bhnce", q_grown, starts)
    intra = product("bhnij,bhnje->bhnie", scores, v_new)
    o = jnp.moveaxis(inter + intra, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t].astype(v.dtype)
