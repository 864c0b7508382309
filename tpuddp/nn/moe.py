"""A mixture-of-experts layer that is told which experts it holds.

Under expert parallelism every chip routes its tokens over all ``E`` experts
and computes the part of the result that its own experts give; an exchange
(not in this repo yet) would bring in the rest. :func:`expert_share_moe` is
that chip's part: the router over all ``E``, the ``k`` largest renormalised,
the held experts ``[first, first + H)`` through a grouped matrix product
(``jax.lax.ragged_dot``) over the assignments that chose them, and the shared
expert once. What absent experts would have added is left out.

No assignment is ever dropped and every shape is static. The ``N k``
assignments are sorted so that the held ones come first, grouped by expert;
rows go through the experts ``rows`` at a time for as many rounds as the
routing needs: one in the common case, ``N k / rows`` if every token chose
only held experts. The round count is a run-time value, so the loop is a
``while`` and the layer brings its own backward pass
(:func:`_routed_experts`), which walks the same rounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpuddp.nn.sequence import matmul, round_to, swiglu
from tpuddp.observability import profiling as _prof

COUNTERS = (  # additive: summed over layers here and over steps by whoever reads them
    "moe_expert_tokens_max",  # largest count of tokens at one held expert, summed over layers
    "moe_expert_tokens_held",  # assignments to held experts, summed over layers
    "moe_absent_assignments",  # assignments to experts this chip does not hold
    "moe_dropped_assignments",  # held assignments that went through no expert: always 0
)


def _round_rows(n_tokens: int, top_k: int, held: int, n_experts: int) -> int:
    """Rows a round: 1.6 times what uniform routing sends to the held
    experts, a multiple of 128, at most every assignment."""
    expected = n_tokens * top_k * held / n_experts
    return int(min(n_tokens * top_k, max(128, -(-int(1.6 * expected) // 128) * 128)))


def _one_round(x_rows, weights, gate_up, down, sizes, compute_dtype):
    """``rows`` assignments through their experts: ``sizes`` rows for each
    expert in turn, the rest nothing."""
    with _prof.scope("experts"):
        x_rows = round_to(x_rows, compute_dtype)
        h = jax.lax.ragged_dot(x_rows, gate_up, sizes, preferred_element_type=jnp.float32)
        f = h.shape[-1] // 2
        act = round_to(jax.nn.silu(h[:, :f]) * h[:, f:], compute_dtype)
        y = jax.lax.ragged_dot(act, down, sizes, preferred_element_type=jnp.float32)
    with _prof.scope("combine"):
        return y * weights[:, None]


def _plan(order_token, counts, total, rows, r):
    """Round ``r``'s rows of the sorted assignments: their tokens, how many
    of them each expert takes, and which are real."""
    start = r * rows
    ends = jnp.cumsum(counts)
    sizes = jnp.clip(jnp.minimum(ends, start + rows) - jnp.maximum(ends - counts, start), 0)
    tokens = jax.lax.dynamic_slice(order_token, (start,), (rows,))
    live = (start + jnp.arange(rows)) < total
    return start, tokens, sizes.astype(jnp.int32), live


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _routed_experts(x, sorted_w, gate_up, down, order_token, counts, rows, compute_dtype):
    return _routed_fwd(x, sorted_w, gate_up, down, order_token, counts, rows, compute_dtype)[0]


def _routed_fwd(x, sorted_w, gate_up, down, order_token, counts, rows, compute_dtype):
    total = jnp.sum(counts)
    gu, dn = round_to(gate_up, compute_dtype), round_to(down, compute_dtype)

    def body(r, carry):
        out, done = carry
        start, tokens, sizes, live = _plan(order_token, counts, total, rows, r)
        with _prof.scope("dispatch"):
            x_rows = x[tokens]
        w = jnp.where(live, jax.lax.dynamic_slice(sorted_w, (start,), (rows,)), 0.0)
        y = _one_round(x_rows, w, gu, dn, sizes, compute_dtype)
        with _prof.scope("combine"):
            return out.at[tokens].add(y), done + jnp.sum(sizes)

    out, done = jax.lax.fori_loop(
        0, -(-total // rows), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros((), counts.dtype)),
    )
    return (out, (total - done).astype(jnp.float32)), (x, sorted_w, gate_up, down, order_token, counts)


def _routed_bwd(rows, compute_dtype, saved, cotangent):
    x, sorted_w, gate_up, down, order_token, counts = saved
    d_out = cotangent[0]
    total = jnp.sum(counts)
    gu, dn = round_to(gate_up, compute_dtype), round_to(down, compute_dtype)

    def body(r, carry):
        d_x, d_w, d_gu, d_dn = carry
        start, tokens, sizes, live = _plan(order_token, counts, total, rows, r)
        with _prof.scope("dispatch"):
            x_rows = x[tokens]
        w = jnp.where(live, jax.lax.dynamic_slice(sorted_w, (start,), (rows,)), 0.0)
        _, pull = jax.vjp(
            lambda xr, wr, a, b: _one_round(xr, wr, a, b, sizes, compute_dtype), x_rows, w, gu, dn
        )
        with _prof.scope("combine"):
            d_y = d_out[tokens]
        d_rows, d_wr, d_a, d_b = pull(d_y)
        with _prof.scope("dispatch"):
            d_x = d_x.at[tokens].add(d_rows.astype(d_x.dtype))
        d_w = jax.lax.dynamic_update_slice(d_w, jnp.where(live, d_wr, 0.0), (start,))
        return d_x, d_w, d_gu + d_a.astype(d_gu.dtype), d_dn + d_b.astype(d_dn.dtype)

    d_x, d_w, d_gu, d_dn = jax.lax.fori_loop(
        0, -(-total // rows), body,
        (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(sorted_w),
         jnp.zeros(gate_up.shape, jnp.float32), jnp.zeros(down.shape, jnp.float32)),
    )
    return (d_x.astype(x.dtype), d_w, d_gu.astype(gate_up.dtype), d_dn.astype(down.dtype), None, None)


_routed_experts.defvjp(_routed_fwd, _routed_bwd)


def route(x, router, *, top_k: int):
    """``(weights, experts, probs)``: the softmax over all experts in
    float32, its ``top_k`` largest renormalised to sum 1, and their ids."""
    logits = jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    return top_w / jnp.sum(top_w, axis=-1, keepdims=True), top_e, probs


def load_balance_loss(probs, experts):
    """``E * sum_e f_e P_e``: ``f_e`` the share of assignments that chose
    expert ``e`` and ``P_e`` its mean router probability (Switch
    Transformers, eq. 4, over all ``E`` router outputs)."""
    n_experts = probs.shape[-1]
    chosen = jnp.sum(experts.reshape(-1, 1) == jnp.arange(n_experts), axis=0, dtype=jnp.float32)
    return n_experts * jnp.sum(chosen / experts.size * jnp.mean(probs, axis=0))


def expert_share_moe(params, x, *, top_k: int, first_expert: int, compute_dtype,
                     round_rows=None):
    """This chip's part of the layer for tokens ``x`` of ``(N, E)``. Returns
    ``(y, aux_loss, counters)``. ``params``: ``router (E, n_experts)``,
    ``experts.gate_up (H, E, 2F)`` and ``experts.down (H, F, E)`` for the
    held experts ``first_expert .. first_expert + H - 1``, ``shared.gate_up``,
    ``shared.down``, ``shared_gate (E, 1)``."""
    n, n_experts = x.shape[0], params["router"].shape[-1]
    held = params["experts"]["gate_up"].shape[0]
    with _prof.scope("router"):
        top_w, top_e, probs = route(x, params["router"], top_k=top_k)
        aux = load_balance_loss(probs, top_e)
    with _prof.scope("dispatch"):
        local = top_e.reshape(-1) - first_expert
        key = jnp.where((local >= 0) & (local < held), local, held).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)  # held assignments first, by expert
        counts = jnp.sum(key[:, None] == jnp.arange(held), axis=0, dtype=jnp.int32)
        order_token = (order // top_k).astype(jnp.int32)
        sorted_w = top_w.reshape(-1)[order]
    rows = round_rows or _round_rows(n, top_k, held, n_experts)
    if (n * top_k) % rows:  # the last round reads a whole block of rows
        fill = rows - (n * top_k) % rows
        order_token = jnp.pad(order_token, (0, fill))
        sorted_w = jnp.pad(sorted_w, (0, fill))
    routed, dropped = _routed_experts(
        x, sorted_w, params["experts"]["gate_up"], params["experts"]["down"],
        order_token, counts, rows, jnp.dtype(compute_dtype),
    )
    with _prof.scope("shared_expert"):
        gate = jax.nn.sigmoid(matmul(x, params["shared_gate"], compute_dtype, jnp.float32))
        shared = swiglu(x, params["shared"]["gate_up"], params["shared"]["down"], compute_dtype)
        y = routed + gate * shared.astype(jnp.float32)
    total = jnp.sum(counts)
    counters = {
        "moe_expert_tokens_max": jnp.max(counts),
        "moe_expert_tokens_held": total,
        "moe_absent_assignments": n * top_k - total,
        "moe_dropped_assignments": dropped,
    }
    counters = {k: jax.lax.stop_gradient(v.astype(jnp.float32)) for k, v in counters.items()}
    return y.astype(x.dtype), aux, counters
