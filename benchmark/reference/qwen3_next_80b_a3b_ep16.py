"""Plain reference for ``qwen3_next_80b_a3b_ep16``: the language model of
Qwen3-Next-80B-A3B-Instruct as one chip of sixteen holds it, forward, loss,
gradients and Adam in straightforward float32 ``jax.numpy`` at ``highest``
matrix precision. Nothing here comes from ``tpuddp``; only the layout of the
parameter tree is shared with the system under test, which hands its seeded
initialisation over.

Every layer: ``h = x + Mixer(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``,
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``; layer ``i`` is gated
softmax attention where ``(i + 1) % full_attention_interval == 0``, else
Gated DeltaNet. The DeltaNet recurrence runs token by token. What is in
blocks is in blocks for memory only and changes no arithmetic: the
recurrence is recomputed 256 tokens at a time in the backward pass, attention
takes 512 queries at a time against all keys (those after a query masked), the loss takes 1024
tokens at a time, and each layer is recomputed in the backward pass.

Departures from the published description, each forced by what the catalog
row gives:
- the columns of ``in_proj_qkvz`` are laid out ``q | k | v | z`` and those of
  ``in_proj_ba`` as ``b | a``, and ``q_proj``'s per head as ``query | gate``;
  the published checkpoint interleaves them per key head. With random weights
  that is a fixed permutation of columns.
- expert share: the router covers all ``num_experts_published`` experts and
  the ``num_experts_per_tok`` largest are renormalised; only experts
  ``first_expert .. first_expert + num_experts - 1`` add to the result.
- the load-balancing loss (Switch Transformers eq. 4, per layer, summed)
  enters the gradient with the configuration's assumed weight; the loss
  reported is the cross-entropy alone.
- no multi-token-prediction head (it is not in the row's ``config``).
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

_SCAN_BLOCK, _QUERY_BLOCK, _LOSS_BLOCK = 256, 512, 1024


def _rms(x, w, eps, centred=True):
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if centred else w)


def _in_blocks(fn, size, length, *arrays):
    """``fn(start, *block)`` over blocks of ``size`` along axis 1, each
    recomputed in the backward pass; results concatenated along axis 1. The
    whole blocks go through one loop (``start`` is then a traced value), what
    is left after them through one more call."""
    fn = jax.checkpoint(fn)
    whole, out = length // size, []
    if whole:
        split = lambda a: jnp.moveaxis(
            a[:, :whole * size].reshape(a.shape[0], whole, size, *a.shape[2:]), 1, 0)
        o = jax.lax.map(lambda xs: fn(*xs), (size * jnp.arange(whole), *(split(a) for a in arrays)))
        out.append(jnp.moveaxis(o, 0, 1).reshape(o.shape[1], whole * size, *o.shape[3:]))
    if whole * size < length:
        out.append(fn(whole * size, *(a[:, whole * size:] for a in arrays)))
    return jnp.concatenate(out, axis=1)


# -- Gated DeltaNet -------------------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token. ``q``, ``k``: ``(B, T, H, Dk)``;
    ``v``: ``(B, T, H, Dv)``; ``g``, ``beta``: ``(B, T, H)``. State ``S`` of
    ``(B, H, Dk, Dv)`` from zero: ``S <- exp(g) S``; ``u = beta (v - S^T k)``;
    ``S <- S + k u^T``; ``o = S^T q``."""

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        u = beta_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    b, t, h, dk = q.shape
    pad = -t % _SCAN_BLOCK  # padded tokens come after every real one
    by_time = lambda a: jnp.moveaxis(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0)
    blocks = lambda a: a.reshape(-1, _SCAN_BLOCK, *a.shape[1:])
    xs = tuple(blocks(by_time(a)) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(t + pad, b, h, -1), 0, 1)[:, :t]


def _causal_conv(x, kernel):
    """Depthwise, causal: ``y[t] = sum_j kernel[j] x[t - (K - 1) + j]``."""
    k, t = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * kernel[j] for j in range(k))


def deltanet_mixer(cfg, p, x):
    b, t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    qkvz, ba = x @ p["in_proj_qkvz"], x @ p["in_proj_ba"]
    qkv, z = qkvz[..., : 2 * hk * dk + hv * dv], qkvz[..., 2 * hk * dk + hv * dv:]
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    qkv = jax.nn.silu(_causal_conv(qkv, p["conv"]))
    q = qkv[..., : hk * dk].reshape(b, t, hk, dk)
    k = qkv[..., hk * dk: 2 * hk * dk].reshape(b, t, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * dk ** -0.5, unit(k)
    q, k = jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2)
    o = delta_rule(q, k, v, g, beta)
    o = _rms(o, p["norm"], cfg["rms_norm_eps"], centred=False) * jax.nn.silu(z.reshape(b, t, hv, dv))
    return o.reshape(b, t, hv * dv) @ p["out_proj"]


# -- gated attention -------------------------------------------------------------

def _rotary(cfg, x):
    d = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    half = d // 2
    inv_freq = 1.0 / (cfg["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, c = x[..., :half], x[..., half:d]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin, x[..., d:]], axis=-1)


def attention_mixer(cfg, p, x):
    b, t, _ = x.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qg = (x @ p["q_proj"]).reshape(b, t, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]).reshape(b, t, hkv, d)
    v = (x @ p["v_proj"]).reshape(b, t, hkv, d)
    q, k = _rotary(cfg, _rms(q, p["q_norm"], eps)), _rotary(cfg, _rms(k, p["k_norm"], eps))
    # each key/value head serves hq / hkv query heads
    k, v = jnp.repeat(k, hq // hkv, axis=2), jnp.repeat(v, hq // hkv, axis=2)

    def queries(start, q_blk):
        scores = jnp.einsum("bqhd,bshd->bhqs", q_blk, k) * d ** -0.5
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(q_blk.shape[1]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    o = _in_blocks(queries, _QUERY_BLOCK, t, q)
    return (o * jax.nn.sigmoid(gate)).reshape(b, t, hq * d) @ p["o_proj"]


# -- experts ---------------------------------------------------------------------

def _swiglu(x, gate_up, down):
    h = x @ gate_up
    f = h.shape[-1] // 2
    return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ down


def routed_part(cfg, p, x, first_expert):
    """What the experts ``first_expert .. + held - 1`` add for tokens ``x`` of
    ``(N, E)``, and the load-balancing loss. Every held expert is computed
    for every token and weighted by that token's renormalised router
    probability for it, zero where it was not among the chosen."""
    k, n_all = cfg["num_experts_per_tok"], p["router"].shape[-1]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    chosen = jnp.sum(top_e[..., None] == jnp.arange(n_all), axis=(0, 1)) / top_e.size
    aux = n_all * jnp.sum(chosen * jnp.mean(probs, axis=0))

    @jax.checkpoint
    def weighted(gate_up, down, weight):
        return weight[:, None] * _swiglu(x, gate_up, down)

    def one(y, expert):
        # a plain sum over experts, each recomputed in the backward pass, so
        # nothing is kept for it but a token's weight for the expert
        e, gate_up, down = expert
        weight = jnp.sum(jnp.where(top_e == first_expert + e, top_w, 0.0), axis=-1)
        return y + weighted(gate_up, down, weight), None

    held = p["experts"]["gate_up"].shape[0]
    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (jnp.arange(held), p["experts"]["gate_up"], p["experts"]["down"])
    )
    return y, aux


def moe(cfg, p, x):
    flat = x.reshape(-1, x.shape[-1])
    routed, aux = routed_part(cfg, p, flat, cfg["deployment"]["first_expert"])
    shared = jax.nn.sigmoid(flat @ p["shared_gate"]) * _swiglu(flat, p["shared"]["gate_up"], p["shared"]["down"])
    return (routed + shared).reshape(x.shape), aux


# -- the model -------------------------------------------------------------------

def hidden_states(cfg, params, tokens):
    """Final normalised hidden states ``(B, T, E)`` and the summed
    load-balancing loss."""
    eps, every = cfg["rms_norm_eps"], cfg["full_attention_interval"]

    @jax.checkpoint
    def deltanet_layer(p, x):
        h = x + deltanet_mixer(cfg, p["mixer"], _rms(x, p["input_norm"], eps))
        y, aux = moe(cfg, p["moe"], _rms(h, p["post_norm"], eps))
        return h + y, aux

    @jax.checkpoint
    def attention_layer(p, x):
        h = x + attention_mixer(cfg, p["mixer"], _rms(x, p["input_norm"], eps))
        y, aux = moe(cfg, p["moe"], _rms(h, p["post_norm"], eps))
        return h + y, aux

    x, aux_total = params["embed"]["weight"][tokens], 0.0
    for i, p in enumerate(params["layers"]):
        x, aux = (attention_layer if (i + 1) % every == 0 else deltanet_layer)(p, x)
        aux_total = aux_total + aux
    return _rms(x, params["final_norm"], eps), aux_total


def loss_and_aux(cfg, params, tokens, targets):
    """Mean cross-entropy over every token, and the load-balancing loss."""
    h, aux = hidden_states(cfg, params, tokens)
    h, y = h.reshape(1, -1, h.shape[-1]), targets.reshape(1, -1)

    def block(start, h_blk, y_blk):
        logp = jax.nn.log_softmax(h_blk @ params["head"]["weight"], axis=-1)
        return -jnp.take_along_axis(logp, y_blk[..., None], axis=-1)[..., 0]

    return jnp.mean(_in_blocks(block, _LOSS_BLOCK, h.shape[1], h, y)), aux


def train_steps(cfg, init_params, init_mstate, batches):
    """Per step the cross-entropy before the update and the global norm of
    the parameter change. Textbook Adam (epsilon outside the root of the
    bias-corrected second moment), leaf by leaf."""
    del init_mstate  # parameters only
    # The programs the window ran stay loaded and the runtime keeps their
    # scratch reserved (7.6 GB here): beside it the float32 state does not
    # fit. Nothing runs them again after the check, so let them go (they sit
    # in reference cycles: collect).
    jax.clear_caches()
    gc.collect()
    opt = cfg["optimizer"]
    (b1, b2), lr, eps = opt["betas"], opt["lr"], opt["eps"]
    aux_weight = cfg["aux_loss_weight"]

    def objective(p, x, y):
        loss, aux = loss_and_aux(cfg, p, x, y)
        return loss + aux_weight * aux, loss

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        m, v = b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g
        step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - step, m, v, jnp.sum(step * step)

    grad = jax.jit(jax.grad(objective, has_aux=True))
    leaves, tree = jax.tree_util.tree_flatten(init_params)
    leaves = [jnp.asarray(a, jnp.float32) for a in leaves]
    # the moments wait on the host between steps: the device then holds the
    # parameters, one set of gradients and one leaf's moments
    m, v = [np.zeros(a.shape, np.float32) for a in leaves], [np.zeros(a.shape, np.float32) for a in leaves]
    losses, norms = [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            grads, loss = grad(jax.tree_util.tree_unflatten(tree, leaves), jnp.asarray(x), jnp.asarray(y))
            grads, moved = jax.tree_util.tree_leaves(grads), 0.0
            for i in range(len(leaves)):
                leaves[i], m_i, v_i, sq = adam(leaves[i], grads[i], m[i], v[i], jnp.float32(t))
                grads[i] = None
                m[i], v[i] = np.asarray(m_i), np.asarray(v_i)
                moved += float(sq)
            losses.append(float(loss))
            norms.append(moved ** 0.5)
    return losses, norms
