"""Plain reference for ``lfm2_8b_a1b_ep4``: the language model of LFM2-8B-A1B
as one chip of four holds it, forward, loss, gradients, Adam and the router's
bias update in straightforward float32 ``jax.numpy`` at ``highest`` matrix
precision. Nothing here comes from ``tpuddp``; only the layout of the
parameter tree and of the state is shared with the system under test, which
hands its seeded initialisation over.

Every layer, pre-norm: ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``,
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``. The layers built are the
published ``layer_types[first_layer : first_layer + num_hidden_layers]``; the
first ``num_dense_layers`` of them have the dense feed-forward.

- ``conv`` operator: ``[B | C | u] = x W_in`` (three streams of
  ``hidden_size``, in that order); ``z_t = sum_j w_j (B * u)_{t - (L - 1) + j}``
  over ``L = conv_L_cache`` taps, depthwise, zeros before the sequence, no
  bias; ``Op = (C * z) W_out``. No activation, no norm.
- ``full_attention`` operator: ``q = x W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads``), no
  bias; ``q`` and ``k`` through an RMSNorm over each head's ``head_dim``;
  rotate-half rotary on the whole head, ``inv_freq_m = rope_theta^(-2m/d)``;
  scores ``q . k / sqrt(head_dim)``, softmax over the keys ``j <= i``; query
  head ``h`` reads key/value head ``h // (heads / key_value_heads)``;
  ``o = concat(heads) W_o``.
- Dense feed-forward: ``(silu(x W_1) * (x W_3)) W_2`` of ``intermediate_size``.
- Sparse feed-forward: ``s = sigmoid(x W_r)`` over all experts; chosen: the
  ``num_experts_per_tok`` largest of ``s + b``; weights ``s[chosen] /
  (sum s[chosen] + 1e-6)`` times ``routed_scaling_factor``; ``MoE(x) = sum_e
  w_e W_down,e (silu(W_gate,e x) * W_up,e x)``. ``b`` is state: no gradient.
  After each training step, per layer, ``b_e += u sign(mean(count) -
  count_e)`` with the step's counts over all experts.
- Embedding, final RMSNorm, the head is the embedding transposed; the loss is
  the mean cross-entropy over tokens. No load-balancing loss.

What is in blocks is in blocks for memory only and changes no arithmetic:
attention takes 512 queries at a time against all keys (those a query does not
see masked), the dense feed-forward 4096 tokens at a time, the loss 1024, the
held experts are summed one at a time, and each layer is recomputed in the
backward pass.

Departures from the published description and assumptions, each forced by what
the catalog row gives (the configuration's file lists them under ``assumed``):
- the split order ``B | C | u``, the per-head RMSNorm on queries and keys, the
  tied head, the ``1e-6`` in the renormalisation, the bias rule and its rate
  ``u``: the family's released code and arXiv:2408.15664, not the row.
- expert share: the router covers all ``experts_published`` experts; only
  experts ``first_expert .. first_expert + num_experts - 1`` add to the result.
- gate and up projections are joined column-wise (gate first): a layout, not
  arithmetic.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK, _MLP_BLOCK, _LOSS_BLOCK = 512, 4096, 1024


def _operand(a):
    """An operand of a matrix product (every product but the router's, which
    no precision below float32 is stated for): as it is. The control of the
    comparison that decides ``correct`` puts a rounding to 8 bits here and
    holds the result against this file's own (PERF.md, section 6)."""
    return a


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def layer_types(cfg):
    """The built layers' published types."""
    first = cfg["deployment"]["first_layer"]
    return cfg["layer_types"][first:first + cfg["num_hidden_layers"]]


# -- the operators ---------------------------------------------------------------

def conv_mixer(cfg, p, x):
    t, taps = x.shape[1], cfg["conv_L_cache"]
    b, c, u = jnp.split(_operand(x) @ _operand(p["in_proj"]), 3, axis=-1)
    padded = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    z = sum(p["conv"][j] * padded[:, j:j + t] for j in range(taps))
    return _operand(c * z) @ _operand(p["out_proj"])


def _rotary(x, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, c = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], axis=-1)


def attention_mixer(cfg, p, x):
    b, t, _ = x.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    x = _operand(x)
    q = (x @ _operand(p["q_proj"])).reshape(b, t, hq, d)
    k = (x @ _operand(p["k_proj"])).reshape(b, t, hkv, d)
    v = (x @ _operand(p["v_proj"])).reshape(b, t, hkv, d)
    q, k = _rotary(_rms(q, p["q_norm"], eps), theta), _rotary(_rms(k, p["k_norm"], eps), theta)
    # each key/value head serves hq / hkv query heads
    k, v = jnp.repeat(_operand(k), hq // hkv, axis=2), jnp.repeat(_operand(v), hq // hkv, axis=2)

    def queries(start, q_blk):
        scores = jnp.einsum("bqhd,bshd->bhqs", _operand(q_blk), k) * d ** -0.5
        behind = (start + jnp.arange(q_blk.shape[1]))[:, None] - jnp.arange(t)[None, :]  # i - j
        probs = jax.nn.softmax(jnp.where(behind >= 0, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", _operand(probs), v)

    return _operand(_in_blocks(queries, _QUERY_BLOCK, t, q).reshape(b, t, hq * d)) @ _operand(p["o_proj"])


# -- the feed-forwards -----------------------------------------------------------

def _swiglu(x, gate_up, down):
    h = _operand(x) @ _operand(gate_up)
    f = h.shape[-1] // 2
    return _operand(jax.nn.silu(h[..., :f]) * h[..., f:]) @ _operand(down)


def dense(p, x):
    return _in_blocks(lambda _, rows: _swiglu(rows, p["gate_up"], p["down"]), _MLP_BLOCK, x.shape[1], x)


def choose(cfg, p, bias, x):
    """``(weights, experts)`` of tokens ``x`` of ``(N, E)``: who is chosen by
    score plus bias, weighted by the scores alone."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, top_e = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), cfg["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_w = top_w / (jnp.sum(top_w, axis=-1, keepdims=True) + 1e-6)
    return top_w * cfg["routed_scaling_factor"], top_e


def routed_part(cfg, p, bias, x, first_expert):
    """What the experts ``first_expert .. + held - 1`` add for tokens ``x`` of
    ``(N, E)``, and how many tokens chose each of all the router's experts.
    Every held expert is computed for every token and weighted by that
    token's weight for it, zero where it was not among the chosen."""
    top_w, top_e = choose(cfg, p, bias, x)
    counts = jnp.sum(top_e[..., None] == jnp.arange(p["router"].shape[-1]), axis=(0, 1))

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
    return y, counts.astype(jnp.float32)


def moe(cfg, p, bias, x):
    flat = x.reshape(-1, x.shape[-1])
    routed, counts = routed_part(cfg, p, bias, flat, cfg["deployment"]["first_expert"])
    return routed.reshape(x.shape), counts


def balanced(cfg, bias, counts):
    """The bias after a step with these counts: an expert under the mean
    count up by the rate, one over it down."""
    return bias + cfg["expert_bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)


# -- the model -------------------------------------------------------------------

def hidden_states(cfg, params, state, tokens):
    """Final normalised hidden states ``(B, T, E)`` and, a layer, the counts
    of tokens at each of the router's experts (``None`` for a dense layer)."""
    eps = cfg["norm_eps"]

    @functools.partial(jax.checkpoint, static_argnums=0)
    def layer(layer_type, p, bias, x):
        op = conv_mixer if layer_type == "conv" else attention_mixer
        h = x + op(cfg, p["mixer"], _rms(x, p["input_norm"], eps))
        if "mlp" in p:
            return h + dense(p["mlp"], _rms(h, p["post_norm"], eps)), None
        y, counts = moe(cfg, p["moe"], bias, _rms(h, p["post_norm"], eps))
        return h + y, counts

    x, counts = params["embed"]["weight"][tokens], []
    for i, (layer_type, p) in enumerate(zip(layer_types(cfg), params["layers"])):
        if ("mlp" in p) != (i < cfg["num_dense_layers"]):
            raise ValueError(f"layer {i}'s tree and num_dense_layers {cfg['num_dense_layers']} disagree")
        x, c = layer(layer_type, p, state[i]["expert_bias"] if "moe" in p else None, x)
        counts.append(c)
    return _rms(x, params["final_norm"], eps), counts


def loss_and_counts(cfg, params, state, tokens, targets):
    """Mean cross-entropy over every token, and the routers' counts."""
    h, counts = hidden_states(cfg, params, state, tokens)
    h, y = h.reshape(1, -1, h.shape[-1]), targets.reshape(1, -1)
    head = params["embed"]["weight"].T  # tied

    def block(start, h_blk, y_blk):
        logp = jax.nn.log_softmax(_operand(h_blk) @ _operand(head), axis=-1)
        return -jnp.take_along_axis(logp, y_blk[..., None], axis=-1)[..., 0]

    return jnp.mean(_in_blocks(block, _LOSS_BLOCK, h.shape[1], h, y)), counts


def train_steps(cfg, init_params, init_mstate, batches):
    """Per step the cross-entropy before the update and the global norm of
    the parameter change. Textbook Adam (epsilon outside the root of the
    bias-corrected second moment), leaf by leaf; the biases move by their own
    rule after each step."""
    # The programs the window ran stay loaded and the runtime keeps their
    # scratch reserved: beside it the float32 state does not fit. Nothing runs
    # them again after the check, so let them go (they sit in reference
    # cycles: collect).
    jax.clear_caches()
    gc.collect()
    opt = cfg["optimizer"]
    (b1, b2), lr, eps = opt["betas"], opt["lr"], opt["eps"]

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def adam(p, g, m, v, t):
        m, v = b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g
        step = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - step, m, v, jnp.sum(step * step)

    grad = jax.jit(jax.value_and_grad(functools.partial(loss_and_counts, cfg), has_aux=True))
    leaves, tree = jax.tree_util.tree_flatten(init_params)
    leaves = [jnp.asarray(a, jnp.float32) for a in leaves]
    state = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), init_mstate)
    # the moments wait on the host between steps: the device then holds the
    # parameters, one set of gradients and one leaf's moments
    m, v = [np.zeros(a.shape, np.float32) for a in leaves], [np.zeros(a.shape, np.float32) for a in leaves]
    losses, norms = [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            (loss, counts), grads = grad(
                jax.tree_util.tree_unflatten(tree, leaves), state, jnp.asarray(x), jnp.asarray(y)
            )
            grads, moved = jax.tree_util.tree_leaves(grads), 0.0
            for i in range(len(leaves)):
                leaves[i], m_i, v_i, sq = adam(leaves[i], grads[i], m[i], v[i], jnp.float32(t))
                grads[i] = None
                m[i], v[i] = np.asarray(m_i), np.asarray(v_i)
                moved += float(sq)
            state = tuple(
                s if c is None else {"expert_bias": balanced(cfg, s["expert_bias"], c)}
                for s, c in zip(state, counts)
            )
            losses.append(float(loss))
            norms.append(moved ** 0.5)
    return losses, norms
