"""Plain reference for ``glm_4_7_flash_ep8``: the language model of
GLM-4.7-Flash as one chip of eight holds it, forward, both heads' losses,
gradients, Adam and the routers' bias update in straightforward float32
``jax.numpy`` at ``highest`` matrix precision. Nothing here comes from
``tpuddp``; only the layout of the parameter tree and of the state is shared
with the system under test, which hands its seeded initialisation over.

Every layer, pre-norm: ``a = x + Attn(RMSNorm(x))``, ``y = a + FF(RMSNorm(a))``,
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``. The first
``first_k_dense_replace`` layers have the dense feed-forward.

- Latent attention (arXiv:2405.04434, section 2.1, decompressed):
  ``c_q = RMSNorm(x W_qa)``; ``[q_nope_h ; q_rope_h] = c_q W_qb`` for each of
  ``num_attention_heads`` heads (``qk_nope_head_dim + qk_rope_head_dim``);
  ``[c_kv ; k_r] = x W_kva`` (``kv_lora_rank + qk_rope_head_dim``);
  ``c_kv <- RMSNorm(c_kv)``; ``[k_nope_h ; v_h] = c_kv W_kvb``
  (``qk_nope_head_dim + v_head_dim`` a head); ``q_h = [q_nope_h ;
  rot(q_rope_h)]``, ``k_h = [k_nope_h ; rot(k_r)]``, the same ``rot(k_r)`` for
  every head; rotate-half rotary, ``inv_freq_m = rope_theta^(-2m/d)`` over the
  rotary part's ``d``; scores ``q_h . k_h / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, softmax over the keys ``j <= i``; ``y = concat_h(o_h)
  W_o``. No bias.
- Dense feed-forward: ``(silu(x W_1) * (x W_3)) W_2`` of ``intermediate_size``.
- Sparse feed-forward: ``s = sigmoid(x W_r)`` over all experts; chosen: the
  ``num_experts_per_tok`` largest of ``s + b``; weights ``s[chosen] /
  (sum s[chosen] + 1e-6)`` times ``routed_scaling_factor``; ``MoE(x) = sum_e
  w_e Expert_e(x) + Shared(x)``, the shared expert ungated. ``b`` is state: no
  gradient. After each training step, per layer, ``b_e += u sign(mean(count)
  - count_e)`` with the step's counts over all experts.
- Embedding, final RMSNorm, an untied head; ``L_main`` is the mean
  cross-entropy of the next token over tokens.
- Multi-token prediction (arXiv:2412.19437, section 2.2, depth 1): ``h'_i =
  W_eh [RMSNorm_h(h_i) ; RMSNorm_e(Emb(x_{i+1}))]`` with ``h_i`` the trunk's
  state before the final norm; ``g = SparseLayer_mtp(h')``; logits
  ``Head(RMSNorm_s(g_i))`` against ``x_{i+2}``, the target of position
  ``i + 1``; ``L_mtp`` is the mean over the positions that have a successor in
  their sequence. The loss differentiated is ``L_main + mtp_loss_weight
  L_mtp``; the loss reported is ``L_main``.

What is in blocks is in blocks for memory only and changes no arithmetic:
attention takes 512 queries at a time against all keys (those a query does not
see masked), the dense feed-forward 4096 tokens at a time, a head's loss 1024,
the held experts are summed one at a time, and each layer is recomputed in the
backward pass.

Departures from the published description and assumptions, each forced by what
the catalog row gives (the configuration's file lists them under ``assumed``):
- the norms inside the low-rank pairs, the order of the parts inside a head,
  the rotate-half pairing, the ``1e-6`` in the renormalisation, the bias rule
  and its rate ``u``, the state that feeds ``RMSNorm_h``, the order of the
  concatenation and ``mtp_loss_weight``: the family's papers and code, not the
  row.
- a sequence's last position has no successor: the module is fed the
  sequence's first token there (the ids rolled by one), and the position has
  weight 0 in ``L_mtp``; it counts as one token in the module's router counts.
- expert share: the router covers all ``experts_published`` experts; only
  experts ``first_expert .. first_expert + n_routed_experts - 1`` add to the
  result, and the shared expert, which every chip computes, adds whole.
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


# -- latent attention --------------------------------------------------------------

def _rotary(x, theta):
    """Rotate-half rotary over the whole last axis of ``x (B, T, H, d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, c = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], axis=-1)


def latent_queries_keys_values(cfg, p, x):
    """``q``, ``k`` ``(B, T, H, nope + rope)`` and ``v (B, T, H, v_head_dim)``
    of inputs ``x (B, T, E)``, every head's key carrying the one rotary key."""
    b, t, _ = x.shape
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = _operand(x)
    c_q = _rms(x @ _operand(p["q_a_proj"]), p["q_a_norm"], eps)
    q = (_operand(c_q) @ _operand(p["q_b_proj"])).reshape(b, t, h, dn + dr)
    latent = x @ _operand(p["kv_a_proj"])
    c_kv, k_r = _rms(latent[..., :rank], p["kv_a_norm"], eps), latent[..., rank:]
    kv = (_operand(c_kv) @ _operand(p["kv_b_proj"])).reshape(b, t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], theta)], axis=-1)
    shared_key = jnp.repeat(_rotary(k_r[:, :, None, :], theta), h, axis=2)
    return q, jnp.concatenate([kv[..., :dn], shared_key], axis=-1), kv[..., dn:]


def latent_mixer(cfg, p, x):
    b, t, _ = x.shape
    q, k, v = latent_queries_keys_values(cfg, p, x)
    k, v = _operand(k), _operand(v)
    scale = q.shape[-1] ** -0.5

    def queries(start, q_blk):
        scores = jnp.einsum("bqhd,bshd->bhqs", _operand(q_blk), k) * scale
        behind = (start + jnp.arange(q_blk.shape[1]))[:, None] - jnp.arange(t)[None, :]  # i - j
        probs = jax.nn.softmax(jnp.where(behind >= 0, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", _operand(probs), v)

    return _operand(_in_blocks(queries, _QUERY_BLOCK, t, q).reshape(b, t, -1)) @ _operand(p["o_proj"])


# -- the feed-forwards -----------------------------------------------------------

def _swiglu(x, gate_up, down):
    h = _operand(x) @ _operand(gate_up)
    f = h.shape[-1] // 2
    return _operand(jax.nn.silu(h[..., :f]) * h[..., f:]) @ _operand(down)


def dense(p, x):
    return _in_blocks(lambda _, rows: _swiglu(rows, p["gate_up"], p["down"]), _MLP_BLOCK, x.shape[1], x)


def choose(cfg, p, bias, x):
    """``(weights, experts)`` of tokens ``x`` of ``(N, E)``: who is chosen by
    score plus bias, weighted by the scores alone, renormalised and scaled."""
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


def shared_part(p, x):
    """The shared expert, which every chip of the deployment computes alike:
    added as it is, no gate."""
    return jax.checkpoint(_swiglu)(x, p["shared"]["gate_up"], p["shared"]["down"])


def moe(cfg, p, bias, x):
    flat = x.reshape(-1, x.shape[-1])
    routed, counts = routed_part(cfg, p, bias, flat, cfg["deployment"]["first_expert"])
    return (routed + shared_part(p, flat)).reshape(x.shape), counts


def balanced(cfg, bias, counts):
    """The bias after a step with these counts: an expert under the mean
    count up by the rate, one over it down."""
    return bias + cfg["expert_bias_update_rate"] * jnp.sign(jnp.mean(counts) - counts)


# -- the model -------------------------------------------------------------------

def _layer(cfg, p, bias, x):
    """One layer, recomputed in the backward pass: ``(y, counts)``, the
    counts ``None`` for a dense feed-forward."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def run(p, bias, x):
        a = x + latent_mixer(cfg, p["mixer"], _rms(x, p["input_norm"], eps))
        if "mlp" in p:
            return a + dense(p["mlp"], _rms(a, p["post_norm"], eps)), None
        y, counts = moe(cfg, p["moe"], bias, _rms(a, p["post_norm"], eps))
        return a + y, counts

    return run(p, bias, x)


def trunk_states(cfg, params, state, tokens):
    """The trunk's state after the last layer, BEFORE the final norm,
    ``(B, T, E)``, and a layer's counts of tokens at each of the router's
    experts (``None`` for a dense layer)."""
    x, counts = params["embed"]["weight"][tokens], []
    for i, p in enumerate(params["layers"]):
        if ("mlp" in p) != (i < cfg["first_k_dense_replace"]):
            raise ValueError(f"layer {i}'s tree and first_k_dense_replace {cfg['first_k_dense_replace']} disagree")
        x, c = _layer(cfg, p, state[i]["expert_bias"] if "moe" in p else None, x)
        counts.append(c)
    return x, counts


def next_token_states(cfg, params, bias, tokens, h):
    """The prediction module: ``(RMSNorm_s(g), counts)`` from the trunk's
    states ``h`` and the ids: position ``i`` joins ``h_i`` with the embedding of
    token ``i + 1`` (a sequence's last position: of its first token; the loss
    gives it weight 0)."""
    p, eps = params["mtp"], cfg["rms_norm_eps"]
    after = params["embed"]["weight"][jnp.roll(tokens, -1, axis=1)]
    joined = jnp.concatenate([_rms(h, p["hidden_norm"], eps), _rms(after, p["embed_norm"], eps)], axis=-1)
    g, counts = _layer(cfg, p["layer"], bias, _operand(joined) @ _operand(p["proj"]))
    return _rms(g, p["head_norm"], eps), counts


def _token_losses(h, head, targets):
    """Cross-entropy a token ``(B, T)`` of states ``h (B, T, E)``."""
    flat, y = h.reshape(1, -1, h.shape[-1]), targets.reshape(1, -1)

    def block(start, h_blk, y_blk):
        logp = jax.nn.log_softmax(_operand(h_blk) @ _operand(head), axis=-1)
        return -jnp.take_along_axis(logp, y_blk[..., None], axis=-1)[..., 0]

    return _in_blocks(block, _LOSS_BLOCK, flat.shape[1], flat, y).reshape(targets.shape)


def losses_and_counts(cfg, params, state, tokens, targets):
    """``(L_main + mtp_loss_weight L_mtp, (L_main, L_mtp, counts))``: the
    first is what is differentiated, ``L_main`` what is reported; ``counts``
    has an entry a layer and the module's last."""
    if cfg["num_nextn_predict_layers"] != 1:
        raise ValueError("one prediction module, at depth 1")
    h, counts = trunk_states(cfg, params, state, tokens)
    head = params["head"]["weight"]
    main = jnp.mean(_token_losses(_rms(h, params["final_norm"], cfg["rms_norm_eps"]), head, targets))
    g, module_counts = next_token_states(cfg, params, state[len(params["layers"])]["expert_bias"], tokens, h)
    # position i's second target is position i + 1's target; the last position has none
    after_next = _token_losses(g, head, jnp.roll(targets, -1, axis=1))[:, :-1]
    mtp = jnp.mean(after_next)
    return main + cfg["mtp_loss_weight"] * mtp, (main, mtp, counts + [module_counts])


def train_steps(cfg, init_params, init_mstate, batches, with_mtp: bool = False):
    """Per step the next token's cross-entropy before the update and the
    global norm of the parameter change (``with_mtp``: the second head's loss
    a step as a third list). Textbook Adam (epsilon outside the root of the
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

    grad = jax.jit(jax.value_and_grad(functools.partial(losses_and_counts, cfg), has_aux=True))
    leaves, tree = jax.tree_util.tree_flatten(init_params)
    leaves = [jnp.asarray(a, jnp.float32) for a in leaves]
    state = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), init_mstate)
    # the moments wait on the host between steps: the device then holds the
    # parameters, one set of gradients and one leaf's moments
    m, v = [np.zeros(a.shape, np.float32) for a in leaves], [np.zeros(a.shape, np.float32) for a in leaves]
    losses, norms, second = [], [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            (_, (loss, mtp, counts)), grads = grad(
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
            second.append(float(mtp))
            norms.append(moved ** 0.5)
    return (losses, norms, second) if with_mtp else (losses, norms)
