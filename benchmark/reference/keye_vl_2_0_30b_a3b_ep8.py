"""Plain reference for ``keye_vl_2_0_30b_a3b_ep8``: the language model of
Keye-VL-2.0-30B-A3B as one chip of eight holds it, forward, the language
model's loss, the indexers' own objective, both stop-gradients, gradients and
Adam in straightforward float32 ``jax.numpy`` at ``highest`` matrix precision.
Nothing here comes from ``tpuddp``; only the layout of the parameter tree is
shared with the system under test, which hands its seeded initialisation over.

Every layer, pre-norm, every feed-forward sparse: ``a = x + Attn(h)`` with
``h = RMSNorm(x)``, ``y = a + MoE(RMSNorm(a))``, ``RMSNorm(x) = x /
sqrt(mean(x^2) + eps) * w``.

- Attention's projections: ``q = rot(RMSNorm_head(h W_q))``
  (``num_attention_heads`` heads of ``head_dim``), ``k = rot(RMSNorm_head(h
  W_k))``, ``v = h W_v`` (``num_key_value_heads`` heads), no bias; rotate-half
  rotary on the whole head, ``inv_freq_m = rope_theta^(-2m/d)``; query head
  ``j`` reads key/value head ``j // (heads / key_value_heads)``.
- The indexer (``sa_config``), from ``hb = stop_gradient(h)``: ``qI[t, j] =
  rot(hb_t W_qI)_j`` for ``indexer_num_heads`` heads of ``indexer_head_dim``;
  ``kI[s] = rot(LayerNorm(hb_s W_kI))``, one key head; ``w[t, j] = (hb_t
  W_w)_j heads^-1/2 head_dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``. ``S_t``: the ``topk`` keys ``s <= t`` of largest
  ``I[t, s]`` (``lax.top_k`` a row: equal scores to the earlier key), every
  key while ``t < topk``.
- Sparse attention: ``alpha[t, j, .] = softmax_{s in S_t}(q[t, j] . k[s] /
  sqrt(head_dim))``, ``o[t, j] = sum_{s in S_t} alpha[t, j, s] v[s]``,
  ``Attn(h) = concat_j(o[t, j]) W_o``; one ``S_t`` for all heads.
- The indexer's objective: ``p[t, .] = stop_gradient(mean_j alpha[t, j, .])``,
  ``r[t, .] = softmax_{s in S_t}(I[t, s])``, ``L_I = sum_layers mean_t
  sum_{s in S_t} p (log p - log r)``.
- Expert layer: ``P = softmax(x W_r)`` over all experts; the
  ``num_experts_per_tok`` largest renormalised to sum 1; ``MoE(x) = sum_e w_e
  W_down,e (silu(W_gate,e x) * W_up,e x)``. No shared expert. The
  load-balancing loss (Switch Transformers eq. 4, per layer, summed) at
  ``aux_loss_weight``.
- Embedding, final RMSNorm, untied head; ``L_LM`` is the mean cross-entropy
  over tokens. Differentiated: ``L_LM + aux_loss_weight aux +
  indexer_loss_weight L_I``; reported: ``L_LM``. By the two stop-gradients
  ``L_I`` reaches the indexer's leaves alone and ``L_LM`` never reaches them.

What is in blocks is in blocks for memory and time only and changes no
arithmetic: a block of queries has its whole row of index scores, its
``top_k``, its mask and its softmax against every key up to the end of the
block's stretch of the sequence (those a query does not see masked; the keys
after the stretch no query of it sees, and masked they would add exact zeros,
so a quarter of the sequence's blocks each leave them out), the loss takes
1024 tokens at a time, the held experts are summed one at a time, and each
layer is recomputed in the backward pass.

Departures from the published description and assumptions, each forced by what
the catalog row gives (the configuration's file lists them under ``assumed``):
- the RMSNorm on each head's queries and keys, that the indexer reads the
  layer's normed input, rotary on the indexer's whole head and a LayerNorm on
  its key, the two scale factors in ``w``, the objective and its weight: the
  family's papers and code, not the row.
- ``-0`` and ``+0`` are one score (``I`` is a sum of products with ReLUs that
  are often all 0): zeros are written ``+0`` before the ``top_k``.
- expert share: the router covers all ``experts_published`` experts; only
  experts ``first_expert .. first_expert + num_experts - 1`` add to the result.
- gate and up projections of an expert are joined column-wise (gate first): a
  layout, not arithmetic.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK, _LOSS_BLOCK = 128, 1024
_STRETCHES = 4  # of a sequence: a stretch's blocks of queries meet the keys up to its end


def _operand(a):
    """An operand of a matrix product (every product but the router's, which
    no precision below float32 is stated for): as it is. The control of the
    comparison that decides ``correct`` puts a rounding to 8 bits here and
    holds the result against this file's own (PERF.md, section 6)."""
    return a


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * w + b


def _in_blocks(fn, size, length, *arrays):
    """``fn(start, *block)`` over blocks of ``size`` along axis 1, each
    recomputed in the backward pass; a tree of results, each concatenated
    along axis 1. The whole blocks go through one loop (``start`` is then a
    traced value), what is left after them through one more call."""
    fn = jax.checkpoint(fn)
    whole, out = length // size, []
    if whole:
        split = lambda a: jnp.moveaxis(
            a[:, :whole * size].reshape(a.shape[0], whole, size, *a.shape[2:]), 1, 0)
        o = jax.lax.map(lambda xs: fn(*xs), (size * jnp.arange(whole), *(split(a) for a in arrays)))
        out.append(jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], whole * size, *a.shape[3:]), o))
    if whole * size < length:
        out.append(fn(whole * size, *(a[:, whole * size:] for a in arrays)))
    return jax.tree_util.tree_map(lambda *parts: jnp.concatenate(parts, axis=1), *out)


# -- attention under the indexer's selection ----------------------------------------

def _rotary(x, theta):
    """Rotate-half rotary over the whole last axis of ``x (B, T, H, d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, c = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], axis=-1)


def queries_keys_values(cfg, p, h):
    """``q (B, T, Hq, d)``, ``k``, ``v (B, T, Hkv, d)`` of normed inputs ``h``."""
    b, t, _ = h.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = _operand(h)
    q = _rotary(_rms((h @ _operand(p["q_proj"])).reshape(b, t, hq, d), p["q_norm"], eps), theta)
    k = _rotary(_rms((h @ _operand(p["k_proj"])).reshape(b, t, hkv, d), p["k_norm"], eps), theta)
    return q, k, (h @ _operand(p["v_proj"])).reshape(b, t, hkv, d)


def indexer_inputs(cfg, p, h):
    """The indexer's queries ``(B, T, Hi, di)``, keys ``(B, T, di)`` and
    weights ``(B, T, Hi)`` of normed inputs ``h``, which no gradient leaves
    through."""
    sa, (b, t, _) = cfg["sa_config"], h.shape
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one key head")
    h = _operand(jax.lax.stop_gradient(h))
    qi = _rotary((h @ _operand(p["q_proj"])).reshape(b, t, hi, di), cfg["rope_theta"])
    ki = _layer_norm(h @ _operand(p["k_proj"]), p["k_norm"]["weight"], p["k_norm"]["bias"], cfg["rms_norm_eps"])
    ki = _rotary(ki[:, :, None, :], cfg["rope_theta"])[:, :, 0]
    return qi, ki, (h @ _operand(p["w_proj"])) * (hi ** -0.5 * di ** -0.5)


def index_scores(qi, ki, wi):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, ``(B, Q, T)``, zeros
    written ``+0``."""
    products = jnp.einsum("bqjd,bsd->bqjs", _operand(qi), _operand(ki))
    scores = jnp.einsum("bqj,bqjs->bqs", wi, jax.nn.relu(products))
    return jnp.where(scores == 0, 0.0, scores)


def selection(scores, start, top_k: int):
    """``(B, Q, T)`` bool: ``S_t`` of the block's queries, the first at
    position ``start``: the ``top_k`` largest of a row's scores over the keys
    ``s <= t`` (``lax.top_k``: equal scores to the lower index), which are all
    of them while ``t < top_k``."""
    b, q, t = scores.shape
    seen = jnp.arange(t)[None, :] <= (start + jnp.arange(q))[:, None]
    _, chosen = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), min(top_k, t))
    rows = jnp.arange(b)[:, None, None], jnp.arange(q)[None, :, None]
    return jnp.zeros((b, q, t), bool).at[(*rows, chosen)].set(True) & seen


def sparse_mixer(cfg, p, h, select=selection):
    """``(Attn(h) (B, T, E), the rows' sum of KL(p || r), the selected
    pairs)``. ``select``: who builds ``S_t`` from the index scores (the tests
    hand in a sort)."""
    b, t, _ = h.shape
    q, k, v = queries_keys_values(cfg, p, h)
    qi, ki, wi = indexer_inputs(cfg, p["indexer"], h)
    k, v = _operand(k), _operand(v)
    hq, hkv, d = q.shape[2], k.shape[2], q.shape[3]
    top_k = cfg["sa_config"]["topk"]

    def stretch(first, end):
        """The blocks of the queries ``first .. end - 1`` against the keys ``0 .. end - 1``."""
        k_s, v_s, ki_s = k[:, :end], v[:, :end], ki[:, :end]

        def queries(start, q_blk, qi_blk, wi_blk):
            scores_i = index_scores(qi_blk, ki_s, wi_blk)
            chosen = select(jax.lax.stop_gradient(scores_i), first + start, top_k)
            grouped = _operand(q_blk).reshape(b, -1, hkv, hq // hkv, d)
            scores = jnp.einsum("bqhgd,bshd->bhgqs", grouped, k_s) / jnp.sqrt(jnp.float32(d))
            alpha = jax.nn.softmax(jnp.where(chosen[:, None, None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bhgqs,bshd->bqhgd", _operand(alpha), v_s).reshape(b, -1, hq * d)
            target = jax.lax.stop_gradient(jnp.mean(alpha, axis=(1, 2)))
            log_r = jax.nn.log_softmax(jnp.where(chosen, scores_i, -jnp.inf), axis=-1)
            counted = chosen & (target > 0)
            kl = jnp.where(counted, target * (jnp.log(jnp.where(counted, target, 1.0)) - jnp.where(counted, log_r, 0.0)), 0.0)
            return out, jnp.sum(kl, axis=-1), jnp.sum(chosen, axis=-1).astype(jnp.float32)

        return _in_blocks(queries, _QUERY_BLOCK, end - first, q[:, first:end], qi[:, first:end], wi[:, first:end])

    span = -(-t // (_STRETCHES * _QUERY_BLOCK)) * _QUERY_BLOCK  # whole blocks a stretch
    out, kl, pairs = jax.tree_util.tree_map(
        lambda *parts: jnp.concatenate(parts, axis=1), *(stretch(first, min(first + span, t)) for first in range(0, t, span))
    )
    return _operand(out) @ _operand(p["o_proj"]), jnp.sum(kl), jnp.sum(pairs)


# -- the expert layer ------------------------------------------------------------

def _swiglu(x, gate_up, down):
    h = _operand(x) @ _operand(gate_up)
    f = h.shape[-1] // 2
    return _operand(jax.nn.silu(h[..., :f]) * h[..., f:]) @ _operand(down)


def routed_part(cfg, p, x, first_expert):
    """What the experts ``first_expert .. + held - 1`` add for tokens ``x`` of
    ``(N, E)``, and the load-balancing loss. Every held expert is computed
    for every token and weighted by that token's renormalised router
    probability for it, zero where it was not among the chosen."""
    k, n_all = cfg["num_experts_per_tok"], p["router"].shape[-1]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
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
    return routed.reshape(x.shape), aux


# -- the model -------------------------------------------------------------------

def hidden_states(cfg, params, tokens):
    """Final normalised hidden states ``(B, T, E)``, the summed
    load-balancing loss, ``L_I`` (a mean over the rows a layer, summed over
    layers) and the selected pairs."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def layer(p, x):
        mixed, kl, pairs = sparse_mixer(cfg, p["mixer"], _rms(x, p["input_norm"], eps))
        a = x + mixed
        y, aux = moe(cfg, p["moe"], _rms(a, p["post_norm"], eps))
        return a + y, aux, kl / (x.shape[0] * x.shape[1]), pairs

    x, totals = params["embed"]["weight"][tokens], (0.0, 0.0, 0.0)
    for p in params["layers"]:
        x, *added = layer(p, x)
        totals = tuple(a + b for a, b in zip(totals, added))
    return (_rms(x, params["final_norm"], eps), *totals)


def losses(cfg, params, tokens, targets):
    """``(L_LM, the load-balancing loss, L_I, the selected pairs)``."""
    h, aux, index_loss, pairs = hidden_states(cfg, params, tokens)
    h, y = h.reshape(1, -1, h.shape[-1]), targets.reshape(1, -1)

    def block(start, h_blk, y_blk):
        logp = jax.nn.log_softmax(_operand(h_blk) @ _operand(params["head"]["weight"]), axis=-1)
        return -jnp.take_along_axis(logp, y_blk[..., None], axis=-1)[..., 0]

    return jnp.mean(_in_blocks(block, _LOSS_BLOCK, h.shape[1], h, y)), aux, index_loss, pairs


def objective(cfg, params, tokens, targets):
    """``(what is differentiated, (L_LM, L_I, pairs))``."""
    loss, aux, index_loss, pairs = losses(cfg, params, tokens, targets)
    return loss + cfg["aux_loss_weight"] * aux + cfg["indexer_loss_weight"] * index_loss, (loss, index_loss, pairs)


def train_steps(cfg, init_params, init_mstate, batches, with_index: bool = False):
    """Per step the language model's cross-entropy before the update and the
    global norm of the parameter change (``with_index``: the indexers'
    objective a step as a third list). Textbook Adam (epsilon outside the root
    of the bias-corrected second moment), leaf by leaf."""
    del init_mstate  # parameters only
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

    grad = jax.jit(jax.grad(functools.partial(objective, cfg), has_aux=True))
    leaves, tree = jax.tree_util.tree_flatten(init_params)
    leaves = [jnp.asarray(a, jnp.float32) for a in leaves]
    # the moments wait on the host between steps: the device then holds the
    # parameters, one set of gradients and one leaf's moments
    m, v = [np.zeros(a.shape, np.float32) for a in leaves], [np.zeros(a.shape, np.float32) for a in leaves]
    loss_by_step, norms, index_by_step = [], [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            grads, (loss, index_loss, _) = grad(jax.tree_util.tree_unflatten(tree, leaves), jnp.asarray(x), jnp.asarray(y))
            grads, moved = jax.tree_util.tree_leaves(grads), 0.0
            for i in range(len(leaves)):
                leaves[i], m_i, v_i, sq = adam(leaves[i], grads[i], m[i], v[i], jnp.float32(t))
                grads[i] = None
                m[i], v[i] = np.asarray(m_i), np.asarray(v_i)
                moved += float(sq)
            loss_by_step.append(float(loss))
            index_by_step.append(float(index_loss))
            norms.append(moved ** 0.5)
    return (loss_by_step, norms, index_by_step) if with_index else (loss_by_step, norms)
