"""Plain reference for ``ouro_2_6b_loop4``: the looped language model of
Ouro-2.6B, forward, its multi-exit loss, gradients and Adam in straightforward
float32 ``jax.numpy`` at ``highest`` matrix precision. Nothing here comes from
``tpuddp``; only the layout of the parameter tree is shared with the system
under test, which hands its seeded initialisation over. Passes and layers are
an unrolled Python loop: no control flow is shared with the program either.

Tokens ``x``, layers ``l = 1..L``, passes ``t = 1..R`` (``total_ut_steps``),
``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``.

- ``h^(0) = Embed[x]``.
- One layer, a norm before and after each half: ``a = u + N2(Attn(N1(u)))``,
  ``Layer(u) = a + N4(SwiGLU(N3(a)))``.
- ``Attn``: ``q, k, v = z W_q, z W_k, z W_v``, ``num_attention_heads`` heads of
  ``head_dim`` each (no grouping, no bias, no per-head norm); rotate-half
  rotary on the whole head, ``inv_freq_m = rope_theta^(-2m/d)``; scores
  ``q . k / sqrt(head_dim)``, softmax over the keys ``j <= i``; ``o W_o``.
- ``SwiGLU(z) = (silu(z W_g) * (z W_u)) W_d`` of ``intermediate_size``.
- One pass: ``s^(t) = Layer_L(...Layer_1(h^(t-1)))``, ``h^(t) = N_f(s^(t))``:
  the normed state is the pass's exit and what the next pass takes in.
- Exit ``t``: logits ``h^(t) W_head`` (one untied matrix for all passes), a
  token's cross-entropy ``l_t``; gate ``lambda_t = sigmoid(h^(t) . w_g + b_g)``.
- A token's exit distribution: ``p_t = lambda_t prod_{j<t}(1 - lambda_j)`` for
  ``t < R``, ``p_R = prod_{j<R}(1 - lambda_j)`` (``lambda_R`` is not used).
- Objective: mean over tokens of ``sum_t p_t l_t - beta H(p)``, ``H(p) =
  -sum_t p_t log p_t``, ``beta = exit_entropy_weight``. Reported: the mean of
  ``sum_t p_t l_t``.

What is in blocks is in blocks for memory only and changes no arithmetic:
attention takes 256 queries at a time against all keys (those a query does not
see masked), the feed-forward 2048 tokens at a time, an exit's loss 1024, each
application of a layer is recomputed in the backward pass, and the training
steps differentiate the passes one at a time (``loss_and_gradients``; the
whole model in one expression is ``objective``).

Assumptions, each forced by what the catalog row gives (the configuration's
file lists them under ``assumed``): the normed state feeds the next pass; no
per-head norm and no bias; the gate's shape, that ``lambda_R`` is unused, and
``beta``. Gate and up projections are joined column-wise (gate first): a
layout, not arithmetic.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK, _MLP_BLOCK, _LOSS_BLOCK = 256, 2048, 1024


def _operand(a):
    """An operand of a matrix product (every product but the gate's, which no
    precision below float32 is stated for): as it is. The control of the
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


# -- a layer ---------------------------------------------------------------------

def _rotary(x, theta):
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    a, c = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], axis=-1)


def attention(cfg, p, x):
    b, t, _ = x.shape
    heads, d, theta = cfg["num_attention_heads"], cfg["head_dim"], cfg["rope_theta"]
    x = _operand(x)
    q = _rotary((x @ _operand(p["q_proj"])).reshape(b, t, heads, d), theta)
    k = _operand(_rotary((x @ _operand(p["k_proj"])).reshape(b, t, heads, d), theta))
    v = _operand((x @ _operand(p["v_proj"])).reshape(b, t, heads, d))

    def queries(start, q_blk):
        scores = jnp.einsum("bqhd,bshd->bhqs", _operand(q_blk), k) * d ** -0.5
        behind = (start + jnp.arange(q_blk.shape[1]))[:, None] - jnp.arange(t)[None, :]  # i - j
        probs = jax.nn.softmax(jnp.where(behind >= 0, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", _operand(probs), v)

    return _operand(_in_blocks(queries, _QUERY_BLOCK, t, q).reshape(b, t, heads * d)) @ _operand(p["o_proj"])


def swiglu(p, x):
    def rows(_, x_blk):
        h = _operand(x_blk) @ _operand(p["gate_up"])
        f = h.shape[-1] // 2
        return _operand(jax.nn.silu(h[..., :f]) * h[..., f:]) @ _operand(p["down"])

    return _in_blocks(rows, _MLP_BLOCK, x.shape[1], x)


def layer(cfg, p, u):
    eps = cfg["rms_norm_eps"]
    a = u + _rms(attention(cfg, p["mixer"], _rms(u, p["input_norm"], eps)), p["mixer_out_norm"], eps)
    return a + _rms(swiglu(p["mlp"], _rms(a, p["post_norm"], eps)), p["ff_out_norm"], eps)


# -- the model -------------------------------------------------------------------

def one_pass(cfg, params, h):
    """One walk over the layers and the final norm: the pass's exit, which is
    what the next pass takes in."""
    if len(params["layers"]) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(params['layers'])} layers in the tree, num_hidden_layers {cfg['num_hidden_layers']}")
    apply_layer = jax.checkpoint(functools.partial(layer, cfg))
    for p in params["layers"]:
        h = apply_layer(p, h)
    return _rms(h, params["final_norm"], cfg["rms_norm_eps"])


def exit_states(cfg, params, tokens):
    """The normed state after each pass, ``total_ut_steps`` arrays of ``(B, T, E)``."""
    h, exits = params["embed"]["weight"][tokens], []
    for _ in range(cfg["total_ut_steps"]):
        h = one_pass(cfg, params, h)
        exits.append(h)
    return exits


def exit_losses(params, h, targets):
    """A token's cross-entropy at one exit: ``(N,)`` for states ``(B, T, E)``."""
    h, y = h.reshape(1, -1, h.shape[-1]), targets.reshape(1, -1)

    def block(_, h_blk, y_blk):
        logp = jax.nn.log_softmax(_operand(h_blk) @ _operand(params["head"]["weight"]), axis=-1)
        return -jnp.take_along_axis(logp, y_blk[..., None], axis=-1)[..., 0]

    return _in_blocks(block, _LOSS_BLOCK, h.shape[1], h, y)[0]


def exit_probabilities(params, exits):
    """``p`` of ``(R, N)``: exit ``t`` takes ``lambda_t`` of what the exits
    before it left, the last all that is left."""
    gate = params["exit_gate"]
    lam = [
        jax.nn.sigmoid(jnp.sum(h.reshape(-1, h.shape[-1]) * gate["weight"][:, 0], axis=-1) + gate["bias"][0])
        for h in exits[:-1]
    ]
    left, p = jnp.ones_like(lam[0]), []
    for lam_t in lam:
        p.append(lam_t * left)
        left = left * (1.0 - lam_t)
    return jnp.stack(p + [left])


def exits_objective(cfg, params, exits, targets):
    """``(what is minimised, what is reported)`` from the passes' exits: the
    mean over tokens of ``sum_t p_t l_t - beta H(p)``, and of its first term
    alone."""
    losses = jnp.stack([exit_losses(params, h, targets) for h in exits])
    p = exit_probabilities(params, exits)
    expected = jnp.mean(jnp.sum(p * losses, axis=0))
    entropy = -jnp.mean(jnp.sum(p * jnp.log(p), axis=0))
    return expected - cfg["exit_entropy_weight"] * entropy, expected


def objective(cfg, params, tokens, targets):
    """The whole model in one expression: ``(what is minimised, what is reported)``."""
    return exits_objective(cfg, params, exit_states(cfg, params, tokens), targets)


def loss_and_gradients(cfg):
    """``(params, tokens, targets) -> (reported loss, gradients of the
    objective)`` with the chain rule written out over the passes, for memory
    only: one program differentiated whole keeps, beside a layer's input for
    each of its ``total_ut_steps * num_hidden_layers`` applications, every
    use's own gradient of each shared leaf until the sum (19 GB at the cell's
    size; a v5e has 16). Here the exits' states are kept, the objective is
    differentiated with respect to them, the head and the gate, and each pass
    is then differentiated alone, last to first: what it adds to the shared
    leaves' gradients is summed as it comes, and what it hands back is the
    next cotangent. The same numbers as ``jax.grad(objective)``
    (tests/test_looped_lm.py)."""
    run_pass = jax.jit(functools.partial(one_pass, cfg))
    from_exits = jax.jit(jax.value_and_grad(
        lambda head_gate, exits, y: exits_objective(cfg, head_gate, exits, y), argnums=(0, 1), has_aux=True
    ))
    back = jax.jit(lambda params, h, cotangent: jax.vjp(run_pass, params, h)[1](cotangent))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=0)
    embed_rows = jax.jit(lambda table, ids, cotangent: jnp.zeros_like(table).at[ids].add(cotangent))

    def run(params, tokens, targets):
        trunk = {k: v for k, v in params.items() if k in ("layers", "final_norm")}
        states = [params["embed"]["weight"][tokens]]
        for _ in range(cfg["total_ut_steps"]):
            states.append(run_pass(trunk, states[-1]))
        head_gate = {k: params[k] for k in ("head", "exit_gate")}
        (_, reported), (grads, at_exits) = from_exits(head_gate, states[1:], targets)
        cotangent, total = None, None
        for t in reversed(range(cfg["total_ut_steps"])):
            cotangent = at_exits[t] if cotangent is None else cotangent + at_exits[t]
            from_pass, cotangent = back(trunk, states[t], cotangent)
            total = from_pass if total is None else add(total, from_pass)
        grads = {**grads, **total, "embed": {"weight": embed_rows(params["embed"]["weight"], tokens, cotangent)}}
        return reported, grads

    return run


def train_steps(cfg, init_params, init_mstate, batches):
    """Per step the reported loss before the update and the global norm of
    the parameter change. Textbook Adam (epsilon outside the root of the
    bias-corrected second moment), leaf by leaf."""
    del init_mstate  # the model has no state
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

    grad = loss_and_gradients(cfg)
    leaves, tree = jax.tree_util.tree_flatten(init_params)
    leaves = [jnp.asarray(a, jnp.float32) for a in leaves]
    # the moments wait on the host between steps: the device then holds the
    # parameters, one set of gradients and one leaf's moments
    m, v = [np.zeros(a.shape, np.float32) for a in leaves], [np.zeros(a.shape, np.float32) for a in leaves]
    losses, norms = [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            loss, grads = grad(jax.tree_util.tree_unflatten(tree, leaves), jnp.asarray(x), jnp.asarray(y))
            grads, moved = jax.tree_util.tree_leaves(grads), 0.0
            for i in range(len(leaves)):
                leaves[i], m_i, v_i, sq = adam(leaves[i], grads[i], m[i], v[i], jnp.float32(t))
                grads[i] = None
                m[i], v[i] = np.asarray(m_i), np.asarray(v_i)
                moved += float(sq)
            losses.append(float(loss))
            norms.append(moved ** 0.5)
    return losses, norms
