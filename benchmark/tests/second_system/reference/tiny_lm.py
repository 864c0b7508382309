"""Plain reference for ``tiny_lm``: a pre-LN decoder with learned positions,
joined QKV, exact GELU and a head tied to the embedding, in float32 at
``highest`` precision; mean cross-entropy over tokens, textbook Adam."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import _plain


def _norm(p, x, eps=1e-5):
    mean, var = jnp.mean(x, -1, keepdims=True), jnp.var(x, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def forward(config, params, tokens):
    heads, t = config["widths"]["n_heads"], tokens.shape[1]
    h = params["embed"]["weight"][tokens] + params["pos"]["weight"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for p in params["blocks"]:
        qkv = _norm(p["ln1"], h) @ p["attn"]["wqkv"] + p["attn"]["bqkv"]
        q, k, v = jnp.moveaxis(qkv.reshape(*h.shape[:2], 3, heads, -1), 2, 0)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
        attn = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        mixed = jnp.einsum("bhqk,bkhd->bqhd", attn, v).reshape(h.shape)
        h = h + mixed @ p["attn"]["wo"] + p["attn"]["bo"]
        hidden = jax.nn.gelu(_norm(p["ln2"], h) @ p["mlp"]["w1"] + p["mlp"]["b1"], approximate=False)
        h = h + hidden @ p["mlp"]["w2"] + p["mlp"]["b2"]
    return _norm(params["ln_f"], h) @ params["embed"]["weight"].T


def train_steps(config, params, model_state, batches):
    del model_state  # parameters only
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = v = jax.tree_util.tree_map(jnp.zeros_like, params)

    def loss_of(p, x, y):
        logits = forward(config, p, jnp.asarray(x))
        return _plain.cross_entropy_mean(logits.reshape(-1, logits.shape[-1]), jnp.asarray(y).reshape(-1))

    losses, norms = [], []
    with jax.default_matmul_precision("highest"):
        for t, (x, y) in enumerate(batches, start=1):
            loss, grads = jax.value_and_grad(loss_of)(params, x, y)
            new, m, v = _plain.adam_step(params, grads, m, v, t, config["optimizer"])
            losses.append(float(loss))
            norms.append(float(_plain._global_norm(jax.tree_util.tree_map(jnp.subtract, new, params))))
            params = new
    return losses, norms
