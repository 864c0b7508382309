"""The token mixture-of-experts language model as the system under test: a
registry model of the hybrid linear-attention family built at the widths the
configuration's file states, Adam, ``nn.CrossEntropyLoss`` and the
``DistributedDataParallel`` wrap on the cell's mesh, stepped on ``(B, T)``
tokens, next-token targets and per-token weights. The token is the unit the
step counts.

The configuration's file carries the published ``config.json`` keys at its
top level; :func:`model_kwargs` is the one place that maps them onto the
model's arguments, so what the file says is what runs.

The batches are a seeded first-order Markov stream over the held slice of the
vocabulary, made on the device: ids drawn from a Zipf-like unigram (exponent
1.0), each id followed by one of 4 seeded successors with probabilities
0.55/0.25/0.15/0.05 (the chain of ``tpuddp/data/tokens.py``, drawn here with
``jax.random`` so that a later edit of the program cannot move the yardstick);
targets are the next token, every position has weight 1, and there are no
document boundaries. A model learns the unigram within its
first steps and the successors after, so the loss falls from the first
read-back on.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from tpuddp import nn, optim
from tpuddp.models import load_model
from tpuddp.parallel.ddp import DistributedDataParallel

_SUCCESSORS = (0.55, 0.25, 0.15, 0.05)


def model_kwargs(config) -> dict:
    deployment = config["deployment"]
    return dict(
        hidden_size=config["hidden_size"], n_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=config["rope_theta"],
        linear_k_heads=config["linear_num_key_heads"], linear_v_heads=config["linear_num_value_heads"],
        linear_k_dim=config["linear_key_head_dim"], linear_v_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        n_experts=deployment["experts_published"], experts_held=config["num_experts"],
        first_expert=deployment["first_expert"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        rms_eps=config["rms_norm_eps"], aux_loss_weight=config["aux_loss_weight"],
        compute_dtype=config["compute_dtype"], **config["model"]["kwargs"],
    )


def build_ddp(cell, mesh, *, check: bool = False):
    del check  # no dropout, no augment: the check steps the same model
    cfg, opt = cell.config, cell.config["optimizer"]
    model = load_model(cfg["model"]["registry_name"], cfg["vocab_size"], **model_kwargs(cfg))
    if opt["name"] != "adam" or opt["state_dtype"] != "float32":
        raise ValueError("the benchmark builds adam with float32 moments only")
    optimizer = optim.Adam(opt["lr"], betas=tuple(opt["betas"]), eps=opt["eps"])
    ddp = DistributedDataParallel(
        model, optimizer, nn.CrossEntropyLoss(), mesh=mesh, mode="shard_map",
        **cell.traffic.get("ddp", {}),
    )
    return model, ddp


def _sample(config):
    return jnp.zeros((1, config["tokens"]["seq_len"]), jnp.int32)


def init_variables(model, config, seed: int, sharding=None):
    """``(params, model_state)`` made on the device from ``seed`` in one
    jitted call; the same seed gives the same values. ``sharding`` lays them
    out as they come."""
    out = {"out_shardings": sharding} if sharding is not None else {}
    params, mstate = jax.jit(lambda key: model.init(key, _sample(config)), **out)(jax.random.key(seed))
    want = jnp.dtype(config["param_dtype"])
    for leaf in jax.tree_util.tree_leaves(params):
        if leaf.dtype != want:
            raise ValueError(f"parameter of dtype {leaf.dtype}, configuration says {want}")
    return params, mstate


def init_state(model, ddp, config, seed: int, variables=None):
    """The replicated train state on ``ddp``'s mesh. The parameters are born
    replicated on that mesh (or, where the caller made them, are handed over
    to it: donated, so the caller's copy is gone) and the moments after them,
    so placing the state copies nothing: 7.5 GB of state must not peak at 15
    before the first step, nor leave 2.5 GB behind for the reference."""
    replicated = NamedSharding(ddp.mesh, PartitionSpec())
    if variables is None:
        params, mstate = init_variables(model, config, seed, replicated)
    else:
        params, mstate = jax.device_put(variables, replicated, donate=True)
    return ddp.init_state(jax.random.key(seed), _sample(config), params=params, model_state=mstate)


def make_seeded_tokens(key, *, n_batches: int, batch: int, seq_len: int, vocab: int):
    k_successor, k_first, k_choice = jax.random.split(key, 3)
    cdf = jnp.cumsum(1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32))
    cdf = cdf / cdf[-1]

    def unigram(k, shape):
        ids = jnp.searchsorted(cdf, jax.random.uniform(k, shape, jnp.float32))
        return jnp.minimum(ids, vocab - 1).astype(jnp.int32)

    successors = unigram(k_successor, (vocab, len(_SUCCESSORS)))
    choices = jax.random.choice(
        k_choice, len(_SUCCESSORS), (seq_len + 1, n_batches, batch), p=jnp.asarray(_SUCCESSORS)
    )

    def follow(current, choice):
        return successors[current, choice], current

    _, stream = jax.lax.scan(follow, unigram(k_first, (n_batches, batch)), choices)
    stream = jnp.moveaxis(stream, 0, -1)  # (n_batches, batch, seq_len + 1)
    return stream[..., :-1], stream[..., 1:]


def make_batches(config, seed: int, n_batches: int, batch: int, layout=None):
    """``(tokens, targets)``, each ``(n_batches, batch, T)`` int32, a function
    of ``seed`` alone."""
    tokens = config["tokens"]

    def make(key):
        return make_seeded_tokens(
            key, n_batches=n_batches, batch=batch, seq_len=tokens["seq_len"], vocab=config["vocab_size"]
        )

    out = {"out_shardings": (layout(3), layout(3))} if layout else {}
    return jax.jit(make, **out)(jax.random.key(seed))


def unit_weights(config, *leading: int):
    """Weight 1 for every unit the step counts: one a token."""
    return np.ones((*leading, config["tokens"]["seq_len"]), np.float32)


def shrunk(config):
    """The configuration at a size the CPU runs in seconds: the registry's
    tiny preset's sizes under the same keys (the same layer pattern, 2 of 8
    experts held, 2 a token) on 48-token sequences over 96 ids; not a
    multiple of its chunk of 16 on purpose."""
    cfg = copy.deepcopy(config)
    cfg["model"] = {"registry_name": "qwen3_next_tiny", "kwargs": {}}  # the preset brings its block sizes
    cfg.update(
        hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, num_experts=2, num_experts_per_tok=2, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, vocab_size=96,
    )
    cfg["deployment"] = {**cfg["deployment"], "experts_published": 8}
    cfg["tokens"] = {**cfg["tokens"], "seq_len": 44}
    return cfg
