"""A token model as the system under test: a registry transformer through
``DistributedDataParallel`` with ``nn.CrossEntropyLoss``, stepped on
``(B, T)`` tokens, targets and weights. The token is the unit the step
counts. Batches are seeded arithmetic sequences (the next token is the
last plus one, modulo the vocabulary), which a step or two of Adam learns.
"""

from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np

from tpuddp import nn, optim
from tpuddp.models import load_model
from tpuddp.parallel.ddp import DistributedDataParallel


def build_ddp(cell, mesh, *, check: bool = False):
    del check  # no dropout, no augment: the check steps the same program
    cfg, opt = cell.config, cell.config["optimizer"]
    model = load_model(
        cfg["registry_name"], cfg["tokens"]["vocab"], max_seq_len=cfg["tokens"]["seq_len"]
    )
    optimizer = optim.Adam(opt["lr"], betas=tuple(opt["betas"]), eps=opt["eps"])
    ddp = DistributedDataParallel(
        model, optimizer, nn.CrossEntropyLoss(), mesh=mesh, mode="shard_map",
        **cell.traffic.get("ddp", {}),
    )
    return model, ddp


def _sample(config):
    return jnp.zeros((1, config["tokens"]["seq_len"]), jnp.int32)


def init_variables(model, config, seed: int):
    return jax.jit(lambda key: model.init(key, _sample(config)))(jax.random.key(seed))


def init_state(model, ddp, config, seed: int, variables=None):
    params, mstate = variables or init_variables(model, config, seed)
    return ddp.init_state(
        jax.random.key(seed), _sample(config), params=params, model_state=mstate
    )


def make_batches(config, seed: int, n_batches: int, batch: int, layout=None):
    """``(tokens, targets)``, each ``(n_batches, batch, T)`` int32."""
    vocab, t = config["tokens"]["vocab"], config["tokens"]["seq_len"]

    def make(key):
        start = jax.random.randint(key, (n_batches, batch, 1), 0, vocab, jnp.int32)
        seq = (start + jnp.arange(t + 1, dtype=jnp.int32)) % vocab
        return seq[..., :-1], seq[..., 1:]

    out = {"out_shardings": (layout(3), layout(3))} if layout else {}
    return jax.jit(make, **out)(jax.random.key(seed))


def unit_weights(config, *leading: int):
    """Weight 1 for every unit the step counts: one a token."""
    return np.ones((*leading, config["tokens"]["seq_len"]), np.float32)


def shrunk(config):
    return copy.deepcopy(config)  # already a size the CPU runs
