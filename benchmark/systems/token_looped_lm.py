"""The looped dense language model as the system under test: a registry model
of the hybrid family's trunk built at the widths the configuration's file
states (full attention without a per-head norm, a norm before and after each
half of a layer, a dense SwiGLU in every layer, an untied head), its stack
walked ``total_ut_steps`` times over the same weights with an exit after every
pass and a learned gate over the exits; Adam, ``nn.CrossEntropyLoss`` (which
the model's deferred exits bind to their own loss) and the
``DistributedDataParallel`` wrap on the cell's mesh, stepped on ``(B, T)``
tokens, next-token targets and per-token weights. The token is the unit the
step counts.

The configuration's file carries the published ``config.json`` keys at its
top level; :func:`model_kwargs` is the one place that maps them onto the
model's arguments, so what the file says is what runs. The model has no state
and nothing random in a step, so the comparison with the reference steps the
timed model. The seeded Markov stream, the seeded initialisation and the
placing of the state are ``token_moe_lm``'s own, taken from that file of the
same checkout.
"""

from __future__ import annotations

import copy
import os

from benchmark import cells
from tpuddp import nn, optim
from tpuddp.models import load_model
from tpuddp.parallel.ddp import DistributedDataParallel

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_tokens = cells.load_module("systems", "token_moe_lm", _ROOT)
init_variables, init_state = _tokens.init_variables, _tokens.init_state
make_seeded_tokens, make_batches, unit_weights = (
    _tokens.make_seeded_tokens, _tokens.make_batches, _tokens.unit_weights
)


def model_kwargs(config) -> dict:
    depth = config["num_hidden_layers"]
    if set(config["layer_types"]) != {"full_attention"} or config["use_sliding_window"] or config["rope_scaling"]:
        raise ValueError("the looped model's layers are full attention under the plain rotary table")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("the looped model has an untied head and a SiLU-gated feed-forward")
    return dict(
        hidden_size=config["hidden_size"], n_layers=depth, layer_types=("FullAttention",) * depth,
        zero_centred_norms=False, sandwich_norms=True, qk_norm=False,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], partial_rotary_factor=1.0, rope_theta=config["rope_theta"],
        dense_layers=depth, dense_width=config["intermediate_size"], tied_head=False,
        loop_steps=config["total_ut_steps"], exit_gate=True, exit_entropy_weight=config["exit_entropy_weight"],
        rms_eps=config["rms_norm_eps"], compute_dtype=config["compute_dtype"], **config["model"]["kwargs"],
    )


def build_ddp(cell, mesh, *, check: bool = False):
    del check  # no dropout, no state, nothing drawn in a step: the check steps the same model
    cfg, opt = cell.config, cell.config["optimizer"]
    try:
        model = load_model(cfg["model"]["registry_name"], cfg["vocab_size"], **model_kwargs(cfg))
    except (ValueError, TypeError) as e:  # a program from before the model: no result line, at once
        raise cells.BenchmarkError(f"the program cannot build {cfg['model']['registry_name']!r}: {e}") from e
    if opt["name"] != "adam" or opt["state_dtype"] != "float32":
        raise ValueError("the benchmark builds adam with float32 moments only")
    optimizer = optim.Adam(opt["lr"], betas=tuple(opt["betas"]), eps=opt["eps"])
    ddp = DistributedDataParallel(
        model, optimizer, nn.CrossEntropyLoss(), mesh=mesh, mode="shard_map",
        **cell.traffic.get("ddp", {}),
    )
    return model, ddp


def shrunk(config):
    """The configuration at a size the CPU runs in seconds: the registry's
    tiny preset's sizes under the same keys (three layers walked four times,
    4 heads of 16, a 96-wide feed-forward) on 44-token sequences over 96 ids."""
    cfg = copy.deepcopy(config)
    cfg["model"] = {"registry_name": "ouro_tiny", "kwargs": {}}  # the preset brings its block sizes
    cfg.update(
        hidden_size=64, intermediate_size=96, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, vocab_size=96, rope_theta=10000,
    )
    cfg["tokens"] = {**cfg["tokens"], "seq_len": 44}
    return cfg
