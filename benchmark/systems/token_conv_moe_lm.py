"""The token language model of gated short convolutions beside full attention,
with a dense leading layer, a sigmoid router balanced by a selection bias and
a tied head, as the system under test: a registry model of the hybrid family
built at the widths the configuration's file states, Adam,
``nn.CrossEntropyLoss`` and the ``DistributedDataParallel`` wrap on the cell's
mesh, stepped on ``(B, T)`` tokens, next-token targets and per-token weights.
The token is the unit the step counts.

The configuration's file carries the published ``config.json`` keys at its
top level (``layer_types``, of which the deployment's ``first_layer`` and
``num_hidden_layers`` say which are built; ``num_dense_layers``;
``use_expert_bias``); :func:`model_kwargs` is the one place that maps them onto
the model's arguments, so what the file says is what runs. The model of the
comparison with the reference (``check=True``) is the timed one but for its
selection biases, which the seeded initialisation then draws at the scale the
file's ``check`` states (the timed model's start at 0, as a fresh router's
do): the bias's place in the choice shows within three steps only where it
decides the choice. The seeded Markov stream, the seeded initialisation and
the placing of the state are ``token_moe_lm``'s own, taken from that file of
the same checkout.
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

# config.json's names of the layer types -> the model's (its scope names)
_LAYER_TYPES = {"conv": "ShortConv", "full_attention": "FullAttention"}


def model_kwargs(config) -> dict:
    deployment = config["deployment"]
    if not (config["use_expert_bias"] and config["norm_topk_prob"]) or config["routed_scaling_factor"] != 1:
        raise ValueError("the model's biased router renormalises its chosen scores and scales them by 1")
    if config["conv_bias"] or config["aux_loss_weight"]:
        raise ValueError("the model's short convolution has no bias and its biased router no auxiliary loss")
    if config["head_dim"] * config["num_attention_heads"] != config["hidden_size"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads in this family")
    first, depth = deployment["first_layer"], config["num_hidden_layers"]
    return dict(
        hidden_size=config["hidden_size"], n_layers=depth,
        layer_types=tuple(_LAYER_TYPES[t] for t in config["layer_types"][first:first + depth]),
        zero_centred_norms=False,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], partial_rotary_factor=1.0, rope_theta=config["rope_theta"],
        conv_kernel=config["conv_L_cache"],
        dense_layers=config["num_dense_layers"], dense_width=config["intermediate_size"],
        n_experts=deployment["experts_published"], experts_held=config["num_experts"],
        first_expert=deployment["first_expert"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"], shared_width=0,
        expert_bias=True, bias_update_rate=config["expert_bias_update_rate"],
        tied_head=config["tie_word_embeddings"],
        rms_eps=config["norm_eps"], aux_loss_weight=0.0,
        compute_dtype=config["compute_dtype"], **config["model"]["kwargs"],
    )


def build_ddp(cell, mesh, *, check: bool = False):
    cfg, opt = cell.config, cell.config["optimizer"]
    drawn = {"expert_bias_std": cfg["check"]["expert_bias_std"]} if check else {}
    try:
        model = load_model(cfg["model"]["registry_name"], cfg["vocab_size"], **model_kwargs(cfg), **drawn)
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
    tiny preset's sizes under the same keys (the same five layers, 2 of 8
    experts held, 2 a token) on 44-token sequences over 96 ids."""
    cfg = copy.deepcopy(config)
    cfg["model"] = {"registry_name": "lfm2_tiny", "kwargs": {}}  # the preset brings its block sizes
    cfg.update(
        hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=2, num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=96, rope_theta=10000,
    )
    cfg["deployment"] = {**cfg["deployment"], "experts_published": 8}
    cfg["tokens"] = {**cfg["tokens"], "seq_len": 44}
    return cfg
