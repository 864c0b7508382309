"""The token language model of latent attention with a second prediction head
as the system under test: a registry model of the hybrid family's trunk built
at the widths the configuration's file states (every layer's queries, keys and
values through low-rank latents, a head's queries and keys a part without
position beside a rotary part whose key all heads share; a dense leading
layer; routed experts chosen by sigmoid score plus a selection bias and scaled
by ``routed_scaling_factor``; a shared expert added ungated; an untied head;
after the stack a module whose head predicts the token after next), Adam,
``nn.CrossEntropyLoss`` (which the model's deferred logits bind to both heads'
losses) and the ``DistributedDataParallel`` wrap on the cell's mesh, stepped on
``(B, T)`` tokens, next-token targets and per-token weights. The token is the
unit the step counts.

The configuration's file carries the published ``config.json`` keys at its
top level; :func:`model_kwargs` is the one place that maps them onto the
model's arguments, so what the file says is what runs. The selection biases
start at 0 in the timed model and in the comparison with the reference alike
(a fresh router's), and nothing is drawn in a step, so the comparison steps
the timed model. The seeded Markov stream, the seeded initialisation and the
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
    deployment, depth = config["deployment"], config["num_hidden_layers"]
    if config["topk_method"] != "noaux_tc" or not config["norm_topk_prob"] or config["aux_loss_weight"]:
        raise ValueError("the model's biased router renormalises its chosen scores and has no auxiliary loss")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("the model's router chooses among all experts: no group limit")
    if config["rope_scaling"] or config["partial_rotary_factor"] != 1 or config["attention_bias"]:
        raise ValueError("the latent mixer's rotary part is rotated whole under the plain table, and no projection has a bias")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu" or config["n_shared_experts"] != 1:
        raise ValueError("the model has an untied head, SiLU-gated feed-forwards and one shared expert")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("a latent-attention layer's heads are not grouped")
    return dict(
        hidden_size=config["hidden_size"], n_layers=depth, layer_types=("LatentAttention",) * depth,
        zero_centred_norms=False,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"], qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        dense_layers=config["first_k_dense_replace"], dense_width=config["intermediate_size"],
        n_experts=deployment["experts_published"], experts_held=config["n_routed_experts"],
        first_expert=deployment["first_expert"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["n_shared_experts"] * config["moe_intermediate_size"], shared_gate=False,
        routed_scale=config["routed_scaling_factor"],
        expert_bias=True, bias_update_rate=config["expert_bias_update_rate"], aux_loss_weight=0.0,
        next_token_modules=config["num_nextn_predict_layers"], next_token_loss_weight=config["mtp_loss_weight"],
        tied_head=False, rms_eps=config["rms_norm_eps"], embed_std=config["embedding_init_std"],
        compute_dtype=config["compute_dtype"], **config["model"]["kwargs"],
    )


def build_ddp(cell, mesh, *, check: bool = False):
    del check  # no dropout, nothing drawn in a step, the biases from 0 in both: the check steps the same model
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
    tiny preset's sizes under the same keys (a dense leading layer and two
    sparse ones, 4 heads of 12 + 4, 2 of 8 experts held, 2 a token, the
    second head's module) on 44-token sequences over 96 ids."""
    cfg = copy.deepcopy(config)
    cfg["model"] = {"registry_name": "glm_4_7_flash_tiny", "kwargs": {}}  # the preset brings its block sizes
    cfg.update(
        hidden_size=64, intermediate_size=96, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
        n_routed_experts=2, num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=96, rope_theta=10000,
    )
    cfg["deployment"] = {**cfg["deployment"], "experts_published": 8}
    cfg["tokens"] = {**cfg["tokens"], "seq_len": 44}
    return cfg
