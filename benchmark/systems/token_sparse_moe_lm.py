"""The token language model of learned sparse attention as the system under
test: a registry model of the hybrid family's trunk built at the widths the
configuration's file states (every layer grouped-query attention with a
per-head norm, in which an indexer of ``sa_config``'s heads over one key head
scores every earlier key and a query attends only its ``topk`` best, the
indexer trained by an objective of its own inside the layer; every
feed-forward routed experts chosen by a renormalised softmax, none shared; an
untied head), Adam, ``nn.CrossEntropyLoss`` (which the model's deferred logits
bind to the language model's loss, the routers' and the indexers' losses in
the gradient alone) and the ``DistributedDataParallel`` wrap on the cell's
mesh, stepped on ``(B, T)`` tokens, next-token targets and per-token weights.
The token is the unit the step counts.

The configuration's file carries the published ``config.json`` keys at its
top level; :func:`model_kwargs` is the one place that maps them onto the
model's arguments, so what the file says is what runs. Nothing is drawn in a
step and the model has no state, so the comparison steps the timed model. The
seeded Markov stream, the seeded initialisation and the placing of the state
are ``token_moe_lm``'s own, taken from that file of the same checkout.
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
    deployment, depth, sa = config["deployment"], config["num_hidden_layers"], config["sa_config"]
    if not config["norm_topk_prob"] or config["mlp_only_layers"] or config["decoder_sparse_step"] != 1:
        raise ValueError("the model's softmax router renormalises its chosen probabilities, and every layer is sparse")
    if config["rope_scaling"]["rope_type"] != "default" or config["attention_bias"] or sa["indexer_num_kv_heads"] != 1:
        raise ValueError("heads are rotated whole under the plain table, no projection has a bias, the indexer has one key head")
    if config["tie_word_embeddings"] or config["hidden_act"] != "silu" or config["use_sliding_window"]:
        raise ValueError("the model has an untied head, SiLU-gated experts and no window")
    if config["num_local_experts"] != config["num_experts"]:
        raise ValueError("num_local_experts follows num_experts: the experts this chip holds")
    return dict(
        hidden_size=config["hidden_size"], n_layers=depth, layer_types=("SparseAttention",) * depth,
        zero_centred_norms=False,
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], partial_rotary_factor=1.0, rope_theta=config["rope_theta"], qk_norm=True,
        index_heads=sa["indexer_num_heads"], index_head_dim=sa["indexer_head_dim"], index_top_k=sa["topk"],
        indexer_loss_weight=config["indexer_loss_weight"],
        n_experts=deployment["experts_published"], experts_held=config["num_experts"],
        first_expert=deployment["first_expert"], top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"], shared_width=0,
        aux_loss_weight=config["aux_loss_weight"],
        tied_head=False, rms_eps=config["rms_norm_eps"], embed_std=config["embedding_init_std"],
        compute_dtype=config["compute_dtype"], **config["model"]["kwargs"],
    )


def build_ddp(cell, mesh, *, check: bool = False):
    del check  # no dropout, nothing drawn in a step, no state: the check steps the same model
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
    tiny preset's sizes under the same keys (two layers, 4 heads over 2 of
    16, an indexer of 2 heads of 16 that chooses 8 keys, 2 of 8 experts
    held, 2 a token) on 64-token sequences over 96 ids."""
    cfg = copy.deepcopy(config)
    cfg["model"] = {"registry_name": "keye_vl_2_0_tiny", "kwargs": {}}  # the preset brings its block sizes
    cfg.update(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=2, num_local_experts=2, num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=96,
        rope_theta=10000,
    )
    cfg["sa_config"] = {**cfg["sa_config"], "indexer_num_heads": 2, "indexer_head_dim": 16, "topk": 8}
    cfg["deployment"] = {**cfg["deployment"], "experts_published": 8}
    cfg["tokens"] = {**cfg["tokens"], "seq_len": 64}
    return cfg
