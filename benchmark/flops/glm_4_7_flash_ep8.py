"""Analytic operations of one ``glm_4_7_flash_ep8`` token, forward and
backward, and the operations and bytes of its distinctive kernels as functions
of their shapes (for their roofline shares): of the unpadded need, whatever
implements it.

Counted per token: a latent-attention layer's five projections (``hidden ->
q_lora_rank -> heads x (nope + rope)``, ``hidden -> kv_lora_rank + rope``,
``kv_lora_rank -> heads x (nope + v)``, ``heads x v -> hidden``); scores and
values over the keys a query sees at the configuration's sequence length, the
mathematics whatever a lowering computes (``(T + 1) / 2`` keys a query on
average; the rotary key is one a token but every head's scores read it, so the
products are ``heads x (nope + rope)`` wide); the dense layer's feed-forward;
in a sparse layer the router, the shared expert whole and the routed experts
this chip holds at what uniform routing sends them (``k * held / published``
experts a token; the run's own count is the ``moe_expert_tokens_held``
counter); the head over the held vocabulary, once for each of the two
predictions; the prediction module's projection and its whole layer. The
module runs on every position (a sequence's last, which has no successor, at
weight 0: one token in 16,384). The embedding lookups, norms, rotary, softmax
and the losses are elementwise and not counted, and nothing recomputed ever is.
"""

from __future__ import annotations

from benchmark.flops import _count

_BF16, _F32 = 2, 4


def visible_pairs(seq_len: int) -> int:
    """Query-key pairs of one sequence under the causal mask."""
    return seq_len * (seq_len + 1) // 2


def attention_layers(config) -> int:
    """Latent-attention layers a token passes: the built layers and the
    prediction modules' one each."""
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def sparse_layers(config) -> int:
    """Expert layers a token passes, the prediction modules' among them."""
    return config["num_hidden_layers"] - config["first_k_dense_replace"] + config["num_nextn_predict_layers"]


def _latent_macs(config) -> int:
    """Multiply-accumulates a token of the low-rank pairs: query down and up,
    key/value down (the rotary key beside it) and up."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    return e * rq + rq * h * (dn + dr) + e * (rkv + dr) + rkv * h * (dn + dv)


def attention_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of causal softmax attention (scores and
    values, no projections) of one layer for ``tokens`` tokens in sequences
    of the configuration's length, forward and backward, over ungrouped heads
    whose scores are ``nope + rope`` wide and whose values ``v_head_dim``; q,
    k, v, the output and their gradients in bfloat16, every head's key read
    whole (the shared rotary part a head each, as the kernel is given it)."""
    h, dqk = config["num_attention_heads"], config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv, seq_len = config["v_head_dim"], config["tokens"]["seq_len"]
    macs = tokens / seq_len * h * (dqk + dv) * visible_pairs(seq_len)  # scores, then values, a pair
    rows = tokens * h * (2 * dqk + 2 * dv)
    return float(3 * 2 * macs), float(3 * rows * _BF16)


def latent_projection_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of one layer's low-rank pairs for ``tokens``
    tokens, forward and backward: the four matrices in bfloat16 read forward
    and twice backward, their float32 gradients written once, and a token's
    rows at both ends of each product (bfloat16) forward and backward."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = config["qk_nope_head_dim"], config["qk_rope_head_dim"], config["v_head_dim"]
    rows = (e + rq) + (rq + h * (dn + dr)) + (e + rkv + dr) + (rkv + h * (dn + dv))
    weights = _latent_macs(config)
    return float(3 * 2 * weights * tokens), float(weights * (3 * _BF16 + _F32) + 2 * tokens * rows * _BF16)


def expert_matmul_cost(config, assignments: float) -> tuple:
    """``(operations, bytes)`` of the grouped expert products of one layer
    for ``assignments`` token-expert pairs at held experts, forward and
    backward: gate, up and down are ``E F`` multiply-accumulates each a pair.
    Bytes: each held expert's three matrices in bfloat16 read forward and
    twice backward, their float32 gradients written once, and a pair's input
    and output rows (bfloat16) forward and backward."""
    e, f, held = config["hidden_size"], config["moe_intermediate_size"], config["n_routed_experts"]
    ops = 3 * 2 * (3 * e * f) * assignments
    weights = held * 3 * e * f
    return float(ops), float(weights * (3 * _BF16 + _F32) + assignments * 4 * e * _BF16)


def _layer_products(config, dense: bool) -> list:
    e, t, h = config["hidden_size"], config["tokens"]["seq_len"], config["num_attention_heads"]
    dqk, dv = config["qk_nope_head_dim"] + config["qk_rope_head_dim"], config["v_head_dim"]
    f = config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["n_routed_experts"] / config["deployment"]["experts_published"]
    mixer = [
        (_latent_macs(config), True),
        (h * (dqk + dv) * visible_pairs(t) // t, True),  # scores and values over the keys a query sees
        (h * dv * e, True),
    ]
    if dense:
        return mixer + [(3 * e * config["intermediate_size"], True)]
    return mixer + [
        (e * config["deployment"]["experts_published"], True),  # router
        (config["n_shared_experts"] * 3 * e * f, True),  # the shared expert, whole
        (int(routed * 3 * e * f), True),  # the held share of the routed
    ]


def products(config):
    """``(macs, needs_input_grad)`` per product of one token, in model order."""
    e = config["hidden_size"]
    layers = []
    for i in range(config["num_hidden_layers"]):
        layers += _layer_products(config, dense=i < config["first_k_dense_replace"])
    head = (e * config["vocab_size"], True)
    for _ in range(config["num_nextn_predict_layers"]):
        layers += [(2 * e * e, True)] + _layer_products(config, dense=False) + [head]
    return layers + [head]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
