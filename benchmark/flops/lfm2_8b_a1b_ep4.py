"""Analytic operations of one ``lfm2_8b_a1b_ep4`` token, forward and backward,
and the operations and bytes of its three distinctive kernels as functions of
their shapes (for their roofline shares): of the unpadded need, whatever
implements it.

Counted per token: every projection of the operators (a convolution layer's
``hidden -> 3 hidden`` and ``hidden -> hidden``; the attention layer's four);
scores and values over the keys a query sees at the configuration's sequence
length, the mathematics whatever a lowering computes (``(T + 1) / 2`` keys a
query on average); the dense layers' feed-forward; the routers and the routed
experts this chip holds, at what uniform routing sends them (``k * held /
published`` experts a token; the run's own count is the
``moe_expert_tokens_held`` counter); the tied head over the held vocabulary.
The embedding lookup, norms, rotary, softmax, the convolutions' gates and taps
and the loss are elementwise and not counted, and nothing recomputed ever is.
"""

from __future__ import annotations

from benchmark.flops import _count

_BF16, _F32 = 2, 4


def built_layer_types(config) -> list:
    first = config["deployment"]["first_layer"]
    return config["layer_types"][first:first + config["num_hidden_layers"]]


def visible_pairs(seq_len: int) -> int:
    """Query-key pairs of one sequence under the causal mask."""
    return seq_len * (seq_len + 1) // 2


def short_conv_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of one layer's gates and taps (no projections)
    for ``tokens`` tokens, forward and backward: an HBM bound. A token's
    ``hidden_size`` channels: forward ``b * u``, ``conv_L_cache`` multiply-adds
    and ``c * z``, reading the three streams and writing one (bfloat16);
    backward reads the three streams and the result's gradient and writes the
    three streams' gradients, at about twice the forward's arithmetic and the
    taps' own gradient."""
    c, taps = config["hidden_size"], config["conv_L_cache"]
    forward = 2 + 2 * taps
    ops = tokens * c * (forward + 2 * forward + 2 * taps)
    return float(ops), float(tokens * c * _BF16 * ((3 + 1) + (3 + 1 + 3)))


def attention_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of causal softmax attention (scores and
    values, no projections) of one layer for ``tokens`` tokens in sequences
    of the configuration's length, forward and backward, over heads as wide
    as published (a lowering that pads them pays for it in its time); q, k,
    v, the output and their gradients in bfloat16."""
    hq, hkv, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    seq_len = config["tokens"]["seq_len"]
    macs = tokens / seq_len * 2 * hq * d * visible_pairs(seq_len)  # two products a pair
    rows = tokens * d * (2 * hq + 2 * hkv)
    return float(3 * 2 * macs), float(3 * rows * _BF16)


def expert_matmul_cost(config, assignments: float) -> tuple:
    """``(operations, bytes)`` of the grouped expert products of one layer
    for ``assignments`` token-expert pairs at held experts, forward and
    backward: gate, up and down are ``E F`` multiply-accumulates each a pair.
    Bytes: each held expert's three matrices in bfloat16 read forward and
    twice backward, their float32 gradients written once, and a pair's input
    and output rows (bfloat16) forward and backward."""
    e, f, held = config["hidden_size"], config["moe_intermediate_size"], config["num_experts"]
    ops = 3 * 2 * (3 * e * f) * assignments
    weights = held * 3 * e * f
    return float(ops), float(weights * (3 * _BF16 + _F32) + assignments * 4 * e * _BF16)


def products(config):
    """``(macs, needs_input_grad)`` per product of one token, in model order."""
    e, t = config["hidden_size"], config["tokens"]["seq_len"]
    hq, hkv, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    published = config["deployment"]["experts_published"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / published
    layers = []
    for i, layer_type in enumerate(built_layer_types(config)):
        if layer_type == "conv":
            layers += [(e * 3 * e, True), (e * e, True)]
        else:
            layers += [
                (e * hq * d, True), (e * hkv * d, True), (e * hkv * d, True),
                (2 * hq * d * visible_pairs(t) // t, True),  # scores and values over the keys a query sees
                (hq * d * e, True),
            ]
        if i < config["num_dense_layers"]:
            layers.append((3 * e * config["intermediate_size"], True))
        else:
            layers += [
                (e * published, True),  # router
                (int(routed * 3 * e * config["moe_intermediate_size"]), True),  # the held share of the routed
            ]
    return layers + [(e * config["vocab_size"], True)]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
