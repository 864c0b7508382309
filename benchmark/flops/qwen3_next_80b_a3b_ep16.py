"""Analytic operations of one ``qwen3_next_80b_a3b_ep16`` token, forward and
backward, and the operations and bytes of its three distinctive kernels as
functions of their shapes (for their roofline shares).

Counted per token: every projection; the Gated DeltaNet scan in its chunked
form (below); causal attention at the configuration's sequence length (a
query sees ``(T + 1) / 2`` keys on average); the router, the shared expert
and the routed experts this chip holds, at what uniform routing sends them
(``k * held / published`` experts a token; the run's own count is the
``moe_expert_tokens_held`` counter); the head over the held vocabulary. The
embedding lookup, norms, the depthwise convolution, gates and the loss are
elementwise and not counted, and nothing recomputed ever is.
"""

from __future__ import annotations

from benchmark.flops import _count

SCAN_CHUNK = 64  # tokens a chunk in the published kernels and in the program
_BF16, _F32 = 2, 4


def scan_macs_per_token(config, chunk: int = SCAN_CHUNK) -> int:
    """Multiply-accumulates a token of the chunked gated delta rule, all value
    heads of one layer. Per chunk of ``C`` tokens and head, the products over
    the chunk cost ``C^2`` times a head size: ``(beta K) K^T``, ``T (beta K
    e^G)`` and ``Q K^T`` over the key size, ``T (beta V)`` and ``(Q K^T) V'``
    over the value size; the three through the state, ``W S``, ``K^T V'`` and
    ``Q S``, cost ``C Dk Dv`` each. The triangular inverse is not counted."""
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    per_head = chunk * (3 * dk + 2 * dv) + 3 * dk * dv
    return config["linear_num_value_heads"] * per_head


def scan_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` the scan of one DeltaNet layer needs for
    ``tokens`` tokens, forward and backward. Backward: twice the forward's
    products. Bytes: q, k (key heads), v and the output in bfloat16, g and
    beta in float32, read once forward; backward reads them and the output's
    gradient again and writes one gradient for each input."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    ops = 3 * 2 * scan_macs_per_token(config) * tokens
    inputs = _BF16 * (2 * hk * dk + hv * dv) + _F32 * 2 * hv
    output = _BF16 * hv * dv
    return float(ops), float(tokens * ((inputs + output) + (inputs + output) + inputs))


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


def attention_cost(config, batch: int, seq_len: int) -> tuple:
    """``(operations, bytes)`` of causal softmax attention (scores and
    values, no projections) of one layer for ``batch`` sequences, forward
    and backward; q, k, v, the output and their gradients in bfloat16."""
    hq, hkv, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    macs = batch * hq * d * seq_len * (seq_len + 1)  # two products over (T + 1) / 2 keys
    rows = batch * seq_len * d * (2 * hq + 2 * hkv)
    return float(3 * 2 * macs), float(3 * rows * _BF16)


def products(config):
    """``(macs, needs_input_grad)`` per product of one token, in model order."""
    e, t = config["hidden_size"], config["tokens"]["seq_len"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    hq, hkv, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    f, s = config["moe_intermediate_size"], config["shared_expert_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / config["deployment"]["experts_published"]
    experts = [
        (e * config["deployment"]["experts_published"], True),  # router
        (3 * e * s + e, True),  # shared expert and its gate
        (int(routed * 3 * e * f), True),  # the held experts' share of the routed ones
    ]
    deltanet = [
        (e * (2 * hk * dk + 2 * hv * dv), True), (e * 2 * hv, True),
        (scan_macs_per_token(config), True), (hv * dv * e, True),
    ]
    attention = [
        (e * hq * 2 * d, True), (e * hkv * d, True), (e * hkv * d, True),
        (hq * d * (t + 1), True),  # scores and values over (T + 1) / 2 keys
        (hq * d * e, True),
    ]
    layers = []
    for i in range(config["num_hidden_layers"]):
        full = (i + 1) % config["full_attention_interval"] == 0
        layers += (attention if full else deltanet) + experts
    return layers + [(e * config["vocab_size"], True)]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
