"""Analytic operations of one ``ouro_2_6b_loop4`` token, forward and backward,
and the operations and bytes of its two distinctive parts as functions of
their shapes (for their roofline shares): of the unpadded need, whatever
implements it.

The stack is walked ``total_ut_steps`` times and the head is applied after
every pass, so a token's work is not six operations a parameter: each layer's
products count once a pass (the four projections; scores and values over the
keys a query sees at the configuration's sequence length, ``(T + 1) / 2`` keys
a query on average; the SwiGLU's three), and the untied head once a pass. The
embedding lookup, norms, rotary, softmax, the gate (one dot product a token
and pass, summed elementwise), the exit distribution and the loss are not
counted, and nothing recomputed ever is.
"""

from __future__ import annotations

from benchmark.flops import _count

_BF16, _F32 = 2, 4


def visible_pairs(seq_len: int) -> int:
    """Query-key pairs of one sequence under the causal mask."""
    return seq_len * (seq_len + 1) // 2


def layer_applications(config) -> int:
    """How often a step applies a layer: every built layer once a pass."""
    return config["num_hidden_layers"] * config["total_ut_steps"]


def attention_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of causal softmax attention (scores and
    values, no projections) of ONE application of one layer for ``tokens``
    tokens in sequences of the configuration's length, forward and backward;
    q, k, v, the output and their gradients in bfloat16."""
    heads, kv_heads, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    seq_len = config["tokens"]["seq_len"]
    macs = tokens / seq_len * 2 * heads * d * visible_pairs(seq_len)  # two products a pair
    rows = tokens * d * (2 * heads + 2 * kv_heads)
    return float(3 * 2 * macs), float(3 * rows * _BF16)


def exit_head_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of the head products of all
    ``total_ut_steps`` exits for ``tokens`` tokens, forward and backward
    (``E V`` multiply-accumulates a token and exit forward, twice that
    backward). Bytes: the head in bfloat16 read forward and twice backward,
    its float32 gradient written once, and an exit's state rows (bfloat16)
    read forward, read and their gradient written backward."""
    e, v, exits = config["hidden_size"], config["vocab_size"], config["total_ut_steps"]
    ops = 3 * 2 * exits * tokens * e * v
    return float(ops), float(e * v * (3 * _BF16 + _F32) + exits * tokens * e * 3 * _BF16)


def products(config):
    """``(macs, needs_input_grad)`` per product of one token, in model order,
    a pass after a pass."""
    e, t = config["hidden_size"], config["tokens"]["seq_len"]
    heads, kv_heads, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    layer = [
        (e * heads * d, True), (e * kv_heads * d, True), (e * kv_heads * d, True),
        (2 * heads * d * visible_pairs(t) // t, True),  # scores and values over the keys a query sees
        (heads * d * e, True),
        (3 * e * config["intermediate_size"], True),
    ]
    one_pass = layer * config["num_hidden_layers"] + [(e * config["vocab_size"], True)]  # the exit's head
    return one_pass * config["total_ut_steps"]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
