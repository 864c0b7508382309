"""Analytic operations of one ``keye_vl_2_0_30b_a3b_ep8`` token, forward and
backward, and the operations and bytes of its distinctive kernels as functions
of their shapes (for their roofline shares): of the unpadded need, the
mathematics, whatever a lowering happens to multiply.

Counted per token, a layer: attention's four projections (``hidden -> heads x
head_dim`` for queries, ``-> key_value_heads x head_dim`` for keys and for
values, and back); the indexer's three (``hidden -> indexer heads x indexer
head_dim``, ``-> indexer head_dim``, ``-> indexer heads``); the index scores
over the keys a query sees at the configuration's sequence length (``(T + 1) /
2`` keys a query on average, ``indexer heads x indexer head_dim`` wide);
attention's scores and values over the keys a query SELECTED (``min(t + 1,
topk)`` of them: :func:`selected_pairs`), not over the causal triangle: a
lowering that scores whole blocks under the mask multiplies more, and that
shows as a low share, never as one over 100%; the router and the routed
experts this chip holds at what uniform routing sends them (``k * held /
published`` experts a token; the run's own count is the
``moe_expert_tokens_held`` counter). Once: the head over the held vocabulary.
The indexer's objective reads the heads' mean attention probability, which is
attention's own and no further product. The embedding lookup, norms, rotary,
the ReLU, the selection (no product at all, whatever it costs), softmax and
the losses are not counted, and nothing recomputed ever is.
"""

from __future__ import annotations

from benchmark.flops import _count

_BF16, _F32 = 2, 4


def visible_pairs(seq_len: int) -> int:
    """Query-key pairs of one sequence under the causal mask: what the index
    scores cover."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, top_k: int) -> int:
    """Query-key pairs of one sequence under the selection: every earlier key
    while a query has ``top_k`` or fewer, ``top_k`` after."""
    full = min(seq_len, top_k)
    return full * (full + 1) // 2 + (seq_len - full) * top_k


def _attention_projection_macs(config) -> int:
    e, d = config["hidden_size"], config["head_dim"]
    return 2 * e * config["num_attention_heads"] * d + 2 * e * config["num_key_value_heads"] * d


def _indexer_projection_macs(config) -> int:
    sa, e = config["sa_config"], config["hidden_size"]
    return e * (sa["indexer_num_heads"] * sa["indexer_head_dim"] + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def sparse_attention_cost(config, tokens: int, pairs: float) -> tuple:
    """``(operations, bytes)`` of attention over the selected keys (scores
    and values, no projections) for ``pairs`` selected (query, key) pairs,
    summed over whatever layers they were counted in, forward and backward:
    a pair costs a head ``4 head_dim`` operations forward (its score, its
    value) and twice that backward. Bytes for ``tokens`` tokens a layer: q, k,
    v, the output and their gradients once, in bfloat16."""
    hq, hkv, d = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    rows = tokens * (2 * hq + 2 * hkv) * d
    return float(3 * 4 * d * hq * pairs), float(3 * rows * _BF16)


def index_scores_cost(config, tokens: int) -> tuple:
    """``(operations, bytes)`` of one layer's index scores ``sum_j w relu(qI
    . kI)`` over the causal triangle for ``tokens`` tokens in sequences of the
    configuration's length, forward and backward (the product and its two
    gradients). Bytes: the indexer's queries and keys (bfloat16), weights
    (float32) and their gradients once; no score is counted as written,
    because none has to be."""
    sa, seq_len = config["sa_config"], config["tokens"]["seq_len"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    macs = tokens / seq_len * hi * di * visible_pairs(seq_len)
    rows = tokens * ((hi * di + di) * _BF16 + hi * _F32)
    return float(3 * 2 * macs), float(2 * rows)


def _layer_products(config) -> list:
    e, t, sa = config["hidden_size"], config["tokens"]["seq_len"], config["sa_config"]
    hq, d, f = config["num_attention_heads"], config["head_dim"], config["moe_intermediate_size"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / config["deployment"]["experts_published"]
    return [
        (_attention_projection_macs(config), True),
        (_indexer_projection_macs(config), False),  # behind a stop-gradient: no gradient goes on to the input
        (sa["indexer_num_heads"] * sa["indexer_head_dim"] * visible_pairs(t) // t, True),  # index scores
        (2 * hq * d * selected_pairs(t, sa["topk"]) // t, True),  # scores and values over the selection
        (e * config["deployment"]["experts_published"], True),  # router
        (int(routed * 3 * e * f), True),  # the held share of the routed
    ]


def products(config):
    """``(macs, needs_input_grad)`` per product of one token, in model order."""
    layers = []
    for _ in range(config["num_hidden_layers"]):
        layers += _layer_products(config)
    return layers + [(config["hidden_size"] * config["vocab_size"], True)]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
