"""Analytic forward+backward operations of one ``tiny_lm`` token: the matrix
products of each block (joined QKV, output, two of the MLP), the attention
scores and values over the sequence, and the tied head; the embedding lookup
has no product."""

from __future__ import annotations

from benchmark.flops import _count


def products(config):
    w, t, vocab = config["widths"], config["tokens"]["seq_len"], config["tokens"]["vocab"]
    e, f = w["d_model"], w["d_mlp"]
    block = [(3 * e * e, True), (2 * t * e, True), (e * e, True), (2 * e * f, True)]
    return block * w["n_layers"] + [(e * vocab, True)]


def train_flops_per_sample(config) -> float:
    return _count.train_flops(products(config))
