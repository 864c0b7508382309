"""A seeded, learnable token stream for language-model runs with no corpus
staged: a first-order Markov chain over ``vocab`` ids. Ids are drawn from a
Zipf-like unigram (exponent 1.0) and each id is followed by one of 4 seeded
successors with probabilities 0.55/0.25/0.15/0.05, so a model learns the
unigram within its first steps and the successors after. Sequences are
independent draws of ``seq_len + 1`` tokens; the target is the next token.
"""

from __future__ import annotations

import copy

import numpy as np

SUCCESSOR_PROBABILITIES = (0.55, 0.25, 0.15, 0.05)


class MarkovTokens:
    """``images`` holds the ``(n, seq_len)`` int32 tokens and ``labels`` the
    next tokens: the names are the loaders' protocol for contiguous arrays
    (``data/loader.py``), whatever the arrays hold."""

    def __init__(self, n: int, seq_len: int, vocab: int, seed: int = 0):
        rng = np.random.RandomState(seed)
        unigram = 1.0 / np.arange(1, vocab + 1)
        unigram /= unigram.sum()
        successors = rng.choice(vocab, size=(vocab, len(SUCCESSOR_PROBABILITIES)), p=unigram)
        choices = rng.choice(len(SUCCESSOR_PROBABILITIES), size=(n, seq_len), p=SUCCESSOR_PROBABILITIES)
        stream = np.empty((n, seq_len + 1), np.int32)
        stream[:, 0] = rng.choice(vocab, size=n, p=unigram)
        for t in range(seq_len):
            stream[:, t + 1] = successors[stream[:, t], choices[:, t]]
        self.num_classes = vocab
        self.images, self.labels = stream[:, :-1].copy(), stream[:, 1:].copy()

    def __len__(self) -> int:
        return len(self.labels)

    def __getitem__(self, i):
        return self.images[i], self.labels[i]

    def get_batch(self, indices):
        idx = np.asarray(indices)
        return self.images[idx], self.labels[idx]


def markov_token_datasets(n_train: int, n_test: int, seq_len: int, vocab: int, seed: int = 0):
    """(train, test): two draws of one chain (same successors, other sequences)."""
    train = MarkovTokens(n_train + n_test, seq_len, vocab, seed)
    test = copy.copy(train)
    test.images, test.labels = train.images[n_train:], train.labels[n_train:]
    train.images, train.labels = train.images[:n_train], train.labels[:n_train]
    return train, test
