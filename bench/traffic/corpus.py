"""Seeded token corpus for training cells: a flat uint16 token file.

Token ranks follow a Zipf law over the vocabulary (p(rank k) ~ k^-a, a
from the mix), and the seed permutes which token id holds which rank, so
two seeds give different files with the same unigram shape.  Vectorised:
one inverse-CDF lookup for the whole file.
"""
from __future__ import annotations

import numpy as np


def zipf_tokens(seed: int, vocab: int, n: int, a: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    ranks = np.searchsorted(cdf, rng.random(n), side="right")
    ranks = np.minimum(ranks, vocab - 1)
    perm = rng.permutation(vocab).astype(np.uint16)
    return perm[ranks]


def write(path, seed: int, vocab: int, mix: dict) -> int:
    """Write the corpus of ``mix`` for ``seed`` to ``path``; returns its
    length in tokens."""
    if vocab > 1 << 16:
        raise ValueError(f"vocab {vocab} does not fit uint16 tokens")
    toks = zipf_tokens(seed, vocab, int(mix["corpus_tokens"]),
                       float(mix["zipf_a"]))
    toks.tofile(path)
    return len(toks)
