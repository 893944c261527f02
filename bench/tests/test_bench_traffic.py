"""Seeded traffic: a seed repeats exactly, seeds differ, the token ranks
follow the declared law."""
import numpy as np

from traffic import corpus


def test_corpus_repeats_per_seed_and_differs_between_seeds(tmp_path):
    mix = {"corpus_tokens": 50000, "zipf_a": 1.1}
    paths = [tmp_path / f"{i}.bin" for i in range(3)]
    for p, seed in zip(paths, (3, 3, 2 ** 31 + 9)):
        assert corpus.write(p, seed, 50304, mix) == 50000
    a, b, c = (np.fromfile(p, np.uint16) for p in paths)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.max() < 50304


def test_corpus_ranks_follow_zipf():
    vocab, a = 1000, 1.1
    toks = corpus.zipf_tokens(5, vocab, 400000, a)
    freq = np.sort(np.bincount(toks, minlength=vocab))[::-1]
    # log-frequency against log-rank over the head of the law: slope -a
    r = np.arange(1, 51)
    slope = np.polyfit(np.log(r), np.log(freq[:50]), 1)[0]
    assert abs(slope + a) < 0.1
    p1 = 1.0 / np.sum(np.arange(1, vocab + 1, dtype=float) ** -a)
    assert abs(freq[0] / len(toks) - p1) < 0.01
