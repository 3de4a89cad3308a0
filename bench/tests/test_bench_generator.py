"""The generator's statistics: power-law word marginals, the calibrated
mean of distinct words per document, and sizes that do not depend on the
seed."""

import numpy as np
import pytest

import tiny
from bench import generator


@pytest.fixture(scope="module")
def docs():
    c = generator.Corpus(5, tiny.CONFIG)
    lens = c.lengths(512, "test")
    return c, lens, c.docs(lens, "test", block=128, n_max=int(lens.max()))


def test_lengths_are_one_multiset_in_seeded_orders():
    a = generator.Corpus(1, tiny.CONFIG).lengths(256, "x")
    b = generator.Corpus(2, tiny.CONFIG).lengths(256, "x")
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert abs(a.mean() - tiny.CONFIG["corpus"]["tokens_per_doc"]) < 1.0


def test_large_seeds_keep_their_high_bits():
    assert (generator.seed_words(2**40 + 7, "s")
            != generator.seed_words(7, "s"))


def test_tokens_and_same_seed_same_docs(docs):
    c, lens, got = docs
    assert [int(cnt.sum()) for _, cnt in got] == lens.tolist()
    again = c.docs(lens[:128], "test", block=128, n_max=int(lens.max()))
    for (w1, c1), (w2, c2) in zip(got[:128], again):
        assert np.array_equal(w1, w2) and np.array_equal(c1, c2)


def test_mean_distinct_words_matches_calibration(docs):
    _, _, got = docs
    mean = np.mean([len(w) for w, _ in got])
    target = tiny.CONFIG["corpus"]["distinct_per_doc"]
    assert abs(mean - target) / target < 0.1


def test_word_marginals_follow_zipf():
    cfg = dict(tiny.CONFIG, vocab_size=4096, num_topics=32)
    cfg["corpus"] = dict(cfg["corpus"], topic_concentration=40.96)
    c = generator.Corpus(9, cfg)
    lens = c.lengths(2048, "zipf")
    freq = np.zeros(cfg["vocab_size"])
    for w, cnt in c.docs(lens, "zipf", block=512, n_max=int(lens.max())):
        np.add.at(freq, w, cnt)
    f = np.sort(freq)[::-1]
    ranks = np.arange(1, f.size + 1)
    sel = (ranks >= 5) & (ranks <= 500)
    slope = np.polyfit(np.log(ranks[sel]), np.log(f[sel]), 1)[0]
    assert abs(-slope - cfg["corpus"]["zipf_exponent"]) < 0.2
