"""The benchmark's corpus generator: LDA documents with power-law word
marginals, drawn on the device from the seed.

- Topics: each topic is a Dirichlet draw whose base measure is Zipfian
  over word ranks (``zipf_exponent``), with total concentration
  ``topic_concentration``.  Every topic's mean is that Zipf law, so the
  corpus's word marginals are a power law, as in real text.  Which word
  id holds which rank is a seeded permutation.
- Documents: a sparse topic mixture per document (symmetric Dirichlet,
  total ``doc_topic_concentration``), token lengths from a lognormal
  whose mean (``tokens_per_doc``) is calibrated so that the mean number
  of distinct words per document matches the source collection.
- Sizes are a fixed set: every seed draws the same multiset of document
  lengths (lognormal quantiles) in another order, and the same number of
  documents, so a seed changes which words are drawn and not how much
  work there is.

Everything heavy is one jitted call on the device; the host only splits
the sorted (document, word) keys into per-document (ids, counts).
"""

from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Doc = Tuple[np.ndarray, np.ndarray]


def seed_words(seed: int, stream: str, n: int = 2) -> List[int]:
    """``n`` independent 31-bit seeds for one named random stream of a
    run.  Seeds above 32 bits keep all their bits (``PRNGKey`` would
    drop the high ones)."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(seed) >> 64,
                                 *stream.encode()])
    return [int(x) & 0x7FFFFFFF for x in ss.generate_state(n)]


def jax_key(seed: int, stream: str):
    return jax.random.PRNGKey(seed_words(seed, stream, 1)[0])


def zipf_base(n_words: int, exponent: float) -> np.ndarray:
    """Zipf probabilities over ranks 1..W."""
    p = np.arange(1, n_words + 1, dtype=np.float64) ** -float(exponent)
    return p / p.sum()


def length_quantiles(n_docs: int, mean: float, sigma: float) -> np.ndarray:
    """``n_docs`` token lengths at the (i + 1/2)/n quantiles of a
    lognormal with the given mean: one fixed multiset for every seed."""
    from statistics import NormalDist
    mu = math.log(mean) - sigma * sigma / 2.0
    nd = NormalDist()
    q = [nd.inv_cdf((i + 0.5) / n_docs) for i in range(n_docs)]
    return np.maximum(1, np.rint(np.exp(mu + sigma * np.asarray(q)))
                      ).astype(np.int32)


@functools.partial(jax.jit, static_argnames=("K", "W"))
def _topics(key, base_by_rank, *, K: int, W: int):
    k_perm, k_gam = jax.random.split(key)
    # row by row, so the sampler's temporaries stay [W]-sized
    g = jax.lax.map(lambda k: jax.random.gamma(k, base_by_rank),
                    jax.random.split(k_gam, K), batch_size=16)
    g = g / jnp.sum(g, axis=1, keepdims=True)
    perm = jax.random.permutation(k_perm, W)     # word id of each rank
    return jnp.zeros((K, W), jnp.float32).at[:, perm].set(g)


def make_topics(seed: int, corpus: dict, K: int, W: int):
    """The generator's topics phi*[K, W] (rows sum to one), on the
    device."""
    base = zipf_base(W, corpus["zipf_exponent"]) * corpus[
        "topic_concentration"]
    return _topics(jax_key(seed, "topics"), jnp.asarray(base, jnp.float32),
                   K=K, W=W)


def _search(cdf_flat, row, n_cols: int, target):
    """Per element: the first column c of row ``row`` of the row-major
    [rows, n_cols] CDF with cdf[row, c] > target (binary search by
    gathers: no [n, n_cols] operand is ever formed)."""
    lo = jnp.zeros_like(row)
    hi = jnp.full_like(row, n_cols - 1)
    steps = max(1, int(math.ceil(math.log2(max(n_cols, 2)))) + 1)

    def body(_, lh):
        lo, hi = lh
        mid = (lo + hi) // 2
        go_right = cdf_flat[row * n_cols + mid] <= target
        return jnp.where(go_right, mid + 1, lo), jnp.where(go_right, hi, mid)

    lo, _ = jax.lax.fori_loop(0, steps, body, (lo, hi))
    return jnp.minimum(lo, n_cols - 1)


@functools.partial(jax.jit, static_argnames=("n_max",))
def _doc_keys(key, topic_cdf, doc_alpha, lengths, *, n_max: int):
    """Sorted (document * W + word) keys of every token of a block of
    documents; padding tokens carry the sentinel D * W."""
    K, W = topic_cdf.shape
    D = lengths.shape[0]
    k_th, k_z, k_w = jax.random.split(key, 3)
    theta = jax.random.dirichlet(k_th, jnp.full((K,), doc_alpha), (D,))
    th_cdf = jnp.cumsum(theta, axis=1)
    doc = jnp.broadcast_to(jnp.arange(D, dtype=jnp.int32)[:, None],
                           (D, n_max))
    u = jax.random.uniform(k_z, (D, n_max)) * th_cdf[:, -1:]
    z = _search(th_cdf.reshape(-1), doc, K, u)
    v = jax.random.uniform(k_w, (D, n_max)) * topic_cdf[z, W - 1]
    w = _search(topic_cdf.reshape(-1), z, W, v)
    real = jnp.arange(n_max)[None, :] < lengths[:, None]
    keys = jnp.where(real, doc * W + w, D * W)
    return jnp.sort(keys.reshape(-1))


def keys_to_docs(keys: np.ndarray, n_docs: int, n_words: int) -> List[Doc]:
    """Run-length count sorted (document, word) keys into per-document
    (word ids ascending, counts)."""
    keys = np.asarray(keys)
    keys = keys[keys < n_docs * n_words]
    start = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    counts = np.diff(np.r_[start, keys.size]).astype(np.float32)
    uniq = keys[start]
    doc, word = uniq // n_words, (uniq % n_words).astype(np.int32)
    cut = np.searchsorted(doc, np.arange(n_docs + 1))
    return [(word[a:b], counts[a:b]) for a, b in zip(cut[:-1], cut[1:])]


class Corpus:
    """Seeded topics plus a generator of document blocks at a fixed
    multiset of lengths."""

    def __init__(self, seed: int, config: dict):
        self.seed = int(seed)
        self.config = config
        self.K = int(config["num_topics"])
        self.W = int(config["vocab_size"])
        self.corpus = config["corpus"]
        self.topics = make_topics(seed, self.corpus, self.K, self.W)
        self._cdf = jnp.cumsum(self.topics, axis=1)
        self._alpha = float(self.corpus["doc_topic_concentration"]) / self.K

    def lengths(self, n_docs: int, stream: str, groups: int = 1
                ) -> np.ndarray:
        """The fixed length multiset for ``n_docs`` documents, dealt
        into ``groups`` consecutive groups (minibatches) of one shape —
        group g holds quantiles g, g + groups, ... — each in a seeded
        order."""
        lens = length_quantiles(n_docs, self.corpus["tokens_per_doc"],
                                self.corpus["doc_len_sigma"])
        rng = np.random.default_rng(seed_words(self.seed, stream + "/order"))
        return np.concatenate([rng.permutation(lens[g::groups])
                               for g in range(groups)])

    def docs(self, lengths: np.ndarray, stream: str, block: int,
             n_max: int) -> List[Doc]:
        """Documents of the given token lengths, drawn ``block`` at a
        time (one compiled shape: [block, n_max])."""
        out: List[Doc] = []
        n = len(lengths)
        for b, i in enumerate(range(0, n, block)):
            lens = np.zeros(block, np.int32)
            part = lengths[i:i + block]
            lens[:len(part)] = part
            keys = _doc_keys(jax.random.fold_in(jax_key(self.seed, stream), b),
                             self._cdf, self._alpha, jnp.asarray(lens),
                             n_max=n_max)
            out.extend(keys_to_docs(np.asarray(keys), block,
                                    self.W)[:len(part)])
        return out

    def phi_acc(self, tokens: float):
        return mid_stream_phi(self.topics, tokens)


def mid_stream_phi(topics, tokens: float):
    """A mid-stream statistic: the topics [K, W] scaled to ``tokens``
    tokens of earlier minibatches, [W, K] float32 on the device (each
    topic's share of the tokens is 1/K: document mixtures are
    symmetric)."""
    return topics.T * (float(tokens) / topics.shape[0])
