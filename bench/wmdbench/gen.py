"""Seeded data and traffic generators: the corpus, the queries and the
arrival schedule of a cell, all made from ``--seed``.

The corpus follows the statistics of the paper's dbpedia set (Tithi &
Petrini, arXiv 2107.06433, section III-B2), the same law as the program's
own `repro.data.make_corpus`, drawn here in bulk so that a corpus of tens of
thousands of documents costs seconds, not minutes:

* embeddings: i.i.d. normal, scale ``embedding.scale`` (1.3), float32;
* words: truncated Zipf(s) over the vocabulary, distinct within a document;
* document lengths: lognormal with mean ``doc_words.mean`` (35) and sigma
  0.55, clipped to [3, 140] and truncated to an integer, so the ELL width
  rounds up to 144. The lengths are the lognormal's quantiles at
  (i + 1/2) / N, shuffled by the seed: every seed gets the same multiset of
  lengths (the same nnz), in another order;
* word counts: integers 1..3, normalised per document.

Queries come from one of two sources, each a stream of its own:

* ``zipf``: ``words`` distinct Zipf(``s``) words with counts 1..3,
  normalised (stream 3);
* ``documents``: whole documents, drawn as the corpus draws one of the
  same configuration (its word law, lengths from its ``doc_words`` law by
  the same quantile-and-shuffle rule, per block of queries, so every block
  and every seed gets the same multiset of lengths; counts 1..3,
  normalised), each cut at the service's ``v_r`` words where the law runs
  longer, since a query holds at most ``v_r`` (stream 6).

Arrivals are a Poisson process made the same way: the exponential's
quantiles shuffled by the seed, so every seed offers the same gaps in
another order.
"""
from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Corpus:
    vecs: np.ndarray          # (V, w) float32
    cols: np.ndarray          # (N, L) int32 word ids, pad = V
    counts: np.ndarray        # (N, L) float32 word counts, pad = 0
    lengths: np.ndarray       # (N,) words per document

    @property
    def nnz(self) -> int:
        return int(self.lengths.sum())

    @property
    def distinct_words(self) -> int:
        return int(np.unique(self.cols[self.counts > 0]).size)

    def frequencies(self) -> np.ndarray:
        """(N, L) float32 counts normalised per document, the ELL values
        the service takes (padding at word id V with value 0)."""
        tot = self.counts.sum(axis=1, dtype=np.float64)[:, None]
        return (self.counts / np.where(tot > 0, tot, 1.0)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Queries:
    ids: np.ndarray           # (n, m) int32 word ids, pad = -1
    weights: np.ndarray       # (n, m) float32 frequencies (sum 1), pad = 0
    clipped: np.ndarray       # (n,) bool: the length was cut at v_r

    def __len__(self) -> int:
        return self.ids.shape[0]

    def dense(self, i: int, vocab: int) -> np.ndarray:
        """Query ``i`` as the (V,) histogram the service's API takes."""
        r = np.zeros(vocab, np.float32)
        keep = self.ids[i] >= 0
        r[self.ids[i][keep]] = self.weights[i][keep]
        return r


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one purpose (``stream``) of one seed."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(s)
    c = np.cumsum(p)
    return c / c[-1]


def distinct_draws(rng: np.random.Generator, cdf: np.ndarray,
                   lengths: np.ndarray) -> np.ndarray:
    """(n, max(lengths)) ids, row j holding ``lengths[j]`` distinct draws
    from ``cdf`` in order of first appearance, padded with -1."""
    lengths = np.asarray(lengths, np.int64)
    n, width = lengths.size, int(lengths.max(initial=1))
    out = np.full((n, width), -1, np.int64)
    todo = np.arange(n)
    over = 3
    while todo.size:
        want = lengths[todo]
        per = over * want + 8
        seg = np.repeat(np.arange(todo.size), per)
        ids = np.searchsorted(cdf, rng.random(seg.size), side="right")
        ids = np.minimum(ids, cdf.size - 1)
        _, first = np.unique(seg * cdf.size + ids, return_index=True)
        first.sort()                       # draw order within each row
        fseg = seg[first]
        start = np.searchsorted(fseg, np.arange(todo.size))
        rank = np.arange(first.size) - start[fseg]
        keep = rank < want[fseg]
        rows = todo[fseg[keep]]
        out[rows, rank[keep]] = ids[first[keep]]
        got = np.bincount(fseg[keep], minlength=todo.size)
        todo = todo[got < want]            # rows short of distinct words
        out[todo] = -1
        over *= 2
    return out


def doc_lengths(n: int, law: dict) -> np.ndarray:
    """The lognormal's quantiles at (i + 1/2) / n, clipped and truncated as
    the paper-statistics generator does."""
    mean, sigma = float(law["mean"]), float(law["sigma"])
    mu = np.log(mean) - sigma ** 2 / 2
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.clip(np.exp(mu + sigma * z), law["min"], law["max"])
    return x.astype(np.int64)


def make_corpus(cfg: dict, num_docs: int, seed: int) -> Corpus:
    v, w = int(cfg["vocab_size"]), int(cfg["embed_dim"])
    vecs = rng_for(seed, 1).standard_normal((v, w), dtype=np.float32)
    vecs *= np.float32(cfg["embedding"]["scale"])
    rng = rng_for(seed, 2)
    lengths = rng.permutation(doc_lengths(num_docs, cfg["doc_words"]))
    ids = distinct_draws(rng, zipf_cdf(v, cfg["word_law"]["s"]), lengths)
    align = int(cfg["nnz_align"])
    width = -(-ids.shape[1] // align) * align
    cols = np.full((num_docs, width), v, np.int32)
    cols[:, :ids.shape[1]] = np.where(ids >= 0, ids, v)
    counts = np.zeros((num_docs, width), np.float32)
    counts[:, :ids.shape[1]] = np.where(
        ids >= 0, rng.integers(1, 4, size=ids.shape), 0)
    return Corpus(vecs=vecs, cols=cols, counts=counts, lengths=lengths)


QUERY_SOURCES = ("zipf", "documents")


def make_queries(cfg: dict, source: dict, n: int, seed: int, *,
                 block: int = 0) -> Queries:
    """Block ``block`` of ``n`` queries of ``source`` (``zipf`` or
    ``documents``, see the module's docstring), drawn by the seed."""
    v = int(cfg["vocab_size"])
    if source["kind"] == "zipf":
        rng = rng_for(seed, 3, block)
        cdf = zipf_cdf(v, source["s"])
        lengths = np.full(n, int(source["words"]))
        clipped = np.zeros(n, bool)
    elif source["kind"] == "documents":
        rng = rng_for(seed, 6, block)
        cdf = zipf_cdf(v, cfg["word_law"]["s"])
        lengths = rng.permutation(doc_lengths(n, cfg["doc_words"]))
        clipped = lengths > int(cfg["v_r"])
        lengths = np.minimum(lengths, int(cfg["v_r"]))
    else:
        raise ValueError(f"unknown query source {source['kind']!r}")
    ids = distinct_draws(rng, cdf, lengths)
    cnt = np.where(ids >= 0, rng.integers(1, 4, size=ids.shape), 0)
    cnt = cnt.astype(np.float32)
    weights = cnt / cnt.sum(axis=1, keepdims=True)
    return Queries(ids=ids.astype(np.int32), weights=weights,
                   clipped=clipped)


def arrival_times(arrival: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop cell at
    the mean rate ``rate_per_s``."""
    rate = float(arrival["rate_per_s"])
    n = int(round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng_for(seed, 4).permutation(-np.log1p(-q))   # unit mean
    return np.cumsum(gaps) * (seconds / n)
