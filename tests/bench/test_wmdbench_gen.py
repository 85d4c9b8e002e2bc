"""The benchmark's data and traffic generators: paper_5k's statistics,
and the same inputs from the same seed."""
import hashlib
import json
import os

import numpy as np
import pytest

from wmdbench_testing import BENCH, load

from wmdbench import gen, spec

PAPER = load(os.path.join(BENCH, "configs", "paper_5k.json"))
ZIPF = {"kind": "zipf", "s": 1.07, "words": 19}
DOCS = {"kind": "documents"}


def test_corpus_matches_paper_5k_statistics():
    """5 000 docs: about 35 words a doc on average (the paper's nnz of
    about 173k), median 30, lengths clipped at 140 so the ELL is 144
    wide, distinct words within each doc, counts 1..3."""
    c = gen.make_corpus(PAPER, 5000, seed=2**31 + 11)
    assert c.vecs.shape == (100_000, 300) and c.vecs.dtype == np.float32
    assert abs(c.lengths.mean() - 35) < 1.0
    assert 165_000 < c.nnz < 180_000
    assert np.median(c.lengths) == 30
    assert c.lengths.max() == 140 and c.cols.shape == (5000, 144)
    for j in range(0, 5000, 97):
        ids = c.cols[j][c.counts[j] > 0]
        assert ids.size == c.lengths[j] == np.unique(ids).size
    live = c.counts[c.counts > 0]
    assert set(np.unique(live)) == {1.0, 2.0, 3.0}
    np.testing.assert_allclose(c.frequencies().sum(axis=1), 1.0, rtol=1e-6)
    # Zipf head: word 0 is the most frequent word of the corpus
    freq = np.bincount(c.cols[c.counts > 0], minlength=100_000)
    assert freq.argmax() == 0


def test_queries_are_19_distinct_zipf_words():
    q = gen.make_queries(PAPER, ZIPF, 256, seed=5)
    assert q.ids.shape == (256, 19) and (q.ids >= 0).all()
    assert all(np.unique(row).size == 19 for row in q.ids)
    np.testing.assert_allclose(q.weights.sum(axis=1), 1.0, rtol=1e-6)
    r = q.dense(3, 100_000)
    assert np.count_nonzero(r) == 19


def test_same_seed_same_inputs_other_seed_same_sizes():
    small = dict(PAPER, vocab_size=4096, embed_dim=16)
    a = gen.make_corpus(small, 400, seed=7)
    b = gen.make_corpus(small, 400, seed=7)
    c = gen.make_corpus(small, 400, seed=8)
    np.testing.assert_array_equal(a.cols, b.cols)
    np.testing.assert_array_equal(a.vecs, b.vecs)
    assert not np.array_equal(a.cols, c.cols)
    # every seed gets the same multiset of document lengths
    np.testing.assert_array_equal(np.sort(a.lengths), np.sort(c.lengths))
    qa = gen.make_queries(small, ZIPF, 64, seed=7, block=1)
    qb = gen.make_queries(small, ZIPF, 64, seed=7, block=1)
    np.testing.assert_array_equal(qa.ids, qb.ids)


def test_arrivals_same_gaps_in_another_order():
    arr = {"kind": "poisson", "rate_per_s": 60.0}
    a = gen.arrival_times(arr, 30.0, seed=1)
    b = gen.arrival_times(arr, 30.0, seed=2)
    assert a.size == b.size == 1800
    assert 0 < a[0] and a[-1] < 30.0
    gaps = [np.sort(np.diff(x, prepend=0.0)) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], atol=1e-9)
    assert not np.allclose(a, b)


def test_zipf_queries_are_the_same_bytes_as_before_documents_came():
    """A Zipf block of paper_5k's mix, as the generator drew it before the
    documents source was added (its own stream, 3, is untouched)."""
    q = gen.make_queries(PAPER, ZIPF, 512, seed=2**31 + 29, block=1)
    digest = hashlib.sha256(q.ids.tobytes() + q.weights.tobytes())
    assert digest.hexdigest() == (
        "0a8e5ce4f02b1cb4e60b63bf7a8c932455a15ff94bf888af1b1f6c1f2799df7b")
    assert not q.clipped.any()


@pytest.mark.parametrize("v_r", [32, 160])
def test_document_queries_follow_the_document_law(v_r):
    """Whole-document queries: lengths are the corpus's law (quantiles of
    the lognormal, clipped at doc_words.max) cut at v_r, the same multiset
    in every block and seed, in another order; words are distinct and
    weighted, the weights normalised. At paper_5k's v_r = 32 against a
    mean of 35, 43% are cut."""
    cfg = dict(PAPER, v_r=v_r)
    law = cfg["doc_words"]
    drawn = gen.doc_lengths(512, law)
    blocks = [gen.make_queries(cfg, DOCS, 512, seed=s, block=b)
              for s, b in ((2**31 + 5, 0), (2**31 + 5, 1), (3, 0))]
    for q in blocks:
        real = q.ids >= 0
        lengths = real.sum(axis=1)
        np.testing.assert_array_equal(np.sort(lengths),
                                      np.sort(np.minimum(drawn, v_r)))
        assert q.ids.shape[1] == lengths.max() <= min(law["max"], v_r)
        assert all(np.unique(row[keep]).size == lengths[i]
                   for i, (row, keep) in enumerate(zip(q.ids, real)))
        assert (q.ids < cfg["vocab_size"]).all()
        assert (q.weights[real] > 0).all() and (q.weights[~real] == 0).all()
        np.testing.assert_allclose(q.weights.sum(axis=1), 1.0, rtol=1e-6)
        assert q.clipped.sum() == (drawn > v_r).sum()
        assert (lengths[q.clipped] == v_r).all()
    assert q.clipped.mean() == (pytest.approx(0.43, abs=0.01)
                                if v_r == 32 else 0.0)
    firsts = [(q.ids >= 0).sum(axis=1)[:16] for q in blocks]
    assert not np.array_equal(firsts[0], firsts[1])
    again = gen.make_queries(cfg, DOCS, 512, seed=2**31 + 5, block=1)
    np.testing.assert_array_equal(again.ids, blocks[1].ids)
    np.testing.assert_array_equal(again.weights, blocks[1].weights)


def _mix(tmp_path, field, value):
    t = load(os.path.join(BENCH, "traffic", "full_bulk.json"))
    t[field[0]][field[1]] = value
    os.makedirs(tmp_path / "bench" / "traffic")
    with open(tmp_path / "bench" / "traffic" / "mix.json", "w") as f:
        json.dump(t, f)
    return t


def test_a_documents_mix_is_accepted(tmp_path):
    want = _mix(tmp_path, ("queries", "kind"), "documents")
    assert spec.traffic({"traffic": "mix"}, str(tmp_path)) == want


@pytest.mark.parametrize("field,value", [
    (("arrival", "burst"), {"period_s": 2.0, "on_share": 0.25,
                            "factor": 3.0}),
    (("queries", "kind"), "sentences"),
    (("writes", "share"), 0.05)])
def test_traffic_the_generator_does_not_drive_is_refused(tmp_path, field,
                                                         value):
    """The schema has bursts and writes, which no generator drives yet,
    and a mix may name a query source that does not exist: a mix that asks
    for one is refused, not ignored."""
    _mix(tmp_path, field, value)
    with pytest.raises(spec.SpecError, match="not implemented"):
        spec.traffic({"traffic": "mix"}, str(tmp_path))
