"""The memory-planned document chunking of the batched solve.

`core.distributed.plan_docs_chunk` picks a doc chunk from the Q bucket,
v_r, the local ELL width, doc and word counts and a byte budget; the service
fixes that budget from its device's memory and sweeps the chunks in one
rolled loop (`core.distributed._local_batched_solve`). Whole-document
queries of mixed lengths, up to v_r, are solved here through
`WMDService.query_batch` with a budget that forces three chunks, the last
one padded with empty documents, and compared with the unchunked program
and with the dense reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import sinkhorn_wmd as wmd_cfg
from repro.core import select_query, sinkhorn_wmd_dense
from repro.core.distributed import (plan_docs_chunk, solve_bytes_per_doc,
                                    solve_fixed_bytes)
from repro.core.formats import EllDocs
from repro.data import make_corpus
from repro.launch.mesh import make_mesh
from repro.serving import WMDService
from repro.serving.wmd_service import PLAN_SLACK

N_DOCS, N_QUERIES, V, W, MEAN_WORDS = 45, 3, 256, 16, 6.0
V_R = int(4 * MEAN_WORDS)          # the longest document: no query is cut
GIB = 2 ** 30


def _whole_document_service(**kw):
    """A corpus of N_DOCS documents of 3..V_R words, and as queries the
    shortest and the longest of 12 further documents of the same law and
    one of V_R words, the law's clip."""
    data = make_corpus(vocab_size=V, embed_dim=W, mean_words=MEAN_WORDS,
                       num_docs=N_DOCS + 12, num_queries=0, seed=11)
    ell = EllDocs(cols=data.ell.cols[:N_DOCS], vals=data.ell.vals[:N_DOCS],
                  num_vocab=V)
    extra = range(N_DOCS, N_DOCS + 12)
    lengths = {j: int(np.count_nonzero(data.ell.vals[j])) for j in extra}
    queries = []
    for j in (min(extra, key=lengths.get), max(extra, key=lengths.get)):
        r = np.zeros(V, np.float32)
        live = data.ell.vals[j] != 0
        r[data.ell.cols[j][live]] = data.ell.vals[j][live]
        queries.append(r)
    rng = np.random.default_rng(11)
    r = np.zeros(V, np.float32)
    r[rng.choice(V, V_R, replace=False)] = rng.integers(1, 4, V_R)
    queries.append(r / r.sum())
    cfg = wmd_cfg.WMDConfig(name="whole-docs", vocab_size=V, embed_dim=W,
                            num_docs=N_DOCS, nnz_max=ell.cols.shape[1],
                            v_r=V_R, lamb=1.0, max_iter=12)
    svc = WMDService(mesh=make_mesh((1, 1), ("data", "model")), cfg=cfg,
                     vecs=data.vecs, ell=ell, **kw)
    return svc, ell, queries


def _three_chunk_budget(svc, q):
    """A budget that fits the solve's fixed part and 16 documents' blocks,
    and not 17: 45 documents then go in 3 chunks of 16, the last holding
    13 and 3 empty ones."""
    per_doc = solve_bytes_per_doc(q, V_R, svc._rb.cols.shape[-1])
    return solve_fixed_bytes(q, V_R, V) + 17 * per_doc - 1


def _dense(svc, ell, queries):
    c = np.zeros((V, N_DOCS), np.float32)
    for j in range(N_DOCS):
        live = ell.vals[j] != 0
        c[ell.cols[j][live], j] = ell.vals[j][live]
    out = []
    with jax.default_matmul_precision("highest"):
        for r in queries:
            sel, r_sel = select_query(r)
            out.append(np.asarray(sinkhorn_wmd_dense(
                jnp.asarray(sel), jnp.asarray(r_sel), jnp.asarray(c),
                jnp.asarray(svc.vecs), svc.cfg.lamb, svc.cfg.max_iter)))
    return np.stack(out)


@pytest.mark.parametrize("tol", [0.0, 3e-2])
def test_planned_chunks_match_unchunked_and_dense(tol):
    svc, ell, queries = _whole_document_service(tol=tol)
    lengths = [int(np.count_nonzero(r)) for r in queries]
    assert len(set(lengths)) == N_QUERIES and max(lengths) == V_R
    assert svc.plan_budget_bytes is None        # the CPU reports no memory
    q = 4                                       # 3 queries, pow2 bucket
    unchunked = svc.query_batch(queries)
    svc.plan_budget_bytes = _three_chunk_budget(svc, q)
    assert svc._docs_chunk(q) == 16
    before = svc.metrics.counter("wmd_solve_chunks_total").value
    planned = svc.query_batch(queries)
    snap = svc.metrics.snapshot()
    assert snap["wmd_solve_chunks_total"] - before == 3
    assert snap["wmd_solve_chunk_docs"] == 16
    assert snap["wmd_solve_chunk_bytes"] == 16 * solve_bytes_per_doc(
        q, V_R, svc._rb.cols.shape[-1])
    # an explicit docs_chunk still wins over the plan: 0 is unchunked
    np.testing.assert_array_equal(
        svc.query_batch(queries, docs_chunk=0), unchunked)
    if tol:
        # early exit freezes each (query, chunk) block once its iterate
        # moves by under tol relative in an iteration, the unchunked query
        # only once all its documents do: a chunk may stop a few
        # iterations sooner (here it does), and those iterations move the
        # distances by a small part of tol
        assert not np.array_equal(planned, unchunked)
        np.testing.assert_allclose(planned, unchunked, rtol=tol / 10)
        return
    # documents never interact and every reduction runs inside one
    # document, so the chunked solve repeats the unchunked one's
    # floating-point operations exactly
    np.testing.assert_array_equal(planned, unchunked)
    # the dense reference sums in another order: float32 rounding apart
    want = _dense(svc, ell, queries)
    np.testing.assert_allclose(planned, want, rtol=1e-5)


def test_paper_5k_plans_unchunked():
    """paper_5k's Q = 8 blocks (3.9 GB by the formula) fit the budget the
    service sets on a 16 GiB chip, and the whole 16 GiB: its program stays
    the unchunked one."""
    cfg = wmd_cfg.config("paper_5k")
    budget = int(16 * GIB * (1.0 - PLAN_SLACK))
    shape = (8, cfg.v_r, 144, cfg.num_docs, cfg.vocab_size)
    for b in (budget, 16 * GIB):
        assert plan_docs_chunk(*shape, b) is None
    assert plan_docs_chunk(*shape, None) is None


def test_news20_plans_chunks_that_fit():
    """news20 at Q = 8 (v_r 288, ELL 288 wide, 11 293 documents) needs
    10.6 MB a document, 120 GB unchunked: the plan's chunk fits the same
    budget, in equal chunks of a multiple of 8 documents whose padding
    stays under 8 a chunk."""
    cfg = wmd_cfg.config("news20")
    n = cfg.num_docs
    per_doc = solve_bytes_per_doc(8, cfg.v_r, cfg.nnz_max)
    fixed = solve_fixed_bytes(8, cfg.v_r, cfg.vocab_size)
    assert 10.6e6 < per_doc < 10.7e6 and 0.8e9 < fixed < 0.85e9
    shape = (8, cfg.v_r, cfg.nnz_max, n, cfg.vocab_size)
    budget = int(16 * GIB * (1.0 - PLAN_SLACK))
    chunk = plan_docs_chunk(*shape, budget)
    chunks = -(-n // chunk)
    assert chunk % 8 == 0 and fixed + chunk * per_doc <= budget
    assert 8 <= chunks <= 15 and chunks * chunk - n < 8 * chunks
    # a budget below one 8-document chunk still plans 8 documents
    assert plan_docs_chunk(*shape, fixed + per_doc) == 8
