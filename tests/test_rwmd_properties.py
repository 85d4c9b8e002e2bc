"""Property suite of the two-tier pruned retriever (core.rwmd + the pruned
`WMDService.top_k`).

The invariants, in decreasing order of load-bearing-ness:
  1. soundness -- the doc-side RWMD bound never exceeds the engine's
     returned distance, chunked or not, and (crucially) at every iteration
     budget. This is THE fact the pruning contract rests on, and the
     reason the doc side was chosen: the engine enforces the doc-side
     marginal exactly at every iterate, while the classic query-side
     bound only holds at convergence (demonstrated below).
  2. exactness -- pruned top-k == the exhaustive chunked scan, bitwise,
     under random k / N / capacity / chunk.
  3. inertness -- pad query rows and pad ELL slots contribute exactly
     zero to the bound reduction.

Each invariant has a seeded always-on test (runs everywhere, no optional
deps) and a hypothesis-driven generalization (random shapes/seeds searched
adversarially; skipped when hypothesis is absent, executed seeded in CI via
``--hypothesis-seed=0`` -- see ci.yml's property step).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sinkhorn_wmd import WMDConfig
from repro.core import (assemble_m_stripes, ell_from_dense, rwmd_bound_batch,
                        rwmd_query_side_bound, select_query,
                        sinkhorn_wmd_sparse_batch)
from repro.core.distributed import pad_query_batch
from repro.data import make_corpus, zipf_query_stream
from repro.launch.mesh import make_mesh
from repro.serving import WMDService

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container without the dev extra:
    given = None                        # seeded subset still runs


# ---------------------------------------------------------------------------
# shared problem builders
# ---------------------------------------------------------------------------

def _problem(seed, *, v=96, w=8, n=20, vr_bucket=8, q=3):
    """Random batched WMD problem: (sel_b, r_b, mask_b, cols, vals, vecs)."""
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(v, w)).astype(np.float32)
    c = np.zeros((v, n), np.float32)
    for j in range(n):
        widx = rng.choice(v, rng.integers(2, 9), replace=False)
        c[widx, j] = rng.random(widx.size).astype(np.float32)
        c[:, j] /= c[:, j].sum()
    ell = ell_from_dense(c)
    rs = []
    for i in range(q):
        r = np.zeros(v, np.float32)
        idx = rng.choice(v, int(rng.integers(3, vr_bucket + 1)),
                         replace=False)
        r[idx] = rng.random(idx.size).astype(np.float32) + 0.1
        r /= r.sum()
        rs.append(r)
    sels, rsels = zip(*[select_query(r) for r in rs])
    sel_b, r_b, mask_b = pad_query_batch(sels, rsels, vr_bucket)
    return sel_b, r_b, mask_b, ell, vecs


def _bound_and_dist(sel_b, r_b, mask_b, ell, vecs, *, max_iter,
                    docs_chunk):
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    lb = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
    d = np.asarray(sinkhorn_wmd_sparse_batch(
        jnp.asarray(sel_b), jnp.asarray(r_b), cols, vals,
        jnp.asarray(vecs), 1.0, max_iter,
        row_mask=jnp.asarray(mask_b), docs_chunk=docs_chunk))
    return lb, d


def _service(seed, *, docs, vocab=512, capacity=0, prune_chunk=16, k_cfg=16):
    data = make_corpus(vocab_size=vocab, embed_dim=32, num_docs=docs,
                       num_queries=1, query_words=11, mean_words=12.0,
                       seed=seed)
    cfg = WMDConfig(name="prop", vocab_size=vocab, embed_dim=32,
                    num_docs=docs, nnz_max=64, v_r=k_cfg, lamb=1.0,
                    max_iter=8)
    mesh = make_mesh((1, 1), ("data", "model"))
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      cache_capacity=capacity, prune_chunk=prune_chunk,
                      bound_docs_chunk=None)


def _queries(vocab, q, seed):
    stream = zipf_query_stream(vocab_size=vocab, query_words=11, s=1.2,
                               seed=seed)
    return [next(stream) for _ in range(q)]


# ---------------------------------------------------------------------------
# 1. soundness: bound <= engine output, every chunking, every budget
# ---------------------------------------------------------------------------

# fp slack of the comparison: the bound and the distance accumulate their
# dot products in different orders, so they may disagree by rounding even
# when mathematically ordered. The service's prune_margin (1e-3) dominates
# this by ~100x.
RTOL, ATOL = 1e-5, 1e-6


# N = 20 documents: unchunked, 5-doc chunks (dividing), 8-doc chunks (not)
@pytest.mark.parametrize("docs_chunk", [None, 5, 8],
                         ids=["none", "dividing", "non-dividing"])
@pytest.mark.parametrize("max_iter", [1, 3, 15])
def test_bound_below_engine_all_impls_all_budgets(docs_chunk, max_iter):
    """rwmd(q, d) <= sinkhorn_wmd(q, d) at ANY fixed iteration budget --
    including budget 1, where the query-side marginal is maximally stale."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=max_iter * 7 + 1)
    lb, d = _bound_and_dist(sel_b, r_b, mask_b, ell, vecs,
                            max_iter=max_iter, docs_chunk=docs_chunk)
    assert np.all(lb <= d * (1 + RTOL) + ATOL), \
        f"bound exceeds engine output by {np.max(lb - d)}"


def test_query_side_bound_only_sound_at_convergence():
    """The classic query-side RWMD bounds the *converged* distance (200
    iterations) but is allowed to exceed a budget-limited one -- the
    asymmetry that drove the doc-side choice (core.rwmd docstring)."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=3, n=24, q=4)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    lb_q = np.asarray(rwmd_query_side_bound(m_pad, jnp.asarray(r_b),
                                            cols, vals))
    d_conv = np.asarray(sinkhorn_wmd_sparse_batch(
        jnp.asarray(sel_b), jnp.asarray(r_b), cols, vals,
        jnp.asarray(vecs), 1.0, 200, row_mask=jnp.asarray(mask_b)))
    assert np.all(lb_q <= d_conv * (1 + 1e-4) + ATOL)


def test_bound_impls_agree():
    """fused == kernel == chunked, and all equal the dense oracle."""
    from repro.kernels import ops, ref
    sel_b, _, mask_b, ell, vecs = _problem(seed=11)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    lb = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
    lb_c = np.asarray(rwmd_bound_batch(m_pad, cols, vals, docs_chunk=7))
    lb_k = np.asarray(ops.rwmd_bound_batch(m_pad, cols, vals))
    lb_r = np.asarray(ref.rwmd_bound_batch(m_pad, cols, vals))
    np.testing.assert_array_equal(lb, lb_c)
    np.testing.assert_allclose(lb_k, lb_r, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(lb, lb_r, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# 2. exactness: pruned top-k == exhaustive scan, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,docs,capacity,chunk",
                         [(1, 48, 0, 8), (5, 80, 256, 16),
                          (16, 64, 64, 32), (7, 100, 0, 100)])
def test_pruned_topk_equals_scan(k, docs, capacity, chunk):
    svc = _service(seed=k, docs=docs, capacity=capacity, prune_chunk=chunk)
    qs = _queries(512, 3, seed=k)
    idx_p, d_p = svc.top_k_batch(qs, k, prune=True)
    ps = dict(svc.last_prune_stats)
    idx_s, d_s = svc.top_k_scan_batch(qs, k)
    np.testing.assert_array_equal(idx_p, idx_s)
    np.testing.assert_array_equal(d_p, d_s)
    # the prefilter must actually do something -- unless one block already
    # covers the whole corpus (chunk >= docs), where nothing CAN be pruned
    if chunk < docs:
        assert ps["solves_avoided"] > 0.0
    # and agree with the production one-program full scan as a SET (only
    # fp32-close: different program shapes vectorize differently, the same
    # engine-vs-engine tolerance the batched/sequential tests use)
    idx_f, d_f = svc.top_k_batch(qs, k)
    np.testing.assert_array_equal(np.sort(idx_p, -1), np.sort(idx_f, -1))
    np.testing.assert_allclose(d_p, d_f, rtol=1e-3, atol=1e-5)


def test_pruned_topk_k_exceeds_docs():
    """k > N degrades to k = N and still matches the scan bitwise."""
    svc = _service(seed=5, docs=24, prune_chunk=8)
    qs = _queries(512, 2, seed=5)
    idx_p, d_p = svc.top_k_batch(qs, 99, prune=True)
    idx_s, d_s = svc.top_k_scan_batch(qs, 99)
    assert idx_p.shape == (2, 24)
    np.testing.assert_array_equal(idx_p, idx_s)
    np.testing.assert_array_equal(d_p, d_s)


def test_pruned_topk_duplicate_docs_tie_deterministic():
    """Duplicate docs produce exactly tied distances; the (distance, id)
    selection rule must return the identical set from every route."""
    data = make_corpus(vocab_size=256, embed_dim=16, num_docs=30,
                      num_queries=1, query_words=9, mean_words=10.0, seed=2)
    dense = data.ell.to_dense()
    dense[:, 15:30] = dense[:, 0:15]          # 15 exact duplicates
    ell = ell_from_dense(dense)
    cfg = WMDConfig(name="ties", vocab_size=256, embed_dim=16, num_docs=30,
                    nnz_max=64, v_r=16, lamb=1.0, max_iter=8)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=ell,
                     prune_chunk=8, bound_docs_chunk=None)
    qs = _queries(256, 2, seed=9)
    idx_p, d_p = svc.top_k_batch(qs, 6, prune=True)
    idx_s, d_s = svc.top_k_scan_batch(qs, 6)
    np.testing.assert_array_equal(idx_p, idx_s)
    np.testing.assert_array_equal(d_p, d_s)
    idx_f, _ = svc.top_k_batch(qs, 6)
    np.testing.assert_array_equal(np.sort(idx_p, -1), np.sort(idx_f, -1))


def test_pruned_single_query_route():
    svc = _service(seed=8, docs=40, prune_chunk=8)
    q = _queries(512, 1, seed=8)[0]
    idx1, d1 = svc.top_k(q, 4, prune=True)
    idx_b, d_b = svc.top_k_batch([q], 4, prune=True)
    np.testing.assert_array_equal(idx1, idx_b[0])
    np.testing.assert_array_equal(d1, d_b[0])


def test_coalesced_topk_bitwise_and_homogeneous():
    """submit_top_k coalesces like plain queries: homogeneous batches, each
    one literally a top_k_batch(prune=True) dispatch -- results bitwise
    equal to the direct call; mixed kinds split at the kind boundary."""
    svc = _service(seed=13, docs=48, capacity=256, prune_chunk=16)
    qs = _queries(512, 6, seed=13)
    svc.query_batch(qs[:4])                       # compile outside serving
    svc.top_k_batch(qs[:4], 3, prune=True)
    with svc.async_service(window_ms=50.0, max_batch=4) as co:
        # homogeneous run: 4 top-k requests must cut as ONE batch
        futs = [co.submit_top_k(r, 3) for r in qs[:4]]
        co.drain()
        idx_d, d_d = svc.top_k_batch(qs[:4], 3, prune=True)
        for i, f in enumerate(futs):
            idx, d = f.result()
            np.testing.assert_array_equal(idx, idx_d[i])
            np.testing.assert_array_equal(d, d_d[i])
        st = co.stats()
        assert st.batch_size_hist.get(4, 0) >= 1   # coalesced, not split
        # mixed kinds: a plain query between top-k runs forces a cut at
        # each kind change -- every request still answered correctly
        f1 = co.submit_top_k(qs[4], 2)
        f2 = co.submit(qs[4])
        f3 = co.submit_top_k(qs[5], 2)
        co.drain()
        np.testing.assert_array_equal(f2.result(),
                                      svc.query_batch([qs[4]])[0])
        i1, dd1 = svc.top_k_batch([qs[4]], 2, prune=True)
        np.testing.assert_array_equal(f1.result()[0], i1[0])
        i3, dd3 = svc.top_k_batch([qs[5]], 2, prune=True)
        np.testing.assert_array_equal(f3.result()[1], dd3[0])


# ---------------------------------------------------------------------------
# 3. inertness: pad rows / pad slots contribute exactly zero
# ---------------------------------------------------------------------------

def test_pad_rows_and_slots_inert():
    """Growing the v_r bucket (more +inf pad rows) and appending pad ELL
    slots must not change a single bit of the bound."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=21, vr_bucket=6)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    lb = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
    # wider bucket: re-pad the same queries to v_r + 5
    pad = ((0, 0), (0, 5))
    sel_w = np.pad(sel_b, pad)
    mask_w = np.pad(mask_b, pad)
    m_w = assemble_m_stripes(sel_w, mask_w, vecs, rows_bucket=8)
    lb_w = np.asarray(rwmd_bound_batch(m_w, cols, vals))
    np.testing.assert_array_equal(lb_w, lb)
    # extra pad slots on every doc (col = V, val = 0)
    n, nnz = ell.cols.shape
    cols_s = np.concatenate(
        [ell.cols, np.full((n, 3), ell.num_vocab, ell.cols.dtype)], axis=1)
    vals_s = np.concatenate([ell.vals, np.zeros((n, 3), ell.vals.dtype)],
                            axis=1)
    lb_s = np.asarray(rwmd_bound_batch(m_pad, jnp.asarray(cols_s),
                                       jnp.asarray(vals_s)))
    np.testing.assert_array_equal(lb_s, lb)


def test_filler_queries_and_empty_docs_bound_zero():
    """All-pad filler queries and empty docs bound to exactly 0.0 -- the
    engine's distance for both -- so a 0 bound can never prune them."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=31, n=12)
    # append a filler query and an empty doc
    sel_f = np.concatenate([sel_b, np.zeros((1,) + sel_b.shape[1:],
                                            sel_b.dtype)])
    mask_f = np.concatenate([mask_b, np.zeros((1,) + mask_b.shape[1:],
                                              mask_b.dtype)])
    n, nnz = ell.cols.shape
    cols_e = np.concatenate(
        [ell.cols, np.full((1, nnz), ell.num_vocab, ell.cols.dtype)])
    vals_e = np.concatenate([ell.vals, np.zeros((1, nnz), ell.vals.dtype)])
    m_pad = assemble_m_stripes(sel_f, mask_f, vecs, rows_bucket=8)
    lb = np.asarray(rwmd_bound_batch(m_pad, jnp.asarray(cols_e),
                                     jnp.asarray(vals_e)))
    assert np.all(lb[-1] == 0.0)        # filler query row
    assert np.all(lb[:, -1] == 0.0)     # empty doc column


# ---------------------------------------------------------------------------
# hypothesis generalizations (skipped without the dev extra; CI runs them
# seeded via --hypothesis-seed=0)
# ---------------------------------------------------------------------------

if given is not None:
    _settings = settings(max_examples=15, deadline=None)

    @_settings
    @given(st.integers(0, 10_000), st.integers(1, 12),
           st.sampled_from([None, 7]))
    def test_hyp_bound_below_engine(seed, max_iter, docs_chunk):
        sel_b, r_b, mask_b, ell, vecs = _problem(seed=seed)
        lb, d = _bound_and_dist(sel_b, r_b, mask_b, ell, vecs,
                                max_iter=max_iter, docs_chunk=docs_chunk)
        assert np.all(lb <= d * (1 + RTOL) + ATOL)

    @_settings
    @given(st.integers(0, 10_000), st.integers(1, 20),
           st.integers(30, 90), st.sampled_from([0, 64, 1024]),
           st.sampled_from([4, 16, 64]))
    def test_hyp_pruned_equals_scan(seed, k, docs, capacity, chunk):
        svc = _service(seed=seed % 97, docs=docs, capacity=capacity,
                       prune_chunk=chunk)
        qs = _queries(512, 2, seed=seed)
        idx_p, d_p = svc.top_k_batch(qs, k, prune=True)
        idx_s, d_s = svc.top_k_scan_batch(qs, k)
        np.testing.assert_array_equal(idx_p, idx_s)
        np.testing.assert_array_equal(d_p, d_s)

    @_settings
    @given(st.integers(0, 10_000), st.integers(1, 8))
    def test_hyp_pad_rows_inert(seed, extra):
        sel_b, _, mask_b, ell, vecs = _problem(seed=seed, vr_bucket=6)
        cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
        m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
        lb = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
        pad = ((0, 0), (0, extra))
        m_w = assemble_m_stripes(np.pad(sel_b, pad), np.pad(mask_b, pad),
                                 vecs, rows_bucket=8)
        lb_w = np.asarray(rwmd_bound_batch(m_w, cols, vals))
        np.testing.assert_array_equal(lb_w, lb)
