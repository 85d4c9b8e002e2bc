"""Property suite of the three-tier retrieval cascade (core.cascade + the
M-row cache + the cascaded `WMDService.top_k_batch(prune=True)`).

The invariants, in decreasing order of load-bearing-ness:
  1. the bound chain -- tier-0 centroid <= LC-RWMD <= doc-side RWMD <=
     engine distance, chunked or not, at every iteration budget. Each
     link is what makes the tier in front of it safe to prune with; the
     LC link is *bitwise* (the same min over the same floats, hoisted
     out of the doc loop -- core.cascade docstring).
  2. tier-disable invariance -- switching any tier (or all of them) off
     changes which docs get solved, never a single result bit: bounds
     only reorder and skip, every solved doc's bits come from the same
     stripes programs.
  3. M-cache transparency -- cache on == cache off bitwise, through
     evictions, at the store level and through the full pruned service.
  4. tier-0 only bites on clustered geometry -- exactly 0 on isotropic
     random embeddings (documented, not a bug) and strictly positive
     when query and corpus words occupy different clusters.

Each invariant has a seeded always-on test and (where shapes vary) a
hypothesis generalization, executed seeded in CI via ``--hypothesis-seed=0``
-- see ci.yml's property step.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.sinkhorn_wmd import WMDConfig
from repro.core import (MCache, assemble_m_stripes, centroid_bound_batch,
                        doc_centroids, ell_from_dense, lc_rwmd_bound_batch,
                        min_cost_vectors, rwmd_bound_batch, select_query,
                        sinkhorn_wmd_sparse_batch)
from repro.core.distributed import pad_query_batch
from repro.data import make_corpus, zipf_query_stream
from repro.launch.mesh import make_mesh
from repro.serving import WMDService

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container without the dev extra:
    given = None                        # seeded subset still runs


# fp slack for cross-tier comparisons that accumulate in different orders;
# the service's prune_margin (1e-3) dominates this by ~100x. The LC link
# itself is exact (assert_array_equal below).
RTOL, ATOL = 1e-5, 1e-6


# ---------------------------------------------------------------------------
# shared problem builders (mirrors tests/test_rwmd_properties.py)
# ---------------------------------------------------------------------------

def _problem(seed, *, v=96, w=8, n=20, vr_bucket=8, q=3):
    """Random batched WMD problem: (sel_b, r_b, mask_b, ell, vecs)."""
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


def _tier_bounds(sel_b, r_b, mask_b, ell, vecs):
    """(tier0, lc, doc_side) bound matrices, all (Q, N) numpy."""
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    vecs_d = jnp.asarray(vecs)
    g, m = doc_centroids(cols, vals, vecs_d)
    lb0 = np.asarray(centroid_bound_batch(
        jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(mask_b),
        vecs_d, g, m))
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs_d, rows_bucket=8)
    lb_lc = np.asarray(lc_rwmd_bound_batch(min_cost_vectors(m_pad),
                                           cols, vals))
    lb_doc = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
    return lb0, lb_lc, lb_doc


def _service(seed, *, docs, vocab=512, capacity=0, mcache=0, prune_chunk=16,
             **kw):
    data = make_corpus(vocab_size=vocab, embed_dim=32, num_docs=docs,
                       num_queries=1, query_words=11, mean_words=12.0,
                       seed=seed)
    cfg = WMDConfig(name="cascade-prop", vocab_size=vocab, embed_dim=32,
                    num_docs=docs, nnz_max=64, v_r=16, lamb=1.0,
                    max_iter=8)
    mesh = make_mesh((1, 1), ("data", "model"))
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      cache_capacity=capacity, mcache_capacity=mcache,
                      prune_chunk=prune_chunk, bound_docs_chunk=None, **kw)


def _queries(vocab, q, seed):
    stream = zipf_query_stream(vocab_size=vocab, query_words=11, s=1.2,
                               seed=seed)
    return [next(stream) for _ in range(q)]


# ---------------------------------------------------------------------------
# 1. the bound chain: tier0 <= LC <= doc-side <= engine, every budget
# ---------------------------------------------------------------------------

# N = 20 documents: unchunked, 5-doc chunks (dividing), 8-doc chunks (not)
@pytest.mark.parametrize("docs_chunk", [None, 5, 8],
                         ids=["none", "dividing", "non-dividing"])
@pytest.mark.parametrize("max_iter", [1, 3, 15])
def test_bound_chain_all_impls_all_budgets(docs_chunk, max_iter):
    """tier0(q,d) <= lc(q,d) <= rwmd(q,d) <= sinkhorn(q,d) at ANY fixed
    iteration budget -- the fact each cascade tier's pruning rests on.
    The LC <= doc-side link is equality down to the bit (hoisted min)."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=max_iter * 13 + 5)
    lb0, lb_lc, lb_doc = _tier_bounds(sel_b, r_b, mask_b, ell, vecs)
    np.testing.assert_array_equal(lb_lc, lb_doc)
    assert np.all(lb0 <= lb_lc * (1 + RTOL) + ATOL), \
        f"tier0 exceeds LC by {np.max(lb0 - lb_lc)}"
    d = np.asarray(sinkhorn_wmd_sparse_batch(
        jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(ell.cols),
        jnp.asarray(ell.vals), jnp.asarray(vecs), 1.0, max_iter,
        row_mask=jnp.asarray(mask_b), docs_chunk=docs_chunk))
    assert np.all(lb_doc <= d * (1 + RTOL) + ATOL), \
        f"doc-side bound exceeds engine output by {np.max(lb_doc - d)}"


def test_lc_impls_agree():
    """LC fused == kernel == chunked == the dense ref oracle."""
    from repro.kernels import ops, ref
    sel_b, _, mask_b, ell, vecs = _problem(seed=17)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    m_pad = assemble_m_stripes(sel_b, mask_b, jnp.asarray(vecs),
                               rows_bucket=8)
    minm = min_cost_vectors(m_pad)
    lb = np.asarray(lc_rwmd_bound_batch(minm, cols, vals))
    lb_c = np.asarray(lc_rwmd_bound_batch(minm, cols, vals, docs_chunk=7))
    lb_k = np.asarray(ops.lc_rwmd_bound_batch(minm, cols, vals))
    lb_r = np.asarray(ref.lc_rwmd_bound_batch(minm, cols, vals))
    np.testing.assert_array_equal(lb, lb_c)
    np.testing.assert_allclose(lb_k, lb_r, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(lb, lb_r, rtol=1e-6, atol=1e-7)


def test_cascade_pads_and_empties_inert():
    """Filler queries and empty docs score exactly 0 in EVERY tier -- the
    engine's distance for both, so a 0 bound can never prune them."""
    sel_b, r_b, mask_b, ell, vecs = _problem(seed=23, n=12)
    sel_f = np.concatenate([sel_b, np.zeros((1,) + sel_b.shape[1:],
                                            sel_b.dtype)])
    r_f = np.concatenate([r_b, np.zeros((1,) + r_b.shape[1:], r_b.dtype)])
    mask_f = np.concatenate([mask_b, np.zeros((1,) + mask_b.shape[1:],
                                              mask_b.dtype)])
    n, nnz = ell.cols.shape
    cols_e = np.concatenate(
        [ell.cols, np.full((1, nnz), ell.num_vocab, ell.cols.dtype)])
    vals_e = np.concatenate([ell.vals, np.zeros((1, nnz), ell.vals.dtype)])
    ell_e = type(ell)(cols=cols_e, vals=vals_e, num_vocab=ell.num_vocab)
    lb0, lb_lc, lb_doc = _tier_bounds(sel_f, r_f, mask_f, ell_e, vecs)
    for lb in (lb0, lb_lc, lb_doc):
        assert np.all(lb[-1] == 0.0)        # filler query row
        assert np.all(lb[:, -1] == 0.0)     # empty doc column


def test_tier0_zero_on_isotropic_positive_on_clustered():
    """Tier-0 is geometry: on isotropic random embeddings the centroid
    bound collapses to ~0 (m*R swamps ||g - m z||; why the random-corpus
    benches report centroid=0.00), while separated query/corpus clusters
    make it strictly positive on every real (query, doc) pair."""
    # clustered: query words hug the origin, doc words sit 10 sigma away
    rng = np.random.default_rng(29)
    v, w, nq = 64, 8, 12
    vecs = np.empty((v, w), np.float32)
    vecs[:nq] = 0.05 * rng.normal(size=(nq, w))
    far = rng.normal(size=(v - nq, w))
    far /= np.linalg.norm(far, axis=1, keepdims=True)
    vecs[nq:] = 10.0 * far + 0.05 * rng.normal(size=(v - nq, w))
    c = np.zeros((v, 6), np.float32)
    for j in range(6):
        widx = nq + rng.choice(v - nq, 5, replace=False)
        c[widx, j] = rng.random(5).astype(np.float32)
        c[:, j] /= c[:, j].sum()
    ell = ell_from_dense(c)
    rs = []
    for i in range(2):
        r = np.zeros(v, np.float32)
        idx = rng.choice(nq, 4, replace=False)
        r[idx] = rng.random(4).astype(np.float32) + 0.1
        r /= r.sum()
        rs.append(r)
    sels, rsels = zip(*[select_query(r) for r in rs])
    sel_b, r_b, mask_b = pad_query_batch(sels, rsels, 8)
    lb0, lb_lc, _ = _tier_bounds(sel_b, r_b, mask_b, ell, vecs)
    assert np.all(lb0[:2] > 1.0)                   # bites hard
    assert np.all(lb0 <= lb_lc * (1 + RTOL) + ATOL)  # still sound
    # isotropic, bench-like corpus (many words per doc): the query radius
    # R swamps the centroid gap and the relu clamps the whole screen to 0
    data = make_corpus(vocab_size=256, embed_dim=32, num_docs=16,
                       num_queries=0, query_words=11, mean_words=30.0,
                       seed=31)
    qs = _queries(256, 2, seed=31)
    sels, rsels = zip(*[select_query(r) for r in qs])
    sel_i, r_i, mask_i = pad_query_batch(sels, rsels, 16)
    lb0_iso, _, _ = _tier_bounds(sel_i, r_i, mask_i, data.ell, data.vecs)
    assert float(lb0_iso.max()) == 0.0


# ---------------------------------------------------------------------------
# 2. tier-disable invariance: any tier subset off, identical result bits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg_kw", [
    {"tier0": False},
    {"lc_impl": None},
    {"tier2_cap": 0},
    {"tier0": False, "lc_impl": None, "tier2_cap": 0},   # no pruning at all
    {"lc_impl": "kernel"},
    {"tier2_cap": 8},
])
def test_tier_toggle_bitwise_invariant(cfg_kw):
    """Disabling or swapping tiers changes how much is pruned, never the
    returned bits: every config equals the default cascade AND the
    exhaustive scan."""
    base = _service(seed=37, docs=64, prune_chunk=16)
    qs = _queries(512, 3, seed=37)
    idx_b, d_b = base.top_k_batch(qs, 5, prune=True)
    idx_s, d_s = base.top_k_scan_batch(qs, 5)
    np.testing.assert_array_equal(idx_b, idx_s)
    np.testing.assert_array_equal(d_b, d_s)
    svc = _service(seed=37, docs=64, prune_chunk=16, **cfg_kw)
    idx_t, d_t = svc.top_k_batch(qs, 5, prune=True)
    np.testing.assert_array_equal(idx_t, idx_b)
    np.testing.assert_array_equal(d_t, d_b)
    if cfg_kw.get("tier0") is False and cfg_kw.get("lc_impl", "x") is None \
            and cfg_kw.get("tier2_cap") == 0:
        # all tiers off: zero bounds prune nothing, the scan in disguise
        assert svc.last_prune_stats["solves_avoided"] == 0.0


def test_tier_funnel_stats_shape():
    """last_prune_stats["tiers"] reports the per-tier funnel: one entry per
    enabled tier, cumulative avoidance monotone, final cumulative equal to
    the headline solves_avoided."""
    svc = _service(seed=41, docs=64, prune_chunk=16)
    qs = _queries(512, 3, seed=41)
    svc.top_k_batch(qs, 5, prune=True)
    ps = svc.last_prune_stats
    tiers = ps["tiers"]
    assert [t["tier"] for t in tiers] == ["centroid", "lc_rwmd", "rwmd"]
    cum = [t["cascade_solves_avoided"] for t in tiers]
    assert all(b >= a for a, b in zip(cum, cum[1:]))    # monotone funnel
    assert all(t["seconds"] >= 0.0 for t in tiers)
    svc2 = _service(seed=41, docs=64, prune_chunk=16, lc_impl=None,
                    tier2_cap=0)
    svc2.top_k_batch(qs, 5, prune=True)
    assert [t["tier"] for t in svc2.last_prune_stats["tiers"]] \
        == ["centroid"]


# ---------------------------------------------------------------------------
# 3. M-cache transparency: on == off bitwise, through evictions
# ---------------------------------------------------------------------------

def _batch(rng, q, v_r, vocab):
    sel = np.zeros((q, v_r), np.int32)
    mask = np.zeros((q, v_r), np.float32)
    for i in range(q):
        n = int(rng.integers(1, v_r + 1))
        sel[i, :n] = rng.choice(vocab, n, replace=False)
        mask[i, :n] = 1.0
    return sel, mask


def test_mcache_stripes_bitwise_equal_recompute_oracle():
    """Random stream with evictions: every M-stripe assembly from the store
    is bitwise equal to the transient recompute (capacity-0) oracle."""
    rng = np.random.default_rng(43)
    vecs = jnp.asarray(rng.normal(size=(96, 8)).astype(np.float32))
    mc = MCache(12, vecs, rows_bucket=4)        # small: forces evictions
    oracle = MCache(0, vecs, rows_bucket=4)
    seen = set()
    for step in range(15):
        sel, mask = _batch(rng, q=int(rng.integers(1, 4)), v_r=5, vocab=96)
        seen.update(np.unique(sel).tolist())
        got, _ = mc.m_stripes_for_batch(sel, mask)
        want, _ = oracle.m_stripes_for_batch(sel, mask)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"step {step}")
    assert len(seen) > mc.capacity              # pressure engaged
    assert mc.stats.evictions > 0
    assert mc.stats.hit_rows > 0
    assert mc.resident <= mc.capacity


def test_service_mcache_on_off_bitwise_with_evictions():
    """Pruned top-k with a tiny M cache (evicting constantly) is bitwise
    identical to use_cache=False and to an mcache-free service, across
    repeat batches (hits) and fresh batches (misses)."""
    svc = _service(seed=47, docs=64, mcache=24, prune_chunk=16)
    svc_off = _service(seed=47, docs=64, mcache=0, prune_chunk=16)
    for s in (47, 48, 47):
        qs = _queries(512, 3, seed=s)
        idx_on, d_on = svc.top_k_batch(qs, 5, prune=True)
        idx_nc, d_nc = svc.top_k_batch(qs, 5, prune=True, use_cache=False)
        idx_off, d_off = svc_off.top_k_batch(qs, 5, prune=True)
        np.testing.assert_array_equal(idx_on, idx_nc)
        np.testing.assert_array_equal(d_on, d_nc)
        np.testing.assert_array_equal(idx_on, idx_off)
        np.testing.assert_array_equal(d_on, d_off)
    assert svc.mcache_stats.hit_rows > 0
    assert svc.mcache_resident <= 24


# ---------------------------------------------------------------------------
# hypothesis generalizations (skipped without the dev extra; CI runs them
# seeded via --hypothesis-seed=0)
# ---------------------------------------------------------------------------

if given is not None:
    _settings = settings(max_examples=15, deadline=None)

    @_settings
    @given(st.integers(0, 10_000), st.integers(1, 12))
    def test_hyp_bound_chain(seed, max_iter):
        sel_b, r_b, mask_b, ell, vecs = _problem(seed=seed)
        lb0, lb_lc, lb_doc = _tier_bounds(sel_b, r_b, mask_b, ell, vecs)
        np.testing.assert_array_equal(lb_lc, lb_doc)
        assert np.all(lb0 <= lb_lc * (1 + RTOL) + ATOL)
        d = np.asarray(sinkhorn_wmd_sparse_batch(
            jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(ell.cols),
            jnp.asarray(ell.vals), jnp.asarray(vecs), 1.0, max_iter,
            row_mask=jnp.asarray(mask_b)))
        assert np.all(lb_doc <= d * (1 + RTOL) + ATOL)

    @_settings
    @given(st.integers(0, 10_000), st.integers(1, 12),
           st.sampled_from([{"tier0": False}, {"lc_impl": None},
                            {"tier2_cap": 0}, {"tier2_cap": 4},
                            {"tier0": False, "lc_impl": None,
                             "tier2_cap": 0}]),
           st.sampled_from([0, 16, 512]))
    def test_hyp_tier_toggle_and_mcache_invariant(seed, k, cfg_kw, mcap):
        svc = _service(seed=seed % 97, docs=48, mcache=mcap,
                       prune_chunk=16, **cfg_kw)
        qs = _queries(512, 2, seed=seed)
        idx_p, d_p = svc.top_k_batch(qs, k, prune=True)
        idx_s, d_s = svc.top_k_scan_batch(qs, k)
        np.testing.assert_array_equal(idx_p, idx_s)
        np.testing.assert_array_equal(d_p, d_s)
