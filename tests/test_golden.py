"""Golden regression table: one tiny deterministic corpus, every engine
route, checked-in expected outputs asserted BITWISE.

The cross-engine tests (batched == sequential, cache on == off, pruned ==
scan, kernel == oracle, live pruned == live scan) catch routes drifting from
*each other*; what they
cannot catch is every route drifting *together* -- a silent change to the
shared math (precompute, safe_recip, iteration order) would ship unnoticed.
This table pins the absolute values: any PR that changes a single bit of
any route's output on the fixed corpus fails exactly one obvious test.

Routes pinned: dense oracle, sparse single-query (fused / unfused /
kernel), batched (fused / chunked), the stripes+K-cache engine,
the service's legacy engine, the RWMD bound prefilter, and the pruned
top-k (ids + distances).

Regeneration (after an *intentional* numerical change, or a jax/XLA
upgrade that re-tiles a kernel -- bitwise pins are per-toolchain):

    PYTHONPATH=src python tests/test_golden.py --regen

then eyeball the diff of `np.load` summaries and commit the new npz with
the justification in the PR description.
"""
import functools
import os

import jax.numpy as jnp
import numpy as np

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "wmd_golden.npz")

LAMB, MAX_ITER, V_R_BUCKET, TOP_K = 1.0, 8, 12, 5


@functools.lru_cache(maxsize=1)
def _corpus():
    from repro.core import ell_from_dense
    rng = np.random.default_rng(1234)
    v, w, n, q = 96, 8, 24, 3
    vecs = rng.normal(size=(v, w)).astype(np.float32)
    c = np.zeros((v, n), np.float32)
    for j in range(n):
        widx = rng.choice(v, rng.integers(3, 10), replace=False)
        c[widx, j] = rng.random(widx.size).astype(np.float32)
        c[:, j] /= c[:, j].sum()
    rs = []
    for i in range(q):
        r = np.zeros(v, np.float32)
        idx = rng.choice(v, 5 + 2 * i, replace=False)   # mixed v_r
        r[idx] = rng.random(idx.size).astype(np.float32) + 0.1
        r /= r.sum()
        rs.append(r)
    return vecs, ell_from_dense(c), rs


@functools.lru_cache(maxsize=1)
def _routes() -> dict:
    """Recompute every pinned route on the fixed corpus."""
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.core import (assemble_m_stripes, rwmd_bound_batch,
                            select_query, sinkhorn_wmd_dense,
                            sinkhorn_wmd_sparse, sinkhorn_wmd_sparse_batch)
    from repro.core.distributed import pad_query_batch
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService

    vecs, ell, rs = _corpus()
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    vecs_j = jnp.asarray(vecs)
    c_dense = jnp.asarray(ell.to_dense())
    out: dict = {}

    sels, rsels = zip(*[select_query(r) for r in rs])
    out["dense"] = np.stack([
        np.asarray(sinkhorn_wmd_dense(jnp.asarray(s), jnp.asarray(rr),
                                      c_dense, vecs_j, LAMB, MAX_ITER))
        for s, rr in zip(sels, rsels)])
    for impl in ("fused", "unfused", "kernel"):
        out[f"single_{impl}"] = np.stack([
            np.asarray(sinkhorn_wmd_sparse(jnp.asarray(s), jnp.asarray(rr),
                                           cols, vals, vecs_j, LAMB,
                                           MAX_ITER, impl=impl))
            for s, rr in zip(sels, rsels)])

    sel_b, r_b, mask_b = pad_query_batch(sels, rsels, V_R_BUCKET)
    batch_args = (jnp.asarray(sel_b), jnp.asarray(r_b), cols, vals, vecs_j,
                  LAMB, MAX_ITER)
    mask_j = jnp.asarray(mask_b)
    out["batched_fused"] = np.asarray(
        sinkhorn_wmd_sparse_batch(*batch_args, row_mask=mask_j))
    out["batched_chunked"] = np.asarray(
        sinkhorn_wmd_sparse_batch(*batch_args, row_mask=mask_j,
                                  docs_chunk=7))

    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    out["rwmd_bound"] = np.asarray(rwmd_bound_batch(m_pad, cols, vals))

    cfg = WMDConfig(name="golden", vocab_size=vecs.shape[0], embed_dim=8,
                    num_docs=ell.num_docs, nnz_max=ell.nnz_max,
                    v_r=V_R_BUCKET, lamb=LAMB, max_iter=MAX_ITER)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell,
                     cache_capacity=64, prune_chunk=8,
                     bound_docs_chunk=None)
    out["service_stripes"] = svc.query_batch(rs)              # K-cache route
    out["service_transient"] = svc.query_batch(rs, use_cache=False)
    idx_p, d_p = svc.top_k_batch(rs, TOP_K, prune=True)
    out["pruned_topk_idx"] = idx_p
    out["pruned_topk_dist"] = d_p
    idx_s, d_s = svc.top_k_scan_batch(rs, TOP_K)
    out["scan_topk_idx"] = idx_s
    out["scan_topk_dist"] = d_s

    svc_legacy = WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell)
    out["service_legacy"] = svc_legacy.query_batch(rs)

    # live-corpus routes: the same docs through a WAL-backed LiveCorpus,
    # three assembly histories that must all land on identical bits --
    # the incremental == batch contract pinned absolutely. normalize=False
    # because doc_lists_from_ell hands back already-normalized weights.
    import tempfile

    from repro.core import formats as fmt
    from repro.data.live_corpus import LiveCorpus
    from repro.serving.faultinject import CrashInjector, InjectedCrash

    docs = fmt.doc_lists_from_ell(ell)
    v = vecs.shape[0]

    def live_service(lc):
        return WMDService(mesh=mesh, cfg=cfg, vecs=vecs, live=lc,
                          cache_capacity=64, prune_chunk=8,
                          bound_docs_chunk=None)

    # one-shot seeding: every doc in a single durable add
    lc = LiveCorpus(tempfile.mkdtemp(prefix="golden-live1-"), v,
                    normalize=False)
    lc.add_docs(range(len(docs)), docs)
    out["live_oneshot"] = live_service(lc).query_batch(rs)

    # incremental assembly: shuffled adds, a wrong doc corrected by
    # upsert, an extraneous doc removed again, a mid-way compaction
    order = list(range(len(docs)))
    np.random.default_rng(7).shuffle(order)
    lc = LiveCorpus(tempfile.mkdtemp(prefix="golden-live2-"), v,
                    normalize=False)
    lc.add_docs([order[0]], [[(0, 1.0)]])          # wrong content first
    for i in order[: len(order) // 2]:
        lc.add_docs([i], [docs[i]])                # (order[0] corrected)
    lc.add_docs([999], [docs[0]])                  # extraneous doc ...
    lc.compact()
    lc.remove_docs([999])                          # ... tombstoned again
    for i in order[len(order) // 2:]:
        lc.add_docs([i], [docs[i]])
    out["live_incremental"] = live_service(lc).query_batch(rs)

    # crash-recovered: killed inside compaction (pre-rename), reopened
    # from WAL replay, finished, then compacted cleanly
    hook = CrashInjector()
    path = tempfile.mkdtemp(prefix="golden-live3-")
    lc = LiveCorpus(path, v, normalize=False, crash_hook=hook)
    for i in order[:16]:
        lc.add_docs([i], [docs[i]])
    hook.target = hook.count + 2                   # compact.snapshot.tmp
    try:
        lc.compact()
        raise AssertionError("injected crash did not fire")
    except InjectedCrash:
        pass
    lc = LiveCorpus(path, v, normalize=False)      # recover from disk
    for i in order[16:]:
        lc.add_docs([i], [docs[i]])
    lc.add_docs([order[0]], [docs[order[0]]])      # upsert to the delta
    lc.compact()
    lsvc = live_service(lc)
    out["live_recovered"] = lsvc.query_batch(rs)

    # live pruned top-k: cascade over the immutable base segment plus an
    # exact-solved delta doc (added after compaction, so the query_batch
    # routes above keep their bits); scan is its exactness oracle
    lc.add_docs([999], [docs[1]])                  # the delta segment
    idx_lp, d_lp = lsvc.top_k_batch(rs, TOP_K, prune=True)
    out["live_pruned_topk_idx"] = idx_lp
    out["live_pruned_topk_dist"] = d_lp
    idx_ls, d_ls = lsvc.top_k_scan_batch(rs, TOP_K)
    out["live_scan_topk_idx"] = idx_ls
    out["live_scan_topk_dist"] = d_ls
    return out


def test_golden_table_bitwise():
    """Every route must reproduce its checked-in table entry bit for bit.

    A failure here means a PR changed the numerics of that route (fix it
    or, if intentional, regenerate -- see the module docstring)."""
    assert os.path.exists(GOLDEN), \
        "golden table missing -- run: python tests/test_golden.py --regen"
    golden = np.load(GOLDEN)
    routes = _routes()
    assert set(golden.files) == set(routes), \
        (set(golden.files) ^ set(routes))
    for name, got in routes.items():
        np.testing.assert_array_equal(
            got, golden[name],
            err_msg=f"route {name!r} drifted from the golden table")


def test_golden_cross_route_consistency():
    """npz-independent sanity: the routes must agree with each other at
    their contracted strengths (bitwise where contracted, fp32 where not),
    so a stale golden file can never mask a real inter-route break."""
    r = _routes()
    # exactness contracts: bitwise
    np.testing.assert_array_equal(r["service_stripes"],
                                  r["service_transient"])
    np.testing.assert_array_equal(r["pruned_topk_idx"], r["scan_topk_idx"])
    np.testing.assert_array_equal(r["pruned_topk_dist"],
                                  r["scan_topk_dist"])
    # the incremental == batch contract: every live-corpus assembly
    # history lands on the frozen service's exact bits
    np.testing.assert_array_equal(r["live_oneshot"], r["service_stripes"])
    np.testing.assert_array_equal(r["live_incremental"], r["live_oneshot"])
    np.testing.assert_array_equal(r["live_recovered"], r["live_oneshot"])
    # the live pruned path (base cascade + exact delta) == its scan oracle
    np.testing.assert_array_equal(r["live_pruned_topk_idx"],
                                  r["live_scan_topk_idx"])
    np.testing.assert_array_equal(r["live_pruned_topk_dist"],
                                  r["live_scan_topk_dist"])
    # engine-vs-engine: fp32
    np.testing.assert_allclose(r["single_fused"], r["dense"],
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(r["batched_fused"][:3], r["single_fused"],
                               rtol=2e-3, atol=1e-5)
    np.testing.assert_allclose(r["service_stripes"], r["batched_fused"][:3],
                               rtol=2e-3, atol=1e-5)
    # the bound is a bound on every route's distances
    for route in ("single_fused", "single_unfused", "batched_fused"):
        d = r[route][:3] if r[route].shape[0] > 3 else r[route]
        assert np.all(r["rwmd_bound"][:3] <= d * (1 + 1e-5) + 1e-6)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="rewrite the golden table from the current "
                         "toolchain's outputs")
    ap.add_argument("--out", default=GOLDEN,
                    help="regen target path (default: the checked-in "
                         "tests/golden/wmd_golden.npz). CI's freshness "
                         "step regens to a temp path and np.load-compares "
                         "against the checked-in table -- npz zip entries "
                         "carry timestamps, so a byte diff of the files "
                         "is NOT a valid staleness check.")
    args = ap.parse_args()
    if args.regen:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        routes = _routes()
        np.savez(args.out, **routes)
        for name, arr in sorted(routes.items()):
            print(f"{name:24s} {str(arr.shape):12s} "
                  f"sum={float(np.asarray(arr, np.float64).sum()):.6f}")
        print(f"wrote {args.out}")
    else:
        print(__doc__)
