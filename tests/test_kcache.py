"""Cross-query K/KM row cache: exactness under hit/miss/evict sequences.

The cache's contract (core.kcache) is *bitwise* exactness: stripes assembled
from resident rows equal the recompute-from-scratch transient path bit for
bit, for any interleaving of hits, misses, evictions, capacity overflows and
lambda invalidations -- and therefore solver output is identical with the
cache on or off, chunked or not, fixed budget or early exit. A seeded
random-stream test always runs; a hypothesis property test (optional dev
dep) drives broader sequences.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import KCache
from repro.data import make_corpus, zipf_query_stream

V, W, LAMB = 192, 16, 1.0


@pytest.fixture(scope="module")
def vecs():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(V, W)).astype(np.float32))


def _batch(rng, q, v_r, vocab=V):
    """Random (sel_b, mask_b) with per-query padding like pad_query_batch."""
    sel = np.zeros((q, v_r), np.int32)
    mask = np.zeros((q, v_r), np.float32)
    for i in range(q):
        n = int(rng.integers(1, v_r + 1))
        sel[i, :n] = rng.choice(vocab, n, replace=False)
        mask[i, :n] = 1.0
    return sel, mask


def _assert_stripes_equal(got, want, ctx=""):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]),
                                  err_msg=f"K {ctx}")
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]),
                                  err_msg=f"KM {ctx}")


def test_cached_stripes_bitwise_equal_recompute_oracle(vecs):
    """Random stream with evictions: every call's assembled stripes are
    bitwise equal to the transient recompute-from-scratch oracle."""
    rng = np.random.default_rng(1)
    kc = KCache(24, vecs, LAMB, rows_bucket=8)        # small: forces evicts
    oracle = KCache(0, vecs, LAMB, rows_bucket=8)     # capacity 0 = always
    for step in range(12):                            # recompute
        sel, mask = _batch(rng, q=int(rng.integers(1, 5)), v_r=6)
        got = kc.stripes_for_batch(sel, mask)
        want = oracle.stripes_for_batch(sel, mask)
        _assert_stripes_equal(got, want, ctx=f"step {step}")
    assert kc.stats.evictions > 0                     # pressure engaged
    assert kc.stats.hit_rows > 0
    assert kc.resident <= kc.capacity


def test_resident_rows_bitwise_equal_fresh_rows(vecs):
    """Rows sitting in the buffer equal a from-scratch recompute of the same
    word id, bit for bit (the row value is independent of which other ids
    missed alongside it)."""
    rng = np.random.default_rng(2)
    kc = KCache(32, vecs, LAMB, rows_bucket=8)
    oracle = KCache(0, vecs, LAMB, rows_bucket=8)
    for _ in range(4):
        sel, mask = _batch(rng, q=3, v_r=6)
        kc.stripes_for_batch(sel, mask)
    for wid, slot in list(kc._slot_of.items())[:10]:
        sel1 = np.full((1, 1), wid, np.int32)
        k_o, km_o, _ = oracle.stripes_for_batch(sel1, np.ones((1, 1),
                                                             np.float32))
        np.testing.assert_array_equal(np.asarray(kc._k_buf[:, slot]),
                                      np.asarray(k_o[:, 0, 0]), err_msg=str(wid))
        np.testing.assert_array_equal(np.asarray(kc._km_buf[:, slot]),
                                      np.asarray(km_o[:, 0, 0]))


def test_eviction_pressure_capacity_below_unique(vecs):
    """capacity < unique words in the stream: the LRU churns constantly yet
    every assembly stays exact, and the batch's own rows are never evicted
    mid-batch (capacity >= one batch's unique ids is the only requirement)."""
    rng = np.random.default_rng(3)
    kc = KCache(10, vecs, LAMB, rows_bucket=4)
    oracle = KCache(0, vecs, LAMB, rows_bucket=4)
    seen = set()
    for step in range(15):
        sel, mask = _batch(rng, q=2, v_r=5)
        seen.update(np.unique(sel).tolist())
        got = kc.stripes_for_batch(sel, mask)
        want = oracle.stripes_for_batch(sel, mask)
        _assert_stripes_equal(got, want, ctx=f"step {step}")
    assert len(seen) > kc.capacity                    # the premise
    assert kc.stats.evictions > 0
    assert kc.resident <= kc.capacity


def test_capacity_overflow_bypasses_store_exactly(vecs):
    """A batch with more unique ids than capacity takes the transient path
    (info.cached False), still bitwise exact, without corrupting the store."""
    rng = np.random.default_rng(4)
    kc = KCache(8, vecs, LAMB, rows_bucket=4)
    sel_small, mask_small = _batch(rng, q=1, v_r=5)
    kc.stripes_for_batch(sel_small, mask_small)
    resident_before = dict(kc._slot_of)
    sel_big = rng.choice(V, (2, 8), replace=False).astype(np.int32)
    mask_big = np.ones((2, 8), np.float32)
    got = kc.stripes_for_batch(sel_big, mask_big)
    assert got[2]["cached"] is False
    oracle = KCache(0, vecs, LAMB, rows_bucket=4)
    want = oracle.stripes_for_batch(sel_big, mask_big)
    _assert_stripes_equal(got, want)
    assert kc._slot_of == resident_before             # store untouched


def test_lamb_invalidation(vecs):
    """ensure_lamb drops the store on a lambda change and re-keys: rows under
    the new lambda equal a fresh cache's rows."""
    rng = np.random.default_rng(5)
    kc = KCache(32, vecs, LAMB, rows_bucket=8)
    sel, mask = _batch(rng, q=2, v_r=6)
    kc.stripes_for_batch(sel, mask)
    assert kc.resident > 0
    kc.ensure_lamb(LAMB)                              # no-op at same lambda
    assert kc.stats.invalidations == 0
    kc.ensure_lamb(2.5)
    assert kc.stats.invalidations == 1 and kc.resident == 0
    got = kc.stripes_for_batch(sel, mask)
    fresh = KCache(32, vecs, 2.5, rows_bucket=8)
    want = fresh.stripes_for_batch(sel, mask)
    _assert_stripes_equal(got, want)


def test_failed_miss_compute_does_not_poison_map(vecs, monkeypatch):
    """If the miss compute/scatter raises, no id may be left mapped as
    resident (unsubstantiated residency would serve zero/stale rows later);
    the allocated slots return to the free list and the next call is exact."""
    from repro.core import kcache as kc_mod
    rng = np.random.default_rng(6)
    kc = KCache(32, vecs, LAMB, rows_bucket=8)
    sel0, mask0 = _batch(rng, q=2, v_r=6)
    kc.stripes_for_batch(sel0, mask0)
    resident_before = dict(kc._slot_of)
    free_before = len(kc._free)
    orig = kc_mod._scatter_rows
    monkeypatch.setattr(kc_mod, "_scatter_rows",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("injected")))
    sel1, mask1 = _batch(rng, q=2, v_r=6)
    with pytest.raises(RuntimeError, match="injected"):
        kc.stripes_for_batch(sel1, mask1)
    # no new id became resident, and the slots went back to the free list
    assert set(kc._slot_of) <= set(resident_before)
    assert len(kc._free) >= free_before
    monkeypatch.setattr(kc_mod, "_scatter_rows", orig)
    got = kc.stripes_for_batch(sel1, mask1)
    want = KCache(0, vecs, LAMB, rows_bucket=8).stripes_for_batch(sel1, mask1)
    _assert_stripes_equal(got, want)


# ---------------------------------------------------------------------------
# hypothesis property test (optional dev dep, mirrors tests/test_properties)
# ---------------------------------------------------------------------------

def test_random_sequences_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    rng0 = np.random.default_rng(7)
    vecs_h = jnp.asarray(rng0.normal(size=(64, 8)).astype(np.float32))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 30), st.integers(0, 10_000),
           st.integers(1, 5), st.integers(2, 7))
    def prop(capacity, seed, n_batches, v_r):
        rng = np.random.default_rng(seed)
        kc = KCache(capacity, vecs_h, LAMB, rows_bucket=4)
        oracle = KCache(0, vecs_h, LAMB, rows_bucket=4)
        for _ in range(n_batches):
            sel, mask = _batch(rng, q=int(rng.integers(1, 4)), v_r=v_r,
                               vocab=64)
            got = kc.stripes_for_batch(sel, mask)
            want = oracle.stripes_for_batch(sel, mask)
            _assert_stripes_equal(got, want)
            assert kc.resident <= kc.capacity

    prop()


# ---------------------------------------------------------------------------
# Service-level: cache on/off bitwise through the full solver
# ---------------------------------------------------------------------------

def _service(**kw):
    from repro.configs import sinkhorn_wmd as wmd_cfg
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = wmd_cfg.smoke_config()
    data = make_corpus(vocab_size=cfg.vocab_size, embed_dim=cfg.embed_dim,
                       num_docs=cfg.num_docs, num_queries=5,
                       query_words=cfg.v_r - 2, seed=11)
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      **kw), data


@pytest.mark.parametrize("engine", [{"docs_chunk": 0},
                                    {"docs_chunk": 24},
                                    {"docs_chunk": 0, "tol": 1e-3}],
                         ids=["unchunked", "solve_chunks", "early_exit"])
def test_service_cache_on_off_bitwise(engine):
    """query_batch with the cache enabled is bitwise identical to the
    cache-off path, unchunked, chunked over documents and under early
    exit, including after evictions (capacity is tiny) and repeat batches
    (hits)."""
    svc, data = _service(cache_capacity=24, cache_rows_bucket=8, **engine)
    for queries in (data.queries[:3], data.queries[1:5], data.queries[:3]):
        on = svc.query_batch(queries)
        off = svc.query_batch(queries, use_cache=False)
        np.testing.assert_array_equal(on, off)
    assert svc.cache_stats.hit_rows > 0


def test_service_cache_matches_sequential_and_stats():
    """Cached batched results match the sequential oracle numerically, and
    the service exposes the phase split + hit-rate stats the bench records."""
    svc, data = _service(cache_capacity=64, cache_rows_bucket=8)
    batch = svc.query_batch(data.queries)
    seq = svc.query_batch_sequential(data.queries)
    err = np.abs(batch - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err
    again = svc.query_batch(data.queries)             # all-hit repeat
    np.testing.assert_array_equal(batch, again)
    st = svc.last_batch_stats
    assert st["hit_rate"] == 1.0 and st["cached"] is True
    assert st["precompute_s"] > 0 and st["solve_s"] > 0
    assert svc.cache_stats.lookups >= 2


def test_service_lamb_change_invalidates_cache():
    """Swapping cfg.lamb between calls re-keys the store (lambda-
    invalidation) and produces the new-lambda answer, bitwise equal to the
    cache-off path under the same service -- and the per-query engine
    (query / the sequential oracle) follows the new lambda too, so the
    service never serves mixed-lambda answers."""
    svc, data = _service(cache_capacity=64, cache_rows_bucket=8)
    before = svc.query_batch(data.queries[:2])
    assert svc.cache_resident > 0
    svc.cfg = dataclasses.replace(svc.cfg, lamb=2.0)
    on = svc.query_batch(data.queries[:2])
    assert svc.cache_stats.invalidations == 1
    assert svc._kcache.lamb == 2.0
    assert np.abs(on - before).max() > 0      # lambda actually changed
    off = svc.query_batch(data.queries[:2], use_cache=False)
    np.testing.assert_array_equal(on, off)
    seq = svc.query_batch_sequential(data.queries[:2])
    err = np.abs(on - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err                    # per-query engine re-keyed too


def test_top_k_batch_matches_argsort_oracle():
    svc, data = _service(cache_capacity=64)
    d = svc.query_batch(data.queries)
    idx, dist = svc.top_k_batch(data.queries, k=4)
    ref = np.argsort(d, axis=-1)[:, :4]
    np.testing.assert_array_equal(idx, ref)
    np.testing.assert_array_equal(dist, np.take_along_axis(d, ref, axis=-1))
    i1, d1 = svc.top_k(data.queries[0], k=3)
    np.testing.assert_array_equal(i1, np.argsort(svc.query(
        data.queries[0]))[:3])
    assert d1.shape == (3,)
    # k > N degrades to a full sort, not an error
    i_all, _ = svc.top_k(data.queries[0], k=10_000)
    assert i_all.shape == (svc.ell.num_docs,)


def test_zipf_query_stream_seeded_and_skewed():
    """The stream is reproducible per seed and actually skewed: two seeds
    agree iff equal, and a steeper exponent concentrates ids."""
    s1 = zipf_query_stream(vocab_size=256, query_words=8, seed=3)
    s2 = zipf_query_stream(vocab_size=256, query_words=8, seed=3)
    a = [next(s1) for _ in range(4)]
    b = [next(s2) for _ in range(4)]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert (x > 0).sum() == 8
    flat = zipf_query_stream(vocab_size=256, query_words=8, s=1.05, seed=0)
    steep = zipf_query_stream(vocab_size=256, query_words=8, s=2.0, seed=0)
    ids_of = lambda s: {int(i) for _ in range(12)        # noqa: E731
                        for i in np.nonzero(next(s))[0]}
    assert len(ids_of(steep)) < len(ids_of(flat))


def test_distributed_cache_stripes_match_single_chip():
    """Cache-assembled stripes through build_wmd_batch_fn_stripes on a
    (2, 2) mesh == per-query single-chip solves, and cache on/off stays
    bitwise on the mesh (subprocess: needs a forced device count)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import select_query, sinkhorn_wmd_sparse, ell_from_dense
from repro.configs.sinkhorn_wmd import WMDConfig
from repro.launch.mesh import make_mesh
from repro.serving import WMDService

mesh = make_mesh((2, 2), ("data", "model"))
rng = np.random.default_rng(3)
V, w, N = 256, 32, 64
vecs = rng.normal(size=(V, w)).astype(np.float32)
c = np.zeros((V, N), np.float32)
for j in range(N):
    widx = rng.choice(V, rng.integers(3, 15), replace=False)
    c[widx, j] = rng.random(widx.size).astype(np.float32)
    c[:, j] /= c[:, j].sum()
ell = ell_from_dense(c)
queries = []
for vrn in (5, 9, 14):
    r = np.zeros(V, np.float32)
    idx = rng.choice(V, vrn, replace=False)
    r[idx] = rng.random(vrn).astype(np.float32); r /= r.sum()
    queries.append(r)
cfg = WMDConfig(name="t", vocab_size=V, embed_dim=w, num_docs=N,
                nnz_max=ell.nnz_max, v_r=16, lamb=1.0, max_iter=12)
svc = WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell,
                 cache_capacity=48, cache_rows_bucket=8)
got = svc.query_batch(queries)
ref = np.stack([np.asarray(sinkhorn_wmd_sparse(
    s, r, jnp.asarray(ell.cols), jnp.asarray(ell.vals), vecs, 1.0, 12))
    for s, r in [select_query(q) for q in queries]])
err = np.abs(got - ref).max() / np.abs(ref).max()
assert err < 1e-4, err
again = svc.query_batch(queries)          # warm: hits
off = svc.query_batch(queries, use_cache=False)
assert np.array_equal(got, again) and np.array_equal(got, off)
assert svc.cache_stats.hit_rows > 0
print("DIST_KCACHE_OK", err)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "DIST_KCACHE_OK" in out.stdout
