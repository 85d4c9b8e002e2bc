"""The batched engine: every batched solve (stripes, mesh, converged;
chunked or not; fixed budget or early exit) against the dense reference,
pad rows/slots and filler queries inert, early-exit convergence == fixed
budget, the distributed convergence vote == single-host masking, and
every service route -- Q = 1 included -- dispatching the batched
program."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ell_from_dense, pad_k, precompute_batch, select_query,
                        sinkhorn_wmd_converged_batch, sinkhorn_wmd_dense,
                        sinkhorn_wmd_sparse_batch)
from repro.core.distributed import pad_query_batch

LAMB, ITERS = 1.0, 12
# a batched solve against the dense reference (`core.sinkhorn`), relative
# to the largest distance: the two sum in different orders over the
# Sinkhorn iterations. Early exit at tol 1e-3 stays inside it too.
REL_ERR = 1e-4


@pytest.fixture(scope="module")
def batch_problem():
    """Corpus (non-dividing N = 45) + Q=4 mixed-v_r queries padded to 16."""
    rng = np.random.default_rng(11)
    v, w, n = 256, 24, 45
    vecs = rng.normal(size=(v, w)).astype(np.float32)
    c = np.zeros((v, n), np.float32)
    for j in range(n):
        widx = rng.choice(v, rng.integers(4, 18), replace=False)
        c[widx, j] = rng.random(widx.size).astype(np.float32)
        c[:, j] /= c[:, j].sum()
    ell = ell_from_dense(c)
    queries = []
    for vr in (4, 7, 11, 16):
        r = np.zeros(v, np.float32)
        idx = rng.choice(v, vr, replace=False)
        r[idx] = rng.random(vr).astype(np.float32)
        r /= r.sum()
        queries.append(r)
    sels, rsels = zip(*[select_query(r) for r in queries])
    sel_b, r_b, mask_b = pad_query_batch(sels, rsels, 16)
    return {"vecs": vecs, "ell": ell, "sels": sels, "rsels": rsels,
            "sel_b": sel_b, "r_b": r_b, "mask_b": mask_b,
            "cols": jnp.asarray(ell.cols), "vals": jnp.asarray(ell.vals)}


def _solver_args(p):
    return (jnp.asarray(p["sel_b"]), jnp.asarray(p["r_b"]), p["cols"],
            p["vals"], jnp.asarray(p["vecs"]), LAMB, ITERS)


# ---------------------------------------------------------------------------
# Pad rows, pad slots and filler queries
# ---------------------------------------------------------------------------

def test_batched_kernel_pad_rows_and_slots_inert(batch_problem):
    """Pad-slot retargeting is bit-identical through the fused batched
    solver (val == 0 gates the contractions), and an all-pad filler stripe
    solves to exactly zero."""
    p = batch_problem
    kw = dict(row_mask=jnp.asarray(p["mask_b"]))
    base = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw))
    args = list(_solver_args(p))
    args[2] = jnp.where(p["vals"] == 0.0, 0, p["cols"])
    got = np.asarray(sinkhorn_wmd_sparse_batch(*args, **kw))
    np.testing.assert_array_equal(got, base)
    # all-pad filler query (the service's Q-bucket filler)
    wmd = sinkhorn_wmd_sparse_batch(
        jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.float32),
        p["cols"], p["vals"], jnp.asarray(p["vecs"]), LAMB, ITERS,
        row_mask=jnp.zeros((1, 16), jnp.float32))
    np.testing.assert_array_equal(np.asarray(wmd), 0.0)


# ---------------------------------------------------------------------------
# Doc-chunked (cache-blocked) solve
# ---------------------------------------------------------------------------

def test_chunked_solver_matches_unchunked(batch_problem):
    """Full batched solver: chunked == unchunked to fp32 tolerance (whole-
    program XLA fusion may reassociate neighbouring ops per chunk shape)."""
    p = batch_problem
    kw = dict(row_mask=jnp.asarray(p["mask_b"]))
    base = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw))
    for dc in (8, 16, 45):
        got = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw,
                                                   docs_chunk=dc))
        err = np.abs(got - base).max() / np.abs(base).max()
        assert err < 1e-5, (dc, err)


# ---------------------------------------------------------------------------
# Every batched solve against the dense reference
# ---------------------------------------------------------------------------

def _with_filler(p):
    """The bucket plus one all-pad filler query (the service's Q-bucket
    filler): pad query rows, a pad query and ELL pad slots all present."""
    q_pad = lambda a, fill: np.concatenate(
        [a, np.full((1, a.shape[1]), fill, a.dtype)])
    return q_pad(p["sel_b"], 0), q_pad(p["r_b"], 1.0), q_pad(p["mask_b"], 0.0)


def _stripes_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk):
    """`sparse_sinkhorn._solve_batch_stripes` (its chunk loop unrolled)."""
    from repro.core import sparse_sinkhorn as ss
    pre = precompute_batch(jnp.asarray(sel_b), jnp.asarray(r_b),
                           jnp.asarray(p["vecs"]), LAMB,
                           row_mask=jnp.asarray(mask_b))
    fn = jax.jit(functools.partial(ss._solve_batch_stripes, max_iter=ITERS,
                                   docs_chunk=docs_chunk, tol=tol))
    return np.asarray(fn(pad_k(pre.K), pad_k(pre.KM), pre.r, p["cols"],
                         p["vals"]))


def _mesh_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk):
    """`distributed.build_wmd_batch_fn` on a (1, 1) mesh (its chunk loop
    rolled)."""
    from repro.core import rebucket_for_vocab_shards
    from repro.core.distributed import build_wmd_batch_fn, shard_wmd_inputs
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rb = rebucket_for_vocab_shards(p["ell"], 1)
    fn = build_wmd_batch_fn(mesh, lamb=LAMB, max_iter=ITERS, tol=tol,
                            docs_chunk=docs_chunk)
    return np.asarray(fn(jnp.asarray(p["vecs"][sel_b]), jnp.asarray(r_b),
                         jnp.asarray(mask_b),
                         *shard_wmd_inputs(mesh, p["vecs"], rb.cols,
                                           rb.vals)))


def _converged_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk):
    """`convergence.sinkhorn_wmd_converged_batch` (unchunked only)."""
    assert docs_chunk is None
    return np.asarray(sinkhorn_wmd_converged_batch(
        jnp.asarray(sel_b), jnp.asarray(r_b), p["cols"], p["vals"],
        jnp.asarray(p["vecs"]), LAMB, ITERS, tol=tol,
        row_mask=jnp.asarray(mask_b)).wmd)


def _dense_reference(p) -> np.ndarray:
    """(Q, N) `core.sinkhorn.sinkhorn_wmd_dense` of the bucket's real
    queries, fixed budget, float32 at highest matmul precision."""
    c = jnp.asarray(p["ell"].to_dense())
    with jax.default_matmul_precision("highest"):
        return np.stack([np.asarray(sinkhorn_wmd_dense(
            jnp.asarray(s), jnp.asarray(r), c, jnp.asarray(p["vecs"]), LAMB,
            ITERS)) for s, r in zip(p["sels"], p["rsels"])])


# (core, docs_chunk): N = 45, so 16-doc chunks do not divide it and
# 15-doc chunks do; the converged core is the unchunked oracle
_SOLVE_CASES = [
    pytest.param(solve, dc, id=f"{core}-{cid}")
    for core, solve in (("stripes", _stripes_solve), ("mesh", _mesh_solve))
    for cid, dc in (("unchunked", None), ("chunks16", 16), ("chunks15", 15))
] + [pytest.param(_converged_solve, None, id="converged-unchunked")]



@pytest.mark.parametrize("tol", [0.0, 1e-3], ids=["fixed", "early_exit"])
@pytest.mark.parametrize("solve,docs_chunk", _SOLVE_CASES)
def test_batched_solves_match_reference(batch_problem, solve, docs_chunk,
                                        tol):
    """Each batched solve matches the dense reference, with pad query
    rows, an all-pad filler query (which solves to exactly 0) and ELL pad
    slots present."""
    p = batch_problem
    got = solve(p, *_with_filler(p), tol=tol, docs_chunk=docs_chunk)
    assert got.shape == (5, p["ell"].num_docs)
    np.testing.assert_array_equal(got[-1], 0.0)
    ref = _dense_reference(p)
    err = np.abs(got[:-1] - ref).max() / np.abs(ref).max()
    assert err < REL_ERR, err


@pytest.mark.parametrize("q,v_r,v,n,nnz", [
    (1, 4, 16, 3, 8), (4, 16, 256, 45, 24), (5, 7, 33, 10, 5),
    (8, 32, 300, 17, 144)])
def test_row_table_gather_matches_block(q, v_r, v, n, nnz):
    """The hoisted gather from the (V+1, Q*v_r) row table
    (`gather_k_rows` of `k_row_table`) is bitwise `gather_k_block`'s
    (Q, v_r, N, nnz) block, with pad query rows, an all-pad last query and
    ELL pad slots (col = V, the zero column)."""
    from repro.core import sparse_sinkhorn as ss
    rng = np.random.default_rng(q * 1000 + v_r)
    mask = (rng.random((q, v_r)) < 0.7).astype(np.float32)
    mask[:, 0], mask[-1] = 1.0, 0.0
    k = rng.random((q, v_r, v)).astype(np.float32) * mask[..., None]
    cols = rng.integers(0, v, (n, nnz)).astype(np.int32)
    cols[rng.random((n, nnz)) < 0.3] = v
    cols[0] = v                                   # an empty document
    k_pad, cols = pad_k(jnp.asarray(k)), jnp.asarray(cols)
    rows = jax.jit(lambda kp, c: ss.gather_k_rows(ss.k_row_table(kp), c, q))
    block = np.asarray(jax.jit(ss.gather_k_block)(k_pad, cols))
    got = np.asarray(rows(k_pad, cols))
    assert got.shape == block.shape == (q, v_r, n, nnz)
    np.testing.assert_array_equal(got, block)
    np.testing.assert_array_equal(got[-1], 0.0)
    np.testing.assert_array_equal(got[:, :, 0], 0.0)


# ---------------------------------------------------------------------------
# Early-exit convergence
# ---------------------------------------------------------------------------

def test_early_exit_full_budget_is_exact(batch_problem):
    """When the tolerance forces full iterations (tol = 0), the early-exit
    loop returns the fixed-max_iter solver's result exactly and the per-query
    counters show every iteration executed."""
    p = batch_problem
    kw = dict(row_mask=jnp.asarray(p["mask_b"]))
    fixed = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw))
    out = sinkhorn_wmd_converged_batch(*_solver_args(p), tol=0.0, **kw)
    np.testing.assert_array_equal(np.asarray(out.wmd), fixed)
    np.testing.assert_array_equal(np.asarray(out.n_iter), ITERS)


def test_early_exit_fewer_iterations_same_result(batch_problem):
    """Easy-convergence workload: the early-exit solver executes strictly
    fewer iterations (per the counter) yet matches the fixed-budget solve
    to fp32 tolerance."""
    p = batch_problem
    budget = 300
    kw = dict(row_mask=jnp.asarray(p["mask_b"]))
    args = _solver_args(p)[:-1] + (budget,)
    fixed = np.asarray(sinkhorn_wmd_sparse_batch(*args, **kw))
    out = sinkhorn_wmd_converged_batch(*args, tol=1e-5, **kw)
    n_iter = np.asarray(out.n_iter)
    assert n_iter.max() < budget, n_iter
    err = (np.abs(np.asarray(out.wmd) - fixed).max() / np.abs(fixed).max())
    assert err < 1e-4, err
    # explicit tol through the jitted fixed-budget solver (regression: tol
    # is branched on in Python, so it must be a static argument)
    early = np.asarray(sinkhorn_wmd_sparse_batch(*args, **kw, tol=1e-5,
                                                 docs_chunk=16))
    err2 = np.abs(early - fixed).max() / np.abs(fixed).max()
    assert err2 < 1e-4, err2


# ---------------------------------------------------------------------------
# Distributed convergence vote
# ---------------------------------------------------------------------------

def test_distributed_vote_matches_single_host_masking():
    """build_wmd_batch_fn(tol>0) on a (2, 2) mesh: unchunked, per-query
    n_iter from the all-shards vote is within one iteration of single-host
    sinkhorn_wmd_converged_batch, and the distances agree; chunked over
    documents, the distances agree too (subprocess: needs a forced device
    count).

    Why not equal: the vote compares a relative iterate delta |dx|/|x| with
    tol, and dx is a difference of two nearly equal f32 iterates, so the
    delta carries ~eps/tol (about 1%) of relative rounding. The sharded
    solve sums x over vocab shards through a psum, a different f32 order
    than the single-host sum, so when a query's delta lands within that
    noise of tol the two runs stop one iteration apart (seen: delta
    1.004e-5 single-host vs 9.83e-6 sharded at iteration 57, tol 1e-5)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import (select_query, ell_from_dense,
                        rebucket_for_vocab_shards,
                        sinkhorn_wmd_converged_batch)
from repro.core.distributed import (build_wmd_batch_fn, pad_query_batch,
                                    shard_wmd_inputs)
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "model"))
rng = np.random.default_rng(5)
V, w, N = 256, 32, 64
vecs = rng.normal(size=(V, w)).astype(np.float32)
c = np.zeros((V, N), np.float32)
for j in range(N):
    widx = rng.choice(V, rng.integers(3, 17), replace=False)
    c[widx, j] = rng.random(widx.size).astype(np.float32)
    c[:, j] /= c[:, j].sum()
ell = ell_from_dense(c)
queries = []
for vrn in (5, 9, 14):
    r = np.zeros(V, np.float32)
    idx = rng.choice(V, vrn, replace=False)
    r[idx] = rng.random(vrn).astype(np.float32); r /= r.sum()
    queries.append(r)
sels, rsels = zip(*[select_query(r) for r in queries])
sel_b, r_b, mask_b = pad_query_batch(sels, rsels, 16)
ref = sinkhorn_wmd_converged_batch(
    jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(ell.cols),
    jnp.asarray(ell.vals), vecs, 1.0, 400, tol=1e-5,
    row_mask=jnp.asarray(mask_b))
assert int(np.asarray(ref.n_iter).max()) < 400   # masking engaged
rb = rebucket_for_vocab_shards(ell, 2)
fn = build_wmd_batch_fn(mesh, lamb=1.0, max_iter=400, tol=1e-5,
                        with_info=True)
vd, cd, vld = shard_wmd_inputs(mesh, vecs, rb.cols, rb.vals)
wmd, n_iter, delta = fn(jnp.asarray(vecs[sel_b]), jnp.asarray(r_b),
                        jnp.asarray(mask_b), vd, cd, vld)
gap = np.abs(np.asarray(n_iter) - np.asarray(ref.n_iter))
assert gap.max() <= 1, (np.asarray(n_iter), np.asarray(ref.n_iter))
err = (np.abs(np.asarray(wmd) - np.asarray(ref.wmd)).max()
       / np.abs(np.asarray(ref.wmd)).max())
assert err < 1e-4, err
# solve chunking (per-chunk freeze): same distances, and no block runs
# longer than the slowest global query
fn2 = build_wmd_batch_fn(mesh, lamb=1.0, max_iter=400, tol=1e-5,
                         docs_chunk=16, with_info=True)
wmd2, n_iter2, _ = fn2(jnp.asarray(vecs[sel_b]), jnp.asarray(r_b),
                       jnp.asarray(mask_b), vd, cd, vld)
err2 = (np.abs(np.asarray(wmd2) - np.asarray(ref.wmd)).max()
        / np.abs(np.asarray(ref.wmd)).max())
assert err2 < 1e-4, err2
assert np.asarray(n_iter2).max() <= np.asarray(ref.n_iter).max()
print("DIST_VOTE_OK", err, err2)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "DIST_VOTE_OK" in out.stdout


# ---------------------------------------------------------------------------
# Service plumbing
# ---------------------------------------------------------------------------

def _smoke_service(**kw):
    from repro.configs import sinkhorn_wmd as wmd_cfg
    from repro.data import make_corpus
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = wmd_cfg.smoke_config()
    data = make_corpus(vocab_size=cfg.vocab_size, embed_dim=cfg.embed_dim,
                       num_docs=cfg.num_docs, num_queries=3,
                       query_words=cfg.v_r - 2, seed=2)
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      **kw), data


def _route_service(route, tmp_path):
    """The smoke service on ``route``: "legacy_fused" (no K cache),
    "stripes" (K cache on) or "live" (a live corpus of the same docs)."""
    kw = {} if route == "legacy_fused" else {"cache_capacity": 64}
    svc, data = _smoke_service(**kw)
    if route == "live":
        from repro.core import formats as fmt
        from repro.data.live_corpus import LiveCorpus
        from repro.serving import WMDService
        lc = LiveCorpus(str(tmp_path), svc.vecs.shape[0], normalize=False)
        lc.add_docs(range(svc.ell.num_docs), fmt.doc_lists_from_ell(svc.ell))
        svc = WMDService.from_live(svc.mesh, svc.cfg, svc.vecs, lc, **kw)
    return svc, data


def _service_reference(svc, data, r) -> np.ndarray:
    """`core.sinkhorn.sinkhorn_wmd_dense` of one query against the smoke
    corpus (a live service's docs in ascending id order), at highest
    matmul precision."""
    s, rr = select_query(r)
    with jax.default_matmul_precision("highest"):
        return np.asarray(sinkhorn_wmd_dense(
            jnp.asarray(s), jnp.asarray(rr), jnp.asarray(data.ell.to_dense()),
            jnp.asarray(svc.vecs), svc.cfg.lamb, svc.cfg.max_iter))


@pytest.mark.parametrize("route", ["legacy_fused", "stripes", "live"])
def test_query_runs_the_batched_engine(route, tmp_path):
    """``query(r)`` is the batched engine at Q = 1 on every route: it
    builds the route's batched program and no other, sweeps one v_r-row
    query bucket, and equals the dense reference."""
    svc, data = _route_service(route, tmp_path)
    r = data.queries[0]
    got = svc.query(r)
    built = (svc._batch_fns, svc._stripe_fns)
    assert [len(f) for f in built] == ([1, 0] if route == "legacy_fused"
                                       else [0, 1])
    snap = svc.metrics.snapshot()
    words = int(np.count_nonzero(r))
    assert snap["wmd_query_slots_total{kind=real}"] == words
    assert snap["wmd_query_slots_total{kind=pad}"] == svc.cfg.v_r - words
    ref = _service_reference(svc, data, r)
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert err < REL_ERR, err
    np.testing.assert_array_equal(svc.query_batch([r])[0], got)


@pytest.mark.parametrize("tol", [0.0, 1e-3], ids=["fixed", "early_exit"])
def test_q1_matches_dense_reference(tol):
    """A one-query batch equals the dense reference, on the fixed budget
    and under early exit (the while-loop program at Q = 1)."""
    svc, data = _smoke_service(tol=tol)
    for r in data.queries:
        got = svc.query_batch([r])
        assert got.shape == (1, svc.ell.num_docs)
        ref = _service_reference(svc, data, r)
        err = np.abs(got[0] - ref).max() / np.abs(ref).max()
        assert err < REL_ERR, err
    assert list(svc._batch_fns) == [(None, tol, svc.cfg.lamb)]


def test_service_forwards_impl_and_chunk():
    """The docs_chunk and tol fields and a per-call docs_chunk override
    reach the engine: every combination, Q = 1 included, matches the
    per-query oracle."""
    svc, data = _smoke_service(docs_chunk=16, tol=1e-6)
    seq = svc.query_batch_sequential(data.queries)
    got = svc.query_batch(data.queries)
    err = np.abs(got - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err
    # per-call docs_chunk override (0 = explicitly unchunked)
    got = svc.query_batch(data.queries, docs_chunk=0)
    err = np.abs(got - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err
    assert {k[0] for k in svc._batch_fns} == {16, None}
    lone = [data.queries[0]]
    got1 = svc.query_batch(lone, docs_chunk=0)
    assert got1.shape == (1, seq.shape[1])
    err1 = np.abs(got1 - seq[:1]).max() / np.abs(seq[:1]).max()
    assert err1 < 1e-4, err1
