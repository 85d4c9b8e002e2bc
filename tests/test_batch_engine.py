"""Cache-blocked batched engine: batched Pallas kernels == batched jnp
fused (mixed-size padded queries, pad rows/slots inert), doc-chunked
iteration bitwise at the op level, early-exit convergence == fixed budget,
and the distributed convergence vote == single-host masking."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ell_from_dense, pad_k, precompute_batch, select_query,
                        sddmm_spmm_type1_batch, sddmm_spmm_type2_batch,
                        sinkhorn_wmd_converged_batch, sinkhorn_wmd_sparse_batch)
from repro.core.distributed import pad_query_batch
from repro.core.sparse_sinkhorn import safe_recip
from repro.kernels import ops, ref

LAMB, ITERS = 1.0, 12


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
    pre = precompute_batch(jnp.asarray(sel_b), jnp.asarray(r_b),
                           jnp.asarray(vecs), LAMB,
                           row_mask=jnp.asarray(mask_b))
    return {"vecs": vecs, "ell": ell, "sels": sels, "rsels": rsels,
            "sel_b": sel_b, "r_b": r_b, "mask_b": mask_b, "pre": pre,
            "k_pad": pad_k(pre.K), "km_pad": pad_k(pre.KM),
            "cols": jnp.asarray(ell.cols), "vals": jnp.asarray(ell.vals),
            "u": safe_recip(jnp.full((4, 16, n), 1.0 / 16, jnp.float32))}


def _solver_args(p):
    return (jnp.asarray(p["sel_b"]), jnp.asarray(p["r_b"]), p["cols"],
            p["vals"], jnp.asarray(p["vecs"]), LAMB, ITERS)


# ---------------------------------------------------------------------------
# Batched kernel vs batched jnp fused (the acceptance gate)
# ---------------------------------------------------------------------------

@pytest.mark.kernel
def test_batched_kernel_type1_matches_jnp_fused(batch_problem):
    """ops.sddmm_spmm_type1_batch (interpret) == jnp fused == naive oracle
    on a mixed-size padded query bucket (pad rows present in K/r/u)."""
    p = batch_problem
    r_b = jnp.asarray(p["r_b"])
    x_jnp = sddmm_spmm_type1_batch(p["k_pad"], r_b, p["u"],
                                   p["cols"], p["vals"])
    x_ref = ref.sddmm_spmm_type1_batch(p["k_pad"], r_b, p["u"],
                                       p["cols"], p["vals"])
    for q_blk in (None, 2):  # single stripe covering Q, and 2-query stripes
        x_pal = ops.sddmm_spmm_type1_batch(p["k_pad"], r_b, p["u"],
                                           p["cols"], p["vals"], q_blk=q_blk)
        np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_jnp),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_ref),
                                   rtol=2e-4, atol=1e-6)


@pytest.mark.kernel
def test_batched_kernel_type2_matches_jnp_fused(batch_problem):
    p = batch_problem
    w_jnp = sddmm_spmm_type2_batch(p["k_pad"], p["km_pad"], p["u"],
                                   p["cols"], p["vals"])
    w_ref = ref.sddmm_spmm_type2_batch(p["k_pad"], p["km_pad"], p["u"],
                                       p["cols"], p["vals"])
    w_pal = ops.sddmm_spmm_type2_batch(p["k_pad"], p["km_pad"], p["u"],
                                       p["cols"], p["vals"])
    np.testing.assert_allclose(np.asarray(w_pal), np.asarray(w_jnp),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w_pal), np.asarray(w_ref),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.kernel
def test_batched_kernel_pad_rows_and_slots_inert(batch_problem):
    """Pad-slot retargeting is bit-identical through the kernel (val == 0
    gates the accumulation), and an all-pad filler stripe solves to exactly
    zero through the full impl="kernel" batched solver."""
    p = batch_problem
    w_a = ops.sddmm_spmm_type2_batch(p["k_pad"], p["km_pad"], p["u"],
                                     p["cols"], p["vals"])
    cols_mut = jnp.where(p["vals"] == 0.0, 0, p["cols"])
    w_b = ops.sddmm_spmm_type2_batch(p["k_pad"], p["km_pad"], p["u"],
                                     cols_mut, p["vals"])
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_b))
    # all-pad filler query (the service's Q-bucket filler), kernel path
    wmd = sinkhorn_wmd_sparse_batch(
        jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16), jnp.float32),
        p["cols"], p["vals"], jnp.asarray(p["vecs"]), LAMB, ITERS,
        row_mask=jnp.zeros((1, 16), jnp.float32), impl="kernel")
    np.testing.assert_array_equal(np.asarray(wmd), 0.0)


@pytest.mark.kernel
def test_batched_solver_kernel_impl_matches_fused(batch_problem):
    """The full batched solver agrees across the impl table (the unified
    fused|unfused|kernel API of the tentpole)."""
    p = batch_problem
    kw = dict(row_mask=jnp.asarray(p["mask_b"]))
    base = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw))
    for impl in ("kernel", "unfused"):
        got = np.asarray(sinkhorn_wmd_sparse_batch(*_solver_args(p), **kw,
                                                   impl=impl))
        err = np.abs(got - base).max() / np.abs(base).max()
        assert err < 1e-4, (impl, err)


# ---------------------------------------------------------------------------
# Doc-chunked (cache-blocked) iteration
# ---------------------------------------------------------------------------

def test_chunked_op_bitwise_including_nondividing(batch_problem):
    """Chunked contraction == unchunked BITWISE at the op level (jitted),
    for dividing and non-dividing docs_chunk values (N = 45)."""
    p = batch_problem
    r_b = jnp.asarray(p["r_b"])
    t1 = jax.jit(functools.partial(sddmm_spmm_type1_batch),
                 static_argnames="docs_chunk")
    t2 = jax.jit(functools.partial(sddmm_spmm_type2_batch),
                 static_argnames="docs_chunk")
    x_base = np.asarray(t1(p["k_pad"], r_b, p["u"], p["cols"], p["vals"]))
    w_base = np.asarray(t2(p["k_pad"], p["km_pad"], p["u"],
                           p["cols"], p["vals"]))
    for dc in (0, 8, 15, 16, 32, 45):      # 0 = unchunked alias, no crash
        x_c = np.asarray(t1(p["k_pad"], r_b, p["u"], p["cols"], p["vals"],
                            docs_chunk=dc))
        np.testing.assert_array_equal(x_c, x_base, err_msg=f"type1 dc={dc}")
        w_c = np.asarray(t2(p["k_pad"], p["km_pad"], p["u"], p["cols"],
                            p["vals"], docs_chunk=dc))
        np.testing.assert_array_equal(w_c, w_base, err_msg=f"type2 dc={dc}")


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
# K gathered once per solve (hoisted) vs once per iteration (in-loop)
# ---------------------------------------------------------------------------

def _with_filler(p):
    """The bucket plus one all-pad filler query (the service's Q-bucket
    filler): pad query rows, a pad query and ELL pad slots all present."""
    q_pad = lambda a, fill: np.concatenate(
        [a, np.full((1, a.shape[1]), fill, a.dtype)])
    return q_pad(p["sel_b"], 0), q_pad(p["r_b"], 1.0), q_pad(p["mask_b"], 0.0)


def _stripes_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk, placement):
    """`sparse_sinkhorn._solve_batch_stripes`, traced afresh (a patched
    `solve_contractions` must not hit an earlier trace)."""
    from repro.core import sparse_sinkhorn as ss
    del placement                    # always "solve" here
    pre = precompute_batch(jnp.asarray(sel_b), jnp.asarray(r_b),
                           jnp.asarray(p["vecs"]), LAMB,
                           row_mask=jnp.asarray(mask_b))
    fn = jax.jit(functools.partial(ss._solve_batch_stripes, max_iter=ITERS,
                                   impl="fused", docs_chunk=docs_chunk,
                                   tol=tol))
    args = (pad_k(pre.K), pad_k(pre.KM), pre.r, p["cols"], p["vals"])
    return np.asarray(fn(*args)), fn.lower(*args).as_text()


def _mesh_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk, placement):
    """`distributed.build_wmd_batch_fn` on a (1, 1) mesh."""
    from repro.core import rebucket_for_vocab_shards
    from repro.core.distributed import build_wmd_batch_fn, shard_wmd_inputs
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    rb = rebucket_for_vocab_shards(p["ell"], 1)
    fn = build_wmd_batch_fn(mesh, lamb=LAMB, max_iter=ITERS, tol=tol,
                            docs_chunk=docs_chunk,
                            chunk_placement=placement)
    args = (jnp.asarray(p["vecs"][sel_b]), jnp.asarray(r_b),
            jnp.asarray(mask_b),
            *shard_wmd_inputs(mesh, p["vecs"], rb.cols, rb.vals))
    return np.asarray(fn(*args)), fn.lower(*args).as_text()


def _converged_solve(p, sel_b, r_b, mask_b, *, tol, docs_chunk, placement):
    """`convergence.sinkhorn_wmd_converged_batch`, traced afresh (past
    its own jit cache); its docs_chunk is per-op only."""
    del placement                    # always "iteration" here
    fn = jax.jit(lambda s, r, m: sinkhorn_wmd_converged_batch.__wrapped__(
        s, r, p["cols"], p["vals"], jnp.asarray(p["vecs"]), LAMB, ITERS,
        tol=tol, docs_chunk=docs_chunk, row_mask=m).wmd)
    args = (jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(mask_b))
    return np.asarray(fn(*args)), fn.lower(*args).as_text()


# (core, docs_chunk, chunk placement): the stripes core chunks the whole
# solve only, the converged core each op only
_HOIST_CASES = [
    pytest.param(solve, dc, placement, id=f"{core}-{cid}")
    for core, solve in (("stripes", _stripes_solve), ("mesh", _mesh_solve),
                        ("converged", _converged_solve))
    for cid, dc, placement in (("unchunked", None, "solve"),
                               ("solve_chunks", 16, "solve"),
                               ("iteration_unchunked", None, "iteration"),
                               ("iteration_chunks", 16, "iteration"))
    if (core, placement) != ("stripes", "iteration")
    and (core, placement) != ("converged", "solve")]


@pytest.mark.parametrize("tol", [0.0, 1e-3], ids=["fixed", "early_exit"])
@pytest.mark.parametrize("solve,docs_chunk,placement", _HOIST_CASES)
def test_hoisted_gather_matches_in_loop(batch_problem, monkeypatch, solve,
                                        docs_chunk, placement, tol):
    """The solve that gathers K once (`hoists_k_gather`) matches the one
    that gathers it every iteration (`solve_contractions(hoist=False)`) to
    1e-6 relative, with pad query rows, an all-pad query and ELL pad slots;
    N = 45 so the 16-doc chunks do not divide it. Every solve without a
    per-op chunk hoists; per-op chunking never does, so its two programs
    are the same."""
    from repro.core import convergence
    from repro.core import sparse_sinkhorn as ss
    p = batch_problem
    args = _with_filler(p)
    kw = dict(tol=tol, docs_chunk=docs_chunk, placement=placement)
    hoisted, text_once = solve(p, *args, **kw)
    in_loop_only = functools.partial(ss.solve_contractions, hoist=False)
    monkeypatch.setattr(ss, "solve_contractions", in_loop_only)
    monkeypatch.setattr(convergence, "solve_contractions", in_loop_only)
    in_loop, text_each = solve(p, *args, **kw)
    per_op_chunked = placement == "iteration" and docs_chunk is not None
    assert (text_once != text_each) == (not per_op_chunked)
    err = np.abs(hoisted - in_loop).max() / np.abs(in_loop).max()
    assert err < 1e-6, err
    assert hoisted.shape == (5, p["ell"].num_docs)
    np.testing.assert_array_equal(hoisted[-1], 0.0)
    np.testing.assert_array_equal(in_loop[-1], 0.0)


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
    """build_wmd_batch_fn(tol>0) on a (2, 2) mesh: per-query n_iter from the
    all-shards vote is within one iteration of single-host
    sinkhorn_wmd_converged_batch, and the distances agree (subprocess: needs
    a forced device count).

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
                        docs_chunk=16, chunk_placement="iteration",
                        with_info=True)
vd, cd, vld = shard_wmd_inputs(mesh, vecs, rb.cols, rb.vals)
wmd, n_iter, delta = fn(jnp.asarray(vecs[sel_b]), jnp.asarray(r_b),
                        jnp.asarray(mask_b), vd, cd, vld)
gap = np.abs(np.asarray(n_iter) - np.asarray(ref.n_iter))
assert gap.max() <= 1, (np.asarray(n_iter), np.asarray(ref.n_iter))
err = (np.abs(np.asarray(wmd) - np.asarray(ref.wmd)).max()
       / np.abs(np.asarray(ref.wmd)).max())
assert err < 1e-4, err
# chunk_placement="solve" (per-chunk freeze): same distances, and no block
# runs longer than the slowest global query
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


def test_service_q1_routes_to_sequential():
    """The Q=1 admission policy returns exactly the sequential result (it IS
    the sequential path -- no batched overhead for singletons), and is NOT
    taken when the service is configured with an engine the sequential path
    doesn't implement (tol > 0)."""
    svc, data = _smoke_service()
    lone = [data.queries[0]]
    np.testing.assert_array_equal(svc.query_batch(lone),
                                  svc.query_batch_sequential(lone))
    # shortcut: no batched fn (legacy or stripes) was built
    assert not svc._batch_fns and not svc._stripe_fns
    svc_tol, _ = _smoke_service(tol=1e-6)
    got = svc_tol.query_batch(lone)
    assert svc_tol._batch_fns           # early-exit engine actually ran
    seq = svc_tol.query_batch_sequential(lone)
    err = np.abs(got - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err


@pytest.mark.kernel
def test_service_forwards_impl_and_chunk():
    """query_batch(impl=...) and the docs_chunk/tol fields reach the engine:
    every combination matches the sequential oracle."""
    svc, data = _smoke_service(docs_chunk=16, tol=1e-6)
    seq = svc.query_batch_sequential(data.queries)
    for impl in ("fused", "kernel"):
        got = svc.query_batch(data.queries, impl=impl)
        err = np.abs(got - seq).max() / np.abs(seq).max()
        assert err < 1e-4, (impl, err)
    # per-call docs_chunk override (0 = explicitly unchunked)
    got = svc.query_batch(data.queries, docs_chunk=0)
    err = np.abs(got - seq).max() / np.abs(seq).max()
    assert err < 1e-4, err
    # an explicit impl override bypasses the Q=1 sequential shortcut and
    # still matches the per-query result
    lone = [data.queries[0]]
    got1 = svc.query_batch(lone, impl="kernel")
    assert got1.shape == (1, seq.shape[1])
    err1 = np.abs(got1 - seq[:1]).max() / np.abs(seq[:1]).max()
    assert err1 < 1e-4, err1
