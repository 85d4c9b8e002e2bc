"""Per-kernel validation: Pallas (interpret=True on CPU) vs the naive jnp
oracle (kernels.ref) vs the production jnp path (core.sparse_sinkhorn),
swept over shapes and dtypes per the assignment."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ell_from_dense, pad_k, precompute,
                        rebucket_for_vocab_shards)
from repro.core import sparse_sinkhorn as core_ss
from repro.kernels import ops, ref

# the whole module exercises the Pallas kernel path; CI runs it explicitly
# via `pytest -m kernel` (see .github/workflows/ci.yml)
pytestmark = pytest.mark.kernel


def _problem(v, w, n, vr, nnz_hi, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(v, w)).astype(dtype)
    sel = rng.choice(v, vr, replace=False).astype(np.int32)
    r_sel = (rng.random(vr).astype(dtype) + 0.1)
    r_sel /= r_sel.sum()
    c = np.zeros((v, n), dtype)
    for j in range(n):
        widx = rng.choice(v, rng.integers(2, nnz_hi), replace=False)
        c[widx, j] = rng.random(widx.size).astype(dtype)
        c[:, j] /= c[:, j].sum()
    ell = ell_from_dense(c)
    pre = precompute(jnp.asarray(sel), jnp.asarray(r_sel),
                     jnp.asarray(vecs), 1.0)
    u = jnp.asarray(rng.random((vr, n)).astype(dtype) + 0.5)
    return pre, ell, u, vecs, sel


SHAPES = [(64, 16, 16, 5, 9), (128, 32, 24, 8, 12), (256, 48, 40, 13, 20)]


@pytest.mark.parametrize("v,w,n,vr,nnz_hi", SHAPES)
def test_sddmm_spmm_type1_threeway(v, w, n, vr, nnz_hi):
    pre, ell, u, _, _ = _problem(v, w, n, vr, nnz_hi, seed=v)
    k_pad = pad_k(pre.K)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    x_ref = ref.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    x_core = core_ss.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    x_pal = ops.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    np.testing.assert_allclose(np.asarray(x_core), np.asarray(x_ref),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_ref),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("v,w,n,vr,nnz_hi", SHAPES)
def test_sddmm_spmm_type2_threeway(v, w, n, vr, nnz_hi):
    pre, ell, u, _, _ = _problem(v, w, n, vr, nnz_hi, seed=v + 1)
    k_pad, km_pad = pad_k(pre.K), pad_k(pre.KM)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    w_ref = ref.sddmm_spmm_type2(k_pad, km_pad, u, cols, vals)
    w_core = core_ss.sddmm_spmm_type2(k_pad, km_pad, u, cols, vals)
    w_pal = ops.sddmm_spmm_type2(k_pad, km_pad, u, cols, vals)
    np.testing.assert_allclose(np.asarray(w_core), np.asarray(w_ref),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w_pal), np.asarray(w_ref),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("docs_blk", [4, 8, 16])
def test_kernel_docs_blk_invariance(docs_blk):
    """BlockSpec tiling must not change results."""
    pre, ell, u, _, _ = _problem(96, 16, 32, 7, 10, seed=7)
    k_pad = pad_k(pre.K)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    base = ops.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals, docs_blk=8)
    got = ops.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals,
                               docs_blk=docs_blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


@pytest.mark.parametrize("vr,v", [(3, 64), (11, 96), (17, 128)])
def test_kernel_unaligned_shapes(vr, v):
    """ops.py padding must handle non-multiple-of-8 v_r and odd doc counts."""
    pre, ell, u, _, _ = _problem(v, 16, 21, vr, 8, seed=vr * v)
    k_pad = pad_k(pre.K)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    x_ref = ref.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    x_pal = ops.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    np.testing.assert_allclose(np.asarray(x_pal), np.asarray(x_ref),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n,m,w", [(8, 64, 16), (13, 96, 300), (32, 128, 64)])
def test_cdist_kernel(n, m, w):
    rng = np.random.default_rng(n * m)
    a = jnp.asarray(rng.normal(size=(n, w)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(m, w)).astype(np.float32))
    got = ops.cdist(a, b, v_tile=32)
    want = ref.cdist(a, b)
    # matmul expansion loses ~1e-3 absolute to cancellation (documented)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=5e-3)


def test_cdist_kernel_squared_exact_on_grid():
    """Squared distances on integer grids are exactly representable."""
    a = jnp.asarray(np.arange(8 * 4, dtype=np.float32).reshape(8, 4) % 5)
    b = jnp.asarray(np.arange(16 * 4, dtype=np.float32).reshape(16, 4) % 7)
    got = ops.cdist(a, b, v_tile=16, squared=True)
    want = ref.cdist(a, b, squared=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,m", [(8, 77), (13, 100)])
def test_cdist_pad_to_tile_arbitrary_v(n, m):
    """V not divisible by v_tile: the kernels pad the vocab axis internally
    and slice back (the old hard requirement V % v_tile == 0 is gone)."""
    rng = np.random.default_rng(n * m)
    a = jnp.asarray(rng.normal(size=(n, 24)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(m, 24)).astype(np.float32))
    got = ops.cdist(a, b, v_tile=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.cdist(a, b)),
                               rtol=2e-3, atol=5e-3)
    k, km = ops.cdist_kexp(a, b, lamb=1.0, v_tile=32)
    k_ref, km_ref = ref.cdist_kexp(a, b, lamb=1.0)
    np.testing.assert_allclose(np.asarray(k), np.asarray(k_ref),
                               rtol=5e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(km), np.asarray(km_ref),
                               rtol=5e-3, atol=1e-3)


@pytest.mark.parametrize("m_rows,v", [(5, 80), (21, 77), (64, 96)])
def test_cdist_kexp_rows_matches_full(m_rows, v):
    """Row-subset fused kexp (the cache-miss path): rows of an arbitrary
    id subset == the same rows of the full-stripe kernel and the oracle,
    across non-tile-multiple row counts AND vocab sizes."""
    rng = np.random.default_rng(m_rows * v)
    vecs = jnp.asarray(rng.normal(size=(v, 24)).astype(np.float32))
    ids = jnp.asarray(rng.choice(v, m_rows, replace=False).astype(np.int32))
    k_rows, km_rows = ops.cdist_kexp_rows(vecs[ids], vecs, lamb=1.0,
                                          rows_blk=8, v_tile=32)
    assert k_rows.shape == (m_rows, v)
    k_ref, km_ref = ref.cdist_kexp(vecs[ids], vecs, lamb=1.0)
    np.testing.assert_allclose(np.asarray(k_rows), np.asarray(k_ref),
                               rtol=5e-3, atol=1e-4)
    # KM inherits the matmul-expansion cancellation of M (~1e-3 absolute,
    # documented at test_cdist_kernel)
    np.testing.assert_allclose(np.asarray(km_rows), np.asarray(km_ref),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("lamb", [0.5, 1.0, 4.0])
def test_cdist_kexp_fused(lamb):
    rng = np.random.default_rng(int(lamb * 10))
    a = jnp.asarray(rng.normal(size=(9, 24)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(80, 24)).astype(np.float32))
    k_got, km_got = ops.cdist_kexp(a, b, lamb=lamb, v_tile=16)
    k_ref, km_ref = ref.cdist_kexp(a, b, lamb=lamb)
    np.testing.assert_allclose(np.asarray(k_got), np.asarray(k_ref),
                               rtol=5e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(km_got), np.asarray(km_ref),
                               rtol=5e-3, atol=1e-3)


def test_chunked_driver_matches_monolithic():
    """Single-chip vocab-chunked kernel == unchunked (multi-chip layout)."""
    pre, ell, u, _, _ = _problem(128, 16, 24, 9, 10, seed=3)
    k_pad = pad_k(pre.K)
    cols, vals = jnp.asarray(ell.cols), jnp.asarray(ell.vals)
    x_full = core_ss.sddmm_spmm_type1(k_pad, pre.r, u, cols, vals)
    shards = 4
    rb = rebucket_for_vocab_shards(ell, shards)
    vloc = 128 // shards
    k_chunks = jnp.stack([pad_k(pre.K[:, s * vloc:(s + 1) * vloc])
                          for s in range(shards)])
    x_chunk = ops.sddmm_spmm_chunked(k_chunks, pre.r, u,
                                     jnp.asarray(rb.cols),
                                     jnp.asarray(rb.vals))
    np.testing.assert_allclose(np.asarray(x_chunk), np.asarray(x_full),
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("backend, interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Mosaic on a TPU, the interpreter on the CPU, and no silent
    interpreter on any other backend."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(NotImplementedError, match="gpu"):
            ops._interpret()
    else:
        assert ops._interpret() is interpret
