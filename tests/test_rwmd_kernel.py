"""Kernel-oracle fuzz for the RWMD min-SDDMM Pallas kernel (kernels.rwmd),
mirroring test_kernels.py: three-way agreement pallas == core-jnp == naive
dense oracle over random shapes, including non-tile-multiple v_r / N / V
and the +inf pad-row convention. CPU runs interpret mode; whether Mosaic
compiles the kernel for a TPU is `tests/test_tpu_compile.py`'s question."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assemble_m_stripes, ell_from_dense, rwmd_bound_batch
from repro.kernels import ops, ref

pytestmark = pytest.mark.kernel


def _problem(v, n, vr_bucket, q, nnz_hi, seed, *, n_pad_rows=2):
    """Random M stripes (+inf pad rows) + ELL; returns (m_pad, cols, vals)."""
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(v, 12)).astype(np.float32)
    c = np.zeros((v, n), np.float32)
    for j in range(n):
        widx = rng.choice(v, rng.integers(2, nnz_hi), replace=False)
        c[widx, j] = rng.random(widx.size).astype(np.float32)
        c[:, j] /= c[:, j].sum()
    ell = ell_from_dense(c)
    sel_b = np.zeros((q, vr_bucket), np.int32)
    mask_b = np.zeros((q, vr_bucket), np.float32)
    for i in range(q):
        real = vr_bucket - (n_pad_rows if i % 2 else 0)
        sel_b[i, :real] = rng.choice(v, real, replace=False)
        mask_b[i, :real] = 1.0
    m_pad = assemble_m_stripes(sel_b, mask_b, vecs, rows_bucket=8)
    return m_pad, jnp.asarray(ell.cols), jnp.asarray(ell.vals)


# (V, N, v_r bucket, Q, nnz_hi) -- deliberately awkward: odd doc counts,
# v_r not a sublane multiple, V not a power of two, Q not a q_blk multiple
SHAPES = [(64, 16, 5, 2, 9), (97, 21, 11, 3, 8), (130, 40, 13, 5, 14),
          (256, 33, 17, 9, 20)]


@pytest.mark.parametrize("v,n,vr,q,nnz_hi", SHAPES)
def test_rwmd_kernel_threeway(v, n, vr, q, nnz_hi):
    m_pad, cols, vals = _problem(v, n, vr, q, nnz_hi, seed=v + n)
    lb_ref = np.asarray(ref.rwmd_bound_batch(m_pad, cols, vals))
    lb_core = np.asarray(rwmd_bound_batch(m_pad, cols, vals))
    lb_pal = np.asarray(ops.rwmd_bound_batch(m_pad, cols, vals))
    np.testing.assert_allclose(lb_core, lb_ref, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(lb_pal, lb_ref, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("docs_blk,q_blk", [(4, 2), (8, 8), (16, 4)])
def test_rwmd_kernel_tiling_invariance(docs_blk, q_blk):
    """BlockSpec tiling must not change results."""
    m_pad, cols, vals = _problem(96, 32, 7, 4, 10, seed=7)
    base = ops.rwmd_bound_batch(m_pad, cols, vals, docs_blk=8)
    got = ops.rwmd_bound_batch(m_pad, cols, vals, docs_blk=docs_blk,
                               q_blk=q_blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


def test_rwmd_kernel_filler_query_rows_zero():
    """All-+inf filler stripes (pow2 admission filler) come back exactly 0
    from the kernel wrapper, matching the jnp and oracle paths."""
    m_pad, cols, vals = _problem(64, 16, 6, 3, 8, seed=3)
    filler = jnp.full((1,) + m_pad.shape[1:], jnp.inf, m_pad.dtype)
    m_f = jnp.concatenate([m_pad, filler])
    for fn in (ops.rwmd_bound_batch, ref.rwmd_bound_batch,
               rwmd_bound_batch):
        lb = np.asarray(fn(m_f, cols, vals))
        assert np.all(lb[-1] == 0.0), fn
        # and the real rows are untouched by the filler's presence
        np.testing.assert_array_equal(
            lb[:-1], np.asarray(fn(m_pad, cols, vals)))


def test_rwmd_kernel_docs_chunk_maps_to_grid():
    """core dispatch impl='kernel' routes docs_chunk onto the doc-tile grid
    (the kernel's native blocking) -- same results as the default tile."""
    m_pad, cols, vals = _problem(64, 24, 5, 2, 8, seed=11)
    base = rwmd_bound_batch(m_pad, cols, vals, impl="kernel")
    got = rwmd_bound_batch(m_pad, cols, vals, impl="kernel", docs_chunk=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


# ---------------------------------------------------------------------------
# LC-RWMD (kernels.lcrwmd): the tier-1 dense-gather + SpMV kernel
# ---------------------------------------------------------------------------

def _lc_problem(v, n, vr, q, nnz_hi, seed):
    """Same random stripes, reduced to (Q, V+1) min-cost vectors."""
    from repro.core import min_cost_vectors
    m_pad, cols, vals = _problem(v, n, vr, q, nnz_hi, seed=seed)
    return min_cost_vectors(m_pad), m_pad, cols, vals


@pytest.mark.parametrize("v,n,vr,q,nnz_hi", SHAPES)
def test_lc_rwmd_kernel_threeway(v, n, vr, q, nnz_hi):
    """pallas == core-jnp == naive dense oracle, and all bitwise equal to
    the doc-side bound they hoist the min out of (the cascade's LC link)."""
    from repro.core import lc_rwmd_bound_batch
    minm, m_pad, cols, vals = _lc_problem(v, n, vr, q, nnz_hi, seed=v + n)
    lb_ref = np.asarray(ref.lc_rwmd_bound_batch(minm, cols, vals))
    lb_core = np.asarray(lc_rwmd_bound_batch(minm, cols, vals))
    lb_pal = np.asarray(ops.lc_rwmd_bound_batch(minm, cols, vals))
    np.testing.assert_allclose(lb_core, lb_ref, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(lb_pal, lb_ref, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(
        lb_core, np.asarray(rwmd_bound_batch(m_pad, cols, vals)))


@pytest.mark.parametrize("docs_blk,q_blk", [(4, 2), (8, 8), (16, 4)])
def test_lc_rwmd_kernel_tiling_invariance(docs_blk, q_blk):
    minm, _, cols, vals = _lc_problem(96, 32, 7, 4, 10, seed=7)
    base = ops.lc_rwmd_bound_batch(minm, cols, vals, docs_blk=8)
    got = ops.lc_rwmd_bound_batch(minm, cols, vals, docs_blk=docs_blk,
                                  q_blk=q_blk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


def test_lc_rwmd_kernel_filler_query_rows_zero():
    """All-+inf min-cost vectors (filler queries) finite-ize to exactly 0
    in every spelling, and their presence leaves real rows untouched."""
    from repro.core import lc_rwmd_bound_batch
    minm, _, cols, vals = _lc_problem(64, 16, 6, 3, 8, seed=3)
    filler = jnp.full((1, minm.shape[1]), jnp.inf, minm.dtype)
    m_f = jnp.concatenate([minm, filler])
    for fn in (ops.lc_rwmd_bound_batch, ref.lc_rwmd_bound_batch,
               lc_rwmd_bound_batch):
        lb = np.asarray(fn(m_f, cols, vals))
        assert np.all(lb[-1] == 0.0), fn
        np.testing.assert_array_equal(
            lb[:-1], np.asarray(fn(minm, cols, vals)))


def test_lc_rwmd_kernel_docs_chunk_maps_to_grid():
    """core dispatch impl='kernel' routes docs_chunk onto the doc-tile
    grid -- same results as the default tile."""
    from repro.core import lc_rwmd_bound_batch
    minm, _, cols, vals = _lc_problem(64, 24, 5, 2, 8, seed=11)
    base = lc_rwmd_bound_batch(minm, cols, vals, impl="kernel")
    got = lc_rwmd_bound_batch(minm, cols, vals, impl="kernel", docs_chunk=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)
