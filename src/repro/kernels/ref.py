"""Pure-jnp oracles for every Pallas kernel -- deliberately *naive*.

These are written in the most transparent form possible (dense materialization
+ masking; no gather tricks, no fusion) so they are independent of both the
Pallas kernels and the production jnp path in `core.sparse_sinkhorn`. Kernel
tests assert a three-way agreement: pallas == core-jnp == this oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TINY = 1e-30


def _ell_to_dense(cols: jax.Array, vals: jax.Array, num_vocab: int):
    """(N, nnz) ELL -> (V, N) dense, dropping pad slots (col == V)."""
    n, nnz = cols.shape
    one_hot = jax.nn.one_hot(cols, num_vocab + 1, dtype=vals.dtype)
    dense = jnp.einsum("nkv,nk->vn", one_hot, vals)
    return dense[:num_vocab]                                  # (V, N)


def sddmm_spmm_type1(k_pad: jax.Array, r_sel: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array) -> jax.Array:
    """Oracle: dense w = K^T u; v = c/w (on support); x = (K/r) v."""
    v = _sampled_inverse_product(k_pad, u, cols, vals)        # (V, N) dense
    k = k_pad[:, :-1]
    return (k @ v) / r_sel[:, None]


def sddmm_spmm_type2(k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array) -> jax.Array:
    v = _sampled_inverse_product(k_pad, u, cols, vals)
    km = km_pad[:, :-1]
    return jnp.sum(u * (km @ v), axis=0)


def _sampled_inverse_product(k_pad, u, cols, vals):
    """Dense SDDMM: full K^T @ u then mask to the sparsity pattern of c."""
    num_vocab = k_pad.shape[1] - 1
    c = _ell_to_dense(cols, vals, num_vocab)                  # (V, N)
    w = k_pad[:, :-1].T @ u                                   # (V, N), dense
    return jnp.where(c != 0.0, c / jnp.maximum(w, TINY), 0.0)


def rwmd_bound_batch(m_pad: jax.Array, cols: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """Oracle for the doc-side RWMD min-SDDMM (core.rwmd / kernels.rwmd):
    densify the ELL, take the per-vocab-word min over query rows of the full
    M stripe, and contract with the dense doc frequencies -- no gather, no
    slot loop. Pad query rows carry +inf in m_pad (never win the min);
    all-pad filler queries produce inf/NaN rows finited to 0 here exactly
    like the production paths."""
    num_vocab = m_pad.shape[-1] - 1
    c = _ell_to_dense(cols, vals, num_vocab)                  # (V, N)
    mins = jnp.min(m_pad[:, :, :num_vocab], axis=1)           # (Q, V)
    lb = jnp.einsum("qv,vn->qn", mins, c)
    return jnp.where(jnp.isfinite(lb), lb, 0.0)


def lc_rwmd_bound_batch(minm: jax.Array, cols: jax.Array,
                        vals: jax.Array) -> jax.Array:
    """Oracle for the LC-RWMD sparse dot (core.cascade / kernels.lcrwmd):
    densify the ELL and contract the (Q, V) min-cost vectors against it as
    one dense matmul -- no gather, no slot loop. Filler queries carry
    all-+inf minm rows, finited to 0 here exactly like the production
    paths."""
    num_vocab = minm.shape[-1] - 1
    c = _ell_to_dense(cols, vals, num_vocab)                  # (V, N)
    lb = minm[:, :num_vocab] @ c
    return jnp.where(jnp.isfinite(lb), lb, 0.0)


def cdist(a: jax.Array, b: jax.Array, *, squared: bool = False) -> jax.Array:
    """Oracle: direct elementwise |a_i - b_j|."""
    d2 = jnp.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)
    return d2 if squared else jnp.sqrt(d2)


def cdist_kexp(a: jax.Array, b: jax.Array, *, lamb: float):
    m = cdist(a, b)
    k = jnp.exp(-lamb * m)
    return k, k * m
