"""Faithful port of the paper's Algorithm 1 / Fig. 3 -- dense Sinkhorn-WMD.

This is the *paper-faithful baseline*: a line-for-line translation of the
Python reference in Fig. 3 of the paper into jnp, with the same matrix
identities and iteration structure:

    I = (r > 0); r = r(I); M = M(I, :); K = exp(-lambda * M)
    x = ones(len(r), n_docs) / len(r)
    repeat:  u = 1/x
             v = c .* (1 / (K^T @ u))        # the dense-heavy hotspot (91.9%)
             x = (diag(1/r) K) @ v
    u = 1/x; v = c .* (1 / (K^T @ u))
    WMD = sum(u .* ((K .* M) @ v), axis=0)

``c`` is dense here (V x N) -- exactly the over-compute the paper removes; the
sparse-heavy PASWD version lives in `repro.core.sparse_sinkhorn`. Keeping both
is deliberate: the dense version is the correctness oracle and the Fig. 8
baseline ("C++ translation of the Python code, without the SDDMM kernel").

Shapes are static under jit: the nonzero selection of ``r`` happens host-side
(`select_query`) because XLA needs static v_r.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SinkhornPrecompute(NamedTuple):
    """Iteration-invariant matrices (paper Fig. 4: ``precompute_matrices``)."""

    K: jax.Array         # (v_r, V) exp(-lambda * M)
    K_over_r: jax.Array  # (v_r, V) diag(1/r) K
    KM: jax.Array        # (v_r, V) K .* M
    r: jax.Array         # (v_r,)


def select_query(r: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side ``I = (r > 0); r = r(I)`` -- returns (sel_idx, r_sel).

    Separated from the jit'd solver because v_r must be a static shape.
    """
    (sel,) = np.nonzero(np.asarray(r) > 0)
    r_sel = np.asarray(r, dtype=np.float32)[sel]
    return sel.astype(np.int32), r_sel


def m_rows(word_ids: jax.Array, vecs: jax.Array,
           *, b2: jax.Array | None = None) -> jax.Array:
    """Cost-matrix rows M[i] = |vecs[id_i] - vecs| (MXU matmul expansion).

    THE single spelling of the M-row expression: the K/K.*M precompute
    (`precompute_rows`, and through it the K cache) and the RWMD prune
    bound (`core.rwmd`) both call it, which is what makes "the bound sees
    the same geometry the engine's K.*M encodes" a structural guarantee
    rather than a kept-in-sync convention -- the pruning exactness
    contract assumes bound-M and engine-M agree bit for bit. ``b2``
    optionally supplies precomputed per-vocab-word squared norms.
    """
    a = vecs[word_ids]                                  # (m, w)
    a2 = jnp.sum(a * a, axis=-1)[:, None]
    if b2 is None:
        b2 = jnp.sum(vecs * vecs, axis=-1)
    # HIGHEST: a TPU's default single bf16 pass loses the cancellation in
    # |a|^2 + |b|^2 - 2 a.b (f32 on the CPU either way)
    ab = jnp.matmul(a, vecs.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.sqrt(jnp.maximum(a2 + b2[None, :] - 2.0 * ab, 0.0))


def precompute_rows(word_ids: jax.Array, vecs: jax.Array, lamb: float,
                    *, b2: jax.Array | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """The *cacheable* half of the precompute: (K, K.*M) rows keyed purely
    by (word_id, lamb) -- nothing query-specific enters.

    One row per requested word id: K[i] = exp(-lamb * |vecs[id_i] - vecs|),
    KM[i] = K[i] * M[i]. ``b2`` optionally supplies the precomputed
    per-vocab-word squared norms (sum(vecs**2, -1)); `core.kcache` passes it
    so the O(V*w) term is paid once per corpus instead of once per miss
    batch. The math is the `cdist_matmul` MXU expansion of `m_rows` so
    cached rows are bit-identical to the from-scratch `precompute` path.
    """
    m = m_rows(word_ids, vecs, b2=b2)
    k = jnp.exp(-lamb * m)
    return k, k * m


def assemble_precompute(k_rows: jax.Array, km_rows: jax.Array,
                        r_sel: jax.Array) -> SinkhornPrecompute:
    """The *per-query* half: a cheap row scale over gathered rows.

    K_over_r = diag(1/r) K is the only query-dependent matrix; K and K.*M
    come straight from `precompute_rows` (or the cross-query cache) for the
    query's word ids.
    """
    return SinkhornPrecompute(
        K=k_rows,
        K_over_r=k_rows / r_sel[:, None],
        KM=km_rows,
        r=r_sel,
    )


def precompute(sel_idx: jax.Array, r_sel: jax.Array, vecs: jax.Array,
               lamb: float) -> SinkhornPrecompute:
    """M = cdist(vecs[sel], vecs); K = exp(-lamb M); K/r; K*M.

    Composition of the cacheable rows (`precompute_rows`) and the per-query
    scale (`assemble_precompute`) -- `core.kcache` splits exactly here.
    """
    k, km = precompute_rows(sel_idx, vecs, lamb)
    return assemble_precompute(k, km, r_sel)


def _safe_recip(x):
    """Guard against exp-underflow-driven 0-division (see sparse_sinkhorn)."""
    return 1.0 / jnp.maximum(x, 1e-30)


def _iterate_dense(pre: SinkhornPrecompute, c: jax.Array, x: jax.Array):
    """One Sinkhorn iteration, dense formulation (the 91.9% hotspot)."""
    u = _safe_recip(x)                                  # (v_r, N)
    w = pre.K.T @ u                                     # (V, N) dense!
    v = c * jnp.where(c != 0.0, _safe_recip(w), 0.0)    # c .* (1/w)
    x = pre.K_over_r @ v                                # (v_r, N)
    return x, v


@functools.partial(jax.jit, static_argnames=("max_iter",))
def sinkhorn_wmd_dense(sel_idx: jax.Array, r_sel: jax.Array, c: jax.Array,
                       vecs: jax.Array, lamb: float, max_iter: int) -> jax.Array:
    """Dense Sinkhorn-WMD of one query against N docs. Returns (N,) distances.

    Args:
      sel_idx: (v_r,) int32 indices of the query's nonzero vocabulary words.
      r_sel:   (v_r,) f32 normalized query word frequencies (sum == 1).
      c:       (V, N) f32 dense doc-frequency matrix, columns sum to 1.
      vecs:    (V, w) f32 word embeddings.
      lamb:    entropy regularization strength (paper passes it negated; we
               follow Fig. 3 and negate inside: K = exp(-lamb * M)).
      max_iter: fixed iteration count (paper: practical cutoff).
    """
    pre = precompute(sel_idx, r_sel, vecs, lamb)
    v_r = r_sel.shape[0]
    n = c.shape[1]
    x0 = jnp.full((v_r, n), 1.0 / v_r, dtype=jnp.float32)

    def body(_, x):
        x, _ = _iterate_dense(pre, c, x)
        return x

    x = jax.lax.fori_loop(0, max_iter, body, x0)
    # final: u = 1/x; v = c .* (1/(K^T u)); WMD = sum(u .* (KM @ v), 0)
    u = _safe_recip(x)
    w = pre.K.T @ u
    v = c * jnp.where(c != 0.0, _safe_recip(w), 0.0)
    return jnp.sum(u * (pre.KM @ v), axis=0)


@functools.partial(jax.jit, static_argnames=("max_iter",))
def sinkhorn_wmd_dense_history(sel_idx, r_sel, c, vecs, lamb, max_iter):
    """Like sinkhorn_wmd_dense but also returns per-iteration |dx|_inf for
    convergence studies (`core.convergence`)."""
    pre = precompute(sel_idx, r_sel, vecs, lamb)
    v_r = r_sel.shape[0]
    n = c.shape[1]
    x0 = jnp.full((v_r, n), 1.0 / v_r, dtype=jnp.float32)

    def body(x, _):
        x_new, _ = _iterate_dense(pre, c, x)
        return x_new, jnp.max(jnp.abs(x_new - x))

    x, deltas = jax.lax.scan(body, x0, None, length=max_iter)
    u = _safe_recip(x)
    w = pre.K.T @ u
    v = c * jnp.where(c != 0.0, _safe_recip(w), 0.0)
    return jnp.sum(u * (pre.KM @ v), axis=0), deltas
