"""Tier-0 centroid screen + LC-RWMD: the cheap front of the retrieval cascade.

The two-tier retriever (`core.rwmd` + the exact stripes rerank) still pays
O(nnz * v_r) doc-side bound work for *all* N docs per query. This module adds
the two cheaper tiers in front of it:

Tier 0 -- centroid / nBOW screen (Werner & Laber)
-------------------------------------------------
One dense matmul over precomputed per-doc moments. With ``z`` any reference
point, ``R = max_i ||x_i - z||`` over the query's real word vectors, and the
doc moments ``g_d = sum_s vals[d,s] * y_s`` (mass-weighted vector sum) and
``m_d = sum_s vals[d,s]`` (doc mass), the triangle inequality gives, per ELL
slot ``s`` of doc ``d``:

    min_i ||x_i - y_s||  >=  ||y_s - z|| - max_i ||x_i - z||  =  ||y_s - z|| - R

and summing with weights ``vals[d, s] >= 0``:

    rwmd(q, d) = sum_s vals[d,s] * min_i ||x_i - y_s||
              >= sum_s vals[d,s] * ||y_s - z||  -  m_d * R
              >= || sum_s vals[d,s] * (y_s - z) ||  -  m_d * R      (Jensen)
               = || g_d - m_d * z ||  -  m_d * R

so ``tier0(q, d) = max(0, ||g_d - m_d z|| - m_d R)`` lower-bounds the
doc-side RWMD -- and hence, by the PR 5 chain, the engine's returned distance
at EVERY iteration budget (the derivation never touches the transport plan,
only the cost matrix geometry, so no convergence assumption enters). The
choice of ``z`` is free; the r-weighted query centroid keeps ``R`` small.
Norm expansion ``||g - m z||^2 = g2 - 2 m (z . g) + m^2 z2`` turns the whole
screen into one (Q, dim) x (dim, N) matmul plus rank-1 terms.

Tier 1 -- LC-RWMD (Atasu et al., linear-complexity RWMD)
--------------------------------------------------------
The doc-side RWMD's inner reduction ``min_i M[sel_q[i], c]`` depends only on
(query, vocab word), not on the doc: gather the per-vocab-word min-cost
vector ``minm[q, c] = min_i m_pad[q, i, c]`` ONCE per query (a (Q, v_r, V+1)
-> (Q, V+1) min), then every doc costs a single sparse dot
``sum_s vals[d,s] * minm[q, cols[d,s]]`` -- O(Q*V*v_r + N*nnz) for the whole
corpus instead of O(N * nnz * v_r) per batch. The value is mathematically
*identical* to `core.rwmd.rwmd_bound_batch` (same min over the same floats,
hoisted out of the doc loop), so its soundness is the doc-side bound's
soundness; the cascade treats it as a separate tier only because its cost
profile differs. Three spellings as usual: the fused jnp path below, the
Pallas dense-gather + SpMV kernel (`kernels.lcrwmd`, ``impl="kernel"``), and
the naive dense oracle (`kernels.ref.lc_rwmd_bound_batch`).

Pad conventions are inherited from `core.rwmd.assemble_m_stripes`: pad query
rows carry +inf (they never win the min, so ``minm`` of an all-pad filler
query is +inf and its bounds finite-ize to 0), pad ELL slots are masked by
``vals == 0``, empty docs and filler queries score exactly 0 -- a 0 bound
can never prune them, matching the engine's 0.0 distance.

Both tiers inherit the prune contract: bounds only reorder and skip; every
solved doc's distance bits come from the same stripes programs as the
exhaustive scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sparse_sinkhorn import _chunk_over_docs

_LC_IMPLS = ("fused", "kernel")

TINY = 1e-30


@jax.jit
def doc_centroids(cols: jax.Array, vals: jax.Array,
                  vecs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-doc moments for the tier-0 screen: (g, m) = (sum vals*y, sum vals).

    cols/vals: corpus ELL (N, nnz_max), pad col == V, pad val == 0. The
    vocab table gets a zero pad row so pad slots contribute nothing to
    either moment. Accumulated slot-by-slot (O(N * dim) live memory, never
    the (N, nnz, dim) gather). Empty docs yield g = 0, m = 0. Computed once
    per corpus version, reused across every query batch.
    """
    vp = jnp.concatenate(
        [vecs, jnp.zeros((1, vecs.shape[1]), vecs.dtype)], axis=0)
    n, nnz_max = cols.shape

    def slot(s, acc):
        return acc + vp[cols[:, s]] * vals[:, s, None]

    g = jax.lax.fori_loop(0, nnz_max, slot,
                          jnp.zeros((n, vecs.shape[1]), vecs.dtype))
    return g, jnp.sum(vals, axis=1)


@jax.jit
def centroid_bound_batch(sel_b: jax.Array, r_b: jax.Array, mask_b: jax.Array,
                         vecs: jax.Array, g: jax.Array,
                         m: jax.Array) -> jax.Array:
    """Tier-0 centroid lower bounds. Returns (Q, N).

    sel_b / r_b / mask_b: the (Q, v_r) padded-query arrays of
    `core.distributed.pad_query_batch` (pad rows mask 0). g / m: the
    corpus moments from `doc_centroids`. All-pad filler queries (mask-sum
    0) and empty docs (m = 0) score exactly 0 -- never pruned. The relu
    also absorbs the sqrt's fp slack; the service's ``prune_margin``
    covers the rest, same as the other tiers.
    """
    x = vecs[sel_b]                                     # (Q, v_r, dim)
    w = r_b * mask_b
    ws = jnp.sum(w, axis=1)                             # (Q,)
    z = jnp.sum(w[:, :, None] * x, axis=1) / jnp.maximum(ws, TINY)[:, None]
    d2 = jnp.sum((x - z[:, None, :]) ** 2, axis=-1)     # (Q, v_r)
    radius = jnp.sqrt(jnp.max(jnp.where(mask_b > 0, d2, 0.0), axis=1))
    g2 = jnp.sum(g * g, axis=-1)                        # (N,)
    z2 = jnp.sum(z * z, axis=-1)                        # (Q,)
    # HIGHEST: the same cancellation as `core.sinkhorn.m_rows`
    zg = jnp.matmul(z, g.T, precision=jax.lax.Precision.HIGHEST)
    n2 = (g2[None, :] - 2.0 * m[None, :] * zg
          + (m[None, :] ** 2) * z2[:, None])            # ||g - m z||^2, (Q,N)
    lb = jnp.sqrt(jnp.maximum(n2, 0.0)) - m[None, :] * radius[:, None]
    lb = jnp.maximum(lb, 0.0)
    return jnp.where(ws[:, None] > 0, lb, 0.0)          # filler queries -> 0


@jax.jit
def min_cost_vectors(m_pad: jax.Array) -> jax.Array:
    """(Q, v_r, V+1) M stripes -> (Q, V+1) per-vocab-word min-cost vectors.

    Pad query rows are +inf by the `assemble_m_stripes` convention, so they
    never win; an all-pad filler query's vector is all +inf and its LC
    bounds finite-ize to 0 downstream. The pad column (index V) rides along
    -- pad ELL slots gather it but are val-masked out anyway.
    """
    return jnp.min(m_pad, axis=1)


def _lc_chunk_jnp(minm: jax.Array, cols_c: jax.Array,
                  vals_c: jax.Array) -> jax.Array:
    """One doc chunk of the fused LC sparse dot: (Q, docs) partial bounds."""
    mg = minm[:, cols_c]                                # (Q, n_c, nnz)
    mg = jnp.where(vals_c[None] != 0.0, mg, 0.0)        # pad slots out
    return jnp.einsum("qnk,nk->qn", mg, vals_c)


@functools.partial(jax.jit, static_argnames=("impl", "docs_chunk"))
def lc_rwmd_bound_batch(minm: jax.Array, cols: jax.Array, vals: jax.Array,
                        impl: str = "fused",
                        docs_chunk: int | None = None) -> jax.Array:
    """Batched LC-RWMD lower bounds: one sparse dot per doc. Returns (Q, N).

    Args:
      minm: (Q, V+1) per-query min-cost vectors from `min_cost_vectors`
            (filler queries all +inf -- finited to 0 here).
      cols / vals: the corpus ELL (N, nnz_max), pad col == V, pad val == 0.
      impl: "fused" (jnp gather + einsum) | "kernel" (the Pallas
            dense-gather + SpMV, `kernels.lcrwmd`).
      docs_chunk: cache-block over static N-chunks via the engine's
            `_chunk_over_docs` (bitwise exactness included).
    """
    if impl not in _LC_IMPLS:
        raise ValueError(f"impl must be one of {_LC_IMPLS}, got {impl!r}")
    if impl == "kernel":
        from repro.kernels import ops
        kw = {} if not docs_chunk else {"docs_blk": docs_chunk}
        return ops.lc_rwmd_bound_batch(minm, cols, vals, **kw)
    q, n = minm.shape[0], cols.shape[0]
    u_dummy = jnp.zeros((q, 1, n), minm.dtype)          # doc-axis carrier
    lb = _chunk_over_docs(
        lambda _, cols_c, vals_c: _lc_chunk_jnp(minm, cols_c, vals_c),
        u_dummy, cols, vals, docs_chunk, pad_col=minm.shape[-1] - 1)
    return jnp.where(jnp.isfinite(lb), lb, 0.0)         # filler queries -> 0
