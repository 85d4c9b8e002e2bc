"""Convergence-monitored Sinkhorn solve ("while x changes" done properly).

The paper (section III-B1) notes the ideal loop runs "as long as there is any
change in the output" but uses a fixed ``max_iter`` cutoff in practice. This
module provides the ideal form -- a `jax.lax.while_loop` on the infinity-norm
iterate delta -- used by the serving path where query latency matters and
most queries converge in far fewer than max_iter iterations.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.sinkhorn import precompute
from repro.core.sparse_sinkhorn import (batched_sinkhorn_loop, pad_k,
                                        precompute_batch, safe_recip,
                                        sddmm_spmm_type1, sddmm_spmm_type2,
                                        solve_contractions)


class ConvergedWMD(NamedTuple):
    wmd: jax.Array     # (N,) distances
    n_iter: jax.Array  # iterations actually executed
    delta: jax.Array   # final |dx|_inf


@functools.partial(jax.jit, static_argnames=("max_iter",))
def sinkhorn_wmd_converged(sel_idx: jax.Array, r_sel: jax.Array,
                           cols: jax.Array, vals: jax.Array, vecs: jax.Array,
                           lamb: float, max_iter: int,
                           tol: float = 1e-6) -> ConvergedWMD:
    """Sparse fused Sinkhorn-WMD with early exit on |x_t - x_{t-1}|_inf < tol."""
    pre = precompute(sel_idx, r_sel, vecs, lamb)
    k_pad = pad_k(pre.K)
    km_pad = pad_k(pre.KM)
    v_r = r_sel.shape[0]
    n = cols.shape[0]
    x0 = jnp.full((v_r, n), 1.0 / v_r, dtype=pre.K.dtype)

    def cond(carry):
        _, delta, it = carry
        return (it < max_iter) & (delta >= tol)

    def body(carry):
        x, _, it = carry
        x_new = sddmm_spmm_type1(k_pad, pre.r, safe_recip(x), cols, vals)
        # relative iterate delta: x spans a huge dynamic range (x ~ K-scale),
        # so an absolute norm would never cross tol for strongly regularized K.
        rel = jnp.max(jnp.abs(x_new - x) / (jnp.abs(x) + 1e-30))
        return x_new, rel, it + 1

    x, delta, n_iter = jax.lax.while_loop(
        cond, body, (x0, jnp.asarray(jnp.inf, x0.dtype), jnp.asarray(0)))
    wmd = sddmm_spmm_type2(k_pad, km_pad, safe_recip(x), cols, vals)
    return ConvergedWMD(wmd=wmd, n_iter=n_iter, delta=delta)


class BatchConvergedWMD(NamedTuple):
    wmd: jax.Array     # (Q, N) distances
    n_iter: jax.Array  # (Q,) iterations each query actually ran
    delta: jax.Array   # (Q,) final per-query relative |dx|_inf


@functools.partial(jax.jit, static_argnames=("max_iter",))
def sinkhorn_wmd_converged_batch(sel_idx: jax.Array, r_sel: jax.Array,
                                 cols: jax.Array, vals: jax.Array,
                                 vecs: jax.Array, lamb: float, max_iter: int,
                                 tol: float = 1e-6,
                                 row_mask: jax.Array | None = None
                                 ) -> BatchConvergedWMD:
    """Batched early-exit solve with **per-query convergence masking**.

    All Q queries advance through the shared-gather batched iteration, but a
    query whose relative iterate delta drops below ``tol`` is *frozen*: its x
    block is carried forward unchanged (`jnp.where` on the per-query active
    mask) while stragglers keep iterating. Freezing is exact -- a frozen
    query's trajectory is bit-identical to one that stopped at its own
    convergence point, because queries never interact. The loop exits when
    every query has converged or at ``max_iter``. (The loop core is
    `sparse_sinkhorn.batched_sinkhorn_loop`, shared with the fixed-budget
    solver and the distributed shard_map engine.)

    sel_idx/r_sel/row_mask are (Q, v_r) bucketed queries (see pad_query).
    The solve is unchunked, so the freeze masks and the reported
    n_iter/delta are global over the doc axis -- the single-host oracle of
    `core.distributed`'s all-shards convergence vote. K is gathered once,
    before the loop (`sparse_sinkhorn.solve_contractions`).
    """
    pre = precompute_batch(sel_idx, r_sel, vecs, lamb, row_mask)
    k_pad = pad_k(pre.K)
    km_pad = pad_k(pre.KM)
    q, v_r = r_sel.shape
    n = cols.shape[0]
    x0 = jnp.full((q, v_r, n), 1.0 / v_r, dtype=pre.K.dtype)

    type1, final = solve_contractions(k_pad, km_pad, pre.r, cols, vals)
    x, delta, n_iter = batched_sinkhorn_loop(
        lambda x: type1(safe_recip(x)), x0, max_iter=max_iter, tol=tol)
    wmd = final(safe_recip(x))
    return BatchConvergedWMD(wmd=wmd, n_iter=n_iter, delta=delta)
