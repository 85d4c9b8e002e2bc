"""Pallas TPU kernels for the paper's fused SDDMM-SpMM (type1 and type2).

TPU mapping of the paper's fusion (DESIGN.md section 2): the kernel gathers
each sampled K column from VMEM **once** and feeds it to both the SDDMM dot
product and the SpMM accumulation, so the column's HBM->VMEM traffic is paid
once instead of twice. The on-the-fly transpose of the paper becomes the
BlockSpec layout: K is held column-major-gatherable (v_r contiguous), u and x
live as (v_r, docs) tiles.

VMEM contract: the kernel holds the *local vocab slice* of K (v_r x (Vloc+1))
resident in VMEM across all grid steps (constant index_map). This is exactly
the shape produced by the vocab-sharded distributed engine
(`core.distributed`), where Vloc = V / model_parallelism <= ~8k. For
single-chip V=100k, `ops.sddmm_spmm_chunked` replays the same decomposition
over host-side vocab chunks -- the kernel and the multi-chip algorithm share
one structure.

Grid: one step per tile of ``docs_blk`` documents. Each step:
  for j in docs_blk:                (lax.fori_loop)
    for s in nnz_max:               (lax.fori_loop)
      kcol = K[:, cols[j,s]]        <- single VMEM gather (dynamic slice)
      w    = <kcol, u[:,j]>         SDDMM half
      v    = vals[j,s] / w
      acc += kcol * v               SpMM half (same kcol, in-register)
  x[:, tile_j] = acc / r            (type1)   or
  wmd[tile_j]  = <u[:,j], acc_km>   (type2, acc over K.*M columns)

A production Mosaic build would stage the cols tile through scalar prefetch
(PrefetchScalarGridSpec) and issue the K-column loads as async copies; the
dynamic-slice form below expresses the same dataflow and validates bit-for-bit
in interpret mode (this container is CPU-only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TINY = 1e-30  # see core.sparse_sinkhorn.safe_recip


def _type1_kernel(k_ref, r_ref, u_ref, cols_ref, vals_ref, x_ref):
    """One doc tile: x[:, tile] = diag(1/r) . SpMM(K, SDDMM(K, u, c))."""
    v_r = u_ref.shape[0]
    docs_blk, nnz_max = cols_ref.shape
    dtype = x_ref.dtype

    def doc_body(j, _):
        u_j = u_ref[:, j]                                    # (v_r,)

        def slot_body(s, acc):
            col = cols_ref[j, s]
            kcol = k_ref[:, col]                             # gather ONCE
            w = jnp.sum(kcol * u_j)                          # SDDMM dot
            val = vals_ref[j, s]
            v = jnp.where(val != 0.0,
                          val / jnp.maximum(w, TINY), 0.0)
            return acc + kcol * v                            # SpMM, in-register

        acc = jax.lax.fori_loop(
            0, nnz_max, slot_body, jnp.zeros((v_r,), dtype))
        x_ref[:, j] = acc / r_ref[:, 0]
        return 0

    jax.lax.fori_loop(0, docs_blk, doc_body, 0)


def _type2_kernel(k_ref, km_ref, u_ref, cols_ref, vals_ref, wmd_ref):
    """One doc tile: wmd[tile] = sum_i u .* SpMM(K.*M, SDDMM(K, u, c))."""
    v_r = u_ref.shape[0]
    docs_blk, nnz_max = cols_ref.shape
    dtype = wmd_ref.dtype

    def doc_body(j, _):
        u_j = u_ref[:, j]

        def slot_body(s, acc):
            col = cols_ref[j, s]
            kcol = k_ref[:, col]                             # shared gather
            kmcol = km_ref[:, col]
            w = jnp.sum(kcol * u_j)
            val = vals_ref[j, s]
            v = jnp.where(val != 0.0,
                          val / jnp.maximum(w, TINY), 0.0)
            return acc + kmcol * v

        acc = jax.lax.fori_loop(
            0, nnz_max, slot_body, jnp.zeros((v_r,), dtype))
        wmd_ref[0, j] = jnp.sum(u_j * acc)
        return 0

    jax.lax.fori_loop(0, docs_blk, doc_body, 0)


@functools.partial(jax.jit,
                   static_argnames=("docs_blk", "interpret"))
def sddmm_spmm_type1(k_pad: jax.Array, r_sel: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array, *,
                     docs_blk: int = 8, interpret: bool = False) -> jax.Array:
    """Fused iteration body. Shapes: k_pad (v_r, Vloc+1), r_sel (v_r,),
    u (v_r, N), cols/vals (N, nnz_max) with N % docs_blk == 0. Returns x
    (v_r, N)."""
    v_r, n = u.shape
    _, nnz_max = cols.shape
    grid = (n // docs_blk,)
    return pl.pallas_call(
        _type1_kernel,
        grid=grid,
        in_specs=[
            # K slice resident in VMEM across the whole grid (constant index).
            pl.BlockSpec(k_pad.shape, lambda i: (0, 0)),
            pl.BlockSpec((v_r, 1), lambda i: (0, 0)),
            pl.BlockSpec((v_r, docs_blk), lambda i: (0, i)),
            pl.BlockSpec((docs_blk, nnz_max), lambda i: (i, 0)),
            pl.BlockSpec((docs_blk, nnz_max), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((v_r, docs_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((v_r, n), u.dtype),
        interpret=interpret,
    )(k_pad, r_sel[:, None], u, cols, vals)


@functools.partial(jax.jit,
                   static_argnames=("docs_blk", "interpret"))
def sddmm_spmm_type2(k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array, *,
                     docs_blk: int = 8, interpret: bool = False) -> jax.Array:
    """Fused final distance (3 dense + 2 sparse inputs). Returns wmd (N,)."""
    v_r, n = u.shape
    _, nnz_max = cols.shape
    grid = (n // docs_blk,)
    out = pl.pallas_call(
        _type2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(k_pad.shape, lambda i: (0, 0)),
            pl.BlockSpec(km_pad.shape, lambda i: (0, 0)),
            pl.BlockSpec((v_r, docs_blk), lambda i: (0, i)),
            pl.BlockSpec((docs_blk, nnz_max), lambda i: (i, 0)),
            pl.BlockSpec((docs_blk, nnz_max), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, docs_blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), u.dtype),
        interpret=interpret,
    )(k_pad, km_pad, u, cols, vals)
    return out[0]
