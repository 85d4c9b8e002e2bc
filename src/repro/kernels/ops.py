"""Public jit'd entry points for the Pallas kernels.

Responsibilities:
  * backend dispatch -- ``interpret=True`` on the CPU backend only, so the
    same call sites validate on CPU and run Mosaic on TPU (other backends
    raise);
  * alignment padding -- v_r to the f32 sublane multiple (8), docs to the
    doc-tile, so callers never think about hardware shapes;
  * the vocab-chunked driver (`sddmm_spmm_chunked`) that replays the
    multi-chip vocab decomposition on one chip when K does not fit VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import cdist as _cdist_kernel
from repro.kernels import kexp as _kexp_kernel
from repro.kernels import lcrwmd as _lcrwmd_kernel
from repro.kernels import rwmd as _rwmd_kernel
from repro.kernels import sddmm_spmm as _sddmm_spmm
from repro.kernels._pad import pad_axis


def _interpret() -> bool:
    """Mosaic on a TPU, the Pallas interpreter on the CPU, and an error on
    any other backend: an interpreted kernel there would run silently slow
    and under a device label it never touched."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise NotImplementedError(
            f"Pallas kernels run on tpu (Mosaic) or cpu (interpret mode), "
            f"not on {backend!r}")
    return backend == "cpu"


_pad_to = pad_axis


def sddmm_spmm_type1(k_pad: jax.Array, r_sel: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array, *,
                     docs_blk: int = 8) -> jax.Array:
    """Fused Sinkhorn iteration body; see kernels.sddmm_spmm.

    Pads v_r to 8 (r pads with 1.0 to keep 1/r finite) and docs to docs_blk;
    un-pads the result. K's zero pad column must already be present.
    """
    v_r, n = u.shape
    k_p = _pad_to(k_pad, 0, 8)
    r_p = _pad_to(r_sel, 0, 8, value=1.0)
    u_p = _pad_to(_pad_to(u, 0, 8), 1, docs_blk)
    # padded docs gather the K pad column (id Vloc) with val 0 -> contribute 0
    cols_p = _pad_to(cols, 0, docs_blk, value=k_pad.shape[1] - 1)
    vals_p = _pad_to(vals, 0, docs_blk)
    x = _sddmm_spmm.sddmm_spmm_type1(
        k_p, r_p, u_p, cols_p, vals_p,
        docs_blk=docs_blk, interpret=_interpret())
    return x[:v_r, :n]


def sddmm_spmm_type2(k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array, *,
                     docs_blk: int = 8) -> jax.Array:
    """Fused final-distance kernel; returns (N,) WMD."""
    v_r, n = u.shape
    k_p = _pad_to(k_pad, 0, 8)
    km_p = _pad_to(km_pad, 0, 8)
    u_p = _pad_to(_pad_to(u, 0, 8), 1, docs_blk)
    cols_p = _pad_to(cols, 0, docs_blk, value=k_pad.shape[1] - 1)
    vals_p = _pad_to(vals, 0, docs_blk)
    wmd = _sddmm_spmm.sddmm_spmm_type2(
        k_p, km_p, u_p, cols_p, vals_p,
        docs_blk=docs_blk, interpret=_interpret())
    return wmd[:n]


def rwmd_bound_batch(m_pad: jax.Array, cols: jax.Array, vals: jax.Array, *,
                     docs_blk: int = 8,
                     q_blk: int | None = None) -> jax.Array:
    """Batched doc-side RWMD min-SDDMM; see kernels.rwmd. Returns (Q, N).

    Pads v_r to 8 and Q to q_blk with **+inf** (a pad query row must never
    win the min -- the opposite of the K stripes' zero pad rows), docs to
    docs_blk with ELL pad slots (val 0 -> masked out); un-pads the result
    and finites all-pad filler-query rows to 0 (the engine's distance for
    them is exactly 0, so a 0 bound can never prune them).
    """
    q, v_r, _ = m_pad.shape
    n = cols.shape[0]
    if q_blk is None:
        q_blk = min(q, 8)
    inf = float("inf")
    m_p = _pad_to(_pad_to(m_pad, 1, 8, value=inf), 0, q_blk, value=inf)
    cols_p = _pad_to(cols, 0, docs_blk, value=m_pad.shape[-1] - 1)
    vals_p = _pad_to(vals, 0, docs_blk)
    lb = _rwmd_kernel.rwmd_bound_batch(
        m_p, cols_p, vals_p,
        docs_blk=docs_blk, q_blk=q_blk, interpret=_interpret())
    lb = lb[:q, :n]
    return jnp.where(jnp.isfinite(lb), lb, 0.0)


def lc_rwmd_bound_batch(minm: jax.Array, cols: jax.Array, vals: jax.Array, *,
                        docs_blk: int = 8,
                        q_blk: int | None = None) -> jax.Array:
    """Batched LC-RWMD sparse dot; see kernels.lcrwmd. Returns (Q, N).

    Pads Q to q_blk with **+inf** minm rows (matching the all-+inf rows
    real filler queries carry), docs to docs_blk with ELL pad slots (val 0
    -> masked out); un-pads the result and finites all-pad filler-query
    rows to 0 (the engine's distance for them is exactly 0, so a 0 bound
    can never prune them).
    """
    q = minm.shape[0]
    n = cols.shape[0]
    if q_blk is None:
        q_blk = min(q, 8)
    minm_p = _pad_to(minm, 0, q_blk, value=float("inf"))
    cols_p = _pad_to(cols, 0, docs_blk, value=minm.shape[-1] - 1)
    vals_p = _pad_to(vals, 0, docs_blk)
    lb = _lcrwmd_kernel.lc_rwmd_bound_batch(
        minm_p, cols_p, vals_p,
        docs_blk=docs_blk, q_blk=q_blk, interpret=_interpret())
    lb = lb[:q, :n]
    return jnp.where(jnp.isfinite(lb), lb, 0.0)


def sddmm_spmm_chunked(k_chunks: jax.Array, r_sel: jax.Array, u: jax.Array,
                       cols_chunks: jax.Array, vals_chunks: jax.Array, *,
                       docs_blk: int = 8) -> jax.Array:
    """Single-chip driver for K too large for VMEM: vocab-chunked type1.

    Args mirror the multi-chip layout (`core.formats.rebucket_for_vocab_shards`):
      k_chunks:    (S, v_r, Vc+1) -- per-chunk K slice with zero pad column.
      cols_chunks: (S, N, nnz_c)  -- localized ids per chunk.
      vals_chunks: (S, N, nnz_c)
    Partial x contributions are summed across chunks (the psum of the
    distributed engine becomes an on-chip accumulation).
    """
    def chunk(carry, operand):
        k_c, cols_c, vals_c = operand
        x_c = sddmm_spmm_type1(k_c, jnp.ones_like(r_sel), u, cols_c, vals_c,
                               docs_blk=docs_blk)
        return carry + x_c, None

    v_r, n = u.shape
    x0 = jnp.zeros((v_r, n), u.dtype)
    x, _ = jax.lax.scan(chunk, x0, (k_chunks, cols_chunks, vals_chunks))
    return x / r_sel[:, None]


def cdist(a: jax.Array, b: jax.Array, *, v_tile: int = 512,
          squared: bool = False) -> jax.Array:
    """Tiled euclidean distance. Pads v_r to 8 and w to 128 lanes (the kernel
    itself pads V to v_tile and slices back)."""
    v_r = a.shape[0]
    a_p = _pad_to(_pad_to(a, 1, 128), 0, 8)
    b_p = _pad_to(b, 1, 128)
    out = _cdist_kernel.cdist(a_p, b_p, v_tile=v_tile, squared=squared,
                              interpret=_interpret())
    return out[:v_r]


def cdist_kexp(a: jax.Array, b: jax.Array, *, lamb: float,
               v_tile: int = 512) -> tuple[jax.Array, jax.Array]:
    """Fused precompute -> (K, K.*M), un-padded to (v_r, V)."""
    v_r = a.shape[0]
    a_p = _pad_to(_pad_to(a, 1, 128), 0, 8)
    b_p = _pad_to(b, 1, 128)
    k, km = _kexp_kernel.cdist_kexp(a_p, b_p, lamb=lamb, v_tile=v_tile,
                                    interpret=_interpret())
    return k[:v_r], km[:v_r]


def cdist_kexp_rows(a: jax.Array, b: jax.Array, *, lamb: float,
                    rows_blk: int = 8, v_tile: int = 512
                    ) -> tuple[jax.Array, jax.Array]:
    """Row-subset fused precompute (the cache-miss path of `core.kcache`):
    a (m, w) miss-row embeddings, b (V, w) -> (K, K.*M), each (m, V).

    Unlike `cdist_kexp` the row operand is not VMEM-resident -- the kernel
    grids over (row tiles x vocab tiles), so m is unbounded. Pads w to 128
    lanes here; the kernel pads rows to rows_blk and V to v_tile.
    """
    m = a.shape[0]
    a_p = _pad_to(a, 1, 128)
    b_p = _pad_to(b, 1, 128)
    k, km = _kexp_kernel.cdist_kexp_rows(a_p, b_p, lamb=lamb,
                                         rows_blk=rows_blk, v_tile=v_tile,
                                         interpret=_interpret())
    return k[:m], km[:m]
