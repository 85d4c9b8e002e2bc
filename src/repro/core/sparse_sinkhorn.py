"""PASWD: the paper's sparse-heavy Sinkhorn-WMD with fused SDDMM-SpMM.

This is the paper's contribution, re-architected for TPU (DESIGN.md sections
2-3). The document-frequency matrix is doc-major padded ELL (`core.formats`);
the SDDMM samples only the nnz dot products, and the fusion reuses the
*single* VMEM gather of K columns for both the SDDMM contraction and the SpMM
contraction (K_over_r differs from K only by the per-row 1/r scale):

    SDDMM : w[j,k] = sum_i K[i, cols[j,k]] * u[i,j]
            v[j,k] = vals[j,k] / w[j,k]
    SpMM  : x[i,j] = (1/r[i]) * sum_k K[i, cols[j,k]] * v[j,k]

type2 (final distance) swaps the SpMM operand to K.*M and reduces in-kernel:

    WMD[j] = sum_i u[i,j] * sum_k (K.*M)[i, cols[j,k]] * v[j,k]

The single-query solver (`sinkhorn_wmd_sparse`) has three execution paths,
selected by ``impl`` (see `_resolve_impl`); it is the paper's Fig. 9
baseline and the oracle of the per-path benchmarks, and no served program
calls it:
  * "fused"    -- one gather feeds both contractions (jnp).
  * "unfused"  -- separate SDDMM / SpMM with independent gathers, mirroring
                  the paper's pre-fusion baseline (Fig. 9 numerator).
  * "kernel"   -- `repro.kernels.ops` Pallas kernels (interpret=True on CPU).

All paths consume K padded with one trailing zero column so ELL pad slots
(col == V) contribute exactly zero.

Batched engine
--------------
The batched solvers (`sinkhorn_wmd_sparse_batch`, the distributed solve
of `core.distributed`) run one contraction path: the fused one, with K
gathered once per solve. K[:, :, cols] depends on neither the iterate nor
the iteration, so `solve_contractions` gathers it once, before the
Sinkhorn loop, into a (Q, v_r, N, nnz) block (nnz on the TPU's 128-wide
lane axis) that every iteration and the final pass read; the final pass
gathers K.*M once more. On one v5e at paper_5k a gather in every
iteration was 95% of the solve. Both gathers read a row table
(`k_row_table`): the stripes laid out (V+1, Q*v_r), built once per solve,
outside any chunk loop, so each ELL slot fetches one contiguous row of
Q*v_r floats for all Q queries (`gather_k_rows`), where the (Q, V+1, v_r)
table of `gather_k_batch` has it fetch Q strided rows of v_r. The gathered
(N, nnz, Q*v_r) rows are relaid out once into the block.

The block grows with N, so ``docs_chunk`` bounds it by chunking the SOLVE
over documents: docs are independent OT problems, so the chunk loop sits
OUTSIDE the whole Sinkhorn loop -- each chunk gathers its own block and
runs all of its iterations while its ``(Q, v_r, docs_chunk)`` iterate
stays cache-resident. Results are bitwise per chunk: both contractions
reduce within a single doc (over v_r resp. nnz), never across docs.
Non-dividing N is handled by padding docs with ELL pad slots (col = V ->
the zero K column, val = 0), whose outputs are sliced off. Here the chunk
loop is unrolled in-trace (`_chunk_over_docs`, which rolls up into
`map_doc_chunks` past MAX_UNROLLED_CHUNKS); the served solve of
`core.distributed` sweeps its chunks with the rolled `map_doc_chunks`.

Early exit: `batched_sinkhorn_loop` is the shared while-loop core -- per
query, iteration stops contributing writes once its relative iterate delta
drops below ``tol`` (freeze masks), and the loop exits when all queries
converge or ``max_iter`` hits. With ``tol = 0.0`` no query ever freezes
(``delta >= 0`` always holds), so results equal the fixed-``max_iter``
loop exactly; the solvers skip the loop's bookkeeping entirely in that
case and run a plain fori_loop.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.cost_matrix import cdist
from repro.core.sinkhorn import SinkhornPrecompute, precompute

_IMPLS = ("fused", "unfused", "kernel")

# Every contraction of the solve runs in float32. A TPU's default precision
# rounds a float32 dot's operands to bfloat16 wherever XLA puts it on the
# MXU, as it did for the SDDMM (K and u) at news20's v_r 288. Where XLA
# keeps a contraction on the vector units (paper_5k's v_r 32) this changes
# nothing.
F32 = jax.lax.Precision.HIGHEST

# Reciprocal guard: K = exp(-lamb*M) underflows f32 for far word pairs, and
# the u = 1/x nonlinearity amplifies it to inf*0 = nan. Clamping the
# denominator at TINY is exact for healthy values and replaces inf by a huge
# finite number otherwise (the paper sidesteps this with f64 inputs).
TINY = 1e-30


def safe_recip(x: jax.Array) -> jax.Array:
    return 1.0 / jnp.maximum(x, TINY)


def pad_k(k: jax.Array) -> jax.Array:
    """Append a zero column: gathers of the ELL pad id (== V) read zeros.

    Works on both (v_r, V) single-query and (Q, v_r, V) batched stripes --
    the pad column is always appended on the vocab (last) axis.
    """
    widths = [(0, 0)] * (k.ndim - 1) + [(0, 1)]
    return jnp.pad(k, widths)


# ---------------------------------------------------------------------------
# jnp building blocks (also serve as kernel oracles via kernels/ref.py)
# ---------------------------------------------------------------------------

def gather_k(k_pad: jax.Array, cols: jax.Array) -> jax.Array:
    """Gather K columns per ELL slot: (v_r, V+1), (N, nnz) -> (N, nnz, v_r)."""
    with jax.named_scope("wmd.gather"):
        return k_pad.T[cols]


def sddmm(k_pad: jax.Array, u: jax.Array, cols: jax.Array,
          vals: jax.Array) -> jax.Array:
    """Sampled dense-dense matmul: v[j,k] = vals[j,k] / (K^T u)[cols[j,k], j]."""
    kg = gather_k(k_pad, cols)                       # gather #1
    w = jnp.einsum("nki,in->nk", kg, u, precision=F32)
    return jnp.where(vals != 0.0, vals * safe_recip(w), 0.0)


def spmm(kor_pad: jax.Array, v: jax.Array, cols: jax.Array) -> jax.Array:
    """x[i,j] = sum_k K_over_r[i, cols[j,k]] * v[j,k] -- re-gathers K."""
    kg = gather_k(kor_pad, cols)                     # gather #2 (unfused cost)
    return jnp.einsum("nki,nk->in", kg, v, precision=F32)


def sddmm_spmm_type1(k_pad: jax.Array, r_sel: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array) -> jax.Array:
    """Fused iteration body: one gather feeds both contractions."""
    kg = gather_k(k_pad, cols)                       # the ONLY gather
    w = jnp.einsum("nki,in->nk", kg, u, precision=F32)
    v = jnp.where(vals != 0.0, vals * safe_recip(w), 0.0)
    x = jnp.einsum("nki,nk->in", kg, v, precision=F32)
    return x / r_sel[:, None]


def sddmm_spmm_type2(k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
                     cols: jax.Array, vals: jax.Array) -> jax.Array:
    """Fused final distance: 3 dense (K, K.*M, u) + 2 sparse (cols, vals)."""
    kg = gather_k(k_pad, cols)
    kmg = gather_k(km_pad, cols)
    w = jnp.einsum("nki,in->nk", kg, u, precision=F32)
    v = jnp.where(vals != 0.0, vals * safe_recip(w), 0.0)
    xm = jnp.einsum("nki,nk->in", kmg, v, precision=F32)
    return jnp.sum(u * xm, axis=0)                   # (N,)


def _type1_unfused(k_pad: jax.Array, r_sel: jax.Array, u: jax.Array,
                   cols: jax.Array, vals: jax.Array) -> jax.Array:
    # independent gathers, with a barrier so XLA cannot CSE them back
    # into the fused form (keeps the Fig. 9 baseline honest).
    v = sddmm(k_pad, u, cols, vals)
    v = jax.lax.optimization_barrier(v)
    return spmm(k_pad / r_sel[:, None], v, cols)


def _resolve_impl(kind: str, impl: str):
    """The single-query solver's impl dispatch table. kind: "type1"
    (iteration contraction, signature (k_pad, r_sel, u, cols, vals)) or
    "type2" (final distance, signature (k_pad, km_pad, u, cols, vals))."""
    if impl not in _IMPLS:
        raise ValueError(f"impl must be one of {_IMPLS}, got {impl!r}")
    if impl == "kernel":
        from repro.kernels import ops
        return {"type1": ops.sddmm_spmm_type1,
                "type2": ops.sddmm_spmm_type2}[kind]
    # the unfused baseline shares the fused final distance (the paper's
    # Fig. 9 baseline differs only in the iteration body).
    if kind == "type2":
        return sddmm_spmm_type2
    return _type1_unfused if impl == "unfused" else sddmm_spmm_type1


def _iteration(impl: str, pre_kpad: jax.Array, r_sel: jax.Array,
               x: jax.Array, cols: jax.Array, vals: jax.Array) -> jax.Array:
    return _resolve_impl("type1", impl)(pre_kpad, r_sel, safe_recip(x), cols,
                                        vals)


def _final(impl: str, k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
           cols: jax.Array, vals: jax.Array) -> jax.Array:
    return _resolve_impl("type2", impl)(k_pad, km_pad, u, cols, vals)


# ---------------------------------------------------------------------------
# Full solver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_iter", "impl"))
def sinkhorn_wmd_sparse(sel_idx: jax.Array, r_sel: jax.Array,
                        cols: jax.Array, vals: jax.Array, vecs: jax.Array,
                        lamb: float, max_iter: int,
                        impl: str = "fused") -> jax.Array:
    """Sparse PASWD Sinkhorn-WMD. Returns (N,) distances.

    Args:
      sel_idx: (v_r,) nonzero-word indices of the query (host-selected).
      r_sel:   (v_r,) normalized query frequencies.
      cols:    (N, nnz_max) ELL word ids (pad == V).
      vals:    (N, nnz_max) ELL normalized counts (pad == 0).
      vecs:    (V, w) embeddings.
      impl:    "fused" | "unfused" | "kernel".
    """
    pre = precompute(sel_idx, r_sel, vecs, lamb)
    return sinkhorn_wmd_sparse_pre(pre, cols, vals, max_iter, impl)


def sinkhorn_wmd_sparse_pre(pre: SinkhornPrecompute, cols: jax.Array,
                            vals: jax.Array, max_iter: int,
                            impl: str = "fused") -> jax.Array:
    """Solver core on precomputed matrices (shared with the distributed path)."""
    k_pad = pad_k(pre.K)
    km_pad = pad_k(pre.KM)
    v_r = pre.r.shape[0]
    n = cols.shape[0]
    x0 = jnp.full((v_r, n), 1.0 / v_r, dtype=pre.K.dtype)

    def body(_, x):
        return _iteration(impl, k_pad, pre.r, x, cols, vals)

    x = jax.lax.fori_loop(0, max_iter, body, x0)
    u = safe_recip(x)
    return _final(impl, k_pad, km_pad, u, cols, vals)


# ---------------------------------------------------------------------------
# Multi-query batched engine: (Q, v_r, N) with ONE shared ELL gather
# ---------------------------------------------------------------------------
#
# The paper batches one query against N docs; the production axis on top of
# that is Q concurrent queries. The ELL structure (cols, vals) is a property
# of the *corpus*, identical for every query, so the irregular part of the
# iteration -- the gather of K columns at the nonzero word-ids -- becomes ONE
# batched gather op serving all Q queries (same index set, Q stripes), laid
# out (Q, v_r, N, nnz) for the fused contractions (see gather_k_block), and
# made once per solve. Everything downstream is dense einsum with a leading
# Q batch axis.
#
# Mixed-size queries ride the exact mask-based padding of core.distributed:
# pad rows carry r = 1 and a zeroed K row, so they contribute exactly zero
# to every w, x and WMD (no epsilon approximations).


class BatchedSinkhornPrecompute(NamedTuple):
    """Per-query iteration-invariant stripes, stacked on a leading Q axis."""

    K: jax.Array   # (Q, v_r, V) exp(-lambda * M), pad rows zeroed
    KM: jax.Array  # (Q, v_r, V) K .* M
    r: jax.Array   # (Q, v_r) pad rows carry 1.0


def precompute_batch(sel_idx: jax.Array, r_sel: jax.Array, vecs: jax.Array,
                     lamb: float, row_mask: jax.Array | None = None
                     ) -> BatchedSinkhornPrecompute:
    """Batched K / K.*M stripes for Q queries bucketed to a common v_r.

    Args:
      sel_idx:  (Q, v_r) word ids per query (pad slots point at word 0).
      r_sel:    (Q, v_r) frequencies (pad rows = 1.0, see pad_query).
      vecs:     (V, w) embeddings.
      row_mask: (Q, v_r) 1.0 for real rows, 0.0 for pad rows; None = all real.
    """
    m = jax.vmap(lambda a: cdist(a, vecs))(vecs[sel_idx])    # (Q, v_r, V)
    k = jnp.exp(-lamb * m)
    if row_mask is not None:
        k = k * row_mask[..., None]
    return BatchedSinkhornPrecompute(K=k, KM=k * m, r=r_sel)


def gather_k_batch(k_pad: jax.Array, cols: jax.Array) -> jax.Array:
    """One batched gather serving all Q queries.

    (Q, v_r, V+1), (N, nnz) -> (Q, N, nnz, v_r): one gather op whose batch
    dims (q, n) lead, from the (Q, V+1, v_r) table, so each ELL slot
    fetches Q rows of v_r floats, one per query, V+1 rows apart (the
    (N, nnz, Q, v_r) alternative forces XLA to re-lay it out before every
    dot inside the loop -- measured ~2.3x slower on CPU). The RWMD bound
    contracts it in this layout; the solve reads the row table instead
    (`gather_k_rows`).
    """
    with jax.named_scope("wmd.gather"):
        return jnp.transpose(k_pad, (0, 2, 1))[:, cols]


def gather_k_block(k_pad: jax.Array, cols: jax.Array) -> jax.Array:
    """K at every ELL slot as a (Q, v_r, N, nnz) block: (Q, v_r, V+1),
    (N, nnz) -> (Q, v_r, N, nnz), by `gather_k_batch` and a relayout.

    nnz is the minor axis, so on a TPU the block fills the 128-wide lanes
    with ELL slots; with v_r (often 32) there it would be padded 4x. This
    is the layout of the block a solve gathers once and carries through
    its Sinkhorn loop (`solve_contractions`), which gathers it from the row
    table (`gather_k_rows`, bitwise the same block);
    `sddmm_spmm_type2_batch` gathers it here, per call.
    """
    kg = gather_k_batch(k_pad, cols)
    with jax.named_scope("wmd.gather"):
        return jnp.transpose(kg, (0, 3, 1, 2))


def k_row_table(k_pad: jax.Array) -> jax.Array:
    """The row table of a solve's stripes: (Q, v_r, V+1) -> (V+1, Q*v_r),
    row c holding word c's K column of every query, the zero pad column
    its last row. Built once per solve, outside any chunk loop."""
    q, v_r, v1 = k_pad.shape
    return jnp.transpose(k_pad, (2, 0, 1)).reshape(v1, q * v_r)


def gather_k_rows(table: jax.Array, cols: jax.Array, q: int) -> jax.Array:
    """The `gather_k_block` block from a `k_row_table`: (V+1, Q*v_r),
    (N, nnz) -> (Q, v_r, N, nnz). Each ELL slot fetches one contiguous row
    of Q*v_r floats; the (N, nnz, Q*v_r) rows are relaid out once.

    The rows are transposed before Q*v_r is split: split first, the split
    of the tiled minor axis is a relayout of its own (at news20's v_r 288,
    one v5e spent 0.98 s of a 20 s window on each of the two)."""
    n, nnz = cols.shape
    with jax.named_scope("wmd.gather"):
        rows = table[cols]                           # (N, nnz, Q*v_r)
        return jnp.transpose(rows, (2, 0, 1)).reshape(q, -1, n, nnz)


def type1_from_block(kg: jax.Array, r_sel: jax.Array, u: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """The fused iteration on a gathered (Q, v_r, N, nnz) K block: SDDMM
    w[q,n,k] = sum_i kg[q,i,n,k] u[q,i,n], v = vals / w on the support,
    then SpMM x[q,i,n] = sum_k kg[q,i,n,k] v[q,n,k], scaled by 1/r."""
    w = jnp.einsum("qink,qin->qnk", kg, u, precision=F32)
    v = jnp.where(vals[None] != 0.0, vals[None] * safe_recip(w), 0.0)
    x = jnp.einsum("qink,qnk->qin", kg, v, precision=F32)
    return x / r_sel[:, :, None]


def type2_from_block(kg: jax.Array, kmg: jax.Array, u: jax.Array,
                     vals: jax.Array) -> jax.Array:
    """The fused final distance on gathered (Q, v_r, N, nnz) K and K.*M
    blocks: (Q, N) WMD.

    The per-doc reduction is spelled sum_k v * <(K.*M) col, u> -- the u
    contraction happens inside the dot_general and the outer reduce runs
    over the nnz (last) axis, whose extent is chunk-independent. That keeps
    a solve's ``docs_chunk`` bitwise exact.
    """
    w = jnp.einsum("qink,qin->qnk", kg, u, precision=F32)
    v = jnp.where(vals[None] != 0.0, vals[None] * safe_recip(w), 0.0)
    wm = jnp.einsum("qink,qin->qnk", kmg, u, precision=F32)
    return jnp.sum(wm * v, axis=-1)                  # (Q, docs)


# Above this many chunks the doc loop rolls up into a lax.map: the HLO
# stays O(1) in S at the cost of defeating XLA's cross-op gather fusion
# inside the loop body (measured up to ~4x slower on CPU) -- callers wanting
# peak throughput should pick docs_chunk so S stays under this.
MAX_UNROLLED_CHUNKS = 64


def _chunk_over_docs(f, u: jax.Array, cols: jax.Array, vals: jax.Array,
                     docs_chunk: int | None, pad_col: int) -> jax.Array:
    """Apply ``f(u_c, cols_c, vals_c)`` over static N-chunks (cache blocking).

    ``f`` maps a doc slice to an output whose LAST axis is the doc axis.
    Chunking is bitwise exact (see module docstring); a non-dividing N is
    padded with ELL pad slots (col = pad_col -> zero K column, val = 0) and
    the pad docs are sliced off the output.

    The chunk loop is UNROLLED into the trace (independent per-chunk chains
    concatenated on the doc axis): each chain keeps XLA's gather-into-
    contraction fusion, so the gathered (Q, docs_chunk, nnz, v_r) block is
    never materialized whole. Past MAX_UNROLLED_CHUNKS chunks it rolls up
    into `map_doc_chunks`, where HLO size matters more than the fusion loss.
    """
    n = cols.shape[0]
    if not docs_chunk or docs_chunk >= n:   # None and 0 both mean unchunked
        return f(u, cols, vals)
    s = -(-n // docs_chunk)
    if s > MAX_UNROLLED_CHUNKS:
        return join_doc_chunks(
            map_doc_chunks(lambda c, v, u_c: f(u_c, c, v), cols, vals,
                           docs_chunk, pad_col, u), n)
    cols, vals, u = _pad_docs(cols, vals, s * docs_chunk - n, pad_col, u)
    outs = [f(u[:, :, c * docs_chunk:(c + 1) * docs_chunk],
              cols[c * docs_chunk:(c + 1) * docs_chunk],
              vals[c * docs_chunk:(c + 1) * docs_chunk])
            for c in range(s)]
    return jnp.concatenate(outs, axis=-1)[..., :n]


def _pad_docs(cols, vals, pad: int, pad_col: int, u=None):
    """Append ``pad`` empty documents: ELL pad slots (col = pad_col, the
    zero K column; val = 0), which solve to 0; ``u``'s doc axis is last."""
    if not pad:
        return cols, vals, u
    cols = jnp.pad(cols, ((0, pad), (0, 0)), constant_values=pad_col)
    vals = jnp.pad(vals, ((0, pad), (0, 0)))
    if u is not None:
        u = jnp.pad(u, ((0, 0),) * (u.ndim - 1) + ((0, pad),))
    return cols, vals, u


def map_doc_chunks(f, cols: jax.Array, vals: jax.Array, docs_chunk: int,
                   pad_col: int, u: jax.Array | None = None):
    """``f(cols_c, vals_c[, u_c])`` over equal doc chunks in one rolled loop
    (`jax.lax.map`): the doc axis is padded with empty documents
    (`_pad_docs`) to a whole number of chunks. Returns ``f``'s outputs (any
    pytree) stacked on a leading chunk axis; `join_doc_chunks` lays a
    doc-axis output back out. One chunk's working set is live at a time,
    and the program holds one copy of ``f`` whatever the chunk count."""
    n, nnz = cols.shape
    s = -(-n // docs_chunk)
    cols, vals, u = _pad_docs(cols, vals, s * docs_chunk - n, pad_col, u)
    ops = (cols.reshape(s, docs_chunk, nnz), vals.reshape(s, docs_chunk, nnz))
    if u is not None:
        ops += (jnp.moveaxis(u.reshape(*u.shape[:-1], s, docs_chunk), -2, 0),)
    return jax.lax.map(lambda op: f(*op), ops)


def join_doc_chunks(out: jax.Array, n: int) -> jax.Array:
    """(S, ..., docs_chunk) per-chunk outputs of `map_doc_chunks` ->
    (..., n): the chunks laid end to end on the doc axis, pad docs cut."""
    out = jnp.moveaxis(out, 0, -2)
    return out.reshape(*out.shape[:-2], -1)[..., :n]


def sddmm_spmm_type2_batch(k_pad: jax.Array, km_pad: jax.Array, u: jax.Array,
                           cols: jax.Array, vals: jax.Array) -> jax.Array:
    """Batched fused final distance on one call's own gathers: (Q, N) WMD
    for all queries at once, `gather_k_block` of K and of K.*M, then
    `type2_from_block`. k_pad/km_pad (Q, v_r, V+1), u (Q, v_r, N),
    cols/vals (N, nnz)."""
    return type2_from_block(gather_k_block(k_pad, cols),
                            gather_k_block(km_pad, cols), u, vals)


def k_row_tables(k_pad: jax.Array, km_pad: jax.Array):
    """The (K, K.*M) `k_row_table` pair that `solve_contractions` gathers
    from. A solve that sweeps chunks builds them here, once, outside its
    chunk loop, and passes them to each chunk's `solve_contractions`."""
    with jax.named_scope("wmd.precompute"):
        return k_row_table(k_pad), k_row_table(km_pad)


def solve_contractions(k_pad: jax.Array, km_pad: jax.Array,
                       r_sel: jax.Array, cols: jax.Array, vals: jax.Array, *,
                       rows=None):
    """The (iteration, final) contractions of one solve over one doc slice:
    ``iteration(u)`` -> (Q, v_r, N) x, ``final(u)`` -> (Q, N) distances.

    K is gathered here, once, from its row table (``rows``, the
    `k_row_tables` pair, built here when None) into a (Q, v_r, N, nnz)
    block under ``wmd.precompute`` that every iteration and the final pass
    read; the final pass gathers K.*M once more. Call it outside the
    Sinkhorn loop: the block is then a loop invariant.
    """
    k_rows, km_rows = rows or k_row_tables(k_pad, km_pad)
    q = r_sel.shape[0]
    with jax.named_scope("wmd.precompute"):
        kg = gather_k_rows(k_rows, cols, q)

    def final(u):
        return type2_from_block(kg, gather_k_rows(km_rows, cols, q), u, vals)

    return (lambda u: type1_from_block(kg, r_sel, u, vals)), final


def batched_sinkhorn_loop(iteration, x0: jax.Array, *, max_iter: int,
                          tol: float | jax.Array = 0.0,
                          delta_all_reduce=None):
    """Early-exit Sinkhorn loop with per-query freeze masks (shared core).

    ``iteration`` maps x -> x_new for the whole (Q, v_r, N) batch. A query
    whose relative iterate delta drops below ``tol`` is *frozen*: its x block
    stops being written (freezing is exact -- queries never interact), and
    the loop exits when every query has converged or at ``max_iter``. With
    ``tol = 0.0`` no query ever freezes (``delta >= 0.0`` always holds, even
    at an exact fixpoint), so all ``max_iter`` iterations run and the result
    equals the fixed-``max_iter`` fori_loop exactly -- callers on a fixed
    budget should prefer a plain fori_loop and skip the delta bookkeeping.

    ``delta_all_reduce`` (distributed hook): maps the (Q,) local delta to the
    global one, e.g. a pmax over mesh axes -- required under shard_map where
    each device sees only its doc slice but the vote must be unanimous.

    Returns (x, delta, n_iter): final iterate, per-query relative |dx|_inf,
    and per-query executed iteration counts (Q,) int32.
    """
    q = x0.shape[0]

    def cond(carry):
        _, delta, _, it = carry
        return (it < max_iter) & jnp.any(delta >= tol)

    def body(carry):
        x, delta, n_iter, it = carry
        active = delta >= tol                              # (Q,)
        x_new = iteration(x)
        # relative iterate delta: x spans a huge dynamic range (x ~ K-scale),
        # so an absolute norm would never cross tol for strongly regularized
        # K (same rationale as core.convergence).
        rel = jnp.max(jnp.abs(x_new - x) / (jnp.abs(x) + 1e-30),
                      axis=(1, 2))                         # per-query delta
        if delta_all_reduce is not None:
            rel = delta_all_reduce(rel)
        x = jnp.where(active[:, None, None], x_new, x)     # freeze converged
        delta = jnp.where(active, rel, delta)
        n_iter = n_iter + active.astype(n_iter.dtype)
        return x, delta, n_iter, it + 1

    x, delta, n_iter, _ = jax.lax.while_loop(
        cond, body, (x0, jnp.full((q,), jnp.inf, x0.dtype),
                     jnp.zeros((q,), jnp.int32), jnp.asarray(0)))
    return x, delta, n_iter


@functools.partial(jax.jit,
                   static_argnames=("max_iter", "docs_chunk", "tol"))
def sinkhorn_wmd_sparse_batch(sel_idx: jax.Array, r_sel: jax.Array,
                              cols: jax.Array, vals: jax.Array,
                              vecs: jax.Array, lamb: float, max_iter: int,
                              row_mask: jax.Array | None = None,
                              docs_chunk: int | None = None,
                              tol: float = 0.0) -> jax.Array:
    """Multi-query sparse PASWD Sinkhorn-WMD. Returns (Q, N) distances.

    The per-query math is that of the fused `sinkhorn_wmd_sparse`, with K
    gathered once per solve (see "Batched engine"); queries never interact
    -- the batch axis only amortizes the ELL gather, the dispatch, and the
    K precompute. Matches the per-query solve to fp32 tolerance.

    docs_chunk: cache-block the SOLVE over N-chunks of this size: the chunk
                loop sits outside the Sinkhorn loop (docs are independent
                OT problems), so each chunk's (Q, v_r, docs_chunk) iterate
                stays cache-resident across all its iterations. Identical
                results (fp32; bitwise per chunk).
    tol:        early-exit tolerance for the per-query freeze masks,
                applied per chunk (a query's docs-chunk block freezes when
                ITS delta crosses tol); 0.0 (default) reproduces the
                fixed-``max_iter`` loop exactly.
    """
    pre = precompute_batch(sel_idx, r_sel, vecs, lamb, row_mask)
    return _solve_batch_stripes(pad_k(pre.K), pad_k(pre.KM), pre.r,
                                cols, vals, max_iter=max_iter,
                                docs_chunk=docs_chunk, tol=tol)


def _solve_batch_stripes(k_pad: jax.Array, km_pad: jax.Array,
                         r_sel: jax.Array, cols: jax.Array, vals: jax.Array,
                         *, max_iter: int, docs_chunk: int | None,
                         tol: float) -> jax.Array:
    """Shared solver core on preassembled (Q, v_r, V+1) stripes (with the
    zero pad column already appended -- `core.kcache` stores rows that way,
    so the cached hot path never runs `pad_k`)."""
    q, v_r = r_sel.shape
    n = cols.shape[0]
    x0 = jnp.full((q, v_r, n), 1.0 / v_r, dtype=k_pad.dtype)
    rows = k_row_tables(k_pad, km_pad)

    def solve_chunk(x0_c, cols_c, vals_c):
        # docs never interact across the Sinkhorn iteration (each doc is an
        # independent 2-marginal OT problem), so the chunk loop hoists
        # OUTSIDE the whole solve: each chunk runs all its iterations while
        # its (Q, v_r, docs_chunk) iterate stays cache-resident -- measured
        # 1.5-3.3x over the iteration-major unchunked loop at bulk shapes
        # on CPU. The chunk's K block is gathered here, once, from the row
        # tables built before the chunk loop.
        type1, final = solve_contractions(k_pad, km_pad, r_sel, cols_c,
                                          vals_c, rows=rows)

        def iteration(x):
            return type1(safe_recip(x))

        if tol:
            x, _, _ = batched_sinkhorn_loop(iteration, x0_c,
                                            max_iter=max_iter, tol=tol)
        else:
            # fixed budget: skip the per-iteration delta/freeze bookkeeping
            # entirely (it could never fire -- delta >= 0.0 always holds)
            x = jax.lax.fori_loop(0, max_iter,
                                  lambda _, xx: iteration(xx), x0_c)
        return final(safe_recip(x))

    return _chunk_over_docs(solve_chunk, x0, cols, vals, docs_chunk,
                            pad_col=k_pad.shape[-1] - 1)


@functools.partial(jax.jit,
                   static_argnames=("max_iter", "docs_chunk", "tol"))
def sinkhorn_wmd_sparse_batch_stripes(k_pad: jax.Array, km_pad: jax.Array,
                                      r_sel: jax.Array, cols: jax.Array,
                                      vals: jax.Array, max_iter: int,
                                      docs_chunk: int | None = None,
                                      tol: float = 0.0) -> jax.Array:
    """Batched solver on *preassembled* precompute stripes. Returns (Q, N).

    The cross-query cache entry point: callers (`core.kcache` via
    `serving.wmd_service`, or anything that hoists the precompute) pass
    k_pad / km_pad of shape (Q, v_r, V+1) -- per-query K and K.*M stripes
    with the trailing zero pad column already in place (ELL pad slots gather
    it) and pad query rows already zeroed. ``r_sel`` (Q, v_r) carries 1.0 in
    pad rows; K_over_r remains the in-solver per-row 1/r scale, so no third
    stripe is materialized. Identical math (chunking and early-exit
    semantics) as `sinkhorn_wmd_sparse_batch`, which merely computes the
    stripes from embeddings and delegates here.
    """
    return _solve_batch_stripes(k_pad, km_pad, r_sel, cols, vals,
                                max_iter=max_iter, docs_chunk=docs_chunk,
                                tol=tol)
