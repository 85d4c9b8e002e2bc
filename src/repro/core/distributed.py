"""Multi-chip / multi-pod Sinkhorn-WMD engine (shard_map).

Distribution plan (DESIGN.md section 4.1) -- the TPU analogue of the paper's
PIUMA DGAS scale-out:

  * docs (N)  shard over the ``data`` (and ``pod``) mesh axes. Documents are
    independent given K, so this axis needs **zero** communication -- the
    paper's "one query vs many target docs" parallelism.
  * vocab (V) shards over ``model``. Each chip holds the K/K.*M stripe for
    its vocab range and exactly the ELL nonzeros whose word-id falls in that
    range (`formats.rebucket_for_vocab_shards`). The SDDMM dot product
    w[j,k] = <K[:, col], u[:, j]> is therefore **fully local** -- a word's K
    column lives with its nonzero, the DGAS locality argument made explicit.
  * the only collective is one ``psum`` over ``model`` per Sinkhorn iteration
    (the partial SpMM contributions, Q x v_r x N_local floats per chip), plus
    one scalar-per-doc psum for the final distances. Per-chip psum bytes are
    independent of pod count at fixed per-chip work -- the TPU version of the
    paper's "no performance hit from 1 die to 8 dies".

One solve serves every query count, Q = 1 included (`build_wmd_batch_fn`,
and `build_wmd_batch_fn_stripes` for the K cache's stripes): the fused
SDDMM-SpMM of `core.sparse_sinkhorn`, with K gathered at each device's ELL
slots once per batch, before the Sinkhorn loop (`ss.solve_contractions`).
Where that block would not fit, the solve is chunked over documents
outside the Sinkhorn loop, by a memory plan (`plan_docs_chunk`).
`ops.sddmm_spmm_chunked` is the one-chip replay of the vocab decomposition.

Query padding: multiple queries are bucketed to a common v_r; pad rows carry
r = 1 and an all-zero K row (`pad_query` + the row mask in `masked_k_batch`),
which makes padded rows contribute *exactly* zero to every w, x and WMD --
no epsilon approximations.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cost_matrix import cdist
from repro.core.sparse_sinkhorn import pad_k, safe_recip
from repro.core import sparse_sinkhorn as ss


# ---------------------------------------------------------------------------
# Query padding (exact, mask-based)
# ---------------------------------------------------------------------------

def pad_query(sel_idx: np.ndarray, r_sel: np.ndarray, v_r_target: int
              ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a query to a bucket size. Returns (sel_idx, r_sel, row_mask).

    Pad rows point at word 0 with r = 1.0; the row mask zeroes their K rows
    so they contribute nothing anywhere (see module docstring).
    """
    v_r = sel_idx.shape[0]
    if v_r > v_r_target:
        raise ValueError(f"query v_r {v_r} exceeds bucket {v_r_target}")
    pad = v_r_target - v_r
    sel_p = np.concatenate([sel_idx, np.zeros(pad, sel_idx.dtype)])
    r_p = np.concatenate([r_sel.astype(np.float32), np.ones(pad, np.float32)])
    mask = np.concatenate([np.ones(v_r, np.float32), np.zeros(pad, np.float32)])
    return sel_p, r_p, mask


def pad_query_batch(sels: Sequence[np.ndarray], rs: Sequence[np.ndarray],
                    v_r_target: int
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bucket Q mixed-size queries to a common v_r. Returns (Q, v_r) arrays
    (sel_idx, r_sel, row_mask) -- each query padded by `pad_query`, stacked."""
    padded = [pad_query(s, r, v_r_target) for s, r in zip(sels, rs)]
    return (np.stack([p[0] for p in padded]),
            np.stack([p[1] for p in padded]),
            np.stack([p[2] for p in padded]))


def masked_k(vecs_sel: jax.Array, vecs_loc: jax.Array, lamb: float,
             row_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Local K / K.*M stripes with padded query rows zeroed."""
    m = cdist(vecs_sel, vecs_loc)                      # (v_r, Vloc)
    k = jnp.exp(-lamb * m) * row_mask[:, None]
    return k, k * m


def masked_k_batch(vecs_sel: jax.Array, vecs_loc: jax.Array, lamb: float,
                   row_mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched local stripes: (Q, v_r, w) queries -> (Q, v_r, Vloc) K, K.*M."""
    m = jax.vmap(lambda a: cdist(a, vecs_loc))(vecs_sel)
    k = jnp.exp(-lamb * m) * row_mask[..., None]
    return k, k * m


# ---------------------------------------------------------------------------
# The served solve
# ---------------------------------------------------------------------------

def build_wmd_batch_fn(mesh: Mesh, *, lamb: float, max_iter: int,
                       doc_axes: Sequence[str] = ("data",),
                       model_axis: str = "model",
                       docs_chunk: int | None = None, tol: float = 0.0,
                       with_info: bool = False):
    """Build the jit'd batched WMD solver for ``mesh``: the served solve.

    Every device gathers K at its ELL slots ONCE for all Q queries, before
    the Sinkhorn loop, and each iteration's SDDMM and SpMM contractions
    read that block (`ss.solve_contractions`). The Q solves share the same
    single psum over ``model`` -- collective count per iteration is
    independent of Q, so batching amortizes both the gather and the
    communication latency.

    docs_chunk bounds each device's blocks by chunking its local doc slice
    with a rolled chunk loop OUTSIDE the Sinkhorn loop: each chunk gathers
    its own K block and runs all its iterations, so one chunk's blocks are
    live at a time (how `plan_docs_chunk` bounds memory) and the program
    holds one Sinkhorn loop whatever the chunk count. The psum count
    becomes iterations x chunks, and tol freezes each (query, chunk) block
    at its own convergence (the reported n_iter/delta are per-query maxima
    over chunks).

    Early exit (tol > 0): the loop is `ss.batched_sinkhorn_loop` with an
    **all-shards convergence vote** -- each device reduces its local doc
    slice to a per-query delta, and a pmax all-reduce over (model, *doc_axes)
    makes the vote unanimous. The pmax of per-shard inf-norms IS the global
    inf-norm, so unchunked, per-query freeze/n_iter decisions match the
    single-host `sinkhorn_wmd_converged_batch` (equivalently one could psum
    per-shard "still active" votes; the pmax also reproduces the reported
    delta). Converged queries stop contributing writes on every shard; the
    loop (and with it all collectives) exits when every query has converged
    or at ``max_iter``. With tol = 0.0 the loop runs the fixed budget and no
    vote collective is issued.

    The returned fn takes (vecs_sel, r_sel, row_mask, vecs, cols_b, vals_b):
      vecs_sel (Q, v_r, w)           replicated -- bucketed query embeddings
      r_sel    (Q, v_r)              replicated    (pad rows = 1.0)
      row_mask (Q, v_r)              replicated    (pad rows = 0.0)
      vecs     (V, w)                P(model)
      cols_b   (S_model, N, nnz_loc) P(model, doc_axes)
      vals_b   (S_model, N, nnz_loc) P(model, doc_axes)
    and returns wmd (Q, N) with the doc axis sharded over doc_axes -- or,
    with with_info=True, (wmd, n_iter (Q,), delta (Q,)) where the trailing
    two are replicated (the vote makes them identical on every device).

    Retracing happens per distinct Q; callers bound it by bucketing Q
    (see serving.wmd_service admission).
    """
    in_specs = (P(None, None, None), P(None, None), P(None, None),
                P(model_axis, None),
                P(model_axis, *[tuple(doc_axes)], None),
                P(model_axis, *[tuple(doc_axes)], None))
    wmd_spec = P(None, tuple(doc_axes))
    out_specs = (wmd_spec, P(None), P(None)) if with_info else wmd_spec
    vote_axes = (model_axis, *doc_axes)

    def per_device(vecs_sel, r_sel, row_mask, vecs_loc, cols_b, vals_b):
        with jax.named_scope("wmd.precompute"):
            k, km = masked_k_batch(vecs_sel, vecs_loc, lamb, row_mask)
            k_pad, km_pad = pad_k(k), pad_k(km)
            cols_loc, vals_loc = cols_b[0], vals_b[0]
        wmd, n_iter, delta = _local_batched_solve(
            k_pad, km_pad, r_sel, cols_loc, vals_loc,
            max_iter=max_iter, model_axis=model_axis,
            docs_chunk=docs_chunk, tol=tol, vote_axes=vote_axes)
        if with_info:
            return wmd, n_iter, delta
        return wmd

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


LANES = 128                  # a TPU vreg's minor axis


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def solve_bytes_per_doc(q: int, v_r: int, nnz_loc: int) -> int:
    """Device bytes a document adds to the fused batched solve (float32).

    Three (Q, v_r, ·, nnz) blocks, nnz padded to the 128-wide lane axis,
    are live at once in the final pass: the K block that the Sinkhorn loop
    carried, and K.*M gathered in the gather's own layout beside its
    relayout copy (`ss.solve_contractions`); plus the (Q, v_r) iterate and
    its update. An upper bound: the compiled temp of the news20 program
    (Q 8, v_r 288, nnz 288: 10.64 MB a document) for one v5e grows by
    2.75 blocks a document from 256- to 512-document chunks, and holds
    1.75 a document at 1 416."""
    block = q * v_r * _ceil_div(nnz_loc, LANES) * LANES * 4
    return 3 * block + 2 * q * v_r * 4


def solve_fixed_bytes(q: int, v_r: int, v_loc: int) -> int:
    """Device bytes of the fused batched solve that do not grow with the
    documents (float32): the (Q, v_r, V+1) K and K.*M stripes over the
    local vocabulary, then the (V+1, Q*v_r) row tables that take their
    place (`ss.k_row_tables`; the stripes die once the tables are built),
    and the cost rows they are made from. 0.82 GB at news20 (Q 8, v_r
    288, V 29 671), over the 0.58 GB that the compiled program's temp
    holds beside its document blocks on one v5e."""
    return 3 * q * v_r * (v_loc + 1) * 4


def plan_docs_chunk(q: int, v_r: int, nnz_loc: int, n_loc: int, v_loc: int,
                    budget_bytes: int | None) -> int | None:
    """The doc chunk of a fused batched solve over ``n_loc`` local
    documents and ``v_loc`` local words whose working set must fit
    ``budget_bytes``: None where the unchunked program fits or no budget
    is known. Else as many chunks as the largest multiple of 8 documents
    that fits beside the solve's fixed part needs, each evened out to the
    next multiple of 8, so the padded last chunk stays nearly full. The
    chunks are swept by a rolled loop (`_local_batched_solve`)."""
    per_doc = solve_bytes_per_doc(q, v_r, nnz_loc)
    if budget_bytes is None:
        return None
    budget_bytes -= solve_fixed_bytes(q, v_r, v_loc)
    if n_loc * per_doc <= budget_bytes:
        return None
    fit = max(8, budget_bytes // per_doc // 8 * 8)
    return _ceil_div(_ceil_div(n_loc, _ceil_div(n_loc, fit)), 8) * 8


def _local_batched_solve(k_pad, km_pad, r_sel, cols_loc, vals_loc, *,
                         max_iter: int, model_axis: str,
                         docs_chunk: int | None, tol: float, vote_axes):
    """Per-device batched Sinkhorn solve on local (Q, v_r, Vloc+1) stripes.

    The shared core of `build_wmd_batch_fn` (stripes computed in-program
    from embeddings) and `build_wmd_batch_fn_stripes` (stripes preassembled
    by the cross-query cache). Returns (wmd, n_iter, delta); runs under
    shard_map, issuing one psum over ``model_axis`` per iteration.
    """
    q, v_r = r_sel.shape
    ones_r = jnp.ones_like(r_sel)
    # the (V+1, Q*v_r) row tables the gathers read: built once, outside
    # the chunk loop
    rows = ss.k_row_tables(k_pad, km_pad)

    def solve_chunk(x0_c, cols_c, vals_c):
        # the chunk's K block is gathered once, before the loop
        type1, type2 = ss.solve_contractions(k_pad, km_pad, ones_r, cols_c,
                                             vals_c, rows=rows)

        def iteration(x):
            x_part = type1(safe_recip(x))
            x_full = jax.lax.psum(x_part, model_axis)  # THE collective
            return x_full / r_sel[:, :, None]

        with jax.named_scope("wmd.iterate"):
            if tol:
                x, delta, n_iter = ss.batched_sinkhorn_loop(
                    iteration, x0_c, max_iter=max_iter, tol=tol,
                    delta_all_reduce=lambda d: jax.lax.pmax(d, vote_axes))
            else:
                x = jax.lax.fori_loop(0, max_iter,
                                      lambda _, xx: iteration(xx), x0_c)
                delta = jnp.zeros((q,), x0_c.dtype)
                n_iter = jnp.full((q,), max_iter, jnp.int32)
        with jax.named_scope("wmd.final"):
            wmd_part = type2(safe_recip(x))
            return jax.lax.psum(wmd_part, model_axis), n_iter, delta

    n_loc = cols_loc.shape[0]
    if not (docs_chunk and docs_chunk < n_loc):
        with jax.named_scope("wmd.iterate"):
            x0 = jnp.full((q, v_r, n_loc), 1.0 / v_r, dtype=k_pad.dtype)
        return solve_chunk(x0, cols_loc, vals_loc)
    # the rolled chunk loop: one chunk's blocks are live at a time, and the
    # program holds one Sinkhorn loop whatever the chunk count
    with jax.named_scope("wmd.iterate"):
        x0 = jnp.full((q, v_r, docs_chunk), 1.0 / v_r, dtype=k_pad.dtype)
    wmd, n_iter, delta = ss.map_doc_chunks(
        lambda c, v: solve_chunk(x0, c, v), cols_loc, vals_loc, docs_chunk,
        pad_col=k_pad.shape[-1] - 1)
    return (ss.join_doc_chunks(wmd, n_loc), jnp.max(n_iter, axis=0),
            jnp.max(delta, axis=0))


def build_wmd_batch_fn_stripes(mesh: Mesh, *, max_iter: int,
                               doc_axes: Sequence[str] = ("data",),
                               model_axis: str = "model",
                               docs_chunk: int | None = None,
                               tol: float = 0.0, with_info: bool = False):
    """Batched WMD solver consuming *preassembled* K / K.*M stripes.

    The distributed consumer of the cross-query cache (`core.kcache`): the
    per-query precompute no longer happens inside the device program -- the
    cache hands each vocab shard its stripe slice, already masked for pad
    query rows and carrying the shard-local zero pad column, laid out like
    the rebucketed ELL:

      k_b, km_b (S_model, Q, v_r, Vloc+1)  P(model)  -- per-shard stripes
      r_sel     (Q, v_r)                   replicated (pad rows = 1.0)
      cols_b    (S_model, N, nnz_loc)      P(model, doc_axes)
      vals_b    (S_model, N, nnz_loc)      P(model, doc_axes)

    and returns wmd (Q, N) sharded over doc_axes (plus (n_iter, delta) with
    ``with_info=True``). No ``lamb``: it is baked into the cached rows, and
    the cache invalidates itself on a lambda change. Everything else
    (docs_chunk, early-exit vote) is identical to `build_wmd_batch_fn`,
    with which it shares `_local_batched_solve`.
    """
    in_specs = (P(model_axis, None, None, None),
                P(model_axis, None, None, None),
                P(None, None),
                P(model_axis, *[tuple(doc_axes)], None),
                P(model_axis, *[tuple(doc_axes)], None))
    wmd_spec = P(None, tuple(doc_axes))
    out_specs = (wmd_spec, P(None), P(None)) if with_info else wmd_spec
    vote_axes = (model_axis, *doc_axes)

    def per_device(k_b, km_b, r_sel, cols_b, vals_b):
        # the stripes come precomputed: this phase only unpacks the shard
        with jax.named_scope("wmd.precompute"):
            k_pad, km_pad = k_b[0], km_b[0]
            cols_loc, vals_loc = cols_b[0], vals_b[0]
        wmd, n_iter, delta = _local_batched_solve(
            k_pad, km_pad, r_sel, cols_loc, vals_loc,
            max_iter=max_iter, model_axis=model_axis,
            docs_chunk=docs_chunk, tol=tol, vote_axes=vote_axes)
        if with_info:
            return wmd, n_iter, delta
        return wmd

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


def build_wmd_fn_docsharded(mesh: Mesh, *, lamb: float, max_iter: int,
                            use_kernel: bool = False):
    """Doc-sharded / K-replicated layout (the §Perf-optimized engine for
    moderate v_r): K is only v_r x V x 4B (12.8 MB at the paper's scale), so
    every chip keeps the whole stripe and docs shard over ALL mesh axes --
    the Sinkhorn loop then has ZERO collectives (vs one psum/iter for the
    vocab-sharded engine). The vocab-sharded engine remains the scale-out
    path for large v_r buckets where K would not fit (DESIGN.md section 4.1).

    Returned fn takes (vecs_sel, r_sel, row_mask, vecs, cols, vals):
      vecs (V, w) replicated; cols/vals (N, nnz) sharded over every mesh
      axis on the doc dim.
    """
    all_axes = tuple(mesh.axis_names)
    in_specs = (P(None, None), P(None), P(None), P(None, None),
                P(all_axes, None), P(all_axes, None))

    def per_device(vecs_sel, r_sel, row_mask, vecs, cols_loc, vals_loc):
        k, km = masked_k(vecs_sel, vecs, lamb, row_mask)
        k_pad, km_pad = pad_k(k), pad_k(km)
        v_r = r_sel.shape[0]
        n_loc = cols_loc.shape[0]
        x0 = jnp.full((v_r, n_loc), 1.0 / v_r, dtype=k.dtype)

        def t1(u):
            if use_kernel:
                from repro.kernels import ops
                return ops.sddmm_spmm_type1(k_pad, r_sel, u, cols_loc,
                                            vals_loc)
            return ss.sddmm_spmm_type1(k_pad, r_sel, u, cols_loc, vals_loc)

        x = jax.lax.fori_loop(0, max_iter,
                              lambda _, x: t1(safe_recip(x)), x0)
        u = safe_recip(x)
        if use_kernel:
            from repro.kernels import ops
            return ops.sddmm_spmm_type2(k_pad, km_pad, u, cols_loc, vals_loc)
        return ss.sddmm_spmm_type2(k_pad, km_pad, u, cols_loc, vals_loc)

    fn = jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                       out_specs=P(all_axes), check_vma=False)
    return jax.jit(fn)


def shard_wmd_inputs(mesh: Mesh, vecs: np.ndarray, cols_b: np.ndarray,
                     vals_b: np.ndarray, *, doc_axes: Sequence[str] = ("data",),
                     model_axis: str = "model"):
    """Place host arrays on the mesh with the layouts build_wmd_batch_fn
    expects."""
    dev = lambda spec: NamedSharding(mesh, spec)
    vecs_d = jax.device_put(vecs, dev(P(model_axis, None)))
    cols_d = jax.device_put(cols_b, dev(P(model_axis, tuple(doc_axes), None)))
    vals_d = jax.device_put(vals_b, dev(P(model_axis, tuple(doc_axes), None)))
    return vecs_d, cols_d, vals_d
