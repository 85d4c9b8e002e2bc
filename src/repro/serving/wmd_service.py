"""Batched Sinkhorn-WMD query service (the paper's workload, production-shaped).

Serves WMD requests against a corpus held sharded on the mesh: vocab-striped
embeddings + rebucketed ELL (loaded once), solved by the fused SDDMM-SpMM
engine with one psum per iteration.

Service API
-----------
  query(r)                  -- one (V,) histogram -> (N,) distances:
      ``query_batch([r])[0]``, the same engine at Q = 1.
  query_batch(rs)           -- Q histograms -> (Q, N) batched:
      queries are padded to the service's v_r bucket (exact mask-based
      padding, `core.distributed.pad_query_batch`) and admitted in
      power-of-two Q buckets (bounding retrace count). With the cross-query
      K cache enabled (``cache_capacity > 0``) the precompute runs through
      `core.kcache`: word-ids are deduped across the whole batch, only rows
      not already resident are computed (row-subset fused kexp), and each
      query's (v_r, Vloc+1) stripe -- zero pad column included, so `pad_k`
      never runs in the hot path -- is assembled by a single slot-gather
      feeding the stripes engine (`build_wmd_batch_fn_stripes`); with the
      cache disabled the legacy single-program engine (precompute fused
      into the solve, `build_wmd_batch_fn`) runs instead -- faster for a
      one-shot batch since the split path pays an extra dispatch. Either
      way the (Q, v_r, N) solve shares a single ELL gather and a single
      psum per Sinkhorn iteration across all Q queries. Slots added by
      Q-bucketing carry an all-zero row mask, so they cost flops but
      contribute nothing and are sliced off before returning. Every query
      count, Q = 1 included, runs this one solve.
      ``use_cache`` routes explicitly: False = the transient
      (dedup + recompute-everything) stripes path, the cache-off baseline
      that is *bitwise identical* to the cached path; True = the stripes
      engine even on a cache-less service (how the bench phase-splits).
  query_batch_sequential(rs) -- a loop of Q = 1 dispatches, kept as the
      oracle of batch composition and the baseline for
      bench_query_batch.py.
  top_k(r, k) / top_k_batch(rs, k) -- nearest-k doc ids + distances
      (argpartition + a tie-deterministic local sort: O(N + k log k), not a
      full argsort; ties are broken by doc id so every route selects the
      same set).
      With ``prune=True`` the two-tier retrieval engine runs instead: every
      doc is scored with the O(nnz) doc-side RWMD lower bound (`core.rwmd`
      -- batched across the query set with the K-cache's word-id dedup),
      docs are visited in ascending-bound order in fixed ``prune_chunk``
      doc blocks (candidate sets stay cache-resident), and the exact
      Sinkhorn rerank (the stripes engine, precompute served by the
      cross-query K cache) runs only until the next block's bound exceeds
      the running k-th exact distance -- every doc past that point is
      provably outside the top-k. The contract is exact: pruned top-k
      returns the bitwise-identical (distance, doc-id) set as
      `top_k_scan_batch`, the exhaustive scan through the SAME chunked
      rerank programs (asserted by tests/test_rwmd_properties.py, the
      golden table, and every bench_prune.py batch), while skipping the
      pruned docs' solves entirely (``last_prune_stats['solves_avoided']``
      -- >= 0.9 at N >= 1024, k <= 16 on the Zipf corpus). Bound soundness
      at a *finite iteration budget* is why the DOC-side RWMD is used --
      see core.rwmd's module docstring.
  top_k_scan_batch(rs, k) -- the pruned path's oracle: exact full scan
      through the same per-query chunked rerank engine (bound order, no
      pruning). Slower than top_k_batch's one-program full scan by
      construction; exists to make "pruned == exact scan" a bitwise
      statement rather than an fp32 one.
  async_service(**kw)       -- async admission front-end: a
      `serving.coalescer.QueryCoalescer` that turns a concurrent stream of
      single-query ``submit(r) -> Future`` calls into full `query_batch`
      dispatches (fill/window/deadline micro-batching, backpressure,
      ServingStats); `drain_async()` flushes every live front-end.
  add_docs / remove_docs / compact -- live-corpus mutation, available on a
      service built via `WMDService.from_live` over a
      `data.live_corpus.LiveCorpus`: WAL-durable upserts/tombstones (the
      return acks fsynced state), lazy per-segment device refresh, and
      interruptible compaction. Live dispatches answer over the live doc
      set in ascending-doc-id order, bitwise identical to a one-shot
      build of the same docs (the incremental == batch contract); top-k
      returns real doc ids via `live_doc_ids`. The K cache is never
      invalidated by corpus mutation (rows don't depend on docs);
      `invalidate_embedding_rows` is the scoped hook for vector updates.

Perf knobs (constructor fields):
  docs_chunk     -- sweep the batched solve over doc chunks of this size
                    (0 = unchunked). None (the default) plans it per Q
                    bucket from the device's memory
                    (`core.distributed.plan_docs_chunk`, budget
                    `plan_budget_bytes`): unchunked wherever the solve's
                    (Q, v_r, N, nnz) blocks fit.
  tol            -- early-exit tolerance: converged queries freeze, the
                    solve stops when all queries converge (0.0 = fixed
                    max_iter).
  cache_capacity -- resident row slots of the cross-query K/KM cache
                    (0 = off: every batch recomputes its deduped rows).
                    Memory: capacity x (V+1) x 2 matrices x 4 B, sharded
                    over the ``model`` axis like the vocab striping.
  cache_rows_bucket -- static chunk size of the cache-miss row compute
                    (one compiled program per bucket; also the cache's
                    bit-reproducibility guarantee, see core.kcache). The
                    RWMD prefilter's M-row dedup reuses the same bucket.
  kexp_impl      -- "jnp" | "kernel": row-precompute path for cache misses.
  prune_chunk    -- doc-block size of the pruned rerank (rounded up to the
                    doc-shard product; one fixed-shape (1, prune_chunk)
                    stripes program reranks every candidate block, which is
                    both the cache-blocking and the bitwise argument: every
                    exact distance -- pruned or scan -- comes from the same
                    program shape).
  prune_margin   -- relative safety slack of the prune test (a doc is
                    pruned only when bound * (1 - margin) exceeds the k-th
                    exact distance): covers fp dot-rounding between the
                    bound and the engine's distance (~1e-6 observed) with
                    ~1000x headroom while costing a negligible number of
                    extra solves (the bound's real gap is >= 4% on the
                    bench corpus).
  bound_impl     -- "fused" | "kernel": min-SDDMM path of the prefilter.
  bound_docs_chunk -- cache-block the (Q, N, nnz, v_r) bound gather over
                    doc chunks (None = unchunked; the default keeps the
                    prefilter's working set ~tens of MB at bulk N).

Cache observability: ``cache_stats`` (cumulative hits / misses / evictions /
hit_rate) and ``last_batch_stats`` (per-call ``precompute_s`` / ``solve_s``
phase split, read off the call's ``spans`` (see `query_batch`), + that
batch's hit_rate -- the fields the bench artifact records). The cache re-keys itself if ``cfg.lamb`` changes between calls
(lambda-invalidation: K rows are keyed by (word_id, lambda)).

`examples/wmd_query_service.py` runs it end-to-end (including a Zipf
query-stream demo of the cache); `launch/serve.py` exposes it via
--arch sinkhorn-wmd (add --batch-queries for the batched path).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import sinkhorn_wmd as wmd_cfg
from repro.core import cascade as cascade_core
from repro.core import formats, select_query
from repro.core import guards as _guards
from repro.core import rwmd as rwmd_core
from repro.core.kcache import KCache, MCache
from repro.core.distributed import (build_wmd_batch_fn,
                                    build_wmd_batch_fn_stripes,
                                    pad_query_batch, plan_docs_chunk,
                                    shard_wmd_inputs, solve_bytes_per_doc)
from repro.obs.trace import span
# one copy of the pow2 bucket-rounding rule for the whole serving layer:
# the coalescer's admission buckets must match the service's Q padding
from repro.serving.coalescer import _next_pow2


def _serialized(fn):
    """Serialize an engine entry point on the service's reentrant lock.

    The engine is stateful (last_batch_stats; the K cache mutates a host
    slot map and donates its device ring buffers), so concurrent callers --
    several `async_service` dispatcher threads, or `warm()` on a client
    thread while a dispatcher is live -- must take turns. Reentrant because
    some entry points call others (the live top-k fallback calls
    query_batch)."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._engine_lock:
            return fn(self, *args, **kwargs)
    return wrapper


# sentinel: "use the service's docs_chunk" (None already means planned)
_UNSET = object()

# Share of the device's ``bytes_limit`` that the planned solve leaves free,
# beside the fixed part and the document blocks that the plan counts
# (`core.distributed.solve_fixed_bytes`, `solve_bytes_per_doc`): room for
# the program's outputs and the allocator's fragmentation, which no reading
# bounds yet. The plan's per-document count is itself an upper bound (3
# blocks a document counted, 1.9 compiled at news20).
PLAN_SLACK = 0.05


def _nonzeros(vals: np.ndarray) -> tuple[int, int]:
    """(real, all) slots of an ELL values array: a pad slot holds 0."""
    return int(np.count_nonzero(vals)), int(vals.size)


@dataclasses.dataclass
class WMDService:
    mesh: jax.sharding.Mesh
    cfg: wmd_cfg.WMDConfig
    vecs: np.ndarray
    ell: formats.EllDocs | None = None
    docs_chunk: int | None = None
    tol: float = 0.0
    cache_capacity: int = 0
    cache_rows_bucket: int = 128
    kexp_impl: str = "jnp"
    prune_chunk: int = 64
    prune_margin: float = 1e-3
    bound_impl: str = "fused"
    bound_docs_chunk: int | None = 256
    mcache_capacity: int = 0
    tier0: bool = True
    lc_impl: str | None = "fused"
    tier2_cap: int | None = None
    guards: bool = True
    live: object | None = None          # data.live_corpus.LiveCorpus
    metrics: object | None = None       # repro.obs.MetricsRegistry

    @classmethod
    def from_live(cls, mesh, cfg, vecs, live, **kw) -> "WMDService":
        """Build a service over a mutable `data.live_corpus.LiveCorpus`.

        The corpus's base segment becomes the service ELL; a delta segment
        (and the tombstone gather map) is refreshed lazily before every
        live dispatch (`_refresh_live`). ``add_docs`` / ``remove_docs`` /
        ``compact`` then mutate the corpus through the service under the
        engine lock."""
        return cls(mesh=mesh, cfg=cfg, vecs=vecs, live=live, **kw)

    def __post_init__(self):
        if self.live is not None:
            # the base segment IS the service corpus; ell, if also passed,
            # is ignored in favor of the live corpus's current base
            self.ell = self.live.base_ell
        if self.ell is None:
            raise ValueError("WMDService needs either ell= or live=")
        model_size = self.mesh.shape["model"]
        self._rb = formats.rebucket_for_vocab_shards(self.ell, model_size)
        self._doc_axes = tuple(a for a in ("pod", "data")
                               if a in self.mesh.axis_names)
        self._batch_fns: dict[tuple, object] = {}
        self._stripe_fns: dict[tuple, object] = {}
        self._vecs_d, self._cols_d, self._vals_d = shard_wmd_inputs(
            self.mesh, self.vecs, self._rb.cols, self._rb.vals,
            doc_axes=self._doc_axes)
        if self.metrics is None:
            # every service owns a registry: it is the single backing
            # store scrape/export read, and async_service shares it with
            # the coalescer so the whole stack lands in one namespace
            from repro.obs.metrics import MetricsRegistry
            self.metrics = MetricsRegistry()
        self._kcache = KCache(self.cache_capacity, self._vecs_d,
                              self.cfg.lamb, mesh=self.mesh,
                              rows_bucket=self.cache_rows_bucket,
                              kexp_impl=self.kexp_impl,
                              metrics=self.metrics)
        # M-row cache for the bound tiers: same LRU machinery, rows keyed
        # by word_id alone (no lambda), replicated like the bound ELL. Its
        # transient path IS assemble_m_stripes, so capacity 0 (the default)
        # changes nothing but the amortization.
        self._mcache = MCache(self.mcache_capacity, self._vecs_d,
                              rows_bucket=self.cache_rows_bucket,
                              metrics=self.metrics)
        # any pruned dispatch that silently degrades to an exact full scan
        # must be countable, not just visible in last_prune_stats
        self._prune_fallbacks = self.metrics.counter(
            "wmd_prune_fallback_total",
            "pruned top-k dispatches that fell back to the exact full scan")
        # the slots a full-distance solve dispatch sweeps: Q_pow2 x v_r
        # query slots, and every slot of the ELL it gathers K at; "real"
        # ones carry a query word or a document nonzero
        self._slots = {
            (what, kind): self.metrics.counter(
                f"wmd_{what}_slots_total",
                f"{what} slots swept by full-distance solve dispatches",
                labels={"kind": kind})
            for what in ("query", "ell") for kind in ("real", "pad")}
        # the document chunks those dispatches sweep, and the plan of the
        # last one (`_count_dispatch`)
        self._solve_chunks = self.metrics.counter(
            "wmd_solve_chunks_total",
            "document chunks swept by full-distance solve dispatches")
        self._ell_nnz = _nonzeros(self._rb.vals)
        self._warm_local = threading.local()    # see warming()
        # prefilter state: the bound runs replicated on the ORIGINAL
        # (un-rebucketed) ELL -- the min over a doc's words needs the doc's
        # whole support, which vocab re-bucketing splits across shards.
        # Replicated over the mesh, not left on the default device (which
        # need not belong to the mesh).
        self._replicated = NamedSharding(self.mesh, P())
        self._ell_cols_d, self._ell_vals_d = jax.device_put(
            (self.ell.cols, self.ell.vals), self._replicated)
        self._b2 = jnp.sum(self._vecs_d * self._vecs_d, axis=-1)
        self._doc_shards = 1
        for a in self._doc_axes:
            self._doc_shards *= self.mesh.shape[a]
        # rerank chunks are placed like the corpus ELL, so the chunk must
        # divide across the doc shards
        self._rerank_chunk = -(-max(self.prune_chunk, 1)
                               // self._doc_shards) * self._doc_shards
        self._rerank_spec = NamedSharding(
            self.mesh, P("model", tuple(self._doc_axes), None))
        # the memory plan's inputs: each device's doc slice and ELL width,
        # and a budget fixed here, from the device's bytes_limit less the
        # arrays just placed (never a momentary free-memory reading, so
        # every run plans the same programs); None where the backend
        # reports no memory (the CPU), and the plan is then unchunked
        self._n_loc = self._rb.cols.shape[1] // self._doc_shards
        self._v_loc = -(-self.vecs.shape[0] // self.mesh.shape["model"])
        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in self.mesh.devices.flat]
        self.device_bytes_limit = min(limits) if all(limits) else None
        self.plan_budget_bytes = None
        if self.device_bytes_limit:
            self.plan_budget_bytes = min(
                int(limit * (1.0 - PLAN_SLACK)) - self._resident_bytes(d)
                for d, limit in zip(self.mesh.devices.flat, limits))
            self.metrics.gauge(
                "wmd_device_bytes_limit",
                "bytes_limit of the service's devices (the least)").set(
                    self.device_bytes_limit)
        # numeric-guard state: the a-priori underflow gate needs the
        # largest embedding norm (cost bound 2*max||v||); docs with zero
        # total mass legitimately solve to distance 0 and are exempt from
        # the armed-gate zero-cell check
        self._max_vec_norm = float(np.sqrt(
            (self.vecs.astype(np.float64) ** 2).sum(axis=-1).max())) \
            if self.vecs.size else 0.0
        self._empty_doc_mask = np.asarray(self.ell.vals.sum(axis=-1) == 0)
        # tier-0 moments (per-doc mass-weighted vector sum + mass) are a
        # pure function of the corpus ELL: computed lazily on the first
        # pruned dispatch, dropped whenever the base segment changes
        self._cent: tuple | None = None
        self.last_batch_stats: dict = {}
        self.last_prune_stats: dict = {}
        self._engine_lock = threading.RLock()   # see _serialized
        # live async front-ends (async_service); weak so a shut-down
        # coalescer the caller dropped doesn't accumulate on the service
        self._coalescers: weakref.WeakSet = weakref.WeakSet()
        # live-corpus device state (refreshed lazily; see _refresh_live).
        # base state was just built from live.base_ell above, so only the
        # delta/gather state starts stale.
        self._live_base_version = (self.live.base_version
                                   if self.live is not None else -1)
        self._live_version = -1
        if self.live is not None and self.live.metrics is None:
            # arm the corpus's compaction lock-hold histogram on this
            # service's registry (late-bindable, like its tracer)
            self.live.metrics = self.metrics

    def _resident_bytes(self, device) -> int:
        """Bytes of the service's own arrays (embeddings, ELL copies,
        caches) on ``device``."""
        arrays = {id(a): a for o in (self, self._kcache, self._mcache)
                  for a in vars(o).values() if isinstance(a, jax.Array)}
        return sum(sh.data.nbytes for a in arrays.values()
                   for sh in a.addressable_shards if sh.device == device)

    def _docs_chunk(self, q: int, docs_chunk=_UNSET) -> int | None:
        """The doc chunk of a batched solve of ``q`` (padded) queries over
        the service ELL: the call's ``docs_chunk``, else the service's
        (0 = unchunked either way), else the memory plan."""
        if docs_chunk is _UNSET:
            docs_chunk = self.docs_chunk
        if docs_chunk is not None:
            return docs_chunk or None
        return plan_docs_chunk(q, self.cfg.v_r, self._rb.cols.shape[-1],
                               self._n_loc, self._v_loc,
                               self.plan_budget_bytes)

    def async_service(self, **kw):
        """Async admission front-end: a `serving.coalescer.QueryCoalescer`
        whose dispatcher feeds this service's `query_batch` (thread-safe
        ``submit(r) -> Future``, micro-batching by fill/window/deadline --
        see the coalescer module docstring for knobs). Usable as a context
        manager (shutdown-with-drain on exit); `drain_async` flushes every
        front-end this service has handed out."""
        from repro.serving.coalescer import QueryCoalescer
        co = QueryCoalescer(self, **kw)
        self._coalescers.add(co)
        return co

    def drain_async(self, timeout: float | None = None) -> None:
        """Drain hook: block until every live `async_service` front-end has
        an empty queue and no in-flight batch (coalescers stay open)."""
        for co in list(self._coalescers):
            co.drain(timeout=timeout)

    # -- live corpus (mutable base + delta segments) ----------------------
    #
    # With ``live`` set, every dispatch runs per-SEGMENT: the same stripes
    # program solves the base and delta ELLs (corpus cols/vals are runtime
    # arguments, so one compiled fn serves both shapes whenever their
    # capacities match, and at most two shapes otherwise), and the results
    # are gathered into ascending-doc-id order through the corpus's
    # (segment, row) location map. Tombstoned/pad rows are solved but never
    # gathered -- pad-slot inertness makes them free of side effects -- so
    # per-doc distances are bitwise identical to a one-shot build of the
    # same logical docs (the incremental == batch contract, pinned by the
    # golden table's live_* routes and the ingest chaos suite).
    #
    # K-cache scoping: cached K rows are functions of (word_id, lambda,
    # vecs) ONLY -- no row depends on which documents exist -- so corpus
    # mutation invalidates NOTHING (the correctly-scoped invalidation set
    # for a corpus mutation is empty; tests pin that resident rows survive
    # add/remove/compact and still hit). Embedding updates are the event
    # that poisons rows by word-id; `invalidate_embedding_rows` is that
    # scoped hook (`core.kcache.KCache.invalidate_ids`). The RWMD bound
    # tier needs no invalidation either: bounds are recomputed per call
    # against the current segment ELLs.

    def _require_live(self):
        if self.live is None:
            raise ValueError("this WMDService has no live corpus "
                             "(construct with WMDService.from_live)")

    def _refresh_live(self) -> None:
        """Sync device state with the corpus (cheap when nothing changed).

        base_version bump (a compaction swapped segments): rebuild the
        rebucketed base, its sharded device arrays and the bound tier's
        replicated ELL. version bump (any mutation): re-place the delta
        segment and rebuild the gather map. Versions are read under the
        engine lock, which every mutating service entry point also holds --
        and under the CORPUS lock (reentrant), because `LiveCorpus.compact`
        builds outside its lock and swaps under it: without the corpus
        lock, the version reads, the base_ell read and the locations()
        read here could straddle a concurrent swap and mix segments."""
        lc = self.live
        with lc._lock:
            self._refresh_live_locked(lc)

    def _refresh_live_locked(self, lc) -> None:
        if lc.base_version != self._live_base_version:
            self.ell = lc.base_ell
            model_size = self.mesh.shape["model"]
            self._rb = formats.rebucket_for_vocab_shards(self.ell,
                                                         model_size)
            _, self._cols_d, self._vals_d = shard_wmd_inputs(
                self.mesh, self.vecs, self._rb.cols, self._rb.vals,
                doc_axes=self._doc_axes)
            self._ell_cols_d, self._ell_vals_d = jax.device_put(
                (self.ell.cols, self.ell.vals), self._replicated)
            self._empty_doc_mask = np.asarray(
                self.ell.vals.sum(axis=-1) == 0)
            self._ell_nnz = _nonzeros(self._rb.vals)
            self._cent = None                # tier-0 moments follow the base
            self._live_base_version = lc.base_version
            self._live_version = -1          # gather map must follow
        if lc.version != self._live_version:
            d_ell = lc.delta_ell
            drb = formats.rebucket_for_vocab_shards(
                d_ell, self.mesh.shape["model"])
            self._dcols_d = jax.device_put(drb.cols, self._rerank_spec)
            self._dvals_d = jax.device_put(drb.vals, self._rerank_spec)
            self._dell_nnz = _nonzeros(drb.vals)
            self._dell_cols_d, self._dell_vals_d = jax.device_put(
                (d_ell.cols, d_ell.vals), self._replicated)
            ids, seg, row = lc.locations()
            self._live_ids = ids
            self._live_seg = seg
            self._live_row = row
            self._live_empty = lc.live_empty_mask()
            self._live_version = lc.version

    def _query_batch_live(self, rs: Sequence[np.ndarray], spans: list,
                          use_cache: bool | None = None):
        """(Q, num_live) exact distances over the live corpus, columns in
        ascending doc-id order, and the route's stats (None for an empty
        call). One K-cache stripes assembly feeds one stripes dispatch per
        non-empty segment; a segment holding no live doc is skipped
        outright. docs_chunk is forced to None -- segments are
        capacity-bounded, and per-doc bits are chunking-independent
        anyway, so one unchunked program per segment is the simplest
        correct plan."""
        with span("wmd.prepare", spans):
            self._refresh_live()
            n_live = self._live_ids.size
            q = len(rs)
            if q and n_live:
                self._validate_queries(rs)
                sel_b, r_b, mask_b = self._padded_query_batch(rs)
        if q == 0 or n_live == 0:
            self.last_batch_stats = {}
            return np.zeros((q, n_live), np.float32), None
        k_s, km_s, info = self._cache_rows(sel_b, mask_b, use_cache, spans)
        fn = self._stripe_fn(None)
        r_d = jnp.asarray(r_b)
        out = np.empty((q, n_live), np.float32)
        segments = 0
        for seg_id, (cols_d, vals_d, nnz) in enumerate(
                ((self._cols_d, self._vals_d, self._ell_nnz),
                 (self._dcols_d, self._dvals_d, self._dell_nnz))):
            pick = self._live_seg == seg_id
            if not pick.any():
                continue
            with span("wmd.dispatch", spans):
                self._count_dispatch(mask_b, nnz)
                d_seg = fn(k_s, km_s, r_d, cols_d, vals_d)
            with span("wmd.fetch", spans):
                d_seg = np.asarray(d_seg)[:q]
            out[:, pick] = d_seg[:, self._live_row[pick]]
            segments += 1
        with span("wmd.check", spans):
            self._check_result(out, what="live query_batch distances",
                               empty_doc_mask=self._live_empty)
        return out, {"segments": segments, **info}

    def _bounds_live(self, rs: Sequence[np.ndarray]) -> np.ndarray:
        """(Q, num_live) RWMD lower bounds over the live corpus: one M-row
        assembly, one prefilter program per non-empty segment, the same
        ascending-id gather as the exact path."""
        self._refresh_live()
        n_live = self._live_ids.size
        q = len(rs)
        if q == 0 or n_live == 0:
            return np.zeros((q, n_live), np.float32)
        self._validate_queries(rs)
        sel_b, r_b, mask_b = self._padded_query_batch(rs)
        m_pad, _ = self._mcache.m_stripes_for_batch(sel_b, mask_b)
        out = np.empty((q, n_live), np.float32)
        for seg_id, (cols_d, vals_d) in enumerate(
                ((self._ell_cols_d, self._ell_vals_d),
                 (self._dell_cols_d, self._dell_vals_d))):
            pick = self._live_seg == seg_id
            if not pick.any():
                continue
            lb = np.asarray(rwmd_core.rwmd_bound_batch(
                m_pad, cols_d, vals_d, impl=self.bound_impl,
                docs_chunk=None))[:q]
            out[:, pick] = lb[:, self._live_row[pick]]
        return out

    @property
    def live_doc_ids(self) -> np.ndarray:
        """Ascending doc ids of the live corpus -- result column j of a
        live dispatch scores the doc ``live_doc_ids[j]`` (and live top-k
        returns these ids, not positions)."""
        self._require_live()
        with self._engine_lock:
            self._refresh_live()
            return self._live_ids

    @_serialized
    def add_docs(self, ids, docs) -> int:
        """Durable live upsert (see `data.live_corpus.LiveCorpus.add_docs`;
        the return acknowledges WAL-fsynced docs). Device state refreshes
        lazily at the next dispatch; the K cache is deliberately NOT
        invalidated -- see the section comment above."""
        self._require_live()
        return self.live.add_docs(ids, docs)

    @_serialized
    def remove_docs(self, ids) -> int:
        """Durable live remove; returns how many ids were actually live."""
        self._require_live()
        return self.live.remove_docs(ids)

    @_serialized
    def compact(self) -> None:
        """Run one interruptible corpus compaction (base <- base + delta,
        atomic swap); the next dispatch picks up the new base segment."""
        self._require_live()
        self.live.compact()

    @_serialized
    def invalidate_embedding_rows(self, word_ids) -> int:
        """Scoped cache invalidation for *embedding* updates: drops exactly
        the rows of ``word_ids`` from BOTH row stores (the K/KM cache and
        the bound tiers' M-row cache -- an M row is a pure function of
        (word_id, vecs) too). Returns the total rows dropped across the two
        stores. Corpus mutations never need this -- rows don't depend on
        docs."""
        return (self._kcache.invalidate_ids(word_ids)
                + self._mcache.invalidate_ids(word_ids))

    # -- numeric guards ---------------------------------------------------

    def _underflow_risk(self) -> bool:
        """Is the lambda-underflow post-check armed for the current lambda?
        Recomputed per call (cfg.lamb is mutable, see ensure_lamb); False
        at every shipped config so the zero-cell check costs nothing."""
        return self.guards and _guards.underflow_possible(
            self.cfg.lamb, self._max_vec_norm)

    def _validate_queries(self, rs) -> None:
        if not self.guards:
            return
        v = self.vecs.shape[0]
        for i, r in enumerate(rs):
            try:
                _guards.validate_query(r, v)
            except _guards.InvalidQueryError as e:
                e.context["query_index"] = i
                raise

    def _check_km(self, km_s, mask_b) -> None:
        """Lambda-underflow pre-check on assembled K*M stripes; the big
        reduction runs on device so only (Q, v_r) scalars come to host."""
        if not self.guards:
            return
        rowmax = np.asarray(jnp.max(jnp.abs(km_s), axis=(0, -1)))
        _guards.check_km_rows(rowmax, mask_b, lamb=self.cfg.lamb)

    def _check_result(self, d, *, what: str,
                      empty_doc_mask: np.ndarray | None = None) -> None:
        if not self.guards:
            return
        if empty_doc_mask is None:
            empty_doc_mask = self._empty_doc_mask
        _guards.check_distances(d, lamb=self.cfg.lamb,
                                risk=self._underflow_risk(),
                                empty_doc_mask=empty_doc_mask, what=what)

    @property
    def cache_stats(self):
        """Cumulative cross-query cache counters (`core.kcache.KCacheStats`)."""
        return self._kcache.stats

    @property
    def cache_resident(self) -> int:
        """Word-id rows currently resident in the cross-query cache."""
        return self._kcache.resident

    @property
    def mcache_stats(self):
        """Cumulative M-row cache counters (`core.kcache.KCacheStats`)."""
        return self._mcache.stats

    @property
    def mcache_resident(self) -> int:
        """M rows currently resident in the bound tiers' row cache."""
        return self._mcache.resident

    def _batch_fn(self, docs_chunk: int | None):
        """Single-program batched solver (precompute fused into the device
        program) -- the engine `query_batch` runs when the cross-query cache
        is disabled; the cache routes through `_stripe_fn` instead. tol and
        lamb are part of the key so mutating svc.tol / svc.cfg.lamb can't
        serve a stale solver."""
        key = (docs_chunk, self.tol, self.cfg.lamb)
        fn = self._batch_fns.get(key)
        if fn is None:
            fn = build_wmd_batch_fn(self.mesh, lamb=self.cfg.lamb,
                                    max_iter=self.cfg.max_iter,
                                    doc_axes=self._doc_axes,
                                    docs_chunk=docs_chunk, tol=self.tol)
            self._batch_fns[key] = fn
        return fn

    def _stripe_fn(self, docs_chunk: int | None):
        """Batched solver on cache-assembled stripes, built once per
        (docs_chunk, tol) -- same caching contract as `_batch_fn`."""
        key = (docs_chunk, self.tol)
        fn = self._stripe_fns.get(key)
        if fn is None:
            fn = build_wmd_batch_fn_stripes(
                self.mesh, max_iter=self.cfg.max_iter,
                doc_axes=self._doc_axes, docs_chunk=docs_chunk,
                tol=self.tol)
            self._stripe_fns[key] = fn
        return fn

    def query(self, r: np.ndarray) -> np.ndarray:
        """r: (V,) sparse query histogram -> (N,) distances (num_live
        columns in ascending doc-id order on a live service): the batched
        engine at Q = 1."""
        return self.query_batch([r])[0]

    @_serialized
    def query_batch(self, rs: Sequence[np.ndarray],
                    docs_chunk=_UNSET,
                    use_cache: bool | None = None) -> np.ndarray:
        """Multiple queries -> (Q, N) via the batched (Q, v_r, N) engine.

        With the cache enabled, the precompute phase dedups word-ids across
        the whole batch and computes only rows missing from the cross-query
        cache; cache-less services run the legacy fused-precompute program.
        The fused solve gathers K at the ELL slots once for the whole batch
        and runs one psum per Sinkhorn iteration either way. Q is rounded up
        to a power of two (retrace bound), with the filler slots masked to
        contribute exactly zero. ``docs_chunk`` overrides the service's for
        this call (pass docs_chunk=0 for explicitly unchunked);
        ``use_cache`` overrides the engine routing (False = transient
        stripes baseline, bitwise identical to the cached path; True =
        stripes engine even with the cache disabled). Built fns are cached
        per docs_chunk.

        Live services route every call through the per-segment dispatch
        (`_query_batch_live`; docs_chunk is forced unchunked there) --
        (Q, num_live) columns in ascending doc-id order, bitwise identical
        to a one-shot build of the same docs.

        Every route runs as the span ``wmd.query_batch`` with children
        ``wmd.prepare`` (validate, select and pad, gather the query
        embeddings on the host), ``wmd.cache_rows`` (the K-cache stripes,
        stripes and live routes), ``wmd.dispatch`` (the jitted call, which
        returns once the work is queued), ``wmd.fetch`` (the wait on the
        device and the copy back) and ``wmd.check`` (the numeric guards);
        ``last_batch_stats`` carries them under ``spans`` (see `_finish`).
        """
        spans: list = []
        with span("wmd.query_batch", spans, q=len(rs)):
            out, stats = self._query_batch_routed(rs, spans, docs_chunk,
                                                  use_cache)
        if stats is not None:
            self._finish(stats, spans)
        return out

    def _query_batch_routed(self, rs, spans: list, docs_chunk, use_cache):
        """`query_batch`'s routes: (distances, stats for `_finish`), the
        stats None when the call dispatched nothing."""
        if self.live is not None:
            return self._query_batch_live(rs, spans, use_cache=use_cache)
        if len(rs) == 0:
            return np.zeros((0, self.ell.num_docs), np.float32), None
        # under an armed underflow gate every dispatch routes through the
        # stripes engine so the K*M pre-check (`core.guards.check_km_rows`)
        # sees the assembled rows; off at every shipped lambda, so the
        # routing below is untouched in production
        risk = self._underflow_risk()
        q = len(rs)
        # cache disabled and no explicit routing request: the legacy
        # single-program engine (precompute fused into the solve) is the
        # faster plan -- the split stripes path pays an extra dispatch that
        # only the cache can win back. Pass use_cache=True/False to route a
        # cache-less service through the stripes engine anyway (e.g. for
        # the bench's phase split).
        legacy = use_cache is None and self.cache_capacity == 0 and not risk
        with span("wmd.prepare", spans):
            self._validate_queries(rs)
            sel_b, r_b, mask_b = self._padded_query_batch(rs)
            dc = self._docs_chunk(len(sel_b), docs_chunk)
            if legacy:
                vecs_b = self.vecs[sel_b]
        if legacy:
            fn = self._batch_fn(dc)
            with span("wmd.dispatch", spans):
                self._count_dispatch(mask_b, self._ell_nnz,
                                     chunk_docs=dc or self._n_loc)
                wmd = fn(jnp.asarray(vecs_b), jnp.asarray(r_b),
                         jnp.asarray(mask_b), self._vecs_d, self._cols_d,
                         self._vals_d)
            # precompute is fused into the solve program here, so the
            # phases are not separable -- still report the solve time
            # instead of silently dropping the call from attribution
            stats = {"phases_separable": False, "route": "legacy_fused"}
        else:
            fn = self._stripe_fn(dc)
            k_s, km_s, stats = self._cache_rows(sel_b, mask_b, use_cache,
                                                spans)
            with span("wmd.dispatch", spans):
                self._count_dispatch(mask_b, self._ell_nnz,
                                     chunk_docs=dc or self._n_loc)
                wmd = fn(k_s, km_s, jnp.asarray(r_b), self._cols_d,
                         self._vals_d)
        with span("wmd.fetch", spans):
            wmd = np.asarray(wmd)[:q]
        with span("wmd.check", spans):
            self._check_result(wmd, what="query_batch distances")
        return wmd, stats

    def _cache_rows(self, sel_b, mask_b, use_cache, spans: list):
        """The K-cache stripes of a padded batch (``use_cache=False`` is
        the transient baseline), waited for, then the K*M pre-check."""
        self._kcache.ensure_lamb(self.cfg.lamb)   # lambda-invalidation
        with span("wmd.cache_rows", spans):
            k_s, km_s, info = self._kcache.stripes_for_batch(
                sel_b, mask_b, use_cache=use_cache is not False)
            jax.block_until_ready((k_s, km_s))
        with span("wmd.check", spans):
            self._check_km(km_s, mask_b)
        return k_s, km_s, info

    def _finish(self, stats: dict, spans: list) -> None:
        """Set ``last_batch_stats`` from a call's route stats and spans.
        The phase split is read off the same spans: ``precompute_s`` is
        ``wmd.cache_rows`` (routes that have it), ``solve_s`` is
        ``wmd.dispatch`` plus ``wmd.fetch``. Outside `warming`, every
        span's seconds go to the ``wmd_span_seconds`` histogram."""
        def seconds(*names):
            return sum(t1 - t0 for n, t0, t1 in spans if n in names)
        stats = dict(stats, solve_s=seconds("wmd.dispatch", "wmd.fetch"),
                     spans=spans)
        if any(n == "wmd.cache_rows" for n, _, _ in spans):
            stats["precompute_s"] = seconds("wmd.cache_rows")
        self.last_batch_stats = stats
        if self._warming:
            return
        for n, t0, t1 in spans:
            self.metrics.histogram(
                "wmd_span_seconds", "seconds in each stage of a service call",
                labels={"span": n}).observe(t1 - t0)

    def _count_dispatch(self, mask: np.ndarray, ell_nnz: tuple, *,
                        chunk_docs: int | None = None) -> None:
        """Count one solve dispatch's swept slots and doc chunks (skipped
        in warm-up): ``mask`` is its padded query mask, ``ell_nnz`` the
        (real, all) slots of the ELL segment it gathers over.
        ``chunk_docs`` is the documents a chunk of a batched solve over the
        service ELL (its local slice where unchunked): such a dispatch also
        sets the plan gauges. Every other dispatch sweeps its documents as
        one chunk."""
        if self._warming:
            return
        real = int(np.count_nonzero(mask))
        for what, (n_real, n_all) in (("query", (real, mask.size)),
                                      ("ell", ell_nnz)):
            self._slots[what, "real"].inc(n_real)
            self._slots[what, "pad"].inc(n_all - n_real)
        if chunk_docs is None:
            self._solve_chunks.inc(1)
            return
        self._solve_chunks.inc(-(-self._n_loc // chunk_docs))
        self.metrics.gauge(
            "wmd_solve_chunk_docs",
            "documents a chunk of the last batched solve dispatch").set(
                chunk_docs)
        self.metrics.gauge(
            "wmd_solve_chunk_bytes",
            "the blocks of a chunk of the last batched solve dispatch, as "
            "core.distributed.solve_bytes_per_doc counts them").set(
                chunk_docs * solve_bytes_per_doc(
                    *mask.shape, self._rb.cols.shape[-1]))

    @property
    def _warming(self) -> bool:
        return getattr(self._warm_local, "on", False)

    @contextlib.contextmanager
    def warming(self):
        """Mark this thread's dispatches as warm-up (`serving.warmup.warm`):
        they compile programs and are not traffic, so the slot counters and
        the span histogram skip them."""
        prev = self._warming
        self._warm_local.on = True
        try:
            yield
        finally:
            self._warm_local.on = prev

    def query_batch_sequential(self, rs: Sequence[np.ndarray]) -> np.ndarray:
        """A loop of Q = 1 dispatches -- the oracle/baseline for
        query_batch."""
        return np.stack([self.query(r) for r in rs])

    def _padded_query_batch(self, rs: Sequence[np.ndarray]):
        """Select + bucket-pad queries and append pow2 admission filler.

        Filler queries are all-pad (mask == 0 everywhere): their stripe
        rows are zeroed (K path) resp. +inf (M path), so they solve to 0 /
        bound to 0 and are sliced off. Returns (sel_b, r_b, mask_b), each
        (Q_pow2, v_r)."""
        sels, rsels = zip(*[select_query(r) for r in rs])
        sel_b, r_b, mask_b = pad_query_batch(sels, rsels, self.cfg.v_r)
        q_pad = _next_pow2(len(rs)) - len(rs)
        if q_pad:
            sel_b = np.concatenate(
                [sel_b, np.zeros((q_pad, self.cfg.v_r), sel_b.dtype)])
            r_b = np.concatenate(
                [r_b, np.ones((q_pad, self.cfg.v_r), r_b.dtype)])
            mask_b = np.concatenate(
                [mask_b, np.zeros((q_pad, self.cfg.v_r), mask_b.dtype)])
        return sel_b, r_b, mask_b

    @staticmethod
    def _top_k(d: np.ndarray, k: int) -> np.ndarray:
        """Indices of the k smallest distances, ordered by (distance,
        doc id): argpartition (O(N)) + an O(N) tie sweep + a local sort of
        k (O(k log k)) instead of a full O(N log N) argsort.

        Ties at the k-th value are broken by the smallest doc id --
        argpartition's internal tie placement is arbitrary, and a
        deterministic selection rule is what lets every route (full scan,
        exhaustive chunked scan, pruned) return the *identical* set even
        when the corpus contains duplicate docs. (On a live corpus the
        positions are ascending-id order, so position ties ARE id ties.)"""
        k = min(k, d.shape[-1])
        if k <= 0:                 # empty live corpus: (Q, 0) selections
            return np.zeros((*d.shape[:-1], 0), np.int64)
        flat = d.reshape(-1, d.shape[-1])
        out = np.empty((flat.shape[0], k), np.int64)
        for i, row in enumerate(flat):
            kth = np.partition(row, k - 1)[k - 1]
            below = np.nonzero(row < kth)[0]           # <= k - 1 of these
            ties = np.nonzero(row == kth)[0][:k - below.size]
            idx = np.concatenate([below, ties])
            out[i] = idx[np.lexsort((idx, row[idx]))]
        return out.reshape(*d.shape[:-1], k)

    def top_k(self, r: np.ndarray, k: int = 10, *, prune: bool = False,
              **kw) -> tuple[np.ndarray, np.ndarray]:
        """Nearest-k docs for one query. ``prune=True`` routes through the
        two-tier pruned engine (see `top_k_batch`)."""
        if prune:
            idx, dist = self.top_k_batch([r], k, prune=True, **kw)
            return idx[0], dist[0]
        d = self.query(r)
        idx = self._top_k(d, k)
        dist = d[idx]
        if self.live is not None and idx.size:
            idx = self._live_ids[idx]      # positions -> real doc ids
        return idx, dist

    def top_k_batch(self, rs: Sequence[np.ndarray], k: int = 10, *,
                    prune: bool = False, rerank: str = "per_query",
                    **kw) -> tuple[np.ndarray, np.ndarray]:
        """Batched nearest-k: (Q, k) doc ids + distances.

        Default: `query_batch` (one device program for all Q x N solves)
        followed by the tie-deterministic selection; ``**kw`` forwards
        docs_chunk / use_cache. With ``prune=True`` the two-tier
        engine runs instead -- RWMD prefilter over all N docs, exact
        Sinkhorn rerank only on the candidate prefix -- and returns the
        bitwise-identical set as `top_k_scan_batch` while skipping the
        pruned docs' solves (stats in ``last_prune_stats``). ``**kw`` then
        forwards use_cache / prune_chunk / prune_margin.

        ``rerank`` picks the pruned rerank strategy: ``"per_query"`` (the
        online default -- each query visits its own candidate blocks with
        (1, chunk) programs) or ``"union"`` (the offline bulk strategy --
        all Q queries rerank shared candidate blocks with ONE (Q, chunk)
        program per block, so correlated batches pay ~1/Q the program
        dispatches). Both return the bitwise-identical set: every solved
        (query, doc) distance comes from the same fixed-shape program
        family, and both prune only docs provably outside the top-k (see
        `_top_k_union`).

        Live services return REAL doc ids (ascending-id positions mapped
        through `live_doc_ids`), and ``prune=True`` runs the cascade over
        the immutable base segment while exact-solving the small delta
        outright (`_top_k_live_pruned`) -- same bits as the full scan,
        most of its speedup. Only ``rerank="union"`` still degrades to the
        exact full scan (`_top_k_live_fallback`, counted by the
        ``wmd_prune_fallback_total`` metric): the answer is identical by
        the pruned == scan contract, only the speedup is forfeited."""
        if rerank not in ("per_query", "union"):
            raise ValueError(f"rerank must be per_query|union, "
                             f"got {rerank!r}")
        if rerank == "union" and not prune:
            raise ValueError("rerank='union' is a pruned-rerank strategy; "
                             "pass prune=True")
        if prune:
            if self.live is not None:
                if rerank == "union":
                    return self._top_k_live_fallback(rs, k, **kw)
                return self._top_k_live_pruned(rs, k, exhaustive=False,
                                               **kw)
            if rerank == "union":
                return self._top_k_union(rs, k, **kw)
            return self._top_k_pruned(rs, k, exhaustive=False, **kw)
        d = self.query_batch(rs, **kw)
        idx = self._top_k(d, k)
        dist = np.take_along_axis(d, idx, axis=-1)
        if self.live is not None and idx.size:
            idx = self._live_ids[idx]      # positions -> real doc ids
        return idx, dist

    def top_k_scan_batch(self, rs: Sequence[np.ndarray], k: int = 10,
                         **kw) -> tuple[np.ndarray, np.ndarray]:
        """The pruned path's exactness oracle: solve EVERY doc through the
        same bound-ordered, fixed-shape chunked rerank programs, then
        select. Bitwise-identical to ``top_k_batch(prune=True)`` by
        construction of the shared prefix (identical programs on identical
        inputs) plus bound soundness for the pruned suffix."""
        if self.live is not None:
            return self._top_k_live_pruned(rs, k, exhaustive=True, **kw)
        return self._top_k_pruned(rs, k, exhaustive=True, **kw)

    @_serialized
    def _top_k_live_fallback(self, rs: Sequence[np.ndarray], k: int, *,
                             use_cache: bool | None = None,
                             prune_chunk: int | None = None,
                             prune_margin: float | None = None
                             ) -> tuple[np.ndarray, np.ndarray]:
        """Pruned-top-k fallback on a live corpus: the exact full scan
        through the per-segment dispatch. The prune knobs are accepted and
        ignored (there is nothing to prune); ``last_prune_stats`` records
        the route and ``wmd_prune_fallback_total`` counts the dispatch so
        callers/benches/dashboards see the forfeited speedup. Since the
        segment-aware pruned path landed, only ``rerank="union"`` (whose
        shared block schedule does not yet span segments) routes here."""
        self._prune_fallbacks.inc()
        t0 = time.perf_counter()
        d = self.query_batch(rs, use_cache=use_cache)
        q, n = d.shape
        k_eff = min(k, n)
        idx = self._top_k(d, k_eff)
        dist = np.take_along_axis(d, idx, axis=-1)
        self.last_prune_stats = {
            "queries": q, "docs": n, "k": k_eff, "chunk": 0, "margin": 0.0,
            "exhaustive": True, "rerank": "live_full_scan",
            "exact_solves": q * n, "scan_solves": q * n,
            "solves_avoided": 0.0, "rerank_programs": 0,
            "bound_s": 0.0, "rerank_s": time.perf_counter() - t0,
        }
        ids = self._live_ids[idx] if idx.size else idx
        return ids, dist

    @_serialized
    def _top_k_live_pruned(self, rs: Sequence[np.ndarray], k: int, *,
                           exhaustive: bool,
                           use_cache: bool | None = None,
                           prune_chunk: int | None = None,
                           prune_margin: float | None = None
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Pruned top-k over a live corpus: cascade bounds over the
        immutable base segment, exact-solve the delta outright.

        Per query, the delta segment -- small, capacity-bounded, and the
        only part that mutates between compactions -- is solved whole with
        the same unchunked per-segment program `_query_batch_live`
        dispatches, seeding the running k-th-distance threshold. Live base
        docs are then visited in ascending cascade-bound order through the
        same fixed ``(1, chunk)`` stripes programs as the static pruned
        path, pruning against that threshold. The result is bitwise the
        full-scan answer for the usual three reasons: per-doc distance
        bits are independent of chunk-mates and batch-mates, the K cache
        assembles bit-identical rows either way, and a pruned doc's exact
        distance strictly exceeds the final threshold so it can neither
        enter nor tie into the top-k. ``exhaustive`` disables the drop
        (same programs, same order) -- the live scan oracle."""
        self._refresh_live()
        n_live = self._live_ids.size
        q = len(rs)
        k_eff = min(k, n_live)
        if q == 0 or n_live == 0:
            return (np.zeros((q, k_eff), np.int64),
                    np.zeros((q, k_eff), np.float32))
        self._validate_queries(rs)
        chunk = self._rerank_chunk if prune_chunk is None else \
            -(-max(prune_chunk, 1) // self._doc_shards) * self._doc_shards
        margin = self.prune_margin if prune_margin is None else prune_margin
        sel_b, r_b, mask_b = self._padded_query_batch(rs)
        use = use_cache is not False
        t0 = time.perf_counter()
        combined, tiers = self._cascade_bounds(sel_b, r_b, mask_b,
                                               use_cache=use)
        bounds = combined[:q]               # columns: base-segment rows
        t_bound = time.perf_counter() - t0
        self._kcache.ensure_lamb(self.cfg.lamb)   # lambda-invalidation
        fn = self._stripe_fn(None)
        bpos = np.nonzero(self._live_seg == 0)[0]   # live base positions
        dpos = np.nonzero(self._live_seg == 1)[0]   # live delta positions
        brow = self._live_row[bpos]                 # base-segment rows
        idx_out = np.empty((q, k_eff), np.int64)
        d_out = np.empty((q, k_eff), np.float32)
        solves = 0
        programs = 0
        hits = misses = 0
        t0 = time.perf_counter()
        for i in range(q):
            k_s, km_s, info = self._kcache.stripes_for_batch(
                sel_b[i:i + 1], mask_b[i:i + 1], use_cache=use)
            self._check_km(km_s, mask_b[i:i + 1])
            hits += info["hits"]
            misses += info["misses"]
            r_q = jnp.asarray(r_b[i:i + 1])
            solved_d = np.full(n_live, np.inf, np.float32)
            if dpos.size:
                d_seg = np.asarray(fn(k_s, km_s, r_q, self._dcols_d,
                                      self._dvals_d))[0]
                solved_d[dpos] = d_seg[self._live_row[dpos]]
                programs += 1
            n_solved = dpos.size
            threshold = np.inf
            if n_solved >= k_eff:
                cur = self._top_k(solved_d, k_eff)
                threshold = float(solved_d[cur[-1]])
            lb = bounds[i][brow]            # bounds per live base position
            order = np.argsort(lb, kind="stable")
            pos = 0
            while pos < bpos.size:
                block = order[pos:pos + chunk]
                if not exhaustive and n_solved >= k_eff:
                    block = block[lb[block] * (1.0 - margin) <= threshold]
                    if block.size == 0:
                        break
                solved_d[bpos[block]] = self._solve_docs(
                    fn, k_s, km_s, r_q, brow[block], chunk)[0]
                solves += block.size
                programs += 1
                n_solved += block.size
                pos += block.size
                if n_solved >= k_eff:
                    cur = self._top_k(solved_d, k_eff)
                    threshold = float(solved_d[cur[-1]])
            sel = self._top_k(solved_d, k_eff)
            idx_out[i] = sel
            d_out[i] = solved_d[sel]
        t_rerank = time.perf_counter() - t0
        exact = solves + q * int(dpos.size)
        final_thresh = (d_out[:, -1].astype(np.float32) if k_eff
                        else np.full(q, np.inf, np.float32))
        n_base = int(self._ell_cols_d.shape[0])
        self.last_prune_stats = {
            "queries": q, "docs": n_live, "k": k_eff, "chunk": chunk,
            "margin": margin, "exhaustive": exhaustive,
            "rerank": "live_pruned",
            "exact_solves": exact, "scan_solves": q * n_live,
            "solves_avoided": 1.0 - exact / (q * n_live),
            "rerank_programs": programs, "delta_docs": int(dpos.size),
            "bound_s": t_bound, "rerank_s": t_rerank,
            "tiers": self._tier_stats(tiers, final_thresh, q, n_base,
                                      margin),
        }
        self._check_result(d_out, what="top_k distances",
                           empty_doc_mask=self._live_empty[idx_out])
        total = hits + misses
        self.last_batch_stats = {
            "hit_rate": hits / total if total else 0.0,
            "precompute_s": t_bound, "solve_s": t_rerank,
        }
        ids = self._live_ids[idx_out] if idx_out.size else idx_out
        return ids, d_out

    # -- two-tier pruned retrieval ---------------------------------------

    def _bounds_for_batch(self, sel_b: np.ndarray, mask_b: np.ndarray, *,
                          use_cache: bool = True) -> np.ndarray:
        """(Q_pow2, v_r) padded queries -> (Q_pow2, N) RWMD lower bounds.

        One batched prefilter program: word ids deduped across the whole
        batch (the K-cache's dedup pattern), M rows served by the M-row
        cache (transient path == `assemble_m_stripes`, bitwise), one
        min-SDDMM over the replicated corpus ELL. This is the brownout
        tier's bound; the pruned top-k paths use `_cascade_bounds`."""
        m_pad, _ = self._mcache.m_stripes_for_batch(sel_b, mask_b,
                                                    use_cache=use_cache)
        lb = rwmd_core.rwmd_bound_batch(
            m_pad, self._ell_cols_d, self._ell_vals_d,
            impl=self.bound_impl, docs_chunk=self.bound_docs_chunk)
        return np.asarray(lb)

    def _base_centroids(self):
        """Cached tier-0 moments of the current base ELL (lazy; dropped by
        `_refresh_live` when a compaction swaps the base segment)."""
        if self._cent is None:
            self._cent = cascade_core.doc_centroids(
                self._ell_cols_d, self._ell_vals_d, self._vecs_d)
        return self._cent

    def _cascade_bounds(self, sel_b: np.ndarray, r_b: np.ndarray,
                        mask_b: np.ndarray, *, use_cache: bool = True
                        ) -> tuple[np.ndarray, list]:
        """Run the enabled bound tiers over the (base) corpus and compose.

        Returns ``(combined, tiers)``: combined (Q_pow2, N) is the
        elementwise max of every enabled tier's bounds -- a max of lower
        bounds is a lower bound, so the composition is sound tier-by-tier
        and the prune contract (bounds only reorder and skip) is inherited
        unchanged. With every tier disabled the combined bound is all
        zeros: distances are >= 0, so a zero bound never prunes and the
        pruned path degenerates to the exhaustive scan -- same bits, no
        speedup. ``tiers`` carries per-tier (name, bounds, seconds) for
        the post-hoc survivor stats (`_tier_stats`).

        Tier 0 (centroid screen) is one dense (Q, dim) x (dim, N) matmul
        over cached per-doc moments. Tier 1 (LC-RWMD) reduces the M
        stripes to per-vocab-word min-cost vectors once per query, then
        scores every doc with one sparse dot. Tier 2 re-derives the
        doc-side RWMD on the ``tier2_cap`` most-promising docs only (by
        min-over-queries combined bound so every query shares one subset)
        -- numerically it equals tier 1 where both run (the LC hoist is an
        identity), so its role is covering LC-disabled configs and pinning
        the tier-subsumption property; its cost is capped by the subset.
        """
        tiers: list[dict] = []
        n = int(self._ell_cols_d.shape[0])
        qp = sel_b.shape[0]
        combined = np.zeros((qp, n), np.float32)
        if self.tier0:
            t0 = time.perf_counter()
            g, m = self._base_centroids()
            b = np.asarray(cascade_core.centroid_bound_batch(
                jnp.asarray(sel_b), jnp.asarray(r_b), jnp.asarray(mask_b),
                self._vecs_d, g, m))
            tiers.append({"tier": "centroid", "bounds": b,
                          "seconds": time.perf_counter() - t0})
            combined = np.maximum(combined, b)
        need_m = self.lc_impl is not None or self.tier2_cap != 0
        if need_m:
            m_pad, _ = self._mcache.m_stripes_for_batch(
                sel_b, mask_b, use_cache=use_cache)
        if self.lc_impl is not None:
            t0 = time.perf_counter()
            minm = cascade_core.min_cost_vectors(m_pad)
            b = np.asarray(cascade_core.lc_rwmd_bound_batch(
                minm, self._ell_cols_d, self._ell_vals_d,
                impl=self.lc_impl, docs_chunk=self.bound_docs_chunk))
            tiers.append({"tier": "lc_rwmd", "bounds": b,
                          "seconds": time.perf_counter() - t0})
            combined = np.maximum(combined, b)
        t2 = (4 * self._rerank_chunk if self.tier2_cap is None
              else self.tier2_cap)
        t2 = min(t2, n)
        if t2 > 0:
            t0 = time.perf_counter()
            key = combined.min(axis=0)
            subset = np.sort(np.argsort(key, kind="stable")[:t2])
            lb2 = np.asarray(rwmd_core.rwmd_bound_batch(
                m_pad, self._ell_cols_d[subset], self._ell_vals_d[subset],
                impl=self.bound_impl, docs_chunk=None))
            b = np.zeros_like(combined)
            b[:, subset] = lb2
            tiers.append({"tier": "rwmd", "bounds": b,
                          "seconds": time.perf_counter() - t0})
            combined = np.maximum(combined, b)
        return combined, tiers

    @staticmethod
    def _tier_stats(tiers: list, thresholds: np.ndarray, q: int, n: int,
                    margin: float) -> list[dict]:
        """Post-hoc per-tier survivor counts against the FINAL per-query
        thresholds: how many (query, doc) cells each tier's bound alone
        fails to prune (the same ``bound * (1 - margin) <= threshold``
        test the rerank loop applies), plus the cumulative survivors of
        the tiers composed so far -- the cascade's actual funnel."""
        out = []
        cum = None
        for t in tiers:
            b = t["bounds"][:q]
            cum = b if cum is None else np.maximum(cum, b)
            alive = b * (1.0 - margin) <= thresholds[:, None]
            alive_cum = cum * (1.0 - margin) <= thresholds[:, None]
            cells = max(q * n, 1)
            out.append({
                "tier": t["tier"], "seconds": t["seconds"],
                "survivors": int(alive.sum()),
                "solves_avoided": 1.0 - int(alive.sum()) / cells,
                "cascade_survivors": int(alive_cum.sum()),
                "cascade_solves_avoided":
                    1.0 - int(alive_cum.sum()) / cells,
            })
        return out

    def _solve_docs(self, fn, k_s, km_s, r_q, doc_ids: np.ndarray,
                    chunk: int) -> np.ndarray:
        """Exact distances of the stripes batch against a doc subset via
        ONE fixed-shape (Q, chunk) stripes program (Q = 1 on the per-query
        rerank path, the pow2 batch on the union path). Shorter subsets are
        padded with ELL pad docs (every slot the shard-local pad id, val 0
        -> the engine solves them to 0) and sliced off. Per-doc bits are
        independent of the chunk-mates, the position in the chunk, AND the
        Q-mates in the batch (each (q, doc) cell reduces over its own nnz /
        v_r axes only) -- the K-cache's fixed-shape-batch reproducibility
        argument extended across Q, which is what makes pruned == scan ==
        union-reranked a bitwise statement (pinned by tests/test_warmup.py
        and the rwmd property suite)."""
        m = doc_ids.size
        cols = self._rb.cols[:, doc_ids, :]
        vals = self._rb.vals[:, doc_ids, :]
        if m < chunk:
            pad = ((0, 0), (0, chunk - m), (0, 0))
            cols = np.pad(cols, pad, constant_values=self._rb.num_vocab)
            vals = np.pad(vals, pad)
        cols_d = jax.device_put(cols, self._rerank_spec)
        vals_d = jax.device_put(vals, self._rerank_spec)
        d = np.asarray(fn(k_s, km_s, r_q, cols_d, vals_d))
        return d[:, :m]

    @_serialized
    def _top_k_pruned(self, rs: Sequence[np.ndarray], k: int, *,
                      exhaustive: bool,
                      use_cache: bool | None = None,
                      prune_chunk: int | None = None,
                      prune_margin: float | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
        """Shared core of the pruned top-k and its exhaustive-scan oracle.

        Per query: visit docs in ascending-bound order in fixed ``chunk``
        blocks; solve each block with one (1, chunk) stripes program
        (precompute via the cross-query K cache); once k docs are solved,
        drop every doc whose ``bound * (1 - margin)`` exceeds the running
        k-th exact distance -- ascending order makes the survivors a
        prefix, so the first empty block ends the query. ``exhaustive``
        disables the drop (the oracle solves everything, same programs,
        same order). Docs pruned have exact distance >= bound > threshold
        *strictly*, so they cannot displace or tie any selected doc.
        """
        n = self.ell.num_docs
        k_eff = min(k, n)
        if len(rs) == 0:
            return (np.zeros((0, k_eff), np.int64),
                    np.zeros((0, k_eff), np.float32))
        self._validate_queries(rs)
        chunk = self._rerank_chunk if prune_chunk is None else \
            -(-max(prune_chunk, 1) // self._doc_shards) * self._doc_shards
        margin = self.prune_margin if prune_margin is None else prune_margin
        q = len(rs)
        sel_b, r_b, mask_b = self._padded_query_batch(rs)
        use = use_cache is not False
        t0 = time.perf_counter()
        combined, tiers = self._cascade_bounds(sel_b, r_b, mask_b,
                                               use_cache=use)
        bounds = combined[:q]
        t_bound = time.perf_counter() - t0
        self._kcache.ensure_lamb(self.cfg.lamb)   # lambda-invalidation
        fn = self._stripe_fn(None)  # chunk IS the block
        idx_out = np.empty((q, k_eff), np.int64)
        d_out = np.empty((q, k_eff), np.float32)
        solves = 0
        programs = 0
        hits = misses = 0
        t0 = time.perf_counter()
        for i in range(q):
            k_s, km_s, info = self._kcache.stripes_for_batch(
                sel_b[i:i + 1], mask_b[i:i + 1], use_cache=use)
            self._check_km(km_s, mask_b[i:i + 1])
            hits += info["hits"]
            misses += info["misses"]
            r_q = jnp.asarray(r_b[i:i + 1])
            lb = bounds[i]
            order = np.argsort(lb, kind="stable")      # ascending bounds
            solved_d = np.full(n, np.inf, np.float32)
            n_solved = 0
            threshold = np.inf
            pos = 0
            while pos < n:
                block = order[pos:pos + chunk]
                if not exhaustive and n_solved >= k_eff:
                    # bounds ascend within the block, so the survivors are
                    # its prefix; an empty prefix proves every remaining
                    # doc is outside the top-k
                    block = block[lb[block] * (1.0 - margin) <= threshold]
                    if block.size == 0:
                        break
                solved_d[block] = self._solve_docs(fn, k_s, km_s, r_q,
                                                   block, chunk)[0]
                solves += block.size
                programs += 1
                n_solved += block.size
                pos += block.size
                if n_solved >= k_eff:
                    cur = self._top_k(solved_d, k_eff)
                    threshold = float(solved_d[cur[-1]])
            sel = self._top_k(solved_d, k_eff)
            idx_out[i] = sel
            d_out[i] = solved_d[sel]
        t_rerank = time.perf_counter() - t0
        final_thresh = (d_out[:, -1].astype(np.float32) if k_eff
                        else np.full(q, np.inf, np.float32))
        self.last_prune_stats = {
            "queries": q, "docs": n, "k": k_eff, "chunk": chunk,
            "margin": margin, "exhaustive": exhaustive,
            "rerank": "per_query",
            "exact_solves": solves, "scan_solves": q * n,
            "solves_avoided": 1.0 - solves / (q * n),
            "rerank_programs": programs,
            "bound_s": t_bound, "rerank_s": t_rerank,
            "tiers": self._tier_stats(tiers, final_thresh, q, n, margin),
        }
        # underflowed zeros sort first, so the selected top-k surfaces them
        self._check_result(d_out, what="top_k distances",
                           empty_doc_mask=self._empty_doc_mask[idx_out])
        # aggregate cache telemetry so coalesced top-k dispatches feed the
        # same hit-rate passthrough as plain query dispatches
        total = hits + misses
        self.last_batch_stats = {
            "hit_rate": hits / total if total else 0.0,
            "precompute_s": t_bound, "solve_s": t_rerank,
        }
        return idx_out, d_out

    @_serialized
    def _top_k_union(self, rs: Sequence[np.ndarray], k: int, *,
                     use_cache: bool | None = None,
                     prune_chunk: int | None = None,
                     prune_margin: float | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Union rerank: the offline bulk-scoring strategy for correlated
        query batches -- one (Q, chunk) stripes program per candidate
        block instead of Q separate (1, chunk) programs.

        All Q queries share one block schedule: among docs still *needed*
        by at least one query, visit the lowest min-over-queries bound
        first, and every program solves the block for the whole batch (the
        rows a query did not ask for are free -- the program's cost is set
        by its shape). A doc is needed by query q until q has k exact
        distances and ``bound_q(doc) * (1 - margin) > threshold_q`` (the
        same sound prune test as the per-query path); thresholds only
        tighten and solved counts only grow, so "needed" is monotone
        decreasing and the loop ends at the first round with no needed doc.

        Bitwise identity with the per-query rerank (and hence with
        `top_k_scan_batch`) rests on three facts, each pinned by tests:
        (1) every solved (query, doc) distance is bit-identical across
        program shapes -- the stripes engine's per-cell contractions never
        cross the Q or chunk axes; (2) the K-cache assembles bit-identical
        stripe rows regardless of batch composition; (3) pruning is sound
        and *strict* -- a skipped doc has exact distance > the running
        threshold >= the true k-th distance, so it can neither enter nor
        tie into the top-k, and extra docs the union schedule solves that
        the per-query path pruned change nothing for the same reason.
        """
        n = self.ell.num_docs
        k_eff = min(k, n)
        if len(rs) == 0:
            return (np.zeros((0, k_eff), np.int64),
                    np.zeros((0, k_eff), np.float32))
        self._validate_queries(rs)
        chunk = self._rerank_chunk if prune_chunk is None else \
            -(-max(prune_chunk, 1) // self._doc_shards) * self._doc_shards
        margin = self.prune_margin if prune_margin is None else prune_margin
        q = len(rs)
        sel_b, r_b, mask_b = self._padded_query_batch(rs)
        use = use_cache is not False
        t0 = time.perf_counter()
        combined, tiers = self._cascade_bounds(sel_b, r_b, mask_b,
                                               use_cache=use)
        lb = combined[:q]                                     # (q, N)
        t_bound = time.perf_counter() - t0
        self._kcache.ensure_lamb(self.cfg.lamb)   # lambda-invalidation
        fn = self._stripe_fn(None)
        # ONE stripes assembly for the whole batch (vs per-query on the
        # online path) -- rows are bit-reproducible either way
        k_s, km_s, info = self._kcache.stripes_for_batch(sel_b, mask_b,
                                                         use_cache=use)
        self._check_km(km_s, mask_b)
        r_all = jnp.asarray(r_b)                  # (Q_pow2, v_r)
        min_lb = lb.min(axis=0)                   # union visit order key
        solved_d = np.full((q, n), np.inf, np.float32)
        unsolved = np.ones(n, bool)
        thresholds = np.full(q, np.inf, np.float32)
        n_solved = 0
        programs = 0
        t0 = time.perf_counter()
        while True:
            if n_solved >= k_eff:
                need = unsolved & (lb * (1.0 - margin)
                                   <= thresholds[:, None]).any(axis=0)
            else:
                # until every query has k exact distances, every unsolved
                # doc is a candidate (thresholds are still +inf)
                need = unsolved
            cand = np.nonzero(need)[0]
            if cand.size == 0:
                break
            block = cand[np.argsort(min_lb[cand], kind="stable")][:chunk]
            solved_d[:, block] = self._solve_docs(fn, k_s, km_s, r_all,
                                                  block, chunk)[:q]
            unsolved[block] = False
            programs += 1
            n_solved += block.size
            if n_solved >= k_eff:
                for i in range(q):
                    cur = self._top_k(solved_d[i], k_eff)
                    thresholds[i] = solved_d[i][cur[-1]]
        t_rerank = time.perf_counter() - t0
        idx_out = np.empty((q, k_eff), np.int64)
        d_out = np.empty((q, k_eff), np.float32)
        for i in range(q):
            sel = self._top_k(solved_d[i], k_eff)
            idx_out[i] = sel
            d_out[i] = solved_d[i][sel]
        solves = q * (n - int(unsolved.sum()))
        final_thresh = (d_out[:, -1].astype(np.float32) if k_eff
                        else np.full(q, np.inf, np.float32))
        self.last_prune_stats = {
            "queries": q, "docs": n, "k": k_eff, "chunk": chunk,
            "margin": margin, "exhaustive": False,
            "rerank": "union",
            "exact_solves": solves, "scan_solves": q * n,
            "solves_avoided": 1.0 - solves / (q * n),
            "rerank_programs": programs,
            "bound_s": t_bound, "rerank_s": t_rerank,
            "tiers": self._tier_stats(tiers, final_thresh, q, n, margin),
        }
        self.last_batch_stats = {
            "hit_rate": info.get("hit_rate", 0.0),
            "precompute_s": t_bound, "solve_s": t_rerank,
        }
        self._check_result(d_out, what="top_k distances",
                           empty_doc_mask=self._empty_doc_mask[idx_out])
        return idx_out, d_out

    # -- degraded tier: bound-only answers --------------------------------

    @_serialized
    def query_batch_bounds(self, rs: Sequence[np.ndarray]) -> np.ndarray:
        """Degraded tier: (Q, N) doc-side RWMD *lower bounds* instead of
        exact Sinkhorn distances -- the brownout answer.

        One O(nnz * v_r) prefilter program, no Sinkhorn iterations at all:
        orders of magnitude cheaper than `query_batch` and a sound lower
        bound at any budget (see core.rwmd). `serving.resilience` serves
        these (wrapped in `DegradedResult`, never raw) when the engine is
        browned out or every exact rung has failed."""
        if self.live is not None:
            t0 = time.perf_counter()
            lb = self._bounds_live(rs)
            self.last_batch_stats = {
                "precompute_s": time.perf_counter() - t0, "solve_s": 0.0,
                "degraded": True}
            if self.guards and lb.size:
                _guards.check_finite(lb, "rwmd bounds", lamb=self.cfg.lamb)
            return lb
        if len(rs) == 0:
            return np.zeros((0, self.ell.num_docs), np.float32)
        self._validate_queries(rs)
        q = len(rs)
        sel_b, r_b, mask_b = self._padded_query_batch(rs)
        t0 = time.perf_counter()
        lb = self._bounds_for_batch(sel_b, mask_b)[:q]
        t_bound = time.perf_counter() - t0
        self.last_batch_stats = {"precompute_s": t_bound, "solve_s": 0.0,
                                 "degraded": True}
        if self.guards:
            _guards.check_finite(lb, "rwmd bounds", lamb=self.cfg.lamb)
        return lb

    @_serialized
    def top_k_batch_bounds(self, rs: Sequence[np.ndarray], k: int = 10
                           ) -> tuple[np.ndarray, np.ndarray]:
        """Degraded top-k: nearest-k by RWMD bound only (no rerank). Same
        tie-deterministic selection as the exact paths, so a given bound
        matrix always yields the same id set."""
        lb = self.query_batch_bounds(rs)
        k_eff = min(k, lb.shape[-1])
        if len(rs) == 0:
            return (np.zeros((0, k_eff), np.int64),
                    np.zeros((0, k_eff), np.float32))
        idx = self._top_k(lb, k_eff)
        dist = np.take_along_axis(lb, idx, axis=-1)
        if self.live is not None and idx.size:
            idx = self._live_ids[idx]      # positions -> real doc ids
        return idx, dist

    # -- ahead-of-time warmup ---------------------------------------------

    def warmup(self, *, max_batch: int = 16, ks: Sequence[int] = (),
               kinds: Sequence[str] | None = None,
               queries: Sequence[np.ndarray] | None = None,
               seed: int = 0):
        """Precompile the full serving envelope (`serving.warmup`).

        Enumerates every program shape this service can be dispatched --
        pow2 Q buckets up to ``max_batch`` x request kinds ("plain", plus
        "top_k" per k in ``ks``; pass ``kinds`` to add the offline mode's
        "top_k_union") -- and runs one dispatch per shape, so a following
        serving session never meets a first-hit XLA compile. Combine with
        `serving.warmup.enable_compilation_cache` to persist the compiled
        programs across processes. Returns the `WarmupReport` (per-shape
        compile times; hand it to `QueryCoalescer.record_warmup` to
        surface in `ServingStats`)."""
        from repro.serving import warmup as _warmup
        registry = _warmup.ShapeRegistry.from_service(
            self, max_batch=max_batch, ks=ks, kinds=kinds)
        return _warmup.warm(self, registry, queries=queries, seed=seed)
