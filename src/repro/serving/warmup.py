"""Ahead-of-time program warmup: a registry of every program shape the
serving envelope can dispatch, precompiled at startup through a persisted
jax compilation cache.

Why a registry
--------------
The engine compiles one XLA program per *dispatch shape*: the pow2 Q
admission bucket x the request kind (plain distances / pruned top-k) x k x
the engine knobs baked into the jitted fns (docs_chunk, tol, prune_chunk). A first-hit compile costs 100-1000x a warm solve (PR 5
measured serve-loop p50 dropping 335 -> 58 ms from warming one program), so
a latency-mode service must never meet a shape cold. The ad-hoc warmers
this module replaces (`QueryCoalescer.warm` / `warm_top_k`, now shims over
this registry) each hand-walked one kind's buckets; the registry instead
*enumerates the whole envelope from the service config* -- the same
config the coalescer's admission rules read -- so "every shape the
coalescer can dispatch is warm" is a checkable statement
(tests/test_warmup.py cross-checks the registry against a randomized
session's dispatch log and asserts zero first-hit compiles after warmup).

    registry = ShapeRegistry.from_service(svc, max_batch=16, ks=(8,))
    report = warm(svc, registry)          # one dispatch per shape
    report.compile_s                      # total backend-compile seconds
    report.shapes["top_k/q8/k8"].compile_s  # ... per shape

Persisted compilation cache
---------------------------
`enable_compilation_cache()` turns on jax's persistent compilation cache
at ``$JAX_COMPILATION_CACHE_DIR`` or else at the fixed in-checkout
``.jax_cache/`` (entry thresholds zeroed so CPU-sized programs persist too).
Compiled programs are keyed by (HLO, jaxlib, flags) and written at compile
time; a later process -- the next serve run, a CI job restoring the
directory from `actions/cache` -- *re-lowers* each shape but skips the
XLA backend compile, which is where nearly all of the time goes. `warm`
reports both sides of that split per shape (``compile_s`` vs
``persistent_hits``/``retrieval_s``), which is how
benchmarks/bench_serving.py measures its cold-vs-warm-start delta.

Compile accounting
------------------
`measure_compiles()` counts *backend compiles* (the jax monitoring event
``/jax/core/compile/backend_compile_duration``) and persistent-cache
retrievals inside a ``with`` block. A shape served entirely from live jit
caches fires neither -- the post-warmup steady state the zero-first-hit
tests assert.

Cascade shapes
--------------
The top-k warm dispatches run the full retrieval cascade, so the tier-0
moments matmul, the LC-RWMD program for the configured ``lc_impl``, the
capped doc-side bound, and the M-cache's miss-compute/scatter programs
(shapes keyed by the same rows_bucket sweep as the K cache's) all compile
during warmup; no extra registry entries are needed because the tiers are
internal to the ``top_k``/``top_k_union`` dispatch shapes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import threading
import time
from typing import Iterable, Sequence

import numpy as np

# the service rounds Q up to these buckets; one copy of the rule
from repro.serving.coalescer import _next_pow2

# jax monitoring events. BACKEND_COMPILE_EVENT wraps the whole
# compile-OR-retrieve step (pxla times `compile_or_get_cached`), so it fires
# on persistent-cache hits too; the retrieval event fires only on hits,
# nested inside the compile span. True backend compiles are therefore
# events - hits (CompileCounter derives exactly that).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_KINDS = ("plain", "top_k", "top_k_union")


# -- compile-event accounting -------------------------------------------------

_listener_lock = threading.Lock()
_listener_installed = False
_active_counters: list["CompileCounter"] = []


@dataclasses.dataclass(eq=False)     # identity: nested counters may tie
class CompileCounter:
    """Compile-or-retrieve tallies for one measured span.

    ``events`` counts every compile-OR-retrieve step jax performed (one per
    program lowered to XLA, whether backend-compiled or deserialized from
    the persistent cache); ``persistent_hits`` the subset served from the
    cache. ``compiles`` -- what the zero-first-hit and cold-start numbers
    mean -- is the difference: programs that actually paid an XLA backend
    compile."""
    events: int = 0
    event_s: float = 0.0
    persistent_hits: int = 0
    retrieval_s: float = 0.0

    @property
    def compiles(self) -> int:
        return self.events - self.persistent_hits

    @property
    def compile_s(self) -> float:
        # retrieval spans are nested inside their compile-event span, so
        # subtracting leaves the pure backend-compile time
        return max(0.0, self.event_s - self.retrieval_s)


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    if event not in (_COMPILE_EVENT, _RETRIEVAL_EVENT):
        return
    with _listener_lock:
        for c in _active_counters:
            if event == _COMPILE_EVENT:
                c.events += 1
                c.event_s += duration
            else:
                c.persistent_hits += 1
                c.retrieval_s += duration


def _install_listener() -> None:
    # one process-wide listener, installed lazily on first measurement
    # (jax.monitoring has no deregistration, so registering per-measure
    # would leak a listener per call)
    global _listener_installed
    import jax.monitoring
    with _listener_lock:
        if not _listener_installed:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _listener_installed = True


@contextlib.contextmanager
def measure_compiles():
    """Count XLA backend compiles (and persistent-cache retrievals) issued
    while the block runs. Nestable; yields a `CompileCounter` whose fields
    are final once the block exits."""
    _install_listener()
    counter = CompileCounter()
    with _listener_lock:
        _active_counters.append(counter)
    try:
        yield counter
    finally:
        with _listener_lock:
            _active_counters.remove(counter)


# -- persisted compilation cache ---------------------------------------------

# in the checkout root (src/repro/serving/warmup.py -> parents[3])
DEFAULT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3]
                        / ".jax_cache")


def enable_compilation_cache(cache_dir: str | os.PathLike | None = None
                             ) -> str:
    """Turn on jax's persistent compilation cache; the one place that sets
    its directory.

    The directory is ``cache_dir`` when given (an explicit override), else
    ``$JAX_COMPILATION_CACHE_DIR`` when set (jax already reads it; nothing
    is set over it), else `DEFAULT_CACHE_DIR`, a fixed path inside the
    checkout -- the path is part of what a later process must find again,
    so it is never built from a temp dir, a pid or the time.

    Zeroes the entry thresholds (min compile time / min entry size) so the
    CPU-sized programs of the test and CI shapes persist too -- the
    defaults only persist second-scale compiles. Call before the first
    compile in the process; programs compiled afterwards are written
    eagerly, keyed by (HLO, jaxlib version, compile flags), so a crash or
    SIGINT after the first compile still leaves a warm cache behind.
    Returns the directory (created if missing)."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir is None and env_dir:
        cache_dir = env_dir
    else:
        cache_dir = os.fspath(cache_dir or DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def flush_compilation_cache() -> dict | None:
    """Surface the persisted compilation cache's on-disk state.

    jax writes cache entries eagerly at compile time, so there is no
    buffered data to force out; "flush" here means walking the configured
    directory so shutdown paths (serve.py's SIGINT handler) exit with the
    persisted state on record -- an interrupted serve run should still
    report the warm cache it leaves behind for the next start. Returns
    ``{"dir", "entries", "bytes"}`` or None when no cache is configured."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir or not os.path.isdir(cache_dir):
        return None
    entries = 0
    n_bytes = 0
    for name in os.listdir(cache_dir):
        if name.endswith("-cache"):
            entries += 1
            with contextlib.suppress(OSError):
                n_bytes += os.path.getsize(os.path.join(cache_dir, name))
    return {"dir": cache_dir, "entries": entries, "bytes": n_bytes}


# -- the registry -------------------------------------------------------------

@dataclasses.dataclass(frozen=True, order=True)
class ProgramShape:
    """One dispatch shape of the serving envelope.

    ``kind`` is the request kind the coalescer cuts batches by ("plain"
    distance rows, "top_k" = pruned per-query rerank, "top_k_union" = the
    offline bulk mode's (Q, chunk) union rerank); ``q_bucket`` the pow2
    admission bucket; ``k`` the retrieval size (None for plain)."""
    kind: str
    q_bucket: int
    k: int | None = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"kind must be one of {_KINDS}, "
                             f"got {self.kind!r}")
        if self.q_bucket != _next_pow2(self.q_bucket):
            raise ValueError(f"q_bucket must be a power of two, "
                             f"got {self.q_bucket}")
        if (self.k is None) == (self.kind != "plain"):
            raise ValueError(f"k must be set iff kind is top_k*, "
                             f"got kind={self.kind!r} k={self.k}")

    @property
    def label(self) -> str:
        tail = "" if self.k is None else f"/k{self.k}"
        return f"{self.kind}/q{self.q_bucket}{tail}"


class ShapeRegistry:
    """The serving envelope as an explicit, enumerable set of shapes.

    Built from the service config (`from_service`) rather than hand-listed:
    the pow2 Q buckets come from the admission rule (`_next_pow2`, the same
    rounding `WMDService._padded_query_batch` and the coalescer's
    ``max_batch`` use), the kinds and ks from what the deployment serves.
    ``covers`` is the membership test the warmup tests use to prove the
    coalescer can never dispatch a shape outside the registry."""

    def __init__(self, shapes: Iterable[ProgramShape]):
        self.shapes: tuple[ProgramShape, ...] = \
            tuple(dict.fromkeys(shapes))           # de-dup, keep order

    @classmethod
    def from_service(cls, svc, *, max_batch: int = 16,
                     ks: Sequence[int] = (),
                     kinds: Sequence[str] | None = None
                     ) -> "ShapeRegistry":
        """Enumerate the envelope: every pow2 Q bucket up to ``max_batch``
        x every request kind x every k the deployment serves.

        ``kinds`` defaults to "plain" plus "top_k" when ``ks`` is
        non-empty ("top_k_union" -- the offline mode's rerank shape -- must
        be requested explicitly: it is never dispatched by the online
        coalescer)."""
        if kinds is None:
            kinds = ("plain",) + (("top_k",) if ks else ())
        for kind in kinds:
            if kind not in _KINDS:
                raise ValueError(f"unknown kind {kind!r}")
        if any(kind != "plain" for kind in kinds) and not ks:
            raise ValueError("top_k kinds need at least one k in ks")
        buckets = []
        b = 1
        while b <= _next_pow2(max_batch):
            buckets.append(b)
            b *= 2
        shapes = []
        for kind in kinds:
            for b in buckets:
                if kind == "plain":
                    shapes.append(ProgramShape(kind, b))
                else:
                    shapes.extend(ProgramShape(kind, b, k=int(k))
                                  for k in ks)
        return cls(shapes)

    def __len__(self) -> int:
        return len(self.shapes)

    def __iter__(self):
        return iter(self.shapes)

    def covers(self, kind: str, q: int, k: int | None = None) -> bool:
        """True iff a dispatch of ``q`` requests of ``kind`` (with ``k``)
        pads into a bucket this registry enumerates."""
        b = _next_pow2(max(int(q), 1))
        return any(s.kind == kind and s.q_bucket == b and s.k == k
                   for s in self.shapes)

    @property
    def labels(self) -> list[str]:
        return [s.label for s in self.shapes]


# -- the warmup pass ----------------------------------------------------------

@dataclasses.dataclass
class ShapeWarmup:
    """Per-shape outcome of one warmup dispatch."""
    shape: ProgramShape
    wall_s: float                 # whole dispatch (compile + solve)
    compiles: int                 # XLA backend compiles triggered
    compile_s: float              # ... their total duration
    persistent_hits: int          # programs served from the persisted cache
    retrieval_s: float            # ... their deserialization time


@dataclasses.dataclass
class WarmupReport:
    """Outcome of one registry-driven warmup pass.

    ``shapes`` maps `ProgramShape.label` to its `ShapeWarmup`; the scalar
    totals are what `ServingStats` and the bench artifact record. A
    *cold* start shows ``compiles > 0`` and ``persistent_hits == 0``; a
    *warm* start (persisted cache primed by an earlier process) flips
    both -- the delta is the startup time the cache buys."""
    registry: ShapeRegistry
    shapes: dict[str, ShapeWarmup]
    wall_s: float

    @property
    def compiles(self) -> int:
        return sum(s.compiles for s in self.shapes.values())

    @property
    def compile_s(self) -> float:
        return sum(s.compile_s for s in self.shapes.values())

    @property
    def persistent_hits(self) -> int:
        return sum(s.persistent_hits for s in self.shapes.values())

    @property
    def retrieval_s(self) -> float:
        return sum(s.retrieval_s for s in self.shapes.values())

    def compile_s_by_label(self) -> dict[str, float]:
        return {lbl: s.compile_s for lbl, s in self.shapes.items()}

    def summary(self) -> dict:
        """JSON-friendly form (the bench artifact's warmup block)."""
        return {"shapes": self.registry.labels,
                "wall_s": self.wall_s,
                "compiles": self.compiles,
                "compile_s": self.compile_s,
                "persistent_hits": self.persistent_hits,
                "retrieval_s": self.retrieval_s,
                "per_shape": {
                    lbl: {"wall_s": s.wall_s, "compiles": s.compiles,
                          "compile_s": s.compile_s,
                          "persistent_hits": s.persistent_hits}
                    for lbl, s in self.shapes.items()}}


def synth_queries(cfg, n: int, *, seed: int = 0) -> list[np.ndarray]:
    """Deterministic synthetic (V,) query histograms for warmup dispatches.

    Shapes are all that matter to compilation -- the padded batch is
    (Q_pow2, cfg.v_r) regardless of content -- so warmup does not need
    real traffic; it draws ``v_r - 1`` distinct words per query (the
    densest admissible support) from a seeded rng."""
    rng = np.random.default_rng(seed)
    words = max(1, min(cfg.v_r - 1, cfg.vocab_size - 1))
    qs = []
    for _ in range(n):
        r = np.zeros(cfg.vocab_size, np.float32)
        idx = rng.choice(cfg.vocab_size, size=words, replace=False)
        r[idx] = rng.random(words).astype(np.float32) + 0.1
        r /= r.sum()
        qs.append(r)
    return qs


def _bound_chunk_payloads(cfg, q: int, rows_bucket: int, *, seed: int = 0):
    """One payload batch per feasible M-table chunk count of a top-k shape.

    The bound tier assembles its M-row table in fixed ``rows_bucket``
    blocks, so the table (and its slot-gather program) has
    ``ceil(unique_ids / rows_bucket) * rows_bucket + 1`` rows -- a program
    shape set by the batch's UNIQUE WORD COUNT, not by (kind, Q, k). One
    dispatch per (kind, Q, k) therefore leaves every other chunk count
    cold (the compile-counter tests caught exactly that). Sweep it: for
    each chunk count c, craft ``q`` queries whose supports union to
    ``min(c * rows_bucket, u_max)`` ids -- word 0 always in the pool (pad
    slots point at it, so it is resident in any real batch's id set),
    per-query supports striding the pool so the union is exact."""
    rng = np.random.default_rng(seed)
    words_max = max(1, min(cfg.v_r - 1, cfg.vocab_size - 1))
    u_max = min(q * words_max, cfg.vocab_size)
    c_max = -(-u_max // rows_bucket)
    for c in range(1, c_max + 1):
        u = min(c * rows_bucket, u_max)
        pool = np.zeros(u, np.int64)
        if u > 1:
            pool[1:] = rng.choice(np.arange(1, cfg.vocab_size),
                                  size=u - 1, replace=False)
        w = min(words_max, u)
        stride = -(-u // q)
        batch = []
        for i in range(q):
            idx = pool[[(i * stride + j) % u for j in range(w)]]
            r = np.zeros(cfg.vocab_size, np.float32)
            r[idx] = rng.random(w).astype(np.float32) + 0.1
            r /= r.sum()
            batch.append(r)
        yield batch


def warm(svc, registry: ShapeRegistry, *,
         queries: Sequence[np.ndarray] | None = None,
         seed: int = 0) -> WarmupReport:
    """Precompile every shape in ``registry`` with one dispatch each.

    Dispatches go through the *public* entry points (`query_batch` /
    `top_k_batch`), so whatever the admission policy routes a bucket to --
    the fused single-program engine, the stripes engine, the pruned rerank --
    is exactly what gets compiled, including the K-cache's fixed-shape
    row-compute/scatter/gather programs on the very first dispatch. Shapes
    run smallest-bucket first so per-shape compile attribution is sharp
    (a bucket never pre-compiles a larger bucket's program).

    ``queries`` (optional) supplies the warmup payloads -- the deprecation
    shims pass the caller's real queries through; by default seeded
    synthetic histograms are used (`synth_queries`). Warmup dispatches hit
    the real engine, so with a K cache enabled they also pre-populate row
    residency (synthetic payloads then fill the store with synthetic ids;
    real Zipf traffic evicts them within a few batches).

    Top-k shapes additionally sweep the bound tier's unique-word-count
    dimension (`_bound_chunk_payloads`): the M-row table's chunk count is
    a program shape of its own, so each (top_k*, Q, k) dispatches once
    per feasible chunk count on top of the ``queries`` payload. The
    zero-first-hit guarantee covers batches whose unique ids fit the K
    cache; a capacity-overflow batch takes the transient bypass, whose
    variably-shaped programs are deliberately outside the envelope.
    """
    max_q = max((s.q_bucket for s in registry), default=0)
    if queries is None:
        qs = synth_queries(svc.cfg, max_q, seed=seed)
    else:
        qs = list(queries)
        if 0 < len(qs) < max_q:                # cycle short payload lists
            reps = -(-max_q // len(qs))
            qs = (qs * reps)[:max_q]
    rows_bucket = getattr(svc, "cache_rows_bucket", 128)
    shapes: dict[str, ShapeWarmup] = {}
    t_start = time.perf_counter()
    # warm-up is not traffic: a service that counts traffic skips it
    warming = getattr(svc, "warming", contextlib.nullcontext)
    with warming():
        for shape in sorted(registry, key=lambda s: (s.q_bucket, s.kind)):
            batch = [qs[i] for i in range(shape.q_bucket)]
            t0 = time.perf_counter()
            with measure_compiles() as counter:
                if shape.kind == "plain":
                    svc.query_batch(batch)
                else:
                    rerank = "union" if shape.kind == "top_k_union" \
                        else "per_query"
                    svc.top_k_batch(batch, shape.k, prune=True,
                                    rerank=rerank)
                    for sweep in _bound_chunk_payloads(
                            svc.cfg, shape.q_bucket, rows_bucket, seed=seed):
                        svc.top_k_batch(sweep, shape.k, prune=True,
                                        rerank=rerank)
            shapes[shape.label] = ShapeWarmup(
                shape=shape, wall_s=time.perf_counter() - t0,
                compiles=counter.compiles, compile_s=counter.compile_s,
                persistent_hits=counter.persistent_hits,
                retrieval_s=counter.retrieval_s)
    return WarmupReport(registry=registry, shapes=shapes,
                        wall_s=time.perf_counter() - t_start)
