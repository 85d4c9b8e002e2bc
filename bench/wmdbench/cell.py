"""One run of one cell: build the deployment from the seed, warm it, drive
its traffic for the window, check what came back, reduce the metrics.

The window drives the service's own entry points, built as
``launch/serve.py`` builds it (one-chip ("data", "model") mesh, fused
solve, no K cache, cascade on, no Pallas kernel unless the configuration's
``service`` block says otherwise):

* ``arrival: bulk`` -- full ``max_batch`` buckets back to back through
  ``WMDService.query_batch`` (``request: full``) or
  ``top_k_batch(prune=True, rerank=...)`` (``request: top_k``), the calls
  `serving.offline.run_offline` makes;
* ``arrival: poisson`` -- one thread submits each request at its due time
  to a `QueryCoalescer` (``submit`` or ``submit_top_k``); latency runs from
  the due time to the answer.

`run` returns a plain dict; `bench/run.py` prints it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import threading
import time

import numpy as np

from wmdbench import devtrace, gen, loadgen, reference, spec

QUERY_BLOCK = 512          # queries drawn per block of the bulk stream
LATE_WAIT_S = 60.0         # an open-loop answer may come this late


@dataclasses.dataclass
class Ctx:
    """What a per-layer metric reader may read (see bench/metrics)."""
    cell: dict
    config: dict
    traffic: dict
    peaks: dict | None
    trace: dict | None
    batches: int                     # dispatches made in the window
    queries: int                     # queries answered in the window
    batch_words: list                # real words of each query, per batch
    nnz: int                         # real nonzeros of the corpus
    distinct_words: int              # distinct word ids of the corpus
    prune: list                      # last_prune_stats of each dispatch
    spans: list                      # the coalescer tracer's request trees
    registry: dict                   # the service registry's snapshot
    setup_s: float                   # process start to the window's start
    window: dict                     # the harness's own clock readings


class _Hooked:
    """The service as the window sees it: every call into the service is
    wrapped in a ``wmdbench.<method>`` profiler annotation (trace runs),
    and ``answer`` may replace what the service produced (the control and
    the fault tests); everything else passes through."""

    def __init__(self, svc, annotate: bool, answer=None, after=None):
        self._svc = svc
        self._annotate = annotate
        self._answer = answer
        self._after = after

    def __getattr__(self, name):
        return getattr(self._svc, name)

    def _call(self, method, rs, *a, **kw):
        import jax
        if self._annotate:
            with jax.profiler.TraceAnnotation(f"wmdbench.{method}"):
                out = getattr(self._svc, method)(rs, *a, **kw)
        else:
            out = getattr(self._svc, method)(rs, *a, **kw)
        if self._after is not None:
            self._after(method)
        if self._answer is not None:
            out = self._answer(method, rs, out)
        return out

    def query_batch(self, rs, *a, **kw):
        return self._call("query_batch", rs, *a, **kw)

    def top_k_batch(self, rs, *a, **kw):
        return self._call("top_k_batch", rs, *a, **kw)


def wmd_config(cfg: dict):
    from repro.configs.sinkhorn_wmd import WMDConfig
    return WMDConfig(name=cfg["name"], vocab_size=cfg["vocab_size"],
                     embed_dim=cfg["embed_dim"], num_docs=cfg["num_docs"],
                     nnz_max=cfg["nnz_max"], v_r=cfg["v_r"],
                     lamb=cfg["lamb"], max_iter=cfg["max_iter"])


def build(cfg: dict, corpus: gen.Corpus, devices):
    from repro.core.formats import EllDocs
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    ell = EllDocs(cols=corpus.cols, vals=corpus.frequencies(),
                  num_vocab=cfg["vocab_size"])
    return WMDService(mesh=mesh, cfg=wmd_config(cfg), vecs=corpus.vecs,
                      ell=ell, **cfg.get("service", {}))


def warm(svc, traffic: dict):
    """Compile the shapes this cell dispatches and no others: the bulk
    bucket of its kind, or every coalescer bucket up to max_batch."""
    from repro.serving.warmup import ProgramShape, ShapeRegistry, warm
    req, srv = traffic["request"], traffic["service"]
    b = int(srv["max_batch"])
    if req["kind"] == "full":
        kind, k = "plain", None
    elif traffic["arrival"]["kind"] == "bulk" and req["rerank"] == "union":
        kind, k = "top_k_union", int(req["k"])
    else:
        kind, k = "top_k", int(req["k"])
    if traffic["arrival"]["kind"] == "bulk":
        buckets = [b]
    else:
        buckets = [1 << i for i in range(b.bit_length()) if 1 << i <= b]
    reg = ShapeRegistry([ProgramShape(kind, q, k=k) for q in buckets])
    return warm(svc, reg)


class QueryStream:
    """The cell's queries in order, drawn from the seed in blocks."""

    def __init__(self, cfg, source, seed):
        self.cfg, self.source, self.seed = cfg, source, seed
        self.blocks: list[gen.Queries] = []

    def _block(self, i: int) -> gen.Queries:
        while len(self.blocks) <= i // QUERY_BLOCK:
            self.blocks.append(gen.make_queries(
                self.cfg, self.source, QUERY_BLOCK, self.seed,
                block=len(self.blocks)))
        return self.blocks[i // QUERY_BLOCK]

    def get(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        blk = self._block(i)
        return blk.ids[i % QUERY_BLOCK], blk.weights[i % QUERY_BLOCK]

    def dense(self, i: int) -> np.ndarray:
        return self._block(i).dense(i % QUERY_BLOCK, self.cfg["vocab_size"])

    def clipped_share(self, n: int) -> float:
        """Share of the first ``n`` queries whose length was cut at v_r."""
        if not n:
            return 0.0
        self._block(n - 1)
        return float(np.concatenate([b.clipped for b in self.blocks])[:n]
                     .mean())


def run_bulk(target, stream: QueryStream, traffic: dict, seconds: float):
    req, b = traffic["request"], int(traffic["service"]["max_batch"])
    answers = []
    t0 = time.perf_counter()
    i = 0
    while True:
        rs = [stream.dense(i + j) for j in range(b)]
        if req["kind"] == "full":
            out = target.query_batch(rs)
            answers.extend((i + j, out[j]) for j in range(b))
        else:
            idx, dist = target.top_k_batch(rs, int(req["k"]), prune=True,
                                           rerank=req["rerank"])
            answers.extend((i + j, (idx[j], dist[j])) for j in range(b))
        i += b
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    return {"answers": dict(answers), "attempted": i, "failed": 0,
            "batches": i // b, "wall_s": wall}


def run_open(target, svc, stream: QueryStream, traffic: dict,
             seconds: float, seed: int, tracer=None):
    from repro.serving.coalescer import QueryCoalescer
    req, srv = traffic["request"], traffic["service"]
    due = gen.arrival_times(traffic["arrival"], seconds, seed)
    n = due.size
    done = np.full(n, np.nan)
    answers: dict = {}
    errors: dict = {}
    lock = threading.Lock()
    all_done = threading.Event()
    left = [n]

    def finish(i, fut):
        t = time.monotonic()
        with lock:
            done[i] = t
            if fut.exception() is not None:
                errors[i] = repr(fut.exception())
            else:
                answers[i] = fut.result()
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    co = QueryCoalescer(target, window_ms=float(srv["window_ms"]),
                        max_batch=int(srv["max_batch"]),
                        max_queue=int(srv.get("max_queue", 256)),
                        metrics=svc.metrics, tracer=tracer)
    k = int(req["k"]) if req["kind"] == "top_k" else None

    def submit(i, r):
        fut = co.submit(r) if k is None else co.submit_top_k(r, k)
        fut.add_done_callback(functools.partial(finish, i))

    try:
        t0, submitted = loadgen.drive(submit, due, prepare=stream.dense)
        if n == 0:
            all_done.set()
        all_done.wait(timeout=max(0.0, t0 + seconds - time.monotonic())
                      + LATE_WAIT_S)
    finally:
        co.shutdown(drain=False)
    with lock:
        return {"answers": dict(answers), "attempted": n,
                "failed": int(np.isnan(done).sum()) + len(errors),
                "errors": dict(errors),
                "latency_ms": loadgen.latency_ms(t0, due, done),
                "lateness_ms": loadgen.lateness_ms(t0, due, submitted),
                "batches": None,
                "wall_s": float(np.nanmax(done) - t0) if n else 0.0}


def _served_sample(answers: dict, n_check: int, seed: int) -> list[int]:
    keys = sorted(answers)
    rng = gen.rng_for(seed, 5)
    take = min(n_check, len(keys))
    return sorted(rng.choice(keys, size=take, replace=False).tolist())


def check(cfg, corpus, stream, traffic, answers, seed, limits) -> tuple:
    """Compare a seeded sample of the answers with the reference, over
    every document. Returns ({name: {"value", "limit"}}, queries checked)."""
    import jax.numpy as jnp
    vecs = jnp.asarray(corpus.vecs)
    sample = _served_sample(answers, int(traffic["check"]["queries"]), seed)
    kw = dict(lamb=cfg["lamb"], iters=cfg["max_iter"], v_r=cfg["v_r"])
    full = traffic["request"]["kind"] == "full"
    rows = topk = 0.0
    missed = 0
    for i in sample:
        ids, w = stream.get(i)
        ref = reference.distances(vecs, ids, w, corpus.cols, corpus.counts,
                                  **kw)
        if full:
            rows = max(rows, reference.rows_error(answers[i], ref))
        else:
            idx, dist = answers[i]
            e, m = reference.topk_errors(idx, dist, ref,
                                         limits["topk_rel_err"])
            topk = max(topk, e)
            missed += m
    if full:
        out = {"rows_rel_err": rows}
    else:
        out = {"topk_rel_err": topk, "topk_missed": missed}
    return {name: {"value": v, "limit": limits[name]}
            for name, v in out.items()}, len(sample)


def control_answers(cfg, corpus, dtype="bfloat16"):
    """The reference, computed in ``dtype`` (a precision step below the
    configuration's float32), in the program's place."""
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    vecs = jnp.asarray(corpus.vecs)
    kw = dict(lamb=cfg["lamb"], iters=cfg["max_iter"], v_r=cfg["v_r"],
              dtype=dtype)

    def rows(rs):
        out = []
        for r in rs:
            ids = np.nonzero(r)[0]
            out.append(reference.distances(vecs, ids, r[ids], corpus.cols,
                                           corpus.counts, **kw))
        return np.stack(out).astype(np.float32)

    def answer(method, rs, served):
        d = rows(rs)
        if method == "query_batch":
            return d
        k = served[0].shape[1]
        idx = np.argsort(d, axis=1, kind="stable")[:, :k]
        return idx, np.take_along_axis(d, idx, axis=1)
    return answer


def run(bm: dict, cell_name: str, *, seed: int, seconds: float,
        trace: bool, devices, t_start: float, answer=None,
        root: str = spec.ROOT) -> dict:
    """Build, warm, drive and check one cell; returns the result fields.

    ``answer(cfg, corpus)``, when given, makes a function
    ``(method, rs, served) -> answers`` that replaces what the service
    produced: the control (`control_answers`) and the fault tests."""
    import jax
    from repro.obs.trace import Tracer
    from repro.serving import measure_compiles
    entry = spec.cell(bm, cell_name)
    cfg = spec.config(bm, entry, root)
    traffic = spec.traffic(entry, root)
    corpus = gen.make_corpus(cfg, int(cfg["num_docs"]), seed)
    stream = QueryStream(cfg, traffic["queries"], seed)
    with measure_compiles() as setup_compiles:
        svc = build(cfg, corpus, devices)
        warm_rep = warm(svc, traffic)
    prune: list = []
    batch_words: list = []

    def after(method):
        if method == "top_k_batch":
            prune.append(dict(svc.last_prune_stats))

    target = _Hooked(svc, annotate=trace, after=after,
                     answer=answer(cfg, corpus) if answer else None)
    tracer = Tracer(ring=1 << 20) if trace else None
    setup_s = time.perf_counter() - t_start
    res: dict = {}
    with measure_compiles() as window_compiles:
        prof = devtrace.capture(res) if trace else contextlib.nullcontext()
        with prof:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                if traffic["arrival"]["kind"] == "bulk":
                    out = run_bulk(target, stream, traffic, seconds)
                else:
                    out = run_open(target, svc, stream, traffic, seconds,
                                   seed, tracer=tracer)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:int(entry["chips"])])
    b = int(traffic["service"]["max_batch"])
    if traffic["request"]["kind"] == "full":
        for lo in range(0, out["attempted"], b):
            batch_words.append([int((stream.get(i)[0] >= 0).sum())
                                for i in range(lo, lo + b)])
    ctx = Ctx(cell=entry, config=cfg, traffic=traffic,
              peaks=None, trace=res.get("trace"),
              batches=out["batches"] if out["batches"] is not None
              else len(prune),
              queries=len(out["answers"]), batch_words=batch_words,
              nnz=corpus.nnz, distinct_words=corpus.distinct_words,
              prune=prune,
              spans=list(tracer.completed) if tracer else [],
              registry=svc.metrics.snapshot(), setup_s=setup_s,
              window={k: v for k, v in out.items() if k != "answers"})
    del svc, target
    gc.collect()
    limits = cfg["check_limits"]
    t_check = time.perf_counter()
    checks, checked = check(cfg, corpus, stream, traffic, out["answers"],
                            seed, limits)
    check_s = time.perf_counter() - t_check
    failed = int(out["failed"])
    correct = failed == 0 and checked > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": failed, "setup_s": setup_s, "out": out, "ctx": ctx,
            "checks": checks, "checked": checked, "check_s": check_s,
            "memory_peak_bytes": int(peak),
            "query_clip_share": stream.clipped_share(int(out["attempted"])),
            "setup_compiles": setup_compiles, "warmup": warm_rep,
            "window_compiles": window_compiles,
            "xplane_bytes": res.get("xplane_bytes")}
