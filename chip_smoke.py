"""End-to-end check that the Sinkhorn-WMD service runs on a TPU.

Drives a deployment of `configs/sinkhorn_wmd.py` (``--preset``: paper_5k,
V=100 000 words, 300-d embeddings, N=5000 docs, v_r=32, lambda=1, 15
iterations; or news20, V=29 671, N=11 293 docs of 72 words on average,
v_r=288) through the service's normal path -- `WMDService` with
`launch/serve.py`'s defaults, behind the async coalescer, warmed through
the shape registry -- and checks what comes back:

  a. Zipf full-distance requests (``submit``);
  b. the same queries as pruned top-k (``submit_top_k``) and through the
     exhaustive ``top_k_scan_batch``: the ids must be equal;
  c. a live corpus (``WMDService.from_live``): writes through the writer
     lane, one of them a doc equal to a query, whose top-1 must then be
     that doc; a removed doc must leave the answers.

news20 runs phase a alone, on one chip: its queries are whole documents
of the corpus's own law, held out of it, and its solve is chunked over
documents by the service's memory plan.

Every distance that is checked is compared with
`core.sinkhorn.sinkhorn_wmd_dense` run on the host CPU backend under
``jax.default_matmul_precision("highest")``, on a seeded sample of docs plus
every doc that landed in a top-k. Every check is fatal.

    python chip_smoke.py            # one chip: phases a, b, c
    python chip_smoke.py --chips 4  # a and b on a (4, 1) doc-sharded mesh,
                                    # compared with the same on one chip
    python chip_smoke.py --preset news20   # one chip: phase a

The last line of stdout is ``{"ok": true, "device": {...}}``. Without a TPU
the script exits non-zero before doing anything and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
MAX_BATCH = 8          # the coalescer's Q bucket; one full batch per phase
WINDOW_MS = 2.0
TOP_K = 10
SAMPLE_DOCS = 256
# A shared word's self-cost sqrt(|a|^2 + |b|^2 - 2 a.b) is f32 cancellation
# noise (up to ~0.03 at |v|^2 ~ 500) on any backend, which moved distances by
# up to 1.4e-3 relative between two f32 spellings on CPU; one bf16 matmul
# pass moves them by ~5e-2.
RTOL, ATOL = 5e-3, 0.05


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def expect(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- the reference -------------------------------------------------------------

class Reference:
    """Dense Sinkhorn-WMD on the host CPU at highest matmul precision, one
    query against a fixed-width block of docs given as (word ids, weights)."""

    def __init__(self, vecs, cfg, width: int):
        import jax
        self.cpu = jax.devices("cpu")[0]
        self.vecs = jax.device_put(vecs, self.cpu)
        self.cfg = cfg
        self.width = width

    def __call__(self, r, docs):
        import jax
        import numpy as np
        from repro.core import select_query, sinkhorn_wmd_dense
        assert len(docs) <= self.width
        c = np.zeros((self.cfg.vocab_size, self.width), np.float32)
        for j, (ids, w) in enumerate(docs):
            c[ids, j] = w
        sel, r_sel = select_query(r)
        put = lambda x: jax.device_put(x, self.cpu)       # noqa: E731
        with jax.default_matmul_precision("highest"):
            d = sinkhorn_wmd_dense(put(sel), put(r_sel), put(c), self.vecs,
                                   self.cfg.lamb, self.cfg.max_iter)
        return np.asarray(d)[:len(docs)]


def ell_doc(ell, j):
    row = ell.vals[j] != 0
    return ell.cols[j][row], ell.vals[j][row]


def compare(what: str, got, ref) -> dict:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.abs(got - ref)
    share = err / (RTOL * np.abs(ref) + ATOL)
    far = np.abs(ref) >= 1.0
    out = {"docs": int(ref.size), "max_abs": float(err.max()),
           "max_rel": float((err[far] / np.abs(ref[far])).max())
           if far.any() else 0.0,
           "tol_share": float(share.max())}
    expect(share.max() <= 1.0,
           f"{what}: distances off the reference by up to {err.max()} "
           f"(max rel {out['max_rel']}; tolerance {RTOL} rel + {ATOL} abs)")
    return out


# -- the phases ----------------------------------------------------------------

def serve_static(svc, qs, top_k=True):
    """Phases a and b (a alone without ``top_k``) through one coalescer.
    Returns the coalesced rows, the pruned and scanned top-k, the warmup
    report and per-phase wall seconds (results arrive as host arrays, so
    each wall covers the device work)."""
    import numpy as np
    from repro.serving import DegradedResult
    fallbacks = svc.metrics.counter("wmd_prune_fallback_total")
    co = svc.async_service(window_ms=WINDOW_MS, max_batch=MAX_BATCH,
                           metrics=svc.metrics)
    with co:
        warm = co.warm_registry(ks=(TOP_K,) if top_k else (), queries=qs)
        t0 = time.perf_counter()
        futs = [co.submit(r) for r in qs]
        co.drain()
        rows = [f.result() for f in futs]
        wall_a = time.perf_counter() - t0
        if not top_k:
            rows = np.stack(rows)
            expect(co.stats().failed == 0 and rows.shape == (
                len(qs), svc.ell.num_docs) and np.isfinite(rows).all(),
                "full-distance rows failed or not finite")
            return {"rows": rows, "warm": warm,
                    "wall": {"a_full_distance": wall_a}}
        t0 = time.perf_counter()
        futs = [co.submit_top_k(r, TOP_K) for r in qs]
        co.drain()
        topk = [f.result() for f in futs]
        wall_b = time.perf_counter() - t0
    st = co.stats()
    expect(st.completed == 2 * len(qs) and st.failed == 0,
           f"coalescer completed {st.completed}, failed {st.failed}")
    expect(st.degraded == 0 and not any(
        isinstance(x, DegradedResult) for x in rows + topk),
        "a response was degraded")
    rows = np.stack(rows)
    idx = np.stack([t[0] for t in topk])
    dist = np.stack([t[1] for t in topk])
    expect(rows.shape == (len(qs), svc.ell.num_docs)
           and np.isfinite(rows).all(), "full-distance rows not finite")
    expect(np.isfinite(dist).all(), "top-k distances not finite")
    t0 = time.perf_counter()
    idx_s, dist_s = svc.top_k_scan_batch(qs, TOP_K)
    wall_scan = time.perf_counter() - t0
    expect(np.array_equal(idx, idx_s),
           f"pruned top-k ids differ from the scan:\n{idx}\n{idx_s}")
    expect(np.array_equal(dist, dist_s), "pruned top-k distances differ "
           "from the scan")
    expect(fallbacks.value == 0,
           f"wmd_prune_fallback_total = {fallbacks.value}")
    return {"rows": rows, "idx": idx, "dist": dist, "warm": warm,
            "wall": {"a_full_distance": wall_a, "b_top_k": wall_b,
                     "b_scan": wall_scan}}


def check_static(ref, data, qs, res, sample, label):
    """Served rows at the sample plus every top-k doc, and the top-k
    distances (where phase b ran), against the reference."""
    import numpy as np
    got_rows, got_topk, want_rows, want_topk = [], [], [], []
    top = res.get("idx")
    for i, r in enumerate(qs):
        ids = list(sample) + ([] if top is None else
                              [j for j in top[i] if j not in sample])
        d = ref(r, [ell_doc(data.ell, j) for j in ids])
        got_rows.append(res["rows"][i][ids])
        want_rows.append(d)
        if top is not None:
            pos = {j: p for p, j in enumerate(ids)}
            got_topk.append(res["dist"][i])
            want_topk.append(d[[pos[j] for j in top[i]]])
    a = compare(f"{label} phase a", np.concatenate(got_rows),
                np.concatenate(want_rows))
    if top is None:
        return a, None
    b = compare(f"{label} phase b", np.concatenate(got_topk),
                np.concatenate(want_topk))
    return a, b


def serve_live(mesh, cfg, data, qs):
    """Phase c: a live corpus behind its own coalescer, with writes."""
    import numpy as np
    from repro.core.formats import doc_lists_from_ell
    from repro.data import LiveCorpus
    from repro.serving import WMDService
    n = data.ell.num_docs
    with tempfile.TemporaryDirectory(prefix="wmd-live-") as live_dir:
        live = LiveCorpus(live_dir, cfg.vocab_size, normalize=False)
        live.add_docs(list(range(n)), doc_lists_from_ell(data.ell))
        live.compact()                 # the corpus is the base segment
        svc = WMDService.from_live(mesh, cfg, vecs=data.vecs, live=live)
        fallbacks = svc.metrics.counter("wmd_prune_fallback_total")
        docs = {j: ell_doc(data.ell, j) for j in range(n)}
        co = svc.async_service(window_ms=WINDOW_MS, max_batch=MAX_BATCH,
                               metrics=svc.metrics)
        with co:
            warm = co.warm_registry(ks=(TOP_K,), kinds=("top_k",),
                                    queries=qs)
            before = [f.result() for f in
                      [co.submit_top_k(r, TOP_K) for r in qs]]
            # writes: a doc equal to query 0, a random doc, and a remove of
            # query 1's nearest doc
            q0_ids = np.nonzero(qs[0])[0]
            echo = (q0_ids, qs[0][q0_ids])
            rng = np.random.default_rng(SEED + 1)
            rnd_ids = rng.choice(cfg.vocab_size, 12, replace=False)
            rnd_w = rng.integers(1, 4, 12).astype(np.float64)
            rnd = (rnd_ids, (rnd_w / rnd_w.sum()).astype(np.float32))
            victim = int(before[1][0][0])
            t0 = time.perf_counter()
            writes = [
                (co.submit_add_docs([n], [list(zip(
                    echo[0].tolist(), echo[1].tolist()))]), 1),
                (co.submit_add_docs([n + 1], [list(zip(
                    rnd[0].tolist(), rnd[1].tolist()))]), 1),
                (co.submit_remove_docs([victim]), 1),
            ]
            futs = [co.submit_top_k(r, TOP_K) for r in qs]
            co.drain()
            after = [f.result() for f in futs]
            wall = time.perf_counter() - t0
        for f, want in writes:
            expect(f.result() == want, f"write acked {f.result()}, "
                   f"expected {want}")
        docs[n], docs[n + 1] = echo, rnd
        del docs[victim]
        st = co.stats()
        expect(st.failed == 0 and st.degraded == 0,
               f"live coalescer failed {st.failed}, degraded {st.degraded}")
        idx = np.stack([a[0] for a in after])
        dist = np.stack([a[1] for a in after])
        expect(np.isfinite(dist).all(), "live top-k distances not finite")
        expect(idx[0][0] == n, f"query 0's top-1 is doc {idx[0][0]}, not "
               f"the added copy of it ({n})")
        expect(victim not in idx, f"removed doc {victim} still answered")
        idx_s, dist_s = svc.top_k_scan_batch(qs, TOP_K)
        expect(np.array_equal(idx, idx_s) and np.array_equal(dist, dist_s),
               "live pruned top-k differs from the live scan")
        expect(fallbacks.value == 0,
               f"live wmd_prune_fallback_total = {fallbacks.value}")
        return {"idx": idx, "dist": dist, "docs": docs, "warm": warm,
                "wall": wall, "gen": live.gen}


def check_live(ref, qs, res):
    """The live top-k distances against the reference on the docs now
    behind each answered id."""
    import numpy as np
    want = [ref(r, [res["docs"][j] for j in res["idx"][i]])
            for i, r in enumerate(qs)]
    return compare("phase c", res["dist"].ravel(), np.concatenate(want))


def document_queries(cfg, seed):
    """A corpus of ``cfg.num_docs`` documents and MAX_BATCH further ones of
    the same law as whole-document queries."""
    import dataclasses
    import numpy as np
    from repro.core.formats import EllDocs
    from repro.data import make_corpus
    n = cfg.num_docs
    data = make_corpus(vocab_size=cfg.vocab_size, embed_dim=cfg.embed_dim,
                       num_docs=n + MAX_BATCH, num_queries=0,
                       mean_words=cfg.mean_words, seed=seed)
    qs = []
    for j in range(n, n + MAX_BATCH):
        r = np.zeros(cfg.vocab_size, np.float32)
        ids, w = ell_doc(data.ell, j)
        r[ids] = w
        qs.append(r)
    ell = EllDocs(cols=data.ell.cols[:n], vals=data.ell.vals[:n],
                  num_vocab=cfg.vocab_size)
    return dataclasses.replace(data, ell=ell), qs


def run(mesh, cfg, *, seed=SEED, live=True, cmp_mesh=None, documents=False):
    """Build paper-shaped data for ``cfg`` and run the phases on ``mesh``.

    With ``cmp_mesh`` phases a and b also run on that mesh and both must
    agree (top-k ids equal, distances within the reference tolerance).
    With ``documents`` the queries are whole documents and phase a runs
    alone. Returns a dict of measurements; raises SmokeFailure on any
    check."""
    import numpy as np
    from repro.data import make_corpus, zipf_query_stream
    from repro.serving import WMDService, measure_compiles
    t0 = time.perf_counter()
    if documents:
        data, qs = document_queries(cfg, seed)
    else:
        data = make_corpus(vocab_size=cfg.vocab_size,
                           embed_dim=cfg.embed_dim, num_docs=cfg.num_docs,
                           num_queries=0, query_words=min(cfg.v_r - 1, 19),
                           seed=seed)
        stream = zipf_query_stream(vocab_size=cfg.vocab_size,
                                   query_words=min(cfg.v_r - 1, 13),
                                   seed=seed)
        qs = [next(stream) for _ in range(MAX_BATCH)]
    sample = np.sort(np.random.default_rng(seed).choice(
        data.ell.num_docs, min(SAMPLE_DOCS, data.ell.num_docs),
        replace=False)).tolist()
    ref = Reference(data.vecs, cfg, width=len(sample) + TOP_K)
    out = {"setup_s": time.perf_counter() - t0, "phases": {}}
    meshes = [("main", mesh)] + ([("compare", cmp_mesh)] if cmp_mesh else [])
    results = {}
    # the reference's CPU compiles stay outside the count
    with measure_compiles() as counter:
        for label, m in meshes:
            svc = WMDService(mesh=m, cfg=cfg, vecs=data.vecs, ell=data.ell)
            results[label] = serve_static(svc, qs, top_k=not documents)
            out["plan"] = {"bytes_limit": svc.device_bytes_limit,
                           "budget_bytes": svc.plan_budget_bytes,
                           "docs_chunk": svc._docs_chunk(MAX_BATCH)}
            del svc
        live_res = serve_live(mesh, cfg, data, qs) if live else None
    for label, m in meshes:
        res = results[label]
        a, b = check_static(ref, data, qs, res, sample, label)
        out["phases"][label] = {
            "devices": int(m.devices.size), "wall_s": res["wall"],
            "warmup": res["warm"].summary(), "ref_a": a}
        if b is not None:
            out["phases"][label]["ref_b"] = b
    if cmp_mesh is not None:
        main, other = results["main"], results["compare"]
        expect(np.array_equal(main["idx"], other["idx"]),
               f"top-k ids differ between meshes:\n{main['idx']}\n"
               f"{other['idx']}")
        out["mesh_agreement"] = {
            "rows": compare("mesh rows", main["rows"], other["rows"]),
            "top_k": compare("mesh top-k", main["dist"], other["dist"])}
    if live_res is not None:
        out["phases"]["live"] = {
            "wall_s": live_res["wall"], "warmup": live_res["warm"].summary(),
            "ref_c": check_live(ref, qs, live_res), "gen": live_res["gen"]}
    out["compiles"] = counter.compiles
    out["compile_s"] = counter.compile_s
    out["persistent_hits"] = counter.persistent_hits
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: phases a and b on a (4, 1) mesh over four "
                         "chips, compared with one chip (no live phase)")
    ap.add_argument("--preset", default="paper_5k",
                    choices=("paper_5k", "news20"),
                    help="the deployment (configs/sinkhorn_wmd.py); news20 "
                         "runs phase a alone, on one chip")
    args = ap.parse_args(argv)
    if args.preset == "news20" and args.chips != 1:
        ap.error("--preset news20 runs on one chip")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke.py: src/repro not found next to this script; run "
              "it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the reference runs on the host CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU found (jax.devices()[0] is a "
              f"{dev.platform!r} device); this check runs on a TPU only",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    from repro.configs import sinkhorn_wmd as wmd_cfg
    from repro.launch.mesh import make_mesh
    from repro.serving import enable_compilation_cache
    cache_dir = enable_compilation_cache()         # before the first compile
    log(f"device_kind={dev.device_kind} platform={dev.platform} "
        f"count={len(devices)} chips_used={args.chips}")
    log(f"compilation cache: {cache_dir}")
    cfg = wmd_cfg.config(args.preset)
    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    t0 = time.perf_counter()
    if args.chips == 4:
        four = make_mesh((4, 1), ("data", "model"), devices=devices[:4])
        out = run(four, cfg, live=False, cmp_mesh=one)
    elif args.preset == "news20":
        out = run(one, cfg, live=False, documents=True)
    else:
        out = run(one, cfg)
    wall = time.perf_counter() - t0
    used = devices[:args.chips]
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in used)
    for label, ph in out["phases"].items():
        w = ph["warmup"]
        log(f"{label}: warmup {len(w['shapes'])} shapes, {w['compiles']} "
            f"compiles in {w['compile_s']:.2f} s, {w['persistent_hits']} "
            f"persistent-cache hits, warmup wall {w['wall_s']:.2f} s")
        log(f"{label}: wall_s {json.dumps(ph['wall_s'])}")
        for key in ("ref_a", "ref_b", "ref_c"):
            if key in ph:
                log(f"{label}: reference {key[-1]}: {json.dumps(ph[key])}")
    if "mesh_agreement" in out:
        log(f"4 chips vs 1 chip: {json.dumps(out['mesh_agreement'])}")
    log(f"compiles={out['compiles']} compile_s={out['compile_s']:.2f} "
        f"persistent_hits={out['persistent_hits']} setup_s="
        f"{out['setup_s']:.2f} total_wall_s={wall:.2f}")
    log(f"peak_bytes_in_use={peak} plan={json.dumps(out['plan'])}")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"chip_smoke_{args.preset}_{args.chips}chip.json"),
              "w") as f:
        json.dump({"device_kind": dev.device_kind, "chips": args.chips,
                   "peak_bytes_in_use": peak, "total_wall_s": wall, **out},
                  f, indent=1, default=str)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
