"""Multi-query batching throughput: batched (Q, v_r, N) engine vs the
sequential per-query dispatch loop, with a ``--docs-chunk`` cache-blocking
sweep and a ``--zipf`` query-stream mode exercising the cross-query K cache.

    PYTHONPATH=src python benchmarks/bench_query_batch.py [--tiny] \
        [--docs-chunk 0 64 128 256] [--out BENCH_query_batch.json]
    PYTHONPATH=src python benchmarks/bench_query_batch.py --zipf \
        [--cache-capacity 2048] [--zipf-s 1.3] [--out BENCH_zipf_cache.json]

Every batched point records the *phase split* -- ``precompute_s`` (dedup +
cache lookup + row compute + stripe assembly) vs ``solve_s`` (the Sinkhorn
loop program) -- plus that batch's cache ``hit_rate``, so BENCH trajectories
can attribute wins to the right phase. ``--zipf`` replays a seeded
Zipf-skewed query stream (`repro.data.zipf_query_stream`) through one
service twice per batch -- cache ON then the transient cache-OFF baseline --
asserts the two are bitwise identical (the cache's exactness contract), and
reports the steady-state hit rate and precompute-phase speedup
(`precompute_speedup_steady`; the cache converts the phase from
O(Q*v_r*V*w) to O(misses*V*w), so at hit rate h it approaches 1/(1-h) minus
assembly overhead).

For each Q the sequential baseline replays `WMDService.query` Q times
(the same engine at Q = 1: re-gathering K, re-running precompute, and
paying one program dispatch per query); the batched path runs ONE device
program. ``--docs-chunk`` sweeps
`WMDService(docs_chunk=...)` (0 = unchunked): the chunk loop sits OUTSIDE
the Sinkhorn loop (docs are independent OT problems), so each chunk's
(Q, v_r, docs_chunk) iterate stays cache-resident across all iterations --
see core.sparse_sinkhorn "Batched engine". All variants are
timed INTERLEAVED (round-robin, median of rounds) so slow-box drift hits
every variant equally. Emits ``name,us_per_call,derived`` CSV rows (the
harness idiom) and writes a JSON artifact for the perf trajectory
(`BENCH_*.json`, uploaded by the nightly CI smoke job) recording the full
chunk sweep and the chosen chunk per Q.

Measured regimes on the 2-core CPU CI box (vocab 2k, nnz ~96, v_r 16):
  * low-latency (N = 128): batched >= 2.5x sequential qps -- per-query
    dispatch + precompute rival solve compute and batching amortizes both.
  * bulk (N >= 1024, Q = 16): the unchunked batched path loses to sequential
    (~0.6x, the (Q, v_r, N)-working-set cache blow); doc-chunking wins it
    back (1.5-1.9x over unchunked), recovering parity-to-1.4x vs sequential.
    The remaining gap to bigger wins is structural: at bulk the per-query
    program overhead batching amortizes is only ~10-15% of a solve, and the
    iteration math itself runs at the same roofline either way -- the bulk
    win of the batched engine is collective amortization on real meshes
    (one psum per iteration regardless of Q), which a single-host bench
    cannot show.
  * at Q = 1 the batched and sequential paths dispatch the same program.

Self-contained on purpose (no benchmarks.common import): CI invokes it as a
script with only the installed `repro` package on the path.
"""
from __future__ import annotations

import argparse
import json
import time


def bench_interleaved(calls: dict, *, warmup: int = 1, rounds: int = 5):
    """Median wall seconds per call, measured round-robin across variants."""
    for fn in calls.values():
        for _ in range(warmup):
            fn()
    times = {name: [] for name in calls}
    for _ in range(rounds):
        for name, fn in calls.items():
            t0 = time.perf_counter()
            fn()
            times[name].append(time.perf_counter() - t0)
    return {name: sorted(ts)[len(ts) // 2] for name, ts in times.items()}


def run(*, vocab: int = 1024, docs: int = 128, qs=(1, 4, 16, 64),
        mean_words: float = 8.0, query_words: int = 13, v_r: int = 16,
        docs_chunks=(0,), rounds: int = 5,
        cache_capacity: int = 0, out: str | None = None) -> dict:
    import numpy as np
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.data import make_corpus
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService

    cfg = WMDConfig(name="bench-qbatch", vocab_size=vocab, embed_dim=64,
                    num_docs=docs, nnz_max=64, v_r=v_r, lamb=1.0, max_iter=15)
    data = make_corpus(vocab_size=vocab, embed_dim=cfg.embed_dim,
                       num_docs=docs, num_queries=max(qs),
                       query_words=query_words, mean_words=mean_words,
                       seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    docs_chunks = tuple(dict.fromkeys(docs_chunks))  # dedup, keep order
    if 0 not in docs_chunks:
        docs_chunks = (0,) + docs_chunks
    # ONE service (one device-sharded corpus); the chunk sweep rides the
    # per-docs_chunk batch-fn cache via query_batch(docs_chunk=...)
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                     cache_capacity=cache_capacity)

    results = {"vocab": vocab, "docs": docs, "v_r": cfg.v_r,
               "nnz_max": data.ell.nnz_max, "max_iter": cfg.max_iter,
               "docs_chunks": list(docs_chunks),
               "cache_capacity": cache_capacity, "points": [],
               "note": ("chunk_times_s sweeps WMDService(docs_chunk=...); "
                        "chosen_chunk minimizes batched time. At bulk N the "
                        "chunked path wins ~1.5-1.8x over unchunked "
                        "(cache-resident per-chunk solve) and reaches "
                        "parity-to-1.4x vs the sequential per-query loop; "
                        "bigger bulk wins need a real mesh (see module "
                        "docstring). Low-latency N (~128) shows >= 2.5x "
                        "vs sequential. precompute_s/solve_s phase-split "
                        "the stripes engine (measured via use_cache=True; "
                        "cache-off defaults run the fused single-program "
                        "engine, which has no separable phases); hit_rate "
                        "is 0 unless --cache-capacity > 0 -- see the "
                        "--zipf artifact for the cache's steady state.")}
    for q in qs:
        queries = data.queries[:q]
        # correctness gate before timing: batched must match the oracle
        # (for every swept chunk size)
        seq_ref = svc.query_batch_sequential(queries)
        err = max(float(np.abs(svc.query_batch(queries, docs_chunk=dc)
                               - seq_ref).max())
                  for dc in docs_chunks)
        calls = {"seq": lambda: svc.query_batch_sequential(queries)}
        for dc in docs_chunks:
            calls[f"dc{dc}"] = (lambda d: (
                lambda: svc.query_batch(queries, docs_chunk=d)))(dc)
        med = bench_interleaved(calls, rounds=rounds)
        t_seq = med["seq"]
        chunk_times = {str(dc): med[f"dc{dc}"] for dc in docs_chunks}
        chosen = min(docs_chunks, key=lambda dc: med[f"dc{dc}"])
        t_bat = med[f"dc{chosen}"]
        t_un = med["dc0"]
        qps_seq, qps_bat = q / t_seq, q / t_bat
        # phase split at the chosen chunk: precompute = dedup + cache +
        # row compute + stripe assembly, solve = the Sinkhorn program (see
        # WMDService.last_batch_stats). use_cache=True routes through the
        # stripes engine even when the service's cache is disabled -- the
        # split is only measurable there (the cache-off default runs the
        # fused single-program engine). First call warms the stripes jits
        # (they are cold when the timed calls ran legacy); the second is
        # the steady-state measurement the artifact records.
        svc.query_batch(queries, docs_chunk=chosen, use_cache=True)
        svc.query_batch(queries, docs_chunk=chosen, use_cache=True)
        phases = svc.last_batch_stats
        point = {
            "Q": q, "t_seq_s": t_seq, "t_batched_s": t_bat,
            "t_unchunked_s": t_un, "chunk_times_s": chunk_times,
            "chosen_chunk": chosen,
            "qps_seq": qps_seq, "qps_batched": qps_bat,
            "speedup": t_seq / t_bat,
            "speedup_chunked_vs_unchunked": t_un / t_bat,
            "max_abs_err": err,
            "precompute_s": phases["precompute_s"],
            "solve_s": phases["solve_s"],
            "hit_rate": phases["hit_rate"],
        }
        results["points"].append(point)
        print(f"qbatch/Q{q},{t_bat / q * 1e6:.1f},"
              f"qps_batched={qps_bat:.1f}:qps_seq={qps_seq:.1f}:"
              f"speedup={point['speedup']:.2f}x:"
              f"chunk={chosen}:chunk_vs_unchunked="
              f"{point['speedup_chunked_vs_unchunked']:.2f}x")
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {out}")
    return results


def run_zipf(*, vocab: int = 8192, docs: int = 128, q: int = 16,
             batches: int = 24, warm: int = 8, query_words: int = 13,
             v_r: int = 16, s: float = 1.3, cache_capacity: int = 2048,
             embed_dim: int = 256, rows_bucket: int = 16,
             out: str | None = None) -> dict:
    """Zipf query-stream mode: steady-state cache hit rate + phase split.

    Replays ``batches`` batches of ``q`` queries drawn from one seeded
    Zipf(s) stream through a single cached service. Per batch both paths
    run on the same queries -- the cache-ON call (serving AND warming the
    store) and the transient cache-OFF baseline -- in alternating order
    (slow-box drift hits both sides equally); ON and OFF results must be
    bitwise equal (asserted -- the exactness contract of core.kcache).
    Batches after ``warm`` form the steady state; the headline speedup is
    the ratio of the lower-quartile per-batch precompute_s of the two sides
    (the same estimator on both; on a shared noisy box low quantiles
    estimate the true phase cost, while means/medians of single shots
    absorb multi-x scheduler spikes -- the artifact records the medians
    too). Defaults model the
    serving regime the cache targets: a head-heavy stream (s = 1.3) against
    a wide-ish vocab/embedding (V = 8192, w = 256 -- directionally the
    paper's 100k x 300) where the row compute, not the stripe assembly,
    dominates the phase.
    """
    import numpy as np
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.data import make_corpus, zipf_query_stream
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService

    cfg = WMDConfig(name="bench-zipf", vocab_size=vocab, embed_dim=embed_dim,
                    num_docs=docs, nnz_max=64, v_r=v_r, lamb=1.0, max_iter=15)
    data = make_corpus(vocab_size=vocab, embed_dim=cfg.embed_dim,
                       num_docs=docs, num_queries=1,
                       query_words=query_words, seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                     cache_capacity=cache_capacity,
                     cache_rows_bucket=rows_bucket)
    stream = zipf_query_stream(vocab_size=vocab, query_words=query_words,
                               s=s, seed=1)
    results = {"mode": "zipf", "vocab": vocab, "docs": docs, "Q": q,
               "v_r": v_r, "query_words": query_words, "zipf_s": s,
               "cache_capacity": cache_capacity,
               "warm_batches": warm, "points": [],
               "note": ("per batch: cache-ON call then transient cache-OFF "
                        "baseline on the same queries, asserted bitwise "
                        "equal. Steady state = batches after warm; "
                        "precompute speedup ~ 1/(1 - hit_rate) minus "
                        "assembly overhead.")}
    for i in range(batches):
        batch = [next(stream) for _ in range(q)]
        if i % 2 == 0:
            on = svc.query_batch(batch)
            st_on = dict(svc.last_batch_stats)
            off = svc.query_batch(batch, use_cache=False)
            st_off = dict(svc.last_batch_stats)
        else:
            off = svc.query_batch(batch, use_cache=False)
            st_off = dict(svc.last_batch_stats)
            on = svc.query_batch(batch)
            st_on = dict(svc.last_batch_stats)
        assert np.array_equal(on, off), "cache on/off must be bitwise equal"
        point = {"batch": i, "unique": st_on["unique"],
                 "hit_rate": st_on["hit_rate"],
                 "precompute_s": st_on["precompute_s"],
                 "precompute_s_nocache": st_off["precompute_s"],
                 "solve_s": st_on["solve_s"],
                 "precompute_speedup":
                     st_off["precompute_s"] / st_on["precompute_s"]}
        results["points"].append(point)
        print(f"zipf/b{i},{st_on['precompute_s'] * 1e6:.1f},"
              f"hit_rate={point['hit_rate']:.2f}:"
              f"pre_speedup={point['precompute_speedup']:.2f}x:"
              f"solve={st_on['solve_s'] * 1e3:.1f}ms")
    steady = results["points"][warm:] or results["points"]  # warm >= batches
    med = lambda xs: sorted(xs)[len(xs) // 2]   # noqa: E731
    q25 = lambda xs: sorted(xs)[len(xs) // 4]   # noqa: E731
    results["hit_rate_steady"] = med([p["hit_rate"] for p in steady])
    pre_on = q25([p["precompute_s"] for p in steady])
    pre_off = q25([p["precompute_s_nocache"] for p in steady])
    results["precompute_s_steady"] = pre_on
    results["precompute_s_nocache_steady"] = pre_off
    results["precompute_s_steady_median"] = med(
        [p["precompute_s"] for p in steady])
    results["precompute_s_nocache_steady_median"] = med(
        [p["precompute_s_nocache"] for p in steady])
    results["precompute_speedup_steady"] = pre_off / pre_on
    results["cache_stats"] = {
        "hit_rate": svc.cache_stats.hit_rate,
        "evictions": svc.cache_stats.evictions,
        "resident": svc.cache_resident}
    print(f"zipf/steady,{pre_on * 1e6:.1f},"
          f"hit_rate={results['hit_rate_steady']:.2f}:"
          f"pre_speedup={results['precompute_speedup_steady']:.2f}x")
    if out:
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"# wrote {out}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=None,
                    help="corpus vocab (default 1024; 8192 in --zipf mode)")
    ap.add_argument("--docs", type=int, default=128)
    ap.add_argument("--mean-words", type=float, default=8.0)
    ap.add_argument("--query-words", type=int, default=13)
    ap.add_argument("--v-r", type=int, default=16)
    ap.add_argument("--qs", type=int, nargs="+", default=[1, 4, 16, 64])
    ap.add_argument("--docs-chunk", type=int, nargs="+", default=[0],
                    help="docs_chunk sweep; 0 = unchunked (always included)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shape (small corpus, Q <= 8)")
    ap.add_argument("--cache-capacity", type=int, default=None,
                    help="cross-query K-cache rows (default: 0 = off; "
                         "2048 in --zipf mode)")
    ap.add_argument("--zipf", action="store_true",
                    help="Zipf query-stream mode: steady-state cache hit "
                         "rate + precompute-phase speedup (cache on vs off)")
    ap.add_argument("--zipf-s", type=float, default=1.3,
                    help="Zipf exponent of the query stream (1.3 = the "
                         "head-heavy serving regime; the corpus generator "
                         "itself defaults to the paper-ish 1.07)")
    ap.add_argument("--zipf-batches", type=int, default=24)
    ap.add_argument("--zipf-warm", type=int, default=8,
                    help="batches excluded from the steady-state aggregate")
    ap.add_argument("--zipf-q", type=int, default=16,
                    help="queries per batch in --zipf mode")
    ap.add_argument("--out", default=None,
                    help="artifact path (default BENCH_query_batch.json, "
                         "BENCH_zipf_cache.json in --zipf mode)")
    args = ap.parse_args()
    out = args.out or ("BENCH_zipf_cache.json" if args.zipf
                       else "BENCH_query_batch.json")
    if args.zipf:
        run_zipf(vocab=args.vocab or 8192,
                 docs=args.docs, q=args.zipf_q, batches=args.zipf_batches,
                 warm=args.zipf_warm, query_words=args.query_words,
                 v_r=args.v_r, s=args.zipf_s,
                 cache_capacity=(2048 if args.cache_capacity is None
                                 else args.cache_capacity),
                 out=out)
    elif args.tiny:
        run(vocab=512, docs=64, qs=(1, 4, 8), docs_chunks=(0, 16, 32),
            rounds=3, cache_capacity=args.cache_capacity or 0, out=out)
    else:
        run(vocab=args.vocab or 1024, docs=args.docs, qs=tuple(args.qs),
            mean_words=args.mean_words, query_words=args.query_words,
            v_r=args.v_r, docs_chunks=tuple(args.docs_chunk),
            rounds=args.rounds,
            cache_capacity=args.cache_capacity or 0, out=out)


if __name__ == "__main__":
    main()
