"""AOT warmup registry + offline bulk mode: the registry must enumerate
exactly the shapes the coalescer can dispatch (cross-checked against a
randomized session's shape_log), a warmed service must serve every shape
with ZERO first-hit compiles (asserted via the jax compile-event counter,
not timing), and the offline driver must reproduce the online path's bits
on a golden query file -- top-k regardless of batch composition (union ==
per_query == scan), plain rows for the same bucket compositions.

Everything runs on one tiny corpus; compile counting uses jax's monitoring
events, so the zero-compile assertions are exact, not statistical.
"""
import os
import random

import numpy as np
import pytest

from repro.serving import (ProgramShape, QueryCoalescer, ShapeRegistry,
                           WMDService, load_query_file, measure_compiles,
                           run_offline, save_query_file, warm)
from repro.serving.warmup import synth_queries

NEVER_MS = 10_000.0


@pytest.fixture(scope="module")
def stack():
    """Tiny corpus + a cached, prunable service (top-k capable)."""
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.data import make_corpus
    from repro.launch.mesh import make_mesh

    cfg = WMDConfig(name="t-warmup", vocab_size=192, embed_dim=16,
                    num_docs=32, nnz_max=32, v_r=8, lamb=1.0, max_iter=8)
    data = make_corpus(vocab_size=192, embed_dim=16, num_docs=32,
                       num_queries=12, query_words=6, mean_words=6.0,
                       seed=0)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                     cache_capacity=48, cache_rows_bucket=8,
                     prune_chunk=8)
    return cfg, data, mesh, svc


def _fresh_service(stack):
    """A new service over the same corpus: fresh jit objects, cold caches."""
    cfg, data, mesh, _ = stack
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      cache_capacity=48, cache_rows_bucket=8,
                      prune_chunk=8)


# ------------------------------------------------------------ the registry

def test_program_shape_validation_and_labels():
    assert ProgramShape("plain", 4).label == "plain/q4"
    assert ProgramShape("top_k", 8, k=5).label == "top_k/q8/k5"
    assert ProgramShape("top_k_union", 2, k=3).label == "top_k_union/q2/k3"
    with pytest.raises(ValueError):
        ProgramShape("weird", 4)
    with pytest.raises(ValueError):
        ProgramShape("plain", 3)            # not a pow2 bucket
    with pytest.raises(ValueError):
        ProgramShape("plain", 4, k=5)       # k on a plain shape
    with pytest.raises(ValueError):
        ProgramShape("top_k", 4)            # top_k without k


def test_registry_enumerates_envelope_from_config(stack):
    _, _, _, svc = stack
    reg = ShapeRegistry.from_service(svc, max_batch=8)
    assert reg.labels == ["plain/q1", "plain/q2", "plain/q4", "plain/q8"]

    reg = ShapeRegistry.from_service(svc, max_batch=4, ks=(3, 5))
    # plain buckets first, then every (bucket, k) of the top_k kind
    assert set(reg.labels) == {
        "plain/q1", "plain/q2", "plain/q4",
        "top_k/q1/k3", "top_k/q1/k5", "top_k/q2/k3", "top_k/q2/k5",
        "top_k/q4/k3", "top_k/q4/k5"}
    # union rerank shapes only appear when requested explicitly
    assert not any(s.kind == "top_k_union" for s in reg)
    reg_u = ShapeRegistry.from_service(
        svc, max_batch=2, ks=(3,), kinds=("top_k_union",))
    assert reg_u.labels == ["top_k_union/q1/k3", "top_k_union/q2/k3"]

    # max_batch rounds up to its pow2 bucket, like the coalescer's
    assert ShapeRegistry.from_service(svc, max_batch=5).labels[-1] \
        == "plain/q8"
    with pytest.raises(ValueError):
        ShapeRegistry.from_service(svc, kinds=("top_k",))   # needs ks
    with pytest.raises(ValueError):
        ShapeRegistry.from_service(svc, kinds=("bogus",))


def test_registry_covers_is_bucket_rounded(stack):
    _, _, _, svc = stack
    reg = ShapeRegistry.from_service(svc, max_batch=4, ks=(3,))
    for q in (1, 2, 3, 4):                  # 3 pads into the q4 bucket
        assert reg.covers("plain", q)
        assert reg.covers("top_k", q, k=3)
    assert not reg.covers("plain", 5)       # beyond the envelope
    assert not reg.covers("top_k", 2, k=9)  # k never enumerated
    assert not reg.covers("top_k_union", 2, k=3)


def test_registry_covers_randomized_session_shape_log(stack):
    """THE envelope contract, both halves: over a randomized serving
    session (any arrival pattern, any mix of plain and top-k), every
    batch the coalescer dispatches lands on a shape the registry
    enumerates -- AND, because the registry was warmed first, the whole
    session fires zero compile-or-retrieve events (no request ever pays
    a first-hit compile)."""
    _, data, _, _ = stack
    svc = _fresh_service(stack)
    rng = random.Random(7)
    with QueryCoalescer(svc, window_ms=5.0, max_batch=4) as co:
        reg = ShapeRegistry.from_service(co.svc, max_batch=co.max_batch,
                                         ks=(3,))
        co.warm_registry(ks=(3,))
        with measure_compiles() as cc:
            futs = []
            for _ in range(40):
                q = data.queries[rng.randrange(len(data.queries))]
                if rng.random() < 0.5:
                    futs.append(co.submit(q))
                else:
                    futs.append(co.submit_top_k(q, k=3))
            for f in futs:
                f.result(timeout=60)
        log = list(co.shape_log)
    assert log, "session dispatched nothing"
    sizes = {q for _, q, _ in log}
    assert len(sizes) > 1, "session never varied batch size"
    for kind, q, k in log:
        assert reg.covers(kind, q, k), \
            f"dispatched shape ({kind}, q={q}, k={k}) outside the registry"
    assert cc.events == 0, \
        f"{cc.events} first-hit compiles during a warmed session (want 0)"


# ------------------------------------------------- warmup: zero first-hits

def test_warm_then_zero_compiles_on_every_shape(stack):
    """After one registry pass, re-dispatching EVERY enumerated shape must
    fire zero compile-or-retrieve events -- the programs are live in the
    jit caches, so steady state never meets a cold (or even persisted)
    program. This is the ISSUE's zero-first-hit acceptance gate."""
    cfg, data, _, _ = stack
    svc = _fresh_service(stack)
    reg = ShapeRegistry.from_service(svc, max_batch=4, ks=(3,),
                                     kinds=("plain", "top_k",
                                            "top_k_union"))
    report = warm(svc, reg)
    assert set(report.shapes) == set(reg.labels)
    # a fresh service's programs are cold IN-PROCESS either way: backend
    # compiles, or persisted-cache retrievals when CI restored a cache dir
    assert report.compiles + report.persistent_hits > 0

    qs = synth_queries(cfg, 4, seed=123)    # different payloads, same shapes
    with measure_compiles() as cc:
        for shape in reg:
            batch = qs[:shape.q_bucket]
            if shape.kind == "plain":
                svc.query_batch(batch)
            elif shape.kind == "top_k":
                svc.top_k_batch(batch, shape.k, prune=True)
            else:
                svc.top_k_batch(batch, shape.k, prune=True, rerank="union")
    assert cc.events == 0, \
        f"{cc.events} compile-or-retrieve events after warmup (want 0)"
    assert cc.compiles == 0


def test_registry_warms_q1_as_batched_program(stack):
    """The plain Q = 1 shape warms the batched program: warm-up builds
    exactly one batched solver, and `query` -- the batched engine at
    Q = 1 -- then dispatches it without a compile."""
    cfg, data, mesh, _ = stack
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell)
    reg = ShapeRegistry.from_service(svc, max_batch=1)
    assert reg.labels == ["plain/q1"]
    warm(svc, reg)
    assert list(svc._batch_fns) == [(None, 0.0, cfg.lamb)]
    assert not svc._stripe_fns
    with measure_compiles() as cc:
        d = svc.query(data.queries[0])
    assert cc.events == 0, f"{cc.events} compile-or-retrieve events"
    assert d.shape == (cfg.num_docs,)
    assert svc.last_batch_stats["route"] == "legacy_fused"


def test_measure_compiles_nests_with_equal_tallies():
    """An inner span whose tallies equal the outer's (both still zero)
    detaches its own counter on exit, so the outer one keeps counting."""
    import jax
    with measure_compiles() as outer:
        with measure_compiles() as inner:
            pass
        jax.jit(lambda x: x * 3.25 + 0.5)(np.float32(1.0))
    assert inner.events == 0
    assert outer.events >= 1


def test_warmup_report_accounting(stack):
    svc = _fresh_service(stack)
    reg = ShapeRegistry.from_service(svc, max_batch=2, ks=(3,))
    report = warm(svc, reg)
    assert report.wall_s > 0
    assert report.compiles == sum(s.compiles for s in
                                  report.shapes.values())
    assert set(report.compile_s_by_label()) == set(reg.labels)
    s = report.summary()
    assert s["shapes"] == reg.labels
    assert set(s["per_shape"]) == set(reg.labels)
    # every program was either backend-compiled or cache-retrieved --
    # a fresh service meets each shape cold in-process (CI may restore a
    # persisted cache dir, which flips compiles into retrievals)
    assert report.compiles + report.persistent_hits > 0
    assert report.retrieval_s >= 0


def test_synth_queries_are_admissible_histograms(stack):
    cfg, _, _, _ = stack
    qs = synth_queries(cfg, 5, seed=3)
    assert len(qs) == 5
    for q in qs:
        assert q.shape == (cfg.vocab_size,) and q.dtype == np.float32
        np.testing.assert_allclose(q.sum(), 1.0, rtol=1e-5)
        assert (q > 0).sum() <= cfg.v_r - 1     # fits the v_r bucket
    np.testing.assert_array_equal(qs[0], synth_queries(cfg, 1, seed=3)[0])


# ------------------------------------------- coalescer wiring + shims

def test_coalescer_warm_registry_populates_stats(stack):
    svc = _fresh_service(stack)
    with QueryCoalescer(svc, window_ms=NEVER_MS, max_batch=4) as co:
        rep = co.warm_registry(ks=(3,))
        st = co.stats()
    assert st.warmed_shapes == len(rep.shapes) == 3 + 3   # plain + top_k
    assert set(st.warmup_compile_s) == set(rep.shapes)
    assert all(v >= 0 for v in st.warmup_compile_s.values())


def test_coalescer_record_warmup_merges_passes(stack):
    svc = _fresh_service(stack)
    with QueryCoalescer(svc, window_ms=NEVER_MS, max_batch=2) as co:
        co.warm_registry()                   # plain only
        co.warm_registry(ks=(3,), kinds=("top_k",))
        st = co.stats()
    assert set(st.warmup_compile_s) == {
        "plain/q1", "plain/q2", "top_k/q1/k3", "top_k/q2/k3"}
    assert st.warmed_shapes == 4


def test_deprecated_warm_shims_forward_to_registry(stack):
    """`warm` / `warm_top_k` keep their signatures but now run the
    registry pass -- and a short query list no longer truncates the
    bucket ladder (the old ad-hoc walkers stopped at len(qs))."""
    _, data, _, _ = stack
    svc = _fresh_service(stack)
    with QueryCoalescer(svc, window_ms=NEVER_MS, max_batch=4) as co:
        co.warm(list(data.queries[:2]))      # 2 queries, 3 buckets
        st = co.stats()
        assert set(st.warmup_compile_s) == {"plain/q1", "plain/q2",
                                            "plain/q4"}
        co.warm_top_k(list(data.queries[:1]), 3)
        st = co.stats()
    assert {"top_k/q1/k3", "top_k/q2/k3", "top_k/q4/k3"} <= \
        set(st.warmup_compile_s)
    # empty payload stays a no-op (the historical contract)
    svc2 = _fresh_service(stack)
    with QueryCoalescer(svc2, window_ms=NEVER_MS, max_batch=4) as co2:
        co2.warm([])
        assert co2.stats().warmed_shapes == 0


# ------------------------------------------------------- offline bulk mode

def test_query_file_roundtrip(tmp_path, stack):
    _, data, _, _ = stack
    qs = list(data.queries[:5])
    for name in ("golden.npz", "golden.npy"):
        path = save_query_file(tmp_path / name, qs)
        back = load_query_file(path)
        assert len(back) == 5
        for a, b in zip(qs, back):
            np.testing.assert_array_equal(np.asarray(a, np.float32), b)
    with pytest.raises(ValueError):
        np.savez(tmp_path / "bad.npz", a=np.zeros(3), b=np.zeros(3))
        load_query_file(tmp_path / "bad.npz")
    with pytest.raises(ValueError):
        np.save(tmp_path / "bad1d.npy", np.zeros(4, np.float32))
        load_query_file(tmp_path / "bad1d.npy")


def test_offline_plain_bitwise_same_compositions(stack):
    """Plain offline rows == a direct query_batch of the same full-bucket
    compositions, bitwise (the coalescer's composition-preserving
    contract applied to the offline scheduler's in-order cuts)."""
    _, data, _, svc = stack
    qs = list(data.queries[:10])             # 4 + 4 + 2 under max_batch=4
    off = run_offline(svc, qs, max_batch=4)
    assert off.mode == "plain" and off.n == 10 and off.batches == 3
    assert off.dists.shape == (10, svc.ell.num_docs)
    for lo in range(0, len(qs), 4):
        direct = np.asarray(svc.query_batch(qs[lo:lo + 4]))
        np.testing.assert_array_equal(off.dists[lo:lo + len(direct)],
                                      direct)


def test_offline_topk_union_equals_per_query_equals_scan(stack):
    """The rerank tier's bit-stability across Q: union rerank (one
    (Q, chunk) program per block), the online per-query rerank, and the
    exhaustive scan all agree bitwise on the same queries -- so offline
    top-k == online top-k REGARDLESS of batch composition."""
    _, data, _, svc = stack
    qs = list(data.queries[:6])
    off_u = run_offline(svc, qs, k=3, max_batch=4, rerank="union")
    off_p = run_offline(svc, qs, k=3, max_batch=4, rerank="per_query")
    np.testing.assert_array_equal(off_u.topk_idx, off_p.topk_idx)
    np.testing.assert_array_equal(off_u.topk_dist, off_p.topk_dist)
    # vs the online path at a DIFFERENT composition (singletons)
    for i, q in enumerate(qs):
        idx_1, d_1 = svc.top_k_batch([q], 3, prune=True)
        np.testing.assert_array_equal(off_u.topk_idx[i], idx_1[0])
        np.testing.assert_array_equal(off_u.topk_dist[i], d_1[0])
    # vs the exhaustive scan oracle
    idx_s, d_s = svc.top_k_scan_batch(qs, 3)
    np.testing.assert_array_equal(off_u.topk_idx, idx_s)
    np.testing.assert_array_equal(off_u.topk_dist, d_s)
    assert off_u.rerank_programs is not None
    assert off_u.rerank_programs <= off_p.rerank_programs


def test_offline_golden_query_file_end_to_end(tmp_path, stack):
    """The serve.py --offline path in miniature: golden query file on
    disk -> load -> bulk-score -> persisted outputs match the online
    engine bitwise."""
    _, data, _, svc = stack
    path = save_query_file(tmp_path / "workload.npz",
                           list(data.queries[:7]))
    qs = load_query_file(path)
    off = run_offline(svc, qs, k=3, max_batch=4)
    out = off.save(tmp_path / "scored.npz")
    with np.load(out) as z:
        np.testing.assert_array_equal(z["topk_idx"], off.topk_idx)
        np.testing.assert_array_equal(z["topk_dist"], off.topk_dist)
    idx_s, d_s = svc.top_k_scan_batch(qs, 3)
    np.testing.assert_array_equal(off.topk_idx, idx_s)
    np.testing.assert_array_equal(off.topk_dist, d_s)
    s = off.summary()
    assert s["mode"] == "top_k" and s["n"] == 7 and s["rerank"] == "union"
    assert s["throughput_qps"] > 0
    assert 0 <= s["solves_avoided"] <= 1


def test_run_offline_rejects_unknown_rerank(stack):
    _, data, _, svc = stack
    with pytest.raises(ValueError):
        run_offline(svc, list(data.queries[:2]), k=3, rerank="sideways")


# -------------------------------------------- persisted compilation cache

def test_persistent_cache_roundtrip_subprocess(tmp_path):
    """Cold process compiles and persists; a second identical process
    re-lowers but retrieves every program (0 backend compiles). Run in
    subprocesses because jax's cache config is process-global state."""
    import subprocess
    import sys
    script = r"""
import sys
import numpy as np
from repro.configs.sinkhorn_wmd import WMDConfig
from repro.data import make_corpus
from repro.launch.mesh import make_mesh
from repro.serving import (ShapeRegistry, WMDService,
                           enable_compilation_cache, warm)
from repro.serving.warmup import flush_compilation_cache

enable_compilation_cache(sys.argv[1])
cfg = WMDConfig(name="t-cache", vocab_size=96, embed_dim=8, num_docs=16,
                nnz_max=24, v_r=8, lamb=1.0, max_iter=4)
data = make_corpus(vocab_size=96, embed_dim=8, num_docs=16,
                   num_queries=2, query_words=5, mean_words=5.0, seed=0)
svc = WMDService(mesh=make_mesh((1, 1), ("data", "model")), cfg=cfg,
                 vecs=data.vecs, ell=data.ell)
rep = warm(svc, ShapeRegistry.from_service(svc, max_batch=2))
info = flush_compilation_cache()
print(f"RESULT compiles={rep.compiles} hits={rep.persistent_hits} "
      f"entries={info['entries']}")
"""
    env = dict(os.environ, PYTHONPATH="src")
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", script,
                            str(tmp_path / "jaxcache")],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__))))
        assert p.returncode == 0, p.stderr
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith("RESULT")][0]
        outs.append(dict(kv.split("=") for kv in line.split()[1:]))
    cold, warm_run = outs
    assert int(cold["compiles"]) > 0
    assert int(cold["entries"]) > 0          # entries persisted on disk
    assert int(warm_run["compiles"]) == 0, \
        f"second process recompiled: {warm_run}"
    assert int(warm_run["hits"]) == int(cold["compiles"])


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compilation_cache_dir_resolution(env_set, tmp_path):
    """With no argument the cache lands at $JAX_COMPILATION_CACHE_DIR when
    set (and nothing overrides jax's own reading of it), else at the fixed
    in-checkout DEFAULT_CACHE_DIR, which .gitignore covers."""
    import subprocess
    import sys
    from repro.serving.warmup import DEFAULT_CACHE_DIR
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(DEFAULT_CACHE_DIR) == root
    with open(os.path.join(root, ".gitignore")) as f:
        assert os.path.basename(DEFAULT_CACHE_DIR) + "/" in f.read().split()
    script = ("import jax\n"
              "from repro.serving import enable_compilation_cache\n"
              "got = enable_compilation_cache()\n"
              "print('RESULT', got, jax.config.jax_compilation_cache_dir)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = DEFAULT_CACHE_DIR
    if env_set:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=120, env=env, cwd=tmp_path)
    assert p.returncode == 0, p.stderr
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT")]
    assert line[0].split()[1:] == [want, want]
    assert os.path.isdir(want)
