"""Program spans and scopes: the solve programs name their device phases,
``query_batch`` names its host stages on the profiler's clock and hands
them back in ``last_batch_stats``, the coalescer's phase children are
those spans, and the slot counters match hand counts.

Contracts pinned here:
  * every op the program's code emits into the compiled batched solve
    (fused and stripes) carries a ``wmd.`` scope, each phase is present,
    and the golden distances stay bitwise equal;
  * a CPU profiler trace of ``query_batch`` holds ``wmd.prepare``,
    ``wmd.dispatch``, ``wmd.fetch`` and ``wmd.check`` inside
    ``wmd.query_batch``;
  * ``precompute_s`` / ``solve_s`` are read off the same spans, on every
    route;
  * ``wmd_query_slots_total`` / ``wmd_ell_slots_total`` count real and pad
    slots per solve dispatch, and warm-up is not counted.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_golden as tg
from repro.configs.sinkhorn_wmd import WMDConfig
from repro.launch.mesh import make_mesh
from repro.obs import Tracer, span
from repro.serving import WMDService
from repro.serving.coalescer import QueryCoalescer

PHASES = ("wmd.precompute", "wmd.iterate", "wmd.final")
STAGES = ("wmd.prepare", "wmd.dispatch", "wmd.fetch", "wmd.check")


def _service(**kw):
    vecs, ell, rs = tg._corpus()
    cfg = WMDConfig(name="spans", vocab_size=vecs.shape[0], embed_dim=8,
                    num_docs=ell.num_docs, nnz_max=ell.nnz_max,
                    v_r=tg.V_R_BUCKET, lamb=tg.LAMB, max_iter=tg.MAX_ITER)
    mesh = make_mesh((1, 1), ("data", "model"))
    return WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell, **kw), rs


def _op_scopes(compiled_text: str) -> list[tuple[str, list[str]]]:
    """(op_name, its wmd.* scopes) of every instruction whose op_name the
    program's code emitted (``jit(...)/...``); parameters, constants and
    the compiler's own plumbing carry none or a bare argument name."""
    out = []
    for m in re.finditer(r'op_name="([^"]*)"', compiled_text):
        name = m.group(1)
        if name.startswith("jit("):
            out.append((name, re.findall(r"wmd\.\w+", name)))
    return out


def test_span_records_nested_and_raised():
    log = []
    with span("wmd.outer", log, q=2):
        with span("wmd.inner", log):
            pass
    with pytest.raises(ValueError):
        with span("wmd.raised", log):
            raise ValueError
    assert [n for n, _, _ in log] == ["wmd.inner", "wmd.outer",
                                      "wmd.raised"]
    (_, a0, a1), (_, b0, b1) = log[0], log[1]
    assert b0 <= a0 <= a1 <= b1


@pytest.mark.parametrize("route", ["legacy", "stripes"])
def test_solve_programs_carry_wmd_scopes(route):
    """Every op of the compiled batched solve names its phase; the scopes
    change metadata only, so the golden distances are unchanged."""
    golden = np.load(tg.GOLDEN)
    if route == "legacy":
        svc, rs = _service()
        np.testing.assert_array_equal(svc.query_batch(rs),
                                      golden["service_legacy"])
        sel_b, r_b, mask_b = svc._padded_query_batch(rs)
        fn = svc._batch_fn(svc.docs_chunk)
        args = (jnp.asarray(svc.vecs[sel_b]), jnp.asarray(r_b),
                jnp.asarray(mask_b), svc._vecs_d, svc._cols_d, svc._vals_d)
    else:
        svc, rs = _service(cache_capacity=64, prune_chunk=8,
                           bound_docs_chunk=None)
        np.testing.assert_array_equal(svc.query_batch(rs),
                                      golden["service_stripes"])
        sel_b, r_b, mask_b = svc._padded_query_batch(rs)
        k_s, km_s, _ = svc._kcache.stripes_for_batch(sel_b, mask_b)
        fn = svc._stripe_fn(svc.docs_chunk)
        args = (k_s, km_s, jnp.asarray(r_b), svc._cols_d, svc._vals_d)
    ops = _op_scopes(fn.lower(*args).compile().as_text())
    assert ops
    unscoped = [name for name, scopes in ops if not scopes]
    assert unscoped == []
    # K is gathered once, in the precompute, before the loop (in the
    # stripes program too, whose stripes come precomputed); the final pass
    # gathers K.*M
    outer = {scopes[0] for _, scopes in ops}
    assert outer == set(PHASES)
    gathers = {scopes[0] for _, scopes in ops if "wmd.gather" in scopes}
    assert gathers == {"wmd.precompute", "wmd.final"}


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name.startswith("wmd.")]


def test_query_batch_stages_in_a_profiler_trace(tmp_path):
    """The stages are profiler annotations nested in ``wmd.query_batch``,
    on the legacy fused route and on the stripes route (which adds
    ``wmd.cache_rows``)."""
    legacy, rs = _service()
    stripes, _ = _service(cache_capacity=64)
    legacy.query_batch(rs)                  # compile outside the trace
    stripes.query_batch(rs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        legacy.query_batch(rs)
        stripes.query_batch(rs)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    roots = sorted((s, e) for n, s, e in spans if n == "wmd.query_batch")
    assert len(roots) == 2
    for (lo, hi), stages in zip(roots, (STAGES,
                                        STAGES + ("wmd.cache_rows",))):
        inside = {n for n, s, e in spans
                  if lo <= s and e <= hi and n != "wmd.query_batch"}
        assert inside == set(stages)


@pytest.mark.parametrize("route", ["q1_legacy_fused", "legacy_fused",
                                   "stripes", "live"])
def test_last_batch_stats_read_off_the_spans(route, tmp_path):
    kw = {"cache_capacity": 64} if route in ("stripes", "live") else {}
    svc, rs = _service(**kw)
    if route == "live":
        from repro.core import formats as fmt
        from repro.data.live_corpus import LiveCorpus
        lc = LiveCorpus(str(tmp_path), svc.vecs.shape[0], normalize=False)
        lc.add_docs(range(svc.ell.num_docs), fmt.doc_lists_from_ell(svc.ell))
        svc = WMDService.from_live(svc.mesh, svc.cfg, svc.vecs, lc, **kw)
    batch = rs[:1] if route == "q1_legacy_fused" else rs
    svc.query_batch(batch)
    st = svc.last_batch_stats
    names = [n for n, _, _ in st["spans"]]
    assert names[-1] == "wmd.query_batch" and set(STAGES) <= set(names)

    def seconds(*which):
        return sum(t1 - t0 for n, t0, t1 in st["spans"] if n in which)
    assert st["solve_s"] == seconds("wmd.dispatch", "wmd.fetch") > 0
    if route in ("stripes", "live"):
        assert st["precompute_s"] == seconds("wmd.cache_rows") > 0
    else:
        assert st["route"] == "legacy_fused" and "precompute_s" not in st
    root = [(t0, t1) for n, t0, t1 in st["spans"]
            if n == "wmd.query_batch"][0]
    assert all(root[0] <= t0 <= t1 <= root[1] for _, t0, t1 in st["spans"])


def test_slot_counters_match_hand_counts():
    """Each solve dispatch adds its Q_pow2 x v_r query slots and every ELL
    slot; warm-up adds nothing, and the span histogram counts calls."""
    from repro.serving.warmup import ProgramShape, ShapeRegistry, warm
    svc, rs = _service()
    warm(svc, ShapeRegistry([ProgramShape("plain", 4)]))
    snap = svc.metrics.snapshot()
    assert snap.get("wmd_query_slots_total{kind=real}", 0) == 0
    assert "wmd_span_seconds{span=wmd.query_batch}" not in snap
    svc.query_batch(rs)                     # Q = 3 -> one Q_pow2 = 4 bucket
    svc.query_batch(rs[:1])                 # Q = 1: one dispatch
    words = [int(np.count_nonzero(r)) for r in rs]
    v_r = tg.V_R_BUCKET
    nnz = int(np.count_nonzero(svc.ell.vals))
    slots = svc.ell.vals.size
    snap = svc.metrics.snapshot()
    assert snap["wmd_query_slots_total{kind=real}"] == sum(words) + words[0]
    assert snap["wmd_query_slots_total{kind=pad}"] == \
        4 * v_r - sum(words) + v_r - words[0]
    assert snap["wmd_ell_slots_total{kind=real}"] == 2 * nnz
    assert snap["wmd_ell_slots_total{kind=pad}"] == 2 * (slots - nnz)
    assert snap["wmd_span_seconds{span=wmd.query_batch}"]["count"] == 2
    assert snap["wmd_span_seconds{span=wmd.dispatch}"]["count"] == 2


def test_coalescer_phase_children_are_the_service_spans():
    """The request tree's ``precompute`` and ``solve`` children are the
    service's own ``wmd.cache_rows`` and dispatch-to-fetch intervals."""
    svc, rs = _service(cache_capacity=64)
    tr = Tracer()
    co = QueryCoalescer(svc, window_ms=50.0, max_batch=len(rs), tracer=tr)
    try:
        futs = [co.submit(r) for r in rs]
        for f in futs:
            f.result(timeout=60.0)
    finally:
        co.shutdown(drain=True)
    spans = svc.last_batch_stats["spans"]
    rows = [(t0, t1) for n, t0, t1 in spans if n == "wmd.cache_rows"][0]
    solve = (min(t0 for n, t0, _ in spans if n == "wmd.dispatch"),
             max(t1 for n, _, t1 in spans if n == "wmd.fetch"))
    trees, _ = tr.snapshot()
    assert len(trees) == len(rs)
    for tree in trees:
        by = {sp["name"]: sp for sp in tree["spans"]}
        assert (by["precompute"]["t0"], by["precompute"]["t1"]) == rows
        assert (by["solve"]["t0"], by["solve"]["t1"]) == solve
        d = by["dispatch"]
        assert d["t0"] <= rows[0] and solve[1] <= d["t1"]
        assert d["attrs"]["solve_s"] == svc.last_batch_stats["solve_s"]
