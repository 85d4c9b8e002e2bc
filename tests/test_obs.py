"""Observability layer: metrics registry, per-request tracing, exporters.

Contracts pinned here (ISSUE 9 acceptance criteria):
  * the registry is a correct concurrent store: exact totals under
    thread contention, get-or-create identity, kind-mismatch rejection;
  * Prometheus text exposition conforms to the 0.0.4 grammar (HELP/TYPE
    lines, sample lines, cumulative histogram buckets ending at +Inf)
    and is actually scrapeable over HTTP;
  * every submitted request -- including quarantined, cancelled,
    degraded and failed ones, under a seeded chaos schedule -- ends as
    exactly ONE closed span tree, with no trees left open;
  * breaker transitions, brownout enter/exit, watchdog strikes, WAL
    appends and compaction boundaries all land in the structured event
    log;
  * observability on is bitwise identical to observability off on the
    golden routes (tracing never touches result arrays);
  * ``last_batch_stats`` is never cleared to ``{}``: the legacy-fused
    route, at Q = 1 and above, reports total solve wall time with an
    explicit ``phases_separable: False`` marker.
"""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from repro.obs import (DEFAULT_SIZE_BUCKETS, JsonlExporter, MetricsRegistry,
                       MetricsServer, NULL_TRACER, Tracer, render_prometheus)
from repro.serving.coalescer import QueryCoalescer
from repro.serving.resilience import (DegradedResult, EngineGuard,
                                      ResiliencePolicy)

# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 3.0
    h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.1, 2.0):     # 0.1 lands in le=0.1 (inclusive)
        h.observe(v)
    cum = h.cumulative()
    assert cum == [(0.1, 2), (1.0, 3), (float("inf"), 4)]
    assert h.sum == pytest.approx(2.65)
    assert h.count == 4


def test_registry_get_or_create_identity_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total", labels={"op": "plain"})
    b = reg.counter("x_total", labels={"op": "plain"})
    other = reg.counter("x_total", labels={"op": "top_k"})
    assert a is b and a is not other
    a.inc(2)
    with pytest.raises(TypeError):
        reg.gauge("x_total", labels={"op": "plain"})
    reg.histogram("h_seconds").observe(0.2)
    snap = reg.snapshot()
    json.dumps(snap)                    # strictly JSON-able (incl. +Inf)
    assert snap["x_total{op=plain}"] == 2.0
    assert snap["h_seconds"]["buckets"][-1][0] == "+Inf"


def test_registry_thread_safety_exact_totals():
    """N concurrent dispatchers hammering shared + private counters must
    lose no increment -- the single-backing-store contract."""
    reg = MetricsRegistry()
    threads, per = 8, 2000
    shared = reg.counter("shared_total")
    hist = reg.histogram("lat_seconds", buckets=(0.5,))
    gate = threading.Barrier(threads)

    def work(i):
        mine = reg.counter("per_thread_total", labels={"t": str(i)})
        gate.wait()
        for _ in range(per):
            shared.inc()
            mine.inc()
            hist.observe(0.25)

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert shared.value == threads * per
    assert hist.count == threads * per
    for i in range(threads):
        assert reg.counter("per_thread_total",
                           labels={"t": str(i)}).value == per


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
_TYPE_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                       # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'   # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?'
    r" (-?[0-9.eE+-]+|\+Inf|-Inf|NaN)$")               # value


def _grammar_check(text: str) -> None:
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP"):
            assert _HELP_RE.match(line), line
        elif line.startswith("# TYPE"):
            assert _TYPE_RE.match(line), line
        else:
            assert _SAMPLE_RE.match(line), line


def test_prometheus_exposition_grammar():
    reg = MetricsRegistry()
    reg.counter("wmd_requests_total", "submitted requests",
                labels={"op": "plain"}).inc(5)
    reg.counter("wmd_requests_total", "submitted requests",
                labels={"op": "top_k"}).inc(2)
    reg.gauge("wmd_queue_depth", "queued requests").set(3)
    # label value that needs escaping
    reg.counter("wmd_errors_total",
                labels={"error": 'Runtime"Error"\nline\\x'}).inc()
    h = reg.histogram("wmd_batch_size", "batch occupancy",
                      buckets=DEFAULT_SIZE_BUCKETS)
    for v in (1, 3, 8, 300):
        h.observe(v)
    text = render_prometheus(reg)
    _grammar_check(text)
    # HELP/TYPE exactly once per metric name, before its samples
    assert text.count("# TYPE wmd_requests_total counter") == 1
    assert text.count("# HELP wmd_requests_total") == 1
    # histogram: cumulative buckets, +Inf == _count, _sum present
    lines = text.splitlines()
    buckets = [ln for ln in lines if ln.startswith("wmd_batch_size_bucket")]
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)                    # cumulative
    assert buckets[-1].startswith('wmd_batch_size_bucket{le="+Inf"}')
    assert counts[-1] == 4.0
    assert any(ln == "wmd_batch_size_count 4" for ln in lines)
    assert any(ln.startswith("wmd_batch_size_sum") for ln in lines)


def test_metrics_server_scrape():
    reg = MetricsRegistry()
    reg.counter("up_total").inc()
    with MetricsServer(reg, port=0, host="127.0.0.1") as srv:
        url = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        _grammar_check(body)
        assert "up_total 1" in body
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            assert r.status == 200


# ---------------------------------------------------------------------------
# tracer: span-tree lifecycle
# ---------------------------------------------------------------------------


def test_tracer_basic_tree_and_chrome_export(tmp_path):
    tr = Tracer()
    tr.begin_request(1, t0=10.0, op="plain")
    tr.add_span(1, "queue", 10.0, 10.5)
    tr.add_span(1, "dispatch", 10.5, 11.0, batch=4)
    tr.end_request(1, t1=11.0, status="ok")
    tr.event("breaker.transition", kind="plain", frm="closed", to="open")
    assert tr.open_count == 0
    trees, events = tr.snapshot()
    assert len(trees) == 1 and trees[0]["status"] == "ok"
    assert [s["name"] for s in trees[0]["spans"]] == ["queue", "dispatch"]
    assert events[0]["event"] == "breaker.transition"
    doc = tr.chrome_trace()
    json.dumps(doc)                                    # strictly JSON-able
    assert doc["traceEvents"], "empty chrome trace"
    phs = {e["ph"] for e in doc["traceEvents"]}
    assert phs <= {"X", "i", "M"}
    root = [e for e in doc["traceEvents"] if e["name"] == "request[ok]"]
    assert len(root) == 1 and root[0]["dur"] == pytest.approx(1e6)
    out = tmp_path / "trace.json"
    tr.export_chrome(str(out))
    assert json.load(open(out))["traceEvents"]


def test_tracer_reused_seq_closes_orphan_and_anon_quarantine():
    tr = Tracer()
    tr.begin_request(7)
    tr.begin_request(7)                 # reuse before close
    tr.end_request(7, status="ok")
    tr.closed_request(status="quarantined", op="plain")
    trees, _ = tr.snapshot()
    statuses = sorted(t["status"] for t in trees)
    assert statuses == ["ok", "orphaned", "quarantined"]
    assert tr.open_count == 0


def test_jsonl_exporter_round_trip(tmp_path):
    tr = Tracer()
    path = tmp_path / "events.jsonl"
    exp = JsonlExporter(tr, str(path), interval_s=0.05)
    tr.event("wal.append.synced", path="/x", bytes=12)
    tr.event("brownout.enter", queue_depth=9)
    exp.close()                         # final flush
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert [ev["event"] for ev in lines] == ["wal.append.synced",
                                            "brownout.enter"]
    assert exp.written == 2
    assert tr.drain_events() == []      # drained by the exporter


# ---------------------------------------------------------------------------
# event log: breaker / brownout / degraded / watchdog / WAL / compaction
# ---------------------------------------------------------------------------


def test_guard_events_breaker_degraded_and_metrics():
    import test_resilience as tres
    tr = Tracer()
    reg = MetricsRegistry()
    svc = tres.FlakyService(fail=50)    # exact tier always fails
    pol = ResiliencePolicy(max_retries=1, breaker_failures=2,
                           breaker_cooldown_s=30.0, backoff_base_s=0.0,
                           backoff_max_s=0.0)
    guard = EngineGuard(svc, pol, clock=tres.FakeClock(),
                        sleep=lambda s: None, tracer=tr, metrics=reg)
    res = guard.dispatch("plain", [np.ones(6, np.float32)])
    assert isinstance(res, DegradedResult)
    kinds = [e["event"] for e in tr.snapshot()[1]]
    assert "dispatch.failure" in kinds
    assert "breaker.transition" in kinds
    assert "degraded" in kinds
    trans = [e for e in tr.snapshot()[1] if e["event"] == "breaker.transition"]
    assert all(e["frm"] == "closed" and e["to"] == "open" for e in trans)
    assert reg.counter("wmd_breaker_transitions_total").value >= 1
    assert reg.counter("wmd_guard_degraded_total").value == 1
    assert reg.gauge("wmd_breaker_open_rungs").value >= 1


def test_guard_events_brownout_enter_exit():
    import test_resilience as tres
    tr = Tracer()
    clk = tres.FakeClock()
    pol = ResiliencePolicy(brownout_queue_hi=4, brownout_queue_lo=1)
    guard = EngineGuard(tres.FlakyService(fail=0), pol, clock=clk,
                        sleep=lambda s: None, tracer=tr)
    res = guard.dispatch("plain", [np.ones(6, np.float32)], queue_depth=10)
    assert isinstance(res, DegradedResult) and res.reason == "brownout"
    clk.advance(60.0)                   # past the exit dwell
    guard.dispatch("plain", [np.ones(6, np.float32)], queue_depth=0)
    kinds = [e["event"] for e in tr.snapshot()[1]]
    assert "brownout.enter" in kinds and "brownout.exit" in kinds
    assert kinds.index("brownout.enter") < kinds.index("brownout.exit")


def test_watchdog_strike_event():
    from repro.distributed.fault_tolerance import (FaultPolicy,
                                                   ServingWatchdog)
    import test_resilience as tres
    tr = Tracer()
    clk = tres.FakeClock()
    wd = ServingWatchdog(FaultPolicy(straggler_factor=2.0,
                                     straggler_strikes=1, timeout_s=5.0),
                         min_samples=1, clock=clk, tracer=tr)
    for _ in range(4):
        wd.beat("plain", 0.01, True)
    wd.beat("plain", 1.0, True)         # 100x the median: strike
    kinds = [e["event"] for e in tr.snapshot()[1]]
    assert "watchdog.strike" in kinds
    # stalled detection also lands in the log
    clk.advance(10.0)
    assert wd.check() == ["plain"]
    assert "watchdog.stalled" in [e["event"] for e in tr.snapshot()[1]]


def test_wal_and_compaction_boundary_events(tmp_path):
    from repro.data.live_corpus import LiveCorpus
    tr = Tracer()
    live = LiveCorpus(str(tmp_path / "live"), 32, tracer=tr)
    live.add_docs([0, 1], [[(2, 0.5), (3, 0.5)], [(4, 1.0)]])
    kinds = [e["event"] for e in tr.snapshot()[1]]
    for k in ("wal.append.pre", "wal.append.torn", "wal.append.synced"):
        assert k in kinds, k
    live.compact()
    kinds = [e["event"] for e in tr.snapshot()[1]]
    for k in ("compact.begin", "compact.built", "compact.snapshot.tmp",
              "compact.renamed", "compact.done"):
        assert k in kinds, k
    # the rotated-in WAL (fresh writer post-compaction) is traced too
    n_synced = kinds.count("wal.append.synced")
    live.add_docs([2], [[(5, 1.0)]])
    kinds = [e["event"] for e in tr.snapshot()[1]]
    assert kinds.count("wal.append.synced") == n_synced + 1


# ---------------------------------------------------------------------------
# chaos: every submitted request closes exactly one span tree
# ---------------------------------------------------------------------------


def test_chaos_every_request_closes_exactly_one_tree():
    import test_resilience as tres
    from repro.serving.faultinject import FaultSchedule, FaultyEngine
    svc = tres._service()
    qs = tres._queries(40, seed=4)
    bad = np.full(tres.VOCAB, np.nan, np.float32)      # quarantine fodder
    tr = Tracer()
    eng = FaultyEngine(svc, FaultSchedule(seed=11, p_error=0.2,
                                          p_latency=0.1, p_corrupt=0.1,
                                          latency_s=0.005))
    co = QueryCoalescer(eng, window_ms=1.0, max_batch=4,
                        resilience=tres.CHAOS_POLICY, tracer=tr)
    try:
        futs = [co.submit(q) for q in qs]
        for _ in range(3):
            with pytest.raises(Exception):
                co.submit(bad)                          # quarantined
        co.drain(timeout=120.0)
    finally:
        co.shutdown(drain=True, timeout=120.0)
    st = co.stats()
    assert st.submitted == len(qs) and st.quarantined == 3
    assert all(f.done() for f in futs)
    # exactly one closed tree per submitted OR quarantined request
    assert tr.open_count == 0, "span trees leaked open"
    trees, events = tr.snapshot()
    assert len(trees) == st.submitted + st.quarantined
    by_status: dict = {}
    for t in trees:
        by_status[t["status"]] = by_status.get(t["status"], 0) + 1
    assert by_status.get("quarantined", 0) == st.quarantined
    assert by_status.get("degraded", 0) == st.degraded
    assert by_status.get("failed", 0) == st.failed
    assert by_status.get("cancelled", 0) == st.cancelled
    ok = by_status.get("ok", 0)
    assert ok == st.completed - st.degraded
    seqs = [t["seq"] for t in trees]
    assert len(seqs) == len(set(seqs)), "a request closed twice"
    # completed requests carry full phase attribution: queue + dispatch
    for t in trees:
        if t["status"] in ("ok", "degraded"):
            names = [s["name"] for s in t["spans"]]
            assert "queue" in names and "dispatch" in names
            assert t["t1"] >= t["t0"]
            for s in t["spans"]:
                assert s["t1"] >= s["t0"] >= t["t0"]
    # the injected faults left their marks in the event log
    kinds = {e["event"] for e in events}
    assert "dispatch.failure" in kinds
    # Perfetto-loadable end product of the chaos run
    doc = tr.chrome_trace()
    json.dumps(doc)
    assert len([e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"].startswith("request[")]) \
        == len(trees)


def test_cancelled_and_shutdown_requests_close_trees():
    import test_resilience as tres
    tr = Tracer()
    svc = tres.FlakyService()
    co = QueryCoalescer(svc, window_ms=10_000.0, max_batch=64, tracer=tr)
    futs = [co.submit(np.ones(6, np.float32)) for _ in range(4)]
    futs[0].cancel()                    # cancelled while queued
    co.shutdown(drain=False)            # rest fail with CoalescerClosedError
    st = co.stats()
    assert st.cancelled == 1 and st.failed == 3
    assert tr.open_count == 0
    trees, _ = tr.snapshot()
    statuses = sorted(t["status"] for t in trees)
    assert statuses == ["cancelled", "failed", "failed", "failed"]


# ---------------------------------------------------------------------------
# bitwise neutrality: obs on == obs off on the golden routes
# ---------------------------------------------------------------------------


def test_obs_on_bitwise_identical_to_golden_routes():
    import test_golden as tg
    golden = np.load(tg.GOLDEN)
    vecs, ell, rs = tg._corpus()
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    cfg = WMDConfig(name="golden", vocab_size=vecs.shape[0], embed_dim=8,
                    num_docs=ell.num_docs, nnz_max=ell.nnz_max,
                    v_r=tg.V_R_BUCKET, lamb=tg.LAMB, max_iter=tg.MAX_ITER)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell,
                     cache_capacity=64, prune_chunk=8, bound_docs_chunk=None)
    assert svc.metrics is not None      # registry auto-attached
    # direct route, obs-on service
    np.testing.assert_array_equal(svc.query_batch(rs),
                                  golden["service_stripes"])
    idx_p, d_p = svc.top_k_batch(rs, tg.TOP_K, prune=True)
    np.testing.assert_array_equal(idx_p, golden["pruned_topk_idx"])
    np.testing.assert_array_equal(d_p, golden["pruned_topk_dist"])
    # traced + coalesced route: same bits as the direct golden route
    tr = Tracer()
    co = QueryCoalescer(svc, window_ms=1.0, max_batch=len(rs), tracer=tr)
    try:
        futs = [co.submit(r) for r in rs]
        out = np.stack([f.result(timeout=60.0) for f in futs])
    finally:
        co.shutdown(drain=True)
    np.testing.assert_array_equal(out, golden["service_stripes"])
    assert tr.open_count == 0 and len(tr.snapshot()[0]) == len(rs)
    # the mirrored K-cache counters saw the traffic without touching it
    assert svc.metrics.counter("wmd_kcache_lookups_total").value > 0


def test_null_tracer_is_inert_shared_default():
    import test_resilience as tres
    co = QueryCoalescer(tres.FlakyService(), window_ms=1.0, max_batch=4)
    assert co._tracer is NULL_TRACER and not NULL_TRACER.enabled
    try:
        assert co.submit(np.ones(6, np.float32)).result(timeout=30.0) \
            .shape == (6,)
    finally:
        co.shutdown(drain=True)
    # private registry by default: two coalescers never sum counters
    co2 = QueryCoalescer(tres.FlakyService(), window_ms=1.0, max_batch=4)
    co2.shutdown(drain=True)
    assert co.metrics is not co2.metrics


# ---------------------------------------------------------------------------
# last_batch_stats: the `{}`-clearing fix
# ---------------------------------------------------------------------------


def test_last_batch_stats_sequential_and_legacy_routes_report_time():
    import test_golden as tg
    vecs, ell, rs = tg._corpus()
    from repro.configs.sinkhorn_wmd import WMDConfig
    from repro.launch.mesh import make_mesh
    from repro.serving import WMDService
    cfg = WMDConfig(name="lbs", vocab_size=vecs.shape[0], embed_dim=8,
                    num_docs=ell.num_docs, nnz_max=ell.nnz_max,
                    v_r=tg.V_R_BUCKET, lamb=tg.LAMB, max_iter=tg.MAX_ITER)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc = WMDService(mesh=mesh, cfg=cfg, vecs=vecs, ell=ell,
                     cache_capacity=0)          # no cache: fast-path routes
    svc.query_batch([rs[0]])                    # Q = 1 -> legacy fused
    st = svc.last_batch_stats
    assert st["route"] == "legacy_fused"
    assert st["phases_separable"] is False and st["solve_s"] > 0.0
    svc.query_batch(rs)                         # Q>1 -> legacy fused
    st = svc.last_batch_stats
    assert st["route"] == "legacy_fused"
    assert st["phases_separable"] is False and st["solve_s"] > 0.0
