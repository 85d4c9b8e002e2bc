"""Resilience layer: numeric guards, breaker/brownout state machines, and
the seeded chaos suite.

Contracts pinned here (ISSUE 7 acceptance criteria):
  * under a seeded fault schedule (dispatch exceptions, latency spikes,
    corrupted outputs) the serving loop never deadlocks and every
    submitted future resolves exactly once;
  * successful (non-degraded, rung-0) responses are bitwise identical to
    a no-fault dispatch of the same batch composition;
  * availability stays >= 0.99 and the degraded fraction is surfaced in
    ServingStats;
  * the breaker and brownout machines hit every transition;
  * high lambda raises a typed NumericalError where the old engine
    silently returned exact-zero distances (pinned with guards off).

Determinism: faults draw from ``default_rng((seed, call_index))`` -- the
schedule replays identically regardless of thread timing; state-machine
tests run on fake clocks and fake engines (no jax at all).
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.configs.sinkhorn_wmd import WMDConfig
from repro.core import guards
from repro.data import make_corpus, zipf_query_stream
from repro.distributed.fault_tolerance import FaultPolicy, ServingWatchdog
from repro.launch.mesh import make_mesh
from repro.serving.coalescer import QueryCoalescer
from repro.serving.faultinject import (FaultSchedule, FaultSpec, FaultyEngine,
                                       InjectedFault)
from repro.serving.resilience import (BrownoutController, CircuitBreaker,
                                      DegradedResult, EngineGuard,
                                      ResiliencePolicy)
from repro.serving.wmd_service import WMDService

VOCAB, DOCS = 512, 24


def _service(*, lamb=1.0, capacity=64, guards_on=True, seed=0):
    data = make_corpus(vocab_size=VOCAB, embed_dim=32, num_docs=DOCS,
                       num_queries=1, query_words=11, mean_words=12.0,
                       seed=seed)
    cfg = WMDConfig(name="res", vocab_size=VOCAB, embed_dim=32,
                    num_docs=DOCS, nnz_max=64, v_r=16, lamb=lamb, max_iter=8)
    mesh = make_mesh((1, 1), ("data", "model"))
    return WMDService(mesh=mesh, cfg=cfg, vecs=data.vecs, ell=data.ell,
                      cache_capacity=capacity, bound_docs_chunk=None,
                      guards=guards_on)


def _queries(n, seed=0):
    stream = zipf_query_stream(vocab_size=VOCAB, query_words=11, s=1.2,
                               seed=seed)
    return [next(stream) for _ in range(n)]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FlakyService:
    """jax-free engine stub: fails the first ``fail`` calls, records every
    route it was dispatched."""

    def __init__(self, fail=0, n_docs=6):
        self.fail = fail
        self.n_docs = n_docs
        self.calls = []

    def _maybe_fail(self):
        if self.fail > 0:
            self.fail -= 1
            raise RuntimeError("flaky")

    def query_batch(self, rs):
        self.calls.append(("query_batch",))
        self._maybe_fail()
        return np.ones((len(rs), self.n_docs), np.float32)

    def top_k_batch(self, rs, k=10, prune=False):
        self.calls.append(("top_k_batch", "pruned" if prune else "scan"))
        self._maybe_fail()
        return (np.zeros((len(rs), k), np.int64),
                np.ones((len(rs), k), np.float32))

    def query_batch_bounds(self, rs):
        self.calls.append(("bounds",))
        return np.full((len(rs), self.n_docs), 0.5, np.float32)

    def top_k_batch_bounds(self, rs, k=10):
        self.calls.append(("bounds_topk",))
        return (np.zeros((len(rs), k), np.int64),
                np.full((len(rs), k), 0.5, np.float32))


# ---------------------------------------------------------------------------
# guards: unit level
# ---------------------------------------------------------------------------

def test_validate_query_rejections():
    ok = np.zeros(8, np.float32)
    ok[3] = 1.0
    assert guards.validate_query(ok, 8) is not None
    cases = {
        "wrong length": (np.ones(5, np.float32), 8),
        "2-D": (np.ones((2, 4), np.float32), None),
        "non-finite": (np.array([1, np.nan, 0, 0], np.float32), None),
        "negative": (np.array([1, -1, 0, 0], np.float32), None),
        "all-zero": (np.zeros(8, np.float32), None),
        "non-numeric": (np.array(["a", "b"]), None),
    }
    for name, (bad, v) in cases.items():
        with pytest.raises(guards.InvalidQueryError):
            guards.validate_query(bad, v)


def test_underflow_gate_threshold():
    # gate = lamb * 2 * max_norm >= 149 ln 2 (~103.28)
    assert not guards.underflow_possible(1.0, 7.7)      # every shipped cfg
    assert not guards.underflow_possible(5.0, 7.7)
    assert guards.underflow_possible(30.0, 7.7)
    assert guards.underflow_possible(1.0, 60.0)         # huge embeddings


def test_check_km_rows_masks_pad_rows():
    # (Q=1, v_r=3) row maxes: one real-dead row fires, pad-dead rows don't
    rowmax = np.array([[0.0, 1.0, 0.0]])
    guards.check_km_rows(rowmax, np.array([[0, 1, 0]]))  # dead rows are pad
    with pytest.raises(guards.NumericalError) as ei:
        guards.check_km_rows(rowmax, np.array([[1, 1, 0]]), lamb=42.0)
    assert ei.value.context["check"] == "km_underflow"
    assert ei.value.context["lamb"] == 42.0


def test_check_distances_zero_cells_gated():
    d = np.array([[0.0, 1.0], [2.0, 3.0]], np.float32)
    guards.check_distances(d, risk=False)               # gate off: fine
    with pytest.raises(guards.NumericalError):
        guards.check_distances(d, risk=True)
    # empty docs legitimately solve to 0 even under an armed gate
    guards.check_distances(d, risk=True,
                           empty_doc_mask=np.array([True, False]))
    with pytest.raises(guards.NumericalError):          # non-finite always
        guards.check_distances(np.array([np.inf]), risk=False)


# ---------------------------------------------------------------------------
# breaker / brownout / backoff state machines (fake clocks, no jax)
# ---------------------------------------------------------------------------

def test_circuit_breaker_every_transition():
    clk = FakeClock()
    br = CircuitBreaker(failures=3, cooldown_s=5.0, probes=2, clock=clk)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"                 # streak below threshold
    br.record_failure()
    assert br.state == "open" and not br.allow()
    clk.advance(5.1)
    assert br.allow() and br.state == "half_open"
    br.record_failure()                         # failed probe -> re-open
    assert br.state == "open"
    clk.advance(5.1)
    assert br.allow() and br.state == "half_open"
    br.record_success()
    assert br.state == "half_open"              # needs 2 probes
    br.record_success()
    assert br.state == "closed"
    assert set(br.transitions) == {("closed", "open"),
                                   ("open", "half_open"),
                                   ("half_open", "open"),
                                   ("half_open", "closed")}


def test_circuit_breaker_success_resets_streak():
    br = CircuitBreaker(failures=2, clock=FakeClock())
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"                 # streak was reset


def test_brownout_hysteresis_and_dwell():
    clk = FakeClock()
    bo = BrownoutController(queue_hi=10, queue_lo=2, miss_hi=0.5,
                            miss_lo=0.1, dwell_s=1.0, clock=clk)
    assert not bo.update(5, 0.0)                # below hi
    assert bo.update(10, 0.0) and bo.entries == 1
    clk.advance(0.5)
    assert bo.update(0, 0.0)                    # calm but dwell not served
    clk.advance(0.6)
    assert bo.update(3, 0.0)                    # dwell served, NOT calm yet
    assert not bo.update(2, 0.0)                # calm + dwell -> exit
    assert bo.update(0, 0.9) and bo.entries == 2   # miss signal re-enters
    clk.advance(1.1)
    assert bo.update(0, 0.2)                    # miss still above lo
    assert not bo.update(0, 0.1)


def test_brownout_disabled_without_thresholds():
    bo = BrownoutController(clock=FakeClock())
    assert not bo.update(10 ** 9, 1.0)


def test_backoff_bounded_and_positive():
    g = EngineGuard(FlakyService(), ResiliencePolicy(
        backoff_base_s=0.01, backoff_mult=2.0, backoff_max_s=0.05,
        backoff_jitter=0.5, seed=3), sleep=lambda s: None)
    waits = [g._backoff(a) for a in range(10)]
    assert all(0.0 < w <= 0.05 * 1.5 for w in waits)
    assert waits[1] >= 0.01                     # base grows with attempts


# ---------------------------------------------------------------------------
# fault schedule determinism
# ---------------------------------------------------------------------------

def test_fault_schedule_deterministic_and_windowed():
    s1 = FaultSchedule(seed=5, p_error=0.3, p_latency=0.3, p_corrupt=0.3)
    s2 = FaultSchedule(seed=5, p_error=0.3, p_latency=0.3, p_corrupt=0.3)
    draws1 = [s1.faults_for(i) for i in range(200)]
    assert draws1 == [s2.faults_for(i) for i in range(200)]
    assert any(f.error for f in draws1) and any(f.corrupt for f in draws1)
    assert [s1.faults_for(i) for i in range(200)] == draws1   # stateless
    sw = FaultSchedule(seed=5, p_error=1.0, window=(10, 12))
    assert not sw.faults_for(9).error
    assert sw.faults_for(10).error and sw.faults_for(11).error
    assert not sw.faults_for(12).error


def test_fault_schedule_from_events():
    sched = FaultSchedule.from_events({3: FaultSpec(error=True),
                                       5: FaultSpec(corrupt=True)})
    assert sched.faults_for(3).error
    assert sched.faults_for(5).corrupt
    assert sched.faults_for(4) == FaultSpec()


# ---------------------------------------------------------------------------
# EngineGuard: retry, demotion, recovery, degradation (fake engine)
# ---------------------------------------------------------------------------

def test_retry_recovers_transient_failures():
    svc = FlakyService(fail=2)
    g = EngineGuard(svc, ResiliencePolicy(max_retries=2, breaker_failures=5),
                    sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)] * 2)
    assert isinstance(res, np.ndarray) and res.shape == (2, 6)
    st = g.stats()
    assert st.retries == 2 and st.failures == 2 and st.demoted == 0
    # rung 0 is the exact unguarded call
    assert svc.calls == [("query_batch",)] * 3


def test_demotion_and_breaker_recovery():
    clk = FakeClock()
    svc = FlakyService(fail=1)
    g = EngineGuard(svc, ResiliencePolicy(
        max_retries=0, breaker_failures=2, breaker_cooldown_s=10.0),
        clock=clk, sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)])
    # retries=0: the engine rung fails once (breaker streak 1); no rung is
    # left, so the bound tier answers
    assert isinstance(res, DegradedResult)
    assert svc.calls == [("query_batch",), ("bounds",)]
    st = g.stats()
    assert st.demoted == 0 and st.degraded == 1
    # fail the engine once more -> streak 2 -> breaker opens
    svc.fail = 1
    g.dispatch("plain", [np.ones(4)])
    assert g.stats().breaker_states["plain/0"] == "open"
    # while open, dispatches skip the engine entirely
    n_calls = len(svc.calls)
    res = g.dispatch("plain", [np.ones(4)])
    assert svc.calls[n_calls:] == [("bounds",)]
    assert res.reason == "all rungs open"
    # cooldown passes: next dispatch probes the engine (half_open), closes
    clk.advance(10.1)
    res = g.dispatch("plain", [np.ones(4)])
    assert isinstance(res, np.ndarray)
    assert svc.calls[-1] == ("query_batch",)
    assert g.stats().breaker_states["plain/0"] == "closed"


def test_top_k_ladder_falls_back_to_scan():
    svc = FlakyService(fail=1)                  # the pruned rung fails
    g = EngineGuard(svc, ResiliencePolicy(max_retries=0, breaker_failures=1,
                                          degrade_on_failure=False),
                    sleep=lambda s: None)
    res = g.dispatch("top_k", [np.ones(4)], k=3)
    assert res[0].shape == (1, 3)
    kinds = [c[1] for c in svc.calls if c[0] == "top_k_batch"]
    assert kinds == ["pruned", "scan"]
    assert g.stats().demoted == 1


def test_plain_ladder_is_one_rung_then_degraded():
    """A plain dispatch has one exact rung, the engine: its retries
    exhausted, the bound tier answers; there is no second engine call."""
    svc = FlakyService(fail=3)
    g = EngineGuard(svc, ResiliencePolicy(max_retries=2, breaker_failures=5),
                    sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)] * 2)
    assert isinstance(res, DegradedResult) and "flaky" in res.reason
    assert svc.calls == [("query_batch",)] * 3 + [("bounds",)]
    assert set(g.stats().breaker_states) == {"plain/0", "top_k/0",
                                             "top_k/1"}
    assert g.dispatch_log[-1] == ("plain", -1, True)


def test_degraded_when_every_rung_fails():
    svc = FlakyService(fail=100)
    g = EngineGuard(svc, ResiliencePolicy(max_retries=1, breaker_failures=2),
                    sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)] * 3)
    assert isinstance(res, DegradedResult)
    assert res.tier == "rwmd_bound"
    assert "engine_failure" in res.reason and "flaky" in res.reason
    np.testing.assert_array_equal(res.value,
                                  np.full((3, 6), 0.5, np.float32))
    st = g.stats()
    assert st.degraded == 1 and st.degraded_requests == 3


def test_degradation_disabled_raises_last_error():
    svc = FlakyService(fail=100)
    g = EngineGuard(svc, ResiliencePolicy(max_retries=0, breaker_failures=1,
                                          degrade_on_failure=False),
                    sleep=lambda s: None)
    with pytest.raises(RuntimeError, match="flaky"):
        g.dispatch("plain", [np.ones(4)])


def test_invalid_query_never_retried():
    class Rejecting(FlakyService):
        def query_batch(self, rs):
            self.calls.append(("query_batch",))
            raise guards.InvalidQueryError("bad row")

    svc = Rejecting()
    g = EngineGuard(svc, ResiliencePolicy(max_retries=5),
                    sleep=lambda s: None)
    with pytest.raises(guards.InvalidQueryError):
        g.dispatch("plain", [np.ones(4)])
    assert len(svc.calls) == 1                  # no retry, no demotion
    assert g.stats().retries == 0


def test_guard_post_check_catches_corruption():
    class Corrupting(FlakyService):
        def query_batch(self, rs):
            self.calls.append(("query_batch",))
            out = np.ones((len(rs), self.n_docs), np.float32)
            if len(self.calls) == 1:            # only the first dispatch
                out[0, 0] = np.nan
            return out

    svc = Corrupting()
    g = EngineGuard(svc, ResiliencePolicy(max_retries=2, breaker_failures=5),
                    sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)])
    assert np.isfinite(res).all()               # retry returned clean data
    assert g.stats().retries == 1


def test_brownout_dispatch_serves_bounds_and_recovers():
    clk = FakeClock()
    svc = FlakyService()
    g = EngineGuard(svc, ResiliencePolicy(
        brownout_queue_hi=4, brownout_queue_lo=1, brownout_dwell_s=1.0),
        clock=clk, sleep=lambda s: None)
    res = g.dispatch("plain", [np.ones(4)], queue_depth=10)
    assert isinstance(res, DegradedResult) and res.reason == "brownout"
    clk.advance(1.1)
    res = g.dispatch("plain", [np.ones(4)], queue_depth=0)
    assert isinstance(res, np.ndarray)          # calm + dwell: exact again
    assert g.stats().brownout_entries == 1


def test_trip_force_opens_active_rung():
    svc = FlakyService()
    g = EngineGuard(svc, ResiliencePolicy(), sleep=lambda s: None)
    g.trip("top_k")
    assert g.stats().breaker_states["top_k/0"] == "open"
    g.dispatch("top_k", [np.ones(4)], k=3)      # served by rung 1
    assert svc.calls[-1] == ("top_k_batch", "scan")
    g.trip("top_k")                             # next non-open rung
    assert g.stats().breaker_states["top_k/1"] == "open"
    g.trip("plain")                             # the engine, plain's one
    assert g.stats().breaker_states["plain/0"] == "open"
    res = g.dispatch("plain", [np.ones(4)])
    assert isinstance(res, DegradedResult)
    assert svc.calls[-1] == ("bounds",)


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------

def test_watchdog_straggler_strikes_trip():
    clk = FakeClock()
    tripped = []
    wd = ServingWatchdog(FaultPolicy(straggler_factor=2.0,
                                     straggler_strikes=3),
                         on_strike=tripped.append, min_samples=3, clock=clk)
    for _ in range(5):
        wd.beat("plain", 0.01, True)            # establish the median
    for _ in range(2):
        wd.beat("plain", 0.1, True)             # 2 strikes: below threshold
    assert tripped == []
    wd.beat("plain", 0.01, True)                # fast beat resets the streak
    for _ in range(3):
        wd.beat("plain", 0.1, True)
    assert tripped == ["plain"]                 # 3 consecutive -> trip
    assert wd.report()["plain"]["tripped"] == 1


def test_watchdog_failures_count_as_strikes():
    tripped = []
    wd = ServingWatchdog(FaultPolicy(straggler_strikes=2),
                         on_strike=tripped.append, clock=FakeClock())
    wd.beat("top_k", 0.01, False)
    wd.beat("top_k", 0.01, False)
    assert tripped == ["top_k"]
    assert wd.report()["top_k"]["failures"] == 2


def test_watchdog_liveness_needs_pending_work():
    clk = FakeClock()
    pending = {"n": 0}
    wd = ServingWatchdog(FaultPolicy(timeout_s=5.0),
                         pending_fn=lambda: pending["n"], clock=clk)
    wd.beat("plain", 0.01, True)
    clk.advance(10.0)
    assert wd.check() == []                     # idle silence is fine
    pending["n"] = 3
    assert wd.check() == ["plain"]              # silent with a backlog
    wd.beat("plain", 0.01, True)
    assert wd.check() == []


# ---------------------------------------------------------------------------
# admission validation at the coalescer
# ---------------------------------------------------------------------------

def test_admission_quarantines_bad_queries():
    svc = _service()
    with svc.async_service(window_ms=1.0, max_batch=4) as co:
        good = _queries(3)
        bad = [np.full(VOCAB, np.nan, np.float32),
               -np.ones(VOCAB, np.float32),
               np.zeros(VOCAB, np.float32),
               np.ones(7, np.float32)]
        futs = [co.submit(q) for q in good]
        for b in bad:
            with pytest.raises(guards.InvalidQueryError):
                co.submit(b)
        rows = [f.result(timeout=60) for f in futs]
    st = co.stats()
    assert st.quarantined == len(bad)
    assert st.completed == len(good) and st.failed == 0
    assert all(np.isfinite(r).all() for r in rows)
    # quarantined requests never reached a dispatch
    assert sum(len(b) for b in co.batch_log) == len(good)


def test_fake_services_keep_light_validation():
    class Fake:
        def query_batch(self, rs):
            return np.zeros((len(rs), 2), np.float32)

    co = QueryCoalescer(Fake(), window_ms=1.0, max_batch=2)
    try:
        f = co.submit(np.zeros(4, np.float32))   # all-zero: fine for fakes
        f.result(timeout=10)
        with pytest.raises(guards.InvalidQueryError):
            co.submit(np.full(4, np.inf, np.float32))   # non-finite: not
    finally:
        co.shutdown()


# ---------------------------------------------------------------------------
# high-lambda underflow: typed error vs the old silent-zero behavior
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("docs_chunk", [0, DOCS // 3],
                         ids=["unchunked", "solve_chunks"])
@pytest.mark.parametrize("capacity", [0, 64])
def test_high_lambda_raises_numerical_error(docs_chunk, capacity):
    svc = _service(lamb=30.0, capacity=capacity)
    svc.docs_chunk = docs_chunk
    qs = _queries(4, seed=1)
    with pytest.raises(guards.NumericalError) as ei:
        svc.query_batch(qs)
    assert ei.value.context["check"] in ("km_underflow", "zero_distance")
    # the old behavior, pinned: guards off -> silent exact-zero distances
    svc.guards = False
    d = svc.query_batch(qs)
    assert np.isfinite(d).all() and (d == 0.0).any()


def test_default_lambda_unchanged_by_guards():
    qs = _queries(4, seed=2)
    d_on = _service(guards_on=True).query_batch(qs)
    d_off = _service(guards_on=False).query_batch(qs)
    np.testing.assert_array_equal(d_on, d_off)  # guards are read-only


def test_degraded_tier_survives_high_lambda():
    # lambda kills the exact tier but not the bound tier (M has no exp):
    # the resilient path keeps answering, degraded
    svc = _service(lamb=30.0)
    g = EngineGuard(svc, ResiliencePolicy(max_retries=0, breaker_failures=1),
                    sleep=lambda s: None)
    res = g.dispatch("plain", _queries(2, seed=3))
    assert isinstance(res, DegradedResult)
    assert np.isfinite(res.value).all()


# ---------------------------------------------------------------------------
# chaos suite: the serving loop under a seeded fault schedule
# ---------------------------------------------------------------------------

CHAOS_POLICY = ResiliencePolicy(
    max_retries=3, breaker_failures=4, breaker_cooldown_s=0.05,
    backoff_base_s=0.001, backoff_max_s=0.01, seed=0)


def _run_chaos(svc, qs, schedule, *, policy=CHAOS_POLICY, top_k=None,
               window_ms=1.0, max_batch=4, concurrency=0):
    eng = FaultyEngine(svc, schedule)
    co = QueryCoalescer(eng, window_ms=window_ms, max_batch=max_batch,
                        resilience=policy)
    futs = []
    try:
        if concurrency:
            from repro.serving.loadgen import closed_loop
            submit = (co.submit if top_k is None
                      else lambda r: co.submit_top_k(r, top_k))
            lg = closed_loop(submit, qs, concurrency=concurrency,
                             keep_results=True)
            return co, eng, lg, None
        submit = (co.submit if top_k is None
                  else lambda r: co.submit_top_k(r, top_k))
        futs = [submit(q) for q in qs]
        co.drain(timeout=120.0)                 # the no-deadlock assertion
        return co, eng, None, futs
    finally:
        co.shutdown(drain=True, timeout=120.0)


def test_chaos_no_deadlock_every_future_resolves_bitwise():
    svc = _service()
    qs = _queries(48, seed=4)
    sched = FaultSchedule(seed=11, p_error=0.2, p_latency=0.15,
                          p_corrupt=0.1, latency_s=0.005)
    co, eng, _, futs = _run_chaos(svc, qs, sched)
    # every submitted future resolved exactly once, with a result
    assert all(f.done() for f in futs)
    exact = degraded = 0
    for f in futs:
        assert f.exception() is None
        r = f.result()
        if isinstance(r, DegradedResult):
            degraded += 1
            r = r.value
        else:
            exact += 1
        assert r.shape == (DOCS,) and np.isfinite(r).all()
    st = co.stats()
    assert st.completed == len(qs) and st.failed == 0
    availability = (st.submitted - st.failed) / st.submitted
    assert availability >= 0.99
    assert st.degraded == degraded
    assert st.degraded_fraction == degraded / len(qs)
    assert eng.injected["error"] > 0            # the schedule actually bit
    # bitwise contract: every clean rung-0 dispatch the injector saw must
    # equal a no-fault dispatch of the same composition on a clean service
    clean = _service()
    replayed = 0
    for rec in eng.dispatch_log:
        if (rec.method == "query_batch" and rec.result is not None
                and not rec.fault.corrupt):
            np.testing.assert_array_equal(
                rec.result, clean.query_batch(rec.payloads))
            replayed += 1
    assert replayed > 0


def test_chaos_closed_loop_top_k():
    svc = _service()
    qs = _queries(24, seed=5)
    sched = FaultSchedule(seed=13, p_error=0.15, p_corrupt=0.1)
    co, eng, lg, _ = _run_chaos(svc, qs, sched, top_k=5, concurrency=3)
    assert lg.submitted == len(qs)
    assert lg.completed + lg.failed == len(qs)
    assert lg.completed / lg.submitted >= 0.99
    st = co.stats()
    assert st.completed == lg.completed
    for res in lg.results:
        if isinstance(res, DegradedResult):
            res = res.value
        idx, dist = res
        assert idx.shape == (5,) and np.isfinite(dist).all()


def test_chaos_open_loop_poisson():
    """Open-loop Poisson arrivals through the injector: offered load does
    not pause for faults, yet availability holds."""
    from repro.serving.loadgen import open_loop
    svc = _service()
    qs = _queries(24, seed=10)
    eng = FaultyEngine(svc, FaultSchedule(seed=29, p_error=0.2,
                                          p_corrupt=0.1))
    co = QueryCoalescer(eng, window_ms=1.0, max_batch=4,
                        resilience=CHAOS_POLICY)
    try:
        lg = open_loop(co.submit, iter(qs), rate_qps=2000.0,
                       keep_results=True)
    finally:
        co.shutdown(drain=True, timeout=120.0)
    assert lg.submitted == len(qs)
    assert lg.completed + lg.failed == len(qs)
    assert lg.completed / lg.submitted >= 0.99
    for res in lg.results:
        if isinstance(res, DegradedResult):
            res = res.value
        assert np.isfinite(res).all()


def test_chaos_fault_storm_recovers():
    """A 100%-error storm window opens breakers and serves degraded; after
    the storm (and the breaker cooldown), probes close the breakers and
    exact serving resumes."""
    svc = _service()
    qs = _queries(40, seed=6)
    # calls 4..16 all fail -- enough to burn every rung's retry budget
    sched = FaultSchedule(seed=17, p_error=1.0, window=(4, 16))
    policy = dataclasses.replace(CHAOS_POLICY, max_retries=1,
                                 breaker_failures=2,
                                 breaker_cooldown_s=0.02)
    eng = FaultyEngine(svc, sched)
    co = QueryCoalescer(eng, window_ms=1.0, max_batch=4, resilience=policy)
    try:
        futs = [co.submit(q) for q in qs]
        co.drain(timeout=120.0)
        assert all(f.done() and f.exception() is None for f in futs)
        assert any(isinstance(f.result(), DegradedResult) for f in futs)
        time.sleep(0.05)                        # > cooldown: breakers cool
        eng.schedule = FaultSchedule()          # storm over
        post = [co.submit(q) for q in _queries(4, seed=60)]
        co.drain(timeout=120.0)
        for f in post:                          # exact serving resumed
            assert isinstance(f.result(), np.ndarray)
    finally:
        co.shutdown(drain=True, timeout=120.0)
    st = co.stats()
    assert st.completed == len(qs) + 4 and st.failed == 0
    assert st.breaker_transitions >= 2          # open + recovery


def test_chaos_brownout_integration():
    """Latency injection builds a backlog; the brownout controller flips
    the coalescer to bound-only responses (marked, counted, bitwise equal
    to a bounds replay of the same composition) until the queue clears."""
    svc = _service()
    qs = _queries(24, seed=7)
    sched = FaultSchedule(seed=19, p_latency=1.0, latency_s=0.02)
    policy = dataclasses.replace(CHAOS_POLICY, brownout_queue_hi=2,
                                 brownout_queue_lo=0, brownout_dwell_s=0.0)
    co, eng, _, futs = _run_chaos(svc, qs, sched, policy=policy,
                                  window_ms=30.0)
    assert all(f.done() and f.exception() is None for f in futs)
    st = co.stats()
    assert st.completed == len(qs) and st.failed == 0
    assert st.degraded > 0
    assert co.guard.stats().brownout_entries >= 1
    # degraded responses are bitwise a bounds dispatch of the same batch
    clean = _service()
    seq_to_q = dict(enumerate(qs))
    degraded_checked = 0
    for batch in co.batch_log:
        rows = [futs[s].result() for s in batch]
        if not all(isinstance(r, DegradedResult) for r in rows):
            continue
        ref = clean.query_batch_bounds([seq_to_q[s] for s in batch])
        for i, r in enumerate(rows):
            np.testing.assert_array_equal(r.value, ref[i])
            assert r.tier == "rwmd_bound" and r.reason == "brownout"
            degraded_checked += 1
    assert degraded_checked > 0


def test_chaos_stats_clean_run_has_no_resilience_noise():
    svc = _service()
    qs = _queries(8, seed=8)
    co, eng, _, futs = _run_chaos(svc, qs, FaultSchedule())   # no faults
    st = co.stats()
    assert st.retries == 0 and st.degraded == 0 and st.quarantined == 0
    assert st.breaker_transitions == 0 and not st.brownout_active
    # and fault-free resilient serving is bitwise the plain engine
    clean = _service()
    for rec in eng.dispatch_log:
        np.testing.assert_array_equal(
            rec.result, clean.query_batch(rec.payloads))


def test_faulty_engine_protects_bounds_tier():
    svc = FlakyService()
    eng = FaultyEngine(svc, FaultSchedule(seed=1, p_error=1.0))
    with pytest.raises(InjectedFault):
        eng.query_batch([np.ones(4)])
    # bounds are exempt from injection by default (the brownout fallback
    # must stay reliable while the exact tier burns)
    np.testing.assert_array_equal(eng.query_batch_bounds([np.ones(4)]),
                                  np.full((1, 6), 0.5, np.float32))


def test_dispatcher_survives_concurrent_chaos_submitters():
    """Multiple client threads + faults: no deadlock, exact accounting."""
    svc = _service()
    qs = _queries(30, seed=9)
    eng = FaultyEngine(svc, FaultSchedule(seed=23, p_error=0.2))
    co = QueryCoalescer(eng, window_ms=1.0, max_batch=4,
                        resilience=CHAOS_POLICY)
    futs = [None] * len(qs)

    def client(lo, hi):
        for i in range(lo, hi):
            futs[i] = co.submit(qs[i])

    threads = [threading.Thread(target=client, args=(i * 10, (i + 1) * 10))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        co.drain(timeout=120.0)
    finally:
        co.shutdown(drain=True, timeout=120.0)
    assert all(f is not None and f.done() for f in futs)
    st = co.stats()
    assert st.submitted == len(qs)
    assert st.completed + st.failed == len(qs)
    assert st.completed / st.submitted >= 0.99
