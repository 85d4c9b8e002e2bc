"""Open-loop submission on an absolute schedule, timed from the due time.

A copy of the program's `serving.loadgen.open_loop` schedule (submission
i fires at t0 + due[i], never waiting for answers, so a slow submit makes
the generator catch up rather than lower the offered rate), changed in
what it measures: a request's latency runs from its *due* time, so a stall
that delays later submissions counts against them, and the generator's own
lateness (submit time minus due time) is returned so that a starved
generator is not read as a fast server. The clock and the sleep are
parameters so the timing can be tested on a fake clock.
"""
from __future__ import annotations

import time

import numpy as np


def drive(submit, due: np.ndarray, *, prepare=None, lead_s: float = 0.05,
          clock=time.monotonic, sleep=time.sleep):
    """Call ``submit(i, prepare(i))`` at ``t0 + due[i]`` for every i, in
    order; returns ``(t0, submitted)`` with each actual submit time."""
    t0 = clock() + lead_s
    submitted = np.full(due.size, np.nan)
    for i in range(due.size):
        payload = prepare(i) if prepare is not None else None
        delay = t0 + due[i] - clock()
        if delay > 0:
            sleep(delay)
        submitted[i] = clock()
        submit(i, payload)
    return t0, submitted


def latency_ms(t0: float, due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Due time to answer, for every answered request (done not NaN)."""
    ok = ~np.isnan(done)
    return (done[ok] - (t0 + due[ok])) * 1e3


def lateness_ms(t0: float, due: np.ndarray,
                submitted: np.ndarray) -> np.ndarray:
    """How late the generator submitted each request."""
    ok = ~np.isnan(submitted)
    return (submitted[ok] - (t0 + due[ok])) * 1e3
