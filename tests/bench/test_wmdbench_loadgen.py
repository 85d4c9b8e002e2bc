"""The open-loop generator on a fake clock: latency runs from the due
time, and the generator's lateness is measured, not hidden."""
import numpy as np

import wmdbench_testing  # noqa: F401  (puts bench/ on the path)

from wmdbench import loadgen


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        assert s > 0
        self.t += s


def test_latency_is_timed_from_the_due_time():
    """A server that answers 0.25 s after the previous answer: requests
    due every 0.1 s queue behind each other, and their latency from the
    due time grows by 0.15 s a request."""
    clock = FakeClock()
    due = np.arange(5) * 0.1
    done = np.full(5, np.nan)
    busy_until = [0.0]

    def submit(i, payload):
        assert payload == i * 10
        busy_until[0] = max(busy_until[0], clock()) + 0.25
        done[i] = busy_until[0]

    t0, submitted = loadgen.drive(submit, due, prepare=lambda i: i * 10,
                                  clock=clock, sleep=clock.sleep)
    np.testing.assert_allclose(submitted, t0 + due)
    lat = loadgen.latency_ms(t0, due, done)
    np.testing.assert_allclose(lat, 250 + 150 * np.arange(5))
    np.testing.assert_allclose(loadgen.lateness_ms(t0, due, submitted), 0,
                               atol=1e-9)


def test_a_slow_generator_is_late_and_latency_counts_the_stall():
    """Preparing a request takes 0.3 s of the generator's time: it falls
    behind a 0.1 s schedule, catches up without sleeping, and each
    request's latency includes how late it was sent."""
    clock = FakeClock()
    due = np.arange(4) * 0.1
    done = np.full(4, np.nan)

    def prepare(i):
        clock.t += 0.3

    def submit(i, payload):
        done[i] = clock() + 0.01

    t0, submitted = loadgen.drive(submit, due, prepare=prepare,
                                  lead_s=0.0, clock=clock,
                                  sleep=clock.sleep)
    late = loadgen.lateness_ms(t0, due, submitted)
    np.testing.assert_allclose(late, [300, 500, 700, 900])
    np.testing.assert_allclose(loadgen.latency_ms(t0, due, done), late + 10)


def test_unanswered_requests_have_no_latency():
    due = np.array([0.0, 0.1, 0.2])
    done = np.array([1.0, np.nan, 1.3])
    np.testing.assert_allclose(loadgen.latency_ms(1.0, due, done),
                               [0.0, 100.0])
