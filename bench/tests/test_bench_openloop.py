"""Open-loop timing: a query is timed from when it was due, so a stall of
the server shows in the latency of every query that arrived during it,
and the generator's lateness is recorded."""

import _paths  # noqa: F401
import numpy as np

from harness.drivers.search import Driver


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt


def _driver(clock, stall_on_flush, stall_s):
    d = Driver.__new__(Driver)
    d.control, d.seed, d.server = None, 0, None
    d.traffic = {"max_batch": 4, "max_wait_s": 0.005}
    d.pool = [{"adj": np.zeros((2, 2), np.float32),
               "labels": np.zeros(2, np.int32)}] * 3
    d.popularity = np.full(3, 1 / 3)
    d.rng = np.random.default_rng(0)
    flushes = []

    def search(batch):
        flushes.append(len(batch))
        clock.now += stall_s if len(flushes) == stall_on_flush else 0.002
        return [(np.arange(3), np.zeros(3))] * len(batch)

    d.search = search
    d.schedule = lambda seconds: [0.01 * i for i in range(20)]
    return d, flushes


def test_latency_counts_from_due_time_through_a_stall():
    clock = FakeClock()
    d, flushes = _driver(clock, stall_on_flush=3, stall_s=0.1)
    d.window(0.2, clock=clock, sleep=clock.sleep)
    lat = d.latencies_ms()
    assert d.attempted == 20 and d.failed == 0
    assert sum(flushes) == 20
    # Before the stall every query waits its 5 ms batching deadline and a
    # 2 ms search.
    assert np.allclose(lat[:2], 7.0, atol=0.05)
    # The stalled flush holds the third query for 100 ms; queries due
    # while it ran were sent late and their latency counts the wait.
    assert lat[2] >= 100.0
    late = np.asarray([(q.sent - q.due) * 1e3 for q in d.queries])
    assert late[:3].max() < 1e-6
    assert late[3:12].min() > 0.0
    for q, ms in zip(d.queries[3:12], lat[3:12]):
        assert ms >= (q.sent - q.due) * 1e3
    assert d.end_to_end()["search_p95_ms"] >= 80.0


def test_without_a_stall_nothing_is_late():
    clock = FakeClock()
    d, _ = _driver(clock, stall_on_flush=0, stall_s=0.0)
    d.window(0.2, clock=clock, sleep=clock.sleep)
    late = np.asarray([(q.sent - q.due) * 1e3 for q in d.queries])
    assert late.max() < 1e-6
    assert d.latencies_ms().max() < 8.0


def test_a_query_never_answered_counts_as_missing():
    clock = FakeClock()
    d, _ = _driver(clock, stall_on_flush=0, stall_s=0.0)
    d.window(0.2, clock=clock, sleep=clock.sleep)
    d.queries[4].result = None
    assert d.failed == 1
    assert np.isinf(d.latencies_ms()[4])
