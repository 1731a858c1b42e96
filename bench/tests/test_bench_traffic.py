"""The traffic generator: a schedule that depends on the seed alone,
and latency timed from when a request was due."""
import numpy as np

from benchtest_util import load_run
from lib import traffic

MIX = {"rate_qps": 2000.0}


def test_schedule_depends_only_on_the_seed():
    seed = 2 ** 33 + 123
    a = traffic.open_loop_schedule(seed, MIX, 5.0, 1024)
    b = traffic.open_loop_schedule(seed, MIX, 5.0, 1024)
    c = traffic.open_loop_schedule(seed + 1, MIX, 5.0, 1024)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0][:100], c[0][:100])


def test_schedule_is_poisson_at_the_rate_and_cycles_the_pool():
    due, q = traffic.open_loop_schedule(7, MIX, 10.0, 1024)
    assert abs(due.size - 20000) < 5 * np.sqrt(20000)
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 10.0
    gaps = np.diff(due)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05     # exponential
    assert np.array_equal(np.sort(q[:1024]), np.arange(1024))
    assert np.array_equal(q[:1024], q[1024:2048])


class _Result:
    def __init__(self, k):
        self.ids = np.arange(k)
        self.scores = -np.arange(k, dtype=np.float32)
        self.docs_evaluated = 1
        self.latency_s = 0.001


class _Future:
    status = "done"

    def __init__(self, k):
        self._r = _Result(k)

    def wait(self, timeout=None):
        return True

    def result(self):
        return self._r


class _StallingServer:
    """Answers 1 ms after submit, but its first submit blocks 50 ms."""

    def __init__(self, k):
        self.k = k
        self.n = 0

    def submit(self, coords, vals):
        import time
        self.n += 1
        if self.n == 1:
            time.sleep(0.05)
        return _Future(self.k)


def test_latency_is_timed_from_the_due_time():
    run = load_run()
    mix = {"rate_qps": 1000.0}
    q = np.zeros((8, 4), np.int32)
    w = run.window_open(_StallingServer(10), mix, 5, 0.2, q,
                        np.ones((8, 4), np.float32), 10, None)
    lat = w["latency_ms"]
    due, _ = traffic.open_loop_schedule(5, mix, 0.2, 8)
    # requests due during the stall waited for it: timed from submit
    # they would read 1 ms, from their due time they read more
    stalled = due[1:] < due[0] + 0.045
    assert stalled.sum() > 10
    assert np.all(lat[1:][stalled] > 1.0 + 1e3 * (due[0] + 0.049
                                                  - due[1:][stalled]))
    assert np.all(lat >= 1.0)
    assert w["answered"].all()
