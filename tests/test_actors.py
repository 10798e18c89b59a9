"""Actor tests (reference: python/ray/tests/test_actor*.py coverage)."""

import os
import time

import pytest

import ray_memory_management_tpu as rmt


@rmt.remote
class Counter:
    def __init__(self, start=0):
        self.n = start

    def inc(self, k=1):
        self.n += k
        return self.n

    def read(self):
        return self.n

    async def aread(self):
        return self.n * 10


def test_actor_basic(rmt_start_regular):
    c = Counter.remote(5)
    assert rmt.get(c.inc.remote()) == 6
    assert rmt.get(c.read.remote()) == 6


def test_actor_async_method(rmt_start_regular):
    c = Counter.remote(3)
    assert rmt.get(c.aread.remote()) == 30


def test_actor_ordering(rmt_start_regular):
    c = Counter.remote()
    refs = [c.inc.remote() for _ in range(100)]
    assert rmt.get(refs[-1]) == 100
    assert rmt.get(refs) == list(range(1, 101))


def test_named_actor(rmt_start_regular):
    Counter.options(name="named_counter").remote(1)
    h = rmt.get_actor("named_counter")
    assert rmt.get(h.inc.remote()) == 2


def test_actor_handle_in_task(rmt_start_regular):
    c = Counter.remote()

    @rmt.remote
    def bump(handle):
        return rmt.get(handle.inc.remote(10))

    assert rmt.get(bump.remote(c)) == 10


def test_actor_method_error(rmt_start_regular):
    @rmt.remote
    class Bad:
        def go(self):
            raise RuntimeError("nope")

    b = Bad.remote()
    with pytest.raises(rmt.TaskError, match="nope"):
        rmt.get(b.go.remote())


def test_actor_constructor_error(rmt_start_regular):
    @rmt.remote
    class BadInit:
        def __init__(self):
            raise RuntimeError("bad init")

        def f(self):
            return 1

    b = BadInit.remote()
    with pytest.raises((rmt.TaskError, rmt.ActorError)):
        rmt.get(b.f.remote(), timeout=30)


def test_kill_actor(rmt_start_regular):
    c = Counter.remote()
    rmt.get(c.inc.remote())
    rmt.kill(c)
    time.sleep(0.3)
    with pytest.raises(rmt.ActorError):
        rmt.get(c.read.remote(), timeout=10)


def test_actor_restart(rmt_start_regular):
    @rmt.remote(max_restarts=2)
    class Fragile:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

        def die(self):
            os._exit(1)

    f = Fragile.remote()
    assert rmt.get(f.inc.remote()) == 1
    with pytest.raises(rmt.RmtError):
        rmt.get(f.die.remote(), timeout=10)
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            # state resets after restart (the reference's restart semantics)
            assert rmt.get(f.inc.remote(), timeout=10) == 1
            break
        except rmt.ActorError:
            time.sleep(0.2)
    else:
        raise AssertionError("actor did not restart")


def test_max_concurrency_parallel(rmt_start_regular):
    @rmt.remote(max_concurrency=4)
    class Sleeper:
        def nap(self, t):
            time.sleep(t)
            return t

        def ping(self):
            return "ok"

    s = Sleeper.remote()
    rmt.get(s.ping.remote(), timeout=60)  # wait out actor cold-start
    t0 = time.time()
    rmt.get([s.nap.remote(0.5) for _ in range(4)], timeout=30)
    elapsed = time.time() - t0
    assert elapsed < 1.6, f"methods did not overlap: {elapsed}"


def test_actor_pass_data_via_store(rmt_start_regular):
    import numpy as np

    @rmt.remote
    class Holder:
        def __init__(self):
            self.data = None

        def set(self, arr):
            self.data = arr.copy()
            return arr.nbytes

        def total(self):
            return float(self.data.sum())

    h = Holder.remote()
    arr = np.ones(500_000, dtype=np.float64)
    assert rmt.get(h.set.remote(arr)) == arr.nbytes
    assert rmt.get(h.total.remote()) == 500_000.0


def test_many_actor_tasks_blocked_on_one_dep(rmt_start_regular):
    """Regression (VERDICT r1 item 9): >8 actor tasks waiting on a single
    unfinished dependency used to park one request-pool thread EACH
    (pool size 8), deadlock-starving all worker-request service. With
    callback-based dep waits, nested worker requests keep flowing while
    12 calls wait on the slow producer."""
    import time

    @rmt.remote
    def slow_dep():
        import time as t

        t.sleep(2.0)
        return 7

    @rmt.remote
    def nested_probe():
        # exercises the request pool while the dep waits are outstanding
        return rmt.get(rmt.put("alive"))

    @rmt.remote
    class Sink:
        def consume(self, v):
            return v + 1

    s = Sink.remote()
    # warm the probe path (worker spawn is seconds on a 1-CPU box and is
    # not what this test measures)
    assert rmt.get(nested_probe.remote(), timeout=120) == "alive"
    dep = slow_dep.remote()
    blocked = [s.consume.remote(dep) for _ in range(12)]
    # while those 12 are blocked, the request pool must still serve
    # nested worker requests promptly
    t0 = time.monotonic()
    assert rmt.get(nested_probe.remote(), timeout=60) == "alive"
    assert time.monotonic() - t0 < 1.9, "request pool starved by dep waits"
    assert rmt.get(blocked, timeout=120) == [8] * 12


def test_methods_submitted_across_actor_startup_keep_order(rmt_start_regular):
    """Submits that straddle the moment the actor turns ALIVE: the ones
    queued while it was starting are sent first, and a later one never
    overtakes them by going straight to the worker."""
    import time

    @rmt.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            time.sleep(0.2)
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    for _ in range(4):
        c = Counter.remote()
        refs = []
        until = time.monotonic() + 0.8
        while time.monotonic() < until:
            refs.append(c.inc.remote())
            time.sleep(0.001)
        assert rmt.get(refs, timeout=120) == list(range(1, len(refs) + 1))
        rmt.kill(c)
