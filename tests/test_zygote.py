"""Worker fork-server (zygote) tests: ms-class spawns, fallback paths,
and the startup-token (bootstrap) delivery contract.

The reference keeps worker processes warm via WorkerPool prestart/startup
tokens (src/ray/raylet/worker_pool.h:104,349,427,446); here the analog is
fork-from-a-preloaded-zygote, so the properties under test are: forked
workers are real, isolated processes; the zygote is an accelerator and
never a single point of failure (cold spawn always works); and the
dedicated-actor token rides the spawn.
"""

import os
import subprocess
import time

import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu.config import Config
from ray_memory_management_tpu.core import zygote
from ray_memory_management_tpu.core.node_manager import (
    package_env,
    spawn_worker_process,
)


def test_forked_workers_run_tasks_and_actors():
    rmt.init(num_cpus=4)
    try:
        @rmt.remote
        def f(x):
            return os.getpid(), x * 2

        pid_a, va = rmt.get(f.remote(3))
        assert va == 6 and pid_a != os.getpid()

        @rmt.remote(num_cpus=0)
        class Counter:
            def __init__(self, start):
                self.n = start

            def add(self, k):
                self.n += k
                return self.n

        c = Counter.remote(10)
        assert rmt.get(c.add.remote(5)) == 15
        assert rmt.get(c.add.remote(1)) == 16
    finally:
        rmt.shutdown()


def test_actor_burst_is_fast():
    """The headline property: a burst of plain actors must create at
    fork-server speed, not cold-interpreter speed (which on this image is
    >2s per actor). The bound is deliberately loose — 30 actors in 10s is
    ~40x slower than measured — so only an architectural regression to
    cold spawns can trip it."""
    rmt.init(num_cpus=4)
    try:
        @rmt.remote(num_cpus=0)
        class Probe:
            def ready(self):
                return b"ok"

        warm = Probe.remote()
        rmt.get(warm.ready.remote())
        t0 = time.perf_counter()
        actors = [Probe.remote() for _ in range(30)]
        assert rmt.get([a.ready.remote() for a in actors],
                       timeout=120) == [b"ok"] * 30
        assert time.perf_counter() - t0 < 10.0
    finally:
        rmt.shutdown()


def test_chip_lease_worker_cold_spawns():
    """The worker of a chip lease needs the lease's environment from
    interpreter start, so it never forks from the (CPU-pinned) zygote."""
    cfg = Config()
    env = dict(package_env())
    env.update({
        "RMT_WORKER_ID": "00" * 16, "RMT_NODE_ID": "00" * 16,
        "RMT_STORE_NAME": "/none", "RMT_SOCKET": "/tmp/none.sock",
        "RMT_AUTHKEY": "", "RMT_INLINE_LIMIT": "1",
        "RMT_LOG_TO_DRIVER": "0", "TPU_VISIBLE_CHIPS": "0",
    })
    called = []
    proc = spawn_worker_process(env, cfg, bootstrap={"type": "noop"},
                                on_cold_bootstrap=lambda: called.append(1),
                                cold=True)
    try:
        assert isinstance(proc, subprocess.Popen)
        assert called == [1]  # cold path must hand the token back
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_forked_proc_liveness_and_kill():
    z = zygote.get_global()
    if z is None:
        pytest.skip("fork server unavailable")
    env = dict(package_env())
    env.update({
        "RMT_WORKER_ID": "00" * 16, "RMT_NODE_ID": "00" * 16,
        "RMT_STORE_NAME": "/none", "RMT_SOCKET": "/tmp/rmt_noexist.sock",
        "RMT_AUTHKEY": "", "RMT_INLINE_LIMIT": "1",
        "RMT_LOG_TO_DRIVER": "0", "JAX_PLATFORMS": "cpu",
    })
    proc = z.spawn(env)
    assert proc is not None and proc.pid > 0
    # the worker exits on its own (no socket to dial); poll must flip
    deadline = time.monotonic() + 30
    while proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.05)
    assert proc.poll() is not None

    proc2 = z.spawn(env)
    assert proc2 is not None
    proc2.kill()
    deadline = time.monotonic() + 10
    while proc2.poll() is None and time.monotonic() < deadline:
        time.sleep(0.05)
    assert proc2.poll() is not None


def test_forked_worker_env_fidelity():
    """A forked worker's environment must be EXACTLY what
    build_worker_env produced — the delta protocol resets the child to
    the client's baseline, not the zygote's own (drifted) environ: a
    child reset to the zygote's environ could run jax on the wrong
    platform."""
    rmt.init(num_cpus=2)
    try:
        @rmt.remote
        def probe_env():
            return (os.environ.get("JAX_PLATFORMS"),
                    os.environ.get("RMT_ZYGOTE_AUTHKEY"),
                    os.environ.get("RMT_WORKER_ID") is not None)

        jax_platforms, authkey, has_wid = rmt.get(probe_env.remote(),
                                                  timeout=120)
        assert jax_platforms == "cpu"   # NOT the zygote's drifted value
        assert authkey is None          # the zygote secret never leaks
        assert has_wid                  # per-worker delta vars applied
    finally:
        rmt.shutdown()


def test_preload_taint_retires_zygote():
    """A class blob whose unpickling initializes a jax backend must not be
    preloaded pre-fork (every later child would inherit a fork-broken
    PJRT client): the zygote retires itself, the class is blacklisted,
    and a fresh zygote serves it with the load deferred to the child."""
    import cloudpickle

    z = zygote.get_global()
    if z is None:
        pytest.skip("fork server unavailable")

    def _touch_backend():
        import jax

        jax.config.update("jax_platforms", "cpu")
        jax.devices("cpu")
        return int

    class _Trigger:
        def __reduce__(self):
            return (_touch_backend, ())

    blob = cloudpickle.dumps(_Trigger())
    env = dict(package_env())
    env["JAX_PLATFORMS"] = "cpu"
    cls_id = b"taint-test-cls"
    boot = {"type": "create_actor", "cls_id": cls_id, "cls_blob": blob}
    assert z.spawn(env, bootstrap=dict(boot)) is None  # retired, no fork
    assert cls_id in zygote._taint_classes
    deadline = time.monotonic() + 10
    while z._proc.poll() is None and time.monotonic() < deadline:
        time.sleep(0.05)
    assert z._proc.poll() is not None  # the tainted zygote exited

    z2 = zygote.get_global()  # fresh replacement
    assert z2 is not None and z2 is not z
    proc = z2.spawn(env, bootstrap=dict(boot))  # no_preload: forks fine
    assert proc is not None and proc.pid > 0
    assert z2._proc.poll() is None  # replacement survived the spawn
    proc.kill()
    zygote._taint_classes.discard(cls_id)
    zygote.shutdown_global()


def test_zygote_death_is_survivable():
    """Killing the fork server must not break worker spawning — the next
    get_global() replaces it, and spawn falls back to cold Popen in the
    interim."""
    z = zygote.get_global()
    if z is None:
        pytest.skip("fork server unavailable")
    z._proc.kill()
    z._proc.wait(timeout=10)
    assert z.spawn({"JAX_PLATFORMS": "cpu"}) is None  # dead server: None
    z2 = zygote.get_global()  # replaced
    assert z2 is not None and z2 is not z
    zygote.shutdown_global()
