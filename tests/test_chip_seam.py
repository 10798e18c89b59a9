"""The chip-acquisition seam, on the CPU: which process may open a chip.

A chip belongs to one process at a time, so the runtime decides per lease who
can see it: ``rmt.init`` and worker construction create no jax backend, a
lease of whole chips is served by a worker cold-spawned with exactly those
chips in its environment, that worker exits before its chip ids are handed
out again, and nothing on the path falls back to a device that was not asked
for. The tests lease fake chips (``num_tpus`` on a host without any) and
read environments and process ids; none of the leased tasks touches jax.
"""

import os
import subprocess
import sys

import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu.config import Config
from ray_memory_management_tpu.core.node_manager import (
    build_worker_env, chip_lease_env, package_env,
)
from ray_memory_management_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _probe():
    return {"jax_imported": "jax" in sys.modules,
            "platforms": os.environ.get("JAX_PLATFORMS"),
            "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "bounds": os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS"),
            "cache_dir": os.environ.get(compile_cache.ENV_VAR),
            "pid": os.getpid()}


def test_init_creates_no_backend_and_a_fresh_worker_has_no_jax():
    """After ``rmt.init()`` the driver has no jax backend (not even jax
    imported by the runtime), and a worker imports jax only when a task
    does. Needs a driver of its own: this process has long used jax."""
    code = (
        "import sys, json\n"
        "import ray_memory_management_tpu as rmt\n"
        "from ray_memory_management_tpu.utils.jax_backend import "
        "initialized_platforms\n"
        "rmt.init(num_cpus=2, num_tpus=1)\n"
        "@rmt.remote\n"
        "def probe():\n"
        "    import sys\n"
        "    return 'jax' in sys.modules\n"
        "out = {'driver_jax': 'jax' in sys.modules,\n"
        "       'driver_platforms': list(initialized_platforms()),\n"
        "       'worker_jax': rmt.get(probe.remote(), timeout=120),\n"
        "       'leased_jax': rmt.get(probe.options(num_tpus=1).remote(),\n"
        "                             timeout=120)}\n"
        "rmt.shutdown()\n"
        "print('PROBE ' + json.dumps(out))\n")
    proc = subprocess.run([sys.executable, "-c", code], env=package_env(),
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("PROBE ")][-1]
    import json

    assert json.loads(line[6:]) == {
        "driver_jax": False, "driver_platforms": [],
        "worker_jax": False, "leased_jax": False}


def test_chip_lease_env_sorts_and_describes_a_sub_host_slice():
    # pop order from the free list is descending; libtpu gets ascending
    assert chip_lease_env([3, 2, 1, 0], host_chips=4) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}
    part = chip_lease_env([3, 2], host_chips=4)
    assert part["TPU_VISIBLE_CHIPS"] == "2,3"
    assert part["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert part["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert set(part) == {"TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                         "TPU_CHIPS_PER_PROCESS_BOUNDS"}
    with pytest.raises(ValueError, match="no slice shape"):
        chip_lease_env([0, 1, 2], host_chips=4)


@pytest.mark.parametrize("driver_platforms", [None, "cpu"])
def test_only_a_leased_worker_can_see_a_chip(monkeypatch, driver_platforms):
    """Per lease, not per cluster: a worker without a lease is pinned to
    the CPU platform whatever the driver's environment says; the worker of
    a lease inherits the driver's JAX_PLATFORMS as it stands (unset where
    jax should find the chip, ``cpu`` where the operator hid it)."""
    if driver_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", driver_platforms)
    args = ("00" * 16, "00" * 16, "/none", "/tmp/none.sock", "", Config())
    plain = build_worker_env(*args)
    assert plain["JAX_PLATFORMS"] == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in plain
    leased = build_worker_env(*args, chips=[1, 0], host_chips=2)
    assert leased["TPU_VISIBLE_CHIPS"] == "0,1"
    assert leased.get("JAX_PLATFORMS") == driver_platforms


@pytest.fixture
def four_fake_chips(monkeypatch):
    # the driver's own environment is what a leased worker inherits
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rt = rmt.init(num_cpus=2, num_tpus=4)
    yield rt
    rmt.shutdown()


def test_leased_task_sees_its_chips_and_no_captured_platform(
        four_fake_chips):
    probe = rmt.remote(_probe)
    cpu = rmt.get(probe.remote(), timeout=120)
    assert cpu["platforms"] == "cpu" and cpu["chips"] is None
    leased = rmt.get(probe.options(num_tpus=2).remote(), timeout=120)
    assert leased["chips"] == "0,1"        # ascending, lowest ids first
    assert leased["bounds"] == "1,2,1"     # half of a four-chip host
    # nothing pinned the platform and jax was not imported before the
    # task ran, so no CPU platform list can have been captured
    assert leased["platforms"] is None and not leased["jax_imported"]
    assert not cpu["jax_imported"]


def test_sub_host_lease_is_an_aligned_run_of_chip_ids(four_fake_chips):
    """With chips 0 and 2 held, a two-chip lease must wait: 1,3 is no
    slice of the host (on the v5e host such a lease crashed its worker in
    libtpu, PR 21). Pairs are 0-1 and 2-3."""
    nm = next(iter(four_fake_chips.nodes.values()))
    assert nm.take_chips(1) == [0]
    assert nm.take_chips(2) == [2, 3]
    assert nm.take_chips(2) is None and nm.free_chips == [1]
    nm.free_chips.extend([2, 3])
    assert nm.take_chips(4) is None
    assert nm.take_chips(1) == [1] and nm.take_chips(2) == [2, 3]
    nm.free_chips.extend([0, 1, 2, 3])
    assert nm.take_chips(4) == [0, 1, 2, 3]
    nm.free_chips.extend([0, 1, 2, 3])


def test_lease_worker_is_retired_before_its_chips_return(four_fake_chips):
    """A worker that has opened a chip holds it until it exits, and the
    next lease may land on another process. So the worker of a chip-leased
    task is never pooled: it is told to exit when the lease ends, and its
    chip ids (and TPU resource) return only once the process has been
    seen to go. Retirement does not ask whether a backend is live, so
    there is no predicate to fake: a second lease of ALL the chips can
    only start after the first worker is gone, and finds it gone."""
    nm = next(iter(four_fake_chips.nodes.values()))

    @rmt.remote(num_tpus=4)
    def whole_host(previous_pid=None):
        gone = None
        if previous_pid is not None:
            try:
                os.kill(previous_pid, 0)
                gone = False
            except ProcessLookupError:
                gone = True
        return os.getpid(), os.environ["TPU_VISIBLE_CHIPS"], gone

    pid1, chips1, _ = rmt.get(whole_host.remote(), timeout=120)
    pid2, chips2, first_gone = rmt.get(whole_host.remote(pid1), timeout=120)
    assert chips1 == chips2 == "0,1,2,3"
    assert pid2 != pid1 and first_gone

    # an actor after a task gets the chips too, keeps them (and the TPU
    # resource) across method calls, and gives them back when killed
    @rmt.remote(num_tpus=4)
    class Holder:
        def chips(self):
            return os.environ["TPU_VISIBLE_CHIPS"], os.getpid()

    holder = Holder.remote()
    assert rmt.get(holder.chips.remote(), timeout=120)[0] == "0,1,2,3"
    _, holder_pid = rmt.get(holder.chips.remote(), timeout=120)
    assert nm.free_chips == []
    assert nm.resources.available.get("TPU") == 0
    rmt.kill(holder)
    pid3, chips3, holder_gone = rmt.get(whole_host.remote(holder_pid),
                                        timeout=120)
    assert chips3 == "0,1,2,3" and holder_gone
    assert pid3 not in (pid1, pid2, holder_pid)


def test_use_tpu_worker_refuses_another_backend(tmp_path):
    """Where the program itself asks for the chip, a default backend other
    than tpu is an error raised in the worker, not a CPU run."""
    from ray_memory_management_tpu.train import (
        JaxTrainer, RunConfig, ScalingConfig,
    )

    ran = tmp_path / "loop_ran"
    rmt.init(num_cpus=2, num_tpus=1)
    try:
        result = JaxTrainer(
            lambda: ran.write_text("x"),
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=1),
            run_config=RunConfig(storage_path=str(tmp_path / "runs")),
        ).fit()
    finally:
        rmt.shutdown()
    assert "default backend is 'cpu'" in str(result.error)
    assert not ran.exists()


def test_one_host_tpu_world_env():
    """An xla world of leased workers: each rank is told the process grid
    and its peers; CPU worlds need nothing; shapes that were never brought
    up on the chip raise instead of hanging in libtpu."""
    from ray_memory_management_tpu.train.backend_executor import (
        TrainingFailedError, _one_host_tpu_world,
    )

    def lease(chips, port, node="n0"):
        return {"node_id": node, "chips": chips, "port": port}

    assert _one_host_tpu_world(
        [lease(None, 1), lease(None, 2)]) == [None, None]
    envs = _one_host_tpu_world(
        [lease(str(i), 9000 + i) for i in range(4)])
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert envs[2]["TPU_PROCESS_PORT"] == "9002"
    assert envs[0]["TPU_PROCESS_BOUNDS"] == "2,2,1"
    assert envs[0]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert envs[3]["TPU_PROCESS_ADDRESSES"] == (
        "localhost:9000,localhost:9001,localhost:9002,localhost:9003")
    pair = _one_host_tpu_world([lease("0,1", 1), lease("2,3", 2)])
    assert pair[1]["TPU_PROCESS_BOUNDS"] == "2,1,1"
    assert pair[1]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    for bad in ([lease("0", 1), lease("1", 2)],             # 2 x 1
                [lease("0", 1), lease(None, 2)],            # mixed
                [lease("0,1", 1), lease("2,3", 2, "n1")]):  # two hosts
        with pytest.raises(TrainingFailedError, match="brought up only"):
            _one_host_tpu_world(bad)


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_rule(monkeypatch, tmp_path, placed_from_outside):
    """Variable set: every process uses it and code names no other
    directory. Unset: one fixed directory in the checkout. Either way the
    driver and a (zygote-forked) worker agree."""
    # set first either way: monkeypatch then restores the variable's
    # original state at teardown, adopt()'s write included
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    if not placed_from_outside:
        monkeypatch.delenv(compile_cache.ENV_VAR)
    want = str(tmp_path) if placed_from_outside else os.path.join(
        REPO, ".jax_compile_cache")
    assert compile_cache.default_dir() == os.path.join(
        REPO, ".jax_compile_cache")
    assert package_env()[compile_cache.ENV_VAR] == want
    rmt.init(num_cpus=2, num_tpus=1)
    try:
        assert os.environ[compile_cache.ENV_VAR] == want
        probe = rmt.remote(_probe)
        assert rmt.get(probe.remote(), timeout=120)["cache_dir"] == want
        assert rmt.get(probe.options(num_tpus=1).remote(),
                       timeout=120)["cache_dir"] == want
    finally:
        rmt.shutdown()


def test_compile_counter_counts_programs():
    import jax
    import jax.numpy as jnp

    counter = compile_cache.CompileCounter()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    snap = counter.snapshot()
    assert snap["programs"] >= 1 and snap["seconds"] > 0
    assert snap["compiled"] == snap["programs"] - snap["cache_hits"]


def test_device_budget_is_taken_from_the_device_on_first_put():
    import jax.numpy as jnp

    from ray_memory_management_tpu.core import device_store as ds

    assert ds.configured_capacity(Config()) is None  # auto: not yet
    assert ds.configured_capacity(
        Config(device_store_capacity_bytes=4096)) == 4096
    assert ds.configured_capacity(
        Config(device_store_capacity_bytes=-1)) == -1
    store = ds.DeviceObjectStore(capacity_bytes=None)
    assert store.capacity_bytes is None
    store.put(b"a", jnp.ones(8))
    # the CPU backend reports no memory stats
    assert store.capacity_bytes == 1 << 30

    class _Device:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    class _Array:
        def __init__(self, stats):
            self._device = _Device(stats)

        def devices(self):
            return {self._device}

    assert ds.device_budget(_Array({"bytes_limit": 1000})) == 600
    # a device that reports stats without a limit is an error, not 1 GiB
    with pytest.raises(RuntimeError, match="without a byte limit"):
        ds.device_budget(_Array({"bytes_in_use": 5}))


def test_initialized_platforms_fails_loudly_if_jax_moves_the_dict(
        monkeypatch):
    import jax
    from jax._src import xla_bridge

    from ray_memory_management_tpu.utils.jax_backend import (
        initialized_platforms,
    )

    jax.devices("cpu")
    assert "cpu" in initialized_platforms()
    monkeypatch.delattr(xla_bridge, "_backends")
    with pytest.raises(RuntimeError, match="_backends"):
        initialized_platforms()


def test_detect_tpu_chips_counts_without_jax(monkeypatch):
    from ray_memory_management_tpu.api import _detect_tpu_chips

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert _detect_tpu_chips() == 2
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    assert _detect_tpu_chips() == 0  # no chip device node on this host


def test_no_fallback_hides_the_device():
    import types

    from ray_memory_management_tpu.parallel import local_tpu_mesh
    from ray_memory_management_tpu.utils.tpu_bench import peak_flops

    assert peak_flops(types.SimpleNamespace(
        device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(ValueError, match="no bf16 peak"):
        peak_flops(types.SimpleNamespace(device_kind="TPU v9 mega"))
    with pytest.raises(RuntimeError):
        local_tpu_mesh()  # no TPU here: an error, not a CPU mesh
