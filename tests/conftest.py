"""Test fixtures: in-process multi-node clusters (the reference's
ray_start_regular / ray_start_cluster fixtures, python/ray/tests/conftest.py:203-348).

jax-facing tests run on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count) so multi-chip sharding logic is
exercised without TPU hardware.
"""

import os

# must be set before jax initializes its backends
os.environ.setdefault("JAX_PLATFORMS", "cpu")
prev = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()
# the suite compiles thousands of small CPU programs and runs few long ones:
# LLVM's cheapest level takes about a fifth off a run of six xdist workers
# on eight cores. Only the CPU backend reads it: a described chip's compile
# (test_chip_compile.py) comes out the same text with and without it.
if "xla_backend_optimization_level" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_backend_optimization_level=0"

# one persistent compile cache for the run, shared by the xdist workers and
# every process they spawn: tests build the same small programs again and
# again (a fresh engine a test, jax.clear_caches()), and each is compiled
# once a run. The directory is new each run and is removed when the run
# exits normally. The described-chip fixtures switch the cache off before
# they compile: what they would write cannot be read back without the chip.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil
    import tempfile

    _cache_dir = tempfile.mkdtemp(prefix="jax-compile-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, True)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import pytest  # noqa: E402

import ray_memory_management_tpu as rmt  # noqa: E402


@pytest.fixture(scope="session")
def cpp_client_dir():
    """The C++ client's directory with its Makefile's targets built (make
    caches them). Two test files need them and xdist gives the files to
    two workers at once: one ``make`` at a time, under a file lock, so that
    neither links what the other is still writing."""
    import fcntl
    import subprocess

    client_dir = os.path.join(os.path.dirname(os.path.abspath(rmt.__file__)),
                              "native", "client")
    with open(os.path.join(client_dir, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when it closes
        try:
            subprocess.run(["make", "-C", client_dir], check=True,
                           capture_output=True, text=True, timeout=300)
        except subprocess.CalledProcessError as e:  # pragma: no cover
            pytest.fail(f"C++ client build failed:\n{e.stderr}")
    return client_dir


@pytest.fixture
def rmt_start_regular():
    rt = rmt.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    rmt.shutdown()


@pytest.fixture
def rmt_start_cluster():
    """3-node virtual cluster, 4 CPUs each."""
    rt = rmt.init(num_cpus=4, num_nodes=3)
    yield rt
    rmt.shutdown()


@pytest.fixture
def rmt_small_store():
    from ray_memory_management_tpu.config import Config

    cfg = Config(object_store_memory=64 << 20)
    rt = rmt.init(num_cpus=4, _config=cfg)
    yield rt
    rmt.shutdown()
