"""Collective layer tests: XLA mesh backend on a virtual 8-device CPU mesh,
object-plane backend across real actor processes.

(reference: python/ray/util/collective tests; the mesh tests exercise the
same ops the reference lowers to NCCL.)
"""

import numpy as np
import pytest

import ray_memory_management_tpu as rmt
from ray_memory_management_tpu import collective as col
from ray_memory_management_tpu.core import metrics_defs as mdefs


# ---------------------------------------------------------------- xla / mesh
@pytest.fixture(scope="module")
def mesh_group():
    import jax

    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 CPU devices"
    return col.MeshCollectives(devices[:8])


def test_mesh_allreduce(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((4,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.allreduce(stacked))
    expect = np.full((4,), sum(range(w)), np.float32)
    for r in range(w):
        np.testing.assert_allclose(out[r], expect)


def test_mesh_allreduce_max(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((3,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.allreduce(stacked, col.ReduceOp.MAX))
    np.testing.assert_allclose(out[0], np.full((3,), w - 1, np.float32))


def test_mesh_reducescatter(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack(
        [np.arange(w * 2, dtype=np.float32) + i for i in range(w)]
    )
    out = np.asarray(mesh_group.reducescatter(stacked))
    # rank r holds slice r of the elementwise sum
    total = stacked.sum(axis=0)
    for r in range(w):
        np.testing.assert_allclose(out[r], total[r * 2:(r + 1) * 2])


def test_mesh_allgather(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((2,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.allgather(stacked))
    np.testing.assert_allclose(out, stacked)


def test_mesh_broadcast(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((2,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.broadcast(stacked, root=3))
    for r in range(w):
        np.testing.assert_allclose(out[r], np.full((2,), 3, np.float32))


def test_mesh_reduce_rooted(mesh_group):
    """reduce is ROOTED (collective.py:311 semantics): only root's slice
    holds the reduction; other slices pass through unchanged (VERDICT r1
    item 9 — previously this silently returned the full allreduce)."""
    w = mesh_group.world_size
    stacked = np.stack([np.full((4,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.reduce(stacked, root_rank=2))
    np.testing.assert_allclose(out[2], np.full((4,), sum(range(w))))
    for r in range(w):
        if r != 2:
            np.testing.assert_allclose(out[r], stacked[r])


def test_mesh_reduce_rooted_max(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((3,), i, np.float32) for i in range(w)])
    out = np.asarray(mesh_group.reduce(stacked, root_rank=0,
                                       op=col.ReduceOp.MAX))
    np.testing.assert_allclose(out[0], np.full((3,), w - 1, np.float32))
    np.testing.assert_allclose(out[1], stacked[1])


def test_mesh_ppermute_ring(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack([np.full((2,), i, np.float32) for i in range(w)])
    perm = [(i, (i + 1) % w) for i in range(w)]
    out = np.asarray(mesh_group.ppermute(stacked, perm))
    for r in range(w):
        np.testing.assert_allclose(
            out[r], np.full((2,), (r - 1) % w, np.float32)
        )


def test_mesh_barrier(mesh_group):
    mesh_group.barrier()  # must simply not hang


def test_init_collective_group_xla():
    import jax

    g = col.init_collective_group(
        8, 0, backend="xla", group_name="xla_t",
        devices=jax.devices("cpu")[:8],
    )
    assert col.is_group_initialized("xla_t")
    assert col.get_collective_group_size("xla_t") == 8
    col.destroy_collective_group("xla_t")
    assert not col.is_group_initialized("xla_t")


# ----------------------------------------------------------- objstore backend
@rmt.remote(max_concurrency=2)
class Rank(col.CollectiveGroupMixin):
    def __init__(self, rank, world):
        self.rank = rank
        self.world = world

    def do_allreduce(self, value):
        out = col.allreduce(np.full((4,), value, np.float32),
                            group_name="grp")
        return np.asarray(out)

    def do_broadcast(self, value):
        return col.broadcast(np.full((2,), value, np.float32), 0, "grp")

    def do_reducescatter(self, base):
        return col.reducescatter(
            np.arange(self.world * 2, dtype=np.float32) + base, "grp")

    def do_allreduce_q(self, value, precision):
        out = col.allreduce(np.full((4,), value, np.float32) + 0.1,
                            group_name="grp", precision=precision)
        return np.asarray(out)

    def do_sendrecv(self, value):
        if self.rank == 0:
            col.send(np.full((2,), value, np.float32), 1, "grp")
            return None
        return col.recv(0, "grp")

    def do_barrier(self):
        col.barrier("grp")
        return True


@pytest.fixture
def rank_actors(rmt_start_regular):
    world = 3
    actors = [Rank.remote(i, world) for i in range(world)]
    col.create_collective_group(
        actors, world, list(range(world)), backend="objstore",
        group_name="grp",
    )
    return actors


def test_objstore_allreduce(rank_actors):
    outs = rmt.get([a.do_allreduce.remote(i + 1)
                    for i, a in enumerate(rank_actors)], timeout=120)
    for out in outs:
        np.testing.assert_allclose(out, np.full((4,), 6.0, np.float32))


def test_objstore_broadcast(rank_actors):
    outs = rmt.get([a.do_broadcast.remote(i * 10)
                    for i, a in enumerate(rank_actors)], timeout=120)
    for out in outs:
        np.testing.assert_allclose(out, np.zeros(2, np.float32))


def test_objstore_sendrecv(rank_actors):
    r0, r1 = rank_actors[0], rank_actors[1]
    out = rmt.get([r0.do_sendrecv.remote(5.0), r1.do_sendrecv.remote(0.0)],
                  timeout=120)
    np.testing.assert_allclose(out[1], np.full((2,), 5.0, np.float32))


def test_objstore_barrier(rank_actors):
    assert all(rmt.get([a.do_barrier.remote() for a in rank_actors],
                       timeout=120))


def test_objstore_reducescatter(rank_actors):
    world = len(rank_actors)
    outs = rmt.get([a.do_reducescatter.remote(0.0) for a in rank_actors],
                   timeout=120)
    total = np.stack([np.arange(world * 2, dtype=np.float32)] * world).sum(0)
    chunks = np.array_split(total, world, axis=0)
    for rank, out in enumerate(outs):
        np.testing.assert_allclose(out, chunks[rank])


def test_mesh_allreduce_product_with_zeros_and_negatives(mesh_group):
    w = mesh_group.world_size
    stacked = np.stack(
        [np.array([i - 2.0, 1.0, 0.0], np.float32) for i in range(w)]
    )
    out = np.asarray(mesh_group.allreduce(stacked, col.ReduceOp.PRODUCT))
    expect = stacked.prod(axis=0)
    for r in range(w):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5)


# ------------------------------------------------------- quantized precision
def _quant_count(op: str, precision: str) -> float:
    return mdefs.collective_quantized_ops().get(
        tags={"op": op, "precision": precision})


@pytest.mark.parametrize("precision,tol", [("bf16", 2.0 ** -7),
                                           ("int8", 0.75 / 127.0)])
def test_mesh_allreduce_quantized_accuracy(mesh_group, precision, tol):
    """Sub-f32 allreduce: quantize-before-wire, f32 accumulation — the
    result must stay within the precision's error envelope (relative to
    the input absmax; elementwise relative error is meaningless near
    zero crossings) and bump the quantized-ops counter."""
    w = mesh_group.world_size
    rng = np.random.default_rng(21)
    stacked = rng.standard_normal((w, 512)).astype(np.float32)
    exact = stacked.sum(axis=0)
    absmax = float(np.abs(stacked).max())
    before = _quant_count("allreduce", precision)
    out = np.asarray(mesh_group.allreduce(stacked, precision=precision))
    assert _quant_count("allreduce", precision) == before + 1
    for r in range(w):
        np.testing.assert_allclose(out[r], exact, rtol=0,
                                   atol=w * absmax * tol)


def test_mesh_allreduce_f32_stays_bit_exact(mesh_group):
    w = mesh_group.world_size
    rng = np.random.default_rng(22)
    stacked = rng.standard_normal((w, 256)).astype(np.float32)
    before = _quant_count("allreduce", "f32")
    default = np.asarray(mesh_group.allreduce(stacked))
    explicit = np.asarray(mesh_group.allreduce(stacked, precision="f32"))
    assert np.array_equal(default, explicit)  # today's program, bit-exact
    assert _quant_count("allreduce", "f32") == before  # f32 never counted


def test_mesh_reducescatter_quantized(mesh_group):
    w = mesh_group.world_size
    rng = np.random.default_rng(23)
    stacked = rng.standard_normal((w, w * 4)).astype(np.float32)
    total = stacked.sum(axis=0)
    absmax = float(np.abs(stacked).max())
    out = np.asarray(mesh_group.reducescatter(stacked, precision="int8"))
    for r in range(w):
        np.testing.assert_allclose(out[r], total[r * 4:(r + 1) * 4],
                                   rtol=0, atol=w * absmax * 0.75 / 127.0)


def test_precision_precedence_chain():
    """per-call > group default > config.collective_precision > f32."""
    from ray_memory_management_tpu.config import (
        Config, global_config, set_global_config,
    )

    assert col.resolve_precision("int8", "bf16") == "int8"
    assert col.resolve_precision(None, "bf16") == "bf16"
    prev = global_config()
    try:
        set_global_config(Config(collective_precision="int8"))
        assert col.resolve_precision(None, None) == "int8"
    finally:
        set_global_config(prev)
    assert col.resolve_precision(None, None) == "f32"
    with pytest.raises(ValueError):
        col.resolve_precision("fp4", None)


def test_mesh_group_default_precision(mesh_group):
    """A group-level default applies when the call names none; a per-call
    precision= always wins over it."""
    import jax

    g = col.MeshCollectives(jax.devices("cpu")[:8], precision="bf16")
    w = g.world_size
    stacked = np.stack([np.full((4,), i + 0.5, np.float32)
                        for i in range(w)])
    expect = stacked.sum(axis=0)
    before = _quant_count("allreduce", "bf16")
    out = np.asarray(g.allreduce(stacked))
    assert _quant_count("allreduce", "bf16") == before + 1
    np.testing.assert_allclose(out[0], expect, rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(g.allreduce(stacked, precision="f32"))[0], expect)
    assert _quant_count("allreduce", "bf16") == before + 1  # f32 call won


def test_objstore_allreduce_quantized(rank_actors):
    """The objstore backend carries the QUANTIZED payload across the
    object plane; dequantize+accumulate stays f32 on every rank."""
    outs = rmt.get([a.do_allreduce_q.remote(float(i + 1), "int8")
                    for i, a in enumerate(rank_actors)], timeout=120)
    expect = np.full((4,), 1.1 + 2.1 + 3.1, np.float32)
    for out in outs:
        np.testing.assert_allclose(out, expect, rtol=0, atol=0.1)
