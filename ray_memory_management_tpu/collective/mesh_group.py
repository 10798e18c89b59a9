"""XLA-backend collectives: jitted mesh programs over ICI.

This is the TPU replacement for the reference's NCCL hot path
(nccl_collective_group.py:579-629 — comm/stream lookup then per-tensor NCCL
kernels). Here each collective is a jit-compiled ``shard_map`` program whose
body is a single XLA collective (lax.psum / all_gather / psum_scatter /
ppermute); XLA schedules it over the ICI links, which is strictly better than
hand-managed streams. Compiled programs are cached per (op, shape, dtype,
world) the way the reference caches comms per device set.

The "one tensor per rank" NCCL model maps to a stacked global array sharded on
its leading axis: rank i's tensor is shard i. On one host this runs over the
local chips; multi-host runs the same program under jax.distributed (the
driver's ``dryrun_multichip`` exercises it on a virtual mesh).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .types import ReduceOp

_AXIS = "ranks"


_INT8_BLOCK = 256  # must match core/codec.py's block-wise scale grain


def _reduce_fn(op: str):
    def _product(t):
        # gather-then-multiply: exact for zeros/negatives/ints (an exp-of-
        # psum-of-logs trick would NaN on non-positive inputs)
        return jnp.prod(lax.all_gather(t, _AXIS, axis=0), axis=0)

    return {
        ReduceOp.SUM: lambda t: lax.psum(t, _AXIS),
        ReduceOp.MAX: lambda t: lax.pmax(t, _AXIS),
        ReduceOp.MIN: lambda t: lax.pmin(t, _AXIS),
        ReduceOp.PRODUCT: _product,
    }[op]


_STACK_REDUCERS = {
    ReduceOp.SUM: jnp.sum,
    ReduceOp.MAX: jnp.max,
    ReduceOp.MIN: jnp.min,
    ReduceOp.PRODUCT: jnp.prod,
}


def _dequant_stack(t, precision: str):
    """Inside a shard_map body: quantize this rank's shard, all_gather
    the QUANTIZED payload (what actually crosses ICI — half the bytes
    for bf16, ~quarter for int8+scales), and return the dequantized
    [world, ...local] float32 stack. The caller reduces over axis 0 at
    full precision — quantize-before-wire, f32 accumulation (EQuARX).
    The jnp twin of core/codec.py's numpy kernels; the block-wise int8
    scale math matches bit-for-bit so both backends report the same
    accuracy envelope."""
    if precision == "bf16":
        g = lax.all_gather(t.astype(jnp.bfloat16), _AXIS, axis=0)
        return g.astype(jnp.float32)
    # int8, block-wise absmax scales (shapes are static under jit, so
    # the padding below is compile-time)
    shape = t.shape
    flat = t.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % _INT8_BLOCK
    padded = jnp.pad(flat, (0, pad)) if pad else flat
    blocks = padded.reshape(-1, _INT8_BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(blocks / safe), -127, 127).astype(jnp.int8)
    gq = lax.all_gather(q, _AXIS, axis=0)        # [world, nblk, B] int8
    gs = lax.all_gather(scale, _AXIS, axis=0)    # [world, nblk, 1] f32
    deq = (gq.astype(jnp.float32) * gs).reshape(gq.shape[0], -1)
    return deq[:, :flat.size].reshape((gq.shape[0],) + shape)


def _count_quantized(op: str, precision: str) -> None:
    from ..core.codec import count_quantized_op

    count_quantized_op(op, precision)


class MeshCollectives:
    """Collectives over a 1-D mesh of devices (one 'rank' per device)."""

    def __init__(self, devices: Optional[list] = None,
                 precision: Optional[str] = None):
        devices = devices if devices is not None else jax.devices()
        self.mesh = Mesh(devices, (_AXIS,))
        self.world_size = len(devices)
        self._sharding = NamedSharding(self.mesh, P(_AXIS))
        # group-level default precision for the reduction collectives;
        # None defers to config.collective_precision, then "f32". A
        # per-call precision= always wins.
        self.precision = precision
        # per-instance program cache (an lru_cache on methods would pin the
        # instance and its compiled executables in a class-level cache
        # forever); dies with the group
        self._programs = {}

    def _cached(self, key, build):
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = build()
        return fn

    # -- helpers --------------------------------------------------------------
    def shard_ranks(self, stacked):
        """Place a [world, ...] array so shard i lives on device i."""
        return jax.device_put(stacked, self._sharding)

    def _smap(self, fn, out_spec=P(_AXIS)):
        # check_vma off: collective bodies intentionally produce values
        # whose replication XLA cannot infer statically (e.g. all_gather
        # then replicated output)
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=P(_AXIS), out_specs=out_spec,
            check_vma=False)

    def _precision(self, precision):
        from .types import resolve_precision

        return resolve_precision(precision, self.precision)

    # -- collectives (each returns a jitted, cached program) ------------------
    def _allreduce_fn(self, op: str, precision: str = "f32"):
        if precision == "f32":
            # today's program, byte for byte — f32 stays bit-exact
            return self._cached(
                ("allreduce", op),
                lambda: jax.jit(self._smap(_reduce_fn(op))),
            )

        def build():
            red = _STACK_REDUCERS[op]

            def body(t):
                return red(_dequant_stack(t, precision), axis=0)

            return jax.jit(self._smap(body))

        return self._cached(("allreduce", op, precision), build)

    def allreduce(self, stacked, op: str = ReduceOp.SUM,
                  precision: Optional[str] = None):
        """[world, ...] -> [world, ...] with every rank-slice = reduction.

        ``precision``: "f32" (bit-exact default) | "bf16" | "int8" —
        sub-f32 runs quantize-on-wire with f32 accumulation; result
        dtype is float32 for quantized runs."""
        p = self._precision(precision)
        if p != "f32":
            _count_quantized("allreduce", p)
        return self._allreduce_fn(op, p)(self.shard_ranks(stacked))

    def _reducescatter_fn(self, op: str, precision: str = "f32"):
        key = (("reducescatter", op) if precision == "f32"
               else ("reducescatter", op, precision))
        return self._cached(
            key, lambda: self._build_reducescatter(op, precision))

    def _build_reducescatter(self, op: str, precision: str = "f32"):
        if precision != "f32":
            red = _STACK_REDUCERS[op]

            def qbody(t):
                full = red(_dequant_stack(t, precision), axis=0)
                rank = lax.axis_index(_AXIS)
                n = t.shape[1] // self.world_size
                return lax.dynamic_slice_in_dim(full, rank * n, n, axis=1)

            return jax.jit(self._smap(qbody))
        if op != ReduceOp.SUM:
            red = _reduce_fn(op)

            def body(t):
                full = red(t)  # replicate reduction, then slice
                rank = lax.axis_index(_AXIS)
                n = t.shape[1] // self.world_size
                return lax.dynamic_slice_in_dim(full, rank * n, n, axis=1)

            return jax.jit(self._smap(body))
        return jax.jit(self._smap(
            lambda t: lax.psum_scatter(t, _AXIS, scatter_dimension=1,
                                       tiled=True)
        ))

    def reducescatter(self, stacked, op: str = ReduceOp.SUM,
                      precision: Optional[str] = None):
        """[world, world*n] -> rank i holds sum-slice i ([world, n] global)."""
        p = self._precision(precision)
        if p != "f32":
            _count_quantized("reducescatter", p)
        return self._reducescatter_fn(op, p)(self.shard_ranks(stacked))

    def _allgather_fn(self):
        # out_spec P(): every rank computes the identical full stack, so the
        # global result is the replicated [world, ...] gather
        return self._cached(("allgather",), lambda: jax.jit(self._smap(
            lambda t: lax.all_gather(t[0], _AXIS, axis=0), out_spec=P()
        )))

    def allgather(self, stacked):
        """[world, ...] -> every rank holds the full stack (returned global
        shape [world, world, ...] collapses to one [world, ...] copy)."""
        out = self._allgather_fn()(self.shard_ranks(stacked))
        return out

    def _broadcast_fn(self, root: int):
        return self._cached(("broadcast", root),
                            lambda: self._build_broadcast(root))

    def _build_broadcast(self, root: int):
        # masked psum: every rank contributes zeros except the root, so the
        # reduction IS the root's slice. Moves O(bytes) per ICI link (the
        # ring allreduce schedule), not the O(world * bytes) of gathering
        # the whole stack to every rank. (jax's ppermute cannot express a
        # one-to-all fanout — sources must be unique — and a log-round tree
        # would be latency-optimal but more program for no bandwidth win.)
        def body(t):
            rank = lax.axis_index(_AXIS)
            contrib = jnp.where(rank == root, t, jnp.zeros_like(t))
            return lax.psum(contrib, _AXIS)

        return jax.jit(self._smap(body))

    def broadcast(self, stacked, root: int = 0):
        """Every rank-slice of the result equals root's input slice."""
        return self._broadcast_fn(root)(self.shard_ranks(stacked))

    def _ppermute_fn(self, perm: tuple):
        return self._cached(("ppermute", perm),
                            lambda: self._build_ppermute(perm))

    def _build_ppermute(self, perm: tuple):
        def body(t):
            return lax.ppermute(t, _AXIS, perm=list(perm))

        return jax.jit(self._smap(body))

    def ppermute(self, stacked, perm):
        """Neighbor exchange over ICI (the ring-attention building block)."""
        return self._ppermute_fn(tuple(map(tuple, perm)))(
            self.shard_ranks(stacked)
        )

    def send_recv(self, stacked, src: int, dst: int):
        """P2P as a degenerate collective-permute (reference send/recv,
        collective.py:531,594 — NCCL P2P maps to ppermute on ICI)."""
        return self.ppermute(stacked, [(src, dst)])

    def _reduce_rooted_fn(self, root: int, op: str,
                          precision: str = "f32"):
        def build():
            if precision != "f32":
                sred = _STACK_REDUCERS[op]

                def qbody(t):
                    out = sred(_dequant_stack(t, precision), axis=0)
                    rank = lax.axis_index(_AXIS)
                    return jnp.where(rank == root, out,
                                     t.astype(jnp.float32))

                return jax.jit(self._smap(qbody))
            red = _reduce_fn(op)

            def body(t):
                out = red(t)
                rank = lax.axis_index(_AXIS)
                # NCCL reduce semantics: only root's output is defined;
                # other ranks keep their input slice (cheap, and closer to
                # "unmodified buffer" than fabricated zeros)
                return jnp.where(rank == root, out, t)

            return jax.jit(self._smap(body))

        key = (("reduce", root, op) if precision == "f32"
               else ("reduce", root, op, precision))
        return self._cached(key, build)

    def reduce(self, stacked, root_rank: int = 0, op: str = ReduceOp.SUM,
               precision: Optional[str] = None):
        """Rooted reduce: root's slice of the result holds the reduction;
        other slices pass through unchanged. (On ICI the wire cost matches
        allreduce — the ring crosses every link either way — but the
        SEMANTICS are rooted, as in the reference's collective.reduce,
        util/collective/collective.py:311.)"""
        p = self._precision(precision)
        if p != "f32":
            _count_quantized("reduce", p)
        return self._reduce_rooted_fn(root_rank, op, p)(
            self.shard_ranks(stacked))

    def barrier(self):
        jax.block_until_ready(self.allreduce(
            jnp.zeros((self.world_size, 1), jnp.float32)
        ))
