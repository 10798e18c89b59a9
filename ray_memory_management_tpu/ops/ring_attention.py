"""Ring attention: sequence/context parallelism over the ICI ring.

Net-new versus the reference (SURVEY.md §5 calls long-context support absent
there). Q, K, V are sharded along the sequence axis of a mesh; each step every
device attends its local Q block against the K/V chunk currently resident,
then rotates K/V one hop around the ring with ``lax.ppermute`` — after
``ring_size`` steps every Q block has seen every K/V chunk. Softmax is merged
online across steps (the same running max/denominator algebra as flash
attention), so the full attention matrix never materializes.

Causal masking works on global positions: chunk j's key offset is derived
from the originating device index, so masks stay exact as chunks rotate.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_NEG_INF = -1e30


def _chunk_attend(q, k, v, q_offset, k_offset, causal, scale, m, l, acc):
    """One flash-style accumulation step of q against one K/V chunk.

    q: [B, H, Sq, D]; k, v: [B, H, Sk, D]; m, l: [B, H, Sq, 1];
    acc: [B, H, Sq, D] fp32.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        Sq, Sk = q.shape[-2], k.shape[-2]
        rows = q_offset + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = k_offset + lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = True, scale: Optional[float] = None):
    """Attention over sequence-sharded [B, H, S, D] arrays.

    S is the GLOBAL sequence length; inputs are (or will be placed)
    sequence-sharded over ``axis``. Communication is one K/V-chunk ppermute
    per step — bandwidth-optimal on an ICI ring.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    ring = mesh.shape[axis]

    def body(q_loc, k_loc, v_loc):
        # q_loc/k_loc/v_loc: [B, H, S/ring, D] local shards
        idx = lax.axis_index(axis)
        S_loc = q_loc.shape[-2]
        q_offset = idx * S_loc
        B, H, _, D = q_loc.shape
        m = jnp.full((B, H, S_loc, 1), _NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, S_loc, 1), jnp.float32)
        acc = jnp.zeros((B, H, S_loc, D), jnp.float32)
        perm = [(i, (i + 1) % ring) for i in range(ring)]

        def step(t, carry):
            m, l, acc, k_cur, v_cur = carry
            # the chunk now resident originated at device (idx - t) mod ring
            src = (idx - t) % ring
            k_offset = src * S_loc
            m, l, acc = _chunk_attend(
                q_loc, k_cur, v_cur, q_offset, k_offset, causal, scale,
                m, l, acc,
            )
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc, _, _ = lax.fori_loop(
            0, ring, step, (m, l, acc, k_loc, v_loc))
        return (acc / jnp.maximum(l, 1e-30)).astype(q_loc.dtype)

    spec = P(None, None, axis, None)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    return mapped(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                      causal: bool = True, scale: Optional[float] = None):
    """Ulysses/DeepSpeed-style sequence parallelism: all-to-all re-shards
    from sequence-sharded to head-sharded, runs full-sequence attention
    locally per head group, and all-to-alls back. Complements ring attention:
    better when heads >> ring size and sequence chunks are small.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    ring = mesh.shape[axis]
    H = q.shape[1]
    if H % ring:
        raise ValueError(f"heads {H} must divide over axis size {ring}")

    def body(q_loc, k_loc, v_loc):
        # in: [B, H, S/ring, D] -> all-to-all -> [B, H/ring, S, D]
        def a2a(x, concat, split):
            return lax.all_to_all(x, axis, split_axis=split,
                                  concat_axis=concat, tiled=True)

        q_h = a2a(q_loc, 2, 1)  # gather seq, scatter heads
        k_h = a2a(k_loc, 2, 1)
        v_h = a2a(v_loc, 2, 1)
        from .flash_attention import reference_attention

        o_h = reference_attention(q_h, k_h, v_h, causal, scale)
        return a2a(o_h, 1, 2)  # back to sequence-sharded

    spec = P(None, None, axis, None)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    return mapped(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
