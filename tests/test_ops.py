"""Ops tests: flash attention kernel (interpret mode) and sequence-parallel
attention vs the jnp reference, all on CPU devices for exact numerics."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ray_memory_management_tpu.ops import (
    flash_attention,
    reference_attention,
    ring_attention,
    ulysses_attention,
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    B, H, S, D = 2, 4, 128, 32
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def cpu_mesh():
    devices = jax.devices("cpu")
    assert len(devices) >= 8
    return Mesh(np.array(devices[:8]), ("sp",))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(qkv, causal):
    q, k, v = qkv
    ref = reference_attention(q, k, v, causal=causal)
    fa = flash_attention(q, k, v, causal=causal, use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(fa), np.asarray(ref), atol=2e-5)


def test_flash_multi_block(qkv):
    # force blocking: block sizes smaller than S so K/V stream through
    # multiple grid steps and the online-softmax accumulators carry across
    q, k, v = qkv
    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, use_pallas="interpret",
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_multi_block_backward(qkv, causal):
    # blockwise backward kernels (dq + dkv) vs jnp autodiff across blocks
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                use_pallas="interpret",
                                block_q=32, block_k=32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) ** 2).sum()

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_flash_gradient(qkv):
    q, k, v = qkv

    def loss_flash(q):
        return flash_attention(q, k, v, use_pallas="interpret").sum()

    def loss_ref(q):
        return reference_attention(q, k, v).sum()

    g = jax.grad(loss_flash)(q)
    gref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=2e-4)


def test_flash_prefix_attention():
    # Skv > S (off != 0): decode/prefix-style causal attention exercises the
    # off-dependent mask and tile-skip predicates in fwd AND bwd kernels
    rng = np.random.default_rng(2)
    B, S, Skv, D = 3, 64, 128, 32
    q = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Skv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Skv, D)), jnp.float32)

    ref = reference_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, use_pallas="interpret",
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                use_pallas="interpret",
                                block_q=32, block_k=32) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


# (S, Skv, D, block_q, block_k, causal, blocks of the smaller block's streamed
# operand a grid step holds; None: what the kernel picks, here the whole head). With one
# block a step every tile is a grid step of its own: wholly below the
# diagonal (no mask built), on it (masked), wholly above it (skipped, its
# index clamped to the block already held); with the head resident the same
# three are slices of the loop inside one step. All three kernels see each.
BF16_CASES = [
    (128, 128, 64, 32, 32, True, None),
    (128, 128, 64, 32, 32, True, 1),
    (128, 128, 64, 32, 32, True, 2),
    (128, 128, 64, 32, 32, False, None),
    (64, 128, 64, 32, 32, True, 1),       # a prefix: off = 64
    (56, 128, 64, 8, 16, True, 2),        # off = 72, no multiple of a block
    (128, 128, 128, 64, 32, True, None),  # block_q > block_k
    (128, 128, 64, 64, 32, True, 1),
    (48, 104, 64, 16, 8, True, 1),        # off = 56, half a block_q over
    (128, 128, 128, 32, 64, True, 1),     # block_q < block_k
    (64, 64, 256, 64, 64, True, None),    # a length of one tile
    (64, 64, 256, 64, 64, False, None),
    (40, 104, 64, 8, 8, False, 1),
    (128, 128, 256, 32, 32, True, 2),
]


@pytest.mark.parametrize("S,Skv,D,block_q,block_k,causal,held", BF16_CASES,
                         ids=lambda v: str(v))
def test_flash_bf16_against_the_references_own_error(
        monkeypatch, S, Skv, D, block_q, block_k, causal, held):
    """bf16 operands, as the cells send them: output and all three gradients
    against the float32 reference on the same values. The limit is what the
    plain reference loses when it computes in bf16 itself (its widest gap
    twice, its rms gap by a quarter more), measured here, not a constant."""
    import importlib

    fa = importlib.import_module(
        "ray_memory_management_tpu.ops.flash_attention")
    if held is not None:
        monkeypatch.setattr(
            fa, "_STREAM_BYTES", held * 2 * min(block_q, block_k) * D * 2)
    rng = np.random.default_rng(S + Skv + D + block_q)
    q, k, v, w = (jnp.asarray(rng.normal(size=(2, n, D)), jnp.bfloat16)
                  for n in (S, Skv, Skv, S))

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return [np.asarray(x, np.float32)
                for x in (out,) + vjp(w.astype(out.dtype))]

    truth = out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    plain = out_and_grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal), q, k, v)
    kernel = out_and_grads(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, use_pallas="interpret", block_q=block_q,
            block_k=block_k), q, k, v)
    for name, t, p, got in zip(("out", "dq", "dk", "dv"), truth, plain,
                               kernel):
        assert got.shape == t.shape
        assert np.abs(got - t).max() <= 2.0 * np.abs(p - t).max(), name
        rms = lambda x: float(np.sqrt(np.mean(x ** 2)))  # noqa: E731
        assert rms(got - t) <= 1.25 * rms(p - t), name


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention(qkv, cpu_mesh, causal):
    q, k, v = qkv
    ref = reference_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, cpu_mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_attention(qkv):
    # ulysses shards heads: the axis size must divide H (=4)
    mesh4 = Mesh(np.array(jax.devices("cpu")[:4]), ("sp",))
    q, k, v = qkv
    ref = reference_attention(q, k, v, causal=True)
    out = ulysses_attention(q, k, v, mesh4, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_long_sequence(cpu_mesh):
    # sequence 8x longer than a single shard; cross-shard causal masking
    rng = np.random.default_rng(1)
    B, H, S, D = 1, 2, 512, 16
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    ref = reference_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, cpu_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
