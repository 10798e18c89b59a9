"""The dense decoder's prefill of one row (``models/gpt.py::prefill_row``:
the prompt's attention through the flash forward kernel, each layer's K and V
out of the layer loop, the head at the prompt's last token only) against the
path it replaced: ``forward_with_cache`` on a fresh row cache, which
``generate()`` keeps. CPU, toy GQA widths, float32: what is checked is the
arithmetic and the dispatch, not a speed.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import gpt, latent_moe

# GQA: 4 query heads on 2 K/V heads
CFG = gpt.TransformerConfig(vocab_size=512, d_model=64, n_layers=3,
                            n_heads=4, n_kv_heads=2, max_seq=128,
                            dtype=jnp.float32)
# (bucket, true_len): a prompt of one token, a full bucket, two part-filled
CASES = [(16, 1), (16, 16), (32, 23), (64, 40)]


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(jax.random.PRNGKey(30), CFG)


def old_prefill_row(params, tokens, cfg, n_positions, true_len):
    row_cache = gpt.init_kv_cache(cfg, 1, n_positions)
    logits, row_cache = gpt.forward_with_cache(
        params, tokens, row_cache, 0, cfg)
    return logits[0, true_len - 1], {k: c[:, 0] for k, c in row_cache.items()}


@pytest.mark.parametrize("attention", ["auto", "flash-interpret"])
@pytest.mark.parametrize("room", [0, 16], ids=["exact", "roomier"])
@pytest.mark.parametrize("bucket,true_len", CASES)
def test_prefill_row_against_the_old_path(params, bucket, true_len, room,
                                          attention):
    """Logits at ``true_len - 1`` and the cache rows of the prompt's
    positions agree to float32 rounding, with ``n_positions`` equal to the
    bucket and larger; on the reference path and with the kernel itself
    under the interpreter."""
    cfg = dataclasses.replace(CFG, attention=attention)
    assert gpt.prefill_takes_kernel(cfg, bucket) \
        == (attention == "flash-interpret")
    n_positions = bucket + room
    tokens = jax.random.randint(jax.random.PRNGKey(bucket + true_len),
                                (1, bucket), 0, cfg.vocab_size)
    # true_len is traced, as in the engine's program
    logits, row = jax.jit(
        lambda p, t, n: gpt.prefill_row(p, t, cfg, n_positions, n))(
        params, tokens, jnp.int32(true_len))
    want, want_row = old_prefill_row(params, tokens, cfg, n_positions,
                                     true_len)
    assert logits.shape == (cfg.vocab_size,) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, rtol=2e-5, atol=2e-5)
    for name in ("k", "v"):
        assert row[name].shape == (cfg.n_layers, cfg.kv_heads, n_positions,
                                   cfg.head_dim)
        np.testing.assert_allclose(row[name][:, :, :true_len],
                                   want_row[name][:, :, :true_len],
                                   rtol=2e-5, atol=2e-5)
        # past the bucket the row is zero: pages the prompt does not reach
        assert not np.asarray(row[name][:, :, bucket:]).any()


def test_the_kernel_is_taken_on_a_tpu_for_lengths_it_can_tile(monkeypatch):
    """Off the TPU the predicate is False; on one (steered here: the
    dispatch asks where default computation lands) it follows
    ``_pick_block``, and ``attention="ref"`` keeps the reference by name."""
    fa = importlib.import_module(
        "ray_memory_management_tpu.ops.flash_attention")
    buckets = (256, 512, 768, 3584)
    assert not any(gpt.prefill_takes_kernel(CFG, n) for n in buckets)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert all(gpt.prefill_takes_kernel(CFG, n) for n in buckets)
    for refused in (1000, 520):  # blocks of 500 and 260 rows
        with pytest.raises(ValueError):
            fa._pick_block(refused, fa.DEFAULT_BLOCK_Q, False)
        assert not gpt.prefill_takes_kernel(CFG, refused)
    assert gpt.prefill_takes_kernel(CFG, 40)  # one block spans it
    ref = dataclasses.replace(CFG, attention="ref")
    assert not gpt.prefill_takes_kernel(ref, 512)


def test_the_latent_model_answers_for_its_own_prefill(monkeypatch):
    """``models/latent_moe.py`` offers the same predicate: the condition
    under which its ``_attend_plain`` reaches the kernel."""
    fa = importlib.import_module(
        "ray_memory_management_tpu.ops.flash_attention")
    even = latent_moe.LatentMoEConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, d_ff=128, moe_d_ff=32, n_routed_experts=8,
        n_shared_experts=1, experts_per_tok=2, routed_scaling_factor=1.8)
    uneven = dataclasses.replace(even, v_head_dim=8)
    assert not latent_moe.prefill_takes_kernel(even, 512)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    assert latent_moe.prefill_takes_kernel(even, 512)
    assert not latent_moe.prefill_takes_kernel(uneven, 512)
