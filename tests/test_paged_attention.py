"""Decode attention over a paged KV pool (ops/paged_attention.py): the Pallas
kernel under the interpreter against the plain block-table reading and
against dense masked attention.

CPU: what is checked is the reading (which pages, which positions, which
heads share a block), not a speed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.ops.paged_attention import (
    kernel_takes, paged_attention, paged_attention_reference,
)

L, HKV, PAGE, DH, WIDTH = 2, 2, 8, 16, 4
POOL_PAGES = 12   # reservable pages; one more is the sink
# nothing, one position, exactly a page, a page and one, the table's width
LENGTHS = [0, 1, PAGE, PAGE + 1, WIDTH * PAGE]


def _case(groups, seed=0, dtype=jnp.bfloat16):
    """Rows of LENGTHS on shuffled, non-contiguous page ids; every table
    entry a row does not own points at the sink, and the sink is NaN."""
    rng = np.random.default_rng(seed)
    B, H, sink = len(LENGTHS), HKV * groups, POOL_PAGES

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    k = arr(L, HKV, POOL_PAGES + 1, PAGE, DH).at[:, :, sink].set(jnp.nan)
    v = arr(L, HKV, POOL_PAGES + 1, PAGE, DH).at[:, :, sink].set(jnp.nan)
    ids = iter(rng.permutation(POOL_PAGES))
    table = np.full((B, WIDTH), sink, np.int32)
    for b, n in enumerate(LENGTHS):
        for i in range(-(-n // PAGE)):
            table[b, i] = next(ids)
    return dict(q=arr(B, H, DH), k=k, v=v, k_cur=arr(B, HKV, DH),
                v_cur=arr(B, HKV, DH), table=jnp.asarray(table),
                lengths=jnp.asarray(LENGTHS, jnp.int32))


def _dense(c, layer, with_cur):
    """Dense masked attention, row by row in float32, from the pages each
    row owns and nothing else."""
    q, k, v = (np.asarray(c[n], np.float32) for n in ("q", "k", "v"))
    B, H, _ = q.shape
    G = H // HKV
    out = np.zeros((B, H, DH), np.float32)
    for b, n in enumerate(LENGTHS):
        pages = np.asarray(c["table"])[b, :-(-n // PAGE)]
        for h in range(H):
            kv = h // G
            keys = k[layer, kv, pages].reshape(-1, DH)[:n]
            vals = v[layer, kv, pages].reshape(-1, DH)[:n]
            if with_cur:
                keys = np.vstack([keys, np.asarray(
                    c["k_cur"], np.float32)[b, kv][None]])
                vals = np.vstack([vals, np.asarray(
                    c["v_cur"], np.float32)[b, kv][None]])
            if not len(keys):
                continue  # nothing to attend over reads 0
            s = keys @ q[b, h] * DH ** -0.5
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ vals
    return out


def _run(c, mode, layer, with_cur):
    cur = dict(k_cur=c["k_cur"], v_cur=c["v_cur"]) if with_cur else {}
    return np.asarray(paged_attention(
        c["q"], c["k"], c["v"], c["lengths"], c["table"], layer=layer,
        use_pallas=mode, **cur), np.float32)


@pytest.mark.parametrize("with_cur", [False, True], ids=["pages", "cur"])
@pytest.mark.parametrize("groups", [1, 4], ids=["mha", "gqa4"])
@pytest.mark.parametrize("mode", ["interpret", "off"])
def test_reads_each_rows_pages_and_nothing_else(mode, groups, with_cur):
    c = _case(groups)
    want = _dense(c, 1, with_cur)
    got = _run(c, mode, 1, with_cur)
    # the sink, every unowned entry's target, is NaN: none of it arrives
    assert np.isfinite(got).all()
    # bf16 probabilities and outputs against a float32 oracle
    np.testing.assert_allclose(got, want, atol=0.03, rtol=0.03)
    if not with_cur:
        assert not got[0].any()  # the row of length 0


@pytest.mark.parametrize("with_cur", [False, True], ids=["pages", "cur"])
@pytest.mark.parametrize("groups", [1, 4], ids=["mha", "gqa4"])
def test_kernel_agrees_with_the_plain_reading(groups, with_cur):
    c = _case(groups, seed=1)
    for layer in range(L):
        np.testing.assert_allclose(
            _run(c, "interpret", layer, with_cur),
            _run(c, "off", layer, with_cur), atol=0.02, rtol=0.02)


def test_one_layers_pages_are_taken_as_they_are():
    """[Hkv, P, page, Dh] is a pool of one layer."""
    c = _case(4, seed=2)
    whole = paged_attention(c["q"], c["k"], c["v"], c["lengths"],
                            c["table"], layer=1, use_pallas="off")
    one = paged_attention(c["q"], c["k"][1], c["v"][1], c["lengths"],
                          c["table"], use_pallas="off")
    np.testing.assert_array_equal(np.asarray(whole, np.float32),
                                  np.asarray(one, np.float32))


def test_the_layer_may_be_traced():
    c = _case(4, seed=3)

    @jax.jit
    def both(layer):
        return paged_attention(c["q"], c["k"], c["v"], c["lengths"],
                               c["table"], layer=layer,
                               use_pallas="interpret")

    for layer in range(L):
        np.testing.assert_allclose(
            np.asarray(both(jnp.int32(layer)), np.float32),
            _run(c, "interpret", layer, False), atol=1e-6)


@pytest.mark.parametrize("dh,page,dtype,takes", [
    (128, 256, jnp.bfloat16, True), (128, 16, jnp.bfloat16, True),
    (128, 8, jnp.float32, True), (128, 8, jnp.bfloat16, False),
    (16, 16, jnp.bfloat16, False), (64, 256, jnp.bfloat16, False)])
def test_the_shape_decides_which_path_a_tpu_takes(dh, page, dtype, takes):
    q = jax.ShapeDtypeStruct((4, 8, dh), dtype)
    k = jax.ShapeDtypeStruct((1, 2, 5, page, dh), dtype)
    assert kernel_takes(q, k) is takes


def test_auto_takes_the_plain_reading_off_the_tpu():
    c = _case(4, seed=4)
    auto = paged_attention(c["q"], c["k"], c["v"], c["lengths"], c["table"])
    plain = paged_attention_reference(c["q"], c["k"], c["v"], c["lengths"],
                                      c["table"])
    np.testing.assert_array_equal(np.asarray(auto, np.float32),
                                  np.asarray(plain, np.float32))
