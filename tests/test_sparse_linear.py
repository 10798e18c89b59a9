"""MiniCPM-SALA's pieces on the serve path at toy widths (ops/ssm.py with a
group a head, the three block kernels of ops/paged_attention.py,
serve/kv_cache.py's strided array, models/sparse_linear.py's pooled keys).

CPU: what is checked is the arithmetic and the bookkeeping, not a speed. The
comparison of the whole model with the plain reference is
tests/chipbench_tests/test_sparse_linear_cell.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import serving_model, sparse_linear
from ray_memory_management_tpu.ops import paged_attention as pa
from ray_memory_management_tpu.ops import ssm
from ray_memory_management_tpu.serve.kv_cache import KVPagePool, _entry

CFG = sparse_linear.SparseLinearConfig(
    vocab_size=256, d_model=64, d_ff=96,
    mixer_types=("minicpm4", "lightning-attn", "minicpm4"), n_heads=4,
    kv_heads=2, head_dim=16, lin_heads=4, lin_head_dim=16, scale_emb=12.0,
    scale_depth=1.4, depth_layers=32, dim_model_base=16, block_size=4,
    top_k=6, kernel_size=4, kernel_stride=2, init_blocks=1, window_size=8,
    dense_len=24, max_seq=128, dtype=jnp.float32, param_dtype=jnp.float32)


# ------------------------------------------- the recurrence, a group a head
def _lightning(T, H=16, N=128, seed=0):
    """x (= v), dt = 1, A = -slope, B (= k), C (= q), D = 0 of a row of T
    positions with a key a head."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (T, H, N)), jnp.ones((T, H)),
            -(2.0 ** (-8.0 * (jnp.arange(H) + 1) / H)),
            jax.random.normal(k[1], (T, H, N)),
            jax.random.normal(k[2], (T, H, N)), jnp.zeros(H))


@pytest.mark.parametrize("use", ["off", "interpret"])
def test_the_chunked_scan_with_a_group_a_head_is_the_recurrence(use):
    """``ssd_scan`` with ``G`` = ``H`` (Lightning attention: a key a head),
    from a state and stopping at a true length, equals ``ssd_sequential``;
    the kernel takes eight heads a grid step, each its own B and C."""
    a = _lightning(512)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (16, 128, 128))
    with jax.default_matmul_precision("highest"):
        want_y, want_h = ssm.ssd_sequential(*a, true_len=300, h0=h0)
        y, h = ssm.ssd_scan(*a, true_len=300, h0=h0, use_pallas=use)
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y - want_y)[:300].max()) < 1e-5 * scale
    assert float(jnp.abs(h - want_h).max()) < 1e-5 * float(
        jnp.abs(want_h).max())


def test_the_decode_update_with_a_group_a_head_under_the_interpreter():
    """The decode kernel with a group a head moves blocks of 16 heads, each
    with its own B and C (as rows across the lanes), and equals its plain
    form: live slots updated, idle ones untouched, counted as fetched."""
    x, dt, A, B, C, D = _lightning(5)
    state = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 16, 128, 128))
    live = jnp.array([True, False, True, True, False])
    want = ssm.ssm_decode_update(state, x, dt, A, B, C, D, live, layer=1,
                                 use_pallas="off")
    got = ssm.ssm_decode_update(state, x, dt, A, B, C, D, live, layer=1,
                                use_pallas="interpret",
                                name="lightning_decode_update")
    assert float(jnp.abs(got[0] - want[0]).max()) < 1e-3
    assert float(jnp.abs(got[1] - want[1]).max()) < 1e-5
    assert int(got[2]) == 3
    assert bool((got[1][:, 1] == state[:, 1]).all())


@pytest.mark.parametrize("shape,update,scan", [
    ((32, 32, 128, 128), (16, 16), (8, 8)),  # a key a head: across groups
    ((32, 2, 256, 128), (8, 1), (8, 1)),     # Falcon-H1: as before
    ((128, 8, 128, 64), None, (16, 1)),      # Nemotron-H's scan: as before
    ((64, 8, 128, 128), (8, 1), None)])      # its update (heads packed)
def test_each_kernel_blocks_each_shape_as_it_did(shape, update, scan):
    """(heads, groups, state, head size) -> (rows a grid step, groups among
    them) of the decode kernel and of the scan: only a group a head spans
    groups; the two older shapes keep their blocking."""
    if update:
        assert ssm._blocks_of(*shape) == update
    if scan:
        assert ssm._scan_blocks(*shape) == scan


# -------------------------------------------------------- the block kernels
def _pools(seed=0, L=2, hkv=2, P=10, page=128, D=128, stride=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (L, hkv, P, page, D)),
            jax.random.normal(k[1], (L, hkv, P, page, D)),
            jax.random.normal(k[2], (L, hkv, P, page // stride, D)))


TABLE = jnp.array([[3, 1, 7, 2], [0, 5, 9, 4], [6, 8, 2, 1]], jnp.int32)


@pytest.mark.parametrize("n,first", [(1, (300, 50, 511)), (64, (0, 128, 448))],
                         ids=["rows", "chunk"])
def test_the_block_kernels_under_the_interpreter_equal_the_plain_forms(
        n, first):
    """Scores (softmax over the complete windows, summed over a group, a
    block's largest), the threshold selection, and the attention over the
    chosen blocks (a decode row fetching its blocks alone, a chunk walking
    key tiles): the kernels under the interpreter against the plain forms,
    at shapes they tile (heads of 128, groups of 16)."""
    kp, vp, pooled = _pools()
    pos = jnp.array(first, jnp.int32)[:, None] + jnp.arange(n)[None]
    q = jax.random.normal(jax.random.PRNGKey(3), (3, n, 2, 16, 128))
    kw = dict(layer=1, stride=4, window=8, block=16, scale=0.1)
    with jax.default_matmul_precision("highest"):
        want = pa.block_scores(q, pooled, TABLE, pos, use_pallas="off", **kw)
        got = pa.block_scores(q, pooled, TABLE, pos, use_pallas="interpret",
                              **kw)
    assert float(jnp.abs(got - want).max()) < 1e-5
    sel = dict(top_k=8, block=16, init_blocks=1, local=64, dense_len=100)
    chosen = pa.block_select(want, pos, use_pallas="off", **sel)
    assert bool((pa.block_select(want, pos, use_pallas="interpret", **sel)
                 == chosen).all())
    live = jnp.array([True, False, True]) if n == 1 else None
    akw = dict(layer=1, block=16, scale=0.1, most=8, live=live)
    with jax.default_matmul_precision("highest"):
        want = pa.block_sparse_attention(q, kp, vp, TABLE, chosen, pos,
                                         use_pallas="off", **akw)
        got = pa.block_sparse_attention(q, kp, vp, TABLE, chosen, pos,
                                        use_pallas="interpret", **akw)
    assert float(jnp.abs(got - want).max()) < 1e-5
    if n == 1:   # a row that is not live reads nothing
        assert float(jnp.abs(got[1]).max()) == 0.0


@pytest.mark.parametrize("use", ["off", "interpret"])
def test_the_forced_blocks_are_always_chosen_and_ties_go_low_twice(use):
    """Block 0 and the blocks of the last ``local`` positions are chosen
    whatever they score; the rest by score up to ``top_k``, **ties to the
    lower block**, the same way twice; none that starts after the query;
    every block up to the query before ``dense_len``."""
    NB, block = 128, 16
    t = jnp.array([[1500], [1000], [50]], jnp.int32)     # [G, n]
    scores = jnp.zeros((3, 1, 2, NB), jnp.float32)
    # the forced blocks score lowest; a tie of ten at 0.5 beyond them
    scores = scores.at[:, :, :, 20:30].set(0.5).at[:, :, :, 40].set(0.9)
    kw = dict(top_k=8, block=block, init_blocks=1, local=48, dense_len=100)
    a = pa.block_select(scores, t, use_pallas=use, **kw)
    b = pa.block_select(scores, t, use_pallas=use, **kw)
    assert bool((a == b).all())
    row = np.asarray(a[0, 0, 0])
    forced = {0} | set(range((1500 - 48 + 1) // block, 1500 // block + 1))
    assert forced <= set(np.flatnonzero(row))
    assert row.sum() == 8
    # five forced (block 0, blocks 90-93); the rest: block 40, then the
    # lowest two of the tie
    assert len(forced) == 5
    assert set(np.flatnonzero(row)) - forced == {40, 20, 21}
    assert not row[1500 // block + 1:].any()
    dense = np.asarray(a[2, 0, 1])
    assert set(np.flatnonzero(dense)) == set(range(50 // block + 1))


def test_above_every_context_dense_len_makes_it_dense_gqa():
    """With ``dense_len`` above every position the block attention is a
    plain causal GQA attention without position: each query head over every
    key of its K/V group up to its own."""
    kp, vp, pooled = _pools(page=32, stride=4)
    n, first = 40, 37
    pos = (first + jnp.arange(n))[None]
    table = TABLE[:1]
    q = jax.random.normal(jax.random.PRNGKey(5), (1, n, 2, 3, 128))
    with jax.default_matmul_precision("highest"):
        r = pa.block_scores(q, pooled, table, pos, layer=0, stride=4,
                            window=8, block=16, scale=0.1)
        chosen = pa.block_select(r, pos, top_k=2, block=16, init_blocks=1,
                                 local=16, dense_len=10_000)
        got = pa.block_sparse_attention(q, kp, vp, table, chosen, pos,
                                        layer=0, block=16, scale=0.1, most=8)
        keys = kp[0][:, table[0]].reshape(2, -1, 128)      # [hkv, T, D]
        vals = vp[0][:, table[0]].reshape(2, -1, 128)
        s = jnp.einsum("nhrd,htd->nhrt", q[0], keys) * 0.1
        s = jnp.where(jnp.arange(keys.shape[1]) <= pos[0][:, None, None,
                                                          None], s, -jnp.inf)
        want = jnp.einsum("nhrt,htd->nhrd", jax.nn.softmax(s, -1), vals)
    assert float(jnp.abs(got[0] - want).max()) < 1e-5


# ---------------------------------------------------------- the strided pool
def test_the_pool_holds_a_strided_array_at_a_stride_th_of_a_position():
    """``pooled`` is an entry a ``kernel_stride`` positions: a page holds
    ``page / stride`` of them, and a position costs a stride-th of an entry;
    every array has the same page ids; the state is a slot's."""
    pool = KVPagePool(CFG, max_slots=3, page_tokens=8)
    spec = sparse_linear.cache_spec(CFG)
    assert _entry(spec["pooled"])[3] == 2 and _entry(spec["k"])[3] == 1
    entry = 2 * 2 * 16 * 4                       # layers x heads x dim x f32
    assert pool.token_bytes == 2 * entry + entry // 2
    arrays = jax.eval_shape(pool.allocate)
    P = pool.capacity_pages + 1
    assert arrays["k"].shape == arrays["v"].shape == (2, 2, P, 8, 16)
    assert arrays["pooled"].shape == (2, 2, P, 4, 16)
    assert arrays["lin"].shape == (1, 3, 4, 16, 16)
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for name, a in arrays.items() if name != "lin")
    assert held == P * pool.page_bytes
    with pytest.raises(ValueError, match="strides"):
        KVPagePool(CFG, max_slots=3, page_tokens=7)
    assert serving_model(CFG) is sparse_linear


def test_a_pooled_key_that_straddles_a_chunk_and_a_page_is_written_once():
    """A prompt of 27 in chunks of 16 (pages of 8), then decode: each
    complete window's pooled key in the pool is the mean of its keys as the
    pages hold them, the window that starts in the page before a chunk and
    the one a decode step completes among them; the chunk's last entries,
    whose windows the next chunk completes, are written by it."""
    params = sparse_linear.init_params(jax.random.PRNGKey(1), CFG)
    spec, state = sparse_linear.cache_spec(CFG), sparse_linear.state_spec(CFG)
    P, page, rows = 12, 8, 2
    pool = {}
    for name, item in spec.items():
        lead, trail, dtype, stride = _entry(item)
        pool[name] = jnp.zeros(lead + (P + 1, page // stride) + trail, dtype)
    for name, (lead, trail, dtype) in state.items():
        pool[name] = jnp.zeros(lead + (rows,) + trail, dtype)
    mine = np.full(CFG.max_seq // page, P, np.int32)
    mine[:6] = [4, 9, 0, 7, 2, 11]
    table = np.full((rows, len(mine)), P, np.int32)
    lengths = np.zeros(rows, np.int32)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (40,), 2,
                                         256))
    for ci in range(2):
        chunk = np.ones(16, np.int32)
        real = min(16, 27 - ci * 16)
        chunk[:real] = toks[ci * 16:ci * 16 + real]
        _, pool, _ = sparse_linear.mixed_step(
            params, pool, jnp.array(chunk), jnp.array(mine[:4]),
            jnp.int32(real - 1), jnp.ones(rows, jnp.int32),
            jnp.array(lengths), jnp.array(lengths), jnp.array(table), CFG,
            chunk_index=jnp.int32(ci), slot=jnp.int32(0))
    table[0], lengths[0] = mine, 27
    for t in range(27, 34):
        tk = np.ones(rows, np.int32)
        tk[0] = toks[t]
        _, pool, _ = sparse_linear.paged_decode(
            params, jnp.array(tk), pool, jnp.array(lengths),
            jnp.array(lengths), jnp.array(table), CFG)
        lengths[0] += 1
    # positions 0..33 are in the pool; windows of 4 from every 2nd
    keys = np.asarray(pool["k"])[:, :, mine[:5]].reshape(2, 2, 40, 16)
    pooled = np.asarray(pool["pooled"])[:, :, mine[:5]].reshape(2, 2, 20, 16)
    for j in range((34 - 4) // 2 + 1):
        want = keys[:, :, 2 * j:2 * j + 4].mean(2)
        np.testing.assert_allclose(pooled[:, :, j], want, rtol=1e-5,
                                   atol=1e-6, err_msg=f"window {j}")
