"""The hybrid state-space model on the serve path (ops/ssm.py's chunked scan
and decode update, models/hybrid_ssm.py, serve/kv_cache.py's state entry, the
engine of serve/llm.py handing a chunk of a prompt, or a whole prefill, its
slot), at toy widths: every kind of part present, two groups, groups != heads.

CPU: what is checked is the arithmetic and the bookkeeping, not a speed. The
comparison with the plain reference is tests/chipbench_tests/
test_hybrid_ssm_cell.py's.
"""

import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import (gpt, hybrid_ssm, latent_moe,
                                              serving_model)
from ray_memory_management_tpu.ops import ssm
from ray_memory_management_tpu.serve.kv_cache import KVPagePool

CFG = hybrid_ssm.HybridSSMConfig(
    vocab_size=512, d_model=64, n_layers=3, n_heads=6, kv_heads=2,
    head_dim=16, d_ff=96, ssm_heads=4, ssm_head_dim=16, ssm_state=8,
    ssm_groups=2, ssm_conv=4, embedding_multiplier=2.0,
    lm_head_multiplier=0.5, attention_in_multiplier=0.9,
    attention_out_multiplier=0.7, key_multiplier=0.8, ssm_in_multiplier=0.6,
    ssm_out_multiplier=1.1, ssm_multipliers=(0.9, 0.8, 1.2, 0.7, 1.3),
    mlp_multipliers=(0.8, 1.2), max_seq=128, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 16


@pytest.fixture(scope="module")
def params():
    """The plain init, with a convolution bias that is not 0."""
    out = hybrid_ssm.init_params(jax.random.PRNGKey(7), CFG)
    for i, layer in enumerate(out["layers"]):
        layer["conv_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), layer["conv_b"].shape)
    return out


def _inputs(T, H=4, P=16, G=2, N=8, seed=0):
    """x, dt, A, B, C, D of a row of T positions: decays from a few
    positions to hundreds."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), jnp.float32, 0.0, 2.7)),
            jax.random.normal(k[3], (T, G, N)),
            jax.random.normal(k[4], (T, G, N)),
            1.0 + 0.1 * jax.random.normal(k[5], (H,)))


def _cut(inputs, lo, hi):
    """Positions lo..hi of a row's inputs (A and D have none)."""
    x, dt, A, B, C, D = inputs
    return x[lo:hi], dt[lo:hi], A, B[lo:hi], C[lo:hi], D


# ------------------------------------------------------------ the chunked scan
@pytest.mark.parametrize("T", [5, 16, 24, 40, 64])
def test_chunked_scan_against_the_sequential_form(T):
    """Lengths that are and are not multiples of the chunk of 16."""
    a = _inputs(T, seed=T)
    want_y, want_h = ssm.ssd_sequential(*a)
    y, h = ssm.ssd_scan(*a, chunk=16)
    assert y.dtype == h.dtype == jnp.float32 and h.shape == (4, 8, 16)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-5)


@pytest.mark.parametrize("true_len", [1, 15, 16, 27, 40])
def test_a_true_len_short_of_the_bucket_leaves_the_state_of_true_len(
        true_len):
    """The bucket's padding moves nothing: the state that comes back is
    that of the first ``true_len`` positions run alone, and so are their
    outputs, whatever lies behind them."""
    a = _inputs(40, seed=3)
    junk = tuple(v.at[true_len:].set(7.0) if v.ndim > 1 else v for v in a)
    want_y, want_h = ssm.ssd_sequential(*_cut(a, 0, true_len))
    for form in (ssm.ssd_sequential,
                 lambda *a, **k: ssm.ssd_scan(*a, chunk=16, **k)):
        y, h = jax.jit(form)(*junk, true_len=jnp.int32(true_len))
        np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(y[:true_len]),
                                   np.asarray(want_y), atol=2e-5)
    # without the mask the padding does move it: the test can fail
    _, moved = ssm.ssd_scan(*junk, chunk=16)
    if true_len < 40:
        assert float(jnp.max(jnp.abs(moved - want_h))) > 1e-2


def test_a_carried_state_continues_the_row():
    a = _inputs(40, seed=4)
    want_y, want_h = ssm.ssd_sequential(*a)
    _, h0 = ssm.ssd_scan(*_cut(a, 0, 27), chunk=16)
    y, h = ssm.ssd_scan(*_cut(a, 27, 40), chunk=8, h0=h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y[27:]),
                               atol=2e-5)


def test_bf16_operands_accumulate_in_float32():
    """On the serve path x, B and C are bf16: the state and the outputs stay
    float32 and near the float32 reading."""
    a = _inputs(48, seed=5)
    low = tuple(v.astype(jnp.bfloat16) if i in (0, 3, 4) else v
                for i, v in enumerate(a))
    want_y, want_h = ssm.ssd_sequential(*low)
    y, h = ssm.ssd_scan(*low, chunk=16)
    assert y.dtype == h.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(h - want_h))) < 0.05
    assert float(jnp.max(jnp.abs(y - want_y))) < 0.1


@pytest.mark.parametrize("T,true_len", [(128, None), (300, None), (300, 211),
                                        (384, 129)])
def test_scan_kernel_in_interpret_mode_against_the_sequential_form(
        T, true_len):
    """The Pallas form of the chunked scan (chunks of 128, whole lanes of
    channels and state) against the sequential form and against the
    ``jax.numpy`` chunked form it is the same algorithm as: rows that are and
    are not whole chunks, and a ``true_len`` inside and at the edge of a
    chunk."""
    a = _inputs(T, P=128, N=128, seed=T)
    kw = dict(chunk=128, true_len=true_len)
    want_y, want_h = ssm.ssd_sequential(*a, true_len=true_len)
    plain_y, plain_h = ssm.ssd_scan(*a, use_pallas="off", **kw)
    y, h = jax.jit(lambda *a: ssm.ssd_scan(
        *a, use_pallas="interpret", **kw))(*a)
    n = T if true_len is None else true_len
    assert y.shape == (T, 4, 128) and h.shape == (4, 128, 128)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(want_y[:n]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(plain_h), atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(plain_y[:n]),
                               atol=1e-4)


def test_scan_kernel_carries_a_state_and_says_what_it_can_tile():
    a = _inputs(300, P=128, N=128, seed=8)
    want_y, want_h = ssm.ssd_sequential(*a)
    _, h0 = ssm.ssd_scan(*_cut(a, 0, 150), chunk=128, use_pallas="off")
    y, h = ssm.ssd_scan(*_cut(a, 150, 300), chunk=128, h0=h0,
                        use_pallas="interpret")
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y[150:]),
                               atol=1e-3)
    bf = jnp.bfloat16
    x, b = jnp.zeros((512, 32, 128), bf), jnp.zeros((512, 2, 256), bf)
    assert ssm.ssd_kernel_takes(x, b)
    assert not ssm.ssd_kernel_takes(x[:100], b[:100])     # under a chunk
    assert not ssm.ssd_kernel_takes(jnp.zeros((512, 4, 16), bf),
                                    jnp.zeros((512, 2, 8), bf))  # toy lanes


# ------------------------------------------------------------ the decode update
def _decode_inputs(L=2, S=6, H=4, P=128, G=2, N=8, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (L, S, H, N, P)),
            jax.random.normal(k[1], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (S, H))),
            -jnp.exp(jax.random.uniform(k[3], (H,), jnp.float32, 0.0, 2.7)),
            jax.random.normal(k[4], (S, G, N)),
            jax.random.normal(k[5], (S, G, N)),
            1.0 + 0.1 * jax.random.normal(k[6], (H,)))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 0], [0, 0, 0, 0, 0, 1],
                                  [1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0]])
def test_update_kernel_in_interpret_mode_against_its_plain_form(layer, live):
    """The kernel walks the live slots only: their state and output are the
    plain form's, an idle slot's state comes back bit for bit, the other
    layer is not touched, and the kernel counts the rows it fetched."""
    state, *rest = _decode_inputs()
    live = jnp.asarray(live, bool)
    want_y, want_s, read_all = ssm.ssm_decode_update(
        state, *rest, live, layer=layer, use_pallas="off")
    y, s, fetched = jax.jit(lambda *a: ssm.ssm_decode_update(
        *a, layer=layer, use_pallas="interpret"))(state, *rest, live)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s), atol=1e-5)
    idle = ~np.asarray(live)
    for got in (s, want_s):
        assert np.array_equal(np.asarray(got[layer])[idle],
                              np.asarray(state[layer])[idle])
        assert np.array_equal(np.asarray(got[1 - layer]),
                              np.asarray(state[1 - layer]))
    assert not np.any(np.asarray(y)[idle])
    assert int(fetched) == int(live.sum()) and int(read_all) == 6


def test_one_update_is_one_position_of_the_scan():
    """A token-step from the state of 19 positions gives the 20th's."""
    a = _inputs(20, P=128, seed=6)
    want_y, want_h = ssm.ssd_sequential(*a)
    _, h19 = ssm.ssd_sequential(*_cut(a, 0, 19))
    x, dt, A, B, C, D = _cut(a, 19, 20)
    for use in ("off", "interpret"):
        y, s, _ = ssm.ssm_decode_update(h19[None, None], x, dt, A, B, C, D,
                                        jnp.asarray([True]), use_pallas=use)
        np.testing.assert_allclose(np.asarray(s[0, 0]), np.asarray(want_h),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want_y[19]),
                                   atol=1e-5)


def test_the_compiled_kernel_is_for_whole_lanes_and_the_rest_reads_plainly():
    f32 = jnp.float32
    assert ssm.ssm_kernel_takes(jnp.zeros((1, 1, 32, 256, 128), f32),
                                jnp.zeros((1, 32, 128), f32))
    # toy channels do not fill the lanes; a bf16 state is another kernel
    assert not ssm.ssm_kernel_takes(jnp.zeros((1, 1, 4, 8, 16), f32),
                                    jnp.zeros((1, 4, 16), f32))
    assert not ssm.ssm_kernel_takes(
        jnp.zeros((1, 1, 32, 256, 128), jnp.bfloat16),
        jnp.zeros((1, 32, 128), f32))
    # at the published shape a grid step moves 8 of a group's 16 heads
    assert ssm._head_block(16, 256, 128) == 8
    assert ssm._head_block(2, 8, 16) == 2


# ------------------------------------------------------------ the page pool
def test_the_pool_holds_a_state_entry_a_slot_beside_the_pages():
    pool = KVPagePool(CFG, max_slots=5, page_tokens=PAGE)
    assert pool.token_bytes == CFG.n_layers * 2 * 2 * 16 * 4
    row = CFG.n_layers * (4 * 8 * 16 * 4 + 3 * CFG.conv_width * 4)
    assert CFG.conv_width == 64 + 2 * 2 * 8
    assert (pool.state_row_bytes, pool.state_bytes) == (row, 5 * row)
    arrays = pool.allocate()
    kv = (CFG.n_layers, 2, pool.capacity_pages + 1, PAGE, 16)
    assert {k: v.shape for k, v in arrays.items()} == {
        "k": kv, "v": kv, "ssm": (CFG.n_layers, 5, 4, 8, 16),
        "conv": (CFG.n_layers, 3, 5, CFG.conv_width)}
    assert arrays["ssm"].dtype == jnp.float32
    stats = pool.stats()
    assert stats["store_bytes"] == sum(a.nbytes for a in arrays.values())
    assert stats["state_bytes"] == arrays["ssm"].nbytes \
        + arrays["conv"].nbytes
    assert stats["state_row_bytes"] == row
    # pages are reserved and freed as before; no page id refers to a state
    assert pool.reserve(1, 3 * PAGE) and pool.pages_in_use == 3
    pool.free(1)
    assert pool.pages_in_use == 0


@pytest.mark.parametrize("cfg", [
    gpt.PRESETS["test"],
    latent_moe.LatentMoEConfig(
        vocab_size=512, d_model=64, n_layers=3, n_heads=4, q_lora_rank=24,
        kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4,
        v_head_dim=16, d_ff=128, moe_d_ff=32, n_routed_experts=8,
        n_shared_experts=1, experts_per_tok=2, routed_scaling_factor=1.8,
        max_seq=128)], ids=["dense", "latent"])
def test_a_model_without_a_state_gets_the_pages_alone(cfg):
    """The dense and latent models' pools: the arrays their cache
    specification names, the bytes they had, and no state."""
    pool = KVPagePool(cfg, max_slots=4, page_tokens=PAGE)
    arrays = pool.allocate()
    assert set(arrays) == set(serving_model(cfg).cache_spec(cfg))
    assert (pool.state_spec, pool.state_row_bytes, pool.state_bytes) \
        == ({}, 0, 0)
    stats = pool.stats()
    assert stats["store_bytes"] == sum(a.nbytes for a in arrays.values()) \
        == (pool.capacity_pages + 1) * pool.page_bytes
    assert stats["state_bytes"] == 0


def test_the_engine_finds_the_hybrid_model():
    assert serving_model(CFG) is hybrid_ssm
    for name in ("init_params", "cache_spec", "state_spec", "prefill_row",
                 "prefill_takes_kernel", "paged_decode", "mixed_step",
                 "forward"):
        assert callable(getattr(hybrid_ssm, name)), name
    assert not hasattr(gpt, "state_spec")
    assert not hasattr(latent_moe, "state_spec")


# ---------------------------------------------------------------- the model
def _prefill(params, prompt, bucket, pool, table_row, slot, cfg=CFG,
             page=PAGE):
    """What the engine's prefill does with a row: its K and V into its
    pages, its state into its slot's entry."""
    toks = np.full((1, bucket), 9, np.int32)  # the junk tail is not token 0
    toks[0, :len(prompt)] = prompt
    logits, row = jax.jit(lambda t, n: hybrid_ssm.prefill_row(
        params, t, cfg, bucket, n))(jnp.asarray(toks), len(prompt))
    n = bucket // page
    pool = dict(pool)
    for name in ("k", "v"):
        pool[name] = pool[name].at[:, :, table_row[:n]].set(
            row[name].reshape(cfg.n_layers, cfg.kv_heads, n, page,
                              cfg.head_dim))
    pool["ssm"] = pool["ssm"].at[:, slot].set(row["ssm"])
    pool["conv"] = pool["conv"].at[:, :, slot].set(row["conv"])
    return logits, pool


def _empty_pool(slots, sink, fill=0.0, cfg=CFG, page=PAGE):
    kv = jnp.zeros((cfg.n_layers, cfg.kv_heads, sink + 1, page,
                    cfg.head_dim), jnp.float32)
    return {"k": kv.at[:, :, sink].set(jnp.nan),
            "v": kv.at[:, :, sink].set(jnp.nan),
            "ssm": jnp.full((cfg.n_layers, slots, cfg.ssm_heads,
                             cfg.ssm_state, cfg.ssm_head_dim), fill,
                            jnp.float32),
            "conv": jnp.full((cfg.n_layers, 3, slots, cfg.conv_width), fill,
                             jnp.float32)}


def test_prefill_then_decode_through_pages_and_state_equals_the_forward(
        params):
    """A row prefilled in a padded bucket into pages 5, 2, 7, ... and slot
    0's state entry, then 40 decode steps across two page boundaries beside
    an idle slot (whose entry is full of junk and must stay so) and a second
    live row: the logits of every step are the whole forward's."""
    rng = np.random.default_rng(0)
    seq = rng.integers(2, CFG.vocab_size, 20 + 40).tolist()
    other = rng.integers(2, CFG.vocab_size, 9 + 40).tolist()
    want = hybrid_ssm.forward(params, jnp.asarray([seq]), CFG)[0]
    want_other = hybrid_ssm.forward(params, jnp.asarray([other]), CFG)[0]
    sink = 10
    pool = _empty_pool(3, sink, fill=3.0)
    table = np.full((3, 8), sink, np.int32)
    table[0, :4] = [5, 2, 7, 0]
    table[2, :4] = [9, 3, 1, 4]
    first, pool = _prefill(params, seq[:20], 32, pool, table[0], 0)
    _, pool = _prefill(params, other[:9], 16, pool, table[2], 2)
    np.testing.assert_allclose(np.asarray(first), np.asarray(want[19]),
                               atol=2e-4)
    step = jax.jit(lambda pool, last, pos, lens: hybrid_ssm.paged_decode(
        params, last, pool, pos, lens, jnp.asarray(table), CFG))
    for t in range(40):
        pos = np.asarray([20 + t, 0, 9 + t], np.int32)
        last = np.asarray([seq[20 + t], 1, other[9 + t]], np.int32)
        logits, pool, counts = step(pool, jnp.asarray(last),
                                    jnp.asarray(pos), jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[20 + t]), atol=2e-4)
        np.testing.assert_allclose(np.asarray(logits[2]),
                                   np.asarray(want_other[9 + t]), atol=2e-4)
        assert int(counts["state_rows_stepped"]) == 2 * CFG.n_layers
        assert int(counts["ssm_layer_steps"]) == CFG.n_layers
    # the idle slot's entry as it was, to the bit; the sink read by no row
    assert bool(jnp.all(pool["ssm"][:, 1] == 3.0))
    assert bool(jnp.all(pool["conv"][:, :, 1] == 3.0))
    assert not bool(jnp.any(jnp.isnan(logits[jnp.asarray([0, 2])])))


@pytest.mark.parametrize("prompt_len", [1, 2, 17, 32])
def test_the_state_of_a_padded_prompt_is_that_of_the_prompt_alone(
        params, prompt_len):
    """The recurrence's state and the convolution's tail of a prompt in a
    bucket of 32, with junk behind it, are those of the prompt in a bucket of
    its own length (a tail that reaches before position 0 is zeros)."""
    prompt = np.random.default_rng(prompt_len).integers(
        2, CFG.vocab_size, prompt_len)
    padded = np.full((1, 32), 9, np.int32)
    padded[0, :prompt_len] = prompt
    row = jax.jit(lambda toks, n: hybrid_ssm.prefill_row(params, toks, CFG,
                                                         32, n))
    got_l, got = row(jnp.asarray(padded), prompt_len)
    want_l, want = row(jnp.asarray(prompt[None]), prompt_len)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               atol=1e-4)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=1e-5)
    assert got["conv"].shape == (CFG.n_layers, 3, CFG.conv_width)
    if prompt_len < 3:
        assert not bool(jnp.any(got["conv"][:, :3 - prompt_len]))


# --------------------------------------------------------------- the engine
def _server(**over):
    from ray_memory_management_tpu.serve.llm import LLMServer

    kwargs = dict(config=CFG, max_batch_size=3, max_new_tokens=24,
                  pad_multiple=32, steps_per_iter=4, kv_page_tokens=PAGE,
                  seed=7)
    kwargs.update(over)
    return LLMServer(**kwargs)


def _greedy(params, prompt, out):
    """What the whole forward puts first at each position of ``out`` behind
    ``prompt`` (equal to ``out`` where ``out`` is its greedy answer)."""
    logits = hybrid_ssm.forward(params, jnp.asarray(
        [list(prompt) + list(out[:-1])]), CFG)[0]
    return np.argmax(np.asarray(logits[len(prompt) - 1:]), -1).tolist()


def _together(srv, prompts, budgets):
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = srv.generate(prompts[i], max_new_tokens=budgets[i])

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    return outs


def test_the_engine_serves_a_short_prompt_in_a_long_bucket():
    """LLMServer(config=...) through ContinuousBatcher and KVPagePool: a
    prompt of 5 in a bucket of 32; greedy tokens are the whole forward's,
    and the engine's counts hold the live row and nothing of idle slots."""
    srv = _server()
    try:
        assert srv.cfg is CFG
        prompt = list(range(2, 7))
        out = srv.generate(prompt, max_new_tokens=9)
        assert len(out) == 9 and out == _greedy(srv.params, prompt, out)
        e = srv.stats()["engine"]
        # 8 decode steps (two iterations of four) of one live row of three
        assert e["state_rows_stepped"] == 8 * CFG.n_layers
        assert e["ssm_layer_steps"] == 8 * CFG.n_layers
        # off the TPU the plain form reads every slot's state
        assert e["state_rows_fetched"] == 8 * CFG.n_layers * 3
        assert e["state_row_bytes"] == srv._engine.kv_pool.state_row_bytes \
            == CFG.n_layers * (4 * 8 * 16 + 3 * CFG.conv_width) * 4
        kv = srv.stats()["kv"]
        assert kv["pages_in_use"] == 0
        assert kv["state_bytes"] == 3 * e["state_row_bytes"]
    finally:
        srv._engine.close()


def test_rows_of_different_lengths_step_together_and_end_inside_an_iteration():
    """Three rows of different lengths in one engine, budgets that end on
    and inside an iteration of four: each answer is what the row gives
    alone."""
    srv = _server()
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(2, CFG.vocab_size, n).tolist()
                   for n in (3, 31, 40)]
        budgets = [9, 6, 11]
        outs = _together(srv, prompts, budgets)
        for p, b, out in zip(prompts, budgets, outs):
            assert len(out) == b and out == _greedy(srv.params, p, out), \
                len(p)
    finally:
        srv._engine.close()


def test_a_slot_retired_and_admitted_again_gives_what_a_fresh_engine_gives():
    """One slot, so that every request inherits its forerunner's entry: a
    long request, then a short one whose answer must be a fresh engine's."""
    rng = np.random.default_rng(2)
    long = rng.integers(2, CFG.vocab_size, 50).tolist()
    short = rng.integers(2, CFG.vocab_size, 2).tolist()
    used, fresh = _server(max_batch_size=1), _server(max_batch_size=1)
    try:
        used.generate(long, max_new_tokens=13)
        assert bool(jnp.any(used._engine._pool["ssm"][:, 0] != 0))
        got = used.generate(short, max_new_tokens=10)
        want = fresh.generate(short, max_new_tokens=10)
        assert len(got) == 10 and got == want \
            == _greedy(fresh.params, short, got)
        for name in ("ssm", "conv"):
            assert np.array_equal(np.asarray(used._engine._pool[name]),
                                  np.asarray(fresh._engine._pool[name]))
    finally:
        used._engine.close()
        fresh._engine.close()


def test_the_pool_goes_with_the_weights_and_comes_back():
    """Whoever owns the replica takes the weights off the device between
    requests (the benchmark's output check does): the idle engine lets its
    pool go too, and allocates it anew at the next admission."""
    import time

    srv = _server(max_batch_size=2)
    try:
        prompt = list(range(2, 12))
        first = srv.generate(prompt, max_new_tokens=5)
        eng, params = srv._engine, srv.params
        assert eng._pool is not None
        eng.params = None
        deadline = time.time() + 10
        while eng._pool is not None and time.time() < deadline:
            time.sleep(0.01)
        assert eng._pool is None
        eng.params = params
        assert srv.generate(prompt, max_new_tokens=5) == first
        assert eng._pool is not None
    finally:
        srv._engine.close()


# ------------------------------------- a prompt's chunks on the decode step
# the scan kernel tiles whole lanes of channels and of state and chunks of
# 128: a second toy whose state-space branch it takes (in interpret mode)
KCFG = hybrid_ssm.HybridSSMConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, kv_heads=2,
    head_dim=16, d_ff=96, ssm_heads=2, ssm_head_dim=128, ssm_state=128,
    ssm_groups=1, ssm_conv=4, ssm_in_multiplier=0.7,
    ssm_multipliers=(0.9, 0.8, 1.2, 0.7, 1.3), max_seq=512,
    dtype=jnp.float32, param_dtype=jnp.float32)
# form -> (configuration, chunk, page): a chunk is two pages
FORMS = {"plain": (CFG, 32, 16), "scan-kernel": (KCFG, 128, 64)}


@pytest.fixture(scope="module")
def kparams():
    out = hybrid_ssm.init_params(jax.random.PRNGKey(11), KCFG)
    for i, layer in enumerate(out["layers"]):
        layer["conv_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(i), layer["conv_b"].shape)
    return out


def _chunked_beside_riders(params, cfg, C, page, n_prompt, *, idle=True):
    """A prompt of ``n_prompt`` tokens through ``mixed_step``, chunk by
    chunk, into slot 1 and pages of its own, beside two rows that decode
    (slots 0 and 2) and an idle slot 3; every slot's entry starts as junk an
    earlier request left. ``idle`` false plants a fault: the row is live
    among the decode rows while its chunks ride. Returns what the chunks
    left (pool, the last chunk's logits), the same riders stepped by
    ``paged_decode`` alone, and the whole prompt through ``prefill_row``."""
    rng = np.random.default_rng(n_prompt)
    n_chunks, per = -(-n_prompt // C), C // page
    sink, width = 4 * per + 8, 4 * per
    table = np.full((4, width), sink, np.int32)
    table[0, :2], table[2, :2] = [sink - 2, 1], [sink - 5, 3]
    mine = np.full(4 * per, sink, np.int32)
    mine[:n_chunks * per] = [sink - 1, 0, sink - 3, 2, sink - 4, 4,
                             sink - 6, 5][:n_chunks * per]
    riders = {0: rng.integers(2, cfg.vocab_size, 13).tolist(),
              2: rng.integers(2, cfg.vocab_size, 6).tolist()}
    pool, last = _empty_pool(4, sink, 3.0, cfg, page), [1, 1, 1, 1]
    for r, p in riders.items():
        logits, pool = _prefill(params, p, -(-len(p) // page) * page, pool,
                                table[r], r, cfg, page)
        last[r] = int(jnp.argmax(logits))
    prompt = rng.integers(2, cfg.vocab_size, n_prompt)
    toks = np.full(n_chunks * C, 9, np.int32)   # the junk tail is not token 0
    toks[:n_prompt] = prompt
    step = jax.jit(functools.partial(hybrid_ssm.mixed_step, cfg=cfg))
    alone = jax.jit(functools.partial(hybrid_ssm.paged_decode, cfg=cfg))
    mixed, plain = pool, pool
    m_last = p_last = jnp.asarray(last, jnp.int32)
    off = np.asarray([13, 0, 6, 0], np.int32)
    for index in range(n_chunks):
        ends = index == n_chunks - 1
        lengths = off + index * (off > 0)
        if not idle:  # the fault: live at the positions its chunks filled
            lengths[1], table[1] = index * C, mine[:width]
        lengths = jnp.asarray(lengths)
        logits, mixed, counts = step(
            params, mixed, jnp.asarray(toks[index * C:(index + 1) * C]),
            jnp.asarray(mine), jnp.int32(n_prompt - 1 - index * C if ends
                                         else C - 1),
            m_last, lengths, lengths, jnp.asarray(table),
            chunk_index=jnp.int32(index), slot=jnp.int32(1))
        assert logits.shape == (5, cfg.vocab_size)
        live = int((np.asarray(lengths) > 0).sum())
        assert int(counts["mixed_state_rows_stepped"]) == cfg.n_layers * live
        # no count under the decode program's names: its readers count
        # token-steps of the decode program alone
        assert set(counts) == {"mixed_state_rows_stepped"}
        if idle:
            ref, plain, _ = alone(params, p_last, plain, lengths, lengths,
                                  jnp.asarray(table))
            np.testing.assert_allclose(logits[:4][np.asarray([0, 2])],
                                       ref[np.asarray([0, 2])], atol=2e-4)
            p_last = jnp.argmax(ref, axis=-1)
        m_last = jnp.argmax(logits[:4], axis=-1)
    want_logits, whole = _prefill(params, prompt, n_chunks * C,
                                  _empty_pool(4, sink, 3.0, cfg, page), mine,
                                  1, cfg, page)
    return mixed, logits[4], plain, want_logits, whole, mine[:n_chunks * per]


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("tail", [1, 2, 3, -1, "one", "two", "three"])
def test_chunks_leave_what_the_whole_prefill_leaves(params, kparams, form,
                                                    tail, monkeypatch):
    """Prompts of 1, 2, 3 and ``C - 1`` positions into their last chunk (a
    convolution tail that reaches back into the chunk before, or into the
    zeros before position 0) and of exactly one, two and three chunks: the
    chunks leave the pages, the state, the tail and the last position's
    logits of ``prefill_row``, whatever junk the slot held; the riders get
    ``paged_decode``'s logits and state, and the idle slot keeps its junk to
    the bit. Once with the recurrence in plain ``jax.numpy`` and once in the
    scan kernel (interpret mode), each chunk after the first from ``h0``."""
    cfg, C, page = FORMS[form]
    if form == "scan-kernel":
        scan = ssm.ssd_scan
        monkeypatch.setattr(ssm, "ssd_scan", lambda *a, **k: scan(
            *a, chunk=128, use_pallas="interpret", **k))
    whole = {"one": C, "two": 2 * C, "three": 3 * C}
    n_prompt = whole[tail] if tail in whole else C + tail % C
    mixed, logits, plain, want_logits, whole, pages = _chunked_beside_riders(
        params if cfg is CFG else kparams, cfg, C, page, n_prompt)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-4)
    for name in ("k", "v"):  # the prompt's positions, and nothing past them
        got, want = (np.asarray(a[name][:, :, pages]) for a in (mixed, whole))
        np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mixed["ssm"][:, 1]),
                               np.asarray(whole["ssm"][:, 1]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(mixed["conv"][:, :, 1]),
                               np.asarray(whole["conv"][:, :, 1]), atol=1e-5)
    for r in (0, 2):  # the riders moved as they move alone
        np.testing.assert_allclose(np.asarray(mixed["ssm"][:, r]),
                                   np.asarray(plain["ssm"][:, r]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(mixed["conv"][:, :, r]),
                                   np.asarray(plain["conv"][:, :, r]),
                                   atol=1e-5)
    assert bool(jnp.all(mixed["ssm"][:, 3] == 3.0))
    assert bool(jnp.all(mixed["conv"][:, :, 3] == 3.0))


@pytest.mark.parametrize("fault", ["none", "live_in_the_decode_half",
                                   "carries_at_the_first_chunk_too"])
def test_only_the_chunks_move_the_prefilling_rows_state(params, fault,
                                                        monkeypatch):
    """Three chunks of a row that is idle among the decode rows leave the
    whole prefill's state. Were the row live there, the decode half would
    move its state between its chunks; were a first chunk to start from
    the slot's entry, it would carry on from an earlier request's: both
    faults show, so the comparison can fail."""
    if fault == "carries_at_the_first_chunk_too":
        monkeypatch.setattr(hybrid_ssm, "_carried", lambda entry, first: entry)
    mixed, _, _, _, whole, _ = _chunked_beside_riders(
        params, CFG, 32, 16, 70, idle=fault != "live_in_the_decode_half")
    gap = float(jnp.max(jnp.abs(mixed["ssm"][:, 1] - whole["ssm"][:, 1])))
    assert (gap < 1e-4) if fault == "none" else (gap > 1e-3), gap


@pytest.fixture(scope="module")
def whole_srv():
    """The engine as it was before the model offered ``mixed_step``: the
    engine chooses when it is built, by what the model offers then."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delattr(hybrid_ssm, "mixed_step")
        srv = _server()
    assert not srv._engine._mixed
    yield srv
    srv._engine.close()


@pytest.fixture(scope="module")
def chunk_srv():
    srv = _server()
    assert srv._engine._mixed and srv._engine._chunk == 32
    yield srv
    srv._engine.close()


def _arrivals(srv, prompts, budgets):
    """``prompts[0]`` is decoding when the others arrive together."""
    first, eng = [None], srv._engine
    steps = eng.steps

    def resident():
        first[0] = srv.generate(prompts[0], max_new_tokens=budgets[0])

    t = threading.Thread(target=resident)
    t.start()
    deadline = time.monotonic() + 120
    while eng.steps == steps and time.monotonic() < deadline:
        time.sleep(0.001)  # until its first iteration has run
    rest = _together(srv, prompts[1:], budgets[1:])
    t.join(300)
    return [first[0]] + rest


@pytest.mark.parametrize("budget", [1, 3, 4, 5, 11])
@pytest.mark.parametrize("n_prompt", [5, 101],
                         ids=["one-chunk", "four-chunks"])
def test_arrivals_while_others_decode_are_the_whole_prefill_engines_tokens(
        chunk_srv, whole_srv, budget, n_prompt):
    """A long answer is decoding when a request of ``budget`` tokens (to,
    on and past an iteration of four) and one of another shape arrive: the
    engine whose prompts ride the decode step in chunks returns, token for
    token, what the engine that prefills whole prompts returns, and what
    the whole forward puts first."""
    rng = np.random.default_rng(100 * budget + n_prompt)
    prompts = [rng.integers(2, CFG.vocab_size, n).tolist()
               for n in (19, n_prompt, 33)]
    budgets = [24, budget, 7]
    got = _arrivals(chunk_srv, prompts, budgets)
    want = _arrivals(whole_srv, prompts, budgets)
    for p, b, out, ref in zip(prompts, budgets, got, want):
        assert len(out) == b and out == ref \
            == _greedy(chunk_srv.params, p, out), len(p)
    e = chunk_srv.stats()["engine"]
    assert e["mixed_steps"] > 0 and e["mixed_state_rows_stepped"] > 0
    assert whole_srv.stats()["engine"]["mixed_steps"] == 0
    assert chunk_srv._engine.kv_pool.pages_in_use == 0


@pytest.mark.parametrize("fault", ["none", "carries_at_the_first_chunk_too"])
def test_a_slot_admitted_again_never_reads_what_the_first_request_left(
        fault, monkeypatch):
    """One slot, so that every request inherits its forerunner's entry, and
    nobody clears it: a long request, then short ones, whose answers are a
    fresh engine's. With a ``mixed_step`` that takes the slot's state and
    tail at a prompt's first chunk too, they are not."""
    rng = np.random.default_rng(2)
    long = rng.integers(2, CFG.vocab_size, 50).tolist()
    shorts = [rng.integers(2, CFG.vocab_size, n).tolist() for n in (2, 1, 7)]
    fresh = [_server(max_batch_size=1) for _ in shorts]
    if fault != "none":
        monkeypatch.setattr(hybrid_ssm, "_carried", lambda entry, first: entry)
    used = _server(max_batch_size=1)
    try:
        assert used._engine._mixed
        want = [f.generate(p, max_new_tokens=10)
                for f, p in zip(fresh, shorts)]
        used.generate(long, max_new_tokens=13)
        assert bool(jnp.any(used._engine._pool["ssm"][:, 0] != 0))
        got = [used.generate(p, max_new_tokens=10) for p in shorts]
        if fault == "none":
            assert got == want
        else:
            assert all(g != w for g, w in zip(got, want)), (got, want)
    finally:
        for srv in [used] + fresh:
            srv._engine.close()


@pytest.mark.parametrize("path", ["chunks", "whole", "dense-chunks"])
def test_positions_are_summed_once_an_iteration_on_both_paths(path,
                                                              monkeypatch):
    """``live_positions`` and ``slab_positions`` grow once an iteration, at
    assembly, whether the iteration carries chunks or runs the decode
    program: their quotient by ``iterations`` is a mean a token-step's
    assembly in both kinds."""
    from ray_memory_management_tpu.serve.llm import (ContinuousBatcher,
                                                     LLMServer)

    calls = []
    count = ContinuousBatcher._count_positions

    def counted(self, offsets, steps):
        calls.append((offsets.tolist(), steps))
        count(self, offsets, steps)

    monkeypatch.setattr(ContinuousBatcher, "_count_positions", counted)
    if path == "whole":
        monkeypatch.delattr(hybrid_ssm, "mixed_step")
    srv = _server() if path != "dense-chunks" else LLMServer(
        config=gpt.PRESETS["test"], max_batch_size=3, max_new_tokens=24,
        pad_multiple=32, steps_per_iter=4, kv_page_tokens=PAGE, seed=7)
    try:
        assert srv._engine._mixed == (path != "whole")
        rng = np.random.default_rng(4)
        vocab = srv.cfg.vocab_size
        prompts = [rng.integers(2, vocab, n).tolist() for n in (40, 3, 70)]
        _arrivals(srv, prompts, [24, 9, 6])
        e = srv.stats()["engine"]
    finally:
        srv._engine.close()
    assert len(calls) == e["iterations"] > 0
    assert (e["mixed_steps"] > 0) == (path != "whole")
    assert e["live_positions"] == sum(sum(o) for o, _ in calls)
    assert e["slab_positions"] == sum(
        -(-(o + k) // PAGE) * PAGE for offs, k in calls for o in offs)
    # an iteration's token-steps: its chunks (at most four), or the four of
    # the decode program
    assert {k for _, k in calls} <= {1, 2, 3, 4}
    assert e["live_positions"] <= e["slab_positions"]
