"""The latent-attention, routed-expert model on the serve path
(models/latent_moe.py, ops/moe.py::moe_dropless, the latent kernel of
ops/paged_attention.py, serve/kv_cache.py's pool by cache specification, the
engine of serve/llm.py asking the configuration's model), at toy widths.

CPU: what is checked is the arithmetic and the bookkeeping, not a speed. The
comparison with the plain reference is tests/chipbench_tests/
test_latent_moe_cell.py's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import gpt, latent_moe, serving_model
from ray_memory_management_tpu.ops import moe
from ray_memory_management_tpu.ops.paged_attention import (
    latent_attention, latent_attention_reference, latent_kernel_takes,
)
from ray_memory_management_tpu.serve.kv_cache import KVPagePool

CFG = latent_moe.LatentMoEConfig(
    vocab_size=512, d_model=64, n_layers=3, n_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    d_ff=128, moe_d_ff=32, n_routed_experts=8, n_shared_experts=1,
    experts_per_tok=2, routed_scaling_factor=1.8, max_seq=128,
    dtype=jnp.float32, param_dtype=jnp.float32)
PAGE = 16


@pytest.fixture(scope="module")
def params():
    """The plain init, with a choosing bias drawn so that it does choose."""
    out = latent_moe.init_params(jax.random.PRNGKey(7), CFG)
    for i, layer in enumerate(out["layers"]):
        if "moe" in layer:
            layer["moe"]["bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), layer["moe"]["bias"].shape)
    return out


def _expert_layer(seed=0, d=32, f=16, e=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda key, *s: jax.random.normal(key, s, jnp.float32)  # noqa: E731
    return {"router": n(k[0], d, e) * d ** -0.5, "bias": n(k[1], e) * 0.1,
            "w1": n(k[2], e, d, f) * d ** -0.5,
            "w3": n(k[3], e, d, f) * d ** -0.5,
            "w2": n(k[4], e, f, d) * f ** -0.5}


def _by_hand(x, layer, top_k, scale):
    """Every expert over every token, gated: the loop the layer replaces."""
    s = jax.nn.sigmoid(x @ layer["router"])
    _, chosen = jax.lax.top_k(s + layer["bias"], top_k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    out = jnp.zeros_like(x)
    for j in range(top_k):
        for e in range(layer["router"].shape[1]):
            y = (jax.nn.silu(x @ layer["w1"][e]) * (x @ layer["w3"][e])) \
                @ layer["w2"][e]
            out = out + jnp.where((chosen[:, j] == e)[:, None],
                                  w[:, j:j + 1] * y, 0.0)
    return out


# ------------------------------------------------------------------ routing
def test_every_token_keeps_its_experts_and_weights_sum_to_the_scale():
    layer = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        4, 1.8)
    assert chosen.shape == (64, 4) and w.shape == (64, 4)
    assert all(len(set(row)) == 4 for row in np.asarray(chosen).tolist())
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.8, rtol=1e-6)
    y, counts = moe.moe_dropless(x, layer, 4, 1.8)
    assert int(counts.sum()) == 64 * 4          # nothing dropped for room
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_by_hand(x, layer, 4, 1.8)),
                               atol=2e-5)


def test_a_tokens_result_is_the_same_alone_and_in_a_batch_of_64():
    """A capacity path fails this: in a batch every token of which chooses
    the same experts, all but the first few would be dropped."""
    layer = _expert_layer(2)
    one = jax.random.normal(jax.random.PRNGKey(3), (1, 32))
    crowd = jnp.concatenate([one, jnp.tile(one * 1.001, (63, 1))])
    alone, n1 = moe.moe_dropless(one, layer, 2, 1.8)
    among, n64 = moe.moe_dropless(crowd, layer, 2, 1.8)
    np.testing.assert_allclose(np.asarray(among[0]), np.asarray(alone[0]),
                               atol=1e-6)
    assert int(n1.sum()) == 2 and int(n64.sum()) == 128
    assert int(n64.max()) == 64                 # 64 rows on one expert
    # the capacity layer at the same sizes drops most of that crowd
    cfg = gpt.TransformerConfig(d_model=32, n_experts=8, expert_top_k=2,
                                dtype=jnp.float32)
    soft = {k: layer[k] for k in ("router", "w1", "w3", "w2")}
    held, _ = moe.moe_ffn(crowd[None], soft, cfg)
    lone, _ = moe.moe_ffn(one[None], soft, cfg)
    assert float(jnp.abs(held[0, -1] - lone[0, 0] * 1.001).max()) > 1e-3


def test_a_bias_that_changes_the_choice_changes_no_weight():
    layer = _expert_layer(4)
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 32))
    c0, w0 = moe.route_sigmoid_top_k(x, layer["router"],
                                     jnp.zeros_like(layer["bias"]), 2, 1.8,
                                     normalize=False)
    push = jnp.zeros_like(layer["bias"]).at[3].set(10.0)
    c1, w1 = moe.route_sigmoid_top_k(x, layer["router"], push, 2, 1.8,
                                     normalize=False)
    assert bool(jnp.all(c1[:, 0] == 3)) and not bool(jnp.all(c0 == c1))
    s = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    # the weight of a chosen expert is its score, bias or no bias
    for c, w in ((c0, w0), (c1, w1)):
        np.testing.assert_allclose(
            np.asarray(w), 1.8 * np.take_along_axis(s, np.asarray(c), -1),
            rtol=1e-6)


def test_idle_rows_touch_no_expert_and_are_counted_nowhere():
    layer = _expert_layer(6)
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 32))
    live = jnp.asarray([True, False, True, False, False, True, False, False])
    y, counts = moe.moe_dropless(x, layer, 2, 1.8, live=live)
    only, n = moe.moe_dropless(x[live], layer, 2, 1.8)
    assert np.array_equal(np.asarray(counts), np.asarray(n))
    assert int(counts.sum()) == 3 * 2
    np.testing.assert_allclose(np.asarray(y[live]), np.asarray(only),
                               atol=1e-6)
    assert float(jnp.abs(y[~live]).max()) == 0.0


# (rows, first held expert, experts held of 64): one row; a decode step of
# 32 with idle rows; more rows than a tile (which keep the grouped matmul); a
# share of the experts, in a decode step and past a tile
SWIGLU = [(1, 0, 64), (32, 0, 64), (300, 0, 64), (32, 16, 16), (300, 48, 16)]


@pytest.mark.parametrize("T,first,held", SWIGLU, ids=str)
def test_swiglu_expert_kernel_in_interpret_mode_against_the_grouped_matmul(
        T, first, held):
    """The Pallas form of the SwiGLU experts (a decode step's rows through
    every held expert's three matrices; past a tile the grouped matmul
    whatever ``use_pallas`` says) against the grouped matmul over the rows
    as they lie: the same results to float32 rounding, the same counts, and
    0 in every idle row."""
    layer = _expert_layer(T, d=128, f=256, e=64)
    share = dict(layer, **{k: layer[k][first:first + held]
                           for k in ("w1", "w3", "w2")})
    x = jax.random.normal(jax.random.PRNGKey(T + 1), (T, 128))
    assert moe.expert_kernel_takes(x, share)
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        4, 1.8)
    live = jnp.arange(T) % 5 != 3
    want, n = moe.grouped_experts(x, chosen, w, share, live, first,
                                  use_pallas="off")
    got, m = jax.jit(lambda x, c, w, s: moe.grouped_experts(
        x, c, w, s, live, first, use_pallas="interpret"))(x, chosen, w, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.array_equal(np.asarray(n), np.asarray(m))
    assert not np.any(np.asarray(got)[~np.asarray(live)])
    assert int(m.sum()) == (int(jnp.sum((chosen[live] >= first)
                                        & (chosen[live] < first + held)))
                            if held < 64 else 4 * int(live.sum()))



# (rows, first held expert, experts held, the router's width, an expert the
# router's bias sends every row to): one row; a decode step of 12; more rows
# than a tile; a share of the experts (16 of 64), in a decode step and past a
# tile; past a tile with one expert's rows over a pass (128 rows here), which
# takes three passes
BLOCKED = [(1, 0, 16, 16, None), (12, 0, 16, 16, None),
           (300, 0, 16, 16, None), (12, 16, 16, 64, None),
           (300, 16, 16, 64, None), (400, 0, 16, 16, 3)]


@pytest.mark.parametrize("T,first,held,width,push", BLOCKED, ids=str)
def test_blocked_swiglu_kernel_in_interpret_mode_against_the_grouped_matmul(
        T, first, held, width, push, monkeypatch):
    """The F-blocked form of the SwiGLU experts, for experts too wide for
    the whole-matrix kernel's VMEM (here a VMEM of 1 MiB refuses experts of
    128 x 512 and takes blocks of 128 of their 512 columns, so that four
    blocks add up): at a decode step the held experts' blocks over the
    step's rows, past a tile the held assignments in passes fetched and
    added back a row at a time; against the grouped matmul over the rows as
    they lie: the same results to float32 rounding, the same counts, and 0
    in every idle row."""
    monkeypatch.setattr(moe, "_EXPERT_VMEM", 1 << 20)
    layer = _expert_layer(T, d=128, f=512, e=width)
    if push is not None:
        layer["bias"] = layer["bias"].at[push].set(100.0)
    share = dict(layer, **{k: layer[k][first:first + held]
                           for k in ("w1", "w3", "w2")})
    x = jax.random.normal(jax.random.PRNGKey(T + 1), (T, 128))
    assert not moe.expert_kernel_takes(x, share)
    assert moe.expert_blocks_take(x, share)
    assert moe._expert_block(x, share) == 128
    assert moe._pass_rows(x, share) == 128
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        4, 1.8)
    live = jnp.arange(T) % 5 != 3
    want, n = moe.grouped_experts(x, chosen, w, share, live, first,
                                  use_pallas="off")
    got, m = jax.jit(lambda x, c, w, s: moe.grouped_experts(
        x, c, w, s, live, first, use_pallas="interpret"))(x, chosen, w, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.array_equal(np.asarray(n), np.asarray(m))
    assert not np.any(np.asarray(got)[~np.asarray(live)])
    assert int(m.sum()) == (int(jnp.sum((chosen[live] >= first)
                                        & (chosen[live] < first + held)))
                            if held < width else 4 * int(live.sum()))
    if push is not None:
        assert int(m[push]) == int(live.sum()) > 2 * 128


# ------------------------------------------------------- the latent kernel
def _latent_case(lengths, width=4, heads=5, w=256, seed=0, pages=12, L=2):
    """Rows of ``lengths`` on shuffled page ids; every table entry a row
    does not own points at the sink, and the sink is NaN."""
    rng = np.random.default_rng(seed)
    B = len(lengths)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    pool = arr(L, pages + 1, PAGE, w).at[:, pages].set(jnp.nan)
    ids = iter(rng.permutation(pages))
    table = np.full((B, width), pages, np.int32)
    for b, n in enumerate(lengths):
        for i in range(-(-n // PAGE)):
            table[b, i] = next(ids)
    return dict(q=arr(B, heads, w), pool=pool, cur=arr(B, w),
                table=jnp.asarray(table),
                lengths=jnp.asarray(lengths, jnp.int32))


# nothing (a row on the sink), one position, a page, a page and one, all
RAGGED = [0, 1, PAGE, PAGE + 1, 4 * PAGE]


@pytest.mark.parametrize("layer", [0, 1])
def test_latent_kernel_in_interpret_mode_against_its_plain_reading(layer):
    c = _latent_case(RAGGED)
    kw = dict(layer=layer, value_width=128, scale=0.1)
    want = latent_attention_reference(c["q"], c["pool"], c["lengths"],
                                      c["table"], c["cur"], **kw)
    got = latent_attention(c["q"], c["pool"], c["lengths"], c["table"],
                           c["cur"], use_pallas="interpret", **kw)
    assert got.shape == (len(RAGGED), 5, 128)
    assert not bool(jnp.any(jnp.isnan(got)))     # the sink is never read
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    # a row with nothing cached sees the token it is computing, alone
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32),
        np.broadcast_to(np.asarray(c["cur"][0, :128], np.float32), (5, 128)),
        atol=1e-2)
    # and by hand, row 3 (a page and one): softmax over its 17 + 1 keys
    q, pool = (np.asarray(c[n], np.float32) for n in ("q", "pool"))
    rows = pool[layer][np.asarray(c["table"])[3, :2]].reshape(-1, 256)[
        :PAGE + 1]
    rows = np.vstack([rows, np.asarray(c["cur"], np.float32)[3][None]])
    s = q[3] @ rows.T * 0.1
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[3], np.float32),
                               p @ rows[:, :128], atol=3e-2)


def test_the_compiled_kernel_is_for_whole_lanes_and_the_rest_reads_plainly():
    bf = jnp.bfloat16
    q = jnp.zeros((2, 20, 640), bf)
    assert latent_kernel_takes(q, jnp.zeros((1, 3, 512, 640), bf), 512)
    assert not latent_kernel_takes(q[..., :576],
                                   jnp.zeros((1, 3, 512, 576), bf), 512)
    assert not latent_kernel_takes(q, jnp.zeros((1, 3, 512, 640),
                                                jnp.float32), 512)
    assert not latent_kernel_takes(q, jnp.zeros((1, 3, 12, 640), bf), 512)


# ------------------------------------------------------------ the page pool
def test_the_pool_holds_what_the_model_says_a_token_leaves():
    pool = KVPagePool(CFG, max_slots=2, page_tokens=PAGE)
    # one vector a token and layer: 32 + 4 values in whole lanes of 128
    assert CFG.latent_width == 36 and CFG.cache_width == 128
    assert pool.token_bytes == CFG.n_layers * 128 * 4
    arrays = pool.allocate()
    assert set(arrays) == {"latent"}
    assert arrays["latent"].shape == (CFG.n_layers, pool.capacity_pages + 1,
                                      PAGE, 128)
    assert pool.stats()["store_bytes"] == arrays["latent"].nbytes
    # at GLM-4.7-Flash's ranks: 576 values a token and layer, held as 640
    glm = dataclasses.replace(CFG, kv_lora_rank=512, qk_rope_head_dim=64,
                              n_layers=7, dtype=jnp.bfloat16)
    assert (glm.latent_width, glm.cache_width) == (576, 640)
    assert KVPagePool(glm, 2, 512).token_bytes == 7 * 640 * 2 == 8960
    # the dense decoder's pool is K and V as before, byte for byte
    dense = gpt.PRESETS["test"]
    kv = KVPagePool(dense, max_slots=2, page_tokens=PAGE)
    got = kv.allocate()
    shape = (dense.n_layers, dense.kv_heads, kv.capacity_pages + 1, PAGE,
             dense.head_dim)
    assert {k: v.shape for k, v in got.items()} == {"k": shape, "v": shape}
    assert kv.token_bytes == 2 * dense.n_layers * dense.kv_heads \
        * dense.head_dim * jnp.dtype(dense.dtype).itemsize
    assert kv.stats()["store_bytes"] == got["k"].nbytes + got["v"].nbytes
    # reservation, table and sink mean what they meant
    assert pool.reserve(0, 3 * PAGE) and pool.pages_in_use == 3
    assert list(pool.table[0, 3:]) == [pool.sink_page] * (
        pool.table_width - 3)
    pool.free(0)
    assert pool.pages_in_use == 0


def test_the_engine_finds_a_configurations_model():
    assert serving_model(CFG) is latent_moe
    assert serving_model(gpt.PRESETS["test"]) is gpt
    with pytest.raises(TypeError):
        serving_model({"d_model": 64})
    for model in (gpt, latent_moe):
        for name in ("init_params", "cache_spec", "prefill_row",
                     "paged_decode"):
            assert callable(getattr(model, name)), (model.__name__, name)


# ------------------------------------------------------------- the model
def _prefill(params, prompt, bucket, pages_of, table_row):
    """What the engine's prefill does with a row: its cache into its pages."""
    toks = np.ones((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, row = latent_moe.prefill_row(params, jnp.asarray(toks), CFG,
                                         bucket, len(prompt))
    n = bucket // PAGE
    paged = row["latent"].reshape(CFG.n_layers, n, PAGE, CFG.cache_width)
    return logits, pages_of.at[:, table_row[:n]].set(paged)


def test_prefill_then_decode_through_latent_pages_equals_the_forward(params):
    """A row prefilled into pages 5, 2, 7, ... then 40 absorbed decode steps
    across two page boundaries, beside an idle row and a second live one:
    the logits of every step are the full (plain) forward's."""
    rng = np.random.default_rng(0)
    seq = rng.integers(2, CFG.vocab_size, 20 + 40).tolist()
    other = rng.integers(2, CFG.vocab_size, 9).tolist()
    want = latent_moe.forward(params, jnp.asarray([seq]), CFG)[0]
    sink = 10
    pool = {"latent": jnp.zeros((CFG.n_layers, sink + 1, PAGE,
                                 CFG.cache_width), jnp.float32
                                ).at[:, sink].set(jnp.nan)}
    table = np.full((3, 8), sink, np.int32)
    table[0, :4] = [5, 2, 7, 0]
    table[2, :2] = [9, 3]
    first, pool["latent"] = _prefill(params, seq[:20], 32, pool["latent"],
                                     table[0])
    _, pool["latent"] = _prefill(params, other, 16, pool["latent"], table[2])
    np.testing.assert_allclose(np.asarray(first), np.asarray(want[19]),
                               atol=1e-4)
    step = jax.jit(lambda pool, last, pos, lens: latent_moe.paged_decode(
        params, last, pool, pos, lens, jnp.asarray(table), CFG))
    tokens = 0
    for t in range(40):
        pos = np.asarray([20 + t, 0, 9 + t], np.int32)
        last = np.asarray([seq[20 + t], 1, other[-1]], np.int32)
        logits, pool, counts = step(pool, jnp.asarray(last),
                                    jnp.asarray(pos),
                                    jnp.asarray([20 + t, 0, 9 + t]))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[20 + t]), atol=1e-4)
        tokens += int(counts["expert_tokens"].sum())
        # two live rows, two experts each, in each of the two expert layers
        assert int(counts["expert_layer_steps"]) == 2
        assert 2 * 2 <= int(counts["experts_touched"]) <= 2 * 4
    assert tokens == 40 * 2 * 2 * 2             # the idle row counted nowhere
    # the idle row wrote the sink and nothing else; no row read it
    assert not bool(jnp.any(jnp.isnan(logits[jnp.asarray([0, 2])])))
    assert not bool(jnp.any(jnp.isnan(pool["latent"][:, :sink])))


def test_absorbed_decode_equals_the_plain_form(params):
    """One layer's attention both ways over the same 37 cached positions:
    K and V expanded from the cache (plain), and kv_b folded into the query
    and applied after the attention (absorbed)."""
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(2), (38, CFG.d_model))
    positions = jnp.arange(38)
    plain, cached = latent_moe._attend_plain(h, layer, positions, CFG)
    lengths = jnp.asarray([37], jnp.int32)
    pages = jnp.pad(cached[:37], ((0, 11), (0, 0))).reshape(
        1, 3, PAGE, CFG.cache_width)

    def attend(q, cur):
        return latent_attention(q, pages, lengths, jnp.asarray([[0, 1, 2]]),
                                cur, value_width=CFG.kv_lora_rank,
                                scale=CFG.qk_head_dim ** -0.5)

    absorbed, cur = latent_moe._attend_absorbed(h[37:], layer,
                                                positions[37:], attend, CFG)
    np.testing.assert_allclose(np.asarray(absorbed[0]),
                               np.asarray(plain[37]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cur[0]), np.asarray(cached[37]),
                               atol=1e-6)
    # kv_b is held once: the two halves are views of the one matrix
    to_k, to_v = latent_moe._kv_b_halves(layer, CFG)
    assert to_k.size + to_v.size == layer["kv_b"].size


def test_the_engine_serves_the_model_and_counts_live_rows_only(params):
    """LLMServer(config=...) through ContinuousBatcher and KVPagePool: greedy
    tokens equal the full forward's argmax, and the engine's counts hold the
    live rows' assignments and nothing of the idle slots."""
    from ray_memory_management_tpu.serve.llm import LLMServer

    srv = LLMServer(config=CFG, max_batch_size=4, max_new_tokens=12,
                    pad_multiple=16, steps_per_iter=4, kv_page_tokens=PAGE,
                    seed=7)
    try:
        assert srv.cfg is CFG
        prompt = list(range(2, 25))
        out = srv.generate(prompt, max_new_tokens=9)
        logits = latent_moe.forward(srv.params, jnp.asarray(
            [prompt + out[:-1]]), CFG)[0]
        assert out == np.argmax(np.asarray(logits[len(prompt) - 1:]),
                                -1).tolist()
        e = srv.stats()["engine"]
        assert e["cache_token_bytes"] == CFG.n_layers * CFG.cache_width * 4
        # 8 decode steps (two iterations of four) of one live row of four:
        # 2 experts in each of 2 expert layers a step
        assert sum(e["expert_tokens"]) == 8 * 2 * 2
        assert e["expert_layer_steps"] == 8 * 2
        assert 8 * 2 <= e["experts_touched"] <= 8 * 2 * 2
        assert len(e["expert_tokens"]) == CFG.n_routed_experts
        assert e["live_positions"] == 23 + 27
        assert srv.stats()["kv"]["pages_in_use"] == 0
    finally:
        srv._engine.close()
