"""The layer-pattern model on the serve path (models/nemotron_h.py: Mamba-2,
attention and latent-space expert layers in a pattern, one chip's share of a
layer's experts; ops/ssm.py at heads of 64 channels, two to a row of lanes;
ops/moe.py's share cut), at toy widths with every kind of layer present.

CPU: what is checked is the arithmetic and the bookkeeping, not a speed. The
comparison with the plain reference is tests/chipbench_tests/
test_nemotron_h_cell.py's.
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_memory_management_tpu.models import (hybrid_ssm, nemotron_h,
                                              serving_model)
from ray_memory_management_tpu.ops import moe, ssm
from ray_memory_management_tpu.serve.kv_cache import KVPagePool

from lm_oracle import greedy_answer

CFG = nemotron_h.NemotronHConfig(
    vocab_size=512, d_model=64, pattern="MEM*EM", n_heads=4, kv_heads=2,
    head_dim=16, ssm_heads=8, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
    moe_latent=32, moe_d_ff=48, shared_d_ff=96, n_routed_experts=32,
    n_held_experts=8, first_held_expert=8, experts_per_tok=6,
    routed_scaling_factor=5.0, max_seq=128, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 16


@pytest.fixture(scope="module")
def params():
    """The plain init, with biases that are not 0 and uneven router gains."""
    out = nemotron_h.init_params(jax.random.PRNGKey(7), CFG)
    for i, (kind, layer) in enumerate(zip(CFG.pattern, out["layers"])):
        k = jax.random.PRNGKey(i)
        if kind == "M":
            layer["conv_b"] = 0.1 * jax.random.normal(k, layer["conv_b"].shape)
        if kind == "E":
            layer["moe"]["bias"] = 0.05 * jax.random.normal(
                k, layer["moe"]["bias"].shape)
    return out


def _inputs(T, H=4, P=64, G=2, N=128, seed=0, dtype=jnp.float32):
    """x, dt, A, B, C, D of a row of T positions: decays from a few
    positions to hundreds."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), jnp.float32, 0.0, 2.7)),
            jax.random.normal(k[3], (T, G, N), dtype),
            jax.random.normal(k[4], (T, G, N), dtype),
            1.0 + 0.1 * jax.random.normal(k[5], (H,)))


# --------------------------------------- ops/ssm.py at heads of 64 channels
@pytest.mark.parametrize("P,N,H,G", [(64, 128, 4, 2), (64, 128, 8, 1),
                                     (128, 256, 4, 2)],
                         ids=["nemotron", "one-group", "falcon"])
@pytest.mark.parametrize("T,true_len", [(256, None), (300, 211), (512, 257)])
def test_scan_kernel_in_interpret_mode_at_both_head_sizes(P, N, H, G, T,
                                                          true_len):
    """The Pallas form of the chunked scan against the sequential form, at
    heads of 64 channels (Nemotron-H's: state 128) and of 128 (Falcon-H1's:
    state 256): rows that are and are not whole chunks, a ``true_len`` inside
    a chunk and one past a chunk's edge."""
    a = _inputs(T, H=H, P=P, G=G, N=N, seed=T + P)
    assert ssm.ssd_kernel_takes(a[0], a[3])
    want_y, want_h = ssm.ssd_sequential(*a, true_len=true_len)
    y, h = jax.jit(lambda *a: ssm.ssd_scan(
        *a, use_pallas="interpret", true_len=true_len))(*a)
    n = T if true_len is None else true_len
    assert y.shape == (T, H, P) and h.shape == (H, N, P)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=2e-4)
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(want_y[:n]),
                               atol=2e-3)


def test_scan_kernel_takes_half_rows_of_lanes_only_in_whole_rows():
    bf = jnp.bfloat16
    x, b = jnp.zeros((512, 128, 64), bf), jnp.zeros((512, 8, 128), bf)
    assert ssm.ssd_kernel_takes(x, b)                   # Nemotron-H's
    assert ssm.ssd_kernel_takes(jnp.zeros((512, 32, 128), bf),
                                jnp.zeros((512, 2, 256), bf))  # Falcon-H1's
    assert not ssm.ssd_kernel_takes(x[:100], b[:100])   # under a chunk
    # one head of 64 channels a group: a block would be half a row of lanes
    assert not ssm.ssd_kernel_takes(jnp.zeros((512, 8, 64), bf), b)
    assert not ssm.ssd_kernel_takes(jnp.zeros((512, 8, 32), bf),
                                    jnp.zeros((512, 2, 128), bf))


def test_heads_lie_side_by_side_and_come_apart_again():
    assert [ssm.heads_a_row(p) for p in (16, 32, 64, 128, 256, 48)] \
        == [8, 4, 2, 1, 1, 1]
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8, 6, 64))
    packed = ssm.pack_heads(h, 2)
    assert packed.shape == (3, 5, 4, 6, 128)
    # head 2j in the row's first lanes, head 2j + 1 in its last
    assert bool(jnp.array_equal(packed[..., 1, :, :64], h[..., 2, :, :]))
    assert bool(jnp.array_equal(packed[..., 1, :, 64:], h[..., 3, :, :]))
    assert bool(jnp.array_equal(ssm.unpack_heads(packed, 2), h))
    assert ssm.pack_heads(h, 1) is h and ssm.unpack_heads(h, 1) is h


def _decode_inputs(L, S, H, P, G, N, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (L, S, H, N, P)),
            jax.random.normal(k[1], (S, H, P)),
            jax.nn.softplus(jax.random.normal(k[2], (S, H))),
            -jnp.exp(jax.random.uniform(k[3], (H,), jnp.float32, 0.0, 2.7)),
            jax.random.normal(k[4], (S, G, N)),
            jax.random.normal(k[5], (S, G, N)),
            1.0 + 0.1 * jax.random.normal(k[6], (H,)))


@pytest.mark.parametrize("H,P,G,N", [(8, 64, 2, 16), (4, 128, 2, 8)],
                         ids=["nemotron", "falcon"])
@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 0], [0, 0, 0, 0, 0, 1],
                                  [1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 0]])
def test_update_kernel_in_interpret_mode_against_the_sequential_form(
        H, P, G, N, live):
    """One token-step of layer 1 of 2 against a state whose heads lie
    ``128 // P`` to a row: a live slot's state and output are one position
    of ``ssd_sequential`` from that slot's state, an idle slot's state comes
    back bit for bit, the other layer is not touched, and the kernel counts
    the rows it fetched (the plain form reads them all)."""
    state, x, dt, A, B, C, D = _decode_inputs(2, 6, H, P, G, N)
    k = ssm.heads_a_row(P)
    resident = ssm.pack_heads(state, k)
    assert resident.shape == (2, 6, H // k, N, 128)
    assert ssm.ssm_kernel_takes(resident, x)
    live = jnp.asarray(live, bool)
    want = [ssm.ssd_sequential(x[s:s + 1], dt[s:s + 1], A, B[s:s + 1],
                               C[s:s + 1], D, h0=state[1, s])
            for s in range(6)]
    for use, reads in (("off", 6), ("interpret", int(live.sum()))):
        y, new, fetched = jax.jit(lambda *a: ssm.ssm_decode_update(
            *a, layer=1, use_pallas=use))(resident, x, dt, A, B, C, D, live)
        assert new.shape == resident.shape and int(fetched) == reads
        new = ssm.unpack_heads(new, k)
        for s in range(6):
            if bool(live[s]):
                np.testing.assert_allclose(
                    np.asarray(new[1, s]), np.asarray(want[s][1]), atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(y[s]), np.asarray(want[s][0][0]), atol=1e-4)
            else:
                assert np.array_equal(np.asarray(new[1, s]),
                                      np.asarray(state[1, s]))
                assert not np.any(np.asarray(y[s]))
        assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))


def test_the_update_kernel_moves_a_groups_pairs_in_one_block():
    f32 = jnp.float32
    # Nemotron-H's resident state: 64 rows of two heads, 8 rows a group
    assert ssm.ssm_kernel_takes(jnp.zeros((1, 1, 64, 128, 128), f32),
                                jnp.zeros((1, 128, 64), f32))
    assert ssm._head_block(8, 128, 128) == 8          # 512 KiB a grid step
    # held a head a row, 64 channels would not fill the lanes
    assert not ssm.ssm_kernel_takes(jnp.zeros((1, 1, 128, 128, 64), f32),
                                    jnp.zeros((1, 128, 64), f32))


# ------------------------------------------- ops/moe.py: a share of a layer
def _expert_layer(key, D=32, F=24, E=16, gated=False):  # toy: no kernel
    k = jax.random.split(key, 6)
    layer = {"router": jax.random.normal(k[0], (D, E)) * D ** -0.5,
             "bias": 0.05 * jax.random.normal(k[1], (E,)),
             "w1": jax.random.normal(k[2], (E, D, F)) * D ** -0.5,
             "w2": jax.random.normal(k[3], (E, F, D)) * F ** -0.5}
    if gated:
        layer["w3"] = jax.random.normal(k[4], (E, D, F)) * D ** -0.5
    return layer


def _dense(x, chosen, w, layer, first=0):
    """Every held expert over every token, weighed by hand."""
    out = jnp.zeros((x.shape[0], layer["w2"].shape[-1]))
    for e in range(layer["w1"].shape[0]):
        a = x @ layer["w1"][e]
        h = jax.nn.silu(a) * (x @ layer["w3"][e]) if "w3" in layer \
            else jnp.square(jax.nn.relu(a))
        gate = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        out = out + gate[:, None] * (h @ layer["w2"][e])
    return out


@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "swiglu"])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole(gated):
    """Four shares of 4 of 16 experts, each told the first id it holds,
    routed over all 16: each is the dense sum over its own experts with the
    weights the router gave (normalised over all of a token's 5), their sum
    is the layer with every expert held, and the counts of all shares are
    the assignments of all tokens. Both forms of expert."""
    layer = _expert_layer(jax.random.PRNGKey(3), gated=gated)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 32))
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        5, 5.0)
    whole, counts = moe.grouped_experts(x, chosen, w, layer)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(_dense(x, chosen, w, layer)),
                               atol=2e-5)
    parts, held = [], []
    for first in (0, 4, 8, 12):
        share = dict(layer, **{k: layer[k][first:first + 4]
                               for k in layer if k.startswith("w")})
        y, n = jax.jit(lambda x, c, w, s: moe.grouped_experts(
            x, c, w, s, None, first))(x, chosen, w, share)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(_dense(x, chosen, w, share, first)),
            atol=2e-5)
        assert n.shape == (4,)
        parts.append(y), held.append(n)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=5e-5)
    assert np.array_equal(np.concatenate(held), np.asarray(counts))
    assert int(counts.sum()) == 24 * 5
    # a share is a part, not the whole scaled: its weights are not renormed
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 0.1


@pytest.mark.parametrize("T,k,first", [(24, 6, 16), (300, 8, 0), (1, 6, 48),
                                       (128, 22, 16)])
def test_expert_tile_kernel_in_interpret_mode_against_the_grouped_matmul(
        T, k, first):
    """The Pallas form of the two-matrix experts (each expert's sorted rows
    from a tile boundary of their own, a tile's two matmuls in one visit)
    against the grouped matmul over the rows as they lie, for a share of 16
    of 64 experts: one row, a decode step's 128, more rows than a tile an
    expert; idle rows among them; the counts are the same."""
    layer = _expert_layer(jax.random.PRNGKey(T), D=128, F=256, E=64)
    share = dict(layer, w1=layer["w1"][first:first + 16],
                 w2=layer["w2"][first:first + 16])
    x = jax.random.normal(jax.random.PRNGKey(4), (T, 128))
    assert moe.expert_kernel_takes(x, share)
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        k, 5.0)
    live = jnp.arange(T) % 5 != 3
    want, n = moe.grouped_experts(x, chosen, w, share, live, first,
                                  use_pallas="off")
    got, m = jax.jit(lambda x, c, w, s: moe.grouped_experts(
        x, c, w, s, live, first, use_pallas="interpret"))(x, chosen, w, share)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.array_equal(np.asarray(n), np.asarray(m))
    assert not np.any(np.asarray(got)[~np.asarray(live)])
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(_dense(x, jnp.where(
            live[:, None], chosen, -1), w, share, first)), atol=5e-5)


def test_the_expert_kernel_takes_either_form_in_whole_lanes_within_vmem():
    bf = jnp.bfloat16
    z = lambda *shape: jnp.zeros(shape, bf)  # noqa: E731
    nemotron = {"w1": z(4, 1024, 2688), "w2": z(4, 2688, 1024)}
    assert moe.expert_kernel_takes(z(128, 1024), nemotron)

    def swiglu(d, f):
        return {"w1": z(4, d, f), "w3": z(4, d, f), "w2": z(4, f, d)}

    # glm-4.7-flash-d7's SwiGLU experts: two experts' three matrices are
    # 37.7 MB; glm-5.2-d6-e16's are 151 MB, past the kernel's VMEM, and take
    # its blocked form, in blocks of 512 of their 2,048 columns, and only
    # they: refused for VMEM alone, SwiGLU, whole lanes, one type
    assert moe.expert_kernel_takes(z(32, 2048), swiglu(2048, 1536))
    assert not moe.expert_kernel_takes(z(32, 6144), swiglu(6144, 2048))
    assert moe.expert_blocks_take(z(32, 6144), swiglu(6144, 2048))
    assert moe._expert_block(z(32, 6144), swiglu(6144, 2048)) == 512
    assert not moe.expert_blocks_take(z(32, 2048), swiglu(2048, 1536))
    assert not moe.expert_blocks_take(z(32, 6144), dict(
        swiglu(6144, 2048), w3=jnp.zeros((4, 6144, 2048))))
    shape = jax.ShapeDtypeStruct   # a shape suffices: 403 MB a matrix
    assert not moe.expert_blocks_take(z(32, 6144), {
        "w1": shape((4, 6144, 8192), bf), "w2": shape((4, 8192, 6144), bf)})
    assert not moe.expert_kernel_takes(z(32, 2048), dict(
        swiglu(2048, 1536), w3=jnp.zeros((4, 2048, 1536))))
    assert not moe.expert_kernel_takes(z(8, 32), {"w1": z(4, 32, 24),
                                                  "w2": z(4, 24, 32)})
    assert not moe.expert_kernel_takes(jnp.zeros((8, 1024)), nemotron)
    # a decode step of 128 rows is one tile of rows: a tile a held expert;
    # a bucket of 2,048: the assignments' own 352 tiles and a tile's empty
    # rest a held expert
    assert moe.expert_tiles(128, 22, 128) == 128
    assert moe.expert_tiles(129, 22, 128) == 23 + 128
    assert moe.expert_tiles(2048, 22, 128) == 480


def test_an_idle_row_of_a_share_is_routed_nowhere():
    layer = _expert_layer(jax.random.PRNGKey(5))
    share = dict(layer, w1=layer["w1"][4:8], w2=layer["w2"][4:8])
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 32))
    chosen, w = moe.route_sigmoid_top_k(x, layer["router"], layer["bias"],
                                        5, 5.0)
    live = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 0], bool)
    y, n = moe.grouped_experts(x, chosen, w, share, live, 4)
    all_y, all_n = moe.grouped_experts(x, chosen, w, share, None, 4)
    assert not np.any(np.asarray(y)[~np.asarray(live)])
    np.testing.assert_allclose(np.asarray(y)[np.asarray(live)],
                               np.asarray(all_y)[np.asarray(live)], atol=1e-6)
    by_hand = [int(jnp.sum((chosen[live] == 4 + e))) for e in range(4)]
    assert n.tolist() == by_hand and int(all_n.sum()) > int(n.sum())


# ------------------------------------------------- the model and the engine
def test_the_engine_finds_the_model_and_its_specifications_by_kind():
    assert serving_model(CFG) is nemotron_h
    for name in ("init_params", "cache_spec", "state_spec", "prefill_row",
                 "prefill_takes_kernel", "paged_decode", "forward",
                 "mixed_step"):
        assert callable(getattr(nemotron_h, name)), name
    assert (CFG.n_layers, CFG.count("M"), CFG.count("E"), CFG.count("*")) \
        == (6, 3, 2, 1)
    # one attention layer leaves K and V; three Mamba layers keep a state,
    # heads of 16 channels eight to a row; the expert layers hold nothing
    assert nemotron_h.cache_spec(CFG) == {
        "k": ((1, 2), (16,), jnp.float32), "v": ((1, 2), (16,), jnp.float32)}
    assert nemotron_h.state_spec(CFG) == {
        "ssm": ((3,), (1, 8, 128), jnp.float32),
        "conv": ((3, 3), (160,), jnp.float32)}
    pool = KVPagePool(CFG, max_slots=4, page_tokens=PAGE,
                      pool_bytes=8 * PAGE * 2 * 2 * 16 * 4)
    arrays = pool.allocate()
    assert {k: v.shape for k, v in arrays.items()} == {
        "k": (1, 2, 9, PAGE, 16), "v": (1, 2, 9, PAGE, 16),
        "ssm": (3, 4, 1, 8, 128), "conv": (3, 3, 4, 160)}
    assert pool.token_bytes == 2 * 2 * 16 * 4
    assert pool.state_row_bytes == 3 * (8 * 8 * 16 * 4 + 3 * 160 * 4)
    with pytest.raises(ValueError):
        nemotron_h.NemotronHConfig(**{**CFG.__dict__, "pattern": "MEX"})
    with pytest.raises(ValueError):
        nemotron_h.NemotronHConfig(**{**CFG.__dict__,
                                      "first_held_expert": 28})
    # the mixer's pieces are models/hybrid_ssm.py's own
    assert nemotron_h._ssm_project is hybrid_ssm._ssm_project
    assert nemotron_h._gate_out is hybrid_ssm._gate_out
    assert nemotron_h._carried is hybrid_ssm._carried


def _prefill(params, prompt, bucket, pool, table_row, slot):
    """What the engine's prefill does with a row: its K and V into its
    pages, its state into its slot's entry."""
    toks = np.full((1, bucket), 9, np.int32)  # the junk tail is not token 0
    toks[0, :len(prompt)] = prompt
    logits, row = jax.jit(lambda t, n: nemotron_h.prefill_row(
        params, t, CFG, bucket, n))(jnp.asarray(toks), len(prompt))
    n = bucket // PAGE
    pool = dict(pool)
    for name in ("k", "v"):
        pool[name] = pool[name].at[:, :, table_row[:n]].set(
            row[name].reshape(1, 2, n, PAGE, 16))
    pool["ssm"] = pool["ssm"].at[:, slot].set(row["ssm"])
    pool["conv"] = pool["conv"].at[:, :, slot].set(row["conv"])
    return logits, pool


def _empty_pool(slots, sink, fill=0.0):
    kv = jnp.zeros((1, 2, sink + 1, PAGE, 16), jnp.float32)
    return {"k": kv.at[:, :, sink].set(jnp.nan),
            "v": kv.at[:, :, sink].set(jnp.nan),
            "ssm": jnp.full((3, slots, 1, 8, 128), fill, jnp.float32),
            "conv": jnp.full((3, 3, slots, CFG.conv_width), fill,
                             jnp.float32)}


@pytest.mark.parametrize("n_prompt,bucket", [(20, 32), (9, 16), (33, 48)])
def test_prefill_then_decode_through_pages_and_state_equals_the_forward(
        params, n_prompt, bucket):
    """A row prefilled in a padded bucket (its prompt ending inside a page)
    into pages 5, 2, 7, ... and slot 0's state entry, then 40 decode steps
    across page boundaries beside an idle slot (whose entry is full of junk
    and must stay so, and which is routed to no expert) and a second live
    row: the logits of every step are the whole forward's, and the step
    counts what it did."""
    rng = np.random.default_rng(n_prompt)
    seq = rng.integers(2, CFG.vocab_size, n_prompt + 40).tolist()
    other = rng.integers(2, CFG.vocab_size, 9 + 40).tolist()
    want = nemotron_h.forward(params, jnp.asarray([seq]), CFG)[0]
    want_other = nemotron_h.forward(params, jnp.asarray([other]), CFG)[0]
    sink = 12
    pool = _empty_pool(3, sink, fill=3.0)
    table = np.full((3, 8), sink, np.int32)
    table[0, :6] = [5, 2, 7, 0, 10, 11]
    table[2, :4] = [9, 3, 1, 4]
    first, pool = _prefill(params, seq[:n_prompt], bucket, pool, table[0], 0)
    _, pool = _prefill(params, other[:9], 16, pool, table[2], 2)
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(want[n_prompt - 1]), atol=2e-4)
    step = jax.jit(lambda pool, last, pos, lens: nemotron_h.paged_decode(
        params, last, pool, pos, lens, jnp.asarray(table), CFG))
    held = 0
    for t in range(40):
        pos = np.asarray([n_prompt + t, 0, 9 + t], np.int32)
        last = np.asarray([seq[n_prompt + t], 1, other[9 + t]], np.int32)
        logits, pool, c = step(pool, jnp.asarray(last), jnp.asarray(pos),
                               jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(want[n_prompt + t]), atol=2e-4)
        np.testing.assert_allclose(np.asarray(logits[2]),
                                   np.asarray(want_other[9 + t]), atol=2e-4)
        # two live rows: 3 Mamba layers' states, 2 expert layers' 6 choices
        assert int(c["state_rows_stepped"]) == 2 * 3
        assert int(c["ssm_layer_steps"]) == 3
        assert int(c["state_rows_fetched"]) == 3 * 3   # the plain form: all
        assert int(c["expert_layer_steps"]) == 2
        assert int(c["expert_assignments"]) == 2 * 2 * 6
        assert c["expert_tokens"].shape == (8,)
        assert int(c["expert_assignments_held"]) \
            == int(c["expert_tokens"].sum()) <= 2 * 2 * 6
        assert int(jnp.sum(c["expert_tokens"] > 0)) \
            <= int(c["experts_touched"]) <= 2 * 8
        held += int(c["expert_assignments_held"])
    assert 0 < held < 40 * 24            # a share of the choices lands here
    # the idle slot's entry as it was, to the bit; the sink read by no row
    assert bool(jnp.all(pool["ssm"][:, 1] == 3.0))
    assert bool(jnp.all(pool["conv"][:, :, 1] == 3.0))
    assert not bool(jnp.any(jnp.isnan(logits[jnp.asarray([0, 2])])))


def test_a_step_with_no_live_row_counts_nothing_and_moves_nothing(params):
    pool = _empty_pool(2, 4, fill=2.0)
    table = np.full((2, 8), 4, np.int32)
    zero = jnp.zeros((2,), jnp.int32)
    _, new, c = jax.jit(lambda pool: nemotron_h.paged_decode(
        params, zero + 1, pool, zero, zero, jnp.asarray(table), CFG))(pool)
    # (off the TPU the plain update reads every slot's state all the same)
    assert all(int(jnp.sum(v)) == 0 for k, v in c.items()
               if k != "state_rows_fetched")
    assert bool(jnp.all(new["ssm"] == 2.0)) \
        and bool(jnp.all(new["conv"] == 2.0))


def test_the_held_share_is_what_the_program_computes(params):
    """The same weights with another share named (experts 0-7 and not 8-15)
    are another model; with every expert held the shares' sum."""
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 40), 0, 512)
    here = nemotron_h.forward(params, tokens, CFG)
    moved = nemotron_h.NemotronHConfig(**{**CFG.__dict__,
                                          "first_held_expert": 0})
    assert float(jnp.max(jnp.abs(
        nemotron_h.forward(params, tokens, moved) - here))) > 1e-2


# ------------------------------------- a prompt's chunks on the decode step
C = 2 * PAGE  # a chunk is two pages
MIXED_COUNTS = {"mixed_state_rows_stepped", "mixed_ssm_layer_steps",
                "mixed_expert_layer_steps", "mixed_expert_assignments_held",
                "mixed_experts_touched"}


def _chunked_beside_riders(params, n_prompt, *, fill=3.0, idle=True):
    """A prompt of ``n_prompt`` tokens through ``mixed_step``, chunk by
    chunk, into slot 1 and pages of its own, beside two rows that decode
    (slots 0 and 2) and an idle slot 3; every slot's entry starts as
    ``fill`` (junk an earlier request left, or zeros). ``idle`` false plants
    a fault: the row is live among the decode rows while its chunks ride.
    Returns what the chunks left (pool, the last chunk's logits), the same
    riders stepped by ``paged_decode`` alone, the whole prompt through
    ``prefill_row``, the prompt's pages, and each mixed step's counts beside
    (the riders' ``expert_assignments_held`` of ``paged_decode``, the
    chunk's own of a mixed step with every decode row idle)."""
    rng = np.random.default_rng(n_prompt)
    n_chunks, per = -(-n_prompt // C), C // PAGE
    sink, width = 4 * per + 8, 4 * per
    table = np.full((4, width), sink, np.int32)
    table[0, :2], table[2, :2] = [sink - 2, 1], [sink - 5, 3]
    mine = np.full(4 * per, sink, np.int32)
    mine[:n_chunks * per] = [sink - 1, 0, sink - 3, 2, sink - 4, 4,
                             sink - 6, 5][:n_chunks * per]
    riders = {0: rng.integers(2, CFG.vocab_size, 13).tolist(),
              2: rng.integers(2, CFG.vocab_size, 6).tolist()}
    pool, last = _empty_pool(4, sink, fill), [1, 1, 1, 1]
    for r, p in riders.items():
        logits, pool = _prefill(params, p, -(-len(p) // PAGE) * PAGE, pool,
                                table[r], r)
        last[r] = int(jnp.argmax(logits))
    prompt = rng.integers(2, CFG.vocab_size, n_prompt)
    toks = np.full(n_chunks * C, 9, np.int32)   # the junk tail is not token 0
    toks[:n_prompt] = prompt
    step = jax.jit(functools.partial(nemotron_h.mixed_step, cfg=CFG))
    alone = jax.jit(functools.partial(nemotron_h.paged_decode, cfg=CFG))
    mixed, plain, seen = pool, pool, []
    m_last = p_last = jnp.asarray(last, jnp.int32)
    off = np.asarray([13, 0, 6, 0], np.int32)
    none = jnp.zeros((4,), jnp.int32)
    for index in range(n_chunks):
        ends = index == n_chunks - 1
        lengths = off + index * (off > 0)
        if not idle:  # the fault: live at the positions its chunks filled
            lengths[1], table[1] = index * C, mine[:width]
        lengths = jnp.asarray(lengths)
        chunk = (jnp.asarray(toks[index * C:(index + 1) * C]),
                 jnp.asarray(mine),
                 jnp.int32(n_prompt - 1 - index * C if ends else C - 1))
        at = dict(chunk_index=jnp.int32(index), slot=jnp.int32(1))
        # the chunk beside no live row: what its real positions alone count
        _, _, own = step(params, mixed, *chunk, m_last, none, none,
                         jnp.asarray(table), **at)
        logits, mixed, counts = step(params, mixed, *chunk, m_last, lengths,
                                     lengths, jnp.asarray(table), **at)
        assert logits.shape == (5, CFG.vocab_size)
        # no count under the decode program's names: its readers count
        # token-steps of the decode program alone
        assert set(counts) == MIXED_COUNTS
        assert all(v.dtype == jnp.int32 and v.shape == ()
                   for v in counts.values())
        if idle:
            ref, plain, c = alone(params, p_last, plain, lengths, lengths,
                                  jnp.asarray(table))
            np.testing.assert_allclose(logits[:4][np.asarray([0, 2])],
                                       ref[np.asarray([0, 2])], atol=2e-4)
            p_last = jnp.argmax(ref, axis=-1)
            seen.append(({k: int(v) for k, v in counts.items()},
                         int(c["expert_assignments_held"]),
                         int(own["mixed_expert_assignments_held"])))
        m_last = jnp.argmax(logits[:4], axis=-1)
    want_logits, whole = _prefill(params, prompt, n_chunks * C,
                                  _empty_pool(4, sink, fill), mine, 1)
    return (mixed, logits[4], plain, want_logits, whole,
            mine[:n_chunks * per], seen)


@pytest.mark.parametrize("n_prompt,fill", [
    (2, 3.0), (C + 1, 3.0), (50, 3.0), (2 * C, 3.0), (70, 3.0), (70, 0.0)],
    ids=["under-the-taps", "one-past-a-chunk", "mid-chunk", "two-chunks",
         "three-chunks", "three-chunks-clean-slot"])
def test_chunks_leave_what_the_whole_prefill_leaves(params, n_prompt, fill):
    """Prompts that end inside their last chunk (one of them shorter than the
    convolution's ``ssm_conv - 1`` taps, one a single position into its
    second chunk, so that its tail reaches back into the chunk before) and
    one of whole chunks: the chunks leave the prompt's K and V in its pages,
    the packed state, the tail and the last position's logits of
    ``prefill_row``, **whether the slot held an earlier request's state and
    tail or zeros**; the riders get ``paged_decode``'s logits and state, the
    idle slot keeps its junk to the bit, and the counts are the mixed
    step's own: the chunk's padding and the idle rows reach no expert."""
    mixed, logits, plain, want_logits, whole, pages, seen = \
        _chunked_beside_riders(params, n_prompt, fill=fill)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               atol=2e-4)
    for name in ("k", "v"):  # the prompt's positions
        got, want = (np.asarray(a[name][:, :, pages]).reshape(
            1, 2, -1, 16)[:, :, :n_prompt] for a in (mixed, whole))
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert np.abs(got).max() > 0
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
    assert bool(jnp.all(mixed["ssm"][:, 3] == fill))
    assert bool(jnp.all(mixed["conv"][:, :, 3] == fill))
    for index, (c, riders_held, chunk_held) in enumerate(seen):
        # two riders live: three Mamba layers' update kernel moved them,
        # not the chunk's row; both expert layers ran
        assert c["mixed_state_rows_stepped"] == 2 * 3
        assert c["mixed_ssm_layer_steps"] == 3
        assert c["mixed_expert_layer_steps"] == 2
        # the riders' assignments are ``paged_decode``'s of the same tokens
        # and the chunk's those of its real positions alone: 6 choices a
        # position and layer of which a share is held; 30 rows of padding
        # beside 2 real ones would pass that bound
        real = min(C, n_prompt - index * C)
        assert c["mixed_expert_assignments_held"] \
            == riders_held + chunk_held
        assert 0 < chunk_held <= real * 2 * 6
        assert 0 < c["mixed_experts_touched"] <= 2 * 8


@pytest.mark.parametrize("fault", ["none", "live_in_the_decode_half",
                                   "carries_at_the_first_chunk_too"])
def test_only_the_chunks_move_the_prefilling_rows_state(params, fault,
                                                        monkeypatch):
    """Three chunks of a row that is idle among the decode rows leave the
    whole prefill's state. Were the row live there, the decode half would
    move its state between its chunks; were a first chunk to start from the
    slot's entry, it would carry on from an earlier request's: both faults
    show, so the comparison can fail."""
    if fault == "carries_at_the_first_chunk_too":
        monkeypatch.setattr(nemotron_h, "_carried",
                            lambda entry, first: entry)
    mixed, _, _, _, whole, _, _ = _chunked_beside_riders(
        params, 70, idle=fault != "live_in_the_decode_half")
    gap = float(jnp.max(jnp.abs(mixed["ssm"][:, 1] - whole["ssm"][:, 1])))
    assert (gap < 1e-4) if fault == "none" else (gap > 1e-3), gap


# ------------------------------------------------------ through the engine
def _server(**over):
    from ray_memory_management_tpu.serve.llm import LLMServer

    kw = dict(config=CFG, max_batch_size=3, max_new_tokens=24,
              pad_multiple=16, steps_per_iter=4, kv_page_tokens=PAGE, seed=7)
    kw.update(over)
    return LLMServer(**kw)


_greedy = functools.partial(greedy_answer, nemotron_h.forward, CFG)


def test_the_engine_serves_rows_of_different_lengths_together(params):
    """Five requests on three slots through ``LLMServer(config=...)``: short
    prompts in long buckets, rows that end inside an iteration, a slot
    admitted again: each answer is greedy decoding by the whole forward."""
    srv = _server(init=lambda key, cfg: params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, 512, n).tolist() for n in (3, 17, 30, 9, 21)]
    budgets = [10, 7, 12, 5, 9]
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = srv.generate(prompts[i], max_new_tokens=budgets[i])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(5)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    try:
        for p, b, o in zip(prompts, budgets, outs):
            assert len(o) == b and o == _greedy(params, p, o)
        eng = srv.stats()["engine"]
        assert len(eng["expert_tokens"]) == 8
        assert eng["expert_assignments"] > eng["expert_assignments_held"] > 0
        assert eng["expert_assignments_held"] == sum(eng["expert_tokens"])
        assert eng["state_rows_stepped"] > 0 and eng["ssm_layer_steps"] > 0
        assert eng["state_row_bytes"] == 3 * (8 * 8 * 16 * 4 + 3 * 160 * 4)
        assert eng["cache_token_bytes"] == 2 * 2 * 16 * 4
    finally:
        srv._engine.close()
