"""``minicpm-sala-d8`` and ``longdoc-batch``, rehearsed off the chip: the
program against the plain reference at toy widths (**a prompt in chunks, then
decode through the pool**, across chunk, page and block boundaries, the toy
``dense_len`` well under the toy context so that the selection engages),
planted faults read by the comparison, the toy cell through the serve
driver's closed loop with its control, the configuration's counts worked by
hand, the readers on hand-made numbers, and the cell's two step programs
compiled for a described v5e chip. No time read here is a device number.

The topology is described inside a module-scoped fixture only (every xdist
worker imports this file; only the one that runs it may load the TPU library).
"""

import json
import os
import re
import time

import numpy as np
import pytest

from chipbench import architectures, flops, harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import (block_score_roofline,
                               block_sparse_attention_roofline,
                               lightning_update_roofline,
                               selected_block_share,
                               sparse_linear_step_roofline)
from chipbench_config_checks import check_config_file

NAME, MIX = "minicpm-sala-d8", "longdoc-batch"
SEED = 2 ** 31 + 41  # the driver's seeds pass 32 signed bits
TOY = dict(
    name="toy-sala", architecture="sparse_linear", model_type="minicpm_sala",
    vocab_size=512, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    lightning_scale="1/sqrt(d)", lightning_use_rope=True,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    num_hidden_layers=4, max_position_embeddings=128, rms_norm_eps=1e-6,
    rope_theta=10000.0, scale_emb=12, scale_depth=1.4, residual_depth=32,
    dim_model_base=16, mup_denominator=32, attention_bias=False,
    attn_use_rope=False, attn_use_output_gate=True, use_output_gate=True,
    use_output_norm=True, qk_norm=True, rand_init=False, hidden_act="silu",
    tie_word_embeddings=False,
    sparse_config={"block_size": 4, "topk": 6, "kernel_size": 4,
                   "kernel_stride": 2, "init_blocks": 1, "window_size": 8,
                   "dense_len": 24},
    param_dtype="float32", activation_dtype="float32")
TOY_BATCH = {
    "name": "toy-longdoc", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.4,
                      "min": 24, "max": 80},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 2, "max": 24},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 24},
    "trace_seconds": 1.0, "check": {"requests": 8, "gap_limit": 0.05}}
CELL = {"name": "toy", "chips": 1}
PAGE, CHUNK, ROWS, PAGES = 8, 16, 3, 20
FAULT_LIMIT = 0.02  # the faithful program reads 0 here; the toy cell's is 0.05


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def both():
    """(architecture, program config, the recipe's weights), float32."""
    import jax

    arch = architectures.of(TOY)
    pc = arch.program_config(TOY)
    return arch, pc, arch.init_program_params(jax.random.PRNGKey(SEED), pc)


def through_the_pool(model, pc, params, tokens, n_prompt, row=1):
    """The program's logits at positions ``n_prompt - 1`` .. of ``tokens``:
    the first ``n_prompt`` as a prompt in chunks of ``CHUNK`` (two pages each)
    on scattered pages, the rest one decode step each, in slot ``row`` of
    ``ROWS`` with the others idle and the slot's state entry full of junk;
    and the last step's counts."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.serve.kv_cache import _entry

    pool = {}
    for name, item in model.cache_spec(pc).items():
        lead, trail, dtype, stride = _entry(item)
        pool[name] = jnp.zeros(lead + (PAGES + 1, PAGE // stride) + trail,
                               dtype)
    for name, (lead, trail, dtype) in model.state_spec(pc).items():
        pool[name] = jnp.full(lead + (ROWS,) + trail, 7.0, dtype)
    width = pc.max_seq // PAGE
    mine = np.full(width, PAGES, np.int32)      # sink past what is reserved
    mine[:10] = [7, 3, 11, 0, 5, 9, 2, 14, 1, 19]
    table = np.full((ROWS, width), PAGES, np.int32)
    lengths = np.zeros(ROWS, np.int32)
    mixed = jax.jit(model.mixed_step, static_argnames=("cfg",))
    decode = jax.jit(model.paged_decode, static_argnames=("cfg",))
    toks, out, per = np.asarray(tokens), {}, CHUNK // PAGE
    reach = -(-n_prompt // CHUNK) * per
    put = jnp.array  # a copy: the host arrays below change between steps
    for ci in range(reach // per):
        real = min(CHUNK, n_prompt - ci * CHUNK)
        chunk = np.ones(CHUNK, np.int32)
        chunk[:real] = toks[ci * CHUNK:ci * CHUNK + real]
        logits, pool, counts = mixed(
            params, pool, put(chunk), put(mine[:reach]),
            jnp.int32(real - 1), jnp.ones(ROWS, jnp.int32), put(lengths),
            put(lengths), put(table), pc, chunk_index=jnp.int32(ci),
            slot=jnp.int32(row))
    out[n_prompt - 1] = np.asarray(logits[-1])
    table[row], lengths[row] = mine, n_prompt
    for t in range(n_prompt, len(toks)):
        tk = np.ones(ROWS, np.int32)
        tk[row] = toks[t]
        logits, pool, counts = decode(
            params, put(tk), pool, put(lengths), put(lengths), put(table), pc)
        out[t] = np.asarray(logits[row])
        lengths[row] += 1
    return out, counts


def gap_of(ref, got):
    """The check's statistic: how far the reference's logit of the token the
    program puts first lies below the reference's best, at the worst of the
    positions ``got`` has."""
    return max(float(ref[t].max() - ref[t][int(np.argmax(np.asarray(g)))])
               for t, g in got.items())


def test_the_recipes_weights_fit_the_programs_tree(both):
    """The benchmark hands the program the recipe's weights
    (``LLMServer(init=...)``): the tree, shapes and types of the program's
    own plain init, an output norm in the lightning layers alone."""
    import jax

    from ray_memory_management_tpu.models import serving_model, sparse_linear

    arch, pc, ours = both
    assert serving_model(pc) is sparse_linear
    plain = sparse_linear.init_params(jax.random.PRNGKey(SEED), pc)
    assert jax.tree.structure(ours) == jax.tree.structure(plain)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(plain)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(ours)) \
        == arch.n_params(TOY)
    assert arch.server_kwargs(TOY)["init"] is arch.init_program_params
    assert ["out_ln" in p for p in ours["layers"]] == [False, True, True,
                                                       False]
    assert ours["layers"][0]["k"].shape == (64, 2 * 16)
    assert ours["layers"][1]["k"].shape == (64, 4 * 16)


@pytest.mark.parametrize("n_prompt", [27, 32, 9])
def test_a_prompt_in_chunks_then_decode_against_the_reference(both, n_prompt):
    """Prompts of 27 (a chunk and part of one), 32 (two whole chunks) and 9
    (under one page), then decode to 60 positions, past the toy
    ``dense_len`` of 24: every logit within float32 rounding of the
    reference's full forward, across chunk, page and block boundaries, the
    pooled windows that straddle them, and the slot's state carried from
    chunk to chunk and into decode."""
    import jax

    from ray_memory_management_tpu.models import sparse_linear as model

    arch, pc, params = both
    toks = jax.random.randint(jax.random.PRNGKey(100 + n_prompt), (60,), 2,
                              512)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(arch.reference().logits(params, toks, TOY))
        got, counts = through_the_pool(model, pc, params, toks, n_prompt)
    assert max(float(np.abs(want[t] - g).max()) for t, g in got.items()) \
        < 1e-4 * float(np.abs(want).max())
    # the last step, at position 59: 15 blocks cached, 6 chosen, a K/V
    # group each of two in both sparse layers
    assert {k: int(v) for k, v in counts.items()} == {
        "blocks_cached": 2 * 2 * 15, "blocks_selected": 2 * 2 * 6,
        "dense_queries": 0, "block_select_steps": 2, "lin_layer_steps": 2,
        "lin_rows_stepped": 2}


def test_what_a_mixed_step_counts_of_itself(both):
    """A chunk of 16 real positions (16..31) beside one live row at 40: the
    counts carry names of the mixed step's own, the chunk's part apart."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import sparse_linear as model
    from ray_memory_management_tpu.serve.kv_cache import _entry

    arch, pc, params = both
    pool = {}
    for name, item in model.cache_spec(pc).items():
        lead, trail, dtype, stride = _entry(item)
        pool[name] = jnp.zeros(lead + (PAGES + 1, PAGE // stride) + trail,
                               dtype)
    for name, (lead, trail, dtype) in model.state_spec(pc).items():
        pool[name] = jnp.zeros(lead + (2,) + trail, dtype)
    table = np.full((2, pc.max_seq // PAGE), PAGES, np.int32)
    table[0, :6] = [1, 2, 3, 4, 5, 6]
    lengths = np.array([40, 0], np.int32)
    _, _, counts = model.mixed_step(
        params, pool, jnp.ones(16, jnp.int32), jnp.array([7, 8, 9, 10]),
        jnp.int32(15), jnp.ones(2, jnp.int32), jnp.array(lengths),
        jnp.array(lengths), jnp.array(table), pc, chunk_index=jnp.int32(1),
        slot=jnp.int32(1))
    c = {k: int(v) for k, v in counts.items()}
    chunk_blocks = 2 * 2 * sum(t // 4 + 1 for t in range(16, 32))
    assert c["mixed_chunk_positions"] == 16
    assert c["mixed_chunk_positions_cached"] == sum(range(17, 33))
    assert c["mixed_chunk_blocks_cached"] == chunk_blocks
    assert c["mixed_blocks_cached"] == chunk_blocks + 2 * 2 * 11
    # queries 16..23 are before dense_len: every block up to their own
    assert c["mixed_dense_queries"] == 2 * 8
    assert c["mixed_lin_rows_stepped"] == 2 and c["mixed_lin_layer_steps"] \
        == 2 and c["mixed_block_select_steps"] == 2
    assert not set(c) & {"blocks_cached", "lin_rows_stepped"}


# ------------------------------------------------------------ planted faults
@pytest.mark.parametrize("fault", [
    "none", "attends_every_position", "state_not_carried", "decay_dropped",
    "local_blocks_not_forced"])
def test_a_planted_fault_reads_over_the_limit(both, fault, monkeypatch):
    """The check's statistic on six seeded rows of 60 positions (a prompt of
    27 in two chunks, then 33 decode steps): the faithful program puts the
    reference's first token first everywhere; a program that attends every
    cached position past ``dense_len``, whose second chunk starts its
    linear state from zeros, whose lightning layers do not decay (lambda =
    1), or that forces no local block beside the query's own, puts another
    token first somewhere, by more than the limit."""
    import jax

    from ray_memory_management_tpu.models import sparse_linear as model

    arch, pc, params = both
    if fault == "attends_every_position":
        pc = arch.program_config(TOY, dense_len=10_000)
    elif fault == "state_not_carried":
        scan = model.ssm.ssd_scan
        monkeypatch.setattr(model.ssm, "ssd_scan",
                            lambda *a, h0=None, **k: scan(*a, **k))
    elif fault == "decay_dropped":
        monkeypatch.setattr(model, "decay_rates",
                            lambda cfg: 0.0 * model.jnp.ones(cfg.lin_heads))
    elif fault == "local_blocks_not_forced":
        select = model.block_select
        monkeypatch.setattr(model, "block_select",
                            lambda *a, **k: select(*a, **dict(k, local=1)))
    jax.clear_caches()
    worst = 0.0
    for seed in range(6):
        toks = jax.random.randint(jax.random.PRNGKey(200 + seed), (60,), 2,
                                  512)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(arch.reference().logits(both[2], toks, TOY))
            got, _ = through_the_pool(model, pc, params, toks, 27)
        worst = max(worst, gap_of(want, got))
    monkeypatch.undo()
    jax.clear_caches()           # nobody after this test runs its trace
    if fault == "none":
        assert worst == 0.0
    else:
        assert worst > FAULT_LIMIT, worst


# ------------------------------------------------- the toy cell, end to end
@pytest.fixture(scope="module")
def toy_cell():
    return serve_driver.run(CELL, TOY, TOY_BATCH, seed=SEED, seconds=3.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu", control="fp8")


@pytest.mark.parametrize("trace", [False, True])
def test_toy_cell_through_the_closed_loop_is_correct(toy_cell, trace):
    r = toy_cell
    assert r["correct"], r["comparisons"]
    assert r["attempted"] >= 8 and r["failed"] == 0
    assert r["comparisons"]["clients_out_of_work"] == [0, 0]
    line = json.loads(json.dumps(harness.result_line(MIX, trace, r)))
    assert list(line)[-1] == "compared"
    if not trace:
        assert set(line["metrics"]) == {"setup_s",
                                        "serve.capacity_tokens_per_s"}
        return
    got = set(line["metrics"])
    # the metrics with no list of cells and the counter metric; the CPU has
    # no device plane, so the four shares read from a trace are left out
    assert got == {"serve.closed.tokens_per_decode_step",
                   "serve.closed.compiles_in_window",
                   "serve.closed.selected_block_share",
                   "runtime.lease_to_device_s", "compile.setup_compile_s"}
    assert line["metrics"]["serve.closed.compiles_in_window"]["value"] == 0
    assert 10.0 < line["metrics"]["serve.closed.selected_block_share"][
        "value"] < 100.0
    # what the steps counted of themselves arrives in the snapshots
    b, a = (r["context"][k]["engine"] for k in ("before", "after"))
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    assert d("mixed_steps") > 0 and d("mixed_chunk_positions") \
        == d("chunk_positions_live")
    assert d("blocks_selected") < d("blocks_cached")
    assert d("mixed_chunk_blocks_cached") < d("mixed_blocks_cached")
    assert a["cache_token_bytes"] == 2 * 2 * 16 * 4 * 2 + 2 * 2 * 16 * 4 // 2
    kv = r["context"]["after"]["kv"]
    assert kv["page_bytes"] == 16 * a["cache_token_bytes"]
    assert kv["state_row_bytes"] == 2 * 4 * 16 * 16 * 4


def test_float32_parameters_read_as_fp8_come_out_not_correct(toy_cell):
    c = toy_cell["comparisons"]
    assert c["served_logit_gap_max"][0] <= TOY_BATCH["check"]["gap_limit"]
    assert c["control_logit_gap_max"][0] > 10 * TOY_BATCH["check"][
        "gap_limit"]


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_one_cut_to_a_stage():
    cfg = manifest.config(NAME)
    check_config_file(cfg)
    reduced = ["max_position_embeddings", "mixer_types", "num_hidden_layers"]
    assert sorted(cfg["reduced"]) == reduced
    entry = [c for c in manifest.benchmark()["configs"]
             if c["name"] == NAME][0]
    assert sorted(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    pub = cfg["published"]
    # every published key is in the file, and only the reduced ones differ
    assert {k for k in pub if cfg.get(k, "missing") != pub[k]} == set(reduced)
    assert cfg["mixer_types"] == pub["mixer_types"][9:17] == \
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert cfg["residual_depth"] == pub["num_hidden_layers"] == 32
    assert cfg["sparse_config"] == {
        "block_size": 64, "topk": 64, "kernel_size": 32, "kernel_stride": 16,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    for key in ("stands_for", "cut", "cache", "sizes", "assumed",
                "departures"):
        assert cfg[key], key
    for word in ("pipeline", "9-16", "whole 73,448-row vocabulary"):
        assert word in cfg["stands_for"]
    assert set(cfg["assumed"]) >= {
        "weights", "sparse_config", "selection", "lightning_decay",
        "gates_and_norm", "mup_denominator", "residual_depth"}
    cell = manifest.cell(MIX)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, MIX, 1)


def test_counts_against_hand_worked_ones():
    """ISSUE 41's arithmetic, redone: parameters a layer of each kind, the
    2,112 B a position leaves, the pairs the published model attends, the
    pool and the state. (The issue's total, 2,820,573,184, counts the final
    norm's 4,096 twice.)"""
    cfg, mix = manifest.config(NAME), manifest.traffic(MIX)
    arch = architectures.of(cfg)
    d, f = 4096, 16384
    sparse = 3 * d * d + 2 * d * 256 + 3 * d * f + 2 * d + 2 * 128
    lin = 5 * d * d + 3 * d * f + 2 * d + 2 * 128 + d
    assert (sparse, lin) == (253_763_840, 285_225_216)
    assert 2 * d * 73448 == 601_686_016
    assert arch.n_params(cfg) == 2 * sparse + 6 * lin + d + 2 * d * 73448 \
        == 2_820_569_088
    assert arch.layer_counts(cfg) == (8, 2, 6)
    assert arch.cache_token_bytes(cfg) == 2 * 2 * 2 * 128 * 2 + 2 * 2 * 128 \
        * 2 // 16 == 2112
    assert arch.state_row_bytes(cfg) == 6 * 32 * 128 * 128 * 4
    e = serve_driver.engine_kwargs(cfg, mix)
    assert e["kv_pool_bytes"] == 32 * 32768 * 2112 == 2_214_592_512
    # a row of 9,000 positions: all up to dense_len, then 63 whole blocks
    # and the query's own up to it
    want = 8192 * 8193 // 2 + sum(63 * 64 + t % 64 + 1
                                  for t in range(8192, 9000))
    assert arch.attended_positions(cfg, 9000) == want
    assert arch.attended_positions(cfg, 100) == 5050
    dense = flops.causal_pairs(9000)
    windows = sum(max(0, (t + 1 - 32) // 16 + 1) for t in range(9000))
    assert arch.forward_flops(cfg, 9000, dense) == pytest.approx(
        2 * 9000 * (2 * 52_428_800 + 6 * 83_886_080 + 8 * 201_326_592
                    + d * 73448)
        + 2 * (want * 4 * 32 * 128 + windows * 2 * 32 * 128)
        + 6 * 9000 * 4 * 32 * 128 * 128)
    # 64 blocks chosen, 64 fetched: 4,096 positions; 250 blocks scored,
    # 25 fetched: 4 pooled keys a block
    f, b = arch.block_attention_work(cfg, 64.0, 64.0)
    assert (f, b) == (4 * 4096 * 16 * 128, 2 * 4096 * 128 * 2.0)
    f, b = arch.block_score_work(cfg, 250.0, 25.0)
    assert (f, b) == (2 * 1000 * 16 * 128, 100 * 128 * 2.0)
    assert arch.row_blocks(cfg, 6400) == 100 * 2 * 2
    assert arch.lightning_update_work(cfg, 32) == (
        4.0 * 32 * 32 * 128 * 128, 2.0 * 32 * 32 * 128 * 128 * 4)
    weights = 2 * 52_428_800 + 6 * 83_886_080 + 8 * 201_326_592 + d * 73448
    f, b = arch.step_work(cfg, 32, 32 * 2 * 2 * 64, 32 * 2 * 2 * 240)
    assert b == pytest.approx(
        2 * weights + 32 * 2 * 2 * 240 * 4 * 128 * 2
        + 32 * 2 * 2 * 64 * 64 * 2 * 128 * 2 + 6 * 32 * 2 * 2 ** 21)
    # ISSUE 41's least time of a decode token-step at 32 rows and about 15k
    # of context: 6.15 GB, 7.5 ms
    assert 6.0e9 < b < 6.3e9


def test_the_mix_is_the_issue_s_and_warms_up_in_six_waves():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["clients"], mix["requests_per_client"]) == (
        "serve-closed", 48, 6)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 12288,
                                    "sigma": 0.4, "min": 8192, "max": 28672}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.7, "min": 128, "max": 3072}
    e = mix["engine"]
    assert (e["max_batch_size"], e["steps_per_iter"], e["pad_multiple"],
            e["kv_page_tokens"], e["max_new_tokens"], e["kv_pool_bytes"]) \
        == (32, 8, 4096, 4096, 3072, 2_214_592_512)
    waves = serve_driver.warm_up_waves(mix)
    sent = [r for w in waves for r in w]
    assert (len(waves), len(sent)) == (6, 26)
    assert sum(r["prompt"] + r["budget"] for r in sent) < 470_000
    # the prompt's lower clip is dense_len: every decode query selects
    cfg = manifest.config(NAME)
    assert mix["prompt_tokens"]["min"] == cfg["sparse_config"]["dense_len"]
    assert mix["prompt_tokens"]["max"] + e["max_new_tokens"] \
        <= cfg["max_position_embeddings"]
    assert mix["tolerance"] and mix["check"]["gap_limit"] > 0


# ----------------------------------------------------------------- the readers
def made_context(trace=True):
    """A window of 100 mixed steps and 400 decode token-steps on the real
    configuration, and five traced seconds that hold 10 and 40 of them."""
    cfg, mix = manifest.config(NAME), manifest.traffic(MIX)
    chunk, ctx = 3500, 9000          # a chunk's real positions, mean context
    rows = 30                        # live decode rows a step
    row_blocks = 15000 // 64 + 1     # a decode row's cached blocks
    chunk_blocks = 2 * 2 * chunk * (ctx // 64 + 1)
    mixed = {"mixed_steps": 100,
             "mixed_chunk_positions": 100 * chunk,
             "mixed_chunk_positions_cached": 100 * chunk * ctx,
             "mixed_chunk_blocks_cached": 100 * chunk_blocks,
             "mixed_chunk_blocks_selected": 100 * 2 * 2 * chunk * 64,
             "mixed_blocks_cached": 100 * (chunk_blocks
                                           + 2 * 2 * rows * row_blocks),
             "mixed_blocks_selected": 100 * 2 * 2 * (chunk + rows) * 64,
             "mixed_lin_layer_steps": 600,
             "mixed_lin_rows_stepped": 600 * rows}
    decode = {"blocks_cached": 400 * 2 * 2 * rows * row_blocks,
              "blocks_selected": 400 * 2 * 2 * rows * 64,
              "lin_layer_steps": 2400, "lin_rows_stepped": 2400 * rows}
    zero = {k: 0 for k in list(mixed) + list(decode)}
    ops = {"%block_scores.1": 0.05, "%block_scores.2": 0.02,
           "%block_select.3": 0.1, "%block_sparse_attention.4": 0.4,
           "%block_sparse_attention.5": 0.2,
           "%lightning_decode_update.6": 0.3,
           "%lightning_mixed_update.7": 0.1, "%fusion.8": 1.0}
    calls = {"%block_scores.1": 20, "%block_scores.2": 80,
             "%block_select.3": 100, "%block_sparse_attention.4": 20,
             "%block_sparse_attention.5": 80,
             "%lightning_decode_update.6": 240,
             "%lightning_mixed_update.7": 60, "%fusion.8": 100}
    return {"cfg": cfg, "mix": mix, "device": {"kind": "TPU v5 lite"},
            "before": {"batches": 1000, "engine": zero},
            "after": {"batches": 1500, "engine": dict(mixed, **decode)},
            "trace": {"ops": ops, "op_calls": calls, "op_text": {},
                      "programs": {"jit_mixed_step": 4.0,
                                   "jit_paged_step_fn": 0.6,
                                   "jit_other": 9.0}} if trace else None}


def test_the_readers_on_hand_made_numbers():
    ctx = made_context()
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    peak, bw = 197e12, 819e9
    rows, chunk, ctx_end = 30, 3500, 9000 + 1750
    row_blocks = 15000 // 64 + 1
    chunk_blocks = 2 * 2 * chunk * (9000 // 64 + 1)
    cached = {"mixed_": chunk_blocks + 4 * rows * row_blocks,
              "": 4 * rows * row_blocks}
    selected = {"mixed_": 4 * (chunk + rows) * 64, "": 4 * rows * 64}
    assert selected_block_share.read(ctx) == pytest.approx(
        100.0 * (100 * selected["mixed_"] + 400 * selected[""])
        / (100 * cached["mixed_"] + 400 * cached[""]))
    # the attention kernel: 10 mixed steps and 40 decode token-steps
    pair = 4 * 16 * 128

    def least(f, b):
        return max(f / peak, b / bw)

    a_mixed = least(selected["mixed_"] * 64 * pair,
                    (min(4 * chunk * 64 * 64, ctx_end * 2 * 2)
                     + 4 * rows * 64 * 64) * 2 * 128 * 2)
    a_rows = least(selected[""] * 64 * pair, selected[""] * 64 * 256 * 2)
    assert block_sparse_attention_roofline.read(ctx) == pytest.approx(
        100.0 * (10 * a_mixed + 40 * a_rows) / 0.6)
    # the scoring kernel: 4 pooled keys a block
    s_mixed = least(cached["mixed_"] * 4 * 2 * 16 * 128,
                    (min(chunk_blocks * 4, ctx_end / 16 * 4)
                     + 4 * rows * row_blocks * 4) * 128 * 2)
    s_rows = least(cached[""] * 4 * 2 * 16 * 128, cached[""] * 4 * 128 * 2)
    assert block_score_roofline.read(ctx) == pytest.approx(
        100.0 * (10 * s_mixed + 40 * s_rows) / 0.07)
    # the state update: 240 calls of 30 rows' 2 MiB read and written
    assert lightning_update_roofline.read(ctx) == pytest.approx(
        100.0 * 240 * least(4.0 * rows * 2 ** 19, 2.0 * rows * 2 ** 21)
        / 0.3)
    # the decode token-step: 40 traced, over the decode program's 0.6 s
    f, b = arch.step_work(cfg, rows, selected[""], cached[""])
    assert sparse_linear_step_roofline.read(ctx) == pytest.approx(
        100.0 * 40 * least(f, b) / 0.6)
    for reader in (block_score_roofline, block_sparse_attention_roofline,
                   lightning_update_roofline, sparse_linear_step_roofline):
        value = reader.read(ctx)
        assert 0 < value < 100, (reader.__name__, value)
        # no share of a roofline is ever 0 or clipped: nothing to read is None
        assert reader.read(made_context(trace=False)) is None
        bare = made_context()
        bare["trace"]["ops"] = {"%fusion.8": 1.0}
        bare["trace"]["op_calls"] = {"%fusion.8": 100}
        assert reader.read(bare) is None
    # the parent's program counts none of it
    old = made_context()
    old["before"]["engine"] = old["after"]["engine"] = {"mixed_steps": 3}
    for reader in (selected_block_share, block_score_roofline,
                   block_sparse_attention_roofline,
                   lightning_update_roofline, sparse_linear_step_roofline):
        assert reader.read(old) is None
    # another architecture's cell reads nothing either
    other = dict(made_context(), cfg=manifest.config("glm-5.2-d6-e16"))
    for reader in (selected_block_share, block_score_roofline,
                   block_sparse_attention_roofline,
                   lightning_update_roofline, sparse_linear_step_roofline):
        assert reader.read(other) is None


@pytest.mark.parametrize("name,reader", [
    ("serve.closed.block_sparse_attention_roofline",
     "block_sparse_attention_roofline"),
    ("serve.closed.block_score_roofline", "block_score_roofline"),
    ("serve.closed.lightning_update_roofline", "lightning_update_roofline"),
    ("serve.closed.sparse_linear_step_roofline",
     "sparse_linear_step_roofline"),
    ("serve.closed.selected_block_share", "selected_block_share")])
def test_the_manifest_finds_each_metric_with_its_cell(name, reader):
    files = manifest.metric_files()
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    mine = {m["name"] for m in manifest.metrics_for(MIX, "per_layer")}
    assert files[name]["reader"] == reader
    assert files[name]["workloads"] == [MIX]
    assert files[name]["moves"] == "serve.capacity_tokens_per_s"
    assert {k: v for k, v in files[name].items() if k != "reader"} \
        == per_layer[name]
    assert name in mine
    assert {m["name"] for m in manifest.metrics_for(MIX, "end_to_end")} \
        == {"setup_s", "serve.capacity_tokens_per_s"}
    assert mine >= {"serve.closed.compiles_in_window",
                    "serve.closed.tokens_per_decode_step"}


# ------------------------------------------------ described-chip compilation
@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("program", ["decode", "mixed"])
def test_the_cells_programs_fit_a_described_v5e(one_chip, program,
                                                monkeypatch):
    """The engine's decode program (8 token-steps) and its one mixed program
    (a chunk of 4,096 beside 32 decode rows) at the published widths, 8
    layers, the pool of 32 x 32,768 positions in three arrays and the 32
    slots' state: each fits the chip beside weights, pool and state (about
    10 s and 20 s of compiling; not marked slow). In a token-step: the three
    block kernels once a sparse layer and the state update once a lightning
    layer; in the mixed step the block kernels twice, the scan and the
    update under its own name once; and the pool and the state stay where
    they came in, never copied."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.ops import ssm
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the kernels' dispatch asks where computation lands: steer it here
    for module in (pa, ssm):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    cfg, mix = manifest.config(NAME), manifest.traffic(MIX)
    arch = architectures.of(cfg)
    hbm = flops.peak("TPU v5 lite")["hbm_bytes"]
    weights = 2 * arch.n_params(cfg)
    e = serve_driver.engine_kwargs(cfg, mix)
    pc = arch.program_config(cfg)
    slots, page = e["max_batch_size"], e["kv_page_tokens"]
    params = shaped(jax.eval_shape(
        lambda: arch.init_program_params(jax.random.PRNGKey(0), pc)))
    eng = ContinuousBatcher(
        None, pc, max_slots=slots, max_new_tokens=e["max_new_tokens"],
        pad_multiple=e["pad_multiple"], steps_per_iter=e["steps_per_iter"],
        kv_page_tokens=page, kv_pool_bytes=e["kv_pool_bytes"])
    try:
        pool = shaped(jax.eval_shape(eng.kv_pool.allocate))
        assert {k: v.shape for k, v in pool.items()} == {
            "k": (2, 2, 257, 4096, 128), "v": (2, 2, 257, 4096, 128),
            "pooled": (2, 2, 257, 256, 128), "lin": (6, 32, 32, 128, 128)}
        width = eng.kv_pool.table_width
        assert width == 8 and eng._chunk == 4096 and eng._mixed
        key = arr((2,), jnp.uint32)
        if program == "decode":
            compiled = eng._paged_step.lower(
                params, pool, arr((slots,)), arr((slots,)),
                arr((slots, width)), key).compile()
        else:
            compiled = eng._mixed_step.lower(
                params, pool, arr((4096,)), arr((8,)), arr(()), arr(()),
                arr(()), arr((slots,)), arr((slots,)), arr((slots, width)),
                key, arr(())).compile()
        stats = eng.kv_pool.stats()
    finally:
        eng.close()
    text = compiled.as_text()
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pool.values())
    assert held == stats["store_bytes"] == 257 * 4096 * 2112 \
        + 32 * 6 * 2 ** 21
    assert 8.2e9 < weights + held < 8.3e9       # 5.64 GB, 2.17 GB, 0.40 GB
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert m.alias_size_in_bytes >= held        # donated
    kernels = {name: len(set(re.findall(
        "%(" + name + r"[.\d]*) = ", text))) for name in (
            "block_scores", "block_select", "block_sparse_attention",
            "lightning_decode_update", "lightning_mixed_update",
            "ssd_chunk_scan")}
    if program == "decode":
        assert kernels == {"block_scores": 2, "block_select": 2,
                           "block_sparse_attention": 2,
                           "lightning_decode_update": 6,
                           "lightning_mixed_update": 0, "ssd_chunk_scan": 0}
        assert total < weights + held + 0.1e9
    else:
        assert kernels == {"block_scores": 4, "block_select": 4,
                           "block_sparse_attention": 4,
                           "lightning_decode_update": 0,
                           "lightning_mixed_update": 6, "ssd_chunk_scan": 6}
        assert total < weights + held + 0.6e9
    for shape in ("bf16[2,2,257,4096,128]", "bf16[2,2,257,256,128]",
                  "f32[6,32,32,128,128]"):
        made = re.findall("= " + re.escape(shape) + r"\{[^ ]* (\S+?)\(", text)
        assert made and not set(made) & {"copy", "copy-start"}, shape
    assert total < hbm - 6e9
