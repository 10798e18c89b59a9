"""``glm-5.2-d6-e16`` and ``longcontext-batch``, rehearsed off the chip: the
program against the plain reference at toy widths (**a prompt in chunks, then
decode through the pool**, across chunk and page boundaries, the toy
``index_topk`` well under the toy context), the new module against
``models/latent_moe.py`` where the selection selects everything, **the shares
of an expert layer adding up to the uncut reference's whole layer**, ties in
the selection, planted faults read by the comparison, the toy cell through
the serve driver's closed loop with its control, the configuration's counts
worked by hand, the readers on hand-made numbers, and the cell's two step
programs compiled for a described v5e chip. No time read here is a device
number.

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
from chipbench.readers import (index_score_roofline, index_select_share,
                               selected_position_share,
                               sparse_attention_roofline,
                               sparse_mixed_step_share, sparse_step_roofline)
from chipbench_config_checks import check_config_file

NAME, MIX = "glm-5.2-d6-e16", "longcontext-batch"
SEED = 2 ** 31 + 39  # the driver's seeds pass 32 signed bits
TOY = dict(
    name="toy-glm", architecture="latent_sparse_moe", model_type="glm_moe_dsa",
    vocab_size=512, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, head_dim=32, q_lora_rank=32, kv_lora_rank=96,
    qk_nope_head_dim=32, qk_rope_head_dim=32, qk_head_dim=64, v_head_dim=32,
    intermediate_size=128, moe_intermediate_size=64, n_router_experts=16,
    n_routed_experts=4, first_held_expert=4, n_shared_experts=1,
    num_experts_per_tok=2, routed_scaling_factor=2.5, norm_topk_prob=True,
    index_n_heads=4, index_head_dim=64, index_topk=8,
    indexer_types=["full", "shared", "full"],
    mlp_layer_types=["dense", "sparse", "sparse"], num_hidden_layers=3,
    num_nextn_predict_layers=0, first_k_dense_replace=1,
    max_position_embeddings=128, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    n_group=1, topk_group=1, param_dtype="float32",
    activation_dtype="float32")
TOY_BATCH = {
    "name": "toy-longcontext", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 16, "max": 80},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 2, "max": 24},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 24},
    "trace_seconds": 1.0, "check": {"requests": 8, "gap_limit": 0.05}}
CELL = {"name": "toy", "chips": 1}
PAGE, CHUNK, ROWS, PAGES = 8, 16, 3, 20


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def both():
    """(architecture, program config, the recipe's weights), float32."""
    import jax

    arch = architectures.of(TOY)
    pc = arch.program_config(TOY)
    return arch, pc, arch.init_program_params(jax.random.PRNGKey(SEED), pc)


def through_the_pool(model, pc, params, tokens, n_prompt, row=1,
                     retrace=False):
    """The program's logits at positions ``n_prompt - 1`` .. of ``tokens``:
    the first ``n_prompt`` as a prompt in chunks of ``CHUNK`` (two pages each)
    on scattered pages, the rest one decode step each, in slot ``row`` of
    ``ROWS`` with the others idle; and the last step's counts. ``retrace``:
    trace the two programs anew (a test has patched the module)."""
    import jax
    import jax.numpy as jnp

    if retrace:
        jax.clear_caches()

    spec = model.cache_spec(pc)
    pool = {k: jnp.zeros(lead + (PAGES + 1, PAGE) + trail, dt)
            for k, (lead, trail, dt) in spec.items()}
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
            put(lengths), put(table), pc, chunk_index=jnp.int32(ci))
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
    own plain init; an indexer in the ``full`` layers alone; a router of the
    published width with a choosing bias that is not 0 over a share of the
    experts; plain attention weights."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import (latent_sparse_moe,
                                                  serving_model)

    arch, pc, ours = both
    assert serving_model(pc) is latent_sparse_moe
    plain = latent_sparse_moe.init_params(jax.random.PRNGKey(SEED), pc)
    assert jax.tree.structure(ours) == jax.tree.structure(plain)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(plain)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(ours)) \
        == arch.n_params(TOY)
    assert arch.server_kwargs(TOY)["init"] is arch.init_program_params
    assert ["index" in p for p in ours["layers"]] == [True, False, True]
    assert ["moe" in p for p in ours["layers"]] == [False, True, True]
    e = ours["layers"][1]["moe"]
    assert e["router"].shape == (64, 16) and e["bias"].shape == (16,) \
        and e["w1"].shape == (4, 64, 64) and e["w2"].shape == (4, 64, 64)
    assert float(jnp.std(e["bias"])) > 0.02
    ix = ours["layers"][2]["index"]
    assert ix["wq_b"].shape == (32, 4 * 64) and ix["wk"].shape == (64, 64) \
        and ix["w"].shape == (64, 4) and float(jnp.std(ix["k_ln_b"])) > 0.03
    # attention keeps plain weights: no gain on W_qb (PERF.md, PR 39)
    assert abs(float(jnp.std(ours["layers"][0]["q_b"])) * 32 ** 0.5 - 1) < 0.1
    assert latent_sparse_moe.cache_spec(pc) == {
        "latent": ((3,), (128,), jnp.float32),
        "index": ((2,), (64,), jnp.float32)}
    assert arch.cache_token_bytes(TOY) == (3 * 128 + 2 * 64) * 4


@pytest.mark.parametrize("n_prompt", [27, 32, 9])
def test_a_prompt_in_chunks_then_decode_against_the_reference(both, n_prompt):
    """A prompt that ends inside a page of its second chunk, one that ends
    on a chunk's edge, one shorter than a chunk (and, for its first tokens,
    than ``index_topk``); then decode to position 40 across page edges: each
    served position's logits equal the reference's full forward to 2e-4 in
    float32 on logits of order 3. The selection binds (8 of up to 40)."""
    import jax

    from ray_memory_management_tpu.models import latent_sparse_moe

    arch, pc, params = both
    toks = jax.random.randint(jax.random.PRNGKey(n_prompt), (40,), 2, 512)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(arch.reference().logits(params, toks, TOY))
        got, counts = through_the_pool(latent_sparse_moe, pc, params, toks,
                                       n_prompt)
    assert len(got) == 40 - n_prompt + 1
    for t, g in got.items():
        np.testing.assert_allclose(np.asarray(g), want[t], atol=2e-4)
    # the last decode step: one live row with 39 cached and its own
    assert int(counts["positions_cached"]) == 40 * 3
    assert int(counts["positions_selected"]) == 8 * 3
    assert int(counts["positions_scored"]) == 40 * 2
    assert int(counts["expert_assignments"]) == 2 * 2
    assert int(counts["expert_layer_steps"]) == 2
    assert int(counts["expert_assignments_held"]) \
        == int(counts["expert_tokens"].sum()) <= 4


def test_what_a_mixed_step_counts_of_itself(both):
    import jax

    from ray_memory_management_tpu.models import latent_sparse_moe

    _, pc, params = both
    toks = jax.random.randint(jax.random.PRNGKey(1), (28,), 2, 512)
    _, counts = through_the_pool(latent_sparse_moe, pc, params, toks[:27], 27)
    # the second chunk's 11 real positions at 16..26, no live decode row
    seen = sum(range(17, 28))
    assert {k: int(v) for k, v in counts.items() if v.ndim == 0} == {
        "mixed_chunk_positions": 11, "mixed_chunk_positions_cached": seen,
        "mixed_positions_cached": 3 * seen, "mixed_positions_scored": 2 * seen,
        "mixed_positions_selected": 3 * 8 * 11,
        "mixed_expert_assignments": 11 * 2 * 2, "mixed_expert_layer_steps": 2,
        "mixed_expert_assignments_held": int(
            counts["mixed_expert_tokens"].sum()),
        "mixed_experts_touched": int(counts["mixed_experts_touched"])}
    assert counts["mixed_expert_tokens"].shape == (4,)


def test_where_everything_is_selected_it_is_the_family_s_other_model(both):
    """With contexts no longer than ``index_topk`` and every expert held, the
    module's logits through chunks and the pool equal
    ``models/latent_moe.py``'s whole forward on the same weights less the
    indexers: the shared projections, RoPE, absorbed product and experts
    hold together."""
    import jax

    from ray_memory_management_tpu.models import latent_moe, latent_sparse_moe

    arch, _, _ = both
    cfg = dict(TOY, index_topk=64, n_routed_experts=16, first_held_expert=0)
    pc = arch.program_config(cfg)
    params = arch.init_program_params(jax.random.PRNGKey(SEED), pc)
    dense = latent_moe.LatentMoEConfig(
        vocab_size=512, d_model=64, n_layers=3, n_heads=4, q_lora_rank=32,
        kv_lora_rank=96, qk_nope_head_dim=32, qk_rope_head_dim=32,
        v_head_dim=32, d_ff=128, moe_d_ff=64, n_routed_experts=16,
        n_shared_experts=1, experts_per_tok=2, routed_scaling_factor=2.5,
        max_seq=128, dtype=np.float32, param_dtype=np.float32)
    theirs = dict(params, layers=[
        {k: v for k, v in p.items() if k != "index"}
        for p in params["layers"]])
    toks = jax.random.randint(jax.random.PRNGKey(2), (40,), 2, 512)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(latent_moe.forward(theirs, toks[None], dense)[0])
        got, _ = through_the_pool(latent_sparse_moe, pc, params, toks, 27)
    for t, g in got.items():
        np.testing.assert_allclose(np.asarray(g), want[t], atol=2e-4)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four shares of four experts each, every one told its first held
    expert, route over all 16 and compute their own experts' terms; with the
    shared expert counted once the parts add up to what the reference's
    uncut layer (all 16 held) gives; and a share alone is not the whole."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import latent_sparse_moe as model

    arch = architectures.of(TOY)
    whole = dict(TOY, n_routed_experts=16, first_held_expert=0)
    ref = arch.reference()
    layer = ref.init_params(jax.random.PRNGKey(SEED), whole)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    mm = ref._mm("f32")
    with jax.default_matmul_precision("highest"):
        h = ref._rms(x, layer["mlp_ln"], 1e-5)
        want = ref._experts(h, layer["moe"], layer["shared"], whole, mm)
        once = ref._swiglu(h, layer["shared"]["w1"], layer["shared"]["w3"],
                           layer["shared"]["w2"], mm)
        total, held = once, 0
        for first in range(0, 16, 4):
            pc = arch.program_config(dict(TOY, first_held_expert=first))
            mine = dict(layer, moe=dict(layer["moe"], **{
                k: layer["moe"][k][first:first + 4]
                for k in ("w1", "w3", "w2")}))
            y, counts = model._ffn(x, mine, pc, None)
            total = total + (y - once)
            held += int(counts.sum())
            part = y
    assert held == 24 * 2              # every assignment is held somewhere
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(part - want))) > 0.05


@pytest.mark.parametrize("use", ["off", "interpret"])
def test_ties_in_the_selection_go_to_the_lower_position_twice(use):
    """Rows of scores with whole runs of equal values: the plain form and the
    kernel (under the interpreter) select the same positions, the same on a
    second call, exactly ``top_k`` of them, and of a run that straddles the
    threshold the lowest positions."""
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(5)
    s = rng.normal(size=(2, 3, 128)).astype(np.float32)
    s[0, 0, :] = 1.0                      # every position ties
    s[0, 1, 10:90] = 0.25                 # a run across the threshold
    s[0, 1, :10], s[0, 1, 90:] = 2.0, -2.0
    s[1, 2, 40:] = pa._NEG_INF            # fewer than top_k to choose from
    s[1, 1, ::2] = s[1, 1, 1::2]          # pairs of equal scores
    first = np.asarray(pa.index_select(jnp.asarray(s), 32, use_pallas=use))
    again = np.asarray(pa.index_select(jnp.asarray(s), 32, use_pallas=use))
    plain = np.asarray(pa.index_select_reference(jnp.asarray(s), 32))
    assert (first == again).all() and (first == plain).all()
    hit = first == 0
    assert (hit.sum(-1) == 32).all()
    assert hit[0, 0, :32].all() and not hit[0, 0, 32:].any()
    assert hit[0, 1, :32].all() and not hit[0, 1, 32:].any()
    assert hit[1, 2, :40].sum() == 32 and not hit[1, 2, 40:].any()


@pytest.mark.parametrize("use", ["interpret"])
def test_the_kernels_under_the_interpreter_equal_the_plain_forms(use):
    """One chunk of 256 queries at positions 128..383 over four scattered
    pages, and three decode rows (one idle): scores, selection and attention
    of the kernels against the plain ``jax.numpy`` forms they share a
    contract with."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    n, heads, dim, page = 256, 4, 128, 128
    q = jax.random.normal(ks[0], (1, n, heads, dim))
    w = jax.random.normal(ks[1], (1, n, heads))
    keys = jax.random.normal(ks[2], (2, 9, page, dim))
    table = jnp.array([[3, 1, 7, 8]], jnp.int32)
    at = (128 + jnp.arange(n))[None]
    plain = pa.index_scores(q, w, keys, table, at, layer=1, use_pallas="off")
    np.testing.assert_allclose(
        np.asarray(pa.index_scores(q, w, keys, table, at, layer=1,
                                   use_pallas=use)), np.asarray(plain),
        atol=1e-4)
    assert (np.asarray(plain[0, 0, 129:]) == pa._NEG_INF).all()
    mask = pa.index_select(plain, 64, use_pallas=use)
    assert (np.asarray(mask) == np.asarray(
        pa.index_select_reference(plain, 64))).all()
    qa = jax.random.normal(ks[3], (1, n, 16, 256))
    latent = jax.random.normal(ks[4], (3, 9, page, 256))
    kw = dict(layer=2, top_k=64, value_width=128, scale=0.1)
    np.testing.assert_allclose(
        np.asarray(pa.sparse_latent_attention(qa, latent, table, mask, at,
                                              use_pallas=use, **kw)),
        np.asarray(pa.sparse_latent_attention(qa, latent, table, mask, at,
                                              use_pallas="off", **kw)),
        atol=1e-5)
    rows = jnp.array([[3, 1, 7, 8], [2, 0, 8, 8], [8, 8, 8, 8]], jnp.int32)
    at = jnp.array([[300], [130], [0]], jnp.int32)
    s = pa.index_scores(jax.random.normal(ks[5], (3, 1, heads, dim)),
                        jax.random.normal(ks[6], (3, 1, heads)), keys, rows,
                        at)
    mask = pa.index_select(s, 64, use_pallas=use)
    assert (np.asarray(mask) == 0).sum(-1).tolist() == [[64], [64], [1]]
    qd = jax.random.normal(ks[7], (3, 1, 16, 256))
    np.testing.assert_allclose(
        np.asarray(pa.sparse_latent_attention(qd, latent, rows, mask, at,
                                              use_pallas=use, **kw)),
        np.asarray(pa.sparse_latent_attention(qd, latent, rows, mask, at,
                                              use_pallas="off", **kw)),
        atol=1e-5)


# ------------------------------------------------------------ planted faults
FAULT_LIMIT = 0.3  # the faithful program reads 0 here; the toy cell's is 0.05


@pytest.mark.parametrize("fault", [
    "none", "attends_every_position", "shared_layer_selects_for_itself",
    "shared_layer_on_a_stale_set", "chunk_tail_keys_not_written"])
def test_a_planted_fault_reads_over_the_limit(both, fault, monkeypatch):
    """The check's statistic on six seeded rows of 40 positions (a prompt of
    27 in two chunks, then 13 decode steps): the faithful program puts the
    reference's first token first everywhere; a program that attends every
    cached position, whose ``shared`` layer selects with an indexer of its
    own, or from the set of the ``full`` layer two below instead of the
    nearest, or that leaves the index keys of a chunk's second page
    unwritten, puts another token first somewhere, by more than the limit."""
    import jax

    from ray_memory_management_tpu.models import latent_sparse_moe as model

    arch, pc, params = both
    if fault == "attends_every_position":
        pc = arch.program_config(dict(TOY, index_topk=128))
    elif fault == "shared_layer_selects_for_itself":
        pc = arch.program_config(dict(TOY, indexer_types=["full"] * 3))
        layers = list(params["layers"])
        layers[1] = dict(layers[1], index=layers[2]["index"])
        params = dict(params, layers=layers)
    elif fault == "shared_layer_on_a_stale_set":
        pc = arch.program_config(
            dict(TOY, indexer_types=["full", "shared", "shared"]))
        layers = list(params["layers"])
        layers[2] = {k: v for k, v in layers[2].items() if k != "index"}
        params = dict(params, layers=layers)
    elif fault == "chunk_tail_keys_not_written":
        put = model._put_pages

        def first_page_only(pages_of, layer, fresh, pages):
            if pages_of.shape[-1] == pc.index_head_dim:
                pages = pages[:1]
            return put(pages_of, layer, fresh, pages)

        monkeypatch.setattr(model, "_put_pages", first_page_only)
    worst = 0.0
    for seed in range(6):
        toks = jax.random.randint(jax.random.PRNGKey(100 + seed), (40,), 2,
                                  512)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(arch.reference().logits(both[2], toks, TOY))
            got, _ = through_the_pool(
                model, pc, params, toks, 27,
                retrace=fault == "chunk_tail_keys_not_written" and not seed)
        worst = max(worst, gap_of(want, got))
    if fault == "chunk_tail_keys_not_written":
        monkeypatch.undo()
        jax.clear_caches()       # nobody after this test runs its trace
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
    # the metrics with no list of cells and the two new counter metrics; the
    # CPU has no device plane, so the four shares read from a trace are left
    # out, and the metrics that list other cells are not this cell's
    assert got == {"serve.closed.tokens_per_decode_step",
                   "serve.closed.compiles_in_window",
                   "serve.closed.selected_position_share",
                   "serve.closed.sparse_mixed_step_share",
                   "runtime.lease_to_device_s", "compile.setup_compile_s"}
    assert line["metrics"]["serve.closed.compiles_in_window"]["value"] == 0
    assert 10.0 < line["metrics"]["serve.closed.selected_position_share"][
        "value"] < 60.0
    assert 20.0 < line["metrics"]["serve.closed.sparse_mixed_step_share"][
        "value"] < 100.0
    # what the steps counted of themselves arrives in the snapshots
    b, a = (r["context"][k]["engine"] for k in ("before", "after"))
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    assert d("mixed_steps") > 0 and d("mixed_chunk_positions") \
        == d("chunk_positions_live")
    assert d("mixed_positions_scored") * 3 == d("mixed_positions_cached") * 2
    assert d("positions_scored") * 3 == d("positions_cached") * 2
    assert d("positions_selected") < d("positions_cached")
    assert len(a["expert_tokens"]) == len(a["mixed_expert_tokens"]) == 4
    assert sum(a["expert_tokens"]) - sum(b.get("expert_tokens", [0])) \
        == d("expert_assignments_held")
    # 2 choices a live row and expert layer, about a quarter of them held
    assert 0.1 < d("expert_assignments_held") / d("expert_assignments") < 0.45
    assert a["cache_token_bytes"] == (3 * 128 + 2 * 64) * 4
    kv = r["context"]["after"]["kv"]
    assert kv["page_bytes"] == 16 * a["cache_token_bytes"]


def test_float32_parameters_read_as_fp8_come_out_not_correct(toy_cell):
    c = toy_cell["comparisons"]
    assert c["served_logit_gap_max"][0] <= TOY_BATCH["check"]["gap_limit"]
    assert c["control_logit_gap_max"][0] > 1.0


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_one_cut_to_one_chips_share():
    cfg = manifest.config(NAME)
    check_config_file(cfg)
    reduced = ["first_k_dense_replace", "indexer_types",
               "max_position_embeddings", "mlp_layer_types",
               "n_routed_experts", "num_hidden_layers",
               "num_nextn_predict_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == reduced
    entry = [c for c in manifest.benchmark()["configs"]
             if c["name"] == NAME][0]
    assert sorted(entry["reduced"]) == reduced
    assert entry["source"] == cfg["source"]
    pub = cfg["published"]
    # every published key is in the file, and only the reduced ones differ
    assert {k for k in pub if cfg.get(k, "missing") != pub[k]} == set(reduced)
    assert cfg["indexer_types"] == pub["indexer_types"][2:8] \
        == ["full", "shared", "shared", "shared", "full", "shared"]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 5
    assert (cfg["n_routed_experts"], cfg["n_router_experts"],
            cfg["first_held_expert"]) == (16, 256, 0)
    assert cfg["vocab_size"] * 8 == pub["vocab_size"]
    for key in ("stands_for", "held_here", "cache", "sizes", "assumed",
                "departures"):
        assert cfg[key], key
    for word in ("16 chips", "data-parallel", "pipeline"):
        assert word in cfg["stands_for"]
    assert set(cfg["assumed"]) >= {"weights", "attention_scores", "indexer",
                                   "rope_pairing", "e_score_correction_bias",
                                   "routed_experts"}
    assert set(cfg["departures"]) >= {"num_nextn_predict_layers",
                                      "index_quantisation",
                                      "max_position_embeddings"}
    cell = manifest.cell(MIX)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, MIX, 1)


def test_counts_against_hand_worked_ones():
    """ISSUE 39's arithmetic, redone: parameters a part, the 8,192 B a token
    leaves, the pairs the published model attends, and the pool."""
    cfg, mix = manifest.config(NAME), manifest.traffic(MIX)
    arch = architectures.of(cfg)
    attn = (6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * 448
            + 16384 * 6144)
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    expert, router = 3 * 6144 * 2048, 6144 * 256
    assert (attn, indexer, expert) == (165_019_648, 9_371_648, 37_748_736)
    layers = 6 * attn + 2 * indexer + 3 * 6144 * 12288 \
        + 5 * (17 * expert + router)
    norms = 6 * (2 * 6144 + 2048 + 512) + 6144 + 2 * 2 * 128
    assert arch.n_params(cfg) == layers + 5 * 256 + norms \
        + 2 * 6144 * 19360 == 4_689_853_184
    assert arch.matmul_params(cfg) == (layers / 6, 6144 * 19360)
    assert arch.layer_counts(cfg) == (6, 2, 5)
    assert arch.cache_token_bytes(cfg) == (6 * 640 + 2 * 128) * 2 == 8192
    e = serve_driver.engine_kwargs(cfg, mix)
    assert e["kv_pool_bytes"] == 12 * 32768 * 8192 == 3_221_225_472
    # a row of 3,000 positions: all pairs up to 2,048, then 2,048 each
    assert arch.selected_pairs(cfg, 3000) == 2048 * 2049 // 2 + 952 * 2048
    assert arch.selected_pairs(cfg, 100) == 5050
    assert arch.selected_pairs(cfg, 3000, 2999) == 2048
    used = layers - 5 * 15.5 * expert + 6144 * 19360   # 0.5 of 8 land here
    dense = flops.causal_pairs(3000)
    assert arch.forward_flops(cfg, 3000, dense) == pytest.approx(
        2 * 3000 * used + 6 * arch.selected_pairs(cfg, 3000) * 2 * 64 * 512
        + 2 * dense * 2 * 32 * 128)
    # handed anything but a whole row's pairs: at most index_topk a token
    assert arch.forward_flops(cfg, 12, 12 * 9000) == pytest.approx(
        2 * 12 * used + 6 * 12 * 2048 * 2 * 64 * 512
        + 2 * 12 * 9000 * 2 * 32 * 128)
    f, b = arch.sparse_attention_work(cfg, 2048.0, 2048.0)
    assert (f, b) == (2.0 * 2048 * 64 * (576 + 512), 2048.0 * 1280)
    f, b = arch.index_score_work(cfg, 1000.0, 100.0)
    assert (f, b) == (1000 * 2.0 * 32 * 128, 100 * 256.0 + 4000)
    f, b = arch.step_work(cfg, 12, 12, 6 * 12 * 2048, 2 * 12 * 9000,
                          6 * 12 * 2048, 2 * 12 * 9000, 5.0)
    assert f == pytest.approx(arch.forward_flops(cfg, 12, 12 * 9000))
    assert b == pytest.approx(
        2 * (layers - 5 * 11 * expert + 6144 * 19360)
        + 6 * 12 * 2048 * 1280 + 2 * 12 * 9000 * 256)


def test_the_mix_is_the_issue_s_and_warms_up_in_eight_waves():
    mix = manifest.traffic(MIX)
    assert (mix["kind"], mix["clients"]) == ("serve-closed", 18)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 12288,
                                    "sigma": 0.6, "min": 2048, "max": 28672}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.8, "min": 16, "max": 1536}
    e = mix["engine"]
    assert (e["max_batch_size"], e["steps_per_iter"], e["pad_multiple"],
            e["kv_page_tokens"], e["max_new_tokens"]) == (12, 8, 4096, 4096,
                                                          1536)
    waves = serve_driver.warm_up_waves(mix)
    sent = [r for w in waves for r in w]
    assert (len(waves), len(sent)) == (8, 42)
    assert sum(r["prompt"] + r["budget"] for r in sent) < 600_000
    assert mix["tolerance"] and mix["check"]["gap_limit"] > 0


# ----------------------------------------------------------------- the readers
def made_context(trace=True):
    """A window of 100 mixed steps and 400 decode token-steps on the real
    configuration, and five traced seconds that hold 10 and 40 of them."""
    cfg, mix = manifest.config(NAME), manifest.traffic(MIX)
    chunk, ctx = 3500, 9000          # a chunk's real positions, mean context
    rows = 10                        # live decode rows a step
    row_ctx = 14000
    mixed = {"mixed_steps": 100,
             "mixed_chunk_positions": 100 * chunk,
             "mixed_chunk_positions_cached": 100 * chunk * ctx,
             "mixed_positions_cached": 100 * 6 * (chunk * ctx
                                                  + rows * row_ctx),
             "mixed_positions_selected": 100 * 6 * (chunk + rows) * 2048,
             "mixed_positions_scored": 100 * 2 * (chunk * ctx
                                                  + rows * row_ctx),
             "mixed_expert_assignments": 100 * (chunk + rows) * 5 * 8,
             "mixed_expert_layer_steps": 500, "mixed_experts_touched": 8000}
    decode = {"positions_cached": 400 * 6 * rows * row_ctx,
              "positions_selected": 400 * 6 * rows * 2048,
              "positions_scored": 400 * 2 * rows * row_ctx,
              "expert_assignments": 400 * rows * 5 * 8,
              "expert_layer_steps": 2000, "experts_touched": 10000}
    zero = {k: 0 for k in list(mixed) + list(decode)}
    ops = {"%index_scores.1": 0.04, "%index_scores.2": 0.04,
           "%index_select.3": 0.2, "%index_select.4": 0.1,
           "%sparse_latent_attention.5": 2.0,
           "%sparse_latent_attention.6": 1.0, "%fusion.7": 1.0}
    calls = {"%index_scores.1": 10, "%index_scores.2": 10,
             "%index_select.3": 40, "%index_select.4": 80,
             "%sparse_latent_attention.5": 60 + 240,
             "%sparse_latent_attention.6": 60, "%fusion.7": 100}
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
    assert selected_position_share.read(ctx) == pytest.approx(
        100.0 * (100 * 3510 + 400 * 10) * 2048
        / (100 * (3500 * 9000 + 10 * 14000) + 400 * 10 * 14000))
    assert sparse_mixed_step_share.read(ctx) == pytest.approx(20.0)
    assert index_select_share.read(ctx) == pytest.approx(100 * 0.3 / 4.6)
    # the scoring kernel: 20 calls of a chunk's 3,500 x 9,000 pairs
    pairs = 3500 * 9000
    least = max(pairs * 2 * 32 * 128 / peak,
                ((9000 + 1750) * 256 + pairs * 4) / bw)
    assert index_score_roofline.read(ctx) == pytest.approx(
        100.0 * 20 * least / 0.08)
    # the attention kernel: 10 mixed steps and 40 decode steps of 6 layers
    pair = 2.0 * 64 * (576 + 512)
    a_mixed = max(3510 * 2048 * pair / peak,
                  (9000 + 1750 + 10 * 2048) * 1280 / bw)
    a_rows = max(10 * 2048 * pair / peak, 10 * 2048 * 1280 / bw)
    assert sparse_attention_roofline.read(ctx) == pytest.approx(
        100.0 * 6 * (10 * a_mixed + 40 * a_rows) / 3.0)
    # the steps: the architecture's count of each kind's least
    seen = 9000 + 1750
    f, b = arch.step_work(cfg, 3510, 13, 6 * 3510 * 2048,
                          2 * (3500 * 9000 + 10 * 14000), 6 * seen, 2 * seen,
                          16.0)
    s_mixed = max(f / peak, b / bw)
    f, b = arch.step_work(cfg, 10, 10, 6 * 10 * 2048, 2 * 10 * 14000,
                          6 * 10 * 2048, 2 * 10 * 14000, 5.0)
    s_rows = max(f / peak, b / bw)
    assert sparse_step_roofline.read(ctx) == pytest.approx(
        100.0 * (10 * s_mixed + 40 * s_rows) / 4.6)
    assert 0 < sparse_step_roofline.read(ctx) < 100
    # no share of a roofline is ever 0 or clipped: nothing to read is None
    for reader in (index_score_roofline, index_select_share,
                   sparse_attention_roofline, sparse_step_roofline):
        assert reader.read(made_context(trace=False)) is None
        bare = made_context()
        bare["trace"]["ops"] = {"%fusion.7": 1.0}
        bare["trace"]["op_calls"] = {"%fusion.7": 100}
        assert reader.read(bare) is None
    # the parent's program counts none of it
    old = made_context()
    old["before"]["engine"] = old["after"]["engine"] = {"mixed_steps": 3}
    for reader in (selected_position_share, sparse_mixed_step_share,
                   index_score_roofline, sparse_attention_roofline,
                   sparse_step_roofline):
        assert reader.read(old) is None
    # another architecture's cell reads nothing either
    other = dict(made_context(), cfg=manifest.config("glm-4.7-flash-d7"))
    assert sparse_attention_roofline.read(other) is None
    assert index_score_roofline.read(other) is None
    assert sparse_step_roofline.read(other) is None


def test_the_manifest_finds_the_six_metrics_with_their_cell():
    files = manifest.metric_files()
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    mine = {m["name"] for m in manifest.metrics_for(MIX, "per_layer")}
    for name, reader in (
            ("serve.closed.sparse_attention_roofline",
             "sparse_attention_roofline"),
            ("serve.closed.index_score_roofline", "index_score_roofline"),
            ("serve.closed.index_select_share", "index_select_share"),
            ("serve.closed.sparse_step_roofline", "sparse_step_roofline"),
            ("serve.closed.selected_position_share",
             "selected_position_share"),
            ("serve.closed.sparse_mixed_step_share",
             "sparse_mixed_step_share")):
        assert files[name]["reader"] == reader
        assert files[name]["workloads"] == [MIX]
        assert files[name]["moves"] == "serve.capacity_tokens_per_s"
        assert {k: v for k, v in files[name].items() if k != "reader"} \
            == per_layer[name]
        assert name in mine
    assert {m["name"] for m in manifest.metrics_for(MIX, "end_to_end")} \
        == {"setup_s", "serve.capacity_tokens_per_s"}
    assert mine >= {"serve.closed.step_mfu", "device_idle_share.serve_closed",
                    "serve.closed.compiles_in_window",
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
    (a chunk of 4,096 beside 12 decode rows) at the published widths, 6
    layers, 16 held experts a layer, the pool of 12 x 32,768 positions in
    both arrays: each fits the chip beside weights and pool (about 7 s and
    30 s of compiling; not marked slow). In a token-step: the selection
    kernel once a ``full`` layer and the attention kernel once a layer; in the
    mixed step each twice, and the scoring kernel once a ``full`` layer; and
    both arrays of the pool stay where they came in, never copied."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import moe
    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the kernels' dispatch asks where computation lands: steer it here
    for module in (pa, moe):
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
        # two arrays of different depth on one set of page ids
        assert {k: v.shape for k, v in pool.items()} == {
            "latent": (6, 97, 4096, 640), "index": (2, 97, 4096, 128)}
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
                key).compile()
        stats = eng.kv_pool.stats()
    finally:
        eng.close()
    text = compiled.as_text()
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pool.values())
    assert held == stats["store_bytes"] == 97 * 4096 * 8192
    assert 12.6e9 < weights + held < 12.7e9     # 9.38 GB and 3.25 GB
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes \
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert m.alias_size_in_bytes >= held        # donated
    kernels = {name: len(set(re.findall(
        "%(" + name + r"[.\d]*) = ", text))) for name in (
            "index_scores", "index_select", "sparse_latent_attention")}
    if program == "decode":
        assert kernels == {"index_scores": 0, "index_select": 2,
                           "sparse_latent_attention": 6}
        assert total < weights + held + 0.5e9
    else:
        assert kernels == {"index_scores": 2, "index_select": 4,
                           "sparse_latent_attention": 12}
        assert total < weights + held + 2.6e9
    for shape in ("bf16[6,97,4096,640]", "bf16[2,97,4096,128]"):
        made = re.findall("= " + re.escape(shape) + r"\{[^ ]* (\S+?)\(", text)
        assert made and not set(made) & {"copy", "copy-start"}, shape
    assert total < hbm - 0.9e9
