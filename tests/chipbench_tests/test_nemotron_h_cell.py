"""``nemotron-3-super-d11-e128`` and ``longanswer-batch``, rehearsed off the
chip: the program against the plain reference at toy widths (whole forward;
a prefill in a padded bucket, then 40 decode steps through pages and state),
**the shares of an expert layer adding up to the uncut reference's whole
layer**, a wrong share read by the comparison, the toy cell through the serve
driver's closed loop with its control, the configuration's counts worked by
hand, the readers on hand-made numbers, and the cell's programs compiled for
a described v5e chip. No time read here is a device number.

The topology is described inside a module-scoped fixture only (every xdist
worker imports this file; only the one that runs it may load the TPU library).
"""

import json
import os
import re
import sys
import time

import numpy as np
import pytest

from chipbench import architectures, flops, harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import (expert_load_max_over_mean,
                               hybrid_moe_step_roofline,
                               moe_expert_tiles_roofline, ssm_update_roofline)
from chipbench_config_checks import check_config_file

NAME, MIX = "nemotron-3-super-d11-e128", "longanswer-batch"
SEED = 2 ** 31 + 35  # the driver's seeds pass 32 signed bits
TOY = dict(
    name="toy-nemotron", architecture="nemotron_h", hidden_size=64,
    intermediate_size=48, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=8, n_groups=2, conv_kernel=4, expand=2,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, num_experts_per_tok=6,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, routed_scaling_factor=5,
    norm_topk_prob=True, n_group=1, topk_group=1, n_shared_experts=1,
    rope_theta=10000, partial_rotary_factor=1, use_bias=False,
    mlp_bias=False, attention_bias=False, mamba_proj_bias=False,
    use_conv_bias=True, tie_word_embeddings=False, mlp_hidden_act="relu2",
    mamba_hidden_act="silu", time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4, chunk_size=128, sliding_window=None,
    moe_shared_expert_overlap=False, model_type="nemotron_h",
    num_hidden_layers=6, hybrid_override_pattern="MEM*EM",
    n_routed_experts=8, vocab_size=512, max_position_embeddings=128,
    num_nextn_predict_layers=0, n_router_experts=32, first_held_expert=8,
    param_dtype="bfloat16", activation_dtype="bfloat16")
TOY_F32 = dict(TOY, param_dtype="float32", activation_dtype="float32")
TOY_BATCH = {
    "name": "toy-longanswer", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                      "min": 4, "max": 48},
    "output_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 2, "max": 24},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 24},
    "trace_seconds": 1.0, "check": {"requests": 8, "gap_limit": 0.2}}
CELL = {"name": "toy", "chips": 1}


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def both():
    """(architecture, program config, program params, reference params),
    float32 throughout, from one key."""
    import jax

    arch = architectures.of(TOY_F32)
    pc = arch.program_config(TOY_F32)
    key = jax.random.PRNGKey(SEED)
    return (arch, pc, arch.init_program_params(key, pc),
            arch.reference().init_params(key, TOY_F32))


def test_the_recipes_weights_fit_the_programs_tree(both):
    """The benchmark hands the program the recipe's weights
    (``LLMServer(init=...)``): they have the tree, shapes and types of the
    program's own plain init, one dict a layer of the layer's kind; the
    router is as wide as published with a choosing bias that is not 0, and
    the experts held are a share."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import nemotron_h

    arch, pc, ours, theirs = both
    plain = nemotron_h.init_params(jax.random.PRNGKey(SEED), pc)
    assert jax.tree.structure(ours) == jax.tree.structure(plain)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(plain)))
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(theirs)) \
        == arch.n_params(TOY_F32)
    assert arch.server_kwargs(TOY_F32)["init"] is arch.init_program_params
    kinds = ["ssm_in" in p and "M" or "wq" in p and "*" or "E"
             for p in theirs["layers"]]
    assert "".join(kinds) == TOY["hybrid_override_pattern"]
    e = theirs["layers"][1]
    assert e["moe"]["router"].shape == (64, 32) and e["moe"]["bias"].shape \
        == (32,) and e["moe"]["w1"].shape == (8, 32, 48) \
        and e["moe"]["w2"].shape == (8, 48, 32) and "w3" not in e["moe"]
    assert e["down"].shape == (64, 32) and e["up"].shape == (32, 64)
    assert e["shared"]["w1"].shape == (64, 96)
    assert float(jnp.std(e["moe"]["bias"])) > 0.02
    gains = jnp.std(e["moe"]["router"].astype(jnp.float32), axis=0)
    assert float(gains.max() / gains.min()) > 2.0
    m = theirs["layers"][0]
    rate = np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert float(jnp.std(m["conv_b"])) > 0.03
    low = arch.reference().init_params(jax.random.PRNGKey(SEED), TOY)
    assert low["layers"][3]["wq"].dtype == jnp.bfloat16
    assert {low["layers"][0][k].dtype for k in ("A_log", "dt_bias", "D")} \
        == {jnp.dtype("float32")}


def test_program_forward_against_the_reference_at_toy_size(both):
    """Tolerance 2e-4 in float32 on logits of order one: both sides compute
    the same sums in another order (a chunked scan against a sequential one,
    sorted grouped matmuls against a loop over experts), which is float32's
    rounding over a few hundred terms; a lower precision reads thousands of
    times that."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import nemotron_h

    arch, pc, ours, theirs = both
    model = arch.reference()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 512)
    want = jnp.stack([model.logits(theirs, t, TOY_F32) for t in tokens])
    got = nemotron_h.forward(ours, tokens, pc)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    assert float(jnp.std(want)) > 0.5            # logits of order one
    low = jnp.stack([model.logits(theirs, t, TOY_F32, "fp8")
                     for t in tokens])
    assert float(jnp.max(jnp.abs(low - want))) > 0.1
    # another share of the same layer, a router that weighs with the bias's
    # choice but no bias, or a convolution without its bias is another model
    assert float(jnp.max(jnp.abs(model.logits(
        theirs, tokens[0], dict(TOY_F32, first_held_expert=0))
        - want[0]))) > 1e-2
    for flat in (
            [dict(p, conv_b=p["conv_b"] * 0) if "conv_b" in p else p
             for p in theirs["layers"]],
            [dict(p, moe=dict(p["moe"], bias=p["moe"]["bias"] * 0))
             if "moe" in p else p for p in theirs["layers"]]):
        assert float(jnp.max(jnp.abs(model.logits(
            dict(theirs, layers=flat), tokens[0], TOY_F32) - want[0]))) \
            > 1e-2


def _decode_after_prefill(ours, pc, seq, n_prompt, bucket):
    """The program's prefill of ``seq[:n_prompt]`` in ``bucket`` positions
    into pages and slot 0's state entry (over what an earlier request left
    there), then a decode step for each further token beside an idle slot:
    the logits of every step."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import nemotron_h

    page, sink = 16, 9
    toks = np.full((1, bucket), 7, np.int32)
    toks[0, :n_prompt] = seq[:n_prompt]
    first, row = jax.jit(lambda t, n: nemotron_h.prefill_row(
        ours, t, pc, bucket, n))(jnp.asarray(toks), n_prompt)
    table = np.full((2, 8), sink, np.int32)
    table[0, :6] = [4, 1, 5, 2, 0, 7]
    n = bucket // page
    spec, state = nemotron_h.cache_spec(pc), nemotron_h.state_spec(pc)
    pool = {}
    for name, (lead, trail, _) in spec.items():
        kv = jnp.zeros(lead + (sink + 1, page) + trail, jnp.float32)
        pool[name] = kv.at[:, :, table[0, :n]].set(
            row[name].reshape(lead + (n, page) + trail))
    for name, (lead, trail, _) in state.items():
        left = 0.5 * jnp.ones(lead + (2,) + trail, jnp.float32)
        at = (slice(None),) * len(lead) + (0,)
        pool[name] = left.at[at].set(row[name])
    step = jax.jit(lambda pool, last, at: nemotron_h.paged_decode(
        ours, last, pool, at, at, jnp.asarray(table), pc))
    out = [first]
    for t in range(n_prompt, len(seq)):
        logits, pool, _ = step(pool, jnp.asarray([seq[t], 1]),
                               jnp.asarray([t, 0]))
        out.append(logits[0])
    return jnp.stack(out)


@pytest.mark.parametrize("fault", ["none", "another_share",
                                   "weights_renormed_over_the_held"])
@pytest.mark.parametrize("n_prompt,bucket", [(23, 32), (41, 48)])
def test_prefill_in_a_padded_bucket_then_40_decode_steps_against_the_reference(
        both, n_prompt, bucket, fault, monkeypatch):
    """Prompts that end inside a page (and, at toy size, inside the scan's
    chunk) in a padded bucket, then 40 decode steps through the block table
    across page boundaries and through the slot's state: every step's logits
    are the reference's plain forward's (a sequential scan, a loop over the
    held experts, no cache), within 2e-4 in float32. A program that holds
    another share than the reference, or that normalises a token's weights
    over the experts it holds instead of over all it chose (a stand-in for
    the absent ones), is not, by hundreds of times that."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import moe

    arch, pc, ours, theirs = both
    if fault == "another_share":
        pc = arch.program_config(TOY_F32, first_held_expert=16)
    elif fault == "weights_renormed_over_the_held":
        plain = moe.grouped_experts

        def renormed(x, chosen, w, layer, live=None, first=0):
            held = (chosen >= first) & (chosen < first + layer["w1"].shape[0])
            w = jnp.where(held, w, 0.0)
            w = 5.0 * w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
            return plain(x, chosen, w, layer, live, first)

        monkeypatch.setattr(moe, "grouped_experts", renormed)
        jax.clear_caches()
    seq = np.random.default_rng(n_prompt).integers(
        2, 512, n_prompt + 40).tolist()
    want = arch.reference().logits(theirs, jnp.asarray(seq),
                                   TOY_F32)[n_prompt - 1:]
    try:
        got = _decode_after_prefill(ours, pc, seq + [0], n_prompt,
                                    bucket)[:41]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    worst = float(jnp.max(jnp.abs(got - want)))
    if fault == "none":
        assert worst < 2e-4, worst
    else:
        assert worst > 0.05, worst


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips share a layer of 32 experts, 8 a chip. Each share's routed
    part (the program's: the router over all 32, the latent projections, its
    own 8 experts, nothing in place of the rest), and the shared expert
    counted once, sum to what the uncut reference gives for the whole layer
    with all 32 held; the reference's own shares do too; and the shares'
    counts of assignments are all of them."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import nemotron_h

    whole_cfg = dict(TOY_F32, n_routed_experts=32, first_held_expert=0)
    arch = architectures.of(whole_cfg)
    model = arch.reference()
    key = jax.random.PRNGKey(SEED + 1)
    p = model.init_params(key, whole_cfg)["layers"][1]      # an E layer
    assert p["moe"]["w1"].shape[0] == 32
    u = jax.random.normal(jax.random.PRNGKey(3), (40, 64))
    mm = jnp.matmul
    want = model._experts(u, p, whole_cfg, mm)              # the uncut layer
    shared = model._relu2(u, p["shared"]["w1"], p["shared"]["w2"], mm)
    ours, theirs, counted = shared, shared, 0
    for first in (0, 8, 16, 24):
        held = dict(p, moe=dict(p["moe"], w1=p["moe"]["w1"][first:first + 8],
                                w2=p["moe"]["w2"][first:first + 8]))
        cut = dict(TOY_F32, first_held_expert=first)
        theirs = theirs + model.routed_part(u, held, cut, mm)
        pc = arch.program_config(cut)
        y, counts = jax.jit(lambda u, held, pc=pc: nemotron_h._experts(
            u, held, pc))(u, held)
        ours = ours + (y - nemotron_h._relu2(u, held["shared"], pc))
        counted += int(counts.sum())
        # a share alone is not the layer
        assert float(jnp.max(jnp.abs(y - want))) > 0.1
    assert float(jnp.max(jnp.abs(theirs - want))) < 2e-5
    assert float(jnp.max(jnp.abs(ours - want))) < 2e-4
    assert counted == 40 * 6


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
    # the metrics with no list of cells and the one new counter metric; the
    # CPU has no device plane, so the two roofline shares are left out, and
    # the metrics that list other cells are not this cell's
    assert got >= {"serve.closed.tokens_per_decode_step",
                   "serve.closed.compiles_in_window",
                   "serve.closed.held_expert_load_max_over_mean",
                   "runtime.lease_to_device_s", "compile.setup_compile_s"}
    assert not got & {"serve.closed.hybrid_moe_step_roofline",
                      "serve.closed.ssm_update_roofline_hd64",
                      "serve.closed.moe_expert_tiles_roofline",
                      "serve.closed.ssm_update_roofline",
                      "serve.closed.hybrid_step_roofline",
                      "serve.closed.decode_step_roofline",
                      "serve.closed.expert_load_max_over_mean",
                      "serve.closed.step_mfu"}
    assert line["metrics"]["serve.closed.compiles_in_window"]["value"] == 0
    assert 1.0 <= line["metrics"][
        "serve.closed.held_expert_load_max_over_mean"]["value"] < 8.0
    # what the step counted of itself arrives in the snapshots
    b, a = (r["context"][k]["engine"] for k in ("before", "after"))
    d = lambda k: a[k] - b[k]  # noqa: E731
    assert d("ssm_layer_steps") > 0 and d("ssm_layer_steps") % 3 == 0
    assert 1.0 <= d("state_rows_stepped") / d("ssm_layer_steps") <= 4.0
    # off the TPU the plain form reads every slot's state, idle or not
    assert d("state_rows_fetched") == 4 * d("ssm_layer_steps")
    assert d("expert_layer_steps") * 3 == d("ssm_layer_steps") * 2
    assert len(a["expert_tokens"]) == 8
    held = sum(a["expert_tokens"]) - sum(b["expert_tokens"])
    assert held == d("expert_assignments_held")
    # 6 choices a live row and expert layer, about a quarter of them held
    assert d("expert_assignments") == 6 * 2 * d("state_rows_stepped") // 3
    assert 0.1 < held / d("expert_assignments") < 0.45
    conv = 128 + 2 * 2 * 8
    assert a["state_row_bytes"] == 3 * (8 * 16 * 8 * 4 + 3 * conv * 2) \
        == architectures.of(TOY).state_row_bytes(TOY)
    assert a["cache_token_bytes"] == 1 * 2 * 2 * 16 * 2
    kv = r["context"]["after"]["kv"]
    assert kv["state_bytes"] == 4 * a["state_row_bytes"]


def test_bf16_parameters_read_as_fp8_come_out_not_correct(toy_cell):
    c = toy_cell["comparisons"]
    assert c["control_logit_gap_max"][0] > 2 * c["served_logit_gap_max"][0]
    assert c["control_logit_gap_max"][0] > TOY_BATCH["check"]["gap_limit"]


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_one_cut_to_one_chips_share():
    cfg = manifest.config(NAME)
    check_config_file(cfg)
    reduced = ["hybrid_override_pattern", "max_position_embeddings",
               "n_routed_experts", "num_hidden_layers",
               "num_nextn_predict_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == reduced
    pub = cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"], cfg["num_nextn_predict_layers"]) \
        == (11, "MEMEMEM*EME", 128, 32768, 4096, 0)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"], pub["max_position_embeddings"],
            pub["num_nextn_predict_layers"]) == (88, 512, 131072, 262144, 1)
    # the first eleven characters of the published pattern, whose ratio of
    # kinds it keeps; the router stays as wide as published
    assert pub["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert [pub["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [40, 40, 8]
    assert [cfg["hybrid_override_pattern"].count(k) for k in "ME*"] \
        == [5, 5, 1]
    assert cfg["n_router_experts"] == pub["n_routed_experts"]
    assert cfg["first_held_expert"] == 0
    differs = {k for k, v in pub.items() if cfg[k] != v}
    assert differs == set(reduced)
    # every number of the catalog's config is in the file under its own key
    assert set(pub) <= set(cfg)
    entry = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == NAME)
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for width in ("moe_latent_size", "mamba_head_dim", "ssm_state_size",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "head_dim"):
        with pytest.raises(AssertionError):
            check_config_file(dict(cfg, reduced=cfg["reduced"] + [width]))
    arch = architectures.of(cfg)
    pc = arch.program_config(cfg)
    assert (pc.d_model, pc.n_heads, pc.kv_heads, pc.head_dim) \
        == (4096, 32, 2, 128)
    assert (pc.ssm_inner, pc.ssm_heads, pc.ssm_head_dim, pc.ssm_state,
            pc.ssm_groups, pc.ssm_conv) == (8192, 128, 64, 128, 8, 4)
    assert (pc.conv_width, pc.ssm_proj_width, pc.state_pack) \
        == (10240, 18560, 2)
    assert (pc.moe_latent, pc.moe_d_ff, pc.shared_d_ff) == (1024, 2688, 5376)
    assert (pc.n_routed_experts, pc.n_held_experts, pc.first_held_expert,
            pc.experts_per_tok, pc.routed_scaling_factor) \
        == (512, 128, 0, 22, 5)
    assert (pc.vocab_size, pc.rms_norm_eps, pc.max_seq, pc.pattern) \
        == (32768, 1e-5, 4096, "MEMEMEM*EME")
    # a published switch the program does not have is refused, not ignored
    for key, value in (("n_group", 2), ("tie_word_embeddings", True),
                       ("mlp_hidden_act", "silu"), ("use_conv_bias", False),
                       ("num_nextn_predict_layers", 1),
                       ("num_hidden_layers", 12), ("expand", 4)):
        with pytest.raises(ValueError):
            arch.program_config(dict(cfg, **{key: value}))
    cell = manifest.cell(MIX)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, MIX, 1)


def test_counts_against_hand_worked_ones():
    cfg = manifest.config(NAME)
    arch = architectures.of(cfg)
    mamba_mm = 4096 * 18560 + 8192 * 4096
    mamba_small = 10240 * 5 + 3 * 128 + 8192
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    expert = 2 * 1024 * 2688
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert mamba_mm + mamba_small + 4096 == 109_640_064
    assert attn + 4096 == 35_655_680
    assert (outside + 512 + 4096, expert) == (54_530_560, 5_505_024)
    head = 4096 * 32768
    held = 5 * mamba_mm + attn + 5 * (outside + 128 * expert)
    assert arch.matmul_params(cfg) == (held / 11, head)
    assert arch.n_params(cfg) == 5 * 109_640_064 + 35_655_680 \
        + 5 * (54_530_560 + 128 * expert) + 2 * head + 4096 \
        == 4_648_163_712
    # whole, by the same parts: the name's 120B-A12B
    whole = 40 * 109_640_064 + 8 * 35_655_680 \
        + 40 * (54_530_560 + 512 * expert) + 2 * 4096 * 131072 + 4096
    active = whole - 40 * (512 - 22) * expert
    assert round(whole / 1e9, 2) == 120.67 and round(active / 1e9, 2) == 12.77
    # the cache: K and V of 2 heads x 128 in bf16 in the one attention layer
    assert arch.cache_token_bytes(cfg) == 2 * 2 * 128 * 2 == 1024
    state = 128 * 64 * 128
    assert arch.state_row_bytes(cfg) == 5 * (state * 4 + 3 * 10240 * 2) \
        == 21_278_720
    assert (arch.ssm_layers(cfg), arch.expert_layers(cfg),
            arch.held_expert_share(cfg)) == (5, 5, 0.25)
    mix = manifest.traffic(MIX)
    e = serve_driver.engine_kwargs(cfg, mix)
    assert e["kv_pool_bytes"] == 128 * 4096 * 1024 == 536_870_912
    assert "max_concurrent_queries" not in e
    assert mix["engine"]["max_concurrent_queries"] >= mix["clients"]
    assert arch.attention_shape(cfg) == (32, 128)
    # FLOPs of the parameters a token uses here: 5.5 of the 128 held experts
    token = 2 * (5 * mamba_mm + attn + 5 * (outside + 5.5 * expert) + head) \
        + 5 * (6 * state + 2 * 8192 + 2 * 4 * 10240)
    pair = 4 * 32 * 128
    assert arch.forward_flops(cfg, 1, 1000) == token + 1000 * pair
    assert arch.forward_flops(cfg, 3, 0) == 3 * token
    assert 2.2e9 < token < 2.5e9          # the issue's 2.3 GFLOP a token
    # the roofline's counts: 128 rows over 128 x 1,000 positions, all held
    # experts touched: the issue's 9.03 GB of weights and 5.4 GB of state
    f, b = arch.decode_step_work(cfg, 128, 128_000, 128)
    weights = 2 * (5 * (mamba_mm + mamba_small) + attn
                   + 5 * (outside + 128 * expert) + head)
    assert b == weights + 2 * 128 * 21_278_720 + 128_000 * 1024
    assert f == arch.forward_flops(cfg, 128, 128_000)
    assert 9.0e9 < weights < 9.06e9 and 5.4e9 < 2 * 128 * 21_278_720 < 5.5e9
    # an expert nobody reached is not streamed, an idle slot not moved
    f2, b2 = arch.decode_step_work(cfg, 100, 128_000, 120)
    assert b - b2 == 2 * 5 * 8 * expert + 2 * 28 * 21_278_720
    f, b = arch.ssm_update_work(cfg, 128)
    assert (f, b) == (6 * 128 * state, 2 * 128 * state * 4)
    # the expert kernel: a decode step's grid, and one call's least work
    # (704 held assignments on 126 touched experts: 1.39 GB of matrices)
    assert arch.expert_kernel_tiles(cfg, 128) == 128   # a tile an expert
    assert arch.expert_kernel_tiles(cfg, 512) == 88 + 128
    assert arch.expert_kernel_tiles(cfg, 2048) == 352 + 128
    f, b = arch.expert_kernel_work(cfg, 704, 126)
    assert f == 704 * 4 * 1024 * 2688
    assert b == 126 * expert * 2 + 704 * 1024 * (2 + 4)
    assert b / 819e9 > 30 * f / 197e12                # bound by the bytes


def test_warm_up_reaches_every_program_of_longanswer_batch():
    mix = manifest.traffic(MIX)
    e = mix["engine"]
    assert (mix["clients"], mix["requests_per_client"], mix["order_block"],
            mix["schedule_seed"]) == (192, 8, 8, 35)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.8, "min": 32, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 640,
                                    "sigma": 0.7, "min": 32, "max": 1536}
    assert {k: e[k] for k in ("max_batch_size", "steps_per_iter",
                              "pad_multiple", "kv_page_tokens",
                              "max_new_tokens", "kv_pool_bytes")} == {
        "max_batch_size": 128, "steps_per_iter": 8, "pad_multiple": 512,
        "kv_page_tokens": 512, "max_new_tokens": 1536,
        "kv_pool_bytes": 536_870_912}
    waves = serve_driver.warm_up_waves(mix)
    up = lambda x, m: -(-x // m) * m  # noqa: E731
    buckets = {up(w["prompt"], e["pad_multiple"]) for wave in waves
               for w in wave}
    assert buckets == {512, 1024, 1536, 2048}        # 4 prefill programs
    assert all(len(wave) <= e["max_batch_size"] for wave in waves)
    assert mix["prompt_tokens"]["max"] + e["max_new_tokens"] \
        <= manifest.config(NAME)["max_position_embeddings"]


# ------------------------------------------------------------- the readers
def _ctx(before, after, trace):
    return {"cfg": manifest.config(NAME), "mix": manifest.traffic(MIX),
            "before": {"engine": before}, "after": {"engine": after},
            "trace": trace, "device": {"kind": "TPU v5 lite",
                                       "platform": "tpu", "count": 1}}


def no_experts_yet(engine):
    return {k: v for k, v in engine.items() if not k.startswith("expert")}


def test_the_readers_on_hand_made_numbers():
    zeros = [0] * 128
    before = {"state_rows_stepped": 0, "state_rows_fetched": 0,
              "ssm_layer_steps": 0, "iterations": 0, "live_positions": 0,
              "expert_tokens": zeros, "experts_touched": 0,
              "expert_layer_steps": 0, "expert_assignments": 0,
              "expert_assignments_held": 0}
    # 100 iterations of 8 token-steps; 120 rows live over 150,000 positions;
    # 127.5 of the 128 held experts touched a layer; the busiest held expert
    # takes twice the mean
    steps = 100 * 8
    tokens = [1000] * 127 + [2000]
    after = {"state_rows_stepped": 120 * 5 * steps,
             "state_rows_fetched": 120 * 5 * steps,
             "ssm_layer_steps": 5 * steps, "iterations": 100,
             "live_positions": 100 * 150_000, "expert_tokens": tokens,
             "experts_touched": int(127.5 * 5 * steps),
             "expert_layer_steps": 5 * steps,
             "expert_assignments": 120 * 5 * 22 * steps,
             "expert_assignments_held": sum(tokens)}
    # the traced seconds hold 50 token-steps: 250 kernel calls in 5 ops
    trace = {"programs": {"jit_paged_step_fn": 50 * 0.024,
                          "jit_prefill": 0.4},
             "ops": {f"%ssm_decode_update.{i}": 50 * 0.0016
                     for i in range(5)},
             "op_calls": {f"%ssm_decode_update.{i}": 50 for i in range(5)},
             "op_text": {}}
    ctx = _ctx(before, after, trace)
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    f, b = arch.decode_step_work(cfg, 120.0, 150_000.0, 127.5)
    least = max(f / 197e12, b / 819e9)
    assert b / 819e9 > f / 197e12                     # bound by the bytes
    got = hybrid_moe_step_roofline.read(ctx)
    assert got == pytest.approx(100 * least / 0.024, rel=1e-9)
    assert 65 < got < 80                              # 17.5 of 24 ms
    # counting the absent experts' weights too would read over 100%
    f, b = arch.decode_step_work(cfg, 120.0, 150_000.0, 512)
    assert 100 * b / 819e9 / 0.024 > 100
    f, b = arch.ssm_update_work(cfg, 120.0)
    assert ssm_update_roofline.read(ctx) == pytest.approx(
        100 * (b / 819e9) / 0.0016, rel=1e-9)
    assert 70 < ssm_update_roofline.read(ctx) < 85    # 1.23 of 1.6 ms
    assert expert_load_max_over_mean.read(ctx) == pytest.approx(
        2000 * 128 / sum(tokens))
    # the expert kernel: the decode step's calls are named for its 128
    # tiles; a prefill's, at another size, are not read
    trace["ops"].update({f"%moe_expert_tiles_128.{i}": 50 * 0.0021
                         for i in range(5)}, **{"%moe_expert_tiles_480": 9.0})
    trace["op_calls"].update({f"%moe_expert_tiles_128.{i}": 50
                              for i in range(5)},
                             **{"%moe_expert_tiles_480": 70})
    held = sum(tokens) / (5 * steps)
    f, b = arch.expert_kernel_work(cfg, held, 127.5)
    assert moe_expert_tiles_roofline.read(ctx) == pytest.approx(
        100 * (b / 819e9) / 0.0021, rel=1e-9)
    assert 75 < moe_expert_tiles_roofline.read(ctx) < 90   # 1.72 of 2.1 ms
    assert moe_expert_tiles_roofline.read(_ctx(before, after, None)) is None
    assert moe_expert_tiles_roofline.read(_ctx(before, no_experts_yet(after),
                                               trace)) is None
    # nothing to read is None and never 0: no trace (an untraced or CPU
    # run), no kernel in it, or a program without the counts (the parent,
    # and every other model's cell)
    for trace_ in (None, dict(trace, ops={}, op_calls={})):
        assert hybrid_moe_step_roofline.read(
            _ctx(before, after, trace_)) is None
    assert hybrid_moe_step_roofline.read(
        _ctx({"iterations": 5}, {"iterations": 105}, trace)) is None
    assert hybrid_moe_step_roofline.read(_ctx(after, after, trace)) is None
    assert hybrid_moe_step_roofline.read(
        _ctx(before, no_experts_yet(after), trace)) is None
    old = {"cfg": manifest.config("mistral-7b-d16"), "before": {},
           "after": {}, "trace": trace, "device": ctx["device"]}
    assert hybrid_moe_step_roofline.read(old) is None
    # the three new metrics name their readers and list this cell alone
    files = manifest.metric_files()
    for name, reader in (
            ("serve.closed.hybrid_moe_step_roofline",
             "hybrid_moe_step_roofline"),
            ("serve.closed.ssm_update_roofline_hd64", "ssm_update_roofline"),
            ("serve.closed.held_expert_load_max_over_mean",
             "expert_load_max_over_mean"),
            ("serve.closed.moe_expert_tiles_roofline",
             "moe_expert_tiles_roofline")):
        assert files[name]["reader"] == reader
        assert files[name]["workloads"] == [MIX]
        assert files[name]["moves"] == "serve.capacity_tokens_per_s"


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


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["decode", "prefill", "check"])
def test_the_cells_programs_fit_a_described_v5e(one_chip, program,
                                                monkeypatch):
    """The engine's one decode step and its prefill of 2,048 positions at
    the published widths, 11 layers, 128 held experts a layer, 128 slots,
    the pool of 128 x 4,096 positions and the 128 slots' state; and the
    output check's comparison of a 4,096-token sample with its control. In
    the decode step: the paged attention kernel once, **the state-update
    kernel, not its plain form, once a Mamba layer**, the expert kernel (and
    no ``ragged_dot``) once an expert layer, and K, V and the recurrence's state where they came
    in, never copied. In the prefill: the flash forward kernel once and the
    chunked scan's kernel and the expert kernel five times each."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = manifest.config(NAME)
    arch = architectures.of(cfg)
    hbm = flops.peak("TPU v5 lite")["hbm_bytes"]
    weights = 2 * arch.n_params(cfg)
    if program == "check":
        model = arch.reference()
        params = shaped(jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), cfg)))

        def gaps(p, tokens, served_at, start):
            ref = model.logits(p, tokens, cfg)
            pos = start + jnp.arange(1536)
            low = jnp.argmax(model.logits(p, tokens, cfg, "fp8"), -1)
            best = jnp.max(ref, axis=-1)[pos]
            return best - ref[pos, served_at], best - ref[pos, low[pos]]

        compiled = jax.jit(gaps).lower(
            params, arr((4096,)), arr((1536,)), arr(())).compile()
        # the reference's weights and a sample's logits over the held
        # vocabulary (0.54 GB); the engine's pool has gone with its weights
        assert weights + 1.0e9 < _total_bytes(compiled) < hbm - 4.0e9
        return
    # the kernels' dispatch asks where computation lands: steer it here
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    import ray_memory_management_tpu.models.nemotron_h  # noqa: F401
    for name in ("ops.flash_attention", "ops.ssm", "ops.moe",
                 "models.hybrid_ssm"):
        monkeypatch.setattr(
            sys.modules["ray_memory_management_tpu." + name],
            "_on_tpu", lambda: True)
    mix = manifest.traffic(MIX)
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
        # by kind: one attention layer's pages, five Mamba layers' state
        # (two heads of 64 channels a row), nothing for the expert layers
        assert {k: v.shape for k, v in pool.items()} == {
            "k": (1, 2, 1025, 512, 128), "v": (1, 2, 1025, 512, 128),
            "ssm": (5, 128, 64, 128, 128), "conv": (5, 3, 128, 10240)}
        width = eng.kv_pool.table_width
        assert width == 8
        if program == "decode":
            compiled = eng._paged_step.lower(
                params, pool, arr((slots,)), arr((slots,)),
                arr((slots, width)), arr((2,), jnp.uint32)).compile()
        else:
            compiled = eng._paged_prefill_fn(2048).lower(
                params, pool, arr((1, 2048)), arr((width,)), arr(()),
                arr((2,), jnp.uint32), arr(())).compile()
        stats = eng.kv_pool.stats()
    finally:
        eng.close()
    text = compiled.as_text()
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pool.values())
    assert held == stats["store_bytes"] == 1025 * 512 * 1024 \
        + 128 * 21_278_720
    assert 12.5e9 < weights + held < 12.6e9   # 9.30 GB, 0.54 + 2.72 GB
    total = _total_bytes(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes >= held  # donated
    if program == "decode":
        assert len(set(re.findall(r"%(ssm_decode_update[.\d]*) = ",
                                  text))) == 5
        assert len(set(re.findall(r"%(paged_decode_attention[.\d]*) = ",
                                  text))) == 1
        assert len(set(re.findall(r"%(moe_expert_tiles_128[.\d]*) = ",
                                  text))) == 5
        assert "ragged-dot" not in text
        assert text.count("tpu_custom_call") == 11
        for shape, layout in (("f32[5,128,64,128,128]", "4,3,2,1,0"),
                              ("bf16[1,2,1025,512,128]", "4,3,2,1,0")):
            made = re.findall(
                "= " + re.escape(shape) + r"\{([\d,]+)[^ ]* (\S+?)\(", text)
            assert made and {lay for lay, _ in made} == {layout}, shape
            assert not {op for _, op in made} & {"copy", "copy-start"}, shape
        assert weights + held < total < weights + held + 0.4e9
    else:
        assert len(set(re.findall(r"%(ssd_chunk_scan[.\d]*) = ", text))) == 5
        assert len(set(re.findall(r"%(_fwd_call[.\d]*) = ", text))) == 1
        assert len(set(re.findall(r"%(moe_expert_tiles_480[.\d]*) = ",
                                  text))) == 5
        assert "ragged-dot" not in text
        assert weights + held < total < weights + held + 0.9e9
    assert total < hbm - 2.0e9
