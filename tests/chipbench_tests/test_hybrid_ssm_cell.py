"""``falcon-h1-34b-d5`` and ``conversation-batch``, rehearsed off the chip:
the program against the plain reference at toy widths (whole forward; a
prefill in a padded bucket, then 40 decode steps through pages and state),
faults planted in the recurrence read by the check's own comparison, the toy
cell through the serve driver's closed loop with its control, the
configuration's counts worked by hand, the two readers on hand-made numbers,
and the cell's programs compiled for a described v5e chip. No time read here
is a device number.

The topology is described inside a module-scoped fixture only (every xdist
worker imports this file; only the one that runs it may load the TPU library).
"""

import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

from chipbench import architectures, flops, harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import hybrid_step_roofline, ssm_update_roofline
from chipbench_config_checks import check_config_file

SEED = 2 ** 31 + 33  # the driver's seeds pass 32 signed bits
TOY = dict(
    name="toy-hybrid", architecture="hybrid_ssm", hidden_size=64,
    intermediate_size=96, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=8, mamba_n_groups=2, mamba_d_conv=4, mamba_expand=1,
    mlp_expansion_factor=1.5, embedding_multiplier=5.5,
    lm_head_multiplier=0.08, attention_in_multiplier=1.0,
    attention_out_multiplier=0.04, key_multiplier=0.1,
    ssm_in_multiplier=0.25, ssm_out_multiplier=0.09,
    ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.35],
    mlp_multipliers=[0.18, 0.011], vocab_size=512, rope_theta=1e4,
    rms_norm_eps=1e-5, mamba_rms_norm=True, mamba_norm_before_gate=False,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    mlp_bias=False, projectors_bias=False, tie_word_embeddings=False,
    hidden_act="silu", rope_scaling=None, num_hidden_layers=3,
    max_position_embeddings=128, param_dtype="bfloat16",
    activation_dtype="bfloat16")
TOY_F32 = dict(TOY, param_dtype="float32", activation_dtype="float32")
TOY_BATCH = {
    "name": "toy-conversation", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 30, "sigma": 0.5,
                      "min": 4, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                      "min": 2, "max": 16},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 16},
    "trace_seconds": 1.0, "check": {"requests": 8, "gap_limit": 0.05}}
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
    program's own plain init; the recurrence's own parameters follow
    Mamba-2's convention and the convolution has a bias that is not 0."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import hybrid_ssm

    arch, pc, ours, theirs = both
    plain = hybrid_ssm.init_params(jax.random.PRNGKey(SEED), pc)
    assert jax.tree.structure(ours) == jax.tree.structure(plain)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(plain)))
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(theirs)) \
        == arch.n_params(TOY_F32)
    assert arch.server_kwargs(TOY_F32)["init"] is arch.init_program_params
    layer = theirs["layers"][1]
    rate = np.exp(np.asarray(layer["A_log"]))
    dt = np.log1p(np.exp(np.asarray(layer["dt_bias"])))
    assert rate.min() >= 1.0 and rate.max() <= 16.0
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(layer["D"]) == 1.0)
    assert float(jnp.std(layer["conv_b"])) > 0.03
    # bf16 weights keep A_log, dt_bias and D in float32
    low = arch.reference().init_params(jax.random.PRNGKey(SEED), TOY)
    assert low["layers"][0]["wq"].dtype == jnp.bfloat16
    assert {low["layers"][0][k].dtype for k in ("A_log", "dt_bias", "D")} \
        == {jnp.dtype("float32")}


def test_program_forward_against_the_reference_at_toy_size(both):
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import hybrid_ssm

    arch, pc, ours, theirs = both
    model = arch.reference()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 512)
    want = jnp.stack([model.logits(theirs, t, TOY_F32) for t in tokens])
    got = hybrid_ssm.forward(ours, tokens, pc)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    assert float(jnp.std(want)) > 0.5            # logits of order one
    low = jnp.stack([model.logits(theirs, t, TOY_F32, "fp8")
                     for t in tokens])
    assert float(jnp.max(jnp.abs(low - want))) > 0.1
    # a multiplier left out, or the convolution's bias dropped, is another
    # model: each moves the reference's own logits
    for other in (dict(TOY_F32, ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.5]),
                  dict(TOY_F32, key_multiplier=0.2),
                  dict(TOY_F32, mlp_multipliers=[0.18, 0.02])):
        assert float(jnp.max(jnp.abs(
            model.logits(theirs, tokens[0], other) - want[0]))) > 1e-2
    flat = dict(theirs, layers=[dict(p, conv_b=p["conv_b"] * 0)
                                for p in theirs["layers"]])
    assert float(jnp.max(jnp.abs(
        model.logits(flat, tokens[0], TOY_F32) - want[0]))) > 1e-2


def _decode_after_prefill(ours, pc, seq, n_prompt, bucket, *, stop=True,
                          reset=True):
    """The program's prefill of ``seq[:n_prompt]`` in ``bucket`` positions
    into pages and slot 0's state entry, then a decode step for each further
    token: the logits of every step. ``stop`` false: the recurrence runs on
    over the bucket's padding; ``reset`` false: the slot keeps what an
    earlier request left in its entry."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.models import hybrid_ssm

    page, sink, L = 16, 9, pc.n_layers
    toks = np.full((1, bucket), 7, np.int32)
    toks[0, :n_prompt] = seq[:n_prompt]
    prefill = jax.jit(lambda t, n: hybrid_ssm.prefill_row(ours, t, pc,
                                                          bucket, n))
    first, row = prefill(jnp.asarray(toks), n_prompt if stop else bucket)
    if not stop:  # the logits of the prompt's last token all the same
        first, _ = prefill(jnp.asarray(toks), n_prompt)
    table = np.full((2, 8), sink, np.int32)
    table[0, :6] = [4, 1, 5, 2, 0, 7]
    n = bucket // page
    kv = jnp.zeros((L, pc.kv_heads, sink + 1, page, pc.head_dim),
                   jnp.float32)
    pool = {name: kv.at[:, :, table[0, :n]].set(row[name].reshape(
        L, pc.kv_heads, n, page, pc.head_dim)) for name in ("k", "v")}
    # what an earlier request left in the slot
    left = {"ssm": 0.5 * jnp.ones((L, 2, pc.ssm_heads, pc.ssm_state,
                                   pc.ssm_head_dim), jnp.float32),
            "conv": 0.5 * jnp.ones((L, 3, 2, pc.conv_width), jnp.float32)}
    pool["ssm"] = left["ssm"].at[:, 0].set(row["ssm"]) if reset \
        else left["ssm"]
    pool["conv"] = left["conv"].at[:, :, 0].set(row["conv"]) if reset \
        else left["conv"]
    step = jax.jit(lambda pool, last, at: hybrid_ssm.paged_decode(
        ours, last, pool, at, at, jnp.asarray(table), pc))
    out = [first]
    for t in range(n_prompt, len(seq)):
        logits, pool, _ = step(pool, jnp.asarray([seq[t], 1]),
                               jnp.asarray([t, 0]))
        out.append(logits[0])
    return jnp.stack(out)


@pytest.mark.parametrize("fault", ["none", "state_not_stopped_at_true_len",
                                   "slot_keeps_its_old_state"])
def test_prefill_in_a_padded_bucket_then_40_decode_steps_against_the_reference(
        both, fault):
    """23 prompt tokens in a bucket of 32, then 40 decode steps through the
    block table across two page boundaries and through the slot's state:
    every step's logits are the reference's plain forward's (a sequential
    scan, no cache), within 2e-4. With the recurrence run on over the
    padding, or the slot's entry left as an earlier request left it, they
    are not, by hundreds of times that."""
    import jax.numpy as jnp

    arch, pc, ours, theirs = both
    seq = np.random.default_rng(3).integers(2, 512, 23 + 40).tolist()
    want = arch.reference().logits(theirs, jnp.asarray(seq), TOY_F32)[22:]
    got = _decode_after_prefill(
        ours, pc, seq + [0], 23, 32,
        stop=fault != "state_not_stopped_at_true_len",
        reset=fault != "slot_keeps_its_old_state")[:41]
    worst = float(jnp.max(jnp.abs(got - want)))
    if fault == "none":
        assert worst < 2e-4, worst
    else:
        assert worst > 0.05, worst
        # the prompt's last token does not pass through the slot's state
        assert float(jnp.max(jnp.abs(got[0] - want[0]))) < 2e-4


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
    line = json.loads(json.dumps(
        harness.result_line("conversation-batch", trace, r)))
    assert list(line)[-1] == "compared"
    if not trace:
        assert set(line["metrics"]) == {"setup_s",
                                        "serve.capacity_tokens_per_s"}
        return
    got = set(line["metrics"])
    # the metrics with no list of cells; the CPU has no device plane, so
    # the two roofline shares this PR brings are left out, and the metrics
    # that list other cells are not this cell's
    assert got >= {"serve.closed.tokens_per_decode_step",
                   "serve.closed.compiles_in_window",
                   "runtime.lease_to_device_s", "compile.setup_compile_s"}
    assert not got & {"serve.closed.ssm_update_roofline",
                      "serve.closed.hybrid_step_roofline",
                      "serve.closed.decode_step_roofline",
                      "serve.closed.step_mfu",
                      "serve.closed.engine_prefill_share",
                      "serve.closed.prefill_ms_per_kpos"}
    assert line["metrics"]["serve.closed.compiles_in_window"]["value"] == 0
    # what the step counted of itself arrives in the snapshots
    b, a = (r["context"][k]["engine"] for k in ("before", "after"))
    rows = a["state_rows_stepped"] - b["state_rows_stepped"]
    steps = a["ssm_layer_steps"] - b["ssm_layer_steps"]
    assert steps > 0 and steps % 3 == 0 and 1.0 <= rows / steps <= 4.0
    # off the TPU the plain form reads every slot's state, idle or not
    assert a["state_rows_fetched"] - b["state_rows_fetched"] == 4 * steps
    conv = 64 + 2 * 2 * 8
    assert a["state_row_bytes"] == 3 * (4 * 16 * 8 * 4 + 3 * conv * 2) \
        == architectures.of(TOY).state_row_bytes(TOY)
    assert a["cache_token_bytes"] == 3 * 2 * 2 * 16 * 2
    kv = r["context"]["after"]["kv"]
    assert kv["state_bytes"] == 4 * a["state_row_bytes"]


def test_bf16_parameters_read_as_fp8_come_out_not_correct(toy_cell):
    c = toy_cell["comparisons"]
    assert c["control_logit_gap_max"][0] > 3 * c["served_logit_gap_max"][0]
    assert c["control_logit_gap_max"][0] > TOY_BATCH["check"]["gap_limit"]


# --------------------- faults in the recurrence, through the check's reading
def _served_by(params, pc, prompts, budget, slots):
    """Greedy answers of the program's engine, in this process."""
    from ray_memory_management_tpu.serve.llm import LLMServer

    engine = dict(serve_driver.engine_kwargs(TOY_F32, TOY_BATCH),
                  max_batch_size=slots)
    srv = LLMServer(config=pc, init=lambda key, cfg: params, **engine)
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = srv.generate(prompts[i], max_new_tokens=budget)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    srv._engine.close()
    return [{"prompt": p, "served": list(o)} for p, o in zip(prompts, outs)]


@pytest.mark.parametrize("fault", ["none", "state_not_stopped_at_true_len",
                                   "slot_keeps_its_old_state"])
def test_a_fault_in_the_recurrence_reads_over_the_limit(both, fault,
                                                        monkeypatch):
    """``check_samples`` on what the program's engine serves with a fault
    planted in it: float32 on both sides, so the reading is the fault's
    alone. Twelve requests on two slots, so that a slot is admitted again,
    most of them short prompts in buckets of 16 with a long tail of padding
    (at toy size a state forgets in tens of positions)."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import ssm
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    arch, pc, ours, _ = both
    if fault == "state_not_stopped_at_true_len":
        scan = ssm.ssd_scan
        monkeypatch.setattr(ssm, "ssd_scan", lambda *a, true_len=None, **k:
                            scan(*a, **k))
    elif fault == "slot_keeps_its_old_state":
        build = ContinuousBatcher._paged_prefill_fn

        def keeps(self, bucket):
            fn = build(self, bucket)

            def prefill(params, pool, *rest):
                old = {k: jnp.copy(pool[k]) for k in ("ssm", "conv")}
                pool, first = fn(params, pool, *rest)
                return dict(pool, **old), first

            return prefill

        monkeypatch.setattr(ContinuousBatcher, "_paged_prefill_fn", keeps)
    jax.clear_caches()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 512, int(n)).tolist()
               for n in (3, 5, 17, 18, 19, 33, 34, 35, 49, 50, 2, 1)]
    try:
        samples = _served_by(ours, pc, prompts, 16, slots=2)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    got = serve_driver.check_samples(TOY_F32, SEED, samples)
    assert got["tokens"] == 12 * 16
    if fault == "none":
        assert got["gap_max"] < 1e-3
    else:
        assert got["gap_max"] > 2 * TOY_BATCH["check"]["gap_limit"], got


# ------------------------------------------------------- the configuration
def test_the_configuration_is_the_published_one_cut_in_depth_and_positions():
    cfg = manifest.config("falcon-h1-34b-d5")
    check_config_file(cfg)
    assert sorted(cfg["reduced"]) == ["max_position_embeddings",
                                      "num_hidden_layers"]
    assert (cfg["num_hidden_layers"], cfg["max_position_embeddings"]) \
        == (5, 4096)
    assert (cfg["published"]["num_hidden_layers"],
            cfg["published"]["max_position_embeddings"]) == (72, 262144)
    differs = {k for k, v in cfg["published"].items() if cfg[k] != v}
    assert differs == set(cfg["reduced"])
    entry = next(c for c in manifest.benchmark()["configs"]
                 if c["name"] == "falcon-h1-34b-d5")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    for width in ("mamba_d_state", "head_dim", "mamba_d_ssm",
                  "intermediate_size"):
        with pytest.raises(AssertionError):
            check_config_file(dict(cfg, reduced=cfg["reduced"] + [width]))
    pc = architectures.of(cfg).program_config(cfg)
    assert (pc.d_model, pc.n_heads, pc.kv_heads, pc.head_dim, pc.d_ff) \
        == (5120, 20, 4, 128, 21504)
    assert (pc.ssm_inner, pc.ssm_heads, pc.ssm_head_dim, pc.ssm_state,
            pc.ssm_groups, pc.ssm_conv) == (4096, 32, 128, 256, 2, 4)
    assert (pc.conv_width, pc.ssm_proj_width) == (5120, 9248)
    assert (pc.vocab_size, pc.rope_theta, pc.rms_norm_eps, pc.max_seq) \
        == (261120, 1e11, 1e-5, 4096)
    assert pc.ssm_multipliers == tuple(cfg["published"]["ssm_multipliers"])
    assert pc.mlp_multipliers == tuple(cfg["published"]["mlp_multipliers"])
    for name in ("embedding_multiplier", "lm_head_multiplier",
                 "attention_in_multiplier", "attention_out_multiplier",
                 "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"):
        assert getattr(pc, name) == cfg["published"][name], name
    # a published switch the program does not have is refused, not ignored
    for key, value in (("mamba_norm_before_gate", True),
                       ("tie_word_embeddings", True),
                       ("attention_bias", True)):
        with pytest.raises(ValueError):
            architectures.of(cfg).program_config(dict(cfg, **{key: value}))
    cell = manifest.cell("conversation-batch")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("falcon-h1-34b-d5", "conversation-batch", 1)


def test_counts_against_hand_worked_ones():
    cfg = manifest.config("falcon-h1-34b-d5")
    arch = architectures.of(cfg)
    attn = 2 * 5120 * 20 * 128 + 2 * 5120 * 4 * 128
    ssm_in, ssm_out = 5120 * 9248, 4096 * 5120
    small = 5120 * 4 + 5120 + 3 * 32 + 4096   # taps, bias, A_log/D/dt, norm
    mlp = 3 * 5120 * 21504
    assert (attn, ssm_in, ssm_out, mlp) == (31_457_280, 47_349_760,
                                            20_971_520, 330_301_440)
    assert ssm_in + ssm_out + small == 68_351_072
    layer = attn + ssm_in + ssm_out + small + mlp + 2 * 5120
    assert layer == 430_120_032
    head = 5120 * 261120
    assert arch.matmul_params(cfg) == (attn + ssm_in + ssm_out + mlp, head)
    assert arch.n_params(cfg) == 5 * layer + 2 * head + 5120 \
        == 4_824_474_080
    # the cache: K and V of 4 heads x 128 in bf16, 5 layers
    assert arch.cache_token_bytes(cfg) == 5 * 2 * 4 * 128 * 2 == 10_240
    state = 32 * 128 * 256
    assert arch.state_row_bytes(cfg) == 5 * (state * 4 + 3 * 5120 * 2) \
        == 21_125_120
    mix = manifest.traffic("conversation-batch")
    e = serve_driver.engine_kwargs(cfg, mix)
    assert e["kv_pool_bytes"] == 64 * 2048 * 10_240 == 1_342_177_280
    assert arch.attention_shape(cfg) == (20, 128)
    # FLOPs: two a matmul parameter and token, the recurrence (6 an element
    # of the state and D x) and the convolution (two a tap and channel) in
    # their sequential form, and attention's pairs
    token = 2 * (5 * (attn + ssm_in + ssm_out + mlp) + head) \
        + 5 * (6 * state + 2 * 4096 + 2 * 4 * 5120)
    pair = 4 * 20 * 128
    assert arch.forward_flops(cfg, 1, 1000) == token + 1000 * 5 * pair
    assert arch.forward_flops(cfg, 3, 0) == 3 * token
    # the roofline's counts: a token-step of 58 rows over 66,000 positions
    f, b = arch.decode_step_work(cfg, 58, 66_000)
    weights = 2 * (5 * (layer - 2 * 5120) + head)
    assert b == weights + 2 * 58 * 21_125_120 + 66_000 * 10_240
    assert f == arch.forward_flops(cfg, 58, 66_000)
    assert 6.9e9 < weights < 7.0e9          # the issue's 6.97 GB of weights
    assert 9.9e9 < b < 10.3e9 and 0.22 < 2 * 58 * 21_125_120 / b < 0.27
    f, b = arch.ssm_update_work(cfg, 58)
    assert (f, b) == (6 * 58 * state, 2 * 58 * state * 4)


def test_warm_up_reaches_every_program_of_conversation_batch():
    mix = manifest.traffic("conversation-batch")
    e = mix["engine"]
    waves = serve_driver.warm_up_waves(mix)
    up = lambda x, m: -(-x // m) * m  # noqa: E731
    buckets = {up(w["prompt"], e["pad_multiple"]) for wave in waves
               for w in wave}
    assert buckets == set(range(512, 3073, 512))     # 6 prefill programs
    assert all(len(wave) <= e["max_batch_size"] for wave in waves)
    assert all(w["prompt"] + w["budget"] <= 4096 for wave in waves
               for w in wave)
    # the longest prompt with the longest answer fits the positions kept
    assert mix["prompt_tokens"]["max"] + e["max_new_tokens"] \
        <= manifest.config("falcon-h1-34b-d5")["max_position_embeddings"]


# ------------------------------------------------------------- the readers
def _ctx(before, after, trace):
    return {"cfg": manifest.config("falcon-h1-34b-d5"),
            "mix": manifest.traffic("conversation-batch"),
            "before": {"engine": before}, "after": {"engine": after},
            "trace": trace, "device": {"kind": "TPU v5 lite",
                                       "platform": "tpu", "count": 1}}


def test_the_roofline_readers_on_hand_made_numbers():
    before = {"state_rows_stepped": 500, "state_rows_fetched": 500,
              "ssm_layer_steps": 50, "iterations": 5,
              "live_positions": 900, "state_row_bytes": 21_125_120}
    # 100 iterations of 8 token-steps; 58 rows live over 66,000 positions
    steps = 100 * 8
    after = {"state_rows_stepped": 500 + 58 * 5 * steps,
             "state_rows_fetched": 500 + 58 * 5 * steps,
             "ssm_layer_steps": 50 + 5 * steps, "iterations": 105,
             "live_positions": 900 + 100 * 66_000,
             "state_row_bytes": 21_125_120}
    # the traced seconds hold 50 token-steps: 250 kernel calls in 5 ops
    trace = {"programs": {"jit_paged_step_fn": 50 * 0.016,
                          "jit_prefill": 0.4},
             "ops": {f"%ssm_decode_update.{i}": 50 * 0.0007
                     for i in range(5)},
             "op_calls": {f"%ssm_decode_update.{i}": 50 for i in range(5)},
             "op_text": {}}
    # an operation that reads the kernel's result names it in its text: it
    # is not the kernel (PR 28's lesson)
    trace["ops"]["%fusion.9"] = 0.3
    trace["op_calls"]["%fusion.9"] = 250
    trace["op_text"]["%fusion.9"] = ("%fusion.9 = f32[64,32,128] fusion("
                                     "%ssm_decode_update.3), kind=kLoop")
    ctx = _ctx(before, after, trace)
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    f, b = arch.decode_step_work(cfg, 58.0, 66_000.0)
    least = max(f / 197e12, b / 819e9)
    assert hybrid_step_roofline.read(ctx) == pytest.approx(
        100 * least / 0.016, rel=1e-9)
    assert 70 < hybrid_step_roofline.read(ctx) < 85   # 12.4 of 16 ms
    f, b = arch.ssm_update_work(cfg, 58.0)
    least = max(f / 197e12, b / 819e9)
    assert b / 819e9 > f / 197e12                     # bound by the bytes
    assert ssm_update_roofline.read(ctx) == pytest.approx(
        100 * least / 0.0007, rel=1e-9)
    assert 75 < ssm_update_roofline.read(ctx) < 90    # 0.59 of 0.7 ms
    # nothing to read is None and never 0: no trace (an untraced or CPU
    # run), no kernel in it, or a program without the counts (the parent,
    # and every other model's cell)
    for reader in (hybrid_step_roofline, ssm_update_roofline):
        assert reader.read(_ctx(before, after, None)) is None
        assert reader.read(_ctx(before, after, dict(
            trace, ops={}, op_calls={}))) is None
        assert reader.read(_ctx({"iterations": 5}, {"iterations": 105},
                                trace)) is None
        assert reader.read(_ctx(after, after, trace)) is None
    old = {"cfg": manifest.config("mistral-7b-d16"), "before": {},
           "after": {}, "trace": trace, "device": ctx["device"]}
    assert hybrid_step_roofline.read(old) is None
    assert ssm_update_roofline.read(old) is None


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
    """The engine's one decode step and its prefill of 3,072 positions at
    Falcon-H1-34B's widths, 5 layers, 64 slots, the pool of 64 x 2,048
    positions and the 64 slots' state; and the output check's comparison of
    a 4,096-token sample with its control. In the decode step: the paged
    attention kernel and the state-update kernel once a layer each, and K,
    V and the recurrence's state where they came in, never copied."""
    import jax
    import jax.numpy as jnp

    from ray_memory_management_tpu.ops import paged_attention as pa
    from ray_memory_management_tpu.serve.llm import ContinuousBatcher

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cfg = manifest.config("falcon-h1-34b-d5")
    arch = architectures.of(cfg)
    hbm = flops.peak("TPU v5 lite")["hbm_bytes"]
    weights = 2 * arch.n_params(cfg)
    if program == "check":
        model = arch.reference()
        params = shaped(jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0), cfg)))

        def gaps(p, tokens, served_at, start):
            ref = model.logits(p, tokens, cfg)
            pos = start + jnp.arange(1024)
            low = jnp.argmax(model.logits(p, tokens, cfg, "fp8"), -1)
            best = jnp.max(ref, axis=-1)[pos]
            return best - ref[pos, served_at], best - ref[pos, low[pos]]

        compiled = jax.jit(gaps).lower(
            params, arr((4096,)), arr((1024,)), arr(())).compile()
        # the reference's weights and a sample's logits over the whole
        # vocabulary (4.3 GB); the engine's pool has gone with its weights
        assert weights + 4.2e9 < _total_bytes(compiled) < hbm - 1.5e9
        return
    # the kernels' dispatch asks where computation lands: steer it here
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    for name in ("flash_attention", "ssm"):
        monkeypatch.setattr(
            sys.modules["ray_memory_management_tpu.ops." + name],
            "_on_tpu", lambda: True)
    monkeypatch.setattr(
        sys.modules["ray_memory_management_tpu.models.hybrid_ssm"],
        "_on_tpu", lambda: True)
    mix = manifest.traffic("conversation-batch")
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
            "k": (5, 4, 257, 512, 128), "v": (5, 4, 257, 512, 128),
            "ssm": (5, 64, 32, 256, 128), "conv": (5, 3, 64, 5120)}
        width = eng.kv_pool.table_width
        assert width == 8
        if program == "decode":
            compiled = eng._paged_step.lower(
                params, pool, arr((slots,)), arr((slots,)),
                arr((slots, width)), arr((2,), jnp.uint32)).compile()
        else:
            compiled = eng._paged_prefill_fn(3072).lower(
                params, pool, arr((1, 3072)), arr((width,)), arr(()),
                arr((2,), jnp.uint32), arr(())).compile()
        stats = eng.kv_pool.stats()
    finally:
        eng.close()
    text = compiled.as_text()
    held = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in pool.values())
    assert held == stats["store_bytes"] == 2 * 257 * 512 * 10_240 // 2 \
        + 64 * 21_125_120
    assert 12.3e9 < weights + held < 12.4e9   # 9.65 GB, 1.35 + 1.35 GB
    total = _total_bytes(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes >= held  # donated
    if program == "decode":
        # paged attention and the state update, once a layer each
        assert text.count("tpu_custom_call") == 10
        assert len(set(re.findall(r"%(ssm_decode_update[.\d]*) = ", text))) \
            == 5
        for shape, layout in (("f32[5,64,32,256,128]", "4,3,2,1,0"),
                              ("bf16[5,4,257,512,128]", "4,3,2,1,0")):
            made = re.findall(
                "= " + re.escape(shape) + r"\{([\d,]+)[^ ]* (\S+?)\(", text)
            assert made and {lay for lay, _ in made} == {layout}, shape
            assert not {op for _, op in made} & {"copy", "copy-start"}, shape
        assert weights + held < total < weights + held + 0.3e9
    else:
        # the flash forward kernel and the chunked scan, once a layer each
        assert text.count("tpu_custom_call") == 10
        assert len(set(re.findall(r"%(ssd_chunk_scan[.\d]*) = ", text))) == 5
        assert weights + held < total < weights + held + 1.0e9
    assert total < hbm - 2.0e9
