"""The hybrid model's mixed step (a chunk of a prompt and one decode token a
live row in one program, ``models/hybrid_ssm.py::mixed_step``) in the
benchmark's sight, rehearsed off the chip: the two readers of what it runs
(``serve.closed.ssm_mixed_update_roofline``, the state-update kernel under the
name it has there; ``serve.closed.hybrid_mixed_step_ms``, the program's device
time a call) on hand-made numbers and on a program that lacks the counts, the
manifest's entries, and ``conversation-batch``'s control of a state left
unchanged, planted where the chunk path reads the slot's entry and read through
the check's own comparison. No time read here is a device number.
"""

import threading

import numpy as np
import pytest

from chipbench import architectures, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import (hybrid_mixed_step_ms, mixed_ssm_steps,
                               ssm_mixed_update_roofline, ssm_steps)

ROOFLINE = "serve.closed.ssm_mixed_update_roofline"
STEP_MS = "serve.closed.hybrid_mixed_step_ms"
CELL = "conversation-batch"

SEED = 2 ** 31 + 36  # the driver's seeds pass 32 signed bits
# the toy deployment of test_hybrid_ssm_cell.py (a test module is not
# imported from another: pytest would then collect it without its assert
# rewriting), float32 on both sides so that a reading is the fault's alone
TOY_F32 = dict(
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
    max_position_embeddings=128, param_dtype="float32",
    activation_dtype="float32")
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


# ------------------------------------------------------------ the manifest
@pytest.mark.parametrize("name,reader,unit,better,layer", [
    (ROOFLINE, "ssm_mixed_update_roofline", "%", "higher", "kernels"),
    (STEP_MS, "hybrid_mixed_step_ms", "ms", "lower", "model step, serve")])
def test_the_manifest_finds_the_metric_with_its_cell(name, reader, unit,
                                                     better, layer):
    entry = manifest.metric_files()[name]
    assert entry["reader"] == reader
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve.capacity_tokens_per_s"
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == (unit, better, "device_trace", layer)
    bench = manifest.benchmark()
    listed = [m for m in bench["per_layer"] if m["name"] == name]
    assert listed == [{k: v for k, v in entry.items() if k != "reader"}]
    # a layer the manifest already names, letter for letter
    assert sum(m["layer"] == layer for m in bench["per_layer"]) > 1
    for cell in (w["name"] for w in bench["workloads"]):
        names = [m["name"] for m in manifest.metrics_for(cell, "per_layer")]
        assert (name in names) == (cell == CELL)
    # a kernel's share is named for the kernel as it is named in this program
    assert ROOFLINE.endswith(mixed_ssm_steps.KERNEL + "_roofline")


def test_the_two_programs_kernels_are_told_apart_by_name():
    """The decode program's readers must not see the mixed step's kernel,
    nor these the decode program's: each counts its own calls."""
    assert ssm_steps.KERNEL not in mixed_ssm_steps.KERNEL
    assert mixed_ssm_steps.KERNEL not in ssm_steps.KERNEL
    assert mixed_ssm_steps.PROGRAM != ssm_steps.PROGRAM


# ------------------------------------------------------------- the readers
def _ctx(before, after, trace, cfg="falcon-h1-34b-d5"):
    return {"cfg": manifest.config(cfg),
            "before": {"engine": before}, "after": {"engine": after},
            "trace": trace, "device": {"kind": "TPU v5 lite",
                                       "platform": "tpu", "count": 1}}


def _window(rows, steps, layers=5):
    before = {"iterations": 5, "mixed_steps": 40,
              "mixed_state_rows_stepped": 9_000, "state_rows_stepped": 500,
              "ssm_layer_steps": 50, "live_positions": 900}
    after = dict(before, iterations=305, mixed_steps=40 + steps,
                 mixed_state_rows_stepped=9_000 + rows * layers * steps)
    return before, after


def _trace(steps=140, kernel_s=0.00079, step_s=0.0256):
    t = {"programs": {"jit_mixed_step": steps * step_s,
                      "jit_paged_step_fn": 8 * 0.133},
         "ops": {f"%ssm_mixed_update.{i}": steps * kernel_s
                 for i in range(5, 10)},
         "op_calls": {f"%ssm_mixed_update.{i}": steps for i in range(5, 10)},
         "op_text": {}}
    # the decode program's kernel is not the mixed step's
    for i in range(35, 40):
        t["ops"][f"%ssm_decode_update.{i}"] = 64 * 0.00082
        t["op_calls"][f"%ssm_decode_update.{i}"] = 64
    return t


def test_the_readers_on_hand_made_numbers():
    # 1,700 mixed steps in the window, 57 rows live in the mean; the traced
    # seconds hold 140 of them: 700 kernel calls in 5 operations
    before, after = _window(57, 1_700)
    ctx = _ctx(before, after, _trace())
    assert mixed_ssm_steps.rows(ctx) == pytest.approx(57.0, rel=1e-12)
    assert mixed_ssm_steps.kernel(ctx) == (700, pytest.approx(700 * 0.00079))
    cfg = ctx["cfg"]
    f, b = architectures.of(cfg).ssm_update_work(cfg, 57.0)
    least = max(f / 197e12, b / 819e9)
    assert b / 819e9 > f / 197e12                     # bound by the bytes
    assert ssm_mixed_update_roofline.read(ctx) == pytest.approx(
        100 * least / 0.00079, rel=1e-9)
    assert 65 < ssm_mixed_update_roofline.read(ctx) < 85  # 0.58 of 0.79 ms
    assert hybrid_mixed_step_ms.read(ctx) == pytest.approx(25.6, rel=1e-9)
    # every slot live at every call is the most the count can say: a
    # reading is under 100% while a call takes longer than 64 rows' bytes
    full = _ctx(*_window(64, 1_700), _trace(kernel_s=0.00066))
    assert 99 < ssm_mixed_update_roofline.read(full) < 100


@pytest.mark.parametrize("reader", [ssm_mixed_update_roofline,
                                    hybrid_mixed_step_ms])
def test_nothing_to_read_is_none_and_never_zero(reader):
    """No trace (an untraced or CPU run), no such kernel in it (the parent:
    its prompts are prefilled whole; or a window whose traced seconds hold
    no mixed step), a program without the counts, or a window without a
    mixed step: the line leaves the metric out, and nothing raises."""
    before, after = _window(57, 1_700)
    trace = _trace()
    assert reader.read(_ctx(before, after, None)) is None
    assert reader.read(_ctx(before, after, dict(
        trace, ops={}, op_calls={}))) is None
    parent_trace = {
        "programs": {"jit_paged_step_fn": 2.5, "jit_prefill": 1.7},
        "ops": {k: v for k, v in trace["ops"].items() if "decode" in k},
        "op_calls": {k: v for k, v in trace["op_calls"].items()
                     if "decode" in k}, "op_text": {}}
    parent = {k: v for k, v in before.items() if "mixed_state" not in k}
    assert reader.read(_ctx(parent, dict(parent, iterations=305),
                            parent_trace)) is None
    assert reader.read(_ctx({}, {}, parent_trace)) is None
    old = {"cfg": manifest.config("mistral-7b-d16"), "before": {},
           "after": {}, "trace": parent_trace,
           "device": {"kind": "TPU v5 lite"}}
    assert reader.read(old) is None
    if reader is ssm_mixed_update_roofline:
        # the counts are a program's that the trace is not: no share
        assert reader.read(_ctx(parent, dict(parent, iterations=305),
                                trace)) is None
        assert reader.read(_ctx(after, after, trace)) is None


# ------- the cell's control of a state left unchanged, on the path that runs
def _served_by(params, pc, prompts, budget, slots):
    """Greedy answers of the program's engine, in this process, and what
    the engine counted of itself meanwhile."""
    from ray_memory_management_tpu.serve.llm import LLMServer

    engine = dict(serve_driver.engine_kwargs(TOY_F32, TOY_BATCH),
                  max_batch_size=slots)
    srv = LLMServer(config=pc, init=lambda key, cfg: params, **engine)
    before = srv.stats()
    outs = [None] * len(prompts)

    def one(i):
        outs[i] = srv.generate(prompts[i], max_new_tokens=budget)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    after = srv.stats()
    srv._engine.close()
    return ([{"prompt": p, "served": list(o)} for p, o in zip(prompts, outs)],
            before, after)


@pytest.mark.parametrize("fault", ["none", "slot_keeps_its_old_state"])
def test_a_state_left_in_the_slot_reads_over_the_limit(fault, monkeypatch):
    """``check_samples``, the comparison that decides the cell's ``correct``,
    on what the program's engine serves when a prompt's first chunk starts
    from what an earlier request left in its slot (the fault
    ``test_hybrid_ssm_cell.py`` plants in the whole-prompt prefill, which
    this model's engine no longer calls; the chunk path's seam is
    ``hybrid_ssm._carried``). Twelve requests on two slots, so that a slot is
    admitted again; float32 on both sides."""
    import jax

    from ray_memory_management_tpu.models import hybrid_ssm

    arch = architectures.of(TOY_F32)
    pc = arch.program_config(TOY_F32)
    params = arch.init_program_params(jax.random.PRNGKey(SEED), pc)
    if fault == "slot_keeps_its_old_state":
        monkeypatch.setattr(hybrid_ssm, "_carried",
                            lambda entry, first: entry)
    jax.clear_caches()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 512, int(n)).tolist()
               for n in (3, 5, 17, 18, 19, 33, 34, 35, 49, 50, 2, 1)]
    try:
        samples, before, after = _served_by(params, pc, prompts, 16, slots=2)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # the path under test ran: every prompt rode the decode step in chunks
    e = after["engine"]
    assert e["mixed_steps"] >= sum(-(-len(p) // 16) for p in prompts)
    got = serve_driver.check_samples(TOY_F32, SEED, samples)
    assert got["tokens"] == 12 * 16
    if fault != "none":
        assert got["gap_max"] > 2 * TOY_BATCH["check"]["gap_limit"], got
        return
    assert got["gap_max"] < 1e-3
    # and the counts the roofline's reader divides: a mixed step's kernel
    # moves the rows live in its decode half, at most the other slot's
    ctx = {"cfg": TOY_F32, "before": before, "after": after}
    rows = mixed_ssm_steps.rows(ctx)
    assert 0 < rows <= 1
    assert e["mixed_state_rows_stepped"] == round(
        rows * e["mixed_steps"] * TOY_F32["num_hidden_layers"])
