"""The five per-layer metrics that read what the serve engine says of the
chip's work (``stats()["engine"]``: ``starved_s``, seconds of each phase
without a program in flight, and ``stalls``, the iterations that took over
four times the median), rehearsed off the chip: each reader on hand-made
snapshots, each metric's file against the manifest's entry and its cells,
and toy runs (a closed loop on each of the engine's two paths, an open
loop) whose ``--trace 1`` lines hold them. No time read here is a device
number.
"""

import json
import time

import pytest

from chipbench import harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import engine_window

CLOSED = ["longprompt-batch", "reasoning-batch", "conversation-batch",
          "longanswer-batch"]
# name -> (reader, unit, cells, the end-to-end metric it moves)
METRICS = {
    "serve.closed.device_starved_share": (
        "device_starved_share", "%", CLOSED, "serve.capacity_tokens_per_s"),
    "serve.device_starved_share": (
        "device_starved_share", "%", ["chat-online"], "serve.tokens_per_s"),
    "serve.closed.admission_starved_ms": (
        "admission_starved_ms", "ms", CLOSED, "serve.capacity_tokens_per_s"),
    "serve.closed.stall_share": (
        "stall_share", "%", CLOSED, "serve.capacity_tokens_per_s"),
    "serve.stall_share": (
        "stall_share", "%", ["chat-online"], "serve.norm_latency_p90_ms"),
}
CLOSED_NAMES = sorted(n for n in METRICS if ".closed." in n)
OPEN_NAMES = sorted(n for n in METRICS if ".closed." not in n)

PHASES = ("idle_wait", "gate", "prefill", "assemble", "step_dispatch",
          "step_wait", "emit", "disassemble")


def snapshot(at, wall, starved=None, admitted=0, stalls=None):
    """``ChipServer.snapshot()`` as far as the readers look; ``starved``
    None: an engine that keeps neither new key (the parent's)."""
    eng = {"phase_s": dict(zip(PHASES, wall)),
           "phase_cpu_s": dict(zip(PHASES, [0.0] * 8)),
           "admitted": admitted, "recent": []}
    if starved is not None:
        eng["starved_s"] = dict(zip(PHASES, starved))
        eng["stalls"] = stalls or []
    return {"time": at, "engine": eng}


def stall(t_end, wall_s, median_s):
    return {"t_end": t_end, "wall_s": wall_s, "median_s": median_s,
            "phase_s": {}, "phase_cpu_s": {}, "starved_s": {}, "rows": 4,
            "token_steps": 8}


# cumulative since the engine started: the window is the difference. In it:
# idle 2, gate 1, prefill 4, assemble 3, dispatch 1, wait 24, emit 2,
# disassemble 3 = 40 s, 38 of them with work; without a program in flight:
# gate 1, prefill 1.5, assemble 3, dispatch 1, wait 0.1, emit 2 = 8.6 s
WALL_0, WALL_1 = [10, 1, 2, 3, 1, 20, 1, 2], [12, 2, 6, 6, 2, 44, 3, 5]
STARVED_0 = [0, 1, 1.5, 3, 1, 0.2, 1, 0]
STARVED_1 = [0, 2, 3.0, 6, 2, 0.3, 3, 0]
OLD = stall(90.0, 9.0, 0.25)          # warm-up's compile, before the window
INSIDE = [stall(120.0, 3.0, 0.25), stall(150.0, 1.5, 0.25)]  # one at its end
LATER = stall(150.5, 7.0, 0.25)       # published before the snapshot's call
BEFORE = snapshot(100.0, WALL_0, STARVED_0, 3, [OLD])
AFTER = snapshot(150.0, WALL_1, STARVED_1, 13, [OLD] + INSIDE + [LATER])
PARENT = {"before": snapshot(100.0, WALL_0, admitted=3),
          "after": snapshot(150.0, WALL_1, admitted=13)}
EXPECT = {"device_starved_share": 100 * 8.6 / 38,
          "admission_starved_ms": 1e3 * 1.5 / 10,
          "stall_share": 100 * (2.75 + 1.25) / 38}


def read(reader, before, after):
    return manifest.reader(reader)({"before": before, "after": after})


@pytest.mark.parametrize("reader", sorted(EXPECT))
def test_reader_on_hand_made_snapshots(reader):
    assert read(reader, BEFORE, AFTER) == pytest.approx(
        EXPECT[reader], rel=1e-12)
    # an engine without the keys (the parent under this PR's benchmark
    # files), no engine, no ``stats()`` to speak of: nothing to read, so the
    # line leaves the metric out
    assert manifest.reader(reader)(PARENT) is None
    half = read(reader, PARENT["before"], AFTER)
    assert half is None or reader == "stall_share"  # ``after``'s ring alone
    assert read(reader, {"time": 1.0}, {"time": 2.0}) is None
    assert read(reader, {}, {}) is None
    # a window in which the engine did nothing at all
    assert read(reader, AFTER, AFTER) is None


def test_a_window_without_an_admission_or_a_stall():
    quiet = snapshot(150.0, WALL_1, STARVED_1, 3, [OLD, LATER])
    assert read("admission_starved_ms", BEFORE, quiet) is None
    assert read("device_starved_share", BEFORE, quiet) == pytest.approx(
        EXPECT["device_starved_share"])
    # no iteration over four times the median: 0 is a reading
    assert read("stall_share", BEFORE, quiet) == 0.0
    # everything starved: the share's ceiling, by construction of the count
    every = snapshot(150.0, WALL_1, [0] + [
        s + w1 - w0 for s, w0, w1 in zip(STARVED_0, WALL_0, WALL_1)][1:], 13)
    assert read("device_starved_share", BEFORE, every) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_finds_the_metric_with_its_cells(name):
    reader, unit, cells, moves = METRICS[name]
    entry = manifest.metric_files()[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": "serve engine", "moves": moves,
        "workloads": cells, "reader": reader}
    bench = manifest.benchmark()
    listed = [m for m in bench["per_layer"] if m["name"] == name]
    assert listed == [{k: v for k, v in entry.items() if k != "reader"}]
    # a layer the manifest already names, letter for letter
    assert sum(m["layer"] == entry["layer"] for m in bench["per_layer"]) > 5
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for cell in (w["name"] for w in bench["workloads"]):
        names = [m["name"] for m in manifest.metrics_for(cell, "per_layer")]
        assert (name in names) == (cell in cells), cell
        if cell in cells:  # a cell that reports what the metric moves
            assert cell in e2e[moves]["workloads"]
    assert "roofline" not in name and "mfu" not in name


# ----------------------------------------------------------- the toy runs
DENSE = dict(name="toy", num_hidden_layers=2, hidden_size=64,
             intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=512,
             rope_theta=1e4, rms_norm_eps=1e-6, max_position_embeddings=128,
             param_dtype="bfloat16", activation_dtype="bfloat16")
# a model that offers no ``mixed_step``: its prompts are prefilled whole
LATENT = dict(
    name="toy-latent", architecture="latent_moe", hidden_size=64,
    intermediate_size=128, moe_intermediate_size=32, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    first_k_dense_replace=1, routed_scaling_factor=1.8, norm_topk_prob=True,
    n_group=1, topk_group=1, topk_method="noaux_tc", num_hidden_layers=3,
    vocab_size=512, rope_theta=1e4, rms_norm_eps=1e-5,
    max_position_embeddings=128, param_dtype="bfloat16",
    activation_dtype="bfloat16")
TOY_BATCH = {
    "name": "toy-batch", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.35,
                      "min": 24, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 16},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 16},
    "trace_seconds": 1.0, "check": {"requests": 6, "gap_limit": 0.4}}
TOY_CHAT = {
    "name": "toy-chat", "kind": "serve-open", "rate_per_s": 6.0,
    "arrivals": {"dist": "exponential"},
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.9,
                      "min": 4, "max": 48},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                      "min": 2, "max": 24},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 24,
               "max_concurrent_queries": 64},
    "trace_seconds": 1.0, "check": {"requests": 4, "gap_limit": 0.15}}
CELL = {"name": "toy", "chips": 1}
SEED = 2 ** 31 + 37  # the driver's seeds pass 32 signed bits


def lines(result, cell):
    """(traced, untraced) metrics of a run's result as ``cell``'s line."""
    return tuple(json.loads(json.dumps(
        harness.result_line(cell, traced, result)))["metrics"]
        for traced in (True, False))


def window(result):
    """(Δ phase_s, Δ starved_s, admissions) of a run's window."""
    ctx = result["context"]
    b, a = engine_window.engines(ctx)
    return (engine_window.phases(ctx), engine_window.phases(ctx, "starved_s"),
            a["admitted"] - b["admitted"])


@pytest.fixture(scope="module", params=["chunks", "whole"])
def toy_closed(request):
    cfg = DENSE if request.param == "chunks" else LATENT
    return request.param, serve_driver.run(
        CELL, cfg, TOY_BATCH, seed=SEED, seconds=4.0, trace=True,
        started=time.time(), expect_platform="cpu")


@pytest.fixture(scope="module")
def toy_chat():
    return serve_driver.run(CELL, DENSE, TOY_CHAT, seed=SEED, seconds=4.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu")


def test_toy_closed_loop_reports_its_three(toy_closed):
    path, r = toy_closed
    assert r["correct"], r["comparisons"]
    traced, untraced = lines(r, "longanswer-batch")
    for name in CLOSED_NAMES:
        assert traced[name]["unit"] == METRICS[name][1]
        assert name not in untraced
    assert not set(OPEN_NAMES) & set(traced)
    phase_s, starved_s, admitted = window(r)
    assert admitted > 0
    share = traced["serve.closed.device_starved_share"]["value"]
    assert 0.0 < share < 100.0
    assert share == pytest.approx(100.0 * sum(starved_s.values())
                                  / engine_window.work(phase_s))
    for name in PHASES:
        assert -1e-9 <= starved_s[name] <= phase_s[name] + 1e-9, name
    ms = traced["serve.closed.admission_starved_ms"]["value"]
    assert ms == pytest.approx(1e3 * starved_s["prefill"] / admitted)
    assert 0.0 < ms <= 1e3 * phase_s["prefill"] / admitted
    if path == "whole":
        # an admission waits for its first token with a program in flight
        assert starved_s["prefill"] < phase_s["prefill"]
    assert 0.0 <= traced["serve.closed.stall_share"]["value"] < 100.0


def test_toy_open_loop_reports_its_two(toy_chat):
    r = toy_chat
    assert r["correct"], r["comparisons"]
    traced, untraced = lines(r, "chat-online")
    for name in OPEN_NAMES:
        assert traced[name]["unit"] == "%"
        assert 0.0 <= traced[name]["value"] < 100.0
        assert name not in untraced
    assert not set(CLOSED_NAMES) & set(traced)
    assert traced["serve.device_starved_share"]["value"] > 0.0


def test_the_windows_stall_rows_are_the_readers(toy_chat):
    """The value is the window's own rows: those of ``after``'s ring whose
    ``t_end`` lies between the snapshots, each at most sixteen."""
    ctx = toy_chat["context"]
    b, a = ctx["before"], ctx["after"]
    assert len(a["engine"]["stalls"]) <= 16
    rows = [r for r in a["engine"]["stalls"]
            if b["time"] < r["t_end"] <= a["time"]]
    work = engine_window.work(engine_window.phases(ctx))
    assert read("stall_share", b, a) == pytest.approx(
        100.0 * sum(r["wall_s"] - r["median_s"] for r in rows) / work)
    for r in a["engine"]["stalls"]:
        assert r["wall_s"] > 4 * r["median_s"] > 0
        assert set(r["starved_s"]) == set(PHASES)
