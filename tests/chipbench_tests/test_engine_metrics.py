"""The seven per-layer metrics that read the serve engine's own counts
(``stats()["engine"]``), rehearsed off the chip: each reader on hand-made
``before`` / ``after`` snapshots, and one toy serve run whose ``--trace 1``
line holds all seven. No time read here is a device number.
"""

import json
import time

import pytest

from chipbench import harness, manifest
from chipbench.drivers import serve as serve_driver
from chipbench.readers import engine_window

# the toy deployment of test_chipbench.py (a test module is not imported
# from another: pytest would then collect it without its assert rewriting)
TOY = dict(name="toy", num_hidden_layers=2, hidden_size=64,
           intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, vocab_size=512,
           rope_theta=1e4, rms_norm_eps=1e-6, max_position_embeddings=128,
           param_dtype="bfloat16", activation_dtype="bfloat16")
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
SEED = 2 ** 31 + 6  # the driver's seeds pass 32 signed bits

PHASES = ("idle_wait", "gate", "prefill", "assemble", "step_dispatch",
          "step_wait", "emit", "disassemble")
METRICS = {
    "serve.engine_headroom_share": "engine_headroom_share",
    "serve.engine_host_share": "engine_host_share",
    "serve.engine_host_cpu_share": "engine_host_cpu_share",
    "serve.engine_prefill_share": "engine_prefill_share",
    "serve.slab_live_share": "slab_live_share",
    "serve.queue_wait_p90_ms": "queue_wait_p90_ms",
    "serve.engine_ttft_p90_ms": "engine_ttft_p90_ms",
}


def snapshot(wall, cpu, iterations, slab, live, admitted, recent):
    return {"engine": {
        "phase_s": dict(zip(PHASES, wall)),
        "phase_cpu_s": dict(zip(PHASES, cpu)), "iterations": iterations,
        "slab_positions": slab, "live_positions": live,
        "admitted": admitted, "recent": recent}}


# cumulative counts: the window is the difference. In it: idle 2, gate 1,
# prefill 4, assemble 3, dispatch 1, wait 24, emit 2, disassemble 3 = 40 s
BEFORE = snapshot([10, 1, 2, 3, 1, 20, 1, 2], [0, 1, 1, 2, 1, 0, 1, 1],
                  100, 40_000, 10_000, 3, [[9.0, 9.0]] * 3)
RECENT = [[9.0, 9.0]] * 3 + [[0.010 * i, 0.100] for i in range(1, 11)]
AFTER = snapshot([12, 2, 6, 6, 2, 44, 3, 5], [0, 1.5, 2, 3.5, 1.5, 0, 2, 2.5],
                 200, 140_000, 35_000, 13, RECENT)
CTX = {"before": BEFORE, "after": AFTER}
EXPECT = {
    "serve.engine_headroom_share": 100 * 2 / 40,
    "serve.engine_host_share": 100 * (1 + 3 + 1 + 2 + 3) / 38,
    "serve.engine_host_cpu_share": 100 * (.5 + 1.5 + .5 + 1 + 1.5) / 10,
    "serve.engine_prefill_share": 100 * 4 / 38,
    "serve.slab_live_share": 100 * 25_000 / 100_000,
    # ten admissions in the window; nearest rank: the 9th of 10
    "serve.queue_wait_p90_ms": 90.0,
    "serve.engine_ttft_p90_ms": 190.0,
}


def read(name, ctx):
    return manifest.reader(METRICS[name])(ctx)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_reader_on_hand_made_snapshots(name):
    entry = manifest.metric_files()[name]
    assert entry["reader"] == METRICS[name]
    assert "chat-online" in entry["workloads"]
    assert entry["layer"] == "serve engine"
    assert read(name, CTX) == pytest.approx(EXPECT[name], rel=1e-12)
    # a program without the engine's counts (the parent, barrier mode):
    # nothing to read, so the line leaves the metric out
    assert read(name, {"before": {}, "after": {}}) is None
    assert read(name, {"before": {}, "after": AFTER}) is None
    # a window in which the engine did nothing at all
    assert read(name, {"before": AFTER, "after": AFTER}) is None


@pytest.mark.parametrize("name", ["serve.queue_wait_p90_ms",
                                  "serve.engine_ttft_p90_ms"])
def test_percentiles_take_the_windows_rows_capped_at_the_ring(name):
    own = 0 if name == "serve.queue_wait_p90_ms" else 1000.0
    # more admissions than the ring holds: every row it has is read
    ring = [[float(i), 1.0] for i in range(1, 513)]
    after = snapshot([0] * 8, [0] * 8, 0, 0, 0, 5000, ring)
    before = snapshot([0] * 8, [0] * 8, 0, 0, 0, 100, [])
    assert read(name, {"before": before, "after": after}) \
        == pytest.approx(461e3 + own)   # ceil(0.9 * 512) = 461
    # two admissions in the window: the older rows of the ring stay out
    before = snapshot([0] * 8, [0] * 8, 0, 0, 0, 4998, [])
    assert read(name, {"before": before, "after": after}) \
        == pytest.approx(512e3 + own)
    # no admission in the window
    before = snapshot([0] * 8, [0] * 8, 0, 0, 0, 5000, ring)
    assert read(name, {"before": before, "after": after}) is None


def test_helpers_agree_with_the_engine_on_the_phases():
    from ray_memory_management_tpu.serve.llm import ENGINE_PHASES

    assert tuple(ENGINE_PHASES) == PHASES
    assert set(engine_window.HOST) < set(ENGINE_PHASES)
    assert not {"idle_wait", "prefill", "step_wait"} & set(engine_window.HOST)


@pytest.fixture(scope="module")
def toy_serve():
    return serve_driver.run(CELL, TOY, TOY_CHAT, seed=SEED, seconds=4.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_toy_serve_run_reports_the_metric(toy_serve, name):
    r = toy_serve
    assert r["correct"], r["comparisons"]
    line = json.loads(json.dumps(
        harness.result_line("chat-online", True, r)))
    m = line["metrics"][name]
    assert m["unit"] == manifest.metric_files()[name]["unit"]
    if m["unit"] == "%":
        assert 0.0 <= m["value"] <= 100.0
    else:
        assert 0.0 < m["value"] < 4000.0
    assert name not in json.loads(json.dumps(
        harness.result_line("chat-online", False, r)))["metrics"]


def test_toy_serve_window_adds_up(toy_serve):
    ctx = toy_serve["context"]
    d = engine_window.phases(ctx)
    # every instant of the engine thread is in one phase, so the window's
    # phases add up to the window, to within what the newest published
    # copy lags at either edge: an iteration, or one idle wait of 1 s
    assert sum(d.values()) == pytest.approx(
        ctx["after"]["time"] - ctx["before"]["time"], abs=1.1)
    b, a = engine_window.engines(ctx)
    sent = sum(1 for r in toy_serve["context"]["clocks"]["late_ms"])
    assert 0 < a["admitted"] - b["admitted"] <= sent
    assert a["iterations"] > b["iterations"]
    assert 0 < a["live_positions"] - b["live_positions"] \
        < a["slab_positions"] - b["slab_positions"]
