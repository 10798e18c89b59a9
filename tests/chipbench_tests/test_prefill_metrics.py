"""The two per-layer metrics that read the engine's prefill positions
(``stats()["engine"]``: ``prefill_positions``, ``prefill_kernel_positions``),
rehearsed off the chip: each reader on hand-made snapshots, the manifest's
entries, and one toy closed-loop run whose ``--trace 1`` line holds both. No
time read here is a device number.
"""

import json
import time

import pytest

from chipbench import harness, manifest
from chipbench.drivers import serve as serve_driver

METRICS = {"serve.closed.prefill_ms_per_kpos": "prefill_ms_per_kpos",
           "serve.closed.prefill_kernel_share": "prefill_kernel_share"}
CELLS = ["longprompt-batch", "reasoning-batch"]
PHASES = ("idle_wait", "gate", "prefill", "assemble", "step_dispatch",
          "step_wait", "emit", "disassemble")

# the toy deployment of test_chipbench.py (a test module is not imported
# from another: pytest would then collect it without its assert rewriting)
TOY = dict(name="toy", num_hidden_layers=2, hidden_size=64,
           intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, vocab_size=512,
           rope_theta=1e4, rms_norm_eps=1e-6, max_position_embeddings=128,
           param_dtype="bfloat16", activation_dtype="bfloat16")
TOY_BATCH = {
    "name": "toy-batch", "kind": "serve-closed", "clients": 6,
    "requests_per_client": 256, "order_block": 4, "schedule_seed": 3,
    "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                      "min": 8, "max": 96},
    "output_tokens": {"dist": "lognormal", "median": 4, "sigma": 0.5,
                      "min": 1, "max": 8},
    "engine": {"max_batch_size": 4, "steps_per_iter": 4, "pad_multiple": 16,
               "kv_page_tokens": 16, "max_new_tokens": 8},
    "trace_seconds": 1.0, "check": {"requests": 6, "gap_limit": 0.15}}
CELL = {"name": "toy", "chips": 1}
SEED = 2 ** 31 + 30  # the driver's seeds pass 32 signed bits


def snapshot(prefill_s, **counts):
    wall = dict.fromkeys(PHASES, 1.0)
    wall["prefill"] = prefill_s
    return {"engine": {"phase_s": wall, "phase_cpu_s": dict(wall),
                       "iterations": 0, "slab_positions": 0,
                       "live_positions": 0, "admitted": 0, "recent": [],
                       **counts}}


def read(name, before, after):
    return manifest.reader(METRICS[name])({"before": before, "after": after})


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_finds_the_metric_with_its_cells(name):
    entry = manifest.metric_files()[name]
    assert entry["reader"] == METRICS[name]
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "model step, serve"
    assert entry["moves"] == "serve.capacity_tokens_per_s"
    listed = [m for m in manifest.benchmark()["per_layer"]
              if m["name"] == name]
    assert listed == [{k: v for k, v in entry.items() if k != "reader"}]
    for cell in CELLS:
        assert name in [m["name"]
                        for m in manifest.metrics_for(cell, "per_layer")]
    assert name not in [m["name"] for m in
                        manifest.metrics_for("chat-online", "per_layer")]
    # neither is a share of a roofline or of a peak
    assert "roofline" not in name and "mfu" not in name


@pytest.mark.parametrize("name,expect", [
    # 554,000 positions in 36.8 s of the prefill phase
    ("serve.closed.prefill_ms_per_kpos", 1e6 * 36.8 / 554_000),
    ("serve.closed.prefill_kernel_share", 100 * 415_500 / 554_000)])
def test_reader_on_hand_made_snapshots(name, expect):
    before = snapshot(3.2, prefill_positions=46_000,
                      prefill_kernel_positions=40_000)
    after = snapshot(40.0, prefill_positions=600_000,
                     prefill_kernel_positions=455_500)
    assert read(name, before, after) == pytest.approx(expect, rel=1e-12)
    assert round(read("serve.closed.prefill_ms_per_kpos", before, after),
                 1) == 66.4
    # a program that does not count them (the parent): nothing to read,
    # so the line leaves the metric out; so without the engine's counts
    assert read(name, snapshot(3.2), snapshot(40.0)) is None
    assert read(name, snapshot(3.2), after) is None
    assert read(name, {}, {}) is None
    # no admission in the window: 0 positions
    assert read(name, after, after) is None


def test_kernel_share_is_0_where_no_bucket_holds_the_kernel():
    before = snapshot(0.0, prefill_positions=0, prefill_kernel_positions=0)
    after = snapshot(1.0, prefill_positions=512, prefill_kernel_positions=0)
    assert read("serve.closed.prefill_kernel_share", before, after) == 0.0
    after["engine"]["prefill_kernel_positions"] = 512
    assert read("serve.closed.prefill_kernel_share", before, after) == 100.0


@pytest.fixture(scope="module")
def toy_serve():
    return serve_driver.run(CELL, TOY, TOY_BATCH, seed=SEED, seconds=4.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu")


def test_toy_closed_loop_reports_both(toy_serve):
    r = toy_serve
    assert r["correct"], r["comparisons"]
    line = json.loads(json.dumps(
        harness.result_line("longprompt-batch", True, r)))
    cost = line["metrics"]["serve.closed.prefill_ms_per_kpos"]
    assert cost["unit"] == "ms" and cost["value"] > 0.0
    # off the TPU no bucket's program holds the kernel
    share = line["metrics"]["serve.closed.prefill_kernel_share"]
    assert share["unit"] == "%" and share["value"] == 0.0
    untraced = json.loads(json.dumps(
        harness.result_line("longprompt-batch", False, r)))["metrics"]
    assert not set(METRICS) & set(untraced)
    b, a = (r["context"][k]["engine"] for k in ("before", "after"))
    grown = a["prefill_positions"] - b["prefill_positions"]
    # every admission computed a whole bucket of 16 to 96 positions
    assert grown > 0 and grown % 16 == 0
    assert 16 * (a["admitted"] - b["admitted"]) <= grown \
        <= 96 * (a["admitted"] - b["admitted"])
    assert a["prefill_kernel_positions"] == 0
