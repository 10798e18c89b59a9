"""``serve.closed.mixed_step_share``, the per-layer metric that says how
often a prompt's chunk rides the decode step (``stats()["engine"]``:
``mixed_steps``, over ``stats()``'s ``batches``), rehearsed off the chip:
the reader on hand-made snapshots, the manifest's entry, and one toy
closed-loop run whose ``--trace 1`` line holds it. No time is read here.
"""

import json
import time

import pytest

from chipbench import harness, manifest
from chipbench.drivers import serve as serve_driver

NAME, READER = "serve.closed.mixed_step_share", "mixed_step_share"

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
SEED = 2 ** 31 + 34  # the driver's seeds pass 32 signed bits


def snapshot(batches, **counts):
    return {"batches": batches, "engine": {"iterations": 0, **counts}}


def read(before, after):
    return manifest.reader(READER)({"before": before, "after": after})


def test_the_manifest_finds_the_metric_with_its_cell():
    entry = manifest.metric_files()[NAME]
    assert entry["reader"] == READER
    assert entry["workloads"] == ["longprompt-batch"]
    assert entry["layer"] == "serve engine"
    assert entry["moves"] == "serve.capacity_tokens_per_s"
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    bench = manifest.benchmark()
    listed = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert listed == [{k: v for k, v in entry.items() if k != "reader"}]
    assert bench["per_layer"][-1]["name"] == NAME  # appended, nothing moved
    # a layer the manifest already names, letter for letter
    assert sum(m["layer"] == entry["layer"] for m in bench["per_layer"]) > 1
    for cell in (w["name"] for w in bench["workloads"]):
        names = [m["name"] for m in manifest.metrics_for(cell, "per_layer")]
        assert (NAME in names) == (cell == "longprompt-batch")
    # not a share of a roofline or of a peak
    assert "roofline" not in NAME and "mfu" not in NAME


@pytest.mark.parametrize("mixed,steps,expect", [
    (3_900, 4_000, 97.5),   # nearly every token-step carried a chunk
    (250, 4_000, 6.25),     # short prompts: the decode program alone, mostly
    (0, 4_000, 0.0)])       # counted, and none: 0 and not nothing
def test_reader_on_hand_made_snapshots(mixed, steps, expect):
    before = snapshot(1_000, mixed_steps=700)
    after = snapshot(1_000 + steps, mixed_steps=700 + mixed)
    assert read(before, after) == pytest.approx(expect, rel=1e-12)


def test_reader_finds_nothing_where_there_is_nothing_to_read():
    before, after = snapshot(1_000, mixed_steps=700), \
        snapshot(5_000, mixed_steps=4_600)
    # an engine that does not count it (the parent; a model whose prompts
    # are prefilled whole by an older program): the line leaves it out
    assert read(snapshot(1_000), snapshot(5_000)) is None
    assert read(snapshot(1_000), after) is None
    # no engine at all, or no ``stats()`` to speak of
    assert read({"batches": 1}, {"batches": 9}) is None
    assert read({}, {}) is None
    # a window in which no token-step ran
    assert read(after, after) is None
    assert read(after, before) is None


@pytest.fixture(scope="module")
def toy_serve():
    return serve_driver.run(CELL, TOY, TOY_BATCH, seed=SEED, seconds=4.0,
                            trace=True, started=time.time(),
                            expect_platform="cpu")


def test_toy_closed_loop_reports_it(toy_serve):
    r = toy_serve
    assert r["correct"], r["comparisons"]
    line = json.loads(json.dumps(
        harness.result_line("longprompt-batch", True, r)))
    share = line["metrics"][NAME]
    assert share["unit"] == "%" and 0.0 < share["value"] <= 100.0
    untraced = json.loads(json.dumps(
        harness.result_line("longprompt-batch", False, r)))["metrics"]
    assert NAME not in untraced
    # another cell's line does not hold it
    other = json.loads(json.dumps(
        harness.result_line("reasoning-batch", True, r)))["metrics"]
    assert NAME not in other


def test_toy_counts_are_the_readers(toy_serve):
    """The value is the window's own counts: chunks of 16 positions that
    rode a token-step, over the token-steps ``stats()`` saw."""
    ctx = toy_serve["context"]
    b, a = ctx["before"], ctx["after"]
    mixed = a["engine"]["mixed_steps"] - b["engine"]["mixed_steps"]
    steps = a["batches"] - b["batches"]
    assert 0 < mixed <= steps
    assert read(b, a) == pytest.approx(100.0 * mixed / steps)
    grown = a["engine"]["prefill_positions"] \
        - b["engine"]["prefill_positions"]
    assert grown == 16 * mixed
    live = a["engine"]["chunk_positions_live"] \
        - b["engine"]["chunk_positions_live"]
    assert 0 < live <= grown
