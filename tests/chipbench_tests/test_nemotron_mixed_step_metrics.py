"""The layer-pattern model's mixed step (a chunk of a prompt and one decode
token a live row in one program, ``models/nemotron_h.py::mixed_step``) in the
benchmark's sight, rehearsed off the chip: the four metrics' files and the
manifest's entries, and the three trace readers
(``serve.closed.moe_mixed_step_ms``, the program's device time a call;
``serve.closed.ssm_mixed_update_roofline_hd64``, the state-update kernel under
the name it has there; ``serve.closed.moe_mixed_expert_tiles_roofline``, the
expert kernel at the mixed step's grid) on hand-made numbers, on a program
that lacks the counts, and on a planted fault of the count. No time read here
is a device number.
"""

import pytest

from chipbench import architectures, manifest
from chipbench.readers import (mixed_ssm_steps, mixed_step_share,
                               moe_expert_tiles_roofline,
                               moe_mixed_expert_tiles_roofline,
                               moe_mixed_step_ms, pattern_mixed_steps,
                               pattern_mixed_update_roofline, ssm_steps,
                               ssm_update_roofline)

CELL, CONFIG = "longanswer-batch", "nemotron-3-super-d11-e128"
SHARE = "serve.closed.moe_mixed_step_share"
STEP_MS = "serve.closed.moe_mixed_step_ms"
UPDATE = "serve.closed.ssm_mixed_update_roofline_hd64"
TILES = "serve.closed.moe_mixed_expert_tiles_roofline"
TRACE_READERS = [moe_mixed_step_ms, pattern_mixed_update_roofline,
                 moe_mixed_expert_tiles_roofline]


# ------------------------------------------------------------ the manifest
@pytest.mark.parametrize("name,reader,unit,better,source,layer", [
    (SHARE, "mixed_step_share", "%", "higher", "program_counter",
     "serve engine"),
    (STEP_MS, "moe_mixed_step_ms", "ms", "lower", "device_trace",
     "model step, serve"),
    (UPDATE, "pattern_mixed_update_roofline", "%", "higher", "device_trace",
     "kernels"),
    (TILES, "moe_mixed_expert_tiles_roofline", "%", "higher", "device_trace",
     "kernels")])
def test_the_manifest_finds_the_metric_with_its_cell(name, reader, unit,
                                                     better, source, layer):
    entry = manifest.metric_files()[name]
    assert entry["reader"] == reader
    assert callable(manifest.reader(reader))
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve.capacity_tokens_per_s"
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == (unit, better, source, layer)
    bench = manifest.benchmark()
    listed = [m for m in bench["per_layer"] if m["name"] == name]
    assert listed == [{k: v for k, v in entry.items() if k != "reader"}]
    # a layer the manifest already names, letter for letter
    assert sum(m["layer"] == layer for m in bench["per_layer"]) > 1
    for cell in (w["name"] for w in bench["workloads"]):
        names = [m["name"] for m in manifest.metrics_for(cell, "per_layer")]
        assert (name in names) == (cell == CELL)


def test_the_two_programs_kernels_are_told_apart_by_name():
    """The decode program's readers must not see the mixed step's kernels,
    nor these the decode program's: each counts its own calls. The expert
    kernel is named for its grid, a tile a held expert in the decode step
    and the assignments' tiles beside in the mixed step."""
    cfg, e = manifest.config(CONFIG), manifest.traffic(CELL)["engine"]
    arch = architectures.of(cfg)
    assert ssm_steps.KERNEL not in mixed_ssm_steps.KERNEL
    assert mixed_ssm_steps.PROGRAM != ssm_steps.PROGRAM
    assert UPDATE.endswith(mixed_ssm_steps.KERNEL + "_roofline_hd64")
    assert arch.expert_kernel_tiles(cfg, e["max_batch_size"]) == 128
    assert arch.expert_kernel_tiles(
        cfg, e["max_batch_size"] + e["pad_multiple"]) == 238
    assert (arch.ssm_layers(cfg), arch.expert_layers(cfg),
            cfg["num_hidden_layers"]) == (5, 5, 11)


# ------------------------------------------------------------- the readers
def _ctx(before, after, trace, cfg=CONFIG):
    return {"cfg": manifest.config(cfg), "mix": manifest.traffic(CELL),
            "before": {"engine": before, "batches": 100},
            "after": {"engine": after, "batches": 100 + 2_400},
            "trace": trace, "device": {"kind": "TPU v5 lite",
                                       "platform": "tpu", "count": 1}}


def _window(rows, assignments, touched, steps, twice=False):
    """Snapshots around a window of ``steps`` mixed steps, each of five
    Mamba layers whose update kernel moves ``rows`` live rows and of five
    expert layers with ``assignments`` held assignments on ``touched`` held
    experts; beside them the decode program's counts, under its own names.
    ``twice`` plants the fault: a mixed step's rows and assignments counted
    twice over."""
    before = {"iterations": 5, "mixed_steps": 40,
              "mixed_state_rows_stepped": 9_000, "mixed_ssm_layer_steps": 200,
              "mixed_expert_layer_steps": 200,
              "mixed_expert_assignments_held": 70_000,
              "mixed_experts_touched": 25_000,
              "state_rows_stepped": 500, "ssm_layer_steps": 50,
              "expert_layer_steps": 50, "experts_touched": 6_000,
              "expert_assignments_held": 30_000, "live_positions": 900}
    k = 2 if twice else 1
    after = dict(
        before, iterations=305, mixed_steps=40 + steps,
        mixed_state_rows_stepped=9_000 + k * rows * 5 * steps,
        mixed_ssm_layer_steps=200 + 5 * steps,
        mixed_expert_layer_steps=200 + 5 * steps,
        mixed_expert_assignments_held=70_000 + k * assignments * 5 * steps,
        mixed_experts_touched=25_000 + touched * 5 * steps,
        # the decode program ran too: 1,500 token-steps of 127 rows
        state_rows_stepped=500 + 127 * 5 * 1_500,
        ssm_layer_steps=50 + 5 * 1_500, expert_layer_steps=50 + 5 * 1_500,
        experts_touched=6_000 + 125 * 5 * 1_500,
        expert_assignments_held=30_000 + 700 * 5 * 1_500)
    return before, after


def _trace(steps=60, update_s=0.00172, tiles_s=0.00205, step_s=0.038):
    """The traced seconds: ``steps`` mixed steps (five calls of each kernel
    a step, an operation a layer) and 80 token-steps of the decode program,
    whose kernels are not the mixed step's."""
    t = {"programs": {"jit_mixed_step": steps * step_s,
                      "jit_paged_step_fn": 10 * 0.205},
         "ops": {}, "op_calls": {}, "op_text": {}}
    for i in range(5):
        for name, s, n in (("ssm_mixed_update", update_s, steps),
                           ("moe_expert_tiles_238", tiles_s, steps),
                           ("ssm_decode_update", 0.00172, 80),
                           ("moe_expert_tiles_128", 0.001855, 80)):
            t["ops"][f"%{name}.{7 * i + 3}"] = n * s
            t["op_calls"][f"%{name}.{7 * i + 3}"] = n
    # an operation that reads the kernel's result names it in its text only
    t["ops"]["%fusion.91"] = 0.4
    t["op_calls"]["%fusion.91"] = 300
    t["op_text"]["%fusion.91"] = "fusion(%moe_expert_tiles_238.3)"
    return t


def test_the_readers_on_hand_made_numbers():
    # 900 mixed steps in the window: 127 rows live, 2,790 held assignments
    # on 127.5 held experts a layer; the traced seconds hold 60 of them
    ctx = _ctx(*_window(127, 2_790, 127.5, 900), _trace())
    w = pattern_mixed_steps.window(ctx)
    assert w == {"rows": pytest.approx(127.0), "assignments":
                 pytest.approx(2_790.0), "experts_touched":
                 pytest.approx(127.5)}
    assert mixed_ssm_steps.kernel(ctx) == (300, pytest.approx(300 * 0.00172))
    assert pattern_mixed_steps.named(ctx, "moe_expert_tiles_238") \
        == (300, pytest.approx(300 * 0.00205))
    # the program's seconds over its calls: the update kernel's over the
    # five layers that keep a state, not over the pattern's eleven
    assert moe_mixed_step_ms.read(ctx) == pytest.approx(38.0, rel=1e-9)
    # a live row's state of one layer, 128 heads x 64 channels x 128 of
    # state in float32, read and written: 8 MiB a row
    state = 128 * 64 * 128
    least = 2 * 127 * state * 4 / 819e9
    assert 6 * 127 * state / 197e12 < least           # bound by the bytes
    assert pattern_mixed_update_roofline.read(ctx) == pytest.approx(
        100 * least / 0.00172, rel=1e-9)
    assert 70 < pattern_mixed_update_roofline.read(ctx) < 80
    # 127.5 experts' two matrices of 1,024 x 2,688 in bf16, each
    # assignment's row read in bf16 and written in float32
    nbytes = 127.5 * 2 * 1024 * 2688 * 2 + 2_790 * 1024 * 6
    least = nbytes / 819e9
    assert 2_790 * 4 * 1024 * 2688 / 197e12 < least   # bound by the bytes
    assert moe_mixed_expert_tiles_roofline.read(ctx) == pytest.approx(
        100 * least / 0.00205, rel=1e-9)
    assert 80 < moe_mixed_expert_tiles_roofline.read(ctx) < 90
    # the engagement counter: 900 of the window's 2,400 token-steps
    assert mixed_step_share.read(ctx) == pytest.approx(37.5)
    # and the decode program's readers read the decode program alone
    assert ssm_steps.window(ctx)["rows"] == pytest.approx(127.0)
    assert ssm_steps.kernel(ctx) == (400, pytest.approx(400 * 0.00172))
    decode = ssm_update_roofline.read(ctx)
    assert decode == pytest.approx(pattern_mixed_update_roofline.read(ctx))
    assert 85 < moe_expert_tiles_roofline.read(ctx) < 100


@pytest.mark.parametrize("reader", TRACE_READERS)
def test_nothing_to_read_is_none_and_never_zero(reader):
    """No trace (an untraced or CPU run), no such kernel in it (the parent:
    its prompts are prefilled whole; or traced seconds that hold no mixed
    step), a program without the counts, a window without a mixed step, or
    another architecture's cell: the line leaves the metric out, and nothing
    raises."""
    before, after = _window(127, 2_790, 127.5, 900)
    trace = _trace()
    assert reader.read(_ctx(before, after, None)) is None
    assert reader.read(_ctx(before, after, dict(
        trace, ops={}, op_calls={}))) is None
    decode_only = {
        "programs": {"jit_paged_step_fn": 3.4, "jit_prefill": 0.8},
        "ops": {k: v for k, v in trace["ops"].items() if "mixed" not in k
                and "238" not in k},
        "op_calls": {k: v for k, v in trace["op_calls"].items()
                     if "mixed" not in k and "238" not in k}, "op_text": {}}
    assert reader.read(_ctx(before, after, decode_only)) is None
    parent = {k: v for k, v in before.items() if not k.startswith("mixed_")}
    grown = {k: v for k, v in after.items() if not k.startswith("mixed_")}
    assert reader.read(_ctx(parent, grown, decode_only)) is None
    assert reader.read(_ctx({}, {}, decode_only)) is None
    old = dict(_ctx({}, {}, decode_only, "mistral-7b-d16"),
               before={}, after={})
    assert reader.read(old) is None
    if reader is not moe_mixed_step_ms:
        # the counts are a program's that the trace is not: no share
        assert reader.read(_ctx(parent, grown, trace)) is None
        assert reader.read(_ctx(after, after, trace)) is None
    # the engagement counter of the parent: it counts 0 mixed steps
    assert mixed_step_share.read(_ctx(
        dict(parent, mixed_steps=0), dict(grown, mixed_steps=0),
        decode_only)) == 0.0


@pytest.mark.parametrize("reader", [pattern_mixed_update_roofline,
                                    moe_mixed_expert_tiles_roofline])
def test_rows_counted_twice_read_over_a_hundred(reader):
    """A share over 100% is a fault of the count and is never clipped: with
    every slot live and every held expert touched at kernel times just over
    the roofline's, a mixed step whose rows were added twice (once as the
    chunk's call and once more as the decode rows') reads over 100."""
    fast = _trace(update_s=0.00135, tiles_s=0.00175)
    honest = reader.read(_ctx(*_window(128, 2_816, 128, 900), fast))
    assert 95 < honest < 100
    doubled = reader.read(_ctx(*_window(128, 2_816, 128, 900, twice=True),
                               fast))
    assert doubled > 100
