"""``serve.closed.sparse_expert_roofline`` on hand-made numbers: the blocked
expert kernel's share of its roofline in ``longcontext-batch``, worked by hand
from the configuration's widths, and ``None`` wherever there is nothing to
read (no trace, a trace without the kernel, a program without the counts,
another architecture). No time read here is a device number."""

import pytest

from chipbench import manifest
from chipbench.readers import sparse_expert_roofline
from test_latent_sparse_cell import MIX, made_context

METRIC = "serve.closed.sparse_expert_roofline"
BLOCKS = ("%moe_swiglu_blocks_16.8", "%moe_swiglu_blocks_102.9")


def blocked_context(trace=True):
    """``made_context``'s window with the held assignments counted (2,000 a
    mixed step's expert layer, 6 a decode token-step's) and the blocked
    kernel traced: 200 decode calls in 0.5 s, 50 mixed-step calls in 0.1 s."""
    ctx = made_context(trace)
    ctx["after"]["engine"].update(
        mixed_expert_assignments_held=500 * 2000,
        expert_assignments_held=2000 * 6)
    ctx["before"]["engine"].update(mixed_expert_assignments_held=0,
                                   expert_assignments_held=0)
    if trace:
        for name, seconds, calls in zip(BLOCKS, (0.5, 0.1), (200, 50)):
            ctx["trace"]["ops"][name] = seconds
            ctx["trace"]["op_calls"][name] = calls
    return ctx


def test_the_blocked_expert_kernel_s_share_on_hand_made_numbers():
    peak, bw = 197e12, 819e9
    # 10 mixed steps' and 40 decode token-steps' five calls; 16 and 5 experts
    # touched a call; three matrices of 6,144 x 2,048 in bf16 an expert, a
    # row in bf16 and its result in float32
    expert = 3 * 6144 * 2048 * 2
    e_mixed = max(2000 * 6 * 6144 * 2048 / peak,
                  (16 * expert + 2000 * 6144 * 6) / bw)
    e_rows = max(6 * 6 * 6144 * 2048 / peak, (5 * expert + 6 * 6144 * 6) / bw)
    assert e_mixed == pytest.approx(1.2817e9 / bw, rel=1e-4)
    share = sparse_expert_roofline.read(blocked_context())
    assert share == pytest.approx(100.0 * (50 * e_mixed + 200 * e_rows) / 0.6)
    assert share == pytest.approx(28.41, abs=0.01)


def test_the_blocked_expert_kernel_s_share_reads_none_without_it():
    assert sparse_expert_roofline.read(blocked_context(trace=False)) is None
    # the parent's trace: the same window with no blocked kernel
    assert sparse_expert_roofline.read(made_context()) is None
    # a program that counts no held assignments
    bare = blocked_context()
    for k in ("mixed_expert_assignments_held", "expert_assignments_held"):
        del bare["after"]["engine"][k]
    assert sparse_expert_roofline.read(bare) is None
    # a program that counts no expert layer at all
    old = blocked_context()
    old["before"]["engine"] = old["after"]["engine"] = {"mixed_steps": 3}
    assert sparse_expert_roofline.read(old) is None
    # another architecture's cell
    other = dict(blocked_context(),
                 cfg=manifest.config("glm-4.7-flash-d7"))
    assert sparse_expert_roofline.read(other) is None


def test_the_manifest_finds_the_expert_roofline_with_its_cell():
    files = manifest.metric_files()
    per_layer = {m["name"]: m for m in manifest.benchmark()["per_layer"]}
    assert files[METRIC]["reader"] == "sparse_expert_roofline"
    assert files[METRIC]["workloads"] == [MIX]
    assert files[METRIC]["moves"] == "serve.capacity_tokens_per_s"
    assert {k: v for k, v in files[METRIC].items() if k != "reader"} \
        == per_layer[METRIC]
    assert METRIC in {m["name"] for m in manifest.metrics_for(MIX,
                                                              "per_layer")}
    for cell in ("reasoning-batch", "longanswer-batch", "longdoc-batch"):
        assert METRIC not in {m["name"] for m in
                              manifest.metrics_for(cell, "per_layer")}
