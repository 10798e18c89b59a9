"""The blocked expert kernel's share of its roofline, for SwiGLU experts too
wide for the whole-matrix kernel's VMEM (``ops/moe.py::_blocked_swiglu``,
``moe_swiglu_blocks_<grid>``: a decode step's calls are named for the held
experts, a step with a chunk of a prompt's for its passes). Counted here,
from the configuration's widths: the least time of one call of each kind
(one expert layer of a decode token-step, of a mixed step) at the window's
means per expert layer that ran in a step of that kind (the held experts
touched, ``experts_touched``; the assignments to held experts,
``expert_assignments_held``, ``mixed_`` before both for a mixed step) is the
touched experts' three matrices read once each, each held assignment's row
read in the activations' type and its result written in float32, over the
chip's bandwidth; or the held assignments' FLOPs (three matmuls of hidden x
intermediate a row) over its peak; whichever is longer. Times each kind's
calls in the traced seconds, over the kernel's device time under both names.
A program without the kernel (the parent, every other cell) or without the
counts reads ``None``; a share over 100% is a fault of the count and is
never clipped."""

from chipbench import architectures, flops
from chipbench.readers import engine_window as ew

KERNEL = "moe_swiglu_blocks_"        # the pallas_call's name, less its grid
SIZE = {"bfloat16": 2, "float32": 4}


def read(ctx):
    cfg, pair, t = ctx["cfg"], ew.engines(ctx), ctx.get("trace")
    if pair is None or not t or not t.get("ops") \
            or not hasattr(architectures.of(cfg), "layer_counts"):
        return None
    b, a = pair
    decode = KERNEL + str(cfg["n_routed_experts"])
    calls = {"": 0.0, "mixed_": 0.0}
    spent = 0.0
    for k, s in t["ops"].items():
        name = k.lstrip("%").split(".")[0]
        if name.startswith(KERNEL) and name[len(KERNEL):].isdigit():
            calls["" if name == decode else "mixed_"] += t["op_calls"][k]
            spent += s
    if not sum(calls.values()) or spent <= 0:
        return None
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * d * f * SIZE[cfg["param_dtype"]]
    row = d * (SIZE[cfg["activation_dtype"]] + 4)
    least = 0.0
    for kind, n in calls.items():
        if not n:
            continue
        steps = a.get(kind + "expert_layer_steps", 0) \
            - b.get(kind + "expert_layer_steps", 0)
        if steps <= 0 or kind + "expert_assignments_held" not in a:
            return None
        held, touched = ((a[kind + k] - b.get(kind + k, 0)) / steps
                         for k in ("expert_assignments_held",
                                   "experts_touched"))
        least += n * flops.roofline_seconds(
            held * 6.0 * d * f, touched * weights + held * row,
            ctx["device"]["kind"])[0]
    return 100.0 * least / spent
