"""The flash kernels' share of their roofline in the traced steps: the
least time the chip could take for the calls seen (the larger of FLOPs over
peak and bytes over peak bandwidth, from shapes) over their device time.

The program gives its ``pallas_call``s no name, so the trace names a Mosaic
call after whatever jax scope surrounds it (``checkpoint``, ``closed_call``,
``rematted_computation``). Until it does (PERF.md, Open questions), the three
kernels are told apart by what they return from [batch x heads, seq,
head_dim] operands: the forward an output and a log-sum-exp column, dk/dv two
tensors, dq one.
"""

import re

from chipbench import architectures, flops

# the kernel functions' names, should a trace carry them
NAMES = {"fwd": "_fwd_kernel", "dq": "_dq_kernel", "dkv": "_dkv_kernel"}
SHAPE = re.compile(r"(bf16|f16|f32)\[([0-9,]+)\]")


def kind_of(text: str, bh: int, seq: int, head_dim: int):
    """Which kernel an instruction of the trace is, or None."""
    for kind, mark in NAMES.items():
        if mark in text:
            return kind
    if " custom-call(" not in text or " = " not in text:
        return None
    result, args = text.split(" = ", 1)[1].split(" custom-call(", 1)

    def shapes_of(part):
        return [tuple(int(d) for d in dims.split(","))
                for _, dims in SHAPE.findall(part)]

    shapes = shapes_of(result)
    tensor, column = (bh, seq, head_dim), (bh, seq, 1)
    if shapes_of(args)[:1] != [tensor]:  # every kernel's first operand
        return None
    if shapes == [tensor, column]:
        return "fwd"
    if shapes == [tensor, tensor]:
        return "dkv"
    if shapes == [tensor]:
        return "dq"
    return None


def read(ctx):
    t, cfg, mix = ctx.get("trace"), ctx["cfg"], ctx["mix"]
    if not t or not t.get("ops") or mix.get("attention") != "flash":
        return None
    heads, head_dim = architectures.of(cfg).attention_shape(cfg)
    bh = mix["batch"] * heads
    least, spent = 0.0, 0.0
    for name, seconds in t["ops"].items():
        kind = kind_of(t["op_text"].get(name, name), bh, mix["seq"],
                       head_dim)
        if kind is None:
            continue
        f, b = flops.flash_call(kind, bh, mix["seq"], head_dim)
        bound, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
        least += bound * t["op_calls"][name]
        spent += seconds
    return 100.0 * least / spent if spent > 0 else None
