"""The expert kernel's share of its roofline in the decode step: the least
time the chip could take for a call of the window's mean shape (one expert
layer: the two matrices of the held experts that have a row, once each, over
819 GB/s, with the rows read and written; or its FLOPs over peak; counted by
the architecture from the change of ``experts_touched`` and
``expert_assignments_held`` over ``expert_layer_steps``) over the kernel's
device time a call in the traced seconds. The kernel is named for its grid's
tiles (``moe_expert_tiles_<tiles>``), which the architecture works out from
the engine's slots, so a prefill's calls, at another size, are not read."""

from chipbench import architectures
from chipbench import flops
from chipbench.readers import engine_window as ew

KERNEL = "moe_expert_tiles_"         # the pallas_call's name, less its tiles


def read(ctx):
    pair, t = ew.engines(ctx), ctx.get("trace")
    if pair is None or not t or not t.get("ops") \
            or not pair[1].get("expert_layer_steps"):
        return None
    b, a = pair
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    layer_steps = d("expert_layer_steps")
    arch = architectures.of(ctx["cfg"])
    name = KERNEL + str(arch.expert_kernel_tiles(
        ctx["cfg"], ctx["mix"]["engine"]["max_batch_size"]))
    # by the operation's own name: the text of an operation that reads the
    # kernel's result names the kernel too
    mine = [k for k in t["ops"] if k.lstrip("%").split(".")[0] == name]
    calls = sum(t["op_calls"][k] for k in mine)
    spent = sum(t["ops"][k] for k in mine)
    if layer_steps <= 0 or not calls or spent <= 0:
        return None
    f, nbytes = arch.expert_kernel_work(
        ctx["cfg"], d("expert_assignments_held") / layer_steps,
        d("experts_touched") / layer_steps)
    least, _ = flops.roofline_seconds(f, nbytes, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
