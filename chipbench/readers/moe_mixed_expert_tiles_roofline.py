"""The expert kernel's share of its roofline inside the mixed step: the least
time the chip could take for a call of the window's mean shape (one expert
layer over the chunk's real positions and the live decode rows together: the
two matrices of the held experts that have a row, once each, over 819 GB/s,
with the rows read and written; or its FLOPs over peak; the architecture's
count, the one ``moe_expert_tiles_roofline.py`` holds the decode step's calls
to) over the kernel's device time a call in the traced seconds. The kernel is
named for its grid's tiles (``moe_expert_tiles_<tiles>``), which the
architecture works out from the mixed step's rows, the engine's slots and one
chunk (the bucket step in whole pages), so the decode step's calls, at
another size, are not read. The tiles' empty rests are the kernel's own waste
and in no count."""

from chipbench import architectures, flops
from chipbench.readers import pattern_mixed_steps as pm
from chipbench.readers.moe_expert_tiles_roofline import KERNEL


def read(ctx):
    w = pm.window(ctx)
    if w is None or not w["assignments"]:
        return None
    arch, e = architectures.of(ctx["cfg"]), ctx["mix"]["engine"]
    page = e["kv_page_tokens"]
    chunk = -(-e["pad_multiple"] // page) * page
    calls, spent = pm.named(ctx, KERNEL + str(arch.expert_kernel_tiles(
        ctx["cfg"], e["max_batch_size"] + chunk)))
    if not calls or spent <= 0:
        return None
    f, nbytes = arch.expert_kernel_work(ctx["cfg"], w["assignments"],
                                        w["experts_touched"])
    least, _ = flops.roofline_seconds(f, nbytes, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
