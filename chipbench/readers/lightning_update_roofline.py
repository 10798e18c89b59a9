"""The Lightning state update's share of its roofline in the decode program
(``lightning_decode_update``: ops/ssm.py's decode kernel with a group a
head): the least time the chip could take for a call of the window's mean
shape (one layer: each live row's 2 MiB of float32 state read once and
written once, or its FLOPs; counted by the architecture) over the kernel's
device time a call in the traced seconds."""

from chipbench import flops
from chipbench.readers import sparse_linear_steps as sl


def read(ctx):
    arch, w, t = sl._arch(ctx), sl.window(ctx), sl.traced(ctx)
    if w is None or t is None or w[""] is None:
        return None
    calls, spent = t[sl.UPDATE[""]]
    if not calls or spent <= 0:
        return None
    f, b = arch.lightning_update_work(ctx["cfg"], w[""]["rows"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
