"""The state-update kernel's share of its roofline: the least time the chip
could take for a call of the window's mean shape (one layer: each live row's
recurrent state read once and written once in float32 over 819 GB/s, or its
FLOPs over peak; counted by the architecture) over the kernel's device time a
call in the traced seconds."""

from chipbench import architectures, flops
from chipbench.readers import ssm_steps as ss


def read(ctx):
    w = ss.window(ctx)
    calls, spent = ss.kernel(ctx)
    if w is None or not calls or spent <= 0:
        return None
    f, b = architectures.of(ctx["cfg"]).ssm_update_work(ctx["cfg"],
                                                        w["rows"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
