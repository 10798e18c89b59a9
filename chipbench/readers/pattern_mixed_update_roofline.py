"""The state-update kernel's share of its roofline inside the layer-pattern
model's mixed step: the least time the chip could take for a call of the
window's mean shape (one Mamba layer: each live decode row's recurrent state
read once and written once in float32 over 819 GB/s, or its FLOPs over peak;
the architecture's count, the one ``ssm_update_roofline.py`` holds the decode
program's calls to) over the kernel's device time a call in the traced
seconds. The rows are the mixed step's own count (``pattern_mixed_steps``):
the chunk's row moves in the scan and is not among them."""

from chipbench import architectures, flops
from chipbench.readers import mixed_ssm_steps as ms
from chipbench.readers import pattern_mixed_steps as pm


def read(ctx):
    w = pm.window(ctx)
    calls, spent = ms.kernel(ctx)
    if w is None or not w["rows"] or not calls or spent <= 0:
        return None
    f, b = architectures.of(ctx["cfg"]).ssm_update_work(ctx["cfg"],
                                                        w["rows"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
