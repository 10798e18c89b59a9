"""The latent decode kernel's share of its roofline: the least time the chip
could take for a call of the window's mean shape (the pages it must fetch,
once for all heads, over 819 GB/s; or its FLOPs over peak; from the engine's
positions, counted by the architecture) over the kernel's device time a call
in the traced seconds."""

from chipbench import architectures, flops
from chipbench.readers import expert_steps as es


def read(ctx):
    w = es.window(ctx)
    calls, spent = es.kernel(ctx)
    if w is None or not calls or spent <= 0:
        return None
    f, b = architectures.of(ctx["cfg"]).latent_attention_work(
        ctx["cfg"], w["fetched"], w["positions"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    return 100.0 * least * calls / spent
