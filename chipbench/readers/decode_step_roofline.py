"""The decode token-step's share of its roofline: the least time the chip
could take for a token-step of the window's mean shape (the weights of the
experts its tokens touch, every other weight of the step once, the live
positions' cache; or its FLOPs, whichever takes longer at the chip's peaks;
counted by the architecture, whatever implements the step) over the decode
program's device time a token-step in the traced seconds. Token-steps
traced: the latent kernel's calls over the layers (it runs once a layer and
token-step; the reduction gives a program's seconds, not its calls)."""

from chipbench import architectures, flops
from chipbench.readers import expert_steps as es


def read(ctx):
    w, t = es.window(ctx), ctx.get("trace")
    calls, _ = es.kernel(ctx)
    spent = (t or {}).get("programs", {}).get(es.PROGRAM, 0.0)
    if w is None or not calls or spent <= 0:
        return None
    f, b = architectures.of(ctx["cfg"]).decode_step_work(
        ctx["cfg"], w["rows"], w["positions"], w["experts_touched"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    steps = calls / ctx["cfg"]["num_hidden_layers"]
    return 100.0 * least * steps / spent
