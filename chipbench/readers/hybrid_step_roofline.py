"""The hybrid decode token-step's share of its roofline: the least time the
chip could take for a token-step of the window's mean shape (every weight of
the step once, each live row's state read and written once, the live
positions' K and V; or its FLOPs, whichever takes longer at the chip's peaks;
counted by the architecture, whatever implements the step) over the decode
program's device time a token-step in the traced seconds. Token-steps traced:
the state-update kernel's calls over the layers (it runs once a layer and
token-step; the reduction gives a program's seconds, not its calls)."""

from chipbench import architectures, flops
from chipbench.readers import ssm_steps as ss


def read(ctx):
    w, t = ss.window(ctx), ctx.get("trace")
    calls, _ = ss.kernel(ctx)
    spent = (t or {}).get("programs", {}).get(ss.PROGRAM, 0.0)
    if w is None or not calls or spent <= 0:
        return None
    f, b = architectures.of(ctx["cfg"]).decode_step_work(
        ctx["cfg"], w["rows"], w["positions"])
    least, _ = flops.roofline_seconds(f, b, ctx["device"]["kind"])
    steps = calls / ctx["cfg"]["num_hidden_layers"]
    return 100.0 * least * steps / spent
