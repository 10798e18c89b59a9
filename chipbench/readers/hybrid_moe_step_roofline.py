"""The decode token-step's share of its roofline, for a model whose layers
keep a state or hold a share of a layer's experts, by kind: the least time
the chip could take for a token-step of the window's mean shape (the touched
*held* experts' weights from the change of ``experts_touched`` over
``expert_layer_steps``, every other weight of the step once, each live row's
state read and written once, the live positions' K and V; or its FLOPs,
whichever takes longer at the chip's peaks; counted by the architecture,
whatever implements the step) over the decode program's device time a
token-step in the traced seconds. Token-steps traced: the state-update
kernel's calls over the layers that keep a state (it runs once in each a
token-step; the reduction gives a program's seconds, not its calls). An
expert that lives elsewhere, an expert no live token reached and an idle
slot's state are in none of the counts; a share over 100% is a fault of the
count and is never clipped."""

from chipbench import architectures, flops
from chipbench.readers import engine_window as ew
from chipbench.readers import ssm_steps as ss


def read(ctx):
    w, t, pair = ss.window(ctx), ctx.get("trace"), ew.engines(ctx)
    calls, _ = ss.kernel(ctx)
    spent = (t or {}).get("programs", {}).get(ss.PROGRAM, 0.0)
    if w is None or not calls or spent <= 0 \
            or not pair[1].get("expert_layer_steps"):
        return None
    b, a = pair
    layer_steps = a["expert_layer_steps"] - b.get("expert_layer_steps", 0)
    if layer_steps <= 0:
        return None
    touched = (a["experts_touched"] - b.get("experts_touched", 0)) \
        / layer_steps
    arch = architectures.of(ctx["cfg"])
    f, nbytes = arch.decode_step_work(ctx["cfg"], w["rows"], w["positions"],
                                      touched)
    least, _ = flops.roofline_seconds(f, nbytes, ctx["device"]["kind"])
    return 100.0 * least * (calls / arch.ssm_layers(ctx["cfg"])) / spent
