"""What one mixed step of the layer-pattern model costs the device: the mixed
program's seconds in the traced seconds over its calls, in ms. Calls traced:
the mixed step's state-update kernel's over the layers that keep a state (it
runs once in each a mixed step; the reduction gives a program's seconds, not
its calls. ``hybrid_mixed_step_ms.py`` divides by every layer, which is right
where every layer keeps a state and wrong for a pattern)."""

from chipbench import architectures
from chipbench.readers import mixed_ssm_steps as ms


def read(ctx):
    calls, _ = ms.kernel(ctx)
    spent = (ctx.get("trace") or {}).get("programs", {}).get(ms.PROGRAM, 0.0)
    if not calls or spent <= 0:
        return None
    cfg = ctx["cfg"]
    return 1e3 * spent * architectures.of(cfg).ssm_layers(cfg) / calls
