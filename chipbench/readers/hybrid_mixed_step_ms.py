"""What one mixed step of the hybrid model costs the device: the mixed
program's seconds in the traced seconds over its calls, in ms. Calls traced:
the mixed step's state-update kernel's over the layers (it runs once a layer
and mixed step; the reduction gives a program's seconds, not its calls)."""

from chipbench.readers import mixed_ssm_steps as ms


def read(ctx):
    calls, _ = ms.kernel(ctx)
    spent = (ctx.get("trace") or {}).get("programs", {}).get(ms.PROGRAM, 0.0)
    if not calls or spent <= 0:
        return None
    return 1e3 * spent * ctx["cfg"]["num_hidden_layers"] / calls
