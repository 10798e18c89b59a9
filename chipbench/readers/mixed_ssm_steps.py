"""What a hybrid state-space model's mixed step (a chunk of a prompt and one
decode token a live row, in one program) counted of itself inside the window,
and what the trace holds of it: the live rows whose state a call of its
state-update kernel moves, as a mean over the window's mixed steps
(``stats()["engine"]``: ``mixed_state_rows_stepped`` over ``mixed_steps`` and
the layers), and the kernel's calls and device seconds in the traced seconds.
The mixed program runs the decode program's kernel under a name of its own, so
that ``ssm_steps.py`` counts the decode program's token-steps alone; this reads
the rest. Shared by the mixed step's two readers; a program without the counts
(one whose prompts are prefilled whole, or a model without a state) reads
``None``.

Two clocks are joined as in ``ssm_steps.py``: the mean comes from the window's
counters, seconds and calls from the traced seconds at its end. A share over
100% is a fault of the count and is never clipped."""

from chipbench.readers import engine_window as ew

KERNEL = "ssm_mixed_update"          # the pallas_call's name
PROGRAM = "jit_mixed_step"           # the engine's one mixed program


def rows(ctx):
    """Mean live rows a call of the kernel moves (one layer of one mixed
    step), or None."""
    pair = ew.engines(ctx)
    if pair is None or not pair[1].get("mixed_state_rows_stepped"):
        return None
    b, a = pair
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    stepped, steps = d("mixed_state_rows_stepped"), d("mixed_steps")
    if stepped <= 0 or steps <= 0:
        return None
    return stepped / (steps * ctx["cfg"]["num_hidden_layers"])


def kernel(ctx):
    """(calls, device seconds) of the mixed step's state-update kernel in
    the trace, by the operation's own name."""
    t = ctx.get("trace")
    if not t or not t.get("ops"):
        return 0, 0.0
    mine = [k for k in t["ops"] if KERNEL in k]
    return (sum(t["op_calls"][k] for k in mine),
            sum(t["ops"][k] for k in mine))
