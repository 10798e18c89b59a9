"""What a hybrid state-space model's decode step counted of itself inside
the window: ``after`` - ``before`` of the counts ``stats()["engine"]``
carries for it (``state_rows_stepped``, ``ssm_layer_steps``, beside the
engine's ``iterations`` and ``live_positions``), as means a token-step, and
the state-update kernel's calls and device seconds in the trace. Shared by
the two roofline readers of the step and of the kernel; a program without
the counts reads ``None``.

The two readers join two clocks, as ``expert_steps.py``'s: means a token-step
come from the window's counters, seconds and calls from the traced seconds at
its end. A share over 100% is a fault of the count and is never clipped."""

from chipbench.readers import engine_window as ew

KERNEL = "ssm_decode_update"         # the pallas_call's name
PROGRAM = "jit_paged_step_fn"        # the engine's one decode program


def window(ctx):
    """Means a decode token-step over the window, or None: live ``rows``
    whose state the step moves, cached ``positions`` they attend over."""
    pair = ew.engines(ctx)
    if pair is None or not pair[1].get("ssm_layer_steps"):
        return None
    b, a = pair
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    layer_steps, its = d("ssm_layer_steps"), d("iterations")
    if layer_steps <= 0 or its <= 0:
        return None
    return {"rows": d("state_rows_stepped") / layer_steps,
            "positions": d("live_positions") / its}


def kernel(ctx):
    """(calls, device seconds) of the state-update kernel in the trace."""
    t = ctx.get("trace")
    if not t or not t.get("ops"):
        return 0, 0.0
    # by the operation's own name: the text of an operation that reads the
    # kernel's result names the kernel too
    mine = [k for k in t["ops"] if KERNEL in k]
    return (sum(t["op_calls"][k] for k in mine),
            sum(t["ops"][k] for k in mine))
