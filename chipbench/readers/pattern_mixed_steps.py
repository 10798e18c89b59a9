"""What the mixed step of a model whose layers are of kinds (Mamba-2 mixers,
attention, a share of a layer's experts: ``models/nemotron_h.py::mixed_step``,
a chunk of a prompt and one decode token a live row in one program) counted
of itself inside the window, as means a call of each of its two kernels
(``stats()["engine"]``, all under names of the mixed step's own, so that the
decode program's means stay the decode program's):

  - the state-update kernel (``ssm_mixed_update``, once a Mamba layer):
    ``mixed_state_rows_stepped`` over ``mixed_ssm_layer_steps``, the live
    decode rows whose state a call moves;
  - the expert kernel (once an expert layer, over the chunk's and the decode
    rows' rows together): ``mixed_expert_assignments_held`` and
    ``mixed_experts_touched`` over ``mixed_expert_layer_steps``.

Shared by the mixed step's three trace readers, with the kernels' calls and
device seconds by the operation's own name (``mixed_ssm_steps.kernel`` for
the state update; :func:`named` for any other). A program without the counts
(the parent: its prompts are prefilled whole) reads ``None``. Two clocks are
joined as in ``ssm_steps.py``: means from the window's counters, seconds and
calls from the traced seconds at its end; a share over 100% is a fault of the
count and is never clipped."""

from chipbench.readers import engine_window as ew


def window(ctx):
    """Means a call over the window's mixed steps, or None: ``rows`` (of the
    state-update kernel), ``assignments`` and ``experts_touched`` (of the
    expert kernel)."""
    pair = ew.engines(ctx)
    if pair is None or not pair[1].get("mixed_ssm_layer_steps") \
            or not pair[1].get("mixed_expert_layer_steps"):
        return None
    b, a = pair
    d = lambda k: a[k] - b.get(k, 0)  # noqa: E731
    ssm_calls, expert_calls = d("mixed_ssm_layer_steps"), \
        d("mixed_expert_layer_steps")
    if ssm_calls <= 0 or expert_calls <= 0:
        return None
    return {"rows": d("mixed_state_rows_stepped") / ssm_calls,
            "assignments": d("mixed_expert_assignments_held") / expert_calls,
            "experts_touched": d("mixed_experts_touched") / expert_calls}


def named(ctx, name):
    """(calls, device seconds) of the operations called exactly ``name`` in
    the trace (``%name.N``): the text of an operation that reads a kernel's
    result names the kernel too, and another grid's kernel begins alike."""
    t = ctx.get("trace")
    if not t or not t.get("ops"):
        return 0, 0.0
    mine = [k for k in t["ops"] if k.lstrip("%").split(".")[0] == name]
    return (sum(t["op_calls"][k] for k in mine),
            sum(t["ops"][k] for k in mine))
