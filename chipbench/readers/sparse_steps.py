"""What the steps of a model that attends under a learned selection
(``models/latent_sparse_moe.py``: one walk for a chunk of a prompt and the
decode rows, ``mixed_step``, and the same walk without a chunk,
``paged_decode``) counted of themselves inside the window, and what the trace
holds of their three kernels. Shared by the six readers of the cell.

Two clocks are joined as in ``ssm_steps.py``: means a step come from the
window's counters (``stats()["engine"]``: the mixed step's under ``mixed_*``
names, the decode step's under plain ones), seconds and calls from the traced
seconds at its end. How many steps of each kind were traced is read from the
kernels' calls: the scoring kernel runs once a ``full`` layer in a mixed step
and never in a decode step (a decode row's one query is scored by a few small
matmuls); the attention kernel runs twice a layer in a mixed step (the chunk,
the rows) and once in a decode step. A program without the counts (the
parent) reads ``None``; a share over 100% is a fault of the count and is
never clipped."""

from chipbench import architectures, flops
from chipbench.readers import engine_window as ew
from chipbench.readers.pattern_mixed_steps import named

PROGRAMS = ("jit_mixed_step", "jit_paged_step_fn")
KINDS = ("mixed_", "")      # the counters' prefix: a mixed step, a decode step


def window(ctx):
    """{prefix: {steps, tokens, chunk, chunk_cached, cached, selected,
    scored, experts_touched}} as means a step of that kind over the window
    (``cached``, ``selected``, ``scored`` summed over the layers; ``chunk``
    the chunk's real positions, ``chunk_cached`` what they had cached in one
    layer), or None."""
    pair = ew.engines(ctx)
    if pair is None or "mixed_positions_cached" not in pair[1]:
        return None
    b, a = pair
    d = lambda k: a.get(k, 0) - b.get(k, 0)  # noqa: E731
    cfg = ctx["cfg"]
    _, _, sparse = architectures.of(cfg).layer_counts(cfg)
    mixed = d("mixed_steps")
    steps = {"mixed_": mixed, "": ctx["after"].get("batches", 0)
             - ctx["before"].get("batches", 0) - mixed}
    out = {}
    for kind, n in steps.items():
        if n <= 0:
            out[kind] = None
            continue
        layer_steps = d(kind + "expert_layer_steps")
        out[kind] = {
            "steps": n,
            "tokens": d(kind + "expert_assignments")
            / (sparse * cfg["num_experts_per_tok"]) / n,
            "chunk": d(kind + "chunk_positions") / n,
            "chunk_cached": d(kind + "chunk_positions_cached") / n,
            "cached": d(kind + "positions_cached") / n,
            "selected": d(kind + "positions_selected") / n,
            "scored": d(kind + "positions_scored") / n,
            "experts_touched": d(kind + "experts_touched") / layer_steps
            if layer_steps > 0 else 0.0}
    return out


def traced(ctx):
    """{"mixed_": mixed steps traced, "": decode token-steps traced,
    "programs_s": the two step programs' device seconds, and (calls, seconds)
    of each kernel by its name}, or None where the trace has no such kernel."""
    t, cfg = ctx.get("trace"), ctx["cfg"]
    if not t or not t.get("ops"):
        return None
    layers, full, _ = architectures.of(cfg).layer_counts(cfg)
    out = {name: named(ctx, name) for name in (
        "index_scores", "index_select", "sparse_latent_attention")}
    if not out["sparse_latent_attention"][0]:
        return None
    out["mixed_"] = out["index_scores"][0] / full
    out[""] = max(0.0, out["sparse_latent_attention"][0] / layers
                  - 2 * out["mixed_"])
    out["programs_s"] = sum(t.get("programs", {}).get(p, 0.0)
                            for p in PROGRAMS)
    return out


def least(ctx, work):
    """Seconds the chip would need at least for the traced steps:
    ``work(kind, means) -> (FLOPs, bytes)`` of one step of a kind, at the
    chip's peaks, times the steps of that kind the trace holds. None where
    the window or the trace has nothing."""
    w, t = window(ctx), traced(ctx)
    if w is None or t is None:
        return None, t
    total = 0.0
    for kind in KINDS:
        if t[kind] > 0 and w[kind] is not None:
            f, b = work(kind, w[kind])
            total += t[kind] * flops.roofline_seconds(
                f, b, ctx["device"]["kind"])[0]
    return total, t


def context(m):
    """Cached positions a chunk's row holds at the chunk's end, from a mixed
    step's means: its real positions' mean context and half the chunk."""
    return m["chunk_cached"] / max(m["chunk"], 1.0) + m["chunk"] / 2.0
