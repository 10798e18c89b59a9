"""What the steps of the model with Lightning and block-sparse layers
(``models/sparse_linear.py``: one walk for a chunk of a prompt and the decode
rows, ``mixed_step``, and the same walk without a chunk, ``paged_decode``)
counted of themselves inside the window, and what the trace holds of their
kernels. Shared by the five readers of the cell.

Two clocks are joined as in ``ssm_steps.py``: means a step come from the
window's counters (``stats()["engine"]``: the mixed step's under ``mixed_*``
names, the decode step's under plain ones), seconds and calls from the traced
seconds at its end. How many steps of each kind were traced is read from the
state update's calls, once a lightning layer under a name of each kind's own
(``lightning_decode_update``, ``lightning_mixed_update``). A program without
the counts (the parent) reads ``None``; a share over 100% is a fault of the
count and is never clipped."""

from chipbench import architectures
from chipbench.readers import engine_window as ew
from chipbench.readers.pattern_mixed_steps import named

KINDS = ("mixed_", "")      # the counters' prefix: a mixed step, a decode step
UPDATE = {"mixed_": "lightning_mixed_update", "": "lightning_decode_update"}
KERNELS = ("block_scores", "block_select", "block_sparse_attention")
PROGRAM = "jit_paged_step_fn"


def _arch(ctx):
    arch = architectures.of(ctx["cfg"])
    return arch if hasattr(arch, "block_attention_work") else None


def window(ctx):
    """{prefix: {steps, rows, selected, cached, chunk, chunk_cached,
    chunk_selected, chunk_blocks}} as means a step of that kind over the
    window (``rows`` the live decode rows a lightning layer moved;
    ``selected``, ``cached`` blocks summed over the sparse layers and K/V
    heads, ``chunk_selected`` and ``chunk_blocks`` the chunk's share of
    them; ``chunk`` the chunk's real positions, ``chunk_cached`` what they
    had cached), or None."""
    pair = ew.engines(ctx)
    if _arch(ctx) is None or pair is None \
            or "mixed_blocks_cached" not in pair[1]:
        return None
    b, a = pair
    d = lambda k: a.get(k, 0) - b.get(k, 0)  # noqa: E731
    mixed = d("mixed_steps")
    steps = {"mixed_": mixed, "": ctx["after"].get("batches", 0)
             - ctx["before"].get("batches", 0) - mixed}
    out = {}
    for kind, n in steps.items():
        calls = d(kind + "lin_layer_steps")
        if n <= 0 or calls <= 0:
            out[kind] = None
            continue
        out[kind] = {
            "steps": n, "rows": d(kind + "lin_rows_stepped") / calls,
            "selected": d(kind + "blocks_selected") / n,
            "cached": d(kind + "blocks_cached") / n,
            "chunk": d(kind + "chunk_positions") / n,
            "chunk_cached": d(kind + "chunk_positions_cached") / n,
            "chunk_selected": d(kind + "chunk_blocks_selected") / n,
            "chunk_blocks": d(kind + "chunk_blocks_cached") / n}
    return out


def traced(ctx):
    """{"mixed_": mixed steps traced, "": decode token-steps traced,
    "decode_s": the decode program's device seconds, and (calls, seconds)
    of each kernel by its name}, or None where the trace has none of it."""
    t, arch = ctx.get("trace"), _arch(ctx)
    if arch is None or not t or not t.get("ops"):
        return None
    lin = arch.layer_counts(ctx["cfg"])[2]
    out = {name: named(ctx, name) for name in KERNELS + tuple(UPDATE.values())}
    for kind, name in UPDATE.items():
        out[kind] = out[name][0] / lin
    if not out[""] and not out["mixed_"]:
        return None
    out["decode_s"] = t.get("programs", {}).get(PROGRAM, 0.0)
    return out


def context(m):
    """Cached positions a chunk's row holds at the chunk's end, from a mixed
    step's means: its real positions' mean context and half the chunk."""
    return m["chunk_cached"] / max(m["chunk"], 1.0) + m["chunk"] / 2.0


def least(ctx, work, kinds=KINDS):
    """Seconds the chip would need at least for the traced steps:
    ``work(kind, means) -> (FLOPs, bytes)`` of one step of a kind, at the
    chip's peaks, times the steps of that kind the trace holds. (None, t)
    where the window or the trace has nothing."""
    from chipbench import flops

    w, t = window(ctx), traced(ctx)
    if w is None or t is None:
        return None, t
    total = 0.0
    for kind in kinds:
        if t[kind] > 0 and w[kind] is not None:
            f, b = work(kind, w[kind])
            total += t[kind] * flops.roofline_seconds(
                f, b, ctx["device"]["kind"])[0]
    return total, t
