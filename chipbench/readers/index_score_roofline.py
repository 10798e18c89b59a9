"""The index scoring kernel's share of its roofline (``index_scores``: a
chunk's queries against the row's cached index keys): the least time the chip
could take for a call of the window's mean shape (2 x index heads x index
head size FLOPs a scored pair; or the keys read once and the float32 scores
written; counted by the architecture) over the kernel's device time a call in
the traced seconds. A decode row's one query is scored outside the kernel and
is in neither count."""

from chipbench import architectures, flops
from chipbench.readers import sparse_steps as ss


def read(ctx):
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    if not hasattr(arch, "index_score_work"):
        return None
    w, t = ss.window(ctx), ss.traced(ctx)
    if w is None or t is None or w["mixed_"] is None:
        return None
    calls, spent = t["index_scores"]
    if not calls or spent <= 0:
        return None
    m = w["mixed_"]
    f, b = arch.index_score_work(cfg, m["chunk_cached"], ss.context(m))
    return 100.0 * calls * flops.roofline_seconds(
        f, b, ctx["device"]["kind"])[0] / spent
