"""The sparse attention kernel's share of its roofline
(``sparse_latent_attention``: absorbed latent attention over the positions a
learned indexer selected): the least time the chip could take for the traced
steps' calls of the window's mean shape (the selected pairs' FLOPs in
absorbed form, 2 x heads x (576 + 512) a pair; or the cached vectors fetched
once for all heads: a decode row's selected rows, a chunk's row once;
counted by the architecture) over the kernel's device time in the traced
seconds."""

from chipbench import architectures
from chipbench.readers import sparse_steps as ss


def read(ctx):
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    if not hasattr(arch, "sparse_attention_work"):
        return None
    layers = arch.layer_counts(cfg)[0]

    def work(kind, m):
        pairs = m["selected"] / layers
        fetched = pairs                # a decode row: its own selected rows
        if kind == "mixed_":           # the chunk's row once, not once a query
            fetched = min(pairs, ss.context(m)
                          + (m["tokens"] - m["chunk"]) * cfg["index_topk"])
        f, b = arch.sparse_attention_work(cfg, pairs, fetched)
        return layers * f, layers * b

    total, t = ss.least(ctx, work)
    if not total or t["sparse_latent_attention"][1] <= 0:
        return None
    return 100.0 * total / t["sparse_latent_attention"][1]
