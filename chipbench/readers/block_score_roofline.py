"""The block-scoring kernel's share of its roofline (``block_scores``: a
query's heads against its row's pooled keys, the softmax and the sum over
the group): the least time the chip could take for the traced steps' calls
of the window's mean shape (2 x head size x the group's heads a scored
(query, pooled key) pair; or the pooled keys read once, a chunk's row once;
counted by the architecture) over the kernel's device time in the traced
seconds."""

from chipbench.readers import sparse_linear_steps as sl


def read(ctx):
    arch = sl._arch(ctx)
    if arch is None:
        return None
    cfg = ctx["cfg"]

    def work(kind, m):
        fetched = m["cached"]
        if kind == "mixed_":   # the chunk's row once, not once a query
            fetched = min(m["chunk_blocks"],
                          arch.row_blocks(cfg, sl.context(m))) \
                + m["cached"] - m["chunk_blocks"]
        return arch.block_score_work(cfg, m["cached"], fetched)

    total, t = sl.least(ctx, work)
    if not total or t["block_scores"][1] <= 0:
        return None
    return 100.0 * total / t["block_scores"][1]
