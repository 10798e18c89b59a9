"""The block-sparse attention kernel's share of its roofline
(``block_sparse_attention``: a query's K/V group over the blocks it chose,
a decode row's fetched alone, a chunk's walked by key tile): the least time
the chip could take for the traced steps' calls of the window's mean shape
(the chosen positions' FLOPs, 4 x head size x the group's heads a position;
or the chosen blocks' keys and values read once, a chunk's row no more than
once; counted by the architecture) over the kernel's device time in the
traced seconds."""

from chipbench.readers import sparse_linear_steps as sl


def read(ctx):
    arch = sl._arch(ctx)
    if arch is None:
        return None
    cfg = ctx["cfg"]

    def work(kind, m):
        fetched = m["selected"]
        if kind == "mixed_":   # the chunk's row once, not once a query
            fetched = min(m["chunk_selected"],
                          arch.row_blocks(cfg, sl.context(m))) \
                + m["selected"] - m["chunk_selected"]
        return arch.block_attention_work(cfg, m["selected"], fetched)

    total, t = sl.least(ctx, work)
    if not total or t["block_sparse_attention"][1] <= 0:
        return None
    return 100.0 * total / t["block_sparse_attention"][1]
