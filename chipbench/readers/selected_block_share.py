"""Of the blocks that held a position up to each query of the window (a
chunk's real positions and the live decode rows, every sparse layer and K/V
head), the share its K/V group attended: what the block selection left. 100
would mean the selection never engaged (every query before ``dense_len``)."""

from chipbench.readers import engine_window as ew
from chipbench.readers import sparse_linear_steps as sl


def read(ctx):
    w = sl.window(ctx)
    if w is None:
        return None
    kinds = [m for m in w.values() if m is not None]
    return ew.share(sum(m["selected"] * m["steps"] for m in kinds),
                    sum(m["cached"] * m["steps"] for m in kinds))
