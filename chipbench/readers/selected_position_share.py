"""Of the positions the window's queries had cached (a chunk's real positions
and the live decode rows, every layer), the share they attended: what the
learned selection left. 100 would mean the traffic never reached the
selection (no row longer than ``index_topk``)."""

from chipbench.readers import engine_window as ew
from chipbench.readers import sparse_steps as ss


def read(ctx):
    w = ss.window(ctx)
    if w is None:
        return None
    kinds = [m for m in w.values() if m is not None]
    return ew.share(sum(m["selected"] * m["steps"] for m in kinds),
                    sum(m["cached"] * m["steps"] for m in kinds))
