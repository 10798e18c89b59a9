"""Of the step programs' device time in the traced seconds (the mixed step's
and the decode step's), the share inside the selection kernel
(``index_select``: the top ``index_topk`` scores a query)."""

from chipbench.readers import engine_window as ew
from chipbench.readers import sparse_steps as ss


def read(ctx):
    t = ss.traced(ctx)
    if t is None or not t["index_select"][0]:
        return None
    return ew.share(t["index_select"][1], t["programs_s"])
