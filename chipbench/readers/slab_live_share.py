"""Useful over attempted KV positions of the decode step: the live rows'
offsets over ``max_slots`` x slab length, both summed at every assembly."""

from chipbench.readers import engine_window as ew


def read(ctx):
    pair = ew.engines(ctx)
    if pair is None:
        return None
    b, a = pair
    return ew.share(a["live_positions"] - b["live_positions"],
                    a["slab_positions"] - b["slab_positions"])
