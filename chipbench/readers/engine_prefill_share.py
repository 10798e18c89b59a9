"""Of the time the engine had work, the share the decode loop stood still
for prefills."""

from chipbench.readers import engine_window as ew


def read(ctx):
    d = ew.phases(ctx)
    return d and ew.share(d["prefill"], ew.work(d))
