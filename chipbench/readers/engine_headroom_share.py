"""Of the engine thread's time, the share it had nothing to do: no request
queued, no slot live. At a fixed offered rate a faster engine has more."""

from chipbench.readers import engine_window as ew


def read(ctx):
    d = ew.phases(ctx)
    return d and ew.share(d["idle_wait"], sum(d.values()))
