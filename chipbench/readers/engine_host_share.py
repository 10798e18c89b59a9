"""Of the time the engine had work, the share the host and not the chip set
the pace: gate, assemble, step dispatch, emit, disassemble."""

from chipbench.readers import engine_window as ew


def read(ctx):
    d = ew.phases(ctx)
    return d and ew.share(sum(d[k] for k in ew.HOST), ew.work(d))
