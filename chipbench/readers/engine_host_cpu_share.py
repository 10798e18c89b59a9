"""Of the host phases' wall time, the share the engine thread was on a
CPU; the rest it waited, for the interpreter lock or another lock."""

from chipbench.readers import engine_window as ew


def read(ctx):
    wall, cpu = ew.phases(ctx), ew.phases(ctx, "phase_cpu_s")
    return wall and ew.share(sum(cpu[k] for k in ew.HOST),
                             sum(wall[k] for k in ew.HOST))
