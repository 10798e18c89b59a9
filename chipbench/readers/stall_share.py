"""Of the time the engine had work, the share that stalled iterations took
over a plain one's: the sum, over the rows of ``stats()["engine"]["stalls"]``
whose ``t_end`` lies inside the window (the chip holder's own clock, as the
snapshots' ``time``), of ``wall_s - median_s``. 0 is a reading (no iteration
passed 4 x the median of the 32 before it); ``None`` only where the engine
keeps no ``stalls``."""

from chipbench.readers import engine_window as ew


def read(ctx):
    pair = ew.engines(ctx)
    if pair is None or "stalls" not in pair[1]:
        return None
    t0, t1 = ctx["before"]["time"], ctx["after"]["time"]
    lost = sum(r["wall_s"] - r["median_s"] for r in pair[1]["stalls"]
               if t0 < r["t_end"] <= t1)
    return ew.share(lost, ew.work(ew.phases(ctx)))
