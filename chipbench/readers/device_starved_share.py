"""Of the time the engine had work, the share in which nothing it had
dispatched was still unread (``stats()["engine"]["starved_s"]``, summed over
the phases): the chip had no work from the engine thread. A lower bound of
the device's idle share, on the host's clock, over the whole window and
with no profiler; ``device_idle_share.*`` is the device's own reading over
the traced seconds. An engine that does not keep it reads ``None``."""

from chipbench.readers import engine_window as ew


def starved(ctx):
    """Seconds without a program in flight inside the window, by phase, or
    None where either snapshot's engine has no ``starved_s``."""
    pair = ew.engines(ctx)
    if pair is None or not all("starved_s" in e for e in pair):
        return None
    return ew.phases(ctx, "starved_s")


def read(ctx):
    d = starved(ctx)
    return d and ew.share(sum(d.values()), ew.work(ew.phases(ctx)))
