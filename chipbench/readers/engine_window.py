"""What the serve engine counted of itself inside the window: ``after`` -
``before`` of ``stats()["engine"]`` (``ContinuousBatcher.engine_stats``),
which is cumulative since the engine started. Shared by the
``serve.engine_*``, ``serve.slab_live_share`` and ``serve.*_p90_ms``
readers; a program without the engine's own counts reads ``None``."""

from chipbench.drivers.serve import percentile

# the phases in which there is work and the chip waits for the host
HOST = ("gate", "assemble", "step_dispatch", "emit", "disassemble")


def engines(ctx):
    """(before, after) engine snapshots, or None where ``stats()`` has no
    ``engine`` (barrier mode, or a program older than the counts)."""
    b, a = ctx["before"].get("engine"), ctx["after"].get("engine")
    return (b, a) if a and b else None


def phases(ctx, key="phase_s"):
    """Seconds by phase inside the window (``phase_cpu_s``: of the engine
    thread on a CPU), or None."""
    pair = engines(ctx)
    if pair is None:
        return None
    b, a = pair
    return {k: v - b[key].get(k, 0.0) for k, v in a[key].items()}


def work(d):
    """Seconds in which the engine had something to do."""
    return sum(d.values()) - d["idle_wait"]


def share(part, whole):
    """``part`` in percent of ``whole``; None where ``whole`` is nothing."""
    return 100.0 * part / whole if whole > 0 else None


def recent_p90_ms(ctx, value):
    """Nearest-rank 90th percentile, in ms, of ``value(queue_wait_s,
    prefill_s)`` over the requests admitted inside the window: the newest
    ``admitted`` difference rows of ``after``'s ring, as many as it holds."""
    pair = engines(ctx)
    if pair is None:
        return None
    b, a = pair
    n = min(a["admitted"] - b["admitted"], len(a["recent"]))
    if n <= 0:
        return None
    return 1e3 * percentile([value(q, p) for q, p in a["recent"][-n:]], 90)
