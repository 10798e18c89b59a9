"""Submit to first token inside the engine (queue wait plus prefill), 90th
percentile. Not the caller's time to first token: the answer does not
stream, and the way to the replica is not in it."""

from chipbench.readers import engine_window as ew


def read(ctx):
    return ew.recent_p90_ms(
        ctx, lambda queue_wait_s, prefill_s: queue_wait_s + prefill_s)
