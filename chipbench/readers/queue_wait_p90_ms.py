"""Submit to admission by the gate, inside the engine, 90th percentile."""

from chipbench.readers import engine_window as ew


def read(ctx):
    return ew.recent_p90_ms(ctx, lambda queue_wait_s, prefill_s: queue_wait_s)
