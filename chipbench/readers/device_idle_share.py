"""1 - (union of device-operation intervals) / traced window."""


def read(ctx):
    t = ctx.get("trace")
    if not t or not t.get("window_s") or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
