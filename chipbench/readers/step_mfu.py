"""The whole step's share of the chips' bf16 peak: model FLOPs of the work
finished in the window (no recomputation counted) over window seconds."""

from chipbench import flops


def read(ctx):
    c, dev = ctx["clocks"], ctx["device"]
    if not c.get("model_flops") or not c.get("window_s"):
        return None
    if dev["platform"] != "tpu":  # a rehearsal: no share of a chip's peak
        return None
    peak = flops.peak(dev["kind"])["bf16_flops_per_s"] * dev["count"]
    return 100.0 * c["model_flops"] / c["window_s"] / peak
