"""Median over report intervals of interval seconds per step."""

import statistics


def read(ctx):
    xs = ctx["clocks"].get("step_s_intervals")
    return statistics.median(xs) * 1e3 if xs else None
