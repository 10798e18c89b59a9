"""How late the load generator sent: send instant minus due instant."""

import math


def read(ctx):
    xs = sorted(ctx["clocks"].get("late_ms") or [])
    if not xs:
        return None
    return xs[min(len(xs) - 1, math.ceil(0.99 * len(xs)) - 1)]
