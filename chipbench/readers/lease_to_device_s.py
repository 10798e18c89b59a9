"""Seconds from ``serve.run`` / ``fit()`` to the leased process's first
sight of its device (benchmark clock to the chip holder's clock, one host)."""


def read(ctx):
    return ctx["clocks"].get("lease_to_device_s")
