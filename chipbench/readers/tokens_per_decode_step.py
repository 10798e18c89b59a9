"""Rows that yield a token per decode step: the change of
``generated_tokens`` over the change of ``batches`` of ``stats()``."""


def read(ctx):
    a, b = ctx["after"], ctx["before"]
    steps = a.get("batches", 0) - b.get("batches", 0)
    if steps <= 0:
        return None
    return (a["generated_tokens"] - b["generated_tokens"]) / steps
