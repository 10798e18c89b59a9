"""Of the window's token-steps, the share that carried a chunk of a prompt:
the change of the engine's ``mixed_steps`` over that of ``stats()``'s
``batches`` (every token-step the engine ran). Where prompts ride the decode
step the loop never stands for a prefill; ``None`` where the engine does not
count it (one that prefills whole prompts in programs of their own) or no
step ran."""

from chipbench.readers import engine_window as ew
from chipbench.readers.prefill_ms_per_kpos import counted


def read(ctx):
    mixed = counted(ctx, "mixed_steps")
    steps = ctx["after"].get("batches", 0) - ctx["before"].get("batches", 0)
    if mixed is None or steps <= 0:
        return None
    return ew.share(mixed, steps)
