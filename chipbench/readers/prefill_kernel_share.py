"""Of the prompt positions the window's prefill programs computed, the
share computed by a program that attends in the flash kernel (the model's
``prefill_takes_kernel``, asked once a bucket by the engine)."""

from chipbench.readers import engine_window as ew
from chipbench.readers.prefill_ms_per_kpos import counted


def read(ctx):
    positions = counted(ctx, "prefill_positions")
    kernel = counted(ctx, "prefill_kernel_positions")
    if not positions or kernel is None:
        return None
    return ew.share(kernel, positions)
