"""What a thousand prompt positions cost the engine loop: the window's
seconds in the ``prefill`` phase over the positions its prefill programs
computed (the sum of the admissions' bucket lengths), in ms."""

from chipbench.readers import engine_window as ew


def counted(ctx, name):
    """How far the engine's count ``name`` grew inside the window; None
    where the program does not count it (one older than the count)."""
    pair = ew.engines(ctx)
    if pair is None or name not in pair[0] or name not in pair[1]:
        return None
    return pair[1][name] - pair[0][name]


def read(ctx):
    positions = counted(ctx, "prefill_positions")
    if not positions:
        return None
    return 1e6 * ew.phases(ctx)["prefill"] / positions
