"""The host's part of an admission, in ms: the ``prefill`` phase's seconds
in which no program was in flight (``starved_s["prefill"]``) over the
requests admitted in the window. Where prompts are prefilled whole that is
an admission up to its prefill program's call returning (clip, bucket, the
split, the uploads, the call), and ``phase_s["prefill"]`` less it is the wait
for the program; where they ride in chunks, ``_begin_prefill`` and the
first chunk of each iteration. ``None`` without an admission."""

from chipbench.readers import engine_window as ew
from chipbench.readers.device_starved_share import starved


def read(ctx):
    d = starved(ctx)
    if d is None:
        return None
    b, a = ew.engines(ctx)
    n = a["admitted"] - b["admitted"]
    return 1e3 * d["prefill"] / n if n > 0 else None
