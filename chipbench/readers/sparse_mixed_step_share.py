"""Of the window's token-steps, the share that carried a chunk of a prompt,
for the model that attends under a learned selection: every prompt rides the
steps in chunks (there is no prefill program), so this is the share of the
steps in which a decode row waits a chunk's time for its token."""

from chipbench.readers import engine_window as ew
from chipbench.readers import sparse_steps as ss


def read(ctx):
    w = ss.window(ctx)
    if w is None:
        return None
    steps = {k: (m or {}).get("steps", 0) for k, m in w.items()}
    return ew.share(steps["mixed_"], steps["mixed_"] + steps[""])
