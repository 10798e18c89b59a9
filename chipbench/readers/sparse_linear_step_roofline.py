"""The decode token-step's share of its roofline, for the model with
Lightning and block-sparse layers: the least time the chip could take for a
token-step of the window's mean shape (every weight once, each live row's
Lightning state read and written, the pooled keys the selection scores and
the chosen blocks' keys and values; whichever of FLOPs and bytes takes
longer at the chip's peaks; counted by the architecture, whatever implements
it) over the decode program's device time a token-step in the traced
seconds."""

from chipbench.readers import sparse_linear_steps as sl


def read(ctx):
    arch = sl._arch(ctx)
    if arch is None:
        return None

    def work(kind, m):
        return arch.step_work(ctx["cfg"], m["rows"], m["selected"],
                              m["cached"])

    total, t = sl.least(ctx, work, kinds=("",))
    if not total or t["decode_s"] <= 0:
        return None
    return 100.0 * total / t["decode_s"]
