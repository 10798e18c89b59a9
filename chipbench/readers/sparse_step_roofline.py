"""The steps' share of their roofline, for a model that attends under a
learned selection: the least time the chip could take for the traced steps,
each of the window's mean shape for its kind (a mixed step: a chunk's real
positions and the live rows; a decode token-step: the live rows): the weights
used once with the *held* experts touched, the selected pairs' and the scored
pairs' FLOPs, the cached vectors and index keys the selection needs;
whichever of FLOPs and bytes takes longer at the chip's peaks; counted by the
architecture, whatever implements it) over the two step programs' device time
in the traced seconds."""

from chipbench import architectures
from chipbench.readers import sparse_steps as ss


def read(ctx):
    cfg = ctx["cfg"]
    arch = architectures.of(cfg)
    if not hasattr(arch, "step_work"):
        return None
    layers, full, _ = arch.layer_counts(cfg)
    rows = ctx["mix"]["engine"]["max_batch_size"]

    def work(kind, m):
        if kind == "mixed_":   # the chunk's row once a layer; the rows' own
            seen = ss.context(m)
            fetched, keys, logit_rows = layers * seen, full * seen, rows + 1
        else:
            fetched, keys, logit_rows = m["selected"], m["scored"], rows
        return arch.step_work(cfg, m["tokens"], min(logit_rows, m["tokens"]),
                              m["selected"], m["scored"], fetched, keys,
                              m["experts_touched"])

    total, t = ss.least(ctx, work)
    if not total or t["programs_s"] <= 0:
        return None
    return 100.0 * total / t["programs_s"]
