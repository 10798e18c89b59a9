"""How unevenly the window's decode tokens fell on the routed experts: the
busiest expert's assignments over the mean expert's (1 is even), from the
change of ``expert_tokens`` of ``stats()["engine"]``."""

from chipbench.readers import engine_window as ew


def read(ctx):
    pair = ew.engines(ctx)
    if pair is None or not pair[1].get("expert_tokens"):
        return None
    b, a = pair
    before = b.get("expert_tokens") or [0] * len(a["expert_tokens"])
    load = [x - y for x, y in zip(a["expert_tokens"], before)]
    return max(load) * len(load) / sum(load) if sum(load) > 0 else None
