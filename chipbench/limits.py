"""Read what the output check's limits are set from: the program's numbers on
many seeds and the control's (the reference one precision down) beside them,
in one process per cell. Not part of a run; a `benchmark` PR uses it.

    python -m chipbench.limits --workload chat-online --seeds 12 --seconds 15
    python -m chipbench.limits --workload longprompt-batch --seeds 12 \\
        --seconds 15 --requests 48
    python -m chipbench.limits --workload pretrain-1chip --seeds 12
    python -m chipbench.limits --workload pretrain-1chip --seeds 3 \\
        --fault half_batch
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from chipbench import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.limits")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--requests", type=int, default=None,
                    help="serve: compare this many answers a seed, and "
                    "print one row a sample, to choose check.requests from")
    args = ap.parse_args(argv)
    harness.prepare_process()
    cell, cfg, mix = harness.load_cell(args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    driver = harness.driver_for(mix)
    if mix["kind"] == "train":
        r = driver.run(cell, cfg, mix, seed=seeds[0], seconds=1.0,
                       trace=False, started=time.time(), control="bf16",
                       fault=args.fault, more_seeds=seeds[1:])
        for row in r["comparisons"]["readings"]:
            print(json.dumps(row), flush=True)
        return 0
    if args.requests:
        mix = dict(mix, check=dict(mix["check"], requests=args.requests))
    served = driver.Served(cell, cfg, mix, seed=seeds[0],
                           expect_platform="tpu", fault=args.fault)
    try:
        for i, seed in enumerate(seeds):
            if i:
                served.call("reseed", seed)
            w = served.window(seed, args.seconds)
            s = driver.summarize(w, cfg, mix)
            _, compared = served.check(seed, w["done"], control="fp8",
                                       detail=bool(args.requests))
            # how far greedy decoding under random weights has fallen into
            # repeating itself: where it has, margins are wide and a lower
            # precision agrees with the reference at the served positions
            pairs = [(a, b) for r in s["ok"]
                     for a, b in zip(r["served"], r["served"][1:])]
            print(json.dumps(dict(
                compared, seed=seed, sent=len(w["done"]),
                failed=s["failed"], wrong_length=s["wrong_length"],
                served_repeat_share=sum(a == b for a, b in pairs)
                / max(1, len(pairs)))), flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
