"""Find the highest rate a serve cell sustains: one deployment, a window at
each rate, one line each. Not part of a run; a `benchmark` PR uses it to set
``rate_per_s`` in the mix's file (four fifths of the knee).

    python -m chipbench.sweep --workload chat-online --seconds 40 \\
        --rates 1,2,3,4,5,6 --seed 1
"""

from __future__ import annotations

import argparse
import json
import sys

from chipbench import harness
from chipbench.drivers import serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    harness.prepare_process()
    cell, cfg, mix = harness.load_cell(args.workload)
    served = serve.Served(cell, cfg, mix, seed=args.seed,
                          expect_platform="tpu")
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = served.window(args.seed + i, args.seconds,
                              mix=dict(mix, rate_per_s=rate))
            s = serve.summarize(w, cfg, mix)
            lat = s["latency_ms_per_token"]
            print(json.dumps({
                "rate_per_s": rate, "sent": len(w["done"]),
                "failed": s["failed"],
                "finished_in_window": s["requests_in_window"],
                "tokens_per_s": s["tokens"] / w["window_s"],
                "norm_latency_p50_ms": serve.percentile(lat, 50),
                "norm_latency_p90_ms": serve.percentile(lat, 90),
                # a queue that grows through the window drains long after
                "drain_s": s["last_finished"] - w["window_s"],
                "tokens_per_decode_step":
                    (w["after"]["generated_tokens"]
                     - w["before"]["generated_tokens"])
                    / max(1, w["after"]["batches"] - w["before"]["batches"]),
                "compiles_in_window": w["after"]["compile"]["programs"]
                    - w["before"]["compile"]["programs"],
            }), flush=True)
    finally:
        served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
