"""One run of one cell: parse, find the cell's files, drive, print.

The last line of standard output is the result object; the numbers the
output check compared, each beside its limit, are its last key and also the
last lines of standard error.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, Optional

from chipbench import manifest


def pin_to_cpu() -> None:
    """Keep this process off the chip, through jax's config and not the
    environment: the leased worker inherits the environment and must find
    the TPU (or fail where ``JAX_PLATFORMS`` hides it)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def prepare_process() -> None:
    """What every entry point does before it touches the runtime: stay off
    the chip, and let the leased process cache the small programs too (the
    engine's pad / slice / concatenate glue), so that a run after the first
    compiles nothing."""
    pin_to_cpu()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from ray_memory_management_tpu.utils import compile_cache

    compile_cache.adopt()


def load_cell(workload: str):
    """(cell, configuration, mix) of a workload's name."""
    cell = manifest.cell(workload)
    return (cell, manifest.config(cell["config"]),
            manifest.traffic(cell["traffic"]))


def driver_for(mix: Dict[str, Any]):
    """``chipbench/drivers/<kind>.py`` by the first word of the mix's kind
    (``serve-open`` and ``serve-closed`` share the serve driver)."""
    return importlib.import_module(
        "chipbench.drivers." + mix["kind"].split("-")[0])


def result_line(workload: str, trace: bool,
                result: Dict[str, Any]) -> Dict[str, Any]:
    """The contract's result object of a driver's result."""
    ctx = result["context"]
    if trace:
        metrics = {}
        files = manifest.metric_files()
        for m in manifest.metrics_for(workload, "per_layer"):
            value = manifest.reader(files[m["name"]]["reader"])(ctx)
            if value is not None:  # nothing to read: left out, never 0
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": result["values"][m["name"]],
                               "unit": m["unit"]}
                   for m in manifest.metrics_for(workload, "end_to_end")
                   if m["name"] in result["values"]}
    line = {"correct": bool(result["correct"]),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": dict(result["device"])}
    reduced = ctx.get("trace")
    if trace and reduced:
        line["device"]["busy_s"] = reduced["busy_s"]
        line["device"]["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        # what the instrumentation cost this run (the driver ignores it)
        line["trace_cost"] = {k: reduced.get(k)
                              for k in ("collect_s", "reduce_s")}
    line["compared"] = result["comparisons"]
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             started: float, **driver_options) -> Dict[str, Any]:
    cell, cfg, mix = load_cell(workload)
    return driver_for(mix).run(cell, cfg, mix, seed=seed, seconds=seconds,
                               trace=trace, started=started,
                               **driver_options)


def main(argv=None, started: Optional[float] = None) -> int:
    started = started or time.time()
    parser = argparse.ArgumentParser(prog="python -m chipbench")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    prepare_process()
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), started)
    line = result_line(args.workload, bool(args.trace), result)
    for name, pair in line["compared"].items():
        print(f"compared {name}: {json.dumps(pair)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
