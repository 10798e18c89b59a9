"""One run of one cell: parse, find the cell's files, drive, print.

The last line of standard output is the result object; the numbers the
output check compared, each beside its limit, are its last key and also the
last lines of standard error.
"""

from __future__ import annotations

import argparse
import ctypes
import importlib
import json
import os
import signal
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

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
    compiles nothing; and be the process that orphans below it fall to, so
    that ``stop_processes`` finds them."""
    pin_to_cpu()
    adopt_orphans()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    from ray_memory_management_tpu.utils import compile_cache

    compile_cache.adopt()


def adopt_orphans() -> None:
    """Make this process the one that orphans below it fall to (Linux's
    child subreaper), so that a worker's own children are still found
    under it, and can be waited for, once the worker is gone."""
    try:
        ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0)
    except (OSError, AttributeError):  # not Linux: ``below`` sees less
        pass


def below(pid: int) -> List[Tuple[int, str]]:
    """(pid, command) of every process under ``pid`` by ``/proc``'s parent
    links, zombies included: one of ours is there until it is waited for."""
    parent, name = {}, {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                comm, rest = f.read().rsplit(")", 1)
        except OSError:  # gone between the listing and the read
            continue
        parent[int(entry)] = int(rest.split()[1])
        name[int(entry)] = comm.split("(", 1)[1]
    found, edge = [], [pid]
    while edge:
        edge = [p for p, pp in parent.items() if pp in edge]
        found += [(p, name[p]) for p in edge]
    return found


def stop_processes(grace_s: float = 20.0, kill_s: float = 30.0,
                   give_up_s: float = 45.0) -> List[Tuple[int, str, str]]:
    """Leave no process behind: wait for everything below this one to end,
    tell what outstays ``grace_s`` to stop, kill what outstays ``kill_s``.
    ``rmt.shutdown`` tells its workers to go and waits a second for them;
    the worker that holds the chip takes 4.5 to 6 s more to be gone.
    Returns (pid, command, what it took) of those not gone at once."""
    me, t0, seen = os.getpid(), time.time(), {}
    while True:
        left = below(me)
        for pid, _ in left:
            try:  # ours to reap, or someone else's child
                os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                pass
        left = below(me)
        waited = time.time() - t0
        if not left or waited > give_up_s:
            return [(p, c, how) for p, (c, how) in seen.items()]
        for pid, comm in left:
            how = ("waited" if waited <= grace_s
                   else "SIGTERM" if waited <= kill_s else "SIGKILL")
            if how != seen.get(pid, (comm, "waited"))[1]:
                try:
                    os.kill(pid, signal.SIGTERM if how == "SIGTERM"
                            else signal.SIGKILL)
                except ProcessLookupError:
                    pass
            seen[pid] = (comm, how)
        time.sleep(0.05)


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
                              for k in ("dispatch_s", "start_s",
                                        "collect_s", "reduce_s")}
    if result.get("check_s") is not None:
        # what the output check cost this run (the driver ignores it)
        line["check_s"] = result["check_s"]
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
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), started)
    finally:  # on every way out
        for pid, comm, how in stop_processes():
            print(f"process {pid} ({comm}) outlasted shutdown: {how}",
                  file=sys.stderr)
    line = result_line(args.workload, bool(args.trace), result)
    for name, pair in line["compared"].items():
        print(f"compared {name}: {json.dumps(pair)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
