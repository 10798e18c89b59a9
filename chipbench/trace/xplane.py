"""From a profiler trace (``.xplane.pb``) to busy time, time by program and
kernel, and idle gaps.

Two stages, so that the arithmetic can be tested on a recorded trace without
a chip: ``events_of`` reads the device planes of an xplane file into plain
rows, and ``reduce_events`` does the rest. Busy time is the union of the
intervals in which an operation ran on a device (its "XLA Ops" line),
averaged over the device planes. A gap between two operations carries the
name of the program that ran next, which is all that can be said without
spans inside the program.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, Iterable, List, Tuple

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
OP_TEXT = 240   # how much of an instruction's text a reader gets to see


def events_of(path: str) -> List[Dict[str, Any]]:
    """Rows {plane, line, name, start_ns, dur_ns} of the device planes."""
    from jax.profiler import ProfileData

    rows = []
    data = ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                rows.append({"plane": plane.name, "line": line.name,
                             "name": ev.name, "start_ns": int(ev.start_ns),
                             "dur_ns": int(ev.duration_ns)})
    return rows


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def short(name: str) -> str:
    """A program's name without its argument list."""
    return name.split("(")[0].strip()


def op_name(text: str) -> str:
    """An operation's own name: the trace gives the whole instruction
    (``%fusion.1 = bf16[...] fusion(...)``)."""
    return text.split(" = ")[0].strip()


def reduce_events(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    planes = sorted({r["plane"] for r in rows})
    if not planes:
        return {"planes": 0, "busy_s": 0.0, "span_s": 0.0, "programs": {},
                "ops": {}, "op_calls": {}, "op_text": {}, "device_ops": [],
                "idle_gaps": []}
    busy, span = 0.0, 0.0
    programs: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    op_calls: Dict[str, int] = {}
    op_text: Dict[str, str] = {}
    gaps: Dict[str, float] = {}
    for plane in planes:
        mine = [r for r in rows if r["plane"] == plane]
        op_rows = [r for r in mine if r["line"] == OPS_LINE]
        mod_rows = sorted((r for r in mine if r["line"] == MODULES_LINE),
                          key=lambda r: r["start_ns"])
        merged = _union((r["start_ns"], r["start_ns"] + r["dur_ns"])
                        for r in (op_rows or mod_rows))
        busy += sum(b - a for a, b in merged) / 1e9
        if merged:
            span += (merged[-1][1] - merged[0][0]) / 1e9
        for r in mod_rows:
            k = short(r["name"])
            programs[k] = programs.get(k, 0.0) + r["dur_ns"] / 1e9
        for r in op_rows:
            k = op_name(r["name"])
            ops[k] = ops.get(k, 0.0) + r["dur_ns"] / 1e9
            op_calls[k] = op_calls.get(k, 0) + 1
            op_text.setdefault(k, r["name"][:OP_TEXT])
        starts = [r["start_ns"] for r in mod_rows]
        for (_, end), (nxt, _) in zip(merged, merged[1:]):
            # the first program that starts at (1 us of slack) or after nxt
            at = bisect.bisect_left(starts, nxt - 1000)
            label = short(mod_rows[at]["name"]) if at < len(starts) else "end"
            gaps["before " + label] = gaps.get("before " + label, 0.0) \
                + (nxt - end) / 1e9
    n = len(planes)
    top = lambda d: [[k, v / n] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"planes": n, "busy_s": busy / n, "span_s": span / n,
            "programs": {k: v / n for k, v in programs.items()},
            "ops": {k: v / n for k, v in ops.items()},
            "op_calls": {k: v / n for k, v in op_calls.items()},
            "op_text": op_text,
            "device_ops": top(programs or ops), "idle_gaps": top(gaps)}


def reduce_dir(trace_dir: str) -> Dict[str, Any]:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows = events_of(files[-1])
    keep = os.environ.get("CHIPBENCH_KEEP_TRACE_ROWS")
    if keep:  # for cutting a recorded trace by hand; not used by a run
        import json

        with open(keep, "w", encoding="utf-8") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return reduce_events(rows)
