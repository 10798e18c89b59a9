"""Make a small recorded trace for the reduction's test. Not part of a run.

A run with ``CHIPBENCH_KEEP_TRACE_ROWS=<file>`` in its environment dumps the
rows of its trace (hundreds of MB for a serve cell). This cuts them to the
first SECONDS, keeps the outermost operations only (a nested operation lies
inside its parent's interval, so the union is the same), shortens the names,
and writes ``<out>.jsonl`` with ``<out>.expect.json`` beside it: busy time by
an event sweep and time by program, computed here with none of
``xplane.py``'s code.

    python3 -m chipbench.trace.cut rows.jsonl chipbench/trace/recorded/x 1.5
"""

import json
import sys

import numpy as np

NAME = 160


def cut(rows, seconds: float):
    t0 = min(r["start_ns"] for r in rows)
    rows = [r for r in rows
            if r["start_ns"] + r["dur_ns"] - t0 <= seconds * 1e9]
    out = []
    for plane in sorted({r["plane"] for r in rows}):
        for line in ("XLA Modules", "XLA Ops"):
            mine = sorted((r for r in rows
                           if r["plane"] == plane and r["line"] == line),
                          key=lambda r: (r["start_ns"], -r["dur_ns"]))
            end = -1
            for r in mine:
                if r["start_ns"] >= end:  # not inside the one before it
                    out.append(dict(r, name=r["name"][:NAME],
                                    start_ns=r["start_ns"] - t0))
                    end = r["start_ns"] + r["dur_ns"]
    return out


def expect(rows):
    planes = sorted({r["plane"] for r in rows})
    busy, programs = 0.0, {}
    for plane in planes:
        ops = [r for r in rows
               if r["plane"] == plane and r["line"] == "XLA Ops"]
        t = np.array([r["start_ns"] for r in ops]
                     + [r["start_ns"] + r["dur_ns"] for r in ops])
        d = np.array([1] * len(ops) + [-1] * len(ops))
        order = np.lexsort((-d, t))  # at one instant, starts before ends
        t, depth = t[order], np.cumsum(d[order])
        busy += float(np.sum((t[1:] - t[:-1])[depth[:-1] > 0])) / 1e9
        for r in rows:
            if r["plane"] == plane and r["line"] == "XLA Modules":
                k = r["name"].split("(")[0].strip()
                programs[k] = programs.get(k, 0.0) + r["dur_ns"] / 1e9
    n = len(planes)
    programs = {k: v / n for k, v in programs.items()}
    return {"planes": n, "busy_s": busy / n, "programs": programs,
            "top_program": max(programs, key=programs.get)}


def main(argv) -> int:
    src, dst, seconds = argv[0], argv[1], float(argv[2])
    with open(src, encoding="utf-8") as f:
        rows = cut([json.loads(line) for line in f], seconds)
    with open(dst + ".jsonl", "w", encoding="utf-8") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    with open(dst + ".expect.json", "w", encoding="utf-8") as f:
        json.dump(expect(rows), f, indent=1)
    print(len(rows), "rows kept")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
