# rmtcheck: disable-file=log-discipline -- main() is the CLI report
# renderer for `rmt check --perf` (same stdout surface as scripts/)
"""``rmt check --perf`` — the perf-regression gate (ROADMAP item 4).

Unlike its AST-rule siblings this checker diffs DATA: the headline JSON
that bench.py prints as its last stdout line and that every recorded
round archives in ``BENCH_r<N>.json`` (``{"n", "cmd", "rc", "tail"}``,
the headline being the tail's final line). The gate compares the round
under test (default: the newest round whose tail still parses — round 4
famously outgrew its tail window and is skipped, not failed) against a
baseline (default: the newest parseable round strictly older), field by
field with per-field tolerance bands:

- throughput-like fields (geomean, GB/s, tasks/s, MFU) regress when the
  new value drops more than the band below the old one — the bands are
  deliberately loose (25-40%) because rounds run on whatever hardware
  the session got, and the gate must flag real cliffs, not host noise;
- overhead-percent fields (tracing/logging/profile ≤5% contracts)
  regress when the new value EXCEEDS the old by more than an absolute
  slack in percentage points.

Only fields present and numeric in BOTH headlines are compared — a
round that predates a suite simply doesn't vote on it. Output is one
``field: old -> new (-N%)`` line per regression and exit 1, or a
one-line OK; ``--json`` emits the full machine-readable diff.
"""

from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

# (dotted field, kind, tolerance). kind "up" = higher is better, the
# tolerance is the allowed fractional drop; kind "down" = lower is
# better (overhead %), the tolerance is allowed absolute increase.
FIELD_SPECS: Tuple[Tuple[str, str, float], ...] = (
    ("vs_baseline", "up", 0.25),
    ("hw.memcpy_gbps", "up", 0.30),
    ("hw.put_vs_memcpy_ceiling", "up", 0.30),
    ("micro.single_client_tasks_sync", "up", 0.35),
    ("micro.single_client_tasks_async", "up", 0.35),
    ("micro.single_client_put_gigabytes", "up", 0.35),
    ("scale.many_tasks_per_s", "up", 0.35),
    ("scale.many_actors_per_s", "up", 0.40),
    ("scale.many_pgs_per_s", "up", 0.40),
    ("scale.broadcast_gbps", "up", 0.40),
    ("scale.cross_node_gbps", "up", 0.40),
    # decentralized-control-plane curve (ISSUE 15): per-node-count task
    # throughput and the 1->4 virtual-node scaling factor must not
    # quietly sink back toward the single-core plateau
    ("scale_curve.tasks_per_s.1", "up", 0.35),
    ("scale_curve.tasks_per_s.4", "up", 0.35),
    ("scale_curve.tasks_scaling_1_to_4", "up", 0.25),
    # pod-scale control plane (ISSUE 19): task throughput at the
    # smallest and largest SIM membership must not collapse; the
    # directory-op tail, head RSS at 256 nodes, and the row flood's
    # RSS bound get absolute slack (us / MB of creep over baseline)
    ("pod_curve.tasks_per_s_8", "up", 0.40),
    ("pod_curve.tasks_per_s_256", "up", 0.45),
    ("pod_curve.dir_p99_us_256", "down", 800.0),
    ("pod_curve.head_rss_mb_256", "down", 768.0),
    ("pod_curve.rows_rss_mb", "down", 768.0),
    ("tpu.train_tokens_per_s", "up", 0.35),
    ("tpu.train_mfu", "up", 0.35),
    # multi-tenant job plane (ISSUE 18): the quota/attribution machinery
    # must not tax the submit hot path (overhead is a percentage, so the
    # band is absolute points), sweeps must stay milliseconds-fast, and
    # the churn soak's aggregate rate must not collapse
    ("jobs.isolation_overhead_pct", "down", 10.0),
    ("jobs.churn_tasks_per_s", "up", 0.40),
    ("jobs.sweep_ms_1000", "down", 50.0),
    ("tracing.overhead_pct", "down", 4.0),
    ("logging.overhead_pct", "down", 4.0),
    ("profile.overhead_pct", "down", 4.0),
    ("health.overhead_pct", "down", 4.0),
)

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def parse_headline(path: str) -> Optional[Dict[str, Any]]:
    """The headline dict archived in one BENCH_r*.json, or None when the
    tail's last line doesn't parse (truncated tail window, crashed run)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    tail = (doc.get("tail") or "").strip()
    if not tail:
        return None
    try:
        headline = json.loads(tail.splitlines()[-1])
    except ValueError:
        return None
    return headline if isinstance(headline, dict) else None


def discover_rounds(root: str) -> List[Tuple[int, str]]:
    """(round_number, path) for every BENCH_r*.json under root, sorted
    oldest-first."""
    out = []
    for path in glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _ROUND_RE.search(os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    out.sort()
    return out


def _resolve_round(selector: str, rounds: List[Tuple[int, str]]
                   ) -> Optional[str]:
    """Accepts '5', 'r05', 'BENCH_r05.json' or a path."""
    if os.path.sep in selector or os.path.isfile(selector):
        return selector
    m = re.search(r"(\d+)", selector)
    if not m:
        return None
    want = int(m.group(1))
    for n, path in rounds:
        if n == want:
            return path
    return None


def _field(headline: Dict[str, Any], dotted: str) -> Optional[float]:
    node: Any = headline
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)


def compare(baseline: Dict[str, Any], current: Dict[str, Any]
            ) -> List[Dict[str, Any]]:
    """Field-by-field diff rows; ``regression`` marks tolerance breaks."""
    rows: List[Dict[str, Any]] = []
    for dotted, kind, tol in FIELD_SPECS:
        old = _field(baseline, dotted)
        new = _field(current, dotted)
        if old is None or new is None:
            continue
        if kind == "up":
            delta_pct = (new - old) / old * 100.0 if old else 0.0
            regression = old > 0 and new < old * (1.0 - tol)
            tolerance_pct = tol * 100.0
        else:  # "down": overhead percentage points, absolute slack
            delta_pct = new - old
            regression = new > old + tol
            tolerance_pct = tol
        rows.append({
            "field": dotted, "kind": kind,
            "old": old, "new": new,
            "delta_pct": round(delta_pct, 2),
            "tolerance_pct": tolerance_pct,
            "regression": regression,
        })
    return rows


def run_gate(root: Optional[str] = None,
             baseline: Optional[str] = None,
             current: Optional[str] = None) -> Dict[str, Any]:
    """The gate as data: {"ok", "baseline", "current", "fields",
    "skipped", "note"} — main() renders it."""
    if root is None:
        # analysis/ -> package -> repo root
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    rounds = discover_rounds(root)
    parseable: List[Tuple[int, str, Dict[str, Any]]] = []
    skipped: List[str] = []
    for n, path in rounds:
        headline = parse_headline(path)
        if headline is None:
            skipped.append(os.path.basename(path))
        else:
            parseable.append((n, path, headline))

    def _pick(selector: Optional[str], default_idx: int
              ) -> Optional[Tuple[str, Dict[str, Any]]]:
        if selector is not None:
            path = _resolve_round(selector, rounds)
            if path is None:
                return None
            headline = parse_headline(path)
            if headline is None:
                return None
            return (os.path.basename(path), headline)
        if not parseable:
            return None
        n, path, headline = parseable[default_idx]
        return (os.path.basename(path), headline)

    cur = _pick(current, -1)
    if cur is None:
        return {"ok": True, "baseline": None, "current": current,
                "fields": [], "skipped": skipped,
                "note": "no parseable round under test — nothing to gate"}
    if baseline is not None:
        base = _pick(baseline, 0)
        if base is None:
            return {"ok": False, "baseline": baseline,
                    "current": cur[0], "fields": [], "skipped": skipped,
                    "note": f"baseline {baseline!r} not found or "
                            "unparseable"}
    else:
        # newest parseable round strictly older than the current one
        older = [(n, p, h) for n, p, h in parseable
                 if os.path.basename(p) != cur[0]
                 and _round_no(p) < _round_no(cur[0])]
        if older:
            n, path, headline = older[-1]
            base = (os.path.basename(path), headline)
        else:
            base = cur  # first recorded round: gate trivially passes
    fields = compare(base[1], cur[1])
    ok = not any(r["regression"] for r in fields)
    return {"ok": ok, "baseline": base[0], "current": cur[0],
            "fields": fields, "skipped": skipped, "note": None}


def _round_no(name: str) -> int:
    m = _ROUND_RE.search(os.path.basename(name))
    return int(m.group(1)) if m else -1


def main(root: Optional[str] = None, baseline: Optional[str] = None,
         current: Optional[str] = None, as_json: bool = False) -> int:
    result = run_gate(root=root, baseline=baseline, current=current)
    if as_json:
        print(json.dumps(result, indent=2))
        return 0 if result["ok"] else 1
    if result.get("note"):
        print(f"perf gate: {result['note']}")
    for name in result["skipped"]:
        print(f"perf gate: skipping {name} (headline unparseable)")
    regressions = [r for r in result["fields"] if r["regression"]]
    for r in regressions:
        sign = "" if r["delta_pct"] >= 0 else "-"
        mag = abs(r["delta_pct"])
        unit = "%" if r["kind"] == "up" else "pp"
        print(f"{r['field']}: {r['old']:g} -> {r['new']:g} "
              f"({sign}{mag:g}{unit}, tolerance "
              f"{r['tolerance_pct']:g}{unit})")
    if result["ok"]:
        if result["baseline"]:
            print(f"perf gate OK: {result['current']} vs "
                  f"{result['baseline']}, {len(result['fields'])} "
                  "fields within tolerance")
        return 0
    print(f"perf gate FAILED: {len(regressions)} field(s) regressed "
          f"past tolerance ({result['current']} vs {result['baseline']})")
    return 1
