#!/usr/bin/env python
"""Mechanical reader for the BENCH_r*.json trajectory.

Each ``BENCH_rNN.json`` at the repo root holds one round's JSON document —
headline MFU plus per-section figures under ``extra``.  This tool compares two rounds (newest vs previous by default),
prints per-section deltas for every shared numeric leaf, and exits
nonzero when a metric moved past the regression threshold in its bad
direction.

Direction is inferred from the metric name: latencies / times / overhead
percentages regress UP, throughputs / MFU / rates / acceptance regress
DOWN, and unclassifiable keys are reported but never flagged (a delta in
``params`` is a config change, not a regression).

Usage:
  python tools/bench_diff.py                      # newest vs previous
  python tools/bench_diff.py OLD.json NEW.json    # explicit rounds
  python tools/bench_diff.py --threshold 0.05     # 5% regression gate
"""

from __future__ import annotations

import argparse
import glob as globmod
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# name fragments that classify a metric's bad direction.  An ``_s``
# duration suffix is checked first (suffix-only: ``tokens_per_sec``
# contains ``_s`` as a substring but is a throughput), then the
# higher-is-better throughput names (more specific), then the generic
# lower-is-better fragments.
HIGHER_IS_BETTER = ("tok_per_sec", "tokens_per_sec", "mfu", "value",
                    "bandwidth", "gbps", "goodput", "rate", "throughput",
                    "accept", "per_chip", "steps_per_sec", "hit")
LOWER_IS_BETTER = ("time", "latency", "ttft", "itl", "inter_token",
                   "overhead", "loss", "stall", "wait", "lag", "p50",
                   "p95", "p99", "failed", "error", "compile")
# sizes and counts: a delta is a config change, never a regression
NEUTRAL = ("params", "bytes", "_gb_", "gib", "num_", "count", "seq_len",
           "batch")


def classify(path: str) -> Optional[bool]:
    """True = lower is better, False = higher is better, None = unknown."""
    leaf = path.rsplit(".", 1)[-1].lower()
    if leaf.endswith("_gb"):
        return None
    for frag in NEUTRAL:
        if frag in leaf:
            return None
    if leaf.endswith("_s") or leaf.endswith("_ms") or leaf.endswith("_us"):
        return True
    for frag in HIGHER_IS_BETTER:
        if frag in leaf:
            return False
    for frag in LOWER_IS_BETTER:
        if frag in leaf:
            return True
    return None


def load_round(path: str) -> dict:
    """A round's parsed result — accepts both the driver wrapper
    ({n, cmd, rc, parsed}) and a bare result document."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc:
        doc = doc.get("parsed") or {}
    return doc if isinstance(doc, dict) else {}


def flatten(doc: dict, prefix: str = "") -> Dict[str, float]:
    """Dotted-path -> numeric leaf (bools excluded; lists indexed)."""
    out: Dict[str, float] = {}
    for k, v in doc.items():
        p = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[p] = float(v)
        elif isinstance(v, dict):
            out.update(flatten(v, p))
        elif isinstance(v, list):
            for i, item in enumerate(v):
                if isinstance(item, dict):
                    out.update(flatten(item, f"{p}[{i}]"))
                elif isinstance(item, (int, float)) \
                        and not isinstance(item, bool):
                    out[f"{p}[{i}]"] = float(item)
    return out


def section_of(path: str) -> str:
    parts = path.split(".")
    if parts[0] == "extra" and len(parts) > 1:
        nxt = parts[1].split("[")[0]
        # extra's scalar leaves (tokens_per_sec, step_time_s, ...) belong
        # to the headline section; dict-valued children are sections
        return nxt if len(parts) > 2 else "headline"
    return "headline"


def diff_rounds(old: dict, new: dict,
                threshold: float) -> Tuple[List[dict], List[dict]]:
    """(rows, regressions): every shared numeric leaf's delta, and the
    subset that moved past ``threshold`` in its bad direction."""
    a, b = flatten(old), flatten(new)
    rows: List[dict] = []
    regressions: List[dict] = []
    for path in sorted(set(a) & set(b)):
        va, vb = a[path], b[path]
        if va == vb:
            continue
        rel = (vb - va) / abs(va) if va else None
        lower_better = classify(path)
        row = {
            "section": section_of(path), "metric": path,
            "old": va, "new": vb,
            "rel_change": round(rel, 4) if rel is not None else None,
            "direction": ("lower_better" if lower_better
                          else "higher_better"
                          if lower_better is False else "unclassified"),
        }
        regressed = (rel is not None and lower_better is not None
                     and (rel > threshold if lower_better
                          else rel < -threshold))
        row["regression"] = bool(regressed)
        rows.append(row)
        if regressed:
            regressions.append(row)
    return rows, regressions


def newest_two(pattern: str, base: str) -> Tuple[str, str]:
    paths = sorted(globmod.glob(os.path.join(base, pattern)))
    if len(paths) < 2:
        raise SystemExit(
            f"need at least two rounds matching {pattern!r} in {base!r} "
            f"(found {len(paths)})")
    return paths[-2], paths[-1]


def run(old_path: str, new_path: str, threshold: float = 0.10) -> dict:
    """Library entry (tier-1 smoke imports this): full diff report."""
    rows, regressions = diff_rounds(load_round(old_path),
                                    load_round(new_path), threshold)
    sections: Dict[str, List[dict]] = {}
    for r in rows:
        sections.setdefault(r["section"], []).append(r)
    return {
        "old": old_path, "new": new_path, "threshold": threshold,
        "sections": sections,
        "changed": len(rows),
        "regressions": regressions,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("rounds", nargs="*",
                    help="OLD.json NEW.json (default: the newest two "
                         "BENCH_r*.json in --dir)")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="relative regression gate (default 0.10 = 10%%)")
    ap.add_argument("--glob", default="BENCH_r*.json",
                    help="round filename pattern for the default pair")
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the rounds (default: repo root)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as one JSON document")
    args = ap.parse_args(argv)

    if len(args.rounds) == 2:
        old_path, new_path = args.rounds
    elif not args.rounds:
        old_path, new_path = newest_two(args.glob, args.dir)
    else:
        ap.error("pass exactly two round files, or none for the default")

    report = run(old_path, new_path, args.threshold)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(f"bench diff: {os.path.basename(old_path)} -> "
              f"{os.path.basename(new_path)} "
              f"(threshold {args.threshold:.0%})")
        for section, rows in sorted(report["sections"].items()):
            print(f"\n[{section}]")
            for r in rows:
                rel = (f"{r['rel_change']:+.1%}"
                       if r["rel_change"] is not None else "new-from-0")
                flag = "  << REGRESSION" if r["regression"] else ""
                print(f"  {r['metric']:<58} {r['old']:>12.4g} -> "
                      f"{r['new']:>12.4g}  {rel}{flag}")
        if not report["changed"]:
            print("  (no shared numeric leaves changed)")
        if report["regressions"]:
            print(f"\n{len(report['regressions'])} regression(s) past "
                  f"the {args.threshold:.0%} gate")
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
