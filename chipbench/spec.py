"""Finding a cell's files by name.

``BENCHMARK.json`` names cells, configurations and metrics; everything that
belongs to one of them is a file of its own under ``chipbench/``, found here
by that name.  No list of cells, configurations, traffic mixes or metrics
exists in any ``.py`` file: a later PR adds an entry and its files, and edits
nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
T0 = time.monotonic()     # process start, as near as an import can stamp it


def log(msg: str) -> None:
    print(f"[chipbench +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


class BenchError(RuntimeError):
    """The run cannot give a result: no result line is printed."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with the files its names lead to."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = benchmark(root)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                             f"(it has {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        if self.entry["config"] not in configs:
            raise BenchError(f"workload {name!r} names configuration "
                             f"{self.entry['config']!r}, which BENCHMARK.json "
                             "does not list")
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            root, "chipbench", "traffic", self.entry["traffic"] + ".json"))
        # the kind of cell (which runner under kinds/) is a property of
        # the traffic: an open loop, a closed loop, a training job
        self.kind = self.traffic["kind"]

    def metrics(self, section: str) -> list:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_module(subdir: str, name: str, root: str = ROOT):
    """``chipbench/<subdir>/<name>.py`` as a module, found by file name."""
    path = os.path.join(root, "chipbench", subdir, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"{os.path.relpath(path, root)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{subdir}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(cell: Cell, section: str, subdir: str, evidence: dict,
                 tolerate: bool = False) -> dict:
    """Each metric's reader (``<subdir>/<name>.py``, ``read(evidence)``)
    gives one number, or nothing where its source was not there; such a
    metric is left out of the line.  ``tolerate`` (a rehearsal, where no
    device is in the table of peaks): a reader that raises is logged."""
    out = {}
    for m in cell.metrics(section):
        try:
            value = load_module(subdir, m["name"], cell.root).read(evidence)
        except (KeyError, ValueError, ZeroDivisionError) as e:
            if not tolerate:
                raise
            log(f"{section} metric {m['name']}: {type(e).__name__}: {e}")
            continue
        if value is None:
            log(f"{section} metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def ttft_ms(evidence: dict) -> list:
    """Time to first token of every request due in an open loop's window,
    from when it was DUE, client's clock, in ms.  A request that failed
    misses: it counts with the time to the drain limit."""
    rows = [r for r in evidence.get("rows", ()) if r["phase"] == "window"]
    if not rows or evidence["traffic"]["loop"] != "open":
        return []
    limit = evidence["seconds"] + float(evidence["traffic"].get("drain_s", 60))
    vals = [((r["first"] - r["due"]) if r["ok"] else (limit - r["due"])) * 1e3
            for r in rows]
    log(f"ttft_ms over {len(vals)} requests: mean {sum(vals) / len(vals):.1f} "
        f"p50 {percentile(vals, 50):.1f} p90 {percentile(vals, 90):.1f} "
        f"p95 {percentile(vals, 95):.1f} max {max(vals):.1f}")
    return vals


def tpot_ms(evidence: dict) -> list:
    """The pace of every stream of an open loop's window that got two tokens
    or more: (last token - first token) / (tokens - 1), client's clock, in
    ms, whatever the framing of ``decode_chunk``."""
    if "tpot_ms" in evidence:  # several readers, one reckoning and one line
        return evidence["tpot_ms"]
    rows = [r for r in evidence.get("rows", ()) if r["phase"] == "window"
            and r["ok"] and r["got"] >= 2]
    if not rows or evidence["traffic"]["loop"] != "open":
        return []
    vals = evidence["tpot_ms"] = [
        (r["last"] - r["first"]) / (r["got"] - 1) * 1e3 for r in rows]
    log(f"tpot_ms over {len(vals)} streams: mean "
        f"{sum(vals) / len(vals):.3f} p50 {percentile(vals, 50):.3f} p90 "
        f"{percentile(vals, 90):.3f} p95 {percentile(vals, 95):.3f} max "
        f"{max(vals):.3f}")
    return vals


def stream_gap(rows, seconds: float = float("inf"), near_s: float = 0.05):
    """The longest pause between two frames of any stream, the ramp's too,
    that ended after the window's start and began before its end
    (``seconds``): ``{"max_ms", "at_s" (when it began, from the window's
    start), "key" (the stream), "paused" (streams, this one too, with a pause
    at least half as long that began within ``near_s`` of it), "live"
    (streams that had begun and not ended when it began)}``; or None where
    no stream has two frames there.  One stream that waits is a row's own
    business; many that wait together are a stall of the engine, the proxy
    or the machine.  (A stall at the window's very start holds no stream of
    the WINDOW: the ramp's streams show it, and the window's first requests'
    times to first token.)"""
    streams = [(r["key"], [f[0] for f in r["frames"]]) for r in rows
               if len(r.get("frames") or ()) >= 2]
    worst = None
    for key, ts in streams:
        for a, b in zip(ts, ts[1:]):
            if b > 0 and a < seconds and (worst is None or b - a > worst[0]):
                worst = (b - a, a, key)
    if worst is None:
        return None
    length, at, key = worst
    paused = sum(1 for _, ts in streams if any(
        abs(a - at) <= near_s and b - a >= 0.5 * length
        for a, b in zip(ts, ts[1:])))
    live = sum(1 for _, ts in streams if ts[0] <= at < ts[-1])
    return {"max_ms": length * 1e3, "at_s": at, "key": key, "paused": paused,
            "live": live}
