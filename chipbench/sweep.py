#!/usr/bin/env python3
"""Find the knee of an open-loop cell: one process, one set-up, ascending
rates.

    python3 chipbench/sweep.py --workload <cell> --rates 2,3,4,5,6,8 [--seconds 30]

For each rate the cell's traffic runs at that rate for ``--seconds`` (after
the traffic file's ramp), then drains.  Printed per rate: requests in flight
at the window's start and end (a sustained rate does not grow them), the
benchmark's gates under their names (``ttft_mean_ms``, ``tpot_mean_ms``,
``tpot_p90_ms``, ``tpot_p85_ms``) and the tails of time to first token and
of the pace, tokens completed per second, failures.  The knee is the highest
rate at which the number in flight does not grow through the window; the
cell's traffic file gets at most 0.6 of it (``README.md``, "A cell";
``steady.py`` is the gate the rate has to pass).  The output for a cell is
kept in ``PERF.md`` beside the rate chosen.

``--keep-trace DIR`` captures one profiler trace during the second rate and
copies the ``.xplane.pb`` there (to look at by hand: ``trace_look.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import serving, spec  # noqa: E402
from chipbench.spec import BenchError, log, percentile  # noqa: E402


def in_flight(rows: list, t: float) -> int:
    """Requests due by ``t`` and not finished at ``t`` (failed ones never
    finish)."""
    return sum(1 for r in rows if r["due"] is not None and r["due"] <= t
               and (r["last"] is None or not r["ok"] or r["last"] > t))


def one_rate(replica, traffic: dict, rate: float, seed: int, seconds: float,
             keep_trace: str = None) -> dict:
    t = json.loads(json.dumps(traffic))
    t["arrivals"]["rate_per_s"] = rate
    workdir = tempfile.mkdtemp(prefix="chipbench_sweep_")
    window_t0 = time.monotonic() + 1.5 + float(t.get("ramp_s", 0))
    child = replica.load(seed, seconds, t, window_t0, workdir)
    samples = []

    def side():
        time.sleep(max(0.0, window_t0 - time.monotonic()))
        traced = False
        while time.monotonic() < window_t0 + seconds:
            if keep_trace and not traced and \
                    time.monotonic() > window_t0 + 0.4 * seconds:
                traced = True
                got = serving.capture_trace(replica.report["pid"], 3.0, workdir)
                if got:
                    os.makedirs(keep_trace, exist_ok=True)
                    shutil.copy(got["path"], os.path.join(
                        keep_trace, f"sweep_rate{rate:g}.xplane.pb"))
            try:
                u = replica.utilization()
                samples.append((u["slots"]["active"], u["kv_blocks"]["used"],
                                u["pending"]))
            except Exception:  # noqa: BLE001 - one sample lost
                pass
            time.sleep(1.0)

    th = threading.Thread(target=side, daemon=True)
    th.start()
    if child.wait(timeout=seconds + float(t.get("drain_s", 60)) + 120) != 0:
        raise BenchError("the load generator failed")
    th.join(timeout=60)
    with open(os.path.join(workdir, "rows.json")) as f:
        rows = json.load(f)["rows"]
    win = [r for r in rows if r["phase"] == "window"]
    ok = [r for r in win if r["ok"]]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok]
    tpot = [(r["last"] - r["first"]) / (r["got"] - 1) * 1e3 for r in ok
            if r["got"] > 1]
    done = [r for r in rows if r["ok"] and 0 <= r["last"] < seconds]
    out = {
        "rate": rate, "requests": len(win), "failed": len(win) - len(ok),
        "in_flight_start": in_flight(rows, 0.0),
        "in_flight_mid": in_flight(rows, seconds / 2),
        "in_flight_end": in_flight(rows, seconds),
        # the benchmark's gates under their names (PR 46), then the tails
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else None,
        "tpot_mean_ms": sum(tpot) / len(tpot) if tpot else None,
        "tpot_p90_ms": percentile(tpot, 90) if tpot else None,
        "tpot_p85_ms": percentile(tpot, 85) if tpot else None,
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
        "tpot_p50_ms": percentile(tpot, 50) if tpot else None,
        "tpot_p95_ms": percentile(tpot, 95) if tpot else None,
        "completed_tokens_per_s": sum(r["prompt_len"] + r["got"]
                                      for r in done) / seconds,
        "late_p95_ms": percentile([(r["sent"] - r["due"]) * 1e3
                                   for r in win if r["sent"] is not None], 95),
        "slots_active_mean": (sum(s[0] for s in samples) / len(samples)
                              if samples else None),
        "kv_used_mean": (sum(s[1] for s in samples) / len(samples)
                         if samples else None),
        "pending_max": max((s[2] for s in samples), default=None),
        "drain_s": max((r["last"] for r in ok), default=seconds) - seconds,
    }
    print("SWEEP " + json.dumps(out), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second, ascending")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ["PYTHONPATH"] = ROOT
    cell = spec.Cell(args.workload)
    if cell.traffic.get("loop") != "open":
        raise SystemExit("a sweep needs an open-loop cell")
    traffic = serving.toy_traffic(cell.traffic) if args.rehearse \
        else cell.traffic
    rates = [float(r) for r in args.rates.split(",")]
    serving.start_cluster(cell.chips, args.rehearse)
    try:
        replica = serving.Replica(cell, args.rehearse)
        try:
            results = []
            for i, rate in enumerate(rates):
                results.append(one_rate(
                    replica, traffic, rate, args.seed, args.seconds,
                    args.keep_trace if i == min(1, len(rates) - 1) else None))
                if results[-1]["failed"] > 0.2 * results[-1]["requests"]:
                    log("over a fifth of the requests failed: stopping")
                    break
            after = replica.handle.device_report.remote().result(timeout_s=120)
            log(f"memory after the sweep: {after['memory']}; utilization "
                f"{json.dumps(after['utilization'], default=str)[:400]}")
            sustained = [r["rate"] for r in results if r["failed"] == 0
                         and r["in_flight_end"] <= 1.25 * max(4, min(
                             r["in_flight_start"], r["in_flight_mid"]))]
            log(f"rates whose in-flight count did not grow through the "
                f"window (end <= 1.25 x the lesser of start and middle): "
                f"{sustained}")
        finally:
            replica.down()
    finally:
        serving.stop_cluster()
    return 0


if __name__ == "__main__":
    sys.exit(main())
