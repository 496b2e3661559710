#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration file,
its traffic file, the runner of its kind (``kinds/<kind>.py``) and the reader
of each of its metrics (``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py``) are found by name.  The last line of standard
output is the one JSON object of the benchmark's contract.  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` is a run of its own with
one profiler capture from the worker that holds the chip, and reports the
per-layer metrics.

A run that finds no TPU, or fewer chips than the cell asks, exits non-zero
and prints no result.  ``--rehearse`` runs the same control flow at toy size
on whatever backend the workers get (the CPU, in a sandbox): it can never
print the result line and always exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402
from chipbench.spec import BenchError, log  # noqa: E402

SECTION_DIR = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend the workers get; "
                         "never prints the result line, always exits 3")
    args = ap.parse_args()
    try:
        return run(args)
    except BenchError as e:
        print(f"[chipbench] NO RESULT: {e}", flush=True)
        return 1


def run(args) -> int:
    cell = spec.Cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.bench["run_seconds"])
    # workers import chipbench (and ray_tpu) from the checkout
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    try:
        import ray_tpu  # noqa: F401 - the system under test has to be there
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}") from e
    kind = spec.load_module("kinds", cell.kind)
    log(f"cell {cell.name}: configuration {cell.entry['config']}, traffic "
        f"{cell.entry['traffic']}, kind {cell.kind}, {cell.chips} chip(s), "
        f"seed {args.seed}, window {args.seconds:.0f}s, trace {args.trace}")
    evidence = kind.run(cell, args)
    correct, attempted, failed, why = kind.correct(evidence, args.rehearse)
    if not correct:
        log(f"NOT CORRECT: {why}")
    section = "per_layer" if args.trace else "end_to_end"
    metrics = spec.read_metrics(cell, section, SECTION_DIR[section], evidence,
                                tolerate=args.rehearse)
    device = kind.device(evidence)
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        trace = evidence.get("trace")
        if not trace:
            raise BenchError("the traced run has no trace")
        from chipbench import trace_reduce

        s = trace_reduce.summary(trace["planes"])
        log(f"trace: device busy {s['busy_s']:.3f}s of {s['window_s']:.3f}s "
            f"on {s['devices']} device(s)")
        if not args.rehearse and s["busy_s"] <= 0:
            raise BenchError("no operation ran on the device in the trace")
        device.update(busy_s=s["busy_s"], window_s=s["window_s"])
        result["breakdown"] = {"device_ops": s["device_ops"],
                               "idle_gaps": s["idle_gaps"]}
    # every number ``correct`` compared, beside its limit: last in the
    # line, and the last lines of standard error
    checks = kind.compared(evidence)
    if args.rehearse:
        # a CPU run's numbers never stand under a device metric's name
        log(f"a chip run would print: correct={result['correct']} "
            f"attempted={result['attempted']} failed={result['failed']} "
            f"metrics={sorted(metrics)} device keys={sorted(device)} "
            f"compared={[name for name, _, _ in checks]}")
        print("[chipbench] rehearsal complete: no result line", flush=True)
        return 3
    if device["platform"] != "tpu" or device["count"] != cell.chips:
        raise BenchError(f"the workers saw {device['count']} x "
                         f"{device['platform']}, the cell asks {cell.chips} "
                         "TPU chip(s)")
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in checks}
    sys.stdout.flush()
    for name, value, limit in checks:
        print(f"[chipbench] compared {name}: {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
