#!/usr/bin/env python3
"""The gate the driver holds a cell to, as a command: sets of runs of one
cell, each a process of its own, and every end-to-end metric's spread beside
half its bound.

    python3 chipbench/steady.py --workload <cell> --sets 2 --runs 6 --keep <dir>
    python3 chipbench/steady.py --from <dir> [--from <dir> ...] [--workload <cell>]

The first form runs ``chipbench/run.py`` ``sets x runs`` times, untraced, as
the driver does (the same seeds in every set, each run of a set another seed;
the seeds are printed), keeps each run's whole log as
``<dir>/set<k>_run<j>_<seed>.log`` and reports.  ``--traced 1`` adds one traced
run of the first seed behind the last set (``<dir>/traced_<seed>.log``) and
holds its client rows against the untraced run of that seed.  It touches no
JAX backend itself: each run holds the chip alone.  On the chip:
``chiprun --timeout 3000 -- python3 chipbench/steady.py ... --keep
chiprun_out/steady/<name>``.  The second form reports from kept logs with no
chip: a directory written by ``--keep`` gives its sets back; any other
directory of ``*.log`` files is one set, in the order of their names.

Reported, for every end-to-end metric in the runs' result lines and for the
candidate statistics taken from each run's ``window rows`` line (``ttft``
mean; ``tpot`` p50, mean, p85, p90, p95): each run's value, each set's
spread, the mean of the sets' spreads, half the metric's bound and whether it
holds (the driver's gate for a new cell: the mean of the sets' spreads at
most half the bound), the bound the rule of PERF.md section 2 gives (the
smallest of ``STEPS`` that is at least 2.5 times that mean), and the driver's
other test, for a bound that is too loose: the widest ``iqr`` of the sets
times ``LOOSE`` has to reach the bound (a bound of 1% is never too loose).
Every run lasts ``BENCHMARK.json``'s ``run_seconds`` and the seeds are
``SEED0 + 1000003 j``: a verdict at another length would not be the gate's.
A run that prints no result ends the sets: the chip's time is the caller's.
Beside each
run: its place in the call, its set-up, its longest pause between two frames
of a stream (``stream gap``), its longest time to first token and whether it
was ``correct``: a run that stalled says so.

**The spread** (``spread``) is the driver's: the range of a set's values over
their median, leaving out the run farthest from the median where that narrows
it.  The contract's other reading, the distance between the quartiles of
``statistics.quantiles(values, n=4)`` over the median, is printed beside it
(``iqr``, over all of a set's runs: what the driver's test for a bound that is
too loose reads).  ``setup_s`` is judged as the driver judges it: each set's
first run, which compiles, is left out, and the sets' medians may differ by
the bound at most; its spread is not held to anything.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402 - the standard library only, no JAX
from chipbench.spec import percentile  # noqa: E402

# a bound is one of these steps (PERF.md section 2)
STEPS = (0.01, 0.02, 0.03, 0.05, 0.08, 0.10)
ROOM = 2.5  # the bound over the mean of the sets' spreads (the gate is 2)
LOOSE = 8.0  # a bound over this many times the widest iqr is too loose
SEED0 = 4_600_000_019  # over 2**32, as the driver's seeds may be
ROWS = re.compile(r"window rows \[[^\]]*\]: (\[.*\])\s*$")
GAP = re.compile(r"stream gap \[[^\]]*\]: (\[.*\])\s*$")
CELL = re.compile(r"\] cell (\S+): configuration")
CANDIDATES = ("ttft_mean", "tpot_p50", "tpot_mean", "tpot_p85", "tpot_p90",
              "tpot_p95")


# -- the rule ---------------------------------------------------------------------


def spread(values) -> float:
    """Range over the median, as a share; with three values or more the run
    farthest from the median is left out where that narrows the range."""
    xs = sorted(float(v) for v in values)
    if len(xs) < 2:
        return 0.0
    med = statistics.median(xs)
    if len(xs) >= 3:
        far = max(xs, key=lambda x: abs(x - med))  # an end of the range
        kept = list(xs)
        kept.remove(far)
        if kept[-1] - kept[0] < xs[-1] - xs[0]:
            xs = kept
    return (xs[-1] - xs[0]) / abs(med) if med else float("inf")


def iqr(values) -> float:
    """Third less first quartile (``statistics.quantiles``, n=4) over the
    median, as a share: the contract's reading of a spread."""
    xs = [float(v) for v in values]
    if len(xs) < 2:
        return 0.0
    q = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q[2] - q[0]) / abs(med) if med else float("inf")


def bound_by_rule(mean_spread: float):
    """The smallest step that is at least ``ROOM`` times the mean of the
    sets' spreads; None where no step is."""
    return next((s for s in STEPS if s >= ROOM * mean_spread), None)


def too_loose(bound, widest_iqr: float) -> bool:
    """The driver's other test: a bound over ``LOOSE`` times the widest
    spread it reads (the quartiles' distance over all of a set's runs) hides
    losses that the runs could tell.  1% is the floor and never too loose."""
    return (bound is not None and bound > STEPS[0]
            and bound > LOOSE * widest_iqr)


def candidates(rows: list) -> dict:
    """The candidate statistics of one run's ``window rows``: ``[key,
    prompt, asked, due s, ttft ms, last ms(, got)]``, the requests that
    succeeded (a log from before PR 46 has no ``got``: a request that
    succeeded got what it asked)."""
    if not rows:
        return {}
    ttft = [r[4] for r in rows]
    out = {"ttft_mean": sum(ttft) / len(ttft), "requests": len(rows)}
    got = [r[6] if len(r) > 6 else r[2] for r in rows]
    tpot = [(r[5] - r[4]) / (n - 1) for r, n in zip(rows, got) if n >= 2]
    if tpot:
        out.update(tpot_mean=sum(tpot) / len(tpot), streams=len(tpot),
                   **{f"tpot_p{q}": percentile(tpot, q)
                      for q in (50, 85, 90, 95)})
    return out


# -- one run's log -----------------------------------------------------------------


def parse_log(path: str) -> dict:
    """What a kept log says: the cell, the result line (the last line that is
    the contract's object), the window's rows, the longest stream gap."""
    out = {"path": path, "cell": None, "result": None, "rows": None,
           "gap": None}
    with open(path, errors="replace") as f:
        for line in f:
            if out["cell"] is None:
                m = CELL.search(line)
                if m:
                    out["cell"] = m.group(1)
            if "window rows [" in line:
                m = ROWS.search(line)
                if m:
                    out["rows"] = json.loads(m.group(1))
            elif "stream gap [" in line:
                m = GAP.search(line)
                if m:
                    out["gap"] = json.loads(m.group(1))
            elif line.startswith('{"correct"'):
                try:
                    out["result"] = json.loads(line)
                except ValueError:
                    pass
    return out


def run_values(run: dict) -> dict:
    """Metric or statistic -> this run's value."""
    vals = {}
    if run["result"]:
        vals.update({k: v["value"]
                     for k, v in run["result"]["metrics"].items()})
    if run["rows"]:
        vals.update({k: v for k, v in candidates(run["rows"]).items()
                     if k in CANDIDATES})
    return vals


# -- the report ----------------------------------------------------------------------


def report(sets: list, bounds: dict, out=sys.stdout, unsound=()) -> dict:
    """``sets``: a list of sets, each a list of parsed runs in the order they
    ran.  ``bounds``: end-to-end metric -> bound.  ``unsound``: numbers (from
    1) of the sets that are printed and left out of every mean and reading
    (``unsound_sets``).  Prints the tables and returns ``{metric: {"spreads",
    "mean", "bound", "holds", "rule", "iqr", "loose"}}``, ``spreads`` of the
    sound sets."""
    def p(s=""):
        print(s, file=out)

    p("| set | run | log | correct | failed | setup_s | ttft max ms | stream "
      "gap ms (at s; paused / live) |")
    p("| --- | --- | --- | --- | --- | --- | --- | --- |")
    for k, runs in enumerate(sets, 1):
        for j, run in enumerate(runs, 1):
            res, gap = run["result"], run["gap"]
            p(f"| {k} | {j} | {os.path.basename(run['path'])} | "
              f"{res['correct'] if res else 'NO RESULT'} | "
              f"{res['failed'] if res else '-'} | "
              + (f"{res['metrics']['setup_s']['value']:.1f}"
                 if res and "setup_s" in res["metrics"] else "-")
              + " | " + (f"{max(r[4] for r in run['rows']):.1f}"
                         if run["rows"] else "-")
              + " | " + (f"{gap[0]:.1f} ({gap[1]:.1f}; {gap[3]} / {gap[4]})"
                         if gap else "-") + " |")
    values = [[run_values(r) for r in runs] for runs in sets]
    names = []
    for runs in values:
        for v in runs:
            names += [n for n in v if n not in names]
    verdict = {}
    p()
    p("| metric | set | each run | spread % | iqr % | mean of spreads % | "
      "half the bound % | holds | bound by rule | 8 x widest iqr % | too "
      "loose |")
    p("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |")
    for name in names:
        per_set = [(k, [v[name] for v in runs if name in v])
                   for k, runs in enumerate(values, 1)]
        if name == "setup_s":  # the first run of a set compiles
            per_set = [(k, xs[1:]) for k, xs in per_set]
        per_set = [(k, xs) for k, xs in per_set if xs]
        if not per_set:  # set-up, in sets of one run: the run that compiles
            continue
        sound = [xs for k, xs in per_set if k not in unsound] or [
            xs for _, xs in per_set]
        spreads = [spread(xs) for xs in sound]
        mean = sum(spreads) / len(spreads)
        bound = bounds.get(name)
        holds = None if bound is None else mean <= 0.5 * bound
        if name == "setup_s" and bound is not None:
            meds = [statistics.median(xs) for xs in sound]
            holds = max(meds) <= min(meds) * (1 + bound)
        rule = bound_by_rule(mean)
        widest = max(iqr(xs) for xs in sound)
        # setup_s is judged by its median alone, never by its spread; two
        # runs have no quartiles to speak of
        loose = (None if bound is None or name == "setup_s"
                 or max(len(xs) for xs in sound) < 3
                 else too_loose(bound, widest))
        verdict[name] = {"spreads": spreads, "mean": mean, "bound": bound,
                         "holds": holds, "rule": rule, "iqr": widest,
                         "loose": loose}
        for i, (k, xs) in enumerate(per_set):
            last = i == len(per_set) - 1
            p(f"| `{name}` | {k}{' (unsound)' if k in unsound else ''} | "
              + " ".join(f"{x:.4f}" for x in xs)
              + f" | {spread(xs) * 100:.3f} | {iqr(xs) * 100:.3f} | "
              + (f"{mean * 100:.3f} | "
                 + ("-" if bound is None else f"{bound * 50:.2f}") + " | "
                 + ("-" if holds is None else "yes" if holds else "NO")
                 + " | " + ("over every step" if rule is None else f"{rule}")
                 + f" | {LOOSE * widest * 100:.2f} | "
                 + ("-" if loose is None else "LOOSE" if loose else "no")
                 if last else " | | | | | ") + " |")
    return verdict


def stalled(sets: list, factor: float = 5.0) -> list:
    """Logs of the runs whose longest stream gap or longest time to first
    token is ``factor`` times the median of all runs' or more: a stall of
    the machine, the engine or the proxy, whatever its cause."""
    runs = [r for rs in sets for r in rs]
    out = set()
    for worst in (lambda r: r["gap"][0] if r["gap"] else None,
                  lambda r: max(x[4] for x in r["rows"]) if r["rows"]
                  else None):
        vals = [(worst(r), r["path"]) for r in runs if worst(r) is not None]
        if len(vals) >= 3:
            med = statistics.median(v for v, _ in vals)
            out |= {p for v, p in vals if v >= factor * med}
    return sorted(os.path.basename(p) for p in out)


def unsound_sets(sets: list) -> list:
    """Numbers (from 1) of the sets that hold two stalled runs or more.  The
    driver's rule forgives a set one run; a set with two is refused whatever
    the bound, so no bound is sized for it: it is printed, and left out of
    every mean, for every metric alike."""
    stalls = set(stalled(sets))
    return [k for k, runs in enumerate(sets, 1) if sum(
        os.path.basename(r["path"]) in stalls for r in runs) >= 2]


def traced_beside(traced: dict, sets: list, out=sys.stdout) -> None:
    """The traced run's client rows against the untraced run of its seed."""
    seed = re.search(r"_(\d+)\.log$", traced["path"])
    same = [r for runs in sets for r in runs
            if seed and r["path"].endswith(f"_{seed.group(1)}.log")]
    if not (same and traced["rows"] and same[-1]["rows"]):
        print("traced run: nothing to hold it against", file=out)
        return
    t, u = candidates(traced["rows"]), candidates(same[-1]["rows"])
    print(f"traced {os.path.basename(traced['path'])} beside untraced "
          f"{os.path.basename(same[-1]['path'])}: " + "; ".join(
              f"{n} {t[n]:.3f} / {u[n]:.3f} ({(t[n] / u[n] - 1) * 100:+.2f}%)"
              for n in ("ttft_mean", "tpot_mean")), file=out)


# -- running the sets -------------------------------------------------------------------


def run_sets(args, keep: str) -> None:
    os.makedirs(keep, exist_ok=True)
    seeds = [SEED0 + 1_000_003 * j for j in range(args.runs)]
    print(f"seeds (the same in every set): {seeds}", flush=True)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}

    def one(tag, seed, trace):
        path = os.path.join(keep, f"{tag}_{seed}.log")
        cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
               "--workload", args.workload, "--seed", str(seed), "--trace",
               str(trace)]  # run.py takes run_seconds from BENCHMARK.json
        t0 = time.monotonic()
        with open(path, "w") as f:
            rc = subprocess.run(cmd, stdout=f, stderr=subprocess.STDOUT,
                                cwd=ROOT, env=env).returncode
        got = parse_log(path)["result"]
        print(f"{tag} seed {seed}: rc {rc}, {time.monotonic() - t0:.0f}s, "
              + (f"correct {got['correct']}, failed {got['failed']}, "
                 + ", ".join(f"{k} {v['value']:.4f}"
                             for k, v in got["metrics"].items())
                 if got else "NO RESULT"), flush=True)
        if not got:
            raise SystemExit(f"{path}: no result; the sets end here")

    for k in range(1, args.sets + 1):
        for j, seed in enumerate(seeds, 1):
            one(f"set{args.first_set + k - 1}_run{j}", seed, 0)
    if args.traced:
        one("traced", seeds[0], 1)


def load_sets(dirs: list, workload=None):
    """Kept logs -> ``(sets, traced runs)``."""
    sets, traced = [], []
    for d in dirs:
        logs = sorted(glob.glob(os.path.join(d, "*.log")))
        runs = [parse_log(p) for p in logs]
        runs = [r for r in runs if r["cell"] is not None
                and (workload is None or r["cell"] == workload)]
        by_set: dict = {}
        for r in runs:
            name = os.path.basename(r["path"])
            m = re.match(r"set(\d+)_run(\d+)_", name)
            if name.startswith("traced_"):
                traced.append(r)
            elif m:
                by_set.setdefault(int(m.group(1)), []).append(
                    (int(m.group(2)), r))
            else:
                by_set.setdefault(("dir", d), []).append((len(by_set), r))
        for key in sorted(by_set, key=str):
            sets.append([r for _, r in sorted(by_set[key],
                                              key=lambda t: t[0])])
    return sets, traced


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=None)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--first-set", type=int, default=1,
                    help="number of the first set (a second call's is 2)")
    ap.add_argument("--traced", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None,
                    help="run the sets and keep every log here")
    ap.add_argument("--from", dest="dirs", action="append", default=[],
                    help="report from kept logs (may be given more than once)")
    return ap


def main() -> int:
    ap = parser()
    args = ap.parse_args()
    if args.keep:
        if not args.workload:
            ap.error("--keep needs --workload")
        run_sets(args, args.keep)
    dirs = args.dirs or ([args.keep] if args.keep else [])
    if not dirs:
        ap.error("one of --keep and --from")
    sets, traced = load_sets(dirs, args.workload)
    if not sets:
        print("no run's log found", flush=True)
        return 1
    cells = sorted({r["cell"] for runs in sets for r in runs})
    bounds = {m["name"]: m["bound"] for m in spec.benchmark()["end_to_end"]}
    print(f"cell(s) {cells}: {len(sets)} set(s) of "
          f"{[len(s) for s in sets]} run(s)")
    unsound = unsound_sets(sets)
    if unsound:
        print(f"set(s) {unsound} hold two stalled runs or more: printed, "
              "and left out of every mean (the driver refuses such a set "
              "whatever the bound)")
    verdict = report(sets, bounds, unsound=unsound)
    for t in traced:
        traced_beside(t, sets)
    failed = [n for n, v in verdict.items() if v["holds"] is False]
    wrong = [r["path"] for runs in sets for r in runs
             if not (r["result"] and r["result"]["correct"])]
    if wrong:
        print(f"not correct, or no result: {wrong}")
    stalls = stalled(sets)
    if stalls:
        print(f"stalled (a stream gap or a time to first token 5 x the "
              f"runs' median or more): {stalls}; the rule leaves out one "
              "run a set")
    print("every end-to-end metric holds" if not failed
          else f"DOES NOT HOLD: {failed}")
    loose = [n for n, v in verdict.items() if v["loose"]]
    if loose:
        print(f"TOO LOOSE for these runs (the bound is over {LOOSE:g} times "
              f"the widest iqr): {loose}; the driver reads the widest over "
              "all the cells that report the metric")
    return 1 if failed or wrong else 0


if __name__ == "__main__":
    sys.exit(main())
