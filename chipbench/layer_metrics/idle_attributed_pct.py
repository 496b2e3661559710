"""``idle_attributed_pct``: of the first device's idle time inside the traced
window, the share that lies under a named region of the program other than
``engine.step`` (``engine.collect``, ``engine.drain``,
``engine.decode_dispatch``, ``kv.demote``, ``serve.step_lock_wait``,
``serve.loop_idle``, ...: ``ray_tpu/util/tracing.py`` ``region``, on the
XPlane's host plane, the profiler's own clock).  Idle time under ``engine.step``
alone, or under no region, is time the program cannot yet name.

``by_region`` gives the idle seconds by region, each piece booked to the
shortest region of the engine loop's thread that covers it, else to another
thread's, else to the whole step; ``read`` logs it (the trace file does not
outlive the run)."""

import bisect

from chipbench import trace_reduce
from chipbench.spec import log

PREFIXES = ("engine.", "kv.", "serve.")
WHOLE = "engine.step"


def _regions(planes):
    """``[(start, end, name, rank)]``: rank 0 for the regions of the thread
    that runs the engine's steps (what the device waits for), 1 for other
    threads' (a caller waiting for the step), 2 for the whole step."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            loop = any(e[0] == WHOLE for e in ln["events"])
            out.extend((start, start + dur, name,
                        2 if name == WHOLE else 0 if loop else 1)
                       for name, start, dur, _ in ln["events"]
                       if dur > 0 and name.startswith(PREFIXES))
    return sorted(out)


def _idle(planes):
    """The first device's idle intervals inside the traced window."""
    plane = trace_reduce.first_device(planes)
    if plane is None:
        return []
    t0, t1 = trace_reduce.window(planes)
    edge, gaps = t0, []
    for _, start, dur, _ in sorted(
            trace_reduce.clip(trace_reduce.op_events(plane), t0, t1),
            key=lambda e: e[1]):
        if start > edge:
            gaps.append((edge, start))
        edge = max(edge, start + dur)
    if t1 > edge:
        gaps.append((edge, t1))
    return gaps


def by_region(planes):
    """``{region name: idle seconds}``, with ``"(no region)"``; None where
    the trace holds no region of the program or no device."""
    regions = _regions(planes)
    gaps = _idle(planes)
    if not regions or not gaps:
        return None
    starts = [r[0] for r in regions]
    longest = max(r[1] - r[0] for r in regions)
    out: dict = {}
    for g0, g1 in gaps:
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_left(starts, g1)
        over = sorted((r for r in regions[lo:hi] if r[1] > g0),
                      key=lambda r: (r[3], r[1] - r[0]))
        free = [(g0, g1)]  # the pieces of this gap no region has taken yet
        for r0, r1, name, _ in over:
            rest = []
            for a, b in free:
                c, d = max(a, r0), min(b, r1)
                if d <= c:
                    rest.append((a, b))
                    continue
                out[name] = out.get(name, 0.0) + (d - c)
                if a < c:
                    rest.append((a, c))
                if d < b:
                    rest.append((d, b))
            free = rest
            if not free:
                break
        left = sum(b - a for a, b in free)
        if left > 0:
            out["(no region)"] = out.get("(no region)", 0.0) + left
    return out


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    got = by_region(trace["planes"])
    if not got:
        return None
    idle = sum(got.values())
    log("device idle by region, s: " + ", ".join(
        f"{k} {v:.5f}" for k, v in sorted(got.items(), key=lambda kv: -kv[1])))
    log("host lines that carry regions (the threads' names): " + ", ".join(
        sorted({repr(ln["name"]) for p in trace["planes"]
                if p["name"].startswith("/host:") for ln in p["lines"]
                if any(e[0].startswith(PREFIXES) for e in ln["events"])})))
    if idle <= 0:
        return None
    named = sum(v for k, v in got.items() if k not in (WHOLE, "(no region)"))
    return 100.0 * named / idle
