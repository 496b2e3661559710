"""From one profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but JAX and needs no
backend, so the harness (which never holds the chip) reduces the trace that
the worker wrote.  ``load`` turns the file into plain lists; everything else
works on those, and is checked in ``chipbench/tests`` on hand-made lists and
on a small trace recorded on the chip.

What a TPU trace looks like (looked at by hand, PR 23, ``trace_look.py``):
one plane per chip named ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per run of a jitted program, named ``<hlo module>(<fingerprint>)``; its
line ``XLA Ops`` has one event per HLO operation that ran, NAMED BY ITS WHOLE
HLO TEXT (``%fusion.8 = bf16[..]{..} fusion(...)``), nested where an operation
(the ``while`` of a layer scan) contains others.  A Pallas kernel is a
``custom-call`` with ``custom_call_target="tpu_custom_call"`` and carries no
name of its own.  ``/host:CPU`` has one line per host thread (unnamed), with
the Python tracer's events (``$file.py:line function``), among them the
profiler's own ``start_trace`` and ``stop_trace``.  ``load`` shortens an
operation's name to ``fusion.8 bf16[1024,1024]`` and keeps ``op=<opcode>``
(and ``tpu_custom_call``) in the event's text, which the patterns match.

- **window**: from the end of the profiler's ``start_trace`` to the start of
  its ``stop_trace`` (0.04 s and 0.28 s of the file's span are the profiler
  itself); where a trace has neither, the span of all its events.
- **busy**: the union of the intervals of the ``XLA Ops`` events of a device
  (of ``XLA Modules`` where a trace has no op line) clipped to the window, in
  seconds, averaged over the devices that ran anything.
- **self time**: an event's duration less that of the events nested directly
  inside it, so a ``while`` does not count its body twice.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


_OPCODE = re.compile(r"[})] ([a-z][a-z0-9\-]*)\(")


def shorten(name: str):
    """``(short name, text)`` of an ``XLA Ops`` event named by its HLO text;
    any other name is kept whole."""
    if not name.startswith("%") or " = " not in name:
        return name, ""
    short, rest = name[1:].split(" = ", 1)
    m = _OPCODE.search(rest)
    text = f"op={m.group(1)}" if m else ""
    if "tpu_custom_call" in rest:
        text += " tpu_custom_call"
    shape = rest.split("{", 1)[0].strip()
    if shape and not shape.startswith("("):
        short = f"{short} {shape}"
    return short, text


def load(path: str) -> list:
    """``[{"name": plane, "lines": [{"name": line, "events": [(name, start_s,
    dur_s, text)]}]}]`` for the planes that hold events."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name, text = shorten(e.name)
                events.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                               text))
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list) -> list:
    return [p for p in planes if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return None


def op_events(plane: dict) -> list:
    """The events that say the device ran something."""
    return _line(plane, OPS_LINE) or _line(plane, MODULES_LINE) or []


def union_seconds(events: list) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, start, dur, _ in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def window(planes: list):
    """``(t0, t1)`` of the traced window: see the module's docstring."""
    starts, stops, lo, hi = [], [], None, None
    for p in planes:
        for ln in p["lines"]:
            for name, start, dur, _ in ln["events"]:
                lo = start if lo is None else min(lo, start)
                hi = start + dur if hi is None else max(hi, start + dur)
                if p["name"].startswith("/host:"):
                    if name.endswith(" start_trace"):
                        starts.append(start + dur)
                    elif name.endswith(" stop_trace"):
                        stops.append(start)
    if lo is None:
        return 0.0, 0.0
    t0 = min(starts) if starts else lo
    t1 = max(stops) if stops else hi
    return (t0, t1) if t1 > t0 else (lo, hi)


def window_seconds(planes: list) -> float:
    t0, t1 = window(planes)
    return t1 - t0


def clip(events: list, t0: float, t1: float) -> list:
    out = []
    for name, start, dur, text in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a, text))
    return out


def busy(planes: list):
    """``(busy_s, window_s, devices)``: busy averaged over the devices that
    ran anything in the window."""
    t0, t1 = window(planes)
    per_device = [union_seconds(clip(op_events(p), t0, t1))
                  for p in device_planes(planes)]
    per_device = [b for b in per_device if b > 0]
    if not per_device:
        return 0.0, t1 - t0, 0
    return sum(per_device) / len(per_device), t1 - t0, len(per_device)


def self_times(events: list) -> list:
    """``[(name, self_seconds, stats_text)]``: each event's duration less its
    directly nested events', on one line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [e[2] for e in events]
    stack = []  # indices of open events
    for i in order:
        _, start, dur, _ = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(events[i][0], max(0.0, own[i]), events[i][3])
            for i in range(len(events))]


def first_device(planes: list):
    """The first device plane that ran anything (one device's view: under
    tensor parallelism every device runs the same program)."""
    for p in device_planes(planes):
        if op_events(p):
            return p
    return None


def module_durations(planes: list, pattern: str) -> list:
    """Durations (s) of the runs of the programs whose module name matches
    ``pattern``, on the first device."""
    plane = first_device(planes)
    if plane is None:
        return []
    rx = re.compile(pattern)
    return [dur for name, _, dur, _ in _line(plane, MODULES_LINE) or []
            if rx.search(name)]


def op_self_seconds(planes: list, pattern: str, within: str = None) -> float:
    """Self time (s) on the first device of the operations whose name or
    string stats match ``pattern``; ``within``: only those that ran inside a
    program whose module name matches it."""
    plane = first_device(planes)
    if plane is None:
        return 0.0
    rx = re.compile(pattern)
    spans = None
    if within is not None:
        wx = re.compile(within)
        spans = [(s, s + d) for n, s, d, _ in _line(plane, MODULES_LINE) or []
                 if wx.search(n)]
    total = 0.0
    events = op_events(plane)
    for (name, own, text), (_, start, _, _) in zip(self_times(events), events):
        if not (rx.search(name) or rx.search(text)):
            continue
        if spans is not None and not any(a <= start < b for a, b in spans):
            continue
        total += own
    return total


def share_pct(planes: list, pattern: str, within: str = None):
    """Self time of the operations that match (see ``op_self_seconds``) as a
    percentage of the first device's busy time, or None where it ran
    nothing."""
    plane = first_device(planes)
    if plane is None:
        return None
    busy_s = union_seconds(op_events(plane))
    if busy_s <= 0:
        return None
    return 100.0 * op_self_seconds(planes, pattern, within) / busy_s


def top_ops(planes: list, n: int = 10) -> list:
    """``[[name, seconds]]``: the operations with most self time on the first
    device, grouped by short name (``fusion.194 bf16[64,14336]``; a Pallas
    kernel is marked ``[tpu_custom_call]``)."""
    plane = first_device(planes)
    if plane is None:
        return []
    by_name: dict = {}
    for name, own, text in self_times(op_events(plane)):
        key = name + (" [tpu_custom_call]" if "tpu_custom_call" in text else "")
        by_name[key] = by_name.get(key, 0.0) + own
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def _host_lines(planes: list) -> list:
    return [(p["name"], ln) for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"]]


# a thread that waits is not what the host was doing
_BLOCKED = re.compile(r"(wait|acquire|_acquire_restore|sleep|recv|read_header"
                      r"|_fill|setprofile|_bootstrap|_bootstrap_inner|select"
                      r"|poll|__enter__)$")


def idle_gaps(planes: list, n: int = 10) -> list:
    """``[[what, seconds]]``: the idle time between the first device's busy
    intervals inside the window, by what the host was doing: each of the 100
    longest gaps is named after the host event (any thread, threads that
    only wait left out) that overlaps most of it, the shortest such event
    where several cover it; gaps of one name are summed."""
    plane = first_device(planes)
    if plane is None:
        return []
    t0, t1 = window(planes)
    merged = []
    for _, start, dur, _ in sorted(clip(op_events(plane), t0, t1),
                                   key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    edges = [[t0, t0]] + merged + [[t1, t1]]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:])
                   if b[0] > a[1]), reverse=True)[:100]
    host = sorted((e[1], e[1] + e[2], e[0]) for _, ln in _host_lines(planes)
                  for e in ln["events"]
                  if e[2] > 0 and not _BLOCKED.search(e[0]))
    by_name: dict = {}
    for length, g0, g1 in gaps:
        best, best_key = "host: nothing traced", (0.0, 0.0)
        for h0, h1, name in host:
            if h0 >= g1:
                break
            cover = min(g1, h1) - max(g0, h0)
            if cover <= 0:
                continue
            key = (round(cover / length, 2), -(h1 - h0))
            if key > best_key:
                best, best_key = name, key
        by_name[best] = by_name.get(best, 0.0) + length
    rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def summary(planes: list) -> dict:
    """What the result line's ``device`` and ``breakdown`` take from a
    trace."""
    busy_s, window_s, devices = busy(planes)
    return {"busy_s": busy_s, "window_s": window_s, "devices": devices,
            "device_ops": top_ops(planes), "idle_gaps": idle_gaps(planes)}
