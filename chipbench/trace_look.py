#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, and the names that take
most time on each line.

    python3 chipbench/trace_look.py <file.xplane.pb> [names per line]

Used once (PR 23) to learn how a TPU trace of this program is laid out, before
``trace_reduce.py`` and the trace-reading layer metrics were written against
it; kept for whoever has to do so again after the program's names change.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402


def look(path: str, top: int = 12) -> None:
    planes = trace_reduce.load(path)
    busy_s, window_s, devices = trace_reduce.busy(planes)
    print(f"{path}: {os.path.getsize(path)} bytes; window {window_s:.4f}s, "
          f"device busy {busy_s:.4f}s over {devices} device(s)")
    for plane in planes:
        print(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            evs = line["events"]
            total = sum(e[2] for e in evs)
            print(f"  LINE {line['name']!r}: {len(evs)} events, "
                  f"{total:.4f}s summed, starts {min(e[1] for e in evs):.4f}"
                  f"..{max(e[1] + e[2] for e in evs):.4f}")
            by: dict = {}
            for name, _, dur, text in evs:
                row = by.setdefault(name, [0, 0.0, text])
                row[0] += 1
                row[1] += dur
            for name, (n, dur, text) in sorted(
                    by.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"      {dur:9.5f}s x{n:<6} {name[:90]}  | {text[:160]}")


if __name__ == "__main__":
    look(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
