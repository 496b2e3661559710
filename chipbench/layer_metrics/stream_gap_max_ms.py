"""``stream_gap_max_ms``: the longest pause between two frames of any stream
inside the window (the ramp's streams that are still live count), client's
clock (``spec.stream_gap``; the run's log says when
it fell and how many streams paused with it).  A decode step is 12 to 25 ms
and a frame is ``decode_chunk`` of them; a stall of the engine, the proxy or
the machine (0.3 to 3 s: ROADMAP S14) stands out by an order of magnitude
and leaves its mark in the run that had it."""

from chipbench.spec import stream_gap


def read(evidence):
    gap = stream_gap(evidence.get("rows", ()),
                     evidence.get("seconds", float("inf")))
    return gap["max_ms"] if gap else None
