"""What the engine dispatched IN THE TRACED SECONDS, for the hybrid
state-space cell's roofline readers: counted, not estimated.

Under bursty arrivals (``chat_bursty``: gamma, cv 2) the rows that decode in
the traced seconds are up to twice or half the window's mean, so a share of a
roofline that sets the window's bytes against the trace's time reads half, or
twice, what the kernel did.  The engine marks every dispatch with a region on
the profiler's host plane (``ray_tpu/util/tracing.py`` ``region``; README has
the table), and the region's attributes are in the trace as the event's
stats: ``engine.decode_dispatch`` carries ``slots`` (the rows that decode: what
``decode_live_rows`` books, a token-step of the dispatch), ``chunk`` (its
token-steps) and ``pages`` (the blocks those rows hold);
``engine.prefill_chunk`` carries ``tokens`` (the chunk's real tokens), ``p0``
(its first position) and ``is_last``.  ``trace_reduce.load`` keeps an event's
name and times and not its stats, so the kind reads them from the trace's file
while it is there (``serve_open_hybrid``: round ``serving.capture_trace``) and
leaves them in ``evidence["trace"]["regions"]``.

A dispatch at the capture's edge may run on the device outside it, and the
other way round: the readers take a MEAN over the marked dispatches (rows a
token-step, operations a chunk) and the count of the device's own runs.
"""

from __future__ import annotations

DECODE = "engine.decode_dispatch"
PREFILL = "engine.prefill_chunk"


def regions(path: str) -> dict:
    """``{"decode": [{slots, chunk, pages, ..}], "prefill": [{tokens, p0,
    is_last, ..}]}``: the stats of every dispatch region in the trace file."""
    from jax.profiler import ProfileData

    out = {"decode": [], "prefill": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == DECODE:
                    out["decode"].append(dict(e.stats))
                elif e.name == PREFILL:
                    out["prefill"].append(dict(e.stats))
    return out


def _marked(evidence: dict, kind: str, *stats) -> list:
    """The traced dispatches of ``kind`` that carry every one of ``stats``
    (a program that books fewer gives none)."""
    found = ((evidence.get("trace") or {}).get("regions") or {}).get(kind)
    return [r for r in found or () if all(s in r for s in stats)]


def rows(evidence: dict):
    """Rows that decoded a token-step, over the traced dispatches; or None."""
    got = _marked(evidence, "decode", "slots", "chunk")
    steps = sum(r["chunk"] for r in got)
    return sum(r["slots"] * r["chunk"] for r in got) / steps if steps else None


def positions(evidence: dict):
    """Cached positions the decoding rows held, a traced dispatch; or None."""
    got = _marked(evidence, "decode", "pages")
    if not got:
        return None
    bs = evidence["config"]["engine"]["block_size"]
    return sum(r["pages"] for r in got) / len(got) * bs


def prefill_chunks(evidence: dict) -> list:
    """``[(first position, real tokens, is the prompt's last)]`` of the
    traced prompt chunks."""
    return [(r["p0"], r["tokens"], bool(r["is_last"]))
            for r in _marked(evidence, "prefill", "p0", "tokens", "is_last")]
