"""Cutting the server's request ledger to the window.

The program keeps cumulative quantile sketches (log-spaced buckets, relative
accuracy ``a``: bucket ``i`` covers ``(g^(i-1), g^i]`` with
``g = (1 + a) / (1 - a)``) of time to first token, of the gap between tokens
and of the engine's stages ``queue_wait`` / ``prefill`` / ``decode``, and
every process publishes its sketches as a row to the cluster's key-value
store about every 2 s.  They include the warm-up and the ramp.  The benchmark
reads the rows at the window's start and after its end and subtracts bucket by
bucket: what is left is the window's requests (to within the 2 s publishing
lag, at both ends).  The arithmetic here is the benchmark's own copy.
"""

from __future__ import annotations

import math

TTFT = "ray_tpu_serve_ttft_seconds"
STAGE = "ray_tpu_serve_stage_seconds"


def _fold(rows: list, family: str, deployment: str, split: str = None) -> dict:
    """Sum of the bucket counts of every point of ``family`` for
    ``deployment`` (and stage ``split``) over all reporters' rows."""
    out = {"bins": {}, "zero": 0, "count": 0, "accuracy": None}
    for row in rows or ():
        for p in row.get("points", ()):
            tags = p.get("tags", {})
            if p.get("name") != family or tags.get("deployment") != deployment:
                continue
            if split is not None and tags.get("stage") != split:
                continue
            out["accuracy"] = p.get("accuracy", 0.01)
            out["zero"] += int(p.get("zero", 0))
            out["count"] += int(p.get("count", 0))
            for i, c in p.get("bins", ()):
                out["bins"][int(i)] = out["bins"].get(int(i), 0) + int(c)
    return out


def window_sketch(before: list, after: list, family: str, deployment: str,
                  split: str = None) -> dict:
    """The sketch of what was booked between the two reads."""
    a = _fold(after, family, deployment, split)
    b = _fold(before, family, deployment, split)
    bins = {i: c - b["bins"].get(i, 0) for i, c in a["bins"].items()}
    return {"bins": {i: c for i, c in bins.items() if c > 0},
            "zero": max(0, a["zero"] - b["zero"]),
            "count": max(0, a["count"] - b["count"]),
            "accuracy": a["accuracy"] or b["accuracy"] or 0.01}


def window_quantile_ms(evidence: dict, family: str, q: float,
                       split: str = None):
    """Milliseconds at rank ``q`` of ``family`` (stage ``split``) between
    the evidence's two ledger reads, or None where there is nothing."""
    if not evidence.get("ledger_after"):
        return None
    v = quantile(window_sketch(evidence["ledger_before"],
                               evidence["ledger_after"], family,
                               evidence["deployment"], split), q)
    return None if v is None else v * 1e3


def quantile(sketch: dict, q: float):
    """Seconds at rank ``q`` (0..1) of a window sketch, or None when it
    holds nothing."""
    n = sketch["zero"] + sum(sketch["bins"].values())
    if n <= 0:
        return None
    acc = sketch["accuracy"]
    gamma = (1.0 + acc) / (1.0 - acc)
    rank = q * (n - 1)
    seen = sketch["zero"]
    if rank < seen:
        return 0.0
    for i in sorted(sketch["bins"]):
        seen += sketch["bins"][i]
        if rank < seen:
            return 2.0 * math.pow(gamma, i) / (gamma + 1.0)
    return 2.0 * math.pow(gamma, max(sketch["bins"])) / (gamma + 1.0)
