"""Means and counter differences between the evidence's two ledger reads.

``ledger.py`` cuts the server's sketches to the window bucket by bucket, for
quantiles.  The sketches also carry ``sum`` and ``count``, so the MEAN of what
was booked between the two reads is exact arithmetic, with no bucket error:
(sum_after - sum_before) / (count_after - count_before).  Means add up where
medians do not, which is what the first-token stage metrics need.

The replica's row also carries its engines' cumulative counters under
``engine[<deployment>]`` (``ray_tpu/serve/_private/slo.py``
``register_engine``), and its own ``time`` (the wall clock when it was
published): a counter's difference between the two reads belongs to the
seconds between the two rows' times, not between the reads.

Every function returns None where its source is missing (a program from
before these were booked, a run without the ledger reads), and raises
nothing.
"""

from __future__ import annotations

from chipbench.ledger import STAGE, TTFT  # noqa: F401 - the families' names


def _sum_count(rows: list, family: str, deployment: str, split: str = None):
    """``(sum of seconds, count)`` of every point of ``family`` for
    ``deployment`` (and stage ``split``) over all reporters' rows."""
    total, n = 0.0, 0
    for row in rows or ():
        for p in row.get("points", ()):
            tags = p.get("tags", {})
            if p.get("name") != family or tags.get("deployment") != deployment:
                continue
            if split is not None and tags.get("stage") != split:
                continue
            total += float(p.get("sum", 0.0))
            n += int(p.get("count", 0))
    return total, n


def window_sum_count(evidence: dict, family: str, split: str = None):
    """``(seconds, count)`` that ``family`` (stage ``split``) booked between
    the two ledger reads, or None where it booked nothing."""
    if not evidence.get("ledger_after"):
        return None
    dep = evidence["deployment"]
    s1, n1 = _sum_count(evidence["ledger_after"], family, dep, split)
    s0, n0 = _sum_count(evidence.get("ledger_before"), family, dep, split)
    if n1 - n0 <= 0:
        return None
    return s1 - s0, n1 - n0


def window_mean_ms(evidence: dict, family: str, split: str = None):
    """Mean, in ms, of what ``family`` (stage ``split``) booked between the
    two ledger reads, or None where it booked nothing."""
    got = window_sum_count(evidence, family, split)
    return None if got is None else got[0] / got[1] * 1e3


def stage_mean_ms(evidence: dict, stage: str):
    return window_mean_ms(evidence, STAGE, stage)


def engine_rows(evidence: dict) -> list:
    """The per-request rows (``kind: "engine"``) in the tail of the recent
    ring that the second ledger read carries: the newest requests' own stage
    times, each row one request."""
    return [r for row in evidence.get("ledger_after") or ()
            for r in row.get("recent") or ()
            if r.get("kind") == "engine"
            and r.get("deployment") == evidence["deployment"]]


def _engine(rows: list, deployment: str):
    """``(summed counters, mean row time)`` over the rows that carry this
    deployment's engine counters, or None."""
    found = [(row["engine"][deployment], float(row.get("time", 0.0)))
             for row in rows or ()
             if isinstance(row.get("engine"), dict)
             and deployment in row["engine"]]
    if not found:
        return None
    total: dict = {}
    for counters, _ in found:
        for k, v in counters.items():
            if isinstance(v, (int, float)):
                total[k] = total.get(k, 0) + v
    return total, sum(t for _, t in found) / len(found)


def counter_delta(evidence: dict, name: str):
    """How much the engine counter ``name`` grew between the two reads."""
    if not evidence.get("ledger_after"):
        return None
    after = _engine(evidence["ledger_after"], evidence["deployment"])
    before = _engine(evidence.get("ledger_before"), evidence["deployment"])
    if after is None or before is None:
        return None
    if name not in after[0] or name not in before[0]:
        return None
    return after[0][name] - before[0][name]


def seconds_between(evidence: dict):
    """Seconds between the publication of the two rows that carry the
    engine counters (the time the counter differences belong to)."""
    if not evidence.get("ledger_after"):
        return None
    after = _engine(evidence["ledger_after"], evidence["deployment"])
    before = _engine(evidence.get("ledger_before"), evidence["deployment"])
    if after is None or before is None or after[1] <= before[1]:
        return None
    return after[1] - before[1]


def ratio_pct(evidence: dict, part: str, *whole: str):
    """100 x the growth of ``part`` over the summed growth of ``whole``."""
    top = counter_delta(evidence, part)
    parts = [counter_delta(evidence, w) for w in whole]
    if top is None or None in parts or sum(parts) <= 0:
        return None
    return 100.0 * top / sum(parts)
