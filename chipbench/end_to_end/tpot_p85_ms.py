"""``tpot_p85_ms``: 85th percentile over the window's streams of
(last token - first token) / (tokens - 1), client's clock: the tail of the
stream's pace in a cell whose window holds 67 to 99 requests, where the 85th
is the highest percentile with ten streams beyond it (``tpot_p90_ms`` from
100 on; PERF.md section 2)."""

from chipbench.spec import percentile, tpot_ms


def read(evidence):
    vals = tpot_ms(evidence)
    return percentile(vals, 85) if vals else None
