"""``tpot_p90_ms``: 90th percentile over the window's streams of
(last token - first token) / (tokens - 1), client's clock: the tail of the
stream's pace, at the highest percentile that has ten streams beyond it in a
window of 100 requests or more.  Cells whose window holds fewer do not
report it (PERF.md section 2)."""

from chipbench.spec import percentile, tpot_ms


def read(evidence):
    vals = tpot_ms(evidence)
    return percentile(vals, 90) if vals else None
