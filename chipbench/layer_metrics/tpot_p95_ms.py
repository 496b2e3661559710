"""``tpot_p95_ms``: 95th percentile over the window's streams of
(last token - first token) / (tokens - 1), client's clock.  An end-to-end
metric from PR 23 to PR 45, per layer since PR 46 under the same name and
definition, so that the ledger keeps its history: the 95th percentile of 75
to 150 streams rests on 4 to 8 of them, and which streams sit there is an
accident of the schedule (PERF.md section 2).  ``tpot_mean_ms`` is the gate."""

from chipbench.spec import percentile, tpot_ms


def read(evidence):
    vals = tpot_ms(evidence)
    return percentile(vals, 95) if vals else None
