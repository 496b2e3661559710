"""``prefill_span_live_pct``: of the KV pages the prefill chunks' attention
loop visited between the two ledger reads (``prefill_visited_pages``: per
chunk dispatch the whole KV tiles up to the chunk's end), the share that held
the prompt through that chunk (``prefill_live_pages``).  What is left is the
tile's rounding and the chunk's bucket padding.  A program that attends the
whole fixed-width table (the parent of the PR that added the counters) books
neither, and the metric is left out."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "prefill_live_pages",
                                   "prefill_visited_pages")
