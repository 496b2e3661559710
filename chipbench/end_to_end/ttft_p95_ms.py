"""``ttft_p95_ms``: 95th percentile, over every request due in the window, of
(first streamed token received - time the request was DUE), client's clock.
A request that failed misses: it counts with the time to the drain limit.
For cells whose window holds some hundreds of requests; no cell reports it
yet (PERF.md section 2)."""

from chipbench.spec import percentile, ttft_ms


def read(evidence):
    vals = ttft_ms(evidence)
    return percentile(vals, 95) if vals else None
