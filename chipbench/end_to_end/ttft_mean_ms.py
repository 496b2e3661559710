"""``ttft_mean_ms``: mean, over every request due in the window, of (first
streamed token received - time the request was DUE), client's clock.  A
request that failed misses: it counts with the time to the drain limit.

The mean and not a percentile where a window holds tens of requests: the
engine hands out prompt chunks one step at a time, so a single request's time
to first token moves by a whole step (a quarter of a second) with the phase of
its arrival, and a percentile of 75 requests rests on one or two of them."""

from chipbench.spec import ttft_ms


def read(evidence):
    vals = ttft_ms(evidence)
    return sum(vals) / len(vals) if vals else None
