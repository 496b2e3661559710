"""``ttft_tail_p95_ms``: 95th percentile of the window's times to first token
(``spec.ttft_ms``), client's clock: the tail that ``prefill_token_budget``
makes, one chunk of a long prompt a step.  Per layer in cells whose window
holds too few requests for a tail to repeat (PERF.md section 2)."""

from chipbench.spec import percentile, ttft_ms


def read(evidence):
    vals = ttft_ms(evidence)
    return percentile(vals, 95) if vals else None
