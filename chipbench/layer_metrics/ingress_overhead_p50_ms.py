"""``ingress_overhead_p50_ms``: what the request path adds around the engine:
the proxy's ledger median of time to first token, less the medians of the
engine's ``queue_wait`` and ``prefill`` stages, all cut to the window.  A
DIFFERENCE OF MEDIANS: the ledger's rows carry no stage times per request, so
the per-request form is not available (listed for the tracing issue)."""

from chipbench import ledger


def read(evidence):
    ttft = ledger.window_quantile_ms(evidence, ledger.TTFT, 0.5)
    wait = ledger.window_quantile_ms(evidence, ledger.STAGE, 0.5, "queue_wait")
    prefill = ledger.window_quantile_ms(evidence, ledger.STAGE, 0.5, "prefill")
    if None in (ttft, wait, prefill):
        return None
    return ttft - wait - prefill
