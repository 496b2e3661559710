"""``prefill_tokens_per_s``: prompt tokens the engine ran through the model
(its ``prefill_tokens`` counter: padding and prefix-cache hits not counted,
recompute after a preemption counted) between the two ledger reads, over the
seconds between them: the server's own count of its intake."""

from chipbench import ledger_window
from chipbench.spec import log


def read(evidence):
    tokens = ledger_window.counter_delta(evidence, "prefill_tokens")
    seconds = ledger_window.seconds_between(evidence)
    if tokens is None or seconds is None:
        return None
    # the second read follows the drain, so the seconds outlast the window's
    # arrivals: the log lets a reader hold the count against the client's
    log(f"prefill_tokens +{tokens:.0f} (prefix hits "
        f"+{ledger_window.counter_delta(evidence, 'prefix_hit_tokens') or 0:.0f})"
        f" in the {seconds:.1f} s between the two ledger rows")
    return tokens / seconds
