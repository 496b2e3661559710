"""``prefix_hit_pct``: of the prompt tokens admitted between the two ledger
reads, the share admission found in the prefix cache (``prefix_hit_tokens``)
rather than ran through the model (``prefill_tokens``; recompute after a
preemption counts there)."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "prefix_hit_tokens",
                                   "prefix_hit_tokens", "prefill_tokens")
