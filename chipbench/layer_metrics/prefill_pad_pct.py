"""``prefill_pad_pct``: of the token positions the prefill programs ran, the
share that was bucket padding (``prefill_padded_tokens`` over itself plus
``prefill_tokens``): a chunk is padded up to a power-of-two bucket."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "prefill_padded_tokens",
                                   "prefill_padded_tokens", "prefill_tokens")
