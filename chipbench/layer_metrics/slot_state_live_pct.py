"""``slot_state_live_pct``: of the rows the decode program ran over between
the two ledger reads (``decode_rows``: ``max_batch_size`` a token-step), the
share that decoded (``decode_live_rows``).  A slot's recurrent state is read
and written only for these: it is the share of the slot-state pool a
token-step touches, and what a state update that follows the decoding rows
saves is the rest."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "decode_live_rows",
                                   "decode_rows")
