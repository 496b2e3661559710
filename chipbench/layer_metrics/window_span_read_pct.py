"""``window_span_read_pct``: of the positions a decoding row holds, the share
ONE window layer's decode attention read, between the two ledger reads: 100 x
``decode_window_positions`` (``min(length + 1, window)`` a decoding row a
token-step) over ``decode_full_positions`` (``length + 1``: what one full
layer read, and what a window layer would read of a cache that keeps every
position).  The decode program books both itself.  100 where every row is
shorter than the window; the lower, the more of a long row's positions the
window layers leave alone: in the bytes a token-step's attention reads and in
the cache a sequence holds."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "decode_window_positions",
                                   "decode_full_positions")
