"""``prefill_stage_mean_ms``: mean of the engine's ``prefill`` stage (first
admitted to a slot -> final prompt chunk dispatched: all of a request's
chunks, and the decode chunks interleaved with them) over what was booked
between the two ledger reads.  The mean beside ``prefill_stage_p50_ms``: means
of stages add up to the mean time to first token, medians do not."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.stage_mean_ms(evidence, "prefill")
