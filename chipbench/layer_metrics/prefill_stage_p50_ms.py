"""``prefill_stage_p50_ms``: median of the engine's ``prefill`` stage
(admitted -> last prompt chunk done, all of a request's chunks and the decode
chunks interleaved with them), from the server's ledger cut to the window."""

from chipbench import ledger


def read(evidence):
    return ledger.window_quantile_ms(evidence, ledger.STAGE, 0.5, "prefill")
