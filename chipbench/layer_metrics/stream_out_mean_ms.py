"""``stream_out_mean_ms``: mean of the ``stream_out`` stage: the engine books
a request's first token -> ``LLMServer._iter_tokens`` hands its first chunk to
the replica's stream (the rest of that step, the loop's hand-over to the
waiters, the waiter's wake-up)."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.stage_mean_ms(evidence, "stream_out")
