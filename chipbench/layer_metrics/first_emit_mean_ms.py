"""``first_emit_mean_ms``: mean of the engine's ``first_emit`` stage: final
prompt chunk dispatched -> the engine books the request's first token
(``_emit_locked``).  The first token is sampled by that final chunk; what this
stage holds is the drain that follows in the same step: the read of the decode
chunk that was already in flight, then of the chunk's own result."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.stage_mean_ms(evidence, "first_emit")
