"""``laguna_serve_mfu_pct``: the share of the chip's peak FLOP/s that the
WHOLE served step of a Laguna share used between the two ledger reads: the
operations of the prompt tokens taken in and of the tokens emitted
(``model_math_laguna.served_flops`` of the engine's counters
``prefill_tokens`` and ``tokens_emitted``) a second (the seconds between the
two rows that carry the counters) over peak FLOP/s.  No trace is needed.
Attention's pairs are left out of the operations (no counter books a prompt's
positions), so it reads the lower for it; a decode token-step is bound by HBM
traffic, not by operations, so this share is small by nature and says how
much of the matrix unit a serving chip leaves unused."""

from chipbench import ledger_window, model_math
from chipbench import model_math_laguna as math_


def read(evidence):
    prompt = ledger_window.counter_delta(evidence, "prefill_tokens")
    emitted = ledger_window.counter_delta(evidence, "tokens_emitted")
    seconds = ledger_window.seconds_between(evidence)
    if prompt is None or emitted is None or not seconds:
        return None
    if prompt + emitted <= 0:
        return None
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    return (100.0 * math_.served_flops(evidence["config"], prompt, emitted)
            / (seconds * peak))
