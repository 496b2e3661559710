"""``window_attn_decode_roofline_pct``: the window layers' decode attention
calls' share of their roofline.  The bytes the calls MUST move in the traced
decode runs over what the chip's HBM moves in the calls' self time in them.

Bytes: ``model_math_laguna.window_attention_bytes``: the keys and values of
the ring positions a decoding row reads (``min(length + 1, window)``), every
window layer, a token-step.  Ring positions a token-step: the decoding rows
COUNTED over the dispatches the trace holds (``hybrid_rows.rows``) times what
a decoding row read between the two ledger reads (``decode_window_positions``
over ``decode_live_rows``: at most the window, and nearly it for every row
past it, so the mean holds for the traced seconds).  Token-steps traced: the
decode program's runs in the trace (module ``jit__decode_chunk_impl``) times
``decode_chunk``.  The calls are found by their NAME
(``window_paged_attention``; the full layers' are ``paged_attention``).
Nothing is read on a program without the name, whose dispatch regions carry
no such stats or that books no such counters."""

from chipbench import hybrid_rows, ledger_window, model_math, trace_reduce
from chipbench import model_math_laguna as math_

KERNEL = r"^window_paged_attention"
PROGRAM = r"^jit__decode_chunk_impl"


def positions_a_row(evidence, counter):
    """What ``counter`` grew a decoding row between the ledger reads."""
    got = ledger_window.counter_delta(evidence, counter)
    rows = ledger_window.counter_delta(evidence, "decode_live_rows")
    return got / rows if got and rows else None


def share_pct(evidence, kernel, counter, bytes_a_step):
    """The calls named ``kernel``: ``bytes_a_step(config, positions)`` for the
    positions ONE layer of their kind read a token-step (the rows counted x
    ``counter`` a row), over the traced token-steps, against what the HBM
    moves in the calls' self time.  ``full_attn_decode_roofline_pct`` is
    this with the full layers' name, counter and bytes."""
    rows = hybrid_rows.rows(evidence)
    a_row = positions_a_row(evidence, counter)
    if not rows or not a_row:
        return None
    planes = evidence["trace"]["planes"]
    runs = trace_reduce.module_durations(planes, PROGRAM)
    kernel_s = trace_reduce.op_self_seconds(planes, kernel, PROGRAM)
    if not runs or kernel_s <= 0:
        return None
    peak = model_math.peaks(evidence["report"]["device_kind"])
    per_step = bytes_a_step(evidence["config"], rows * a_row)
    traced_steps = len(runs) * evidence["decode_chunk"]
    return (100.0 * per_step * traced_steps
            / (kernel_s * peak["hbm_bytes_per_s"]))


def read(evidence):
    return share_pct(evidence, KERNEL, "decode_window_positions",
                     math_.window_attention_bytes)
