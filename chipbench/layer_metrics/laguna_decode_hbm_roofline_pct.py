"""``laguna_decode_hbm_roofline_pct``: the bytes a whole decode token-step of
a Laguna share must move (``model_math_laguna.decode_step_bytes``: every
weight a token-step needs once, the held routed experts counted by the share
of them that a token-step's rows HIT, 4 KiB a position a layer over the
positions the decoding rows read: all of a row's in the full layers, its last
``sliding_window`` in the window layers) over what the chip's HBM moves in the
token-step's device time (``decode_step_ms`` from the trace x peak bytes/s):
the WORK of the whole step, whatever reads it, so that no later skip can read
over 100.  Decoding rows: counted over the dispatches the trace holds
(``hybrid_rows.rows``); positions a decoding row: ``decode_full_positions``
and ``decode_window_positions`` over ``decode_live_rows`` between the two
ledger reads; experts hit: ``moe_experts_hit`` over ``moe_experts_held``
there (the decode program books all five a token-step).  Nothing is read on a
program whose regions carry no such stats or that books no such counters."""

import statistics

from chipbench import hybrid_rows, ledger_window, model_math, spec, trace_reduce
from chipbench import model_math_laguna as math_

PROGRAM = r"^jit__decode_chunk_impl"


def share_pct(cfg, step_s, window_positions, full_positions, experts_hit,
              hbm_bytes_per_s):
    """The share, given the step's device time and what it moved."""
    return (100.0 * math_.decode_step_bytes(cfg, window_positions,
                                            full_positions, experts_hit)
            / (step_s * hbm_bytes_per_s))


def read(evidence):
    a_row = spec.load_module(
        "layer_metrics", "window_attn_decode_roofline_pct").positions_a_row
    rows = hybrid_rows.rows(evidence)
    window = a_row(evidence, "decode_window_positions")
    full = a_row(evidence, "decode_full_positions")
    hit = ledger_window.counter_delta(evidence, "moe_experts_hit")
    held = ledger_window.counter_delta(evidence, "moe_experts_held")
    if not rows or not window or not full or not hit or not held:
        return None
    runs = trace_reduce.module_durations(evidence["trace"]["planes"], PROGRAM)
    if not runs:
        return None
    step_s = statistics.median(runs) / evidence["decode_chunk"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    return share_pct(evidence["config"], step_s, rows * window, rows * full,
                     hit / held, peak["hbm_bytes_per_s"])
