"""``kimi_decode_hbm_roofline_pct``: the bytes a whole decode token-step of a
Kimi-Linear share must move (``model_math_kimi_linear.decode_step_bytes``:
every weight a token-step needs once, the held routed experts counted by the
share of them that a token-step's rows HIT, the decoding rows' state and
windows both ways, 1,152 B a live position an MLA layer) over what the chip's
HBM moves in the token-step's device time (``decode_step_ms`` from the trace
x peak bytes/s): the WORK of the whole step, whatever reads it, so that no
later skip can read over 100.  Decoding rows and live positions: counted over
the dispatches the trace holds (``hybrid_rows``: the ``slots``, ``chunk`` and
``pages`` of every ``engine.decode_dispatch`` region).  Experts hit:
``moe_experts_hit`` over ``moe_experts_held`` between the two ledger reads
(the decode program books both a token-step).  Nothing is read on a program
whose regions carry no such stats or that books no such counters."""

import statistics

from chipbench import hybrid_rows, ledger_window, model_math, trace_reduce
from chipbench import model_math_kimi_linear as math_

PROGRAM = r"^jit__decode_chunk_impl"


def share_pct(cfg, step_s, rows, positions, experts_hit, hbm_bytes_per_s):
    """The share, given the step's device time and what it moved."""
    return (100.0 * math_.decode_step_bytes(cfg, rows, positions, experts_hit)
            / (step_s * hbm_bytes_per_s))


def read(evidence):
    rows, live = hybrid_rows.rows(evidence), hybrid_rows.positions(evidence)
    hit = ledger_window.counter_delta(evidence, "moe_experts_hit")
    held = ledger_window.counter_delta(evidence, "moe_experts_held")
    if not rows or live is None or not hit or not held:
        return None
    runs = trace_reduce.module_durations(evidence["trace"]["planes"], PROGRAM)
    if not runs:
        return None
    step_s = statistics.median(runs) / evidence["decode_chunk"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    return share_pct(evidence["config"], step_s, rows, live, hit / held,
                     peak["hbm_bytes_per_s"])
