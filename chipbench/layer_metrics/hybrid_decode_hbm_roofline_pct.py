"""``hybrid_decode_hbm_roofline_pct``: the bytes a whole decode token-step of
a hybrid state-space configuration must move
(``model_math_granite_hybrid.decode_step_bytes``: every weight once, the
decoding rows' state and window both ways, 8,192 B a live position) over what
the chip's HBM moves in the token-step's device time (``decode_step_ms`` from
the trace x peak bytes/s): the share of the WHOLE step.  Decoding rows and
live positions: counted over the dispatches the trace holds (``hybrid_rows``:
the ``slots``, ``chunk`` and ``pages`` of every ``engine.decode_dispatch``
region).  Nothing is read on a program whose regions carry no such stats."""

import statistics

from chipbench import hybrid_rows, model_math, trace_reduce
from chipbench import model_math_granite_hybrid as math_

PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    rows, live = hybrid_rows.rows(evidence), hybrid_rows.positions(evidence)
    if not rows or live is None:
        return None
    runs = trace_reduce.module_durations(evidence["trace"]["planes"], PROGRAM)
    if not runs:
        return None
    step_s = statistics.median(runs) / evidence["decode_chunk"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    return (100.0 * math_.decode_step_bytes(evidence["config"], rows, live)
            / (step_s * peak["hbm_bytes_per_s"]))
