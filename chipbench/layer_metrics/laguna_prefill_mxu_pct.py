"""``laguna_prefill_mxu_pct``: how near the prefill programs of a Laguna share
run to the chip's peak.  The operations the TRACED prompt chunks needed
(``model_math_laguna.chunk_flops`` of every ``engine.prefill_chunk`` region
the trace holds: its real tokens, its first position, whether it ends its
prompt; ``hybrid_rows.prefill_chunks``), as a mean a chunk, times the prefill
program's runs in the trace (module ``jit__prefill_chunk_impl``), over those
runs' device time x peak FLOP/s.  A window layer's attention counts as
WINDOWED work (a query sees its last ``sliding_window`` keys); a chunk's
padding, masked pairs, the products with held experts a token did not choose
and the head's other rows are the program's cost, not counted as work.
Nothing is read on a program whose regions carry no ``p0``."""

from chipbench import hybrid_rows, model_math, trace_reduce
from chipbench import model_math_laguna as math_

PROGRAM = r"^jit__prefill_chunk_impl"


def read(evidence):
    chunks = hybrid_rows.prefill_chunks(evidence)
    if not chunks:
        return None
    runs = trace_reduce.module_durations(evidence["trace"]["planes"], PROGRAM)
    if not runs:
        return None
    cfg = evidence["config"]
    a_chunk = sum(math_.chunk_flops(cfg, *c) for c in chunks) / len(chunks)
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    return 100.0 * a_chunk * len(runs) / (sum(runs) * peak)
