"""``decode_step_ms``: device time of one decode token-step: the median
duration of the decode program's runs in the trace (module
``jit__decode_chunk_impl``, one run = ``decode_chunk`` token-steps over the
whole batch) divided by ``decode_chunk``."""

import statistics

from chipbench import trace_reduce

PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    if not runs:
        return None
    return statistics.median(runs) * 1e3 / evidence["decode_chunk"]
