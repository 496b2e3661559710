"""``kda_decode_share_pct``: device self time of the KDA state-update kernel
over device busy time, in the trace (first device).  The kernel is found by
its NAME, ``kda_state_update`` (``pl.pallas_call(name=)`` names the HLO
instruction, and a trace names an operation by its instruction), inside the
decode program (``jit__decode_chunk_impl``): that program has two Pallas
kernels."""

from chipbench import trace_reduce

KERNEL = r"^kda_state_update"
PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    return trace_reduce.share_pct(trace["planes"], KERNEL, PROGRAM) or None
