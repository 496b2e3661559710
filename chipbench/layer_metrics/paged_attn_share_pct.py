"""``paged_attn_share_pct``: device self time of the paged-attention kernel
over device busy time, in the trace (first device).  The kernel has no name
of its own in the trace: it is the one Pallas kernel (``tpu_custom_call``) of
the decode program (``jit__decode_chunk_impl``), and is found as that."""

from chipbench import trace_reduce

KERNEL = r"tpu_custom_call"
PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    return trace_reduce.share_pct(trace["planes"], KERNEL, PROGRAM) or None
