"""``kda_decode_roofline_pct``: the KDA state-update kernel's share of its
roofline.  The bytes the kernel MUST move in the traced decode runs over what
the chip's HBM moves in the kernel's self time in them.

Bytes: ``model_math_kimi_linear.kda_kernel_bytes``: a decoding row's matrix
state read and written once a KDA layer a token-step, and what the call reads
and writes a row beside it.  Decoding rows a token-step: COUNTED over the
dispatches the trace holds (``hybrid_rows.rows``: the ``slots`` and ``chunk``
of every ``engine.decode_dispatch`` region, which are what the engine books
as ``decode_live_rows``).  Token-steps traced: the decode program's runs in
the trace (module ``jit__decode_chunk_impl``) times ``decode_chunk``.  The
kernel is found by its NAME (``kda_state_update``).  Nothing is read on a
program without the kernel or whose dispatch regions carry no such stats."""

from chipbench import hybrid_rows, model_math, trace_reduce
from chipbench import model_math_kimi_linear as math_

KERNEL = r"^kda_state_update"
PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    rows = hybrid_rows.rows(evidence)
    if not rows:
        return None
    planes = evidence["trace"]["planes"]
    runs = trace_reduce.module_durations(planes, PROGRAM)
    kernel_s = trace_reduce.op_self_seconds(planes, KERNEL, PROGRAM)
    if not runs or kernel_s <= 0:
        return None
    peak = model_math.peaks(evidence["report"]["device_kind"])
    per_step = math_.kda_kernel_bytes(evidence["config"], rows)
    traced_steps = len(runs) * evidence["decode_chunk"]
    return (100.0 * per_step * traced_steps
            / (kernel_s * peak["hbm_bytes_per_s"]))
