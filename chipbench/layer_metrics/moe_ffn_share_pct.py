"""``moe_ffn_share_pct``: device self time of the expert layers'
feed-forward (the program's scope ``moe``: router, shared expert, the held
experts) in the decode program, over device busy time, in the trace.

A trace names a device operation by its HLO text, which carries no scope, so
the scope's operations are found by what only they produce in the decode
program (``jit__decode_chunk_impl``, batch B rows): outputs of the router's
width (``[B,256]``, and its top-k's ``[B,8]``), of the held gates
(``[B,16]``), of the experts' hidden width (``[B,16,2048]``, ``[B,32768]``,
the shared expert's ``[B,2048]``), and the float32 ``[B,hidden]`` the two
down-projections leave (every other projection of the step leaves bf16).
An operation the compiler fused across the scope's edge is counted by its
output, and one with several outputs has no shape in the reduced trace: the
shared expert's down-projection, which the compiler fuses with the sum of the
two parts and the following norm's sum of squares, is left out (31 MB of an
expert layer's 0.8 GB of feed-forward weights: 4%).  Reading the scope itself
needs ``trace_reduce.load`` to keep an event's ``op_name`` (PERF.md section
7)."""

import re

from chipbench import trace_reduce

PROGRAM = r"^jit__decode_chunk_impl"


def pattern(cfg: dict) -> str:
    b = cfg["engine"]["max_batch_size"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    k, r, d = cfg["num_experts_per_tok"], cfg["router_outputs"], cfg["hidden_size"]
    shapes = [f"{b},{r}", f"{b},{k}", f"{b},{k},{e}", f"{b},{e}",
              f"{b},{e},{f}", f"{b},{e * f}", f"{b},{fs}"]
    return (r"\[(?:" + "|".join(re.escape(s) for s in shapes) + r")\]"
            + rf"|f32\[{b},{d}\]")


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    return trace_reduce.share_pct(trace["planes"],
                                  pattern(evidence["config"]), PROGRAM) or None
