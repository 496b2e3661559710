"""``latent_prefill_mxu_pct``: how near the prefill programs of a
latent-attention expert configuration run to the chip's peak.
``prefill_mxu_pct`` for this family: the operations the window's prompts
required per second (``model_math_mla_moe.prefill_flops``: a prompt's tokens
behind its cached prefix, the traffic's shared prefix where the kind loaded
it during set-up; summed over requests whose first token arrived in the
window, over the window) divided by the share of traced time the device spent
in prefill programs (module ``jit__prefill_chunk_impl``) and by peak FLOP/s.
Assumes the traced seconds stand for the window; a chunk's padding, the
products with held experts a token did not choose and the head's other rows
are the program's cost, not counted as work."""

from chipbench import model_math, model_math_mla_moe, trace_reduce

PROGRAM = r"^jit__prefill_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    window = trace_reduce.window_seconds(trace["planes"])
    if not runs or window <= 0:
        return None
    cfg, seconds = evidence["config"], evidence["seconds"]
    traffic = evidence["traffic"]
    cached = (int(traffic["shared_prefix"]["len"])
              if traffic.get("preload_shared_prefix") else 0)
    chunk = cfg["engine"]["prefill_chunk"]
    flops = sum(model_math_mla_moe.prefill_flops(
        cfg, r["prompt_len"] - cached, cached, chunk)
        for r in evidence["rows"]
        if r["first"] is not None and 0 <= r["first"] < seconds
        and r["prompt_len"] > cached)
    if flops <= 0:
        return None
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    share = sum(runs) / window
    return 100.0 * (flops / seconds) / (share * peak)
