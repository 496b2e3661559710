"""``prefill_mxu_pct``: how near the prefill programs run to the chip's peak:
the operations the window's prompts required per second (``model_math``: each
prompt of n tokens needs n * prefill_flops_per_token(n), summed over requests
whose first token arrived in the window, over the window) divided by the share
of traced time the device spent in prefill programs (module
``jit__prefill_chunk_impl``) and by peak FLOP/s times chips.  Assumes the
traced seconds stand for the window (a steady window); padding of a chunk to
its bucket is the program's cost, not counted as work."""

from chipbench import model_math, trace_reduce

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
    flops = sum(r["prompt_len"] * model_math.prefill_flops_per_token(
        cfg, r["prompt_len"]) for r in evidence["rows"]
        if r["first"] is not None and 0 <= r["first"] < seconds)
    if flops <= 0:
        return None
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    share = sum(runs) / window
    return 100.0 * (flops / seconds) / (share * peak * evidence["report"]["device_count"])
