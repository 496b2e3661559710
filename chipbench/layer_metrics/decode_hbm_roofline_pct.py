"""``decode_hbm_roofline_pct``: the bytes a decode token-step must read
(``model_math_mla_moe.decode_step_bytes``: the weights less the embedding,
once, the held routed experts counted by the share of them that a
token-step's rows HIT, plus 1,152 B a live position a layer) over what the
chip's HBM moves in the token-step's device time (``decode_step_ms`` from the
trace x peak bytes/s).  Live positions a token-step: ``decode_live_pages`` a
dispatch between the two ledger reads, times the block size.  Experts hit:
``moe_experts_hit`` over ``moe_experts_held`` between the same reads (the
decode program books both a token-step).  Until PR 46 every held expert
counted, wanted or not: the share read the program's traffic and not the
work, and a decode that skipped unwanted experts would have read over 100."""

import statistics

from chipbench import ledger_window, model_math, model_math_mla_moe, trace_reduce

PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    pages = ledger_window.counter_delta(evidence, "decode_live_pages")
    dispatches = ledger_window.counter_delta(evidence, "decode_dispatches")
    hit = ledger_window.counter_delta(evidence, "moe_experts_hit")
    held = ledger_window.counter_delta(evidence, "moe_experts_held")
    if not trace or not pages or not dispatches or not hit or not held:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    if not runs:
        return None
    cfg = evidence["config"]
    step_s = statistics.median(runs) / evidence["decode_chunk"]
    live = pages / dispatches * cfg["engine"]["block_size"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    return (100.0 * model_math_mla_moe.decode_step_bytes(cfg, live, hit / held)
            / (step_s * peak["hbm_bytes_per_s"]))
