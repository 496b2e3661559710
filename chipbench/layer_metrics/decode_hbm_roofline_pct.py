"""``decode_hbm_roofline_pct``: the bytes a decode token-step must read
(``model_math_mla_moe.decode_step_bytes``: the weights less the embedding,
once, plus 1,152 B a live position a layer) over what the chip's HBM moves in
the token-step's device time (``decode_step_ms`` from the trace x peak
bytes/s).  Live positions a token-step: ``decode_live_pages`` a dispatch
between the two ledger reads, times the block size."""

import statistics

from chipbench import ledger_window, model_math, model_math_mla_moe, trace_reduce

PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    pages = ledger_window.counter_delta(evidence, "decode_live_pages")
    dispatches = ledger_window.counter_delta(evidence, "decode_dispatches")
    if not trace or not pages or not dispatches:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    if not runs:
        return None
    cfg = evidence["config"]
    step_s = statistics.median(runs) / evidence["decode_chunk"]
    live = pages / dispatches * cfg["engine"]["block_size"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    return (100.0 * model_math_mla_moe.decode_step_bytes(cfg, live)
            / (step_s * peak["hbm_bytes_per_s"]))
