"""``mla_decode_roofline_pct``: the latent decode kernel's share of its
roofline.  The least time the chip could take for the kernel's work in the
traced decode runs, over the kernel's self time in them.

Work: a decode token-step's kernel calls (one a layer) touch every live
position of the decoding rows: ``model_math_mla_moe.mla_kernel_flops`` and
``mla_kernel_bytes`` (a position a layer: 128 heads x (576 + 512) x 2
operations, 1,152 B), and the least time is the LARGER of operations over
peak FLOP/s and bytes over peak HBM bytes/s (on a v5e the two are within a
percent of each other).  Live positions a token-step: the engine's
``decode_live_pages`` a dispatch between the two ledger reads, times the
block size (the blocks decoding rows hold, so up to a block a row more than
the positions: under 0.4% at 8k positions).  Token-steps traced: the decode
program's runs in the trace (module ``jit__decode_chunk_impl``) times
``decode_chunk``.  The ledger's mean stands for the traced seconds (a steady
window; the drain after it has fewer rows, so the mean reads low and so does
the share)."""

from chipbench import ledger_window, model_math, model_math_mla_moe, trace_reduce

KERNEL = r"mla_paged_attention|tpu_custom_call"
PROGRAM = r"^jit__decode_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    pages = ledger_window.counter_delta(evidence, "decode_live_pages")
    dispatches = ledger_window.counter_delta(evidence, "decode_dispatches")
    if not trace or not pages or not dispatches:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    kernel_s = trace_reduce.op_self_seconds(trace["planes"], KERNEL, PROGRAM)
    if not runs or kernel_s <= 0:
        return None
    cfg = evidence["config"]
    live = pages / dispatches * cfg["engine"]["block_size"]
    peak = model_math.peaks(evidence["report"]["device_kind"])
    least_s = max(
        model_math_mla_moe.mla_kernel_flops(cfg, live) / peak["flops_per_s"],
        model_math_mla_moe.mla_kernel_bytes(cfg, live) / peak["hbm_bytes_per_s"])
    return 100.0 * least_s * len(runs) * evidence["decode_chunk"] / kernel_s
