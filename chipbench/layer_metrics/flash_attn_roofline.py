"""``flash_attn_roofline``: the flash-attention kernels' share of their
roofline in the train step: attention operations of the traced steps
(``model_math.flash_attention_flops``: forward plus backward, causal, the
backward's recomputation of scores not counted) over the device self time of
the flash kernels (forward and backward) in the trace, over peak FLOP/s.  The
compute bound applies: at sequence 2048 and head size 128 the kernel does
about 2048 operations a byte it must move, far over the chip's 240."""

from chipbench import model_math, trace_reduce

KERNEL = r"tpu_custom_call"  # the step's only Pallas kernels are flash's


def read(evidence):
    trace = evidence.get("trace")
    if not trace or not evidence.get("traced_steps"):
        return None
    seconds = trace_reduce.op_self_seconds(trace["planes"], KERNEL)
    if seconds <= 0:
        return None
    flops = evidence["traced_steps"] * model_math.flash_attention_flops(
        evidence["config"], evidence["batch"], evidence["seq_len"])
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    return 100.0 * flops / seconds / peak
