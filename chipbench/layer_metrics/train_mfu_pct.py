"""``train_mfu_pct``: model FLOP/s utilization of this run's own window:
training operations per token (``model_math.train_flops_per_token``: matrix
multiplies without the embedding lookup, plus causal attention, forward and
backward, recomputation not counted) times tokens per second, over chips
times peak."""

from chipbench import model_math


def read(evidence):
    steps = evidence.get("window_steps")
    if not steps:
        return None
    rate = (len(steps) * evidence["tokens_per_step"]
            / (steps[-1]["end"] - steps[0]["start"]))
    per_token = model_math.train_flops_per_token(evidence["config"],
                                                 evidence["seq_len"])
    peak = model_math.peaks(evidence["report"]["device_kind"])["flops_per_s"]
    return 100.0 * per_token * rate / (peak * evidence["report"]["device_count"])
