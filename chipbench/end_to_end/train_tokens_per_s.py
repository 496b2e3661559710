"""``train_tokens_per_s``: tokens of the steps completed in the window over
the window, worker's clock around steps that each end with the loss read on
the host (the device has then finished the step)."""

from chipbench.spec import log, percentile


def read(evidence):
    steps = evidence.get("window_steps")
    if not steps:
        return None
    span = steps[-1]["end"] - steps[0]["start"]
    times = [s["end"] - s["start"] for s in steps]
    log(f"train: {len(steps)} steps in {span:.2f}s, step p50 "
        f"{percentile(times, 50) * 1e3:.1f}ms max {max(times) * 1e3:.1f}ms")
    return len(steps) * evidence["tokens_per_step"] / span
