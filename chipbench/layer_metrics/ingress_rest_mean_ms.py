"""``ingress_rest_mean_ms``: what is left of the proxy's mean time to first
token (its ledger sketch, cut to the window) after the means of the engine's
``queue_wait``, ``prefill``, ``first_emit`` and ``stream_out`` stages: the
proxy, the handle, the replica's dispatch, the wait for the engine's lock
(``enqueue_wait_mean_ms``, reported on its own) and the way back.  A
difference of MEANS, so the five add up to the ledger's mean by construction
(``ingress_overhead_p50_ms`` is a difference of medians and does not)."""

from chipbench import ledger_window
from chipbench.spec import log

STAGES = ("queue_wait", "prefill", "first_emit", "stream_out")


def read(evidence):
    ttft = ledger_window.window_sum_count(evidence, ledger_window.TTFT)
    stages = [ledger_window.window_sum_count(evidence, ledger_window.STAGE, s)
              for s in STAGES]
    if ttft is None or None in stages:
        return None
    means = [1e3 * s / n for s, n in stages]
    # the means add up as far as the stages were booked for the same requests
    # (the two reads cut each sketch at the same instants, not at the same
    # request), so the counts are part of the reading
    log("first-token stages between the ledger reads, mean ms (count): "
        f"ttft {1e3 * ttft[0] / ttft[1]:.1f} ({ttft[1]}), " + ", ".join(
            f"{s} {m:.1f} ({n})" for s, m, (_, n) in zip(STAGES, means, stages)))
    own = [sum(r[f"{s}_s"] for s in STAGES)
           for r in ledger_window.engine_rows(evidence)
           if all(f"{s}_s" in r for s in STAGES)]
    if own:
        log(f"engine rows in the ledger's tail: {len(own)} requests, their own "
            f"enqueue -> first yield mean {1e3 * sum(own) / len(own):.1f} ms; "
            f"the four stage means sum to {sum(means):.1f} ms")
    return 1e3 * ttft[0] / ttft[1] - sum(means)
