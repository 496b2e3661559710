"""``pipelined_dispatch_pct``: of the decode dispatches between the two ledger
reads, the share that found the previous chunk still in flight and queued
behind it (the device never waited for the host).  The others followed a
drain: an admission's final prompt chunk, a finished request, a preemption
(ROADMAP S2's question, answered over the whole window)."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "decode_dispatches_pipelined",
                                   "decode_dispatches")
