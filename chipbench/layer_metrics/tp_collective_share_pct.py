"""``tp_collective_share_pct``: device self time of the all-reduce
operations over device busy time, in the trace, on one device (tensor
parallelism: every device runs the same program)."""

from chipbench import trace_reduce

COLLECTIVE = r"op=(all-reduce|all-gather|reduce-scatter|collective-permute)"


def read(evidence):
    trace = evidence.get("trace")
    if not trace or evidence["report"]["device_count"] < 2:
        return None
    return trace_reduce.share_pct(trace["planes"], COLLECTIVE)
