"""``prefill_device_share_pct``: time of the prefill programs' runs (module
``jit__prefill_chunk_impl``, first device) over device busy time, in the
trace: the share of the device the prompts cost, which the decode streams
beside them pay as a slower pace."""

from chipbench import trace_reduce

PROGRAM = r"^jit__prefill_chunk_impl"


def read(evidence):
    trace = evidence.get("trace")
    if not trace:
        return None
    runs = trace_reduce.module_durations(trace["planes"], PROGRAM)
    busy_s = trace_reduce.busy(trace["planes"])[0]
    if not runs or busy_s <= 0:
        return None
    return 100.0 * sum(runs) / busy_s
