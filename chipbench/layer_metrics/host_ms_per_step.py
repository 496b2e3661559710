"""``host_ms_per_step``: host time of an engine step: the step's wall time
less the time it was blocked in device reads (``host_s`` over ``steps``,
between the two ledger reads).  Hidden while a decode chunk is in flight;
what the device waits for once it is not."""

from chipbench import ledger_window


def read(evidence):
    host_s = ledger_window.counter_delta(evidence, "host_s")
    steps = ledger_window.counter_delta(evidence, "steps")
    if host_s is None or not steps:
        return None
    return host_s / steps * 1e3
