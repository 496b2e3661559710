"""``engine_idle_share_pct``: the device's idle share by the program's own
account, over the whole window and with no capture: ``device_empty_s`` (the
engine's counter: seconds from the moment the host LEARNED that nothing is
queued on the device, a drain whose reads left no chunk in flight, to the next
dispatch of a program, while a request is live) between the two ledger reads,
over the seconds between them.  To be read beside the trace's idle share
(``device.busy_s`` over ``window_s``, 4 s of the window): the trace also sees
a device that runs dry behind a chunk the host has not read yet, which this
counter cannot; this counter sees the untraced run's conditions, which a
capture changes.  None where the program books no such counter."""

from chipbench import ledger_window


def read(evidence):
    empty_s = ledger_window.counter_delta(evidence, "device_empty_s")
    seconds = ledger_window.seconds_between(evidence)
    if empty_s is None or not seconds:
        return None
    return 100.0 * empty_s / seconds
