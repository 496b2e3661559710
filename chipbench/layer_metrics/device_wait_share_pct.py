"""``device_wait_share_pct``: how device-bound the engine loop is by its own
clocks: of the wall time of the steps between the two ledger reads, the share
spent blocked in device reads (``device_wait_s`` over ``host_s`` +
``device_wait_s``; the engine books both every step, with no capture, so the
number covers the whole window and not the traced seconds).  Near 100 the
loop only waits for the device; what is left is host work a step, hidden
while a decode chunk is in flight."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.ratio_pct(evidence, "device_wait_s",
                                   "host_s", "device_wait_s")
