"""``compiles_in_window``: backend compiles in the replica's process between
the two ledger reads (the engine's ``compiles`` counter: ``jax.monitoring``'s
backend-compile event, counted from the moment ``warmup()`` returned).  A
compile inside the window is a stall of seconds that ``warmup()`` missed."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.counter_delta(evidence, "compiles")
