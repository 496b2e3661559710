"""``enqueue_wait_mean_ms``: mean of the ``enqueue_wait`` stage:
``add_request`` entered -> the request is in the engine's queue.  The engine's
step holds the engine's lock for its whole body, so a request that arrives
during a step waits for the step's end before it is even queued.  It precedes
``queue_wait``; of the five means that add up to the mean time to first token
it is a part of ``ingress_rest_mean_ms``."""

from chipbench import ledger_window


def read(evidence):
    return ledger_window.stage_mean_ms(evidence, "enqueue_wait")
