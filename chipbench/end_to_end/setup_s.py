"""``setup_s``: process start to the first request or step of the window
(host clock): cluster start, replica or train worker up (weights, pool,
``warmup()`` or compile), and the ramp of the cell's own traffic."""


def read(evidence):
    return evidence["setup_s"]
