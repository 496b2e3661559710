"""``replica_up_s``: ``serve.run`` to ``device_report`` answering, runner's
clock: actor start, weights, pool, ``warmup()``."""


def read(evidence):
    return evidence.get("replica_up_s")
