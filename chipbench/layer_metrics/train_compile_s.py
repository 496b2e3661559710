"""``train_compile_s``: worker's clock around ``lower().compile()`` of the
train step (a cache hit after a cell's first run in a checkout)."""


def read(evidence):
    return evidence.get("compile_s")
