"""``queue_wait_p95_ms``: 95th percentile of the engine's ``queue_wait`` stage
(request enqueued -> admitted to a slot, ``llm/paged.py`` ``_admit_locked``),
from the server's ledger cut to the window (``chipbench/ledger.py``)."""

from chipbench import ledger


def read(evidence):
    return ledger.window_quantile_ms(evidence, ledger.STAGE, 0.95, "queue_wait")
