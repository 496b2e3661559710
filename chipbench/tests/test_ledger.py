"""Cutting the server's cumulative sketches to the window."""

import math

from chipbench import ledger


def _point(stage, values, acc=0.01):
    gamma = (1 + acc) / (1 - acc)
    bins = {}
    for v in values:
        i = math.ceil(math.log(v) / math.log(gamma))
        bins[i] = bins.get(i, 0) + 1
    return {"name": ledger.STAGE, "tags": {"deployment": "d", "stage": stage},
            "accuracy": acc, "bins": sorted(bins.items()), "zero": 0,
            "count": len(values)}


def test_difference_leaves_the_window():
    warm = [5.0] * 40                       # warm-up: slow, before the window
    window = [0.010 * (i + 1) for i in range(100)]
    before = [{"points": [_point("queue_wait", warm)]}]
    after = [{"points": [_point("queue_wait", warm + window)]},
             {"points": [_point("prefill", [1.0])]}]  # another stage, ignored
    s = ledger.window_sketch(before, after, ledger.STAGE, "d", "queue_wait")
    assert s["count"] == 100
    p50, p95 = ledger.quantile(s, 0.5), ledger.quantile(s, 0.95)
    assert abs(p50 - 0.505) / 0.505 < 0.03
    assert abs(p95 - 0.95) / 0.95 < 0.03
    # uncut, the warm-up would own the tail
    whole = ledger.window_sketch([], after, ledger.STAGE, "d", "queue_wait")
    assert ledger.quantile(whole, 0.95) > 4.0


def test_nothing_booked_reads_nothing():
    s = ledger.window_sketch([], [], ledger.STAGE, "d", "queue_wait")
    assert ledger.quantile(s, 0.5) is None
