"""``decode_table_live_pct`` on two hand-made ledger rows: the ratio of the
two counters' growth between the reads; None, and nothing raised, where the
program books no such counters (the parent of the PR that added them) or the
run was not traced."""

import pytest

from chipbench import spec

DEP = "d"


def _row(time, **counters):
    return {"time": time, "points": [], "engine": {DEP: counters}}


def _evidence(before, after):
    return {"deployment": DEP, "ledger_before": [_row(100.0, **before)],
            "ledger_after": [_row(150.0, **after)]}


@pytest.fixture(scope="module")
def read():
    return spec.load_module("layer_metrics", "decode_table_live_pct").read


def test_ratio_of_the_window(read):
    # warm-up: 10 dispatches of a 64 x 8 table; window: 100 of 64 x 128
    ev = _evidence({"decode_table_pages": 5120, "decode_live_pages": 400},
                   {"decode_table_pages": 5120 + 819200,
                    "decode_live_pages": 400 + 61440})
    assert read(ev) == pytest.approx(7.5)


@pytest.mark.parametrize("case", ["untraced", "parent", "one_counter",
                                  "no_dispatch"])
def test_none_where_there_is_nothing_to_read(read, case):
    ev = {
        "untraced": {"deployment": DEP},
        "parent": _evidence({"steps": 1}, {"steps": 9}),
        "one_counter": _evidence({"decode_table_pages": 1},
                                 {"decode_table_pages": 9}),
        "no_dispatch": _evidence(
            {"decode_table_pages": 512, "decode_live_pages": 40},
            {"decode_table_pages": 512, "decode_live_pages": 40}),
    }[case]
    assert read(ev) is None
