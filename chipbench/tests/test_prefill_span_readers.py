"""``prefill_span_live_pct`` and ``prefill_device_share_pct`` on hand-made
evidence: two ledger rows, a few planes.  Each gives None, and raises
nothing, where there is nothing to read: an untraced run, the parent of the
PR that added the counters, a window without a prompt chunk."""

import pytest

from chipbench import spec

DEP = "d"
CELL = "m7b-d16.chat_steady"


def _row(time, **counters):
    return {"time": time, "points": [], "engine": {DEP: counters}}


def _ledger(before, after):
    return {"deployment": DEP, "ledger_before": [_row(100.0, **before)],
            "ledger_after": [_row(150.0, **after)]}


def _trace(prefill_runs, device=True):
    """A device busy 0-4, 6-8 and 9-10 s; the prefill program ran
    ``prefill_runs`` (start, seconds) of it."""
    mods = [("jit__decode_chunk_impl(123)", 0.0, 4.0, "")]
    mods += [("jit__prefill_chunk_impl(9)", s, d, "") for s, d in prefill_runs]
    ops = [("fusion.1", 0.0, 4.0, ""), ("fusion.2", 6.0, 2.0, ""),
           ("fusion.3", 9.0, 1.0, "")]
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("$paged.py:1 step", 0.0, 10.0, "")]}]}
    dev = {"name": "/device:TPU:0",
           "lines": [{"name": "XLA Modules", "events": mods},
                     {"name": "XLA Ops", "events": ops}]}
    return {"trace": {"planes": [dev, host] if device else [host]}}


def _reader(name):
    return spec.load_module("layer_metrics", name).read


def test_span_live_is_the_ratio_of_the_window():
    # warm-up: 5 chunks of one 32-page tile each, 16 pages live; window: 40
    # chunks of 256 tokens at p0 = 0 (16 of 32) and 10 at p0 = 256 (32 of 32)
    ev = _ledger({"prefill_live_pages": 80, "prefill_visited_pages": 160},
                 {"prefill_live_pages": 80 + 40 * 16 + 10 * 32,
                  "prefill_visited_pages": 160 + 50 * 32})
    assert _reader("prefill_span_live_pct")(ev) == pytest.approx(60.0)


@pytest.mark.parametrize("case", ["untraced", "parent", "one_counter",
                                  "no_chunk"])
def test_span_live_none_where_there_is_nothing_to_read(case):
    ev = {
        "untraced": {"deployment": DEP},
        "parent": _ledger({"prefill_tokens": 1}, {"prefill_tokens": 9}),
        "one_counter": _ledger({"prefill_live_pages": 1},
                               {"prefill_live_pages": 9}),
        "no_chunk": _ledger(
            {"prefill_live_pages": 16, "prefill_visited_pages": 32},
            {"prefill_live_pages": 16, "prefill_visited_pages": 32}),
    }[case]
    assert _reader("prefill_span_live_pct")(ev) is None


def test_device_share_is_prefill_time_over_busy_time():
    ev = _trace([(6.0, 0.5), (7.0, 0.9)])
    assert _reader("prefill_device_share_pct")(ev) == pytest.approx(
        100.0 * 1.4 / 7.0)


@pytest.mark.parametrize("case", ["untraced", "no_trace", "no_prefill_run",
                                  "no_device"])
def test_device_share_none_where_there_is_nothing_to_read(case):
    ev = {
        "untraced": {"deployment": DEP},
        "no_trace": {"trace": None},
        "no_prefill_run": _trace([]),
        "no_device": _trace([(6.0, 0.5)], device=False),
    }[case]
    assert _reader("prefill_device_share_pct")(ev) is None


@pytest.mark.parametrize("name,better,source", [
    ("prefill_mxu_pct", "higher", "device_trace"),
    ("prefill_device_share_pct", "lower", "device_trace"),
    ("prefill_span_live_pct", "higher", "program_counter")])
def test_benchmark_json_lists_the_readers(name, better, source):
    entry = {m["name"]: m for m in spec.Cell(CELL).metrics("per_layer")}[name]
    assert entry["better"] == better and entry["source"] == source
    assert entry["moves"] == "ttft_mean_ms" and entry["unit"] == "%"
    assert entry["layer"] == "model step, prefill (models/llama.py)"
    assert CELL in entry["workloads"]  # whichever other cells report it
