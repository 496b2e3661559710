"""The readers of the engine's own clocks and of the capture's cost
(``device_wait_share_pct``, ``engine_idle_share_pct``, ``trace_write_s``) on
hand-made evidence: the arithmetic between the two ledger reads; None, and
nothing raised, where the program books no such thing (the parent of the PR
that added them) or the run was not traced; and ``BENCHMARK.json`` lists each
for the three serving cells."""

import pytest

from chipbench import spec

DEP = "d"
SERVING = ["m7b-d16.chat_steady", "pangu-ep16.docqa_warm",
           "granite-h-micro.chat_bursty"]
ENTRIES = {
    "device_wait_share_pct": ("%", "higher", "program_counter",
                              "admission and batching (llm/paged.py)",
                              "tpot_mean_ms"),
    "engine_idle_share_pct": ("%", "lower", "program_counter",
                              "admission and batching (llm/paged.py)",
                              "tpot_mean_ms"),
    "trace_write_s": ("s", "lower", "host_clock",
                      "process layout (raylet, zygote, warmup, compile)",
                      "setup_s"),
}


def _row(time, **counters):
    return {"time": time, "points": [], "engine": {DEP: counters}}


def _evidence(before, after, **more):
    return {"deployment": DEP, "ledger_before": [_row(100.0, **before)],
            "ledger_after": [_row(160.0, **after)], **more}


def _read(name):
    return spec.load_module("layer_metrics", name).read


TRACED = {"trace": {"planes": []}}
CASES = {
    # 60 s between the rows; the steps' wall time 50 s, 42.5 of them blocked
    ("device_wait_share_pct", "window"): (_evidence(
        {"host_s": 2.0, "device_wait_s": 10.0},
        {"host_s": 9.5, "device_wait_s": 52.5}), 85.0),
    ("device_wait_share_pct", "no_step"): (_evidence(
        {"host_s": 2.0, "device_wait_s": 10.0},
        {"host_s": 2.0, "device_wait_s": 10.0}), None),
    ("device_wait_share_pct", "one_counter"): (_evidence(
        {"host_s": 2.0}, {"host_s": 9.5}), None),
    ("device_wait_share_pct", "untraced"): ({"deployment": DEP}, None),
    # 0.3 s of a known-empty device in 60 s
    ("engine_idle_share_pct", "window"): (_evidence(
        {"device_empty_s": 1.25, "steps": 10},
        {"device_empty_s": 1.55, "steps": 2500}), 0.5),
    ("engine_idle_share_pct", "never_empty"): (_evidence(
        {"device_empty_s": 1.25}, {"device_empty_s": 1.25}), 0.0),
    ("engine_idle_share_pct", "parent"): (_evidence(
        {"steps": 10}, {"steps": 2500}), None),
    ("engine_idle_share_pct", "untraced"): ({"deployment": DEP}, None),
    ("trace_write_s", "reported"): ({"report_after": {"last_capture": {
        "traced_s": 4.0, "write_s": 11.5, "bytes": 37_000_000}}, **TRACED},
        11.5),
    ("trace_write_s", "parent"): ({"report_after": {"pid": 7}, **TRACED},
                                  None),
    ("trace_write_s", "no_capture_yet"): ({"report_after": {
        "last_capture": None}, **TRACED}, None),
    ("trace_write_s", "capture_failed"): ({"report_after": {"last_capture": {
        "write_s": 11.5}}, "trace": None}, None),
    ("trace_write_s", "untraced"): ({"deployment": DEP}, None),
}


@pytest.mark.parametrize("name,case", sorted(CASES))
def test_reader(name, case):
    evidence, want = CASES[name, case]
    got = _read(name)(evidence)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_json_lists_the_reader(name):
    (entry,) = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == name]
    unit, better, source, layer, moves = ENTRIES[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": SERVING}
    for cell in SERVING:
        assert name in [m["name"]
                        for m in spec.Cell(cell).metrics("per_layer")]
