"""The readers of what the program books itself (ledger stage means, engine
counters, profiler regions), on hand-made evidence: two ledger rows, a few
planes.  Every reader gives None, and raises nothing, where the program books
no such thing (the parent of the PR that added them)."""

import pytest

from chipbench import ledger_window, spec

DEP = "d"


def _stage(stage, total, count):
    return {"name": ledger_window.STAGE, "sum": total, "count": count,
            "tags": {"deployment": DEP, "stage": stage}}


def _ttft(total, count, tenant="default"):
    return {"name": ledger_window.TTFT, "sum": total, "count": count,
            "tags": {"deployment": DEP, "tenant": tenant}}


def _counters(**kw):
    base = {"steps": 0, "prefill_tokens": 0, "prefill_padded_tokens": 0,
            "decode_dispatches": 0, "decode_dispatches_pipelined": 0,
            "host_s": 0.0, "compiles": 0, "drains": {"finish": 1}}
    return {**base, **kw}


def _evidence():
    """A warm-up of 10 slow requests, then a window of 20: the cut has to
    leave the window's means and counts."""
    warm = {"queue_wait": (10.0, 10), "prefill": (20.0, 10),
            "first_emit": (30.0, 10), "stream_out": (40.0, 10),
            "enqueue_wait": (50.0, 10)}
    window = {"queue_wait": (0.04, 20), "prefill": (4.0, 20),
              "first_emit": (2.0, 20), "stream_out": (0.1, 20),
              "enqueue_wait": (1.0, 20)}
    before = [{"time": 100.0, "points": [_ttft(200.0, 10)]},  # the proxy
              {"time": 101.0,
               "points": [_stage(s, *v) for s, v in warm.items()],
               "engine": {DEP: _counters(steps=100, prefill_tokens=5000,
                                         prefill_padded_tokens=1000,
                                         decode_dispatches=90,
                                         decode_dispatches_pipelined=30,
                                         host_s=1.0, compiles=0),
                          "other": _counters(steps=7)}}]
    after = [{"time": 160.0, "points": [_ttft(200.0 + 10.0, 30)]},
             {"time": 151.0,
              "points": [_stage(s, warm[s][0] + v[0], warm[s][1] + v[1])
                         for s, v in window.items()],
              "engine": {DEP: _counters(steps=300, prefill_tokens=55000,
                                        prefill_padded_tokens=13500,
                                        decode_dispatches=290,
                                        decode_dispatches_pipelined=80,
                                        host_s=3.0, compiles=1)}}]
    return {"deployment": DEP, "ledger_before": before, "ledger_after": after}


WANT = {
    "prefill_stage_mean_ms": 200.0, "first_emit_mean_ms": 100.0,
    "stream_out_mean_ms": 5.0, "enqueue_wait_mean_ms": 50.0,
    # ttft 10 s over 20 requests = 500 ms, less 2 + 200 + 100 + 5
    "ingress_rest_mean_ms": 193.0,
    "prefill_tokens_per_s": 1000.0,            # 50,000 tokens in 50 s
    "prefill_pad_pct": 20.0,                   # 12,500 of 62,500
    "pipelined_dispatch_pct": 25.0,            # 50 of 200
    "host_ms_per_step": 10.0,                  # 2 s over 200 steps
    "compiles_in_window": 1.0,
}


def _reader(name):
    return spec.load_module("layer_metrics", name).read


@pytest.mark.parametrize("name", sorted(WANT))
def test_ledger_readers_cut_to_the_window(name):
    assert _reader(name)(_evidence()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT) + ["idle_attributed_pct"])
def test_readers_find_nothing_in_a_program_that_books_nothing(name):
    read = _reader(name)
    assert read({"deployment": DEP}) is None          # an untraced run
    old = _evidence()                                 # the parent's ledger
    for rows in (old["ledger_before"], old["ledger_after"]):
        for row in rows:
            row.pop("engine", None)
            row["points"] = [p for p in row["points"]
                             if p["tags"].get("stage") in (
                                 None, "queue_wait", "prefill", "decode")]
    old["trace"] = {"planes": _planes(regions=False)}
    if name == "prefill_stage_mean_ms":   # the stage the parent books too
        assert read(old) == pytest.approx(200.0)
    else:
        assert read(old) is None


def test_nothing_booked_between_the_reads():
    ev = _evidence()
    ev["ledger_after"] = ev["ledger_before"]
    for name in WANT:
        # no compile between the reads is a reading, not a missing source
        want = 0 if name == "compiles_in_window" else None
        assert _reader(name)(ev) == want, name


def test_engine_rows_of_the_ledgers_tail():
    ev = _evidence()
    assert ledger_window.engine_rows(ev) == []
    assert ledger_window.engine_rows({"deployment": DEP}) == []
    mine = {"kind": "engine", "deployment": DEP, "queue_wait_s": 0.002}
    ev["ledger_after"][1]["recent"] = [
        mine, {"kind": "engine", "deployment": "other"},
        {"deployment": DEP, "status": "ok"}]      # an ingress row
    assert ledger_window.engine_rows(ev) == [mine]
    assert ledger_window.window_sum_count(
        ev, ledger_window.STAGE, "prefill") == pytest.approx((4.0, 20))


def _planes(regions=True):
    """A device busy 0-1, 1.5-2.5 and 3-3.9 s of a window 0-4 s: idle 0.5 s
    (under a drain, 0.4 of it under the collect inside the drain), 0.5 s
    (0.3 under a lock wait on another thread, the rest under the step
    alone) and 0.1 s under no region."""
    host = [{"name": "loop", "events": [
        ("$profiler.py:101 start_trace", -0.1, 0.1, ""),
        ("$profiler.py:213 stop_trace", 4.0, 0.2, ""),
        ("$paged.py:1 step", 0.9, 2.2, "")]}]
    if regions:
        host[0]["events"] += [
            ("engine.step", 0.9, 2.2, ""),
            ("engine.drain", 0.95, 0.6, ""),
            ("engine.collect", 1.0, 0.4, "")]
        # a caller's short wait inside the drain takes nothing from it: the
        # engine loop's own regions come first
        host.append({"name": "actor-exec-0", "events": [
            ("serve.step_lock_wait", 1.42, 0.08, ""),
            ("serve.step_lock_wait", 2.4, 0.4, "")]})
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_f(1)", 0.0, 1.0, "")]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 0.0, 1.0, "op=fusion"),
                ("fusion.1", 1.5, 1.0, "op=fusion"),
                ("fusion.1", 3.0, 0.9, "op=fusion")]}]},
        {"name": "/host:CPU", "lines": host}]


def test_idle_time_by_region():
    mod = spec.load_module("layer_metrics", "idle_attributed_pct")
    got = mod.by_region(_planes())
    assert got == pytest.approx({"engine.collect": 0.4, "engine.drain": 0.1,
                                 "serve.step_lock_wait": 0.3,
                                 "engine.step": 0.2, "(no region)": 0.1})
    # 0.8 of 1.1 s of idle under a region other than the whole step
    assert mod.read({"trace": {"planes": _planes()}}) == pytest.approx(
        100.0 * 0.8 / 1.1)
    assert mod.by_region(_planes(regions=False)) is None
    assert mod.read({"trace": None}) is None


def test_benchmark_json_lists_the_new_readers():
    cell = spec.Cell("m7b-d16.chat_steady")
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(WANT) | {"idle_attributed_pct"} <= names
    for m in cell.metrics("per_layer"):
        if m["name"] in WANT:
            assert m["source"] == "program_counter"
            assert m["moves"] in ("ttft_mean_ms", "tpot_mean_ms")
