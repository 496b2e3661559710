"""``steady.py``'s spread rule and report, the pace readers and the stall
reader on made-up rows, and the order of a traced run's capture and parse."""

import io
import json
import os

import pytest

from chipbench import serving, spec, steady


# -- the rule -------------------------------------------------------------------


@pytest.mark.parametrize("values,want", [
    # the farthest run (30) is left out: 10.0 to 10.4 over the median
    ([10.0, 10.1, 10.2, 10.3, 10.4, 30.0], 0.4 / 10.25),
    # the farthest is the lowest
    ([1.0, 10.1, 10.2, 10.3, 10.4, 10.5], 0.4 / 10.25),
    # two far runs: one is left out, the other stays
    ([10.0, 10.1, 10.2, 10.3, 20.0, 30.0], 10.0 / 10.25),
    # two runs: nothing to leave out
    ([10.0, 11.0], 1.0 / 10.5),
    ([5.0], 0.0),
    ([7.0, 7.0, 7.0], 0.0),
])
def test_spread_is_the_range_over_the_median_less_the_farthest_run(values,
                                                                   want):
    assert steady.spread(values) == pytest.approx(want)
    assert steady.spread(list(reversed(values))) == pytest.approx(want)


def test_the_contract_s_quartile_spread():
    xs = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    import statistics
    q = statistics.quantiles(xs, n=4)
    assert steady.iqr(xs) == pytest.approx((q[2] - q[0]) / 10.25)


@pytest.mark.parametrize("mean,want", [
    (0.001, 0.01), (0.004, 0.01), (0.0041, 0.02), (0.008, 0.02),
    (0.0081, 0.03), (0.012, 0.03), (0.02, 0.05), (0.032, 0.08),
    (0.04, 0.10), (0.0401, None)])
def test_the_bound_is_the_smallest_step_with_the_room(mean, want):
    assert steady.bound_by_rule(mean) == want


@pytest.mark.parametrize("bound,widest,want", [
    (0.08, 0.011, False),   # 8 x 1.1% = 8.8%
    (0.08, 0.009, True),    # 7.2%: the runs could tell less than 8%
    (0.02, 0.0024, True), (0.02, 0.0025, False),
    (0.01, 0.0001, False),  # 1% is the floor: never too loose
    (None, 0.0001, False)])
def test_a_bound_over_eight_times_the_widest_iqr_is_too_loose(bound, widest,
                                                              want):
    assert steady.too_loose(bound, widest) is want


def test_the_gate_runs_at_the_benchmark_s_length_and_seeds_alone():
    """No ``--seed`` and no ``--seconds``: a verdict at another length or on
    other seeds is not the gate's."""
    options = steady.parser()._option_string_actions
    assert {"--workload", "--sets", "--runs", "--first-set", "--traced",
            "--keep", "--from"} <= set(options)
    assert "--seed" not in options and "--seconds" not in options


# -- rows -> statistics ------------------------------------------------------------


def _window_rows(n=20):
    """[key, prompt, asked, due s, ttft ms, last ms, got]: stream i streams
    ``10 + i`` ms a token."""
    return [[i, 100, 11, float(i), 50.0 + i, 50.0 + i + 10 * (10 + i), 11]
            for i in range(n)]


def test_candidates_from_a_window_rows_line_old_and_new():
    rows = _window_rows()
    got = steady.candidates(rows)
    paces = [10.0 + i for i in range(20)]
    assert got["ttft_mean"] == pytest.approx(59.5)
    assert got["tpot_mean"] == pytest.approx(sum(paces) / 20)
    assert got["tpot_p50"] == pytest.approx(19.5)
    assert got["tpot_p90"] == pytest.approx(spec.percentile(paces, 90))
    assert got["tpot_p95"] == pytest.approx(spec.percentile(paces, 95))
    assert got["requests"] == 20 and got["streams"] == 20
    # a log from before PR 46 has no ``got``: asked stands for it
    assert steady.candidates([r[:6] for r in rows]) == got
    # a stream of one token has no pace
    one = rows + [[99, 10, 1, 0.0, 40.0, 40.0, 1]]
    assert steady.candidates(one)["streams"] == 20
    assert steady.candidates([]) == {}


def _log(path, cell, seed, rows, metrics, gap=None, correct=True):
    lines = [f"[chipbench +   0.0s] cell {cell}: configuration c, traffic t, "
             f"kind serve_open, 1 chip(s), seed {seed}, window 50s, trace 0",
             "[chipbench +  90.0s] window rows [key, prompt, asked, due s, "
             "ttft ms, last ms, got]: " + json.dumps(rows)]
    if gap:
        lines.append("[chipbench +  90.0s] stream gap [longest pause ms, at "
                     "s, stream key, streams paused within 50 ms of it, "
                     "streams live then]: " + json.dumps(gap))
    lines.append(json.dumps({
        "correct": correct, "attempted": len(rows), "failed": 0,
        "metrics": {k: {"value": v, "unit": "ms"} for k, v in metrics.items()},
        "device": {"platform": "tpu"}}))
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def test_report_from_kept_logs_judges_each_metric_by_the_mean_of_the_sets(
        tmp_path):
    keep = tmp_path / "kept"
    os.makedirs(keep)
    for k in (1, 2):
        for j in range(1, 7):
            rows = _window_rows()
            for r in rows:  # set 2 runs a little slower; run 3 stalls
                r[5] += 2.0 * (k - 1) + (300.0 if j == 3 else 0.1 * j)
            _log(keep / f"set{k}_run{j}_{4600000019 + j}.log", "a.cell",
                 4600000019 + j, rows,
                 {"ttft_mean_ms": 100.0 + j * (0.5 if k == 1 else 1.5),
                  "tpot_mean_ms": steady.candidates(rows)["tpot_mean"],
                  "setup_s": 200.0 if j == 1 else 80.0 + 0.1 * j},
                 gap=[45.0 + j, 12.5, 3, 1, 9])
    _log(keep / "traced_4600000020.log", "a.cell", 4600000020,
         _window_rows(), {})
    _log(keep / "other.log", "b.cell", 1, _window_rows(), {})
    sets, traced = steady.load_sets([str(keep)], "a.cell")
    assert [len(s) for s in sets] == [6, 6] and len(traced) == 1
    assert [os.path.basename(r["path"])[:9] for r in sets[1]] == [
        f"set2_run{j}" for j in range(1, 7)]
    out = io.StringIO()
    verdict = steady.report(sets, {"ttft_mean_ms": 0.05, "tpot_mean_ms": 0.01,
                                   "setup_s": 0.1}, out=out)
    # ttft: sets spread (2.0 / 101.75) and (6.0 / 105.25) with the farthest
    # run left out; their mean against half of 5%
    want = (2.0 / 101.75 + 6.0 / 105.25) / 2
    assert verdict["ttft_mean_ms"]["mean"] == pytest.approx(want)
    assert verdict["ttft_mean_ms"]["holds"] is (want <= 0.025)
    assert verdict["ttft_mean_ms"]["rule"] == steady.bound_by_rule(want)
    # the driver's other test: 5% against 8 x the widest iqr of the two sets
    widest = max(steady.iqr([100.0 + j * d for j in range(1, 7)])
                 for d in (0.5, 1.5))
    assert verdict["ttft_mean_ms"]["iqr"] == pytest.approx(widest)
    assert verdict["ttft_mean_ms"]["loose"] is (0.05 > 8 * widest)
    assert verdict["setup_s"]["loose"] is None  # judged by its median alone
    # the stalled run is the farthest of its set and is left out
    assert verdict["tpot_mean_ms"]["mean"] < 0.005
    assert verdict["tpot_mean_ms"]["holds"] is True
    # set-up: the first run of a set compiles and is left out; by medians
    assert verdict["setup_s"]["holds"] is True
    assert len(verdict["setup_s"]["spreads"]) == 2
    # the candidates are reported beside the metrics, with no bound
    assert verdict["tpot_p90"]["bound"] is None
    assert verdict["tpot_p90"]["holds"] is None
    text = out.getvalue()
    assert "| `tpot_mean_ms` | 2 |" in text
    assert "| 69.0 | 48.0 (12.5; 1 / 9) |" in text
    assert steady.stalled(sets) == []
    sets[0][2]["gap"][0] = 1870.3  # a run says that it stalled
    sets[1][4]["rows"][0][4] = 2404.6
    assert steady.stalled(sets) == ["set1_run3_4600000022.log",
                                    "set2_run5_4600000024.log"]
    # one stalled run a set is the run the rule leaves out; a set with two
    # is no sound set and stays out of every mean, for every metric alike
    assert steady.unsound_sets(sets) == []
    sets[0][4]["gap"][0] = 2404.6
    assert steady.unsound_sets(sets) == [1]
    out = io.StringIO()
    sound = steady.report(sets, {"ttft_mean_ms": 0.05}, out=out, unsound=[1])
    assert sound["ttft_mean_ms"]["spreads"] == pytest.approx([6.0 / 105.25])
    assert sound["tpot_mean_ms"]["spreads"] == verdict["tpot_mean_ms"][
        "spreads"][1:]
    assert "| `ttft_mean_ms` | 1 (unsound) |" in out.getvalue()
    out = io.StringIO()
    steady.traced_beside(traced[0], sets, out=out)
    assert "ttft_mean 59.500 / 59.500 (+0.00%)" in out.getvalue()


def test_one_run_alone_reports_and_judges_no_spread(tmp_path):
    _log(tmp_path / "set1_run1_7.log", "a.cell", 7, _window_rows(),
         {"ttft_mean_ms": 100.0, "setup_s": 160.0})
    sets, _ = steady.load_sets([str(tmp_path)])
    verdict = steady.report(sets, {"ttft_mean_ms": 0.08, "setup_s": 0.1},
                            out=io.StringIO())
    assert "setup_s" not in verdict  # its one run is the run that compiles
    assert verdict["ttft_mean_ms"]["mean"] == 0.0
    assert verdict["ttft_mean_ms"]["loose"] is None


def test_a_bound_the_sets_do_not_support_does_not_hold(tmp_path):
    for j, v in enumerate((100.0, 103.0, 101.0, 102.0, 104.0, 100.5)):
        _log(tmp_path / f"run{j}.log", "a.cell", j, _window_rows(),
             {"ttft_mean_ms": v})
    sets, _ = steady.load_sets([str(tmp_path)])
    assert [len(s) for s in sets] == [6]  # a plain directory is one set
    verdict = steady.report(sets, {"ttft_mean_ms": 0.05}, out=io.StringIO())
    assert verdict["ttft_mean_ms"]["mean"] == pytest.approx(3.0 / 101.5)
    assert verdict["ttft_mean_ms"]["holds"] is False
    assert verdict["ttft_mean_ms"]["rule"] == 0.08


# -- the readers on made-up client rows -------------------------------------------------


def _client_rows():
    """Ten streams of 11 tokens at 10, 11, .. 19 ms a token, a frame every
    two tokens; stream 4 and stream 5 pause together for 0.8 s."""
    rows = []
    for i in range(10):
        pace = 0.010 + 0.001 * i
        first = 1.0 + 0.04 * i
        frames = [[first + 2 * k * pace, 2 if k else 1] for k in range(6)]
        if i in (4, 5):
            at = frames[2][0]
            frames = frames[:3] + [[t + 0.8, n] for t, n in frames[3:]]
        rows.append({"phase": "window", "ok": True, "key": i, "got": 11,
                     "due": first - 0.1, "first": first,
                     "last": frames[-1][0], "frames": frames,
                     "prompt_len": 100, "max_tokens": 11})
    rows.append({"phase": "ramp", "ok": True, "key": 100, "got": 11,
                 "due": -5.0, "first": -4.0, "last": 9.0,
                 "frames": [[-4.0, 1], [-1.0, 10]], "prompt_len": 10,
                 "max_tokens": 11})
    rows.append({"phase": "window", "ok": True, "key": 101, "got": 1,
                 "due": 2.0, "first": 2.1, "last": 2.1, "frames": [[2.1, 1]],
                 "prompt_len": 10, "max_tokens": 1})
    return rows


def _read(subdir, name, evidence):
    return spec.load_module(subdir, name).read(evidence)


def test_the_pace_readers_and_the_stall_reader():
    ev = {"rows": _client_rows(), "traffic": {"loop": "open"},
          "seconds": 50.0}
    paces = [(r["last"] - r["first"]) / 10 * 1e3 for r in ev["rows"][:10]]
    assert paces[4] == pytest.approx(14.0 + 80.0)
    assert _read("end_to_end", "tpot_mean_ms", ev) == pytest.approx(
        sum(paces) / 10)
    assert _read("end_to_end", "tpot_p90_ms", ev) == pytest.approx(
        spec.percentile(paces, 90))
    assert _read("end_to_end", "tpot_p85_ms", ev) == pytest.approx(
        spec.percentile(paces, 85))
    assert _read("layer_metrics", "tpot_p95_ms", ev) == pytest.approx(
        spec.percentile(paces, 95))
    gap = spec.stream_gap(ev["rows"])
    # the longest pause: stream 5's (0.8 s + its own two tokens), begun at
    # its third frame; stream 4 paused within 50 ms of it; the ramp's row
    # (its pause ended before the window began) and the one-token stream
    # do not count
    assert gap["key"] == 5 and gap["paused"] == 2
    assert gap["max_ms"] == pytest.approx(800.0 + 2 * 15.0)
    assert gap["at_s"] == pytest.approx(1.20 + 4 * 0.015)
    assert gap["live"] >= 2
    assert _read("layer_metrics", "stream_gap_max_ms", ev) == pytest.approx(
        gap["max_ms"])
    # a stall across the window's start holds no stream of the window: a
    # stream of the ramp that is still live shows it; one past the window's
    # end does not count
    ev["rows"][10]["frames"] = [[-4.0, 1], [-0.5, 5], [2.4, 5]]
    ev["rows"][11]["frames"] = [[55.0, 1], [99.0, 1]]
    gap = spec.stream_gap(ev["rows"], 50.0)
    assert gap["key"] == 100 and gap["max_ms"] == pytest.approx(2900.0)
    assert gap["at_s"] == pytest.approx(-0.5) and gap["paused"] == 1
    assert _read("layer_metrics", "stream_gap_max_ms", ev) == pytest.approx(
        2900.0)


def test_the_readers_find_nothing_where_there_is_nothing():
    closed = {"rows": _client_rows(), "traffic": {"loop": "closed"},
              "seconds": 50.0}
    empty = {"rows": [], "traffic": {"loop": "open"}, "seconds": 50.0}
    for ev in (closed, empty):
        assert _read("end_to_end", "tpot_mean_ms", ev) is None
        assert _read("end_to_end", "tpot_p90_ms", ev) is None
        assert _read("end_to_end", "tpot_p85_ms", ev) is None
        assert _read("layer_metrics", "tpot_p95_ms", ev) is None
    assert _read("layer_metrics", "stream_gap_max_ms", empty) is None
    assert spec.stream_gap([{"phase": "window", "key": 1,
                             "frames": [[1.0, 1]]}]) is None


# -- a traced run's capture, and its parse after the drain -------------------------------


def test_the_capture_returns_the_file_and_the_parse_comes_later(
        tmp_path, monkeypatch):
    from ray_tpu.util import state

    from chipbench import trace_reduce

    pb = tmp_path / "trace" / "x.xplane.pb"
    os.makedirs(pb.parent)
    pb.write_bytes(b"not parsed by the capture")
    monkeypatch.setattr(state, "jax_profile", lambda pid, duration_s, logdir:
                        {"files": [str(pb), str(pb) + ".json"]})
    loaded = []
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: loaded.append(path) or {"planes": 1})
    got = serving.capture_trace(123, 4.0, str(tmp_path))
    assert got == {"path": str(pb)} and not loaded
    assert serving.parse_trace(got)["planes"] == {"planes": 1}
    assert loaded == [str(pb)]
    monkeypatch.setattr(state, "jax_profile",
                        lambda pid, duration_s, logdir: {"files": []})
    assert serving.capture_trace(123, 4.0, str(tmp_path)) is None


def test_every_compared_number_stands_beside_its_limit():
    """One list: what a check's verdict says it compared is what the result
    line prints, behind the failed requests."""
    rows = [{"phase": "window", "ok": True}, {"phase": "window", "ok": False},
            {"phase": "ramp", "ok": False},
            {"phase": "window", "ok": False, "cut": True}]
    ev = {"rows": rows, "reference": {
        "ok": True, "compared": [["max_logit_gap", 0.04, 0.25]]}}
    assert serving.compared(ev) == [["failed_requests", 1, 0],
                                    ["max_logit_gap", 0.04, 0.25]]
    # a probe failed: the check has no number to state
    ev = {"rows": rows[:1], "reference": {"ok": False,
                                          "why": "probe 0: HTTP 500"}}
    assert serving.compared(ev) == [["failed_requests", 0, 0]]
    fam = spec.load_module("kinds", "serve_open_family")
    assert fam.compared is serving.compared
    got = fam.judge([{"document": 0, "logit_gaps": [0.0, 0.006]},
                     {"document": 8192, "logit_gaps": [0.0, 0.3]}])
    assert got["compared"] == [
        ["short_mean_logit_gap", 0.003, fam.REF_MEAN_TOL],
        ["long_mean_logit_gap", 0.15, fam.REF_LONG_MEAN_TOL],
        ["max_logit_gap", 0.3, fam.REF_MAX_TOL]]
    # the verdict follows from the same list
    assert got["ok"] is all(v <= lim for _, v, lim in got["compared"])
    assert not got["ok"] and "behind a document" in got["why"]
    hyb = spec.load_module("kinds", "serve_open_hybrid")
    assert hyb.compared is serving.compared
    got = hyb.judge(
        [{"tokens": 8, "logit_gaps": [0.0, 2e-5]},
         {"tokens": hyb.LONG_DECODE, "logit_gaps": [0.0, 4e-5]}],
        {"ssm": {"finite": True, "rel_err": 0.03}, "positions": 300})
    assert [c[0] for c in got["compared"]] == [
        "prompt_mean_logit_gap", "decode_mean_logit_gap", "max_logit_gap",
        "state_rel_err"]
    assert got["compared"][3] == ["state_rel_err", 0.03, hyb.REF_STATE_TOL]
    assert got["ok"] is all(v <= lim for _, v, lim in got["compared"])
    train = spec.load_module("kinds", "train")
    assert train.compared({"checks": [
        {"sequence": 0, "loss": 10.5, "reference": 10.25}]}) == [
            ["loss_gap_sequence_0", 0.25, train.LOSS_TOL]]
