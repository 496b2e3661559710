"""The reduction from a trace to numbers: on hand-made lists, and on a small
trace recorded on the chip (``record_trace.py``; TPU v5 lite, PR 23)."""

import os

import pytest

from chipbench import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(__file__), "tiny_tpu_v5e.xplane.pb")


def _planes():
    ops = [("while.1", 0.0, 4.0, ""),            # nests the next two
           ("fusion.2", 0.5, 1.0, ""),
           ("paged_attention.3", 2.0, 1.5, "op=custom-call tpu_custom_call"),
           ("fusion.9", 6.0, 2.0, ""),
           ("all-reduce.4", 9.0, 1.0, "")]
    mods = [("jit__decode_chunk_impl(123)", 0.0, 4.0, ""),
            ("jit__prefill_chunk_impl(9)", 6.0, 2.0, ""),
            ("jit__decode_chunk_impl(123)", 9.0, 1.0, "")]
    host = [("$paged.py:1594 step", 3.9, 2.2, ""),
            ("$loader.py:7 prepare_inputs", 8.0, 1.2, ""),
            ("$threading.py:323 wait", 0.0, 10.0, "")]   # a thread that waits
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": mods},
                       {"name": "XLA Ops", "events": ops}]},
            {"name": "/device:TPU:1", "lines": []},
            {"name": "/host:CPU",
             "lines": [{"name": "python", "events": host}]}]


def test_busy_is_the_union_and_window_the_span():
    busy, window, devices = tr.busy(_planes())
    assert busy == pytest.approx(4.0 + 2.0 + 1.0)
    assert window == pytest.approx(10.0)
    assert devices == 1
    assert tr.union_seconds([("a", 0, 2, ""), ("b", 1, 3, ""), ("c", 10, 1, "")]) == 5


def test_self_time_does_not_count_a_while_twice():
    own = dict((n, s) for n, s, _ in tr.self_times(_planes()[0]["lines"][1]["events"]))
    assert own["while.1"] == pytest.approx(4.0 - 1.0 - 1.5)
    assert own["fusion.2"] == pytest.approx(1.0)
    top = dict(tr.top_ops(_planes()))
    assert top["fusion.9"] == pytest.approx(2.0)
    assert top["paged_attention.3 [tpu_custom_call]"] == pytest.approx(1.5)
    assert sum(top.values()) == pytest.approx(7.0)   # equals busy: no overlap


def test_programs_kernels_and_gaps_by_name():
    p = _planes()
    assert tr.module_durations(p, r"^jit__decode_chunk_impl") == [4.0, 1.0]
    assert tr.op_self_seconds(p, r"tpu_custom_call", r"^jit__decode") == pytest.approx(1.5)
    assert tr.op_self_seconds(p, r"tpu_custom_call", r"^jit__prefill") == 0
    assert tr.op_self_seconds(p, r"fusion", within=r"prefill") == pytest.approx(2.0)
    assert tr.op_self_seconds(p, r"all-reduce") == pytest.approx(1.0)
    gaps = dict(tr.idle_gaps(p))
    assert gaps == {"$paged.py:1594 step": pytest.approx(2.0),
                    "$loader.py:7 prepare_inputs": pytest.approx(1.0)}


def test_names_are_shortened_and_the_window_leaves_the_profiler_out():
    name = ('%closed_call.11 = f32[64,32,128]{2,1,0:T(8,128)S(1)} custom-call('
            's32[1]{0:T(128)} %x), custom_call_target="tpu_custom_call"')
    assert tr.shorten(name) == ("closed_call.11 f32[64,32,128]",
                                "op=custom-call tpu_custom_call")
    assert tr.shorten("%while.3 = (s32[]{:T(128)}, bf16[4]{0}) while((s32[]"
                      "{:T(128)}) %t), body=%b") == ("while.3", "op=while")
    assert tr.shorten("jit_step(123)") == ("jit_step(123)", "")
    p = _planes()
    p[2]["lines"][0]["events"] += [("$profiler.py:101 start_trace", -1.0, 1.5, ""),
                                   ("$profiler.py:213 stop_trace", 9.5, 3.0, "")]
    assert tr.window(p) == (0.5, 9.5)
    busy, window, _ = tr.busy(p)
    assert window == pytest.approx(9.0)
    assert busy == pytest.approx(3.5 + 2.0 + 0.5)     # clipped at both ends


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    planes = tr.load(RECORDED)
    busy, window, devices = tr.busy(planes)
    assert devices == 1 and 0 < busy < window < 1.0
    # three runs of each of the two programs, by their stable names
    assert len(tr.module_durations(planes, r"^jit_bench_matmul")) == 3
    scans = tr.module_durations(planes, r"^jit_bench_scan")
    assert len(scans) == 3
    # the scan's while does not count its body twice: self times sum to busy
    plane = tr.first_device(planes)
    own = sum(s for _, s, _ in tr.self_times(tr.op_events(plane)))
    assert busy <= own <= 1.15 * busy   # async copies overlap a little
    # the recorder slept 5 and 10 ms between programs: the gaps are there
    gaps = tr.idle_gaps(planes)
    assert gaps and sum(g for _, g in gaps) > 0.03
    s = tr.summary(planes)
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
