"""Flight recorder, hang & straggler diagnosis, goodput ledger (ISSUE 6).

Tier-1 lane: unit tests run on injected clocks and synthetic late members
(no wall-clock sleeps); the acceptance hang test uses a real 3-member store
group with ONE member deliberately withheld (chaos-style, like
test_preemption's injected notices) and a short ``hang_detect_timeout_s``.

reference direction: hang/straggler localization as the first operational
capability that breaks at scale (arxiv 2510.20171); goodput-denominated
cost accounting (arxiv 2605.25645).
"""

import json
import threading
import time
import types
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import flight_recorder as fr
from ray_tpu._private.accelerators.tpu import TpuMaintenanceWatcher
from ray_tpu._private.flight_recorder import FlightRecorder
from ray_tpu.train._internal.goodput import BUCKETS, GoodputLedger
from ray_tpu.train._internal.watchdog import StepWatchdog
from ray_tpu.util import collective as col
from ray_tpu.util import tracing
from ray_tpu.util.collective.store import _CollectiveStoreActor


class FakeClock:
    def __init__(self, t0: float = 100.0):
        self.t = t0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _wait_for(predicate, timeout=60, interval=0.05, desc="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = predicate()
        if out:
            return out
        time.sleep(interval)
    raise TimeoutError(f"{desc} not reached within {timeout}s")


# ---------------------------------------------------------------------------
# Flight recorder: ring semantics
# ---------------------------------------------------------------------------


def test_ring_records_in_order():
    r = FlightRecorder(capacity=64)
    for i in range(10):
        r.record("task", f"t{i}", i)
    rows = r.tail()
    assert [e["name"] for e in rows] == [f"t{i}" for i in range(10)]
    assert [e["detail"] for e in rows] == list(range(10))
    assert all(e["kind"] == "task" for e in rows)


def test_ring_wraparound_keeps_newest():
    cap = 16
    r = FlightRecorder(capacity=cap)
    for i in range(50):
        r.record("k", str(i))
    rows = r.tail()
    # exactly the newest `cap` entries, still in record order
    assert [e["name"] for e in rows] == [str(i) for i in range(50 - cap, 50)]
    # memory stays fixed: the slot list never grows
    assert len(r._slots) == cap


def test_ring_tail_limit():
    r = FlightRecorder(capacity=64)
    for i in range(20):
        r.record("k", str(i))
    rows = r.tail(limit=5)
    assert [e["name"] for e in rows] == ["15", "16", "17", "18", "19"]


def test_ring_tail_seconds_window(monkeypatch):
    clock = FakeClock(1000.0)
    monkeypatch.setattr(fr, "time", types.SimpleNamespace(time=clock))
    r = FlightRecorder(capacity=64)
    r.record("k", "old")
    clock.advance(100.0)
    r.record("k", "new1")
    clock.advance(1.0)
    r.record("k", "new2")
    rows = r.tail(seconds=30.0)
    assert [e["name"] for e in rows] == ["new1", "new2"]
    assert [e["name"] for e in r.tail()] == ["old", "new1", "new2"]


def test_ring_concurrent_writers():
    """Writers claim distinct slots from the shared counter: N threads
    hammering one ring never tear an entry or lose a slot claim."""
    cap = 64
    r = FlightRecorder(capacity=cap)
    n_threads, per_thread = 8, 1000
    start = threading.Barrier(n_threads)

    def writer(tid):
        start.wait()
        for i in range(per_thread):
            r.record("w", f"{tid}:{i}", i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every record claimed exactly one slot index
    assert r._head == n_threads * per_thread
    rows = r.tail()
    # ring is full and every surviving entry is a complete record
    assert len(rows) == cap
    for e in rows:
        assert e["kind"] == "w"
        tid, i = e["name"].split(":")
        assert 0 <= int(tid) < n_threads and 0 <= int(i) < per_thread


def test_ring_reader_concurrent_with_writers():
    """tail() snapshots while writers keep wrapping the ring: every row it
    returns is complete (old or new value of a slot, never torn)."""
    r = FlightRecorder(capacity=32)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            r.record("w", str(i), i)
            i += 1

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(200):
            for e in r.tail():
                assert set(e) <= {"time", "kind", "name", "detail",
                                  "trace_id"}
                assert e["kind"] == "w" and e["name"] == str(e["detail"])
    finally:
        stop.set()
        t.join()


def test_disabled_recorder_records_nothing():
    r = FlightRecorder(capacity=16, enabled=False)
    for i in range(5):
        r.record("k", str(i))
    assert r.tail() == [] and r._head == 0


def test_module_configure_swaps_fast_path():
    """configure(enabled=False) rebinds the module-level ``record`` to the
    no-op stub (the disabled cost is one global read + no-op call)."""
    orig_cap = fr.get_recorder()._capacity
    try:
        rec = fr.configure(enabled=False, capacity=32)
        fr.record("k", "dropped")
        assert rec.tail() == []
        assert fr.record is fr._disabled_record
        rec = fr.configure(enabled=True, capacity=32)
        fr.record("k", "kept")
        assert [e["name"] for e in rec.tail()] == ["kept"]
        assert fr.record == rec.record
    finally:
        fr.configure(enabled=True, capacity=orig_cap)


def test_trace_context_cross_link():
    """Satellite: entries recorded under an active tracing context carry
    its trace_id, so diagnose/tails link straight to state.get_trace()."""
    r = fr.configure(enabled=True, capacity=64)
    tid = "ab" * 16
    r.record("task", "untraced")
    with tracing.activate(tid, "cd" * 8):
        fr.record("collective", "traced-op")
    rows = r.tail()
    by_name = {e["name"]: e for e in rows}
    assert "trace_id" not in by_name["untraced"]
    assert by_name["traced-op"]["trace_id"] == tid


def test_dump_to_file_and_read_dump(monkeypatch, tmp_path):
    """Crash-dump half of the recorder: dump appends a header + the tail as
    JSON lines; read_dump parses it back (dead-worker path of the agent
    endpoint)."""
    monkeypatch.setattr(
        fr, "dump_path",
        lambda pid=None: str(tmp_path / f"{pid or 12345}.flight"))
    rec = fr.configure(enabled=True, capacity=32)
    rec.record("step", "report", "rank0")
    fr.dump_to_file(reason="test-crash")
    rec.record("step", "report", "rank0-later")
    fr.dump_to_file(reason="second")  # appended, stays ordered
    rows = fr.read_dump(12345)
    assert rows is not None
    headers = [r for r in rows if "reason" in r]
    assert [h["reason"] for h in headers] == ["test-crash", "second"]
    entries = [r for r in rows if r.get("kind") == "step"]
    assert entries and entries[0]["name"] == "report"
    assert fr.read_dump(99999999) is None
    # freshness horizon: a stale file (recycled pid's prior-process dump)
    # reads as absent; a fresh one passes
    assert fr.read_dump(12345, max_age_s=600.0) is not None
    import os as _os

    path = str(tmp_path / "12345.flight")
    _os.utime(path, (1.0, 1.0))          # mtime: the epoch
    assert fr.read_dump(12345, max_age_s=600.0) is None
    assert fr.read_dump(12345) is not None   # unbounded read still works


def test_dump_truncates_prior_process_leftover(monkeypatch, tmp_path):
    """The OS recycles pids: THIS process's first dump to a path must
    truncate a prior process's leftover file, not append to it (appending
    would mix two post-mortems AND refresh the mtime the freshness
    horizon checks)."""
    monkeypatch.setattr(
        fr, "dump_path", lambda pid=None: str(tmp_path / "777.flight"))
    monkeypatch.setattr(fr, "_dumped_paths", set())
    stale = tmp_path / "777.flight"
    stale.write_text('{"pid": 777, "reason": "prior-process-crash"}\n')
    fr.configure(enabled=True, capacity=8)
    fr.dump_to_file(reason="fresh")
    rows = fr.read_dump(777)
    reasons = [r["reason"] for r in rows if "reason" in r]
    assert reasons == ["fresh"]          # the stale section is gone
    fr.dump_to_file(reason="second")     # same process: appends
    rows = fr.read_dump(777)
    assert [r["reason"] for r in rows if "reason" in r] == ["fresh", "second"]


# ---------------------------------------------------------------------------
# Step watchdog (injected clock — no wall-clock sleeps)
# ---------------------------------------------------------------------------


def test_watchdog_quiet_before_timeout():
    clock = FakeClock()
    wd = StepWatchdog(timeout_s=30.0, clock=clock)
    clock.advance(29.9)
    assert not wd.stalled and not wd.check()


def test_watchdog_fires_once_per_stall_episode():
    clock = FakeClock()
    wd = StepWatchdog(timeout_s=30.0, clock=clock)
    clock.advance(31.0)
    assert wd.stalled
    assert wd.check() is True        # the one sweep trigger
    clock.advance(100.0)
    assert wd.check() is False       # same episode: no sweep storm
    assert wd.stalled_for_s() == pytest.approx(131.0)
    wd.notify_progress()             # progress re-arms
    assert not wd.stalled and wd.stalled_for_s() == 0.0
    clock.advance(31.0)
    assert wd.check() is True        # next episode fires again


# ---------------------------------------------------------------------------
# Goodput ledger (injected clock; the sum invariant is exact, not approx)
# ---------------------------------------------------------------------------


def test_ledger_buckets_sum_to_wall_clock_exactly():
    clock = FakeClock()
    led = GoodputLedger("run1", clock=clock)
    led.start("restore")            # gang bring-up
    clock.advance(12.0)
    led.mark("productive_step")
    clock.advance(50.0)
    led.mark("checkpoint")
    clock.advance(3.0)
    led.mark("productive_step")
    clock.advance(35.0)
    led.stop()
    b = led.buckets
    assert b["restore"] == 12.0
    assert b["productive_step"] == 85.0
    assert b["checkpoint"] == 3.0
    # the acceptance invariant: buckets sum EXACTLY to the wall-clock
    assert sum(b.values()) == 100.0 == led.wall_clock_s()
    snap = led.snapshot()
    assert sum(snap["buckets_s"].values()) == snap["wall_clock_s"]
    assert snap["goodput_ratio"] == pytest.approx(0.85)


def test_ledger_snapshot_accrues_open_span():
    clock = FakeClock()
    led = GoodputLedger("run2", clock=clock)
    led.start("productive_step")
    clock.advance(7.0)
    snap = led.snapshot()           # mid-run: open span accrued to now
    assert snap["buckets_s"]["productive_step"] == 7.0
    assert snap["wall_clock_s"] == 7.0 and snap["current"] == "productive_step"
    clock.advance(3.0)
    led.stop()
    assert led.wall_clock_s() == 10.0


def test_ledger_same_bucket_mark_is_idempotent():
    clock = FakeClock()
    led = GoodputLedger("run3", clock=clock)
    led.start("productive_step")
    clock.advance(5.0)
    led.mark("productive_step")     # trainer marks per result round
    clock.advance(5.0)
    led.mark("productive_step")
    led.stop()
    assert led.buckets["productive_step"] == 10.0
    assert led.wall_clock_s() == 10.0


def test_ledger_reclassify_input_wait_keeps_sum():
    clock = FakeClock()
    led = GoodputLedger("run4", clock=clock)
    led.start("productive_step")
    clock.advance(60.0)
    led.stop()
    moved = led.reclassify("productive_step", "input_wait", 14.0)
    assert moved == 14.0
    assert led.buckets["productive_step"] == 46.0
    assert led.buckets["input_wait"] == 14.0
    assert led.wall_clock_s() == 60.0  # moving never changes the sum
    # clamped to what the source actually holds
    moved = led.reclassify("productive_step", "input_wait", 1e9)
    assert moved == 46.0
    assert led.buckets["productive_step"] == 0.0
    assert led.wall_clock_s() == 60.0
    assert led.reclassify("productive_step", "input_wait", -5.0) == 0.0


def test_ledger_stopped_mark_is_a_noop():
    """A timed-out bench section thread that unblocks late calls mark()
    on a ledger whose result was already discarded — the stopped ledger
    must not resurrect accrual (phantom productive seconds on a partial
    round)."""
    clock = FakeClock()
    led = GoodputLedger("run_zombie", clock=clock)
    led.start("restore")
    clock.advance(5.0)
    led.stop()
    led.mark("productive_step")          # the zombie thread's late mark
    clock.advance(100.0)
    snap = led.snapshot()
    assert led.current is None
    assert snap["wall_clock_s"] == 5.0
    assert snap["buckets_s"]["productive_step"] == 0.0
    # start() reopens it (the trainer's restart paths never stop first,
    # but the ledger API stays symmetric)
    led.start("restore")
    clock.advance(1.0)
    led.mark("productive_step")
    assert led.current == "productive_step"


def test_ledger_metric_gauges_mirror_buckets_exactly():
    """ray_tpu_train_goodput_seconds is a gauge set from the ledger's
    buckets — after a reclassify the metric surface still sums to
    wall-clock exactly (a monotonic counter would double-book the moved
    seconds)."""
    from ray_tpu._private.runtime_metrics import TRAIN_GOODPUT_SECONDS

    clock = FakeClock()
    led = GoodputLedger("run_gauge", clock=clock)
    led.start("productive_step")
    clock.advance(10.0)
    led.stop()
    led.reclassify("productive_step", "input_wait", 4.0)
    pts = {p["tags"]["bucket"]: p["value"]
           for p in TRAIN_GOODPUT_SECONDS._snapshot()
           if p["tags"].get("run") == "run_gauge"}
    assert pts["productive_step"] == pytest.approx(6.0)
    assert pts["input_wait"] == pytest.approx(4.0)
    assert sum(pts.values()) == pytest.approx(led.wall_clock_s()) == 10.0
    assert pts == {b: v for b, v in led.buckets.items() if v}


def test_ledger_rejects_unknown_bucket():
    led = GoodputLedger("run5", clock=FakeClock())
    with pytest.raises(ValueError):
        led.start("coffee_break")
    led.start("restore")
    with pytest.raises(ValueError):
        led.mark("coffee_break")
    with pytest.raises(ValueError):
        led.reclassify("restore", "coffee_break", 1.0)


def test_ledger_stall_episode_and_recovery():
    """The trainer flips to `stall` when the watchdog fires and back to
    `productive_step` when results resume — replayed on one clock so the
    sum invariant holds across the episode."""
    clock = FakeClock()
    led = GoodputLedger("run6", clock=clock)
    wd = StepWatchdog(timeout_s=30.0, clock=clock)
    led.start("productive_step")
    clock.advance(20.0)
    wd.notify_progress()
    clock.advance(31.0)             # silence past the timeout
    assert wd.check()
    led.mark("stall")
    clock.advance(44.0)             # hang persists; no second sweep
    assert not wd.check()
    wd.notify_progress()            # a result landed: stall episode over
    led.mark("productive_step")
    clock.advance(5.0)
    led.stop()
    assert led.buckets["stall"] == 44.0
    assert led.buckets["productive_step"] == 56.0
    assert led.wall_clock_s() == 100.0


def test_ledger_preemption_replay_from_injected_notice():
    """Replay PR 4's injected preemption notice through the trainer's
    classification: the watcher fires a synthetic notice, the drain restart
    is charged to `preemption_recovery` (announced, not a failure), and the
    buckets still sum exactly."""
    fired = []
    w = TpuMaintenanceWatcher(on_notice=fired.append,
                              testing_notice="0.05:preempted:10")
    w.start()
    _wait_for(lambda: fired, timeout=5, desc="injected notice")
    w.stop()
    assert fired[0]["kind"] == "preempted"

    # trainer fit() transition sequence on a _PreemptionDrain episode
    clock = FakeClock()
    led = GoodputLedger("run7", clock=clock)
    led.start("restore")                # gang bring-up
    clock.advance(10.0)
    led.mark("productive_step")
    clock.advance(40.0)
    led.mark("checkpoint")              # round checkpoint persisted
    clock.advance(4.0)
    led.mark("productive_step")
    clock.advance(6.0)
    led.mark("preemption_recovery")     # notice observed -> gang restart
    clock.advance(25.0)
    led.mark("productive_step")         # restarted on survivors
    clock.advance(15.0)
    led.stop()
    b = led.buckets
    assert b["preemption_recovery"] == 25.0
    assert b["checkpoint"] == 4.0 and b["restore"] == 10.0
    assert b["productive_step"] == 61.0
    assert led.wall_clock_s() == 100.0
    snap = led.snapshot()
    assert sum(snap["buckets_s"].values()) == snap["wall_clock_s"] == 100.0
    assert set(snap["buckets_s"]) == set(BUCKETS)


def test_goodput_metrics_snapshot_shape():
    """The snapshot derives ratio/wall from the counter points."""
    from ray_tpu._private import runtime_metrics as rm

    clock = FakeClock()
    led = GoodputLedger("snap_run", clock=clock)
    led.start("restore")
    clock.advance(2.0)
    led.mark("productive_step")
    clock.advance(8.0)
    led.stop()
    snap = rm.goodput_metrics_snapshot()
    row = snap["snap_run"]
    assert row["buckets_s"]["productive_step"] >= 8.0
    assert 0.0 < row["goodput_ratio"] <= 1.0
    assert row["wall_clock_s"] >= 10.0


# ---------------------------------------------------------------------------
# Arrival monitor / straggler scores (store actor object, injected clock)
# ---------------------------------------------------------------------------


def _store_with_clock(clock):
    s = _CollectiveStoreActor()
    s._clock = clock
    return s


def test_arrival_monitor_names_missing_rank():
    clock = FakeClock()
    s = _store_with_clock(clock)
    s.declare_group("g", 3, "store")
    for r in range(3):
        s.join_member("g", r, {"actor_id": f"a{r}", "node_id": f"n{r}"})
    key = ("g", "barrier", 1)
    s.barrier_arrive(key, 0, 3)
    clock.advance(2.0)
    s.barrier_arrive(key, 1, 3)
    clock.advance(40.0)             # rank 2 never arrives
    rep = s.straggler_report()
    g = rep["groups"]["g"]
    assert len(g["pending"]) == 1
    round_ = g["pending"][0]
    assert round_["op"] == "barrier" and round_["seq"] == 1
    assert round_["arrived"] == [0, 1] and round_["missing"] == [2]
    assert round_["waiting_s"] == pytest.approx(42.0)
    assert g["members"][2]["actor_id"] == "a2"
    # the late arrival completes the round: pending drains, EWMA appears
    s.barrier_arrive(key, 2, 3)
    rep = s.straggler_report("g")
    g = rep["groups"]["g"]
    assert g["pending"] == []
    assert g["lag_ewma_s"][2] == pytest.approx(42.0)
    assert g["lag_ewma_s"][0] == 0.0


def test_arrival_monitor_gather_round_learns_expected_from_reader():
    """contribute() doesn't carry the world size; the first collect() poll
    teaches the round its expected count so missing ranks are computable."""
    clock = FakeClock()
    s = _store_with_clock(clock)
    s.declare_group("g2", 3, "store")
    key = ("g2", "allreduce", 7)
    s.contribute(key, 0, [1.0])
    clock.advance(1.0)
    assert s.collect(key, 3, 0) is None   # still waiting; expected learned
    clock.advance(30.0)
    rep = s.straggler_report("g2")
    round_ = rep["groups"]["g2"]["pending"][0]
    assert round_["expected"] == 3
    assert round_["missing"] == [1, 2]
    assert round_["op"] == "allreduce" and round_["seq"] == 7


def test_arrival_monitor_subgroup_round_speaks_global_ranks():
    """Hierarchical subgroup rounds contribute under SUBRANKS (the gather
    key) but stamp arrivals under group-global ranks with the subgroup's
    member set — so a hang in slice 1 names global rank 5, never the
    subrank-1 member of a different slice, and completed rounds feed the
    EWMA under global ranks (world 8, slice_size 4 ⇒ hier_rs_s1 members
    are global ranks 4..7)."""
    clock = FakeClock()
    s = _store_with_clock(clock)
    s.declare_group("gh", 8, "store")
    for r in range(8):
        s.join_member("gh", r, {"actor_id": f"a{r}", "node_id": f"n{r}"})
    key = ("gh", "hier_rs_s1", 3)
    members = [4, 5, 6, 7]
    for g, sub in ((4, 0), (6, 2), (7, 3)):   # global rank 5 withheld
        s.contribute(key, sub, [1.0], arrival_rank=g, expected_ranks=members)
    clock.advance(40.0)
    round_ = s.straggler_report("gh")["groups"]["gh"]["pending"][0]
    assert round_["arrived"] == [4, 6, 7]
    assert round_["missing"] == [5]
    assert round_["expected"] == 4
    # late arrival completes the round: lag lands on GLOBAL rank 5
    s.contribute(key, 1, [1.0], arrival_rank=5, expected_ranks=members)
    g = s.straggler_report("gh")["groups"]["gh"]
    assert g["pending"] == []
    assert g["lag_ewma_s"][5] == pytest.approx(40.0)
    assert 1 not in g["lag_ewma_s"]


def test_straggler_ewma_converges_on_persistent_laggard():
    """Rank 2 is 5s late every round: its EWMA converges toward 5s while
    punctual ranks stay ~0 (the persistent-straggler score)."""
    clock = FakeClock()
    s = _store_with_clock(clock)
    s.declare_group("g3", 3, "store")
    for seq in range(1, 9):
        key = ("g3", "barrier", seq)
        s.barrier_arrive(key, 0, 3)
        s.barrier_arrive(key, 1, 3)
        clock.advance(5.0)
        s.barrier_arrive(key, 2, 3)
        clock.advance(1.0)
    lags = s.straggler_report("g3")["groups"]["g3"]["lag_ewma_s"]
    assert lags[0] == 0.0 and lags[1] == 0.0
    assert lags[2] == pytest.approx(5.0, abs=0.01)
    # surfaced as the metric family too
    from ray_tpu._private.runtime_metrics import COLLECTIVE_STRAGGLER_LAG

    pts = {(p["tags"]["group"], p["tags"]["rank"]): p["value"]
           for p in COLLECTIVE_STRAGGLER_LAG._snapshot()}
    assert pts[("g3", "2")] == pytest.approx(5.0, abs=0.01)


def test_arrival_state_cleared_with_group():
    clock = FakeClock()
    s = _store_with_clock(clock)
    s.declare_group("g4", 2, "store")
    s.barrier_arrive(("g4", "barrier", 1), 0, 2)
    assert s.straggler_report("g4")["groups"]["g4"]["pending"]
    s.declare_group("g4", 2, "store")   # re-init clears stale rounds
    g = s.straggler_report("g4")["groups"].get("g4", {})
    assert g.get("pending", []) == []


# ---------------------------------------------------------------------------
# Acceptance: injected hang in a real cluster -> diagnose names the blocker
# ---------------------------------------------------------------------------


def _make_member_class():
    class _Member:
        def __init__(self, rank, world, group):
            self.rank = rank
            col.init_collective_group(world, rank, backend="store",
                                      group_name=group)
            self.group = group

        def barrier_then(self, v):
            col.barrier(self.group)
            return v

        def my_ids(self):
            ctx = ray_tpu.get_runtime_context()
            return (ctx.get_actor_id().hex(), ctx.get_node_id().hex())

    return _Member


@pytest.mark.timeout(180)
def test_injected_hang_diagnose_names_blocking_member(ray_start_regular):
    """One collective member deliberately withheld (chaos-style per
    test_preemption): state.diagnose() must name the blocking worker, node
    and collective op within hang_detect_timeout_s + 2s — and must NOT
    flag a healthy run."""
    from ray_tpu.util import state

    M = ray_tpu.remote(_make_member_class()).options(num_cpus=0)
    members = [M.remote(r, 3, "hang_g") for r in range(3)]
    ids = ray_tpu.get([m.my_ids.remote() for m in members], timeout=120)

    # healthy round: all three arrive; no false positive
    assert ray_tpu.get([m.barrier_then.remote(i)
                        for i, m in enumerate(members)], timeout=60) == [0, 1, 2]
    rep = state.diagnose(hang_timeout_s=1.0, source="test-healthy")
    assert rep["hung"] is False and rep["blocking"] == []
    assert "hang_g" in rep["stragglers"]  # completed rounds scored

    # withhold rank 2: ranks 0 and 1 enter the barrier and wait
    t0 = time.monotonic()
    pending = [members[0].barrier_then.remote(0),
               members[1].barrier_then.remote(1)]
    rep = _wait_for(
        lambda: (lambda r: r if r["hung"] else None)(
            state.diagnose(hang_timeout_s=1.0, source="test-hang")),
        timeout=30, interval=0.25, desc="diagnose flags the hang")
    elapsed = time.monotonic() - t0
    assert elapsed < 1.0 + 2.0, f"diagnosis took {elapsed:.2f}s"

    rows = [b for b in rep["blocking"] if b["group"] == "hang_g"]
    assert rows, rep["blocking"]
    b = rows[0]
    assert b["op"] == "barrier" and b["rank"] == 2
    assert (b["actor_id"], b["node_id"]) == ids[2]  # the withheld member
    assert b["pid"], "blocking member resolves to a live process"
    assert b["waiting_s"] >= 1.0
    # stacks of the blocking worker are folded in
    assert any(s.get("pid") == b["pid"] for s in rep.get("stacks") or [])
    # flight-recorder tails came back from the cluster's processes, and the
    # waiting members' last entries show the barrier they entered
    tails = rep["flight_recorder"]
    assert len(tails) >= 3
    entered = [e for row in tails for e in row.get("entries") or []
               if e["kind"] == "collective" and "hang_g:barrier" in e["name"]
               and str(e.get("detail", "")).startswith("enter")]
    assert len(entered) >= 2

    # release the withheld member: the round completes, next sweep is clean
    pending.append(members[2].barrier_then.remote(2))
    assert ray_tpu.get(pending, timeout=60) == [0, 1, 2]
    rep = state.diagnose(hang_timeout_s=1.0, source="test-released")
    assert rep["hung"] is False and rep["blocking"] == []
    # the withheld member now carries the dominant straggler score
    lags = rep["stragglers"]["hang_g"]
    lag2 = lags.get(2, lags.get("2"))
    assert lag2 == max(lags.values())


@pytest.mark.timeout(180)
def test_flight_recorder_state_api_and_task_marks(ray_start_regular):
    """state.flight_recorder() folds per-process tails over the agent RPC;
    worker rings carry the task start/end transitions."""
    from ray_tpu.util import state

    @ray_tpu.remote
    def traced_work(x):
        return x * 2

    assert ray_tpu.get([traced_work.remote(i) for i in range(4)],
                       timeout=120) == [0, 2, 4, 6]
    rows = state.flight_recorder(seconds=300)
    assert any(r.get("role") == "raylet" for r in rows)
    task_marks = [e for r in rows for e in r.get("entries") or []
                  if e["kind"] == "task" and e["name"] == "traced_work"]
    starts = [e for e in task_marks
              if str(e.get("detail", "")).startswith("start")]
    ends = [e for e in task_marks if str(e.get("detail", "")).startswith("end")]
    assert len(starts) >= 4 and len(ends) >= 4
    # lease transitions from the owner-side submitter are recorded too
    assert any(e["kind"] == "lease" for r in rows
               for e in r.get("entries") or [])


@pytest.mark.timeout(180)
def test_dead_worker_dump_folded_by_agent(ray_start_regular):
    """A crashed worker that was already reaped from the pool leaves only
    its <pid>.flight file; the agent endpoint scans the dump dir and
    surfaces it as a dead-worker row."""
    import os

    from ray_tpu.util import state

    # a pid no live worker owns (our own pid is not in the raylet pool)
    fake_pid = os.getpid()
    path = fr.dump_path(fake_pid)
    try:
        with open(path, "w") as f:
            f.write(json.dumps({"pid": fake_pid,
                                "reason": "uncaught:BoomError",
                                "time": time.time()}) + "\n")
            f.write(json.dumps({"time": time.time(), "kind": "collective",
                                "name": "g:allreduce",
                                "detail": "enter:seq9:rank1/4"}) + "\n")
        rows = state.flight_recorder()
        dead = [r for r in rows if r.get("role") == "dead-worker"
                and r.get("pid") == fake_pid]
        assert dead, [r.get("role") for r in rows]
        dump = dead[0]["crash_dump"]
        assert any(e.get("reason") == "uncaught:BoomError" for e in dump)
        assert any(e.get("kind") == "collective" for e in dump)
        # pid-targeted reads hit it too; other pids don't
        assert any(r.get("pid") == fake_pid
                   for r in state.flight_recorder(pid=fake_pid))
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


@pytest.mark.timeout(180)
def test_goodput_published_to_state_and_dashboard(ray_start_regular):
    """Ledger -> GCS KV -> state.goodput() / GET /api/goodput; plus the
    diagnose + flight-recorder dashboard endpoints round-trip."""
    from ray_tpu.dashboard import DashboardHead
    from ray_tpu.util import state

    led = GoodputLedger("pubrun", job_id="j0b")
    led.start("restore")
    led.mark("productive_step")
    led.stop()
    assert led.publish(force=True)

    got = state.goodput()
    assert "pubrun" in got
    snap = got["pubrun"]
    assert set(snap["buckets_s"]) == set(BUCKETS)
    assert sum(snap["buckets_s"].values()) == pytest.approx(
        snap["wall_clock_s"])
    # narrowing by run name and by job id both hit
    assert "pubrun" in state.goodput("pubrun")
    assert "pubrun" in state.goodput("j0b")
    assert state.goodput("nope") == {}

    head = DashboardHead()
    try:
        def _get(path):
            with urllib.request.urlopen(head.url + path, timeout=30) as resp:
                return json.loads(resp.read())

        view = _get("/api/goodput?run=pubrun")
        assert view["pubrun"]["goodput_ratio"] == pytest.approx(
            snap["goodput_ratio"])
        fr_view = _get("/api/flight_recorder?seconds=300")
        assert any(r.get("role") == "raylet" for r in fr_view)
        diag = _get("/api/diagnose?hang_timeout_s=5")
        assert diag["hung"] is False and "flight_recorder" in diag
    finally:
        head.shutdown()
