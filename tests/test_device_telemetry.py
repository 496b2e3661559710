"""Chip-level telemetry (ISSUE 16): HBM accounting, engine utilization &
headroom, compile watch + storm detector, MFU accounting, the telemetry
heartbeat, on-demand profiler capture, and the bench_diff reader.

The occupancy tests assert EXACT equality against the engine's own
bookkeeping (``_slot_req`` / ``blocks.num_free()``) — utilization rows
are the SLO-feedback autoscaler's input surface, so "close" is wrong.
"""

import gc
import json
import os
import sys
import time

import pytest

from ray_tpu._private import device_telemetry as dt
from ray_tpu._private import runtime_metrics as rtm
from ray_tpu._private.config import global_config

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def reset_telemetry():
    dt._reset_for_tests()
    yield
    dt._reset_for_tests()


def _metric_state():
    """Canonical byte string of every device-telemetry metric point."""
    return json.dumps(rtm.device_telemetry_snapshot(), sort_keys=True)


# ---------------------------------------------------------------------------
# EngineTelemetry math (injected clock — no wall-clock racing)
# ---------------------------------------------------------------------------


def test_engine_telemetry_duty_and_spend_math():
    tel = dt.EngineTelemetry("dep-math", weights_bytes=100, kv_pool_bytes=50,
                             clock=lambda: 100.0, flush_interval_s=1e9)
    tel.note_step(active_slots=3, max_slots=8, free_blocks=20,
                  total_blocks=31, pending=2, prefill_spent=64,
                  prefill_budget=256, busy_s=0.5, now=101.0)
    # wall = 101 - 100 = 1s, busy 0.5s
    assert tel.duty_cycle == 0.5
    r = tel.rates()
    assert r["prefill_spend_ratio"] == 0.25
    assert r["prefill_spent_tokens"] == 64
    assert r["steps"] == 1
    # busy > wall (clock skew / overlapping dispatch): duty clamps to 1.0
    tel.note_step(active_slots=8, max_slots=8, free_blocks=0,
                  total_blocks=31, pending=5, prefill_spent=0,
                  prefill_budget=256, busy_s=5.0, now=102.0)
    assert tel.duty_cycle == 1.0
    assert tel.rates()["prefill_spend_ratio"] == 0.0
    # a fully idle gap depresses duty exactly: 0.1 busy over 10 wall
    tel.note_step(active_slots=1, max_slots=8, free_blocks=30,
                  total_blocks=31, pending=0, prefill_spent=0,
                  prefill_budget=0, busy_s=0.1, now=112.0)
    assert tel.duty_cycle == pytest.approx(0.01)
    assert tel.rates()["prefill_spend_ratio"] == 0.0  # budget 0: no div


def test_hbm_split_transient_clamped(monkeypatch):
    tel = dt.EngineTelemetry("dep-hbm", weights_bytes=300, kv_pool_bytes=200,
                             clock=lambda: 0.0, flush_interval_s=1e9)
    monkeypatch.setattr(dt, "device_used_bytes", lambda: 1000)
    split = tel.hbm_split()
    assert split == {"weights_bytes": 300, "kv_pool_bytes": 200,
                     "transient_bytes": 500, "device_used_bytes": 1000}
    # another process freed our view of the chip: transient clamps at 0
    monkeypatch.setattr(dt, "device_used_bytes", lambda: 100)
    assert dt.EngineTelemetry(
        "d", weights_bytes=300, kv_pool_bytes=200, clock=lambda: 0.0,
        flush_interval_s=1e9).hbm_split()["transient_bytes"] == 0


def test_fold_utilization_rows_headroom_exact(reset_telemetry):
    rows = [
        {"deployment": "dep", "replica": "r1", "duty_cycle": 0.25,
         "slots": {"active": 3, "max": 8, "free": 5},
         "kv_blocks": {"total": 31, "free": 20, "used": 11}},
        {"deployment": "dep", "replica": "r2", "duty_cycle": 0.75,
         "slots": {"active": 5, "max": 8, "free": 3},
         "kv_blocks": {"total": 31, "free": 10, "used": 21}},
        {"deployment": "other", "replica": "r3",
         "slots": {"active": 0, "max": 4, "free": 4},
         "kv_blocks": {"total": 15, "free": 15, "used": 0}},
    ]
    snap = dt.fold_utilization_rows(rows)
    assert snap["replicas"] == 3
    d = snap["deployments"]["dep"]
    # headroom = capacity - occupancy, exactly
    assert d["active_slots"] == 8 and d["total_slots"] == 16
    assert d["free_slots"] == d["total_slots"] - d["active_slots"]
    assert d["free_kv_blocks"] == 30 and d["total_kv_blocks"] == 62
    assert d["slot_occupancy"] == pytest.approx(8 / 16)
    assert d["kv_occupancy"] == pytest.approx(32 / 62, abs=1e-4)
    assert d["mean_duty_cycle"] == pytest.approx(0.5)
    o = snap["deployments"]["other"]
    assert o["slot_occupancy"] == 0.0 and o["kv_occupancy"] == 0.0
    assert o["mean_duty_cycle"] == 0.0  # no duty reported: 0, not NaN


def test_local_provider_registry_weakref_prune(reset_telemetry):
    class FakeEngine:
        def utilization(self):
            return {"deployment": "weak-dep",
                    "slots": {"active": 1, "max": 2, "free": 1},
                    "kv_blocks": {"total": 7, "free": 7, "used": 0}}

    eng = FakeEngine()
    dt.register_utilization_object("weak-dep:0", eng)
    rows = dt.local_utilization_rows()
    assert len(rows) == 1
    assert rows[0]["replica"] == "weak-dep:0"
    assert rows[0]["source"] == "local"
    del eng
    gc.collect()
    assert dt.local_utilization_rows() == []
    # and the dead provider was pruned from the registry itself
    with dt._providers_lock:
        assert "weak-dep:0" not in dt._providers


def test_util_kv_key_shape():
    assert dt.util_kv_key("app", "dep", "abc123") == "util:app/dep/abc123"
    assert dt.util_kv_key("a", "d", "r").startswith(dt.UTIL_KV_PREFIX)


# ---------------------------------------------------------------------------
# MFU accounting
# ---------------------------------------------------------------------------


def test_mfu_matches_hand_computed_flops_over_wall():
    # 2e9 FLOPs in 0.5s against a 1e12 FLOPs/s roofline = 0.4% MFU
    mfu = dt.note_train_step("mfu-test-run", model_flops=2e9, wall_s=0.5,
                             peak=1e12)
    assert mfu == pytest.approx(2e9 / 0.5 / 1e12)
    assert rtm.device_telemetry_snapshot()["train_mfu"][
        "mfu-test-run"] == pytest.approx(mfu)
    # degenerate inputs book nothing and return 0
    assert dt.note_train_step("r", model_flops=0, wall_s=1.0) == 0.0
    assert dt.note_train_step("r", model_flops=1e9, wall_s=0.0) == 0.0


def test_jit_flops_from_cost_analysis_hand_computed():
    import jax.numpy as jnp

    # (8,8) @ (8,8): 2*M*N*K = 1024 FLOPs — XLA's figure must match the
    # hand count exactly on this kernel
    x = jnp.ones((8, 8), jnp.float32)
    flops = dt.jit_flops(lambda a: a @ a, x, key="tel-test-matmul")
    assert flops == 1024.0
    # cached: same key returns without re-lowering
    assert dt.jit_flops(lambda a: a @ a, x, key="tel-test-matmul") == 1024.0


def test_serving_rate_per_chip_normalization():
    per_chip = dt.note_serving_rate("rate-dep", 1000.0, n_chips=4)
    assert per_chip == 250.0
    assert rtm.device_telemetry_snapshot()["serve_tokens_per_chip"][
        "rate-dep"] == 250.0


# ---------------------------------------------------------------------------
# Disabled path: books nothing, byte-identical metric output
# ---------------------------------------------------------------------------


def test_disabled_path_books_nothing(reset_telemetry):
    cfg = global_config()
    saved = cfg.device_telemetry_enabled
    cfg.device_telemetry_enabled = False
    try:
        before = _metric_state()
        # engines get no recorder at all
        assert dt.engine_telemetry_for("some-dep") is None
        # every recorder goes quiet (the snapshot APIs still work)
        dt.record_hbm()
        dt.note_train_step("off-run", model_flops=1e12, wall_s=1.0,
                           peak=1e12)
        dt.note_serving_rate("off-dep", 500.0)
        dt.note_trace("off-program", shape_key=(1,))
        dt._watch.note_compile("off-program", 0.25)
        assert _metric_state() == before, "disabled path booked a point"
        # ...but the watch itself still counts (compile_count() APIs must
        # work with the metric layer off — the rl pin depends on it)
        assert dt.trace_count("off-program") == 1
    finally:
        cfg.device_telemetry_enabled = saved


def test_engine_telemetry_for_unnamed_engine_is_none():
    # engines not serving a named deployment never book
    assert dt.engine_telemetry_for(None) is None


# ---------------------------------------------------------------------------
# Compile watch + storm detector
# ---------------------------------------------------------------------------


def test_note_trace_attributes_backend_compiles(reset_telemetry):
    import jax
    import jax.numpy as jnp

    prog = "tel.test.attr_prog"

    @jax.jit
    def f(x):
        dt.note_trace(prog, shape_key=x.shape)
        return x * 2

    f(jnp.ones((4,))).block_until_ready()
    f(jnp.ones((4,))).block_until_ready()  # cache hit: no retrace
    assert dt.trace_count(prog) == 1
    snap = dt.compile_snapshot()
    assert snap["compiles"].get(prog, 0) >= 1
    assert snap["compile_seconds"].get(prog, 0.0) > 0.0
    f(jnp.ones((5,))).block_until_ready()  # new shape: retrace
    assert dt.trace_count(prog) == 2


def test_unattributed_compiles_book_under_sentinel(reset_telemetry):
    dt._watch.note_compile(None, 0.125)
    snap = dt.compile_snapshot()
    assert snap["compiles"]["_jax"] == 1
    assert snap["compile_seconds"]["_jax"] == pytest.approx(0.125)


def test_storm_report_names_churning_program(reset_telemetry):
    quiet = "tel.test.quiet"
    churn = "tel.test.shape_churn"
    dt.note_trace(quiet, shape_key=(2, 64))
    for i in range(6):  # shape churn: a new bucket every call
        dt.note_trace(churn, shape_key=(2, 64 + i))
    report = dt.storm_report(threshold=5, window_s=60.0)
    assert [r["program"] for r in report] == [churn]
    row = report[0]
    assert row["compiles"] == 6
    assert row["total_traces"] == 6
    assert len(row["shape_keys"]) == 6  # the churning shapes, named
    # the storm report blames the retracing call site
    assert "test_device_telemetry.py" in row["callers"]
    # below threshold / outside window: silence
    assert dt.storm_report(threshold=7, window_s=60.0) == []
    assert dt.storm_report(threshold=1, window_s=1e-9) == []


# ---------------------------------------------------------------------------
# Heartbeat (gauge expiry during long compiles)
# ---------------------------------------------------------------------------


def test_heartbeat_pushes_without_step_traffic(monkeypatch):
    """The regression the heartbeat fixes: every normal metrics push rides
    request/step completions, so a replica whose threads are all blocked
    inside one long jit compile stops pushing and the GCS's 30s sweep
    expires its gauges.  The daemon heartbeat must keep pushing with ZERO
    step traffic (here: nothing else in this test touches the metrics
    layer — the pushes can only come from the heartbeat thread)."""
    pushes = []
    monkeypatch.setattr(dt, "_heartbeat_push",
                        lambda: pushes.append(time.monotonic()))
    cfg = global_config()
    saved = cfg.device_telemetry_heartbeat_s
    cfg.device_telemetry_heartbeat_s = 0.05
    try:
        dt._start_heartbeat()
        with dt._hb_lock:
            t = dt._hb_thread
        assert t is not None and t.daemon and t.is_alive()
        # an already-running thread may be mid-sleep on the default 5s
        # period; it re-reads the config every loop, so give it one full
        # default period before the fast cadence must show
        deadline = time.monotonic() + 8.0
        while len(pushes) < 3 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(pushes) >= 3, (
            f"heartbeat made {len(pushes)} pushes in 8s at a 50ms period")
    finally:
        cfg.device_telemetry_heartbeat_s = saved


# ---------------------------------------------------------------------------
# Engine wiring: utilization() == the engine's own books, exactly
# ---------------------------------------------------------------------------


def _micro_cfg():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny(vocab_size=48, dim=32, n_layers=1, n_heads=2,
                            n_kv_heads=1, ffn_dim=64, max_seq_len=48,
                            compute_dtype=jnp.float32)


def test_paged_engine_utilization_matches_internal_books(reset_telemetry):
    import jax
    import numpy as np

    from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
    from ray_tpu.models.llama import init_params

    cfg = _micro_cfg()
    lcfg = LLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=48,
                     block_size=8, prefill_chunk=16, decode_chunk=4,
                     num_blocks=24)
    eng = PagedJaxLLMEngine(lcfg, params=init_params(cfg,
                                                     jax.random.PRNGKey(0)))
    eng.slo_label = "tel-paged"
    assert eng._telemetry is not None
    for s in (0, 1):
        prompt = list(np.random.RandomState(s).randint(1, 47, size=9))
        eng.add_request(prompt, GenerationConfig(max_new_tokens=6))
    for _ in range(3):
        eng.step()
    u = eng.utilization()
    # exact equality against the engine's own bookkeeping
    with eng._lock:
        active = sum(1 for r in eng._slot_req if r is not None)
        free = eng.blocks.num_free()
        cached = len(eng.blocks.free_cached)
        pending = len(eng._pending)
    assert u["engine"] == "paged"
    assert u["deployment"] == "tel-paged"
    assert u["slots"] == {"active": active, "max": 2,
                          "free": 2 - active}
    # block 0 is the sink and never allocated: capacity = num_blocks-1
    # (cached: free blocks that still hold a registered prefix)
    assert u["kv_blocks"] == {"total": 23, "free": free,
                              "used": 23 - free, "cached": cached}
    assert u["pending"] == pending
    assert 0.0 <= u["duty_cycle"] <= 1.0
    assert u["rates"]["steps"] == 3
    hbm = u["hbm"]
    assert hbm["weights_bytes"] == dt.tree_nbytes(eng.params)
    assert hbm["kv_pool_bytes"] == dt.tree_nbytes(eng.pool)
    assert hbm["transient_bytes"] >= 0
    # the local fold (what state.utilization() serves with no
    # cluster) names the deployment with the same exact numbers
    from ray_tpu.util import state

    snap = state.utilization()
    d = snap["deployments"]["tel-paged"]
    assert d["active_slots"] == active
    assert d["free_slots"] == 2 - active
    assert d["free_kv_blocks"] == free
    assert d["total_kv_blocks"] == 23
    assert state.utilization("no-such-dep")["deployments"] == {}


def test_disagg_local_app_utilization_fold(reset_telemetry):
    """state.utilization() on a live disagg-shaped app: both stage
    deployments fold with per-replica internal-books-exact rows (the
    acceptance surface for the SLO-feedback autoscaler)."""
    import jax
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig, build_disagg_llm_deployment
    from ray_tpu.models.llama import init_params
    from ray_tpu.util import state

    cfg = _micro_cfg()
    lcfg = LLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=48,
                     block_size=8, prefill_chunk=16, decode_chunk=4,
                     num_blocks=24)
    app = build_disagg_llm_deployment(
        lcfg, init_params(cfg, jax.random.PRNGKey(0)), name="dtel")
    h = serve.run(app, name="dtel-app", _local_testing_mode=True)
    try:
        prompt = list(np.random.RandomState(3).randint(1, 47, size=11))
        out = h.generate.remote(prompt=prompt,
                                max_new_tokens=4).result(timeout_s=120)
        assert len(out) == 4
        snap = state.utilization()
        deps = snap["deployments"]
        assert "dtel-prefill" in deps and "dtel-decode" in deps
        for dep in deps.values():
            assert dep["replicas"], "deployment folded with no rows"
            # headroom = capacity - occupancy, per deployment and per row
            assert dep["free_slots"] == \
                dep["total_slots"] - dep["active_slots"]
            for row in dep["replicas"]:
                s, b = row["slots"], row["kv_blocks"]
                assert s["free"] == s["max"] - s["active"]
                assert b["used"] == b["total"] - b["free"]
                assert 0.0 <= row["duty_cycle"] <= 1.0
        # the prefill stage really spent chunked-prefill budget
        pre = deps["dtel-prefill"]["replicas"][0]
        assert pre["rates"]["prefill_spent_tokens"] == len(prompt)
        assert pre["rates"]["prefill_budget_tokens"] == 16
    finally:
        serve.delete("dtel-app")


# ---------------------------------------------------------------------------
# Cluster surface: diagnose storm fold + profiler round-trip
# ---------------------------------------------------------------------------


def test_profile_roundtrip_and_storm_in_diagnose(ray_start_regular,
                                                 reset_telemetry):
    import ray_tpu
    from ray_tpu.util import state

    @ray_tpu.remote
    class Sleeper:
        def pid(self):
            return os.getpid()

        def nap(self, s):
            time.sleep(s)
            return True

    a = Sleeper.remote()
    pid = ray_tpu.get(a.pid.remote())
    ref = a.nap.remote(6.0)
    # cpu mode: deterministic on the CPU lane (jax_profile needs the
    # target to be running jitted compute; test_reporter covers it)
    out = state.profile(pid, duration_s=0.5, mode="cpu")
    assert out["pid"] == pid and out["mode"] == "cpu"
    assert out["samples"] > 0
    assert isinstance(out["trace_ids"], list)
    # the artifact round-trips: a real file holding the stack samples
    assert os.path.exists(out["artifact"])
    with open(out["artifact"]) as f:
        art = json.load(f)
    assert art["pid"] == pid and art["stacks"]
    os.unlink(out["artifact"])
    with pytest.raises(ValueError):
        state.profile(pid, mode="flamegraph")
    # compile storm (driver-side churn) surfaces in state.diagnose()
    for i in range(6):
        dt.note_trace("tel.test.diagnose_churn", shape_key=(i,))
    report = state.diagnose()
    assert any(r["program"] == "tel.test.diagnose_churn"
               for r in report["compile_storm"])
    assert ray_tpu.get(ref, timeout=60) is True


# ---------------------------------------------------------------------------
# bench_diff: the BENCH_r*.json mechanical reader
# ---------------------------------------------------------------------------


def _round(tmp_path, name, parsed):
    """One round in the driver's wrapper shape: {n, cmd, rc, tail, parsed},
    where ``tail`` is the end of the round's output (its last line is the
    result document) and ``parsed`` that document."""
    p = tmp_path / name
    p.write_text(json.dumps({"n": 1, "cmd": "bench", "rc": 0,
                             "tail": json.dumps(parsed), "parsed": parsed}))
    return str(p)


def test_bench_diff_flags_regressions_directionally(tmp_path):
    from tools.bench_diff import main, run

    old = _round(tmp_path, "BENCH_r01.json", {
        "metric": "train_mfu", "value": 0.50,
        "extra": {"step_time_s": 1.0,
                  "serving": {"aggregate_tok_per_sec": 100.0,
                              "ttft_p50_ms": 30.0}}})
    worse = _round(tmp_path, "BENCH_r02.json", {
        "metric": "train_mfu", "value": 0.40,          # -20% MFU: regress
        "extra": {"step_time_s": 1.5,                   # +50% step: regress
                  "serving": {"aggregate_tok_per_sec": 85.0,  # -15%: regress
                              "ttft_p50_ms": 31.0}}})   # +3%: under gate
    report = run(old, worse, threshold=0.10)
    regressed = {r["metric"] for r in report["regressions"]}
    assert regressed == {"value", "extra.step_time_s",
                         "extra.serving.aggregate_tok_per_sec"}
    assert {r["section"] for r in report["regressions"]} == \
        {"headline", "serving"}
    by_metric = {r["metric"]: r
                 for rows in report["sections"].values() for r in rows}
    assert by_metric["extra.serving.ttft_p50_ms"]["regression"] is False
    assert main([old, worse, "--threshold", "0.10"]) == 1
    # pure improvement exits clean
    assert main([worse, old, "--threshold", "0.10"]) == 0


def test_bench_diff_tolerates_partial_rounds(tmp_path):
    from tools.bench_diff import main, run

    good = _round(tmp_path, "BENCH_r01.json",
                  {"metric": "train_mfu", "value": 0.5,
                   "extra": {"tokens_per_sec": 1000.0}})
    dead = _round(tmp_path, "BENCH_r02.json",
                  {"metric": "train_mfu", "value": 0.0,
                   "error": "no output"})
    nul = tmp_path / "BENCH_r03.json"
    nul.write_text(json.dumps({"n": 3, "cmd": "bench", "rc": 1,
                               "parsed": None}))
    # a dead round shares no improving leaves — must not crash or flag
    report = run(good, str(nul), threshold=0.10)
    assert report["changed"] == 0 and report["regressions"] == []
    assert main([good, str(nul)]) == 0
    assert main([str(dead), good]) == 0  # recovery is not a regression
    # default mode picks the newest two rounds in --dir
    assert main(["--dir", str(tmp_path), "--threshold", "1000"]) == 0


def test_bench_diff_reads_checked_in_rounds(tmp_path):
    from tools.bench_diff import run

    # two rounds of the record's shape and trajectory (ROADMAP "What the
    # record holds": r01 and r03), written here: the record files
    # themselves left the tree with the device link they were taken through
    def r(mfu, tps, step):
        return {"metric": "llama1b_train_mfu_1chip", "value": mfu,
                "unit": "MFU", "vs_baseline": round(mfu / 0.40, 4),
                "extra": {"tokens_per_sec": tps, "step_time_s": step,
                          "device": "TPU v5 lite"}}

    report = run(_round(tmp_path, "BENCH_r01.json", r(0.6461, 16631.9, 0.9851)),
                 _round(tmp_path, "BENCH_r03.json", r(0.6452, 16609.2, 0.9865)),
                 threshold=0.5)
    # the real trajectory: headline leaves shared and compared
    assert "headline" in report["sections"]
    metrics = {r["metric"] for r in report["sections"]["headline"]}
    assert "value" in metrics
