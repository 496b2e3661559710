"""Microbenchmark suite smoke (reference: _private/ray_perf.py runs per
release; here we assert the harness runs and reports sane rates) plus the
hermetic lease fast-path budget guard (ISSUE 5): steady-state submission
must reuse cached leases instead of paying a lease RPC per task."""

import math
import os
import sys


def test_flight_recorder_overhead_under_budget():
    """The flight recorder rides EVERY hot path (task exec, collective
    entry/exit, lease transitions) always-on, so its record cost is
    budget-gated like the metrics/tracing recorders: generous CI budgets
    (order-of-magnitude guard, not scheduler-noise sensitivity); idle-host
    numbers are ~0.3-0.9 µs enabled, ~0.1 µs disabled."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.flight_recorder_overhead_bench import run

    enabled, disabled = run()
    assert max(enabled.values()) < 25_000, enabled
    assert max(disabled.values()) < 5_000, disabled


def test_slo_record_overhead_under_budget():
    """The serving SLO ledger's per-token recorder runs once per SSE frame
    at full decode rate and its stage recorders run under the engine step
    lock (ISSUE 9): enabled record < 5 µs, disabled (NOOP tracker) <
    0.5 µs, and the 64-replica sketch fold state.serving_slo() pays stays
    bounded.  CI-loose budgets — idle-host numbers are ~1-3 µs enabled,
    ~0.1 µs disabled, ~7 ms for the 64-way fold."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.slo_overhead_bench import run

    extra = run()
    assert extra["tokens_enabled_ns"] < 5_000, extra
    assert extra["stage_enabled_ns"] < 5_000, extra
    assert extra["tokens_disabled_ns"] < 500, extra
    assert extra["merge_64_ms"] < 250, extra
    assert extra["merge_64_count"] == 64 * 10_000, extra


def test_device_telemetry_overhead_under_budget():
    """The device-telemetry booking path runs once per engine step right
    after the lock is released, and the disabled path is one attribute
    read + None check inside ``step()`` (ISSUE 16): enabled note_step <
    10 µs, disabled < 1 µs, and the 16-replica state.utilization() fold
    < 50 ms.  CI-loose budgets — idle-host numbers are ~1 µs enabled
    (amortized over the throttled gauge flush), ~0.05 µs disabled, and
    well under 1 ms for the fold."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.device_telemetry_bench import run

    extra = run()
    assert extra["note_step_enabled_ns"] < 10_000, extra
    assert extra["step_disabled_ns"] < 1_000, extra
    assert extra["fold_16_ms"] < 50, extra
    assert extra["fold_16_deployments"] == 4, extra


def test_watch_overhead_under_budget():
    """Metrics-history + watch-engine budget gates (ISSUE 17).  The fold
    rides (rate-limited) on ReportMetrics inside the GCS and the watch
    tick rides the health loop, so both are budget-gated:

      - one fold of a ~60-series cluster aggregate < 20 ms (idle-host
        ~1 ms; amortized per-push cost is this divided by pushes-per-fold,
        and every non-folding push pays only the fold_due gate < 2 µs);
      - watch-tick cost per rule stays flat in rule count at fixed
        families (64-rule per-rule cost within 3x of 8-rule — i.e. no
        superlinear scan);
      - the disabled path (metrics_history_enabled=False) books NOTHING
        (gcs.history is None) and its entire addition to ReportMetrics —
        one attribute read + None check — costs < 1 µs;
      - the global history byte cap HOLDS under adversarial tagset churn
        (5000 unique tagsets vs a 256 KiB cap), counter-enforced: the
        byte meter is pure counting, no wall clock anywhere."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.watch_overhead_bench import run

    extra = run()
    assert extra["fold_us"] < 20_000, extra
    assert extra["fold_due_ns"] < 2_000, extra
    assert extra["tick_flatness"] < 3.0, extra
    assert extra["report_disabled_ns"] < 50_000, extra
    assert extra["disabled_guard_ns"] < 1_000, extra
    assert extra["cap_ok"], extra
    assert extra["cap_evictions"] > 0, extra


def test_bench_diff_report_nonblocking(tmp_path):
    """Non-blocking perf-trend report step (ISSUE 17 satellite): when at
    least two BENCH_r*.json snapshots exist, run tools/bench_diff.py over
    the newest pair and PRINT the report — visibility, not a gate.  A
    regression verdict must not fail the lane (that's a human call on
    snapshot data from heterogeneous boxes); only a crash in bench_diff
    itself — a real bug in the tool — fails."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import glob

    from tools.bench_diff import run

    # the newest pair of snapshots, in the driver's {n, cmd, rc, tail,
    # parsed} shape; written here, since the tree keeps one record file
    import json

    for n, (mfu, step) in enumerate([(0.6459, 0.9853), (0.6452, 0.9865)], 2):
        doc = {"metric": "llama1b_train_mfu_1chip", "value": mfu,
               "unit": "MFU", "extra": {"step_time_s": step}}
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(
            {"n": n, "cmd": "bench", "rc": 0,
             "tail": json.dumps(doc), "parsed": doc}))
    snaps = sorted(glob.glob(os.path.join(str(tmp_path), "BENCH_r*.json")))
    report = run(snaps[-2], snaps[-1])
    assert report["old"] == snaps[-2] and report["new"] == snaps[-1]
    print(f"bench_diff {os.path.basename(report['old'])} -> "
          f"{os.path.basename(report['new'])}: {report['changed']} metrics "
          f"changed, {len(report['regressions'])} regressions "
          f"(non-blocking)")
    for section, rows in sorted(report["sections"].items()):
        for r in rows:
            print(f"  [{section}] {r}")


def test_data_ingest_overhead_zero_copy_and_wait_budget():
    """Data-plane budget gates (ISSUE 13), all counter/ratio-based:

      - batch assembly must cost far under a training step (CI-loose
        1 ms/batch vs ~50 µs idle-host);
      - an ALIGNED fixed-dtype stream books ZERO copied bytes — every
        batch is a view over the block's buffers (no full-block memcpy
        anywhere in the path);
      - a ragged stream copies only at straddling batch boundaries
        (copied ≪ total);
      - with an instant producer the steady-state buffer-empty wait
        fraction after the ramp batch is under 1% — the hermetic stand-in
        for the goodput ledger's input_wait < 1% acceptance, measured
        from the same counters the ledger reclassifies."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.data_ingest_bench import run

    out = run()
    assert out["per_batch_us"] < 1_000, out
    assert out["aligned_copied_bytes"] == 0, out
    assert out["aligned_view_bytes"] > 0, out
    assert out["ragged_copied_bytes"] < out["ragged_total_bytes"] / 4, out
    assert out["steady_wait_fraction"] < 0.01, out


def test_checkpoint_async_stall_and_delta_budget():
    """Checkpoint-subsystem budget gates (ISSUE 14), the hermetic stand-in
    for the ~1GiB acceptance geometry (same machinery, smaller state so CI
    stays fast; ``python benchmarks/checkpoint_bench.py`` runs the full
    geometry):

      - async snapshots keep checkpoint-induced step stall under 1% of
        step time (the step pays ONLY staging + backpressure; idle-host
        number ~0.5%) while the synchronous baseline measured in the same
        run pays an order of magnitude more;
      - with only params changing, a delta checkpoint writes <25% of the
        full-snapshot bytes (params ~1/5 of the adam+EMA state geometry)
        and still restores bit-exactly;
      - the goodput ledger the async phase ran under keeps its sum
        invariant with the stall reclassified into ``checkpoint``."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.checkpoint_bench import run

    out = run()
    assert out["async_stall_frac"] < 0.01, out
    assert out["sync_stall_frac"] > out["async_stall_frac"], out
    assert out["delta_ratio"] < 0.25, out
    assert out["delta_restore_exact"], out
    assert out["ledger_sum_exact"], out


def test_ray_perf_fast_mode():
    from ray_tpu._private.ray_perf import main

    results = main(fast=True)
    by_name = {r["name"]: r["ops_per_s"] for r in results}
    assert len(results) == 10
    assert all(v > 0 for v in by_name.values())


def test_cache_aware_route_decision_budget():
    """Hermetic route-decision cost gate (ISSUE 7): one cache-aware choice
    — chain-hash the prompt, scan every replica's digest, apply the
    overload guard, fall through to pow-2 when cold — must stay far below
    a queue-probe RPC, or routing overhead would eat the TTFT win at high
    QPS.  Budget is CI-loose (order-of-magnitude guard): 2 ms/decision vs
    ~50 µs idle-host; no RPCs are permitted at all (counted, not timed)."""
    import time

    import ray_tpu.serve.handle as H
    from ray_tpu._private.prefix_hash import prefix_chain_hashes

    class _Id:
        def __init__(self, h):
            self._h = h

        def hex(self):
            return self._h

    class _Rep:
        def __init__(self, h):
            self._actor_id = _Id(h)

    router = H._Router("app", "dep")
    router._refresh = lambda: None
    router._digest_ts = time.monotonic() + 3600  # digests are warm
    reps = [_Rep(f"r{i}") for i in range(8)]
    router._replicas = reps
    warm_prompt = [(7 * j) % 251 for j in range(512)]
    bs = 16
    chain = prefix_chain_hashes(warm_prompt, bs)
    digests = {}
    for i, r in enumerate(reps):
        held = set(chain[: (i * len(chain)) // len(reps)])
        held.update(range(10_000 + i * 2000, 10_000 + i * 2000 + 1024))
        digests[r._actor_id.hex()] = {
            "held": held, "block_size": bs, "models": set(), "v": 1}
    router._digests = digests
    now = time.monotonic()
    router._qcache = {r._actor_id.hex(): (0.0, now + 3600) for r in reps}

    cold_prompt = [13] * 512
    n = 300
    t0 = time.perf_counter()
    for i in range(n):
        # alternate warm (digest win) and cold (full scan + pow-2 fallback)
        router.choose_replica((), {"prompt": warm_prompt if i % 2 else
                                   cold_prompt})
    per_decision = (time.perf_counter() - t0) / n
    assert router.probe_rpcs == 0, (
        f"{router.probe_rpcs} probe RPCs leaked into warm-cache routing")
    assert per_decision < 0.002, (
        f"route decision {per_decision * 1e6:.0f}µs exceeds the 2ms budget")


def test_delta_sync_bytes_flat_in_cluster_size():
    """Hermetic control-plane budget gate (ISSUE 8): steady-state sync
    traffic per raylet per tick must NOT grow with cluster size — the
    whole point of versioned delta sync.  Counter-based via
    ray_tpu_gcs_sync_bytes_total{kind=delta} (no wall clock): at fixed
    churn (none), the per-tick delta reply is a constant-size frame, so
    the per-raylet byte rate at 200 nodes equals the rate at 50."""
    from ray_tpu._private.sim_cluster import MegaClusterHarness

    per_tick = {}
    for n in (50, 200):
        h = MegaClusterHarness(num_nodes=n)
        try:
            h.build()
            h.tick_all()  # settle to the current version
            steady = h.tick_all(rounds=5)
            assert steady["full_bytes"] == 0, (
                "steady state must never need a full snapshot")
            per_tick[n] = steady["delta_bytes"] / steady["ticks"]
        finally:
            h.close()
    assert per_tick[200] <= per_tick[50] * 1.1 + 2, (
        f"steady-state delta bytes/tick grew with cluster size: {per_tick}")


def test_lease_reuse_rpc_budget():
    """Counted via the owner-side lease metrics (hermetic — no wall-clock):
    in steady state the reuse path issues ≤1 RequestWorkerLease RPC per
    max_tasks_in_flight_per_worker tasks, and the reuse hit rate exceeds
    90% — cached leases serve nearly every submission."""
    import ray_tpu
    from ray_tpu._private import runtime_metrics
    from ray_tpu._private.config import global_config

    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        def tiny():
            return 1

        # warm: spawn workers, populate the lease cache
        ray_tpu.get([tiny.remote() for _ in range(8)])

        before = runtime_metrics.lease_snapshot()
        n_tasks = 200
        for _ in range(10):
            ray_tpu.get([tiny.remote() for _ in range(20)])
        after = runtime_metrics.lease_snapshot()

        requests = after["lease_requests"] - before["lease_requests"]
        assignments = after["assignments"] - before["assignments"]
        hits = after["reuse_hits"] - before["reuse_hits"]
        assert assignments >= n_tasks
        max_if = global_config().max_tasks_in_flight_per_worker
        budget = math.ceil(n_tasks / max_if)
        assert requests <= budget, (
            f"{requests} lease RPCs for {n_tasks} tasks exceeds the "
            f"≤1-per-{max_if}-tasks budget ({budget})")
        hit_rate = hits / assignments
        assert hit_rate > 0.90, f"lease reuse hit rate {hit_rate:.2%} ≤ 90%"
    finally:
        ray_tpu.shutdown()


def test_planner_decision_budget():
    """Hermetic planner cost gate (ISSUE 10): a CACHED plan decision sits
    on the allreduce hot path (once per collective call), so it must stay
    far below the op itself — budget 5 µs/decision (idle-host ~0.3-0.6 µs
    dict hit; CI-loose headroom, no RPCs, no wall-clock racing)."""
    import time

    from ray_tpu.util.collective import compression as comp
    from ray_tpu.util.collective import planner as pl

    topo = pl.Topology.from_slice_ids((0, 0, 0, 0, 1, 1, 1, 1))
    spec = comp.CompressionSpec()
    pl.plan_allreduce(4 << 20, topo, spec)  # warm the cache
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        pl.plan_allreduce(4 << 20, topo, spec)
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"cached plan decision {per * 1e6:.2f}µs > 5µs budget"


def test_serving_decode_plan_cache_budget():
    """TP serving hot-loop gate (ISSUE 20): the paged engine plans its
    per-layer allreduces ONCE at init (decode message sizes are
    compile-time constants), so a steady-state decode step pays at most a
    cached plan lookup and zero plan RPCs.  Gate the cached KiB-scale
    decision at the same 5 µs budget as the training-size one — and pin
    that re-planning the exact serving (nbytes, topo, spec, allowed)
    tuple is a dict hit, not a re-derivation."""
    import time

    from ray_tpu.util.collective import compression as comp
    from ray_tpu.util.collective import planner as pl

    topo = pl.Topology.flat(4, link=pl.LINK_ICI)
    spec = comp.CompressionSpec(scheme="none", min_bytes=0)
    allowed = ("flat", "ring", "tree")
    first = pl.plan_allreduce(2 << 10, topo, spec, allowed=allowed)
    assert pl.plan_allreduce(2 << 10, topo, spec, allowed=allowed) is first
    n = 5000
    t0 = time.perf_counter()
    for _ in range(n):
        pl.plan_allreduce(2 << 10, topo, spec, allowed=allowed)
    per = (time.perf_counter() - t0) / n
    assert per < 5e-6, f"cached decode plan {per * 1e6:.2f}µs > 5µs budget"


def test_overlap_off_emits_zero_new_metric_families():
    """Overlap/planner off (the defaults) books NOTHING into the new
    ray_tpu_collective_plan_total family — fused-step metric output stays
    byte-identical to the pre-planner runtime."""
    import jax

    from ray_tpu._private import runtime_metrics as rtm
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import make_train_step

    before = dict(rtm.plan_snapshot())
    cfg = LlamaConfig.tiny()
    init_fn, step_fn = make_train_step(cfg)  # overlap_grad_sync defaults off
    st = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    st, _ = step_fn(st, tokens)
    assert rtm.plan_snapshot() == before


def test_specdec_disabled_path_budget_and_byte_identity(greedy_reference):
    """Speculative decoding off (the default) must cost the non-spec
    engine NOTHING measurable and change NOTHING observable (ISSUE 11):

      - the disabled-path additions to the step loop are two Python
        branch evaluations (`self._spec is None` + the appends-per-step
        select) — gated at < 1 µs per step, orders of magnitude under
        the ~ms step itself;
      - a spec-disabled engine's greedy output stays token-identical to
        argmax over the full forward (conftest's ``greedy_reference``;
        the shared ``_sample`` is itself pinned to exact argmax in
        tests/test_specdec.py) — the output pin;
      - the specdec metric families book nothing.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import runtime_metrics as rtm
    from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(vocab_size=48, dim=32, n_layers=1, n_heads=2,
                           n_kv_heads=1, ffn_dim=64, max_seq_len=48,
                           compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    before = rtm.specdec_snapshot()
    paged = PagedJaxLLMEngine(
        LLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=48,
                  block_size=8, prefill_chunk=16, decode_chunk=4),
        params=params)
    assert paged._spec is None and paged._spec_k == 0
    # micro-gate the added per-step branch cost on the live engine
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        app = (paged._spec_k + 1) if paged._spec is not None \
            else paged.config.decode_chunk
    dt_ns = (time.perf_counter() - t0) / n * 1e9
    assert app == 4 and dt_ns < 1_000, dt_ns
    # token-identity pin vs the full forward
    prompts = [list(np.random.RandomState(s).randint(1, 47, size=7))
               for s in (0, 1)]
    gen = GenerationConfig(max_new_tokens=8)
    assert paged.generate(prompts, gen) == greedy_reference(
        cfg, params, prompts, 8)
    assert rtm.specdec_snapshot() == before


def test_anakin_steps_per_sec_budget():
    """Perf-smoke for the co-located RL path (ISSUE 15): steady-state
    (post-compile) env-steps/s on the 8-device CPU mesh must stay within
    budget.  The real figure was ~1-3M steps/s on this box (round 15's
    notes); the gate sits 10x+ below it so scheduler
    noise can't flake the lane while an order-of-magnitude regression
    (e.g. a host round-trip sneaking into the rollout) still fails."""
    import time

    from ray_tpu.rllib import AnakinConfig

    cfg = AnakinConfig(env="CartPole-v1", num_envs=128, unroll_length=32,
                       updates_per_iter=2, seed=0)
    algo = cfg.algo_class(cfg)
    try:
        algo.train()  # compile + warm
        n = 0
        t0 = time.perf_counter()
        for _ in range(3):
            algo.train()
            n += algo.steps_per_iter
        rate = n / (time.perf_counter() - t0)
    finally:
        algo.stop()
    assert rate > 150_000, f"anakin {rate:,.0f} env-steps/s under budget"


def test_sebulba_sample_loop_lease_rpc_budget():
    """Hermetic counter gate (no wall clock): the Sebulba sample hot loop
    rides actor-task submission over cached leases — consuming N fragments
    must book at most ceil(N / max_tasks_in_flight_per_worker) NEW lease
    RPCs beyond the actor-creation warmup (in practice ~0: actor calls
    reuse the actor's dedicated worker outright)."""
    import math

    import ray_tpu
    from ray_tpu._private import runtime_metrics
    from ray_tpu._private.config import global_config
    from ray_tpu.rllib import IMPALAConfig

    ray_tpu.init(num_cpus=4)
    try:
        algo = (IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=2, num_envs_per_runner=2,
                             rollout_fragment_length=16)
                .training(execution="sebulba", sample_queue_capacity=4)
                .build())
        try:
            algo.train()  # warm: actors staffed, pipeline primed
            before = runtime_metrics.lease_snapshot()
            n_fragments = 30
            for _ in range(n_fragments):
                algo.train()
            after = runtime_metrics.lease_snapshot()
            requests = after["lease_requests"] - before["lease_requests"]
            max_if = global_config().max_tasks_in_flight_per_worker
            budget = math.ceil(n_fragments / max_if)
            assert requests <= budget, (
                f"{requests} lease RPCs for {n_fragments} fragments exceeds "
                f"the ≤1-per-{max_if}-fragments budget ({budget})")
        finally:
            algo.stop()
    finally:
        ray_tpu.shutdown()


def test_ingress_admission_overhead_and_byte_identity():
    """Admission-gate budget gates (ISSUE 18).  The gate's decide() runs
    once per ingress request ahead of any handle work:

      - warm admitted decide() < 5 µs (two metric bookings, bucket take,
        inflight bookkeeping, cached burn compare); the full
        decide()+release() round trip < 10 µs;
      - the refusal verdict (throttle + exact Retry-After) < 5 µs;
      - a WFQ push+pop cycle at a steady 64-deep backlog < 10 µs;
      - serve_admission_enabled=False: get_controller() is one None
        check (< 1 µs) and the admission metric families book NOTHING
        (byte-identical surface, asserted not measured)."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.ingress_overhead_bench import run

    extra = run()
    assert extra["decide_admit_ns"] < 5_000, extra
    assert extra["cycle_ns"] < 10_000, extra
    assert extra["decide_throttle_ns"] < 5_000, extra
    assert extra["wfq_cycle_ns"] < 10_000, extra
    assert extra["disabled_lookup_ns"] < 1_000, extra
    assert extra["booked_disabled"] == 0, extra


def test_kv_migration_quiet_path_budget_and_books_nothing():
    """Live KV migration (ISSUE 19) costs a serving path with no
    migration traffic NOTHING measurable and books NOTHING:

      - neither new family (ray_tpu_serve_kv_migrations_total /
        ray_tpu_serve_kv_migration_latency_seconds) gains a point from
        ordinary serving — recorders only exist on the migration path;
      - the only addition to the hot emission loop is a set-membership
        check against the (empty) migrating-wkey set — gated < 1 µs,
        orders of magnitude under the ~ms engine step;
      - the engine step path itself is untouched: a served stream's
        greedy output stays byte-identical to the bare engine's.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import runtime_metrics as rtm
    from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(vocab_size=48, dim=32, n_layers=1, n_heads=2,
                           n_kv_heads=1, ffn_dim=64, max_seq_len=48,
                           compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    lcfg = LLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=48,
                     block_size=8, prefill_chunk=16, decode_chunk=4)
    before = rtm.kv_migration_snapshot()

    server = LLMServer(lcfg, params=params)
    try:
        prompt = list(np.random.RandomState(7).randint(1, 47, size=9))
        served = server.generate(prompt, max_new_tokens=8)
        # micro-gate the added per-emission branch on the live server
        migrating, wk = server._migrating, (None, 0, 12345)
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            hot = wk in migrating
        dt_ns = (time.perf_counter() - t0) / n * 1e9
        assert hot is False and dt_ns < 1_000, dt_ns
    finally:
        server.shutdown()
    bare = PagedJaxLLMEngine(lcfg, params=params)
    assert served == bare.generate(
        [prompt], GenerationConfig(max_new_tokens=8))[0]
    assert rtm.kv_migration_snapshot() == before
