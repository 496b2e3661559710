"""Request-level serving SLO layer (ISSUE 9, tier-1).

Covers: the DDSketch-style latency sketch's rank-error bound
(property-style over adversarial distributions), lossless merge ==
combined-stream sketch, serialization round-trip, the sketch metric kind
folding through the GCS aggregate, tenant extraction (header / kwarg /
default), lifecycle event ordering through a fake engine, burn-rate math
with an injected clock, router decision forensics, the
disabled-path-records-nothing guarantee, and the end-to-end cluster
acceptance (burst of shared-prefix streaming clients, two tenants, one
slow replica -> state.serving_slo() percentiles + tenant split + a
burn-rate breach naming the deployment, driven entirely by injected
latency).  Real-engine abort/slot-free regression tests ride the slow
lane at the bottom.
"""

import json
import math
import random
import threading
import time
import urllib.request

import pytest

from ray_tpu._private.latency_sketch import (
    LatencySketch,
    merge_points,
    point_quantiles,
    summary,
)

# ---------------------------------------------------------------------------
# sketch: rank-error bound / merge / serialization
# ---------------------------------------------------------------------------


def _adversarial_streams():
    rng = random.Random(1234)
    yield "lognormal", [rng.lognormvariate(0, 2) for _ in range(20_000)]
    yield "uniform", [rng.uniform(1e-4, 10.0) for _ in range(20_000)]
    # point masses: every quantile sits ON a mass — the bucket estimate
    # must stay within relative accuracy of the exact value
    yield "pointmass", [rng.choice([1e-3, 0.5, 0.5, 7.0])
                        for _ in range(20_000)]
    # 16 decades of dynamic range (adversarial for static-bucket
    # histograms; the log-bucket sketch doesn't care)
    yield "widerange", [10 ** rng.uniform(-8, 8) for _ in range(20_000)]
    # heavy zero mass + a tail
    yield "zeroheavy", [0.0] * 5_000 + [rng.expovariate(1.0)
                                        for _ in range(5_000)]


def test_sketch_rank_error_bound_adversarial():
    """For every adversarial stream and every quantile, the estimate is
    within the configured relative accuracy (1%, guaranteed <= 2%) of the
    true empirical quantile's rank neighborhood."""
    for name, vals in _adversarial_streams():
        s = LatencySketch(relative_accuracy=0.01)
        for v in vals:
            s.add(v)
        sv = sorted(vals)
        for q in (0.01, 0.1, 0.25, 0.5, 0.9, 0.95, 0.99, 0.999):
            est = s.quantile(q)
            rank = q * (len(sv) - 1)
            lo, hi = sv[math.floor(rank)], sv[math.ceil(rank)]
            if lo <= est <= hi:
                continue
            err = min(abs(est - lo) / max(lo, 1e-12),
                      abs(est - hi) / max(hi, 1e-12))
            assert err <= 0.02, (name, q, est, lo, hi, err)


def test_sketch_merge_is_lossless():
    """merge(a, b) must be IDENTICAL (bins, counts, extremes) to the
    sketch of the combined stream — the property that makes per-replica
    p99s fold into a true cluster p99."""
    rng = random.Random(7)
    a, b, combined = (LatencySketch(0.01), LatencySketch(0.01),
                      LatencySketch(0.01))
    for _ in range(5_000):
        v = rng.lognormvariate(0, 1)
        a.add(v)
        combined.add(v)
    for _ in range(5_000):
        v = rng.uniform(0, 5)
        b.add(v)
        combined.add(v)
    a.merge(b)
    assert a.bins == combined.bins
    assert a.count == combined.count
    assert a.zero == combined.zero
    assert a.min == combined.min and a.max == combined.max
    assert abs(a.sum - combined.sum) < 1e-9 * combined.sum
    for q in (0.5, 0.95, 0.99):
        assert a.quantile(q) == combined.quantile(q)
    # mismatched accuracies must refuse (merging would break the bound)
    with pytest.raises(ValueError):
        a.merge(LatencySketch(0.05))


def test_sketch_serialization_round_trip():
    rng = random.Random(3)
    s = LatencySketch(0.01)
    for _ in range(10_000):
        s.add(rng.lognormvariate(-3, 2))
    r = LatencySketch.from_blob(s.to_blob())
    assert r.bins == s.bins and r.count == s.count and r.zero == s.zero
    assert r.min == s.min and r.max == s.max
    assert r.quantile(0.99) == s.quantile(0.99)
    # dict-point interop (the metrics-plane transport) is also lossless
    p = s.to_point()
    assert json.loads(json.dumps(p))  # KV/ReportMetrics serializable
    r2 = LatencySketch.from_point(p)
    assert r2.bins == s.bins and r2.quantile(0.5) == s.quantile(0.5)
    assert merge_points([p, p])["count"] == 2 * s.count
    # empty sketch round-trips too
    e = LatencySketch.from_blob(LatencySketch().to_blob())
    assert e.count == 0 and math.isnan(e.quantile(0.5))


def test_sketch_collapse_bounds_memory_preserves_tail():
    """max_bins collapses the LOWEST buckets, so memory stays constant
    under adversarial ranges while the upper tail stays exact."""
    rng = random.Random(11)
    capped = LatencySketch(0.005, max_bins=128)
    exact = LatencySketch(0.005)
    vals = [10 ** rng.uniform(-9, 9) for _ in range(50_000)]
    for v in vals:
        capped.add(v)
        exact.add(v)
    assert len(capped.bins) <= 128
    assert capped.count == exact.count
    # the p99/p999 tail is untouched by low-bucket collapse
    assert capped.quantile(0.99) == exact.quantile(0.99)
    assert capped.quantile(0.999) == exact.quantile(0.999)


def test_sketch_metric_folds_through_gcs_aggregate():
    """Two reporters push sketch points; the GCS CollectMetrics fold must
    equal the combined stream (lossless), and prometheus rendering emits
    summary-style quantile series computed from the FOLDED bins."""
    from ray_tpu._private.gcs import GcsServer
    from ray_tpu.util.metrics import Sketch, prometheus_text

    m = Sketch("test_slo_fold_sketch", "t", tag_keys=("dep",))
    rng = random.Random(5)
    va = [rng.lognormvariate(0, 1) for _ in range(2_000)]
    vb = [rng.uniform(0, 3) for _ in range(2_000)]
    combined = LatencySketch(m.relative_accuracy)
    for v in va + vb:
        combined.add(v)

    def points_for(vals):
        s = LatencySketch(m.relative_accuracy)
        for v in vals:
            s.add(v)
        return [dict({"name": "test_slo_fold_sketch", "kind": "sketch",
                      "tags": {"dep": "d"}, "description": "t"},
                     **s.to_point())]

    gcs = GcsServer()
    try:
        gcs.HandleReportMetrics({"reporter": "ra", "points": points_for(va),
                                 "time": time.time()})
        gcs.HandleReportMetrics({"reporter": "rb", "points": points_for(vb),
                                 "time": time.time()})
        agg = gcs.HandleCollectMetrics({})
    finally:
        gcs.shutdown()
    pts = [p for p in agg if p["name"] == "test_slo_fold_sketch"]
    assert len(pts) == 1
    folded = LatencySketch.from_point(pts[0])
    assert folded.bins == combined.bins
    assert folded.count == combined.count
    assert folded.quantile(0.99) == combined.quantile(0.99)
    txt = prometheus_text(pts)
    assert '# TYPE test_slo_fold_sketch summary' in txt
    assert 'test_slo_fold_sketch{dep="d",quantile="0.99"}' in txt
    assert "test_slo_fold_sketch_count" in txt
    # point_quantiles (the renderer's primitive) agrees with the instance
    assert point_quantiles(pts[0], [0.5])[0] == combined.quantile(0.5)


# ---------------------------------------------------------------------------
# tenant extraction
# ---------------------------------------------------------------------------


def test_tenant_extraction_header_kwarg_default():
    from ray_tpu.serve._private import slo

    assert slo.extract_tenant(headers={"x-tenant": "acme"}) == "acme"
    # header wins over payload
    assert slo.extract_tenant(headers={"x-tenant": "acme"},
                              payload={"tenant": "p"}) == "acme"
    assert slo.extract_tenant(payload={"tenant": "p"}) == "p"
    assert slo.extract_tenant(kwargs={"tenant": "k"}) == "k"
    assert slo.extract_tenant(kwargs={"request": {"tenant": "nested"}}) \
        == "nested"
    assert slo.extract_tenant() == slo.DEFAULT_TENANT
    assert slo.extract_tenant(headers={}) == slo.DEFAULT_TENANT
    # hostile header: length-capped (tags must stay bounded), non-strings
    # fall back to default
    assert len(slo.extract_tenant(headers={"x-tenant": "x" * 500})) == 64
    assert slo.extract_tenant(payload={"tenant": 123}) == slo.DEFAULT_TENANT


# ---------------------------------------------------------------------------
# lifecycle ledger (fake engine; injected clocks)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture
def ledger():
    from ray_tpu.serve._private import slo

    mono, wall = _Clock(1000.0), _Clock(1_700_000_000.0)
    led = slo.ServingSLOLedger(clock=mono, wall=wall)
    led.mono, led.wallc = mono, wall  # test handles
    return led


def test_lifecycle_event_ordering_through_fake_engine(ledger):
    """Drive one request through a fake engine's lifecycle and assert the
    flight-recorder ring holds the events in causal order with the right
    payloads, and the recent-requests row folds them."""
    from ray_tpu._private import flight_recorder
    from ray_tpu.serve._private import slo

    rec = flight_recorder.configure(enabled=True, capacity=256)
    try:
        tr = ledger.start_request("fake-llm", "tenant-a", trace_id="t123")
        tr.route("prefix_hit")
        ledger.mono.t += 0.010          # fake engine: queue wait
        ledger.record_stage("fake-llm", "queue_wait", 0.010)
        ledger.mono.t += 0.040          # fake engine: prefill
        ledger.record_stage("fake-llm", "prefill", 0.040)
        tr.first_token()                # TTFT = 50 ms
        for _ in range(4):              # fake decode: 4 frames x 2 tokens
            ledger.mono.t += 0.020
            tr.tokens(2)
        tr.finish("ok")

        events = [e for e in rec.tail()
                  if e["kind"] == "request" and e["name"] == "fake-llm"]
        # the event label is the first string in each entry's detail tuple
        # (ingress/route/first_token/terminal carry (rid, label, ...);
        # stage entries carry (stage, ms))
        kinds = [next(x for x in e["detail"] if isinstance(x, str))
                 for e in events]
        # ingress -> route -> stages -> first_token -> terminal, in order
        assert kinds[0] == "ingress"
        assert kinds[1] == "route"
        assert "queue_wait" in kinds and "prefill" in kinds
        assert kinds.index("route") < kinds.index("first_token") \
            < kinds.index("ok")

        row = ledger.recent()[-1]
        assert row["deployment"] == "fake-llm"
        assert row["tenant"] == "tenant-a"
        assert row["route"] == "prefix_hit"
        assert row["status"] == "ok"
        assert abs(row["ttft_s"] - 0.050) < 1e-9
        assert row["tokens"] == 9            # first + 4x2
        assert abs(row["itl_mean_s"] - 0.010) < 1e-9
        assert row["trace_id"] == "t123"
        # sketches booked under the deployment/tenant tags
        snap = ledger.snapshot()["deployments"]["fake-llm"]
        assert snap["tenants"]["tenant-a"]["ttft"]["count"] == 1
        assert snap["tenants"]["tenant-a"]["itl"]["count"] == 8
        assert set(snap["stages"]) >= {"queue_wait", "prefill"}
    finally:
        flight_recorder.configure()
        slo.reset_ledger()


def test_terminal_states_first_wins_and_statuses(ledger):
    tr = ledger.start_request("d", "t")
    tr.finish("ok")
    tr.abort()     # idempotent: first terminal wins
    assert ledger.recent()[-1]["status"] == "ok"
    tr = ledger.start_request("d", "t")
    tr.abort()
    assert ledger.recent()[-1]["status"] == "aborted"
    tr = ledger.start_request("d", "t")
    tr.shed()
    assert ledger.recent()[-1]["status"] == "shed"
    snap = ledger.snapshot()["deployments"]["d"]
    assert snap["status"]["t"] == {"ok": 1, "aborted": 1, "shed": 1}


def test_burn_rate_math_with_injected_clock(ledger):
    """Exact burn-rate arithmetic: breach fraction over each trailing
    window divided by the error budget, windows aging out on the injected
    wall clock."""
    from ray_tpu.serve._private import slo

    slo.register_targets("burn-d", {"slo_ttft_ms": 100.0,
                                    "slo_availability": 0.99})
    try:
        # 10 requests, every TTFT 200 ms > 100 ms target -> breach
        for _ in range(10):
            tr = ledger.start_request("burn-d", "t")
            ledger.mono.t += 0.2
            tr.first_token()
            tr.finish("ok")
        rates = ledger.burn_rates("burn-d")
        # breach fraction 1.0 / budget 0.01 = 100, both windows
        assert rates["ttft"]["5m"] == pytest.approx(100.0)
        assert rates["ttft"]["1h"] == pytest.approx(100.0)
        assert rates["availability"]["5m"] == 0.0

        # 10 minutes later, 10 healthy requests: the 5m window sees only
        # them (burn 0); the 1h window still carries the old breaches
        ledger.wallc.t += 600
        for _ in range(10):
            tr = ledger.start_request("burn-d", "t")
            ledger.mono.t += 0.01
            tr.first_token()
            tr.finish("ok")
        rates = ledger.burn_rates("burn-d")
        assert rates["ttft"]["5m"] == 0.0
        assert rates["ttft"]["1h"] == pytest.approx((10 / 20) / 0.01)

        # availability objective: errors and sheds burn, aborts don't
        for status in ("error", "shed", "aborted"):
            tr = ledger.start_request("burn-d", "t")
            tr.finish(status)
        rates = ledger.burn_rates("burn-d")
        assert rates["availability"]["5m"] == pytest.approx(
            (2 / 12) / 0.01)  # 10 ok + error + shed counted; abort excluded

        # a fold of this row reports the breach naming the deployment
        report = slo.fold_rows([ledger.row()], now_wall=ledger.wallc.t)
        assert any(b["deployment"] == "burn-d" and b["objective"] == "ttft"
                   and b["window"] == "1h" for b in report["breaches"])
    finally:
        slo._local_targets.pop("burn-d", None)


def test_fold_rows_sums_windows_and_merges_sketches(ledger):
    """Two processes' rows: window buckets SUM (wall-aligned), sketches
    merge losslessly, tenants union."""
    from ray_tpu.serve._private import slo

    tr = ledger.start_request("f", "a")
    ledger.mono.t += 0.05
    tr.first_token()
    tr.finish("ok")
    row1 = ledger.row()
    # a "second process": same wall bucket, different tenant.  Strip the
    # first row's cumulative sketch points from the second (a real second
    # process has its own registry; here both rows snapshot one registry)
    tr = ledger.start_request("f", "b")
    ledger.mono.t += 0.15
    tr.first_token()
    tr.finish("ok")
    row2 = ledger.row()
    report = slo.fold_rows([row1, row2], now_wall=ledger.wallc.t)
    dep = report["deployments"]["f"]
    assert set(dep["tenants"]) == {"a", "b"}
    # availability window: 1 (row1) + 2 (row2 is cumulative) requests
    counts = dep["burn_rate"]["availability"]
    assert counts["5m"] == 0.0
    assert dep["status"]["a"]["ok"] + dep["status"]["b"]["ok"] == 3


def test_disabled_path_records_nothing(monkeypatch):
    """serve_slo_enabled=False: the NOOP tracker books no sketches, no
    windows, no recent rows, no flight events, no route attribution — and
    record_stage is inert even with a label."""
    from ray_tpu._private import flight_recorder, runtime_metrics
    from ray_tpu._private.config import global_config
    from ray_tpu.serve._private import slo

    monkeypatch.setattr(global_config(), "serve_slo_enabled", False)
    slo.reset_ledger()
    rec = flight_recorder.configure(enabled=True, capacity=128)
    try:
        before_ttft = len(runtime_metrics.SERVE_TTFT._snapshot())
        before_stage = len(runtime_metrics.SERVE_STAGE_SECONDS._snapshot())
        tr = slo.start_request("disabled-dep", "t")
        assert tr is slo.NOOP_TRACKER
        tr.route("prefix_hit")
        tr.first_token()
        tr.tokens(5)
        tr.finish("ok")
        tr.abort()
        slo.record_stage("disabled-dep", "prefill", 0.5)
        assert slo.maybe_publish() is False
        assert len(runtime_metrics.SERVE_TTFT._snapshot()) == before_ttft
        assert len(runtime_metrics.SERVE_STAGE_SECONDS._snapshot()) \
            == before_stage
        assert slo._ledger is None  # not even constructed
        assert not [e for e in rec.tail()
                    if e["kind"] == "request"
                    and e["name"] == "disabled-dep"]
    finally:
        flight_recorder.configure()


# ---------------------------------------------------------------------------
# router decision forensics
# ---------------------------------------------------------------------------


class _FakeId:
    def __init__(self, hex_):
        self._h = hex_

    def hex(self):
        return self._h


class _FakeReplica:
    def __init__(self, hex_, qlen=0):
        self._actor_id = _FakeId(hex_)
        self.qlen = qlen


@pytest.fixture
def router(monkeypatch):
    import ray_tpu.serve.handle as H

    r = H._Router("app", "dep")
    monkeypatch.setattr(r, "_refresh", lambda: None)
    monkeypatch.setattr(H, "_resolve_refs", lambda refs, timeout: [0] * len(refs))
    r._digest_ts = time.monotonic() + 3600  # digests injected, never fetched
    return r


def _digest_row(prompt, bs, qlen=None):
    from ray_tpu._private.prefix_hash import prefix_chain_hashes

    return {"held": set(prefix_chain_hashes(prompt, bs)),
            "block_size": bs, "models": set(), "v": 1, "qlen": qlen}


def test_route_decision_counters(router):
    """Each router outcome books its reason: prefix_hit, pow2_cold,
    overload_divert, stale_row — plus shun_resubmit on the dead-replica
    re-route path."""
    from ray_tpu._private import runtime_metrics
    from ray_tpu.serve._private import slo

    def deltas(fn):
        before = runtime_metrics.route_decision_snapshot()
        fn()
        after = runtime_metrics.route_decision_snapshot()
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(after) | set(before)
                if after.get(k, 0) != before.get(k, 0)}

    a, b = _FakeReplica("aa"), _FakeReplica("bb")
    router._replicas = [a, b]
    warm = list(range(64))
    router._digests = {"aa": _digest_row(warm, 8)}

    d = deltas(lambda: router.choose_replica((), {"prompt": warm}))
    assert d == {"prefix_hit": 1}
    d = deltas(lambda: router.choose_replica((), {"prompt": [1] * 32}))
    assert d == {"pow2_cold": 1}
    # overload: the winner's digest-fed queue is far above the field floor
    router._digests = {"aa": _digest_row(warm, 8, qlen=100),
                       "bb": _digest_row([1] * 9, 8, qlen=0)}
    router._fetch_digests = lambda cfg: None
    router._qcache = {"aa": (100.0, time.monotonic()),
                      "bb": (0.0, time.monotonic())}
    d = deltas(lambda: router.choose_replica((), {"prompt": warm}))
    assert d == {"overload_divert": 1}
    # stale row: the would-be winner left the live set
    router._digests = {"gone": _digest_row(warm, 8)}
    router._qcache = {}
    d = deltas(lambda: router.choose_replica((), {"prompt": warm}))
    assert d == {"stale_row": 1}
    # shun_resubmit books on the dead-replica re-route
    d = deltas(lambda: slo.note_route("shun_resubmit"))
    assert d == {"shun_resubmit": 1}


def test_route_reason_attributed_to_active_tracker(router, ledger):
    from ray_tpu.serve._private import slo

    a, b = _FakeReplica("aa"), _FakeReplica("bb")
    router._replicas = [a, b]
    warm = list(range(64))
    router._digests = {"aa": _digest_row(warm, 8)}
    tr = ledger.start_request("d", "t")
    with slo.activate(tr):
        router.choose_replica((), {"prompt": warm})
    tr.finish("ok")
    assert ledger.recent()[-1]["route"] == "prefix_hit"


def test_handle_kwarg_tenant_attribution(ledger):
    from ray_tpu.serve._private import slo

    tr = ledger.start_request("d")
    with slo.activate(tr):
        slo.note_request_args(({"prompt": [1, 2], "tenant": "kw-tenant"},),
                              {})
    tr.finish("ok")
    assert ledger.recent()[-1]["tenant"] == "kw-tenant"


# ---------------------------------------------------------------------------
# proxy lifecycle: SSE abort through a fake streaming deployment (tier-1)
# ---------------------------------------------------------------------------


def test_sse_disconnect_records_aborted_and_closes_generator(tmp_path):
    """A client that drops the SSE stream mid-decode must leave a terminal
    ``aborted`` lifecycle row AND close the replica-side generator (the
    hook that frees a real engine's slot — proven against the paged
    engine in the slow lane below)."""
    import socket as socket_mod

    from ray_tpu import serve
    from ray_tpu.serve._private import slo

    slo.reset_ledger()
    closed_marker = str(tmp_path / "gen-closed")

    @serve.deployment(name="abort-stream")
    class Streamer:
        def __init__(self, marker_path):
            self._marker = marker_path

        def __call__(self, request):
            marker = self._marker

            def gen():
                try:
                    for i in range(200):
                        yield [i]
                        time.sleep(0.01)
                finally:
                    open(marker, "w").close()
            return gen()

    try:
        h = serve.run(Streamer.bind(closed_marker), name="abort-app",
                      _local_testing_mode=True)
        serve.add_route("/abort", h)
        host, port = serve.start_http_proxy(port=0)
        body = json.dumps({"stream": True, "tenant": "dropper"}).encode()
        sock = socket_mod.create_connection((host, port), timeout=10)
        sock.sendall(
            b"POST /abort HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        # read a couple of frames, then hang up mid-stream
        got = b""
        while got.count(b"\n\ndata:") < 2:
            got += sock.recv(4096)
        sock.close()
        import os as os_mod

        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and not os_mod.path.exists(closed_marker)):
            time.sleep(0.05)
        assert os_mod.path.exists(closed_marker), \
            "generator never closed on disconnect"
        rows = []
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            rows = [r for r in slo.get_ledger().recent()
                    if r["deployment"] == "abort-stream"]
            if rows and rows[-1]["status"] == "aborted":
                break
            time.sleep(0.05)
        assert rows and rows[-1]["status"] == "aborted", rows
        assert rows[-1]["tenant"] == "dropper"
        assert rows[-1].get("ttft_s") is not None  # it DID stream first
        assert rows[-1]["tokens"] < 200  # cancelled well before completion
    finally:
        serve.shutdown()
        slo.reset_ledger()


# ---------------------------------------------------------------------------
# end-to-end acceptance: cluster, two tenants, one slow replica (tier-1;
# latency injected — no jax compiles anywhere)
# ---------------------------------------------------------------------------


@pytest.mark.timeout(300)
def test_e2e_cluster_slo_percentiles_tenants_and_breach(
        ray_start_regular, tmp_path):
    """Burst of shared-prefix streaming clients against a disagg-shaped
    app (ingress -> prefill deployment -> streamed decode) on a REAL
    cluster: state.serving_slo() p50 TTFT matches the empirically measured
    value within sketch error, per-tenant rows split correctly for two
    tenants, and one slow prefill replica surfaces as a burn-rate breach
    naming the deployment."""
    from ray_tpu import serve
    from ray_tpu.serve._private import slo
    from ray_tpu.util import state

    slo.reset_ledger()
    marker = str(tmp_path / "slow-replica.lock")

    @serve.deployment(name="slo-prefill", num_replicas=2,
                      ray_actor_options={"num_cpus": 0.1})
    class FakePrefill:
        def __init__(self, marker_path):
            # exactly ONE replica claims the marker and becomes the slow
            # one (injected latency: the "overloaded chip")
            try:
                open(marker_path, "x").close()
                self.delay = 0.30
            except FileExistsError:
                self.delay = 0.01

        def prep(self, prompt):
            time.sleep(self.delay)
            return {"first": prompt[0] if prompt else 0}

    @serve.deployment(name="slo-llm", ray_actor_options={"num_cpus": 0.1},
                      slo_config={"slo_ttft_ms": 100.0,
                                  "slo_availability": 0.95})
    class FakeIngress:
        def __init__(self, prefill):
            self._prefill = prefill

        def __call__(self, request):
            prompt = request.get("prompt") or []

            def gen():
                h = self._prefill.prep.remote(prompt).result(timeout_s=60)
                yield [h["first"]]
                for i in range(3):
                    time.sleep(0.002)
                    yield [i, i + 1]
            return gen()

    try:
        h = serve.run(FakeIngress.bind(FakePrefill.bind(marker)),
                      name="slo-e2e")
        serve.add_route("/slo-e2e", h)
        host, port = serve.start_http_proxy(port=0)
        base = f"http://{host}:{port}/slo-e2e"

        shared = list(range(100, 116))  # shared prefix across the burst
        measured = {}

        def client(i):
            tenant = "alpha" if i % 2 == 0 else "beta"
            body = json.dumps({"stream": True,
                               "prompt": shared + [i]}).encode()
            headers = {"Content-Type": "application/json"}
            if tenant == "alpha":
                headers["x-tenant"] = "alpha"          # header path
            else:
                body = json.dumps({"stream": True, "tenant": "beta",
                                   "prompt": shared + [i]}).encode()
            req = urllib.request.Request(base, data=body, headers=headers)
            t0 = time.perf_counter()
            first = None
            with urllib.request.urlopen(req, timeout=60) as resp:
                for raw in resp:
                    line = raw.decode("utf-8", "replace").strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        if first is None:
                            first = time.perf_counter() - t0
            measured[i] = (tenant, first)

        n = 16
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(measured) == n
        assert all(f is not None for _, f in measured.values())

        slo.get_ledger().maybe_publish(force=True)
        report = state.serving_slo()
        dep = report["deployments"]["slo-llm"]

        # per-tenant split: 8 alpha (header) + 8 beta (payload field)
        assert dep["tenants"]["alpha"]["ttft"]["count"] == n // 2
        assert dep["tenants"]["beta"]["ttft"]["count"] == n // 2
        assert dep["status"]["alpha"]["ok"] == n // 2
        assert dep["status"]["beta"]["ok"] == n // 2

        # p50 TTFT: the sketch figure must match the empirical p50 of the
        # EXACT per-request values the ledger recorded, within the
        # sketch's relative accuracy bound (2%)
        recent = state.recent_requests(limit=100, deployment="slo-llm")
        exact = sorted(r["ttft_s"] for r in recent if "ttft_s" in r)
        assert len(exact) == n
        p50_exact = exact[(len(exact) - 1) // 2]
        p50_sketch = dep["ttft"]["p50"]
        assert abs(p50_sketch - p50_exact) / p50_exact <= 0.02 + 1e-6, (
            p50_sketch, p50_exact)
        # ... and agree with the client-side measurement (same events seen
        # from the other end of the socket; generous skew allowance)
        cl = sorted(f for _, f in measured.values())
        p50_client = cl[(len(cl) - 1) // 2]
        assert abs(p50_sketch - p50_client) <= 0.05 + 0.3 * p50_client, (
            p50_sketch, p50_client)

        # the slow prefill replica (300 ms >> the 100 ms target) burned the
        # 5% error budget: a breach row names the deployment
        assert any(b["deployment"] == "slo-llm" and b["objective"] == "ttft"
                   for b in report["breaches"]), report["breaches"]
        burn = dep["burn_rate"]["ttft"]["5m"]
        assert burn > 1.0, burn
        # /api-shape sanity: the report is JSON-serializable end to end
        json.dumps(report)
    finally:
        serve.shutdown()
        slo.reset_ledger()


# ---------------------------------------------------------------------------
# slow lane: real paged engine — abort frees the slot/blocks
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_llm():
    import jax

    from ray_tpu.llm.config import LLMConfig
    from ray_tpu.models.llama import LlamaConfig, init_params

    mcfg = LlamaConfig.tiny()
    params = init_params(mcfg, jax.random.PRNGKey(0))
    lcfg = LLMConfig(model_config=mcfg, max_batch_size=4, decode_chunk=4,
                     block_size=8, prefill_chunk=16,
                     max_seq_len=256, num_blocks=40)
    return lcfg, params


@pytest.mark.slow
def test_engine_cancel_request_frees_slot_and_blocks(tiny_llm):
    """Engine-level abort at every lifecycle point: queued, mid-decode.
    Cancelled requests return their slot AND blocks to the pool."""
    from ray_tpu.llm.config import GenerationConfig
    from ray_tpu.llm.engine import make_engine

    lcfg, params = tiny_llm
    eng = make_engine(lcfg, params=params)
    free0 = eng.blocks.num_free()
    # queued cancel
    rid = eng.add_request(list(range(1, 20)), GenerationConfig(max_new_tokens=200))
    assert eng.cancel_request(rid) is True
    assert not eng.has_work()
    assert eng.blocks.num_free() == free0
    # mid-decode cancel
    rid = eng.add_request(list(range(1, 20)), GenerationConfig(max_new_tokens=200))
    for _ in range(200):
        eng.step()
        with eng._lock:
            r = eng._requests.get(rid)
            if r is not None and r.out_tokens:
                break
    with eng._lock:
        assert eng._requests[rid].slot >= 0
    assert eng.cancel_request(rid) is True
    with eng._lock:
        assert rid not in eng._requests
        assert all(r is None for r in eng._slot_req)
    eng.step()  # post-cancel step must be clean
    # all blocks return (cached prefix blocks stay registered-but-free,
    # which still counts as allocatable)
    assert eng.blocks.num_free() == free0
    # double-cancel is a no-op
    assert eng.cancel_request(rid) is False


@pytest.mark.slow
@pytest.mark.timeout(300)
def test_sse_disconnect_frees_paged_engine_slot(tiny_llm):
    """ISSUE 9 satellite regression: a disconnected streaming client's
    slot returns to the PAGED ENGINE pool — proxy disconnect -> generator
    close -> LLMServer abort -> engine.cancel_request."""
    import socket as socket_mod

    from ray_tpu import serve
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.serve._private import slo

    lcfg, params = tiny_llm
    slo.reset_ledger()

    @serve.deployment(name="paged-stream")
    class Wrap:
        def __init__(self):
            self.server = LLMServer(lcfg, params)

        def set_slo_label(self, name):
            self.server.set_slo_label(name)

        def __call__(self, request):
            return self.server.generate_stream(
                request["prompt"],
                max_new_tokens=request.get("max_new_tokens", 64),
                temperature=1.0, top_k=50)

    try:
        h = serve.run(Wrap.bind(), name="paged-abort",
                      _local_testing_mode=True)
        serve.add_route("/paged", h)
        eng = h._instance.server._engine
        free0 = eng.blocks.num_free()
        host, port = serve.start_http_proxy(port=0)
        body = json.dumps({"stream": True, "prompt": list(range(1, 30)),
                           "max_new_tokens": 200}).encode()
        sock = socket_mod.create_connection((host, port), timeout=30)
        sock.sendall(
            b"POST /paged HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/json\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        got = b""
        while got.count(b"\n\ndata:") < 2:   # mid-decode, far from done
            got += sock.recv(4096)
        sock.close()
        # the slot must return to the pool long before 200 tokens decode
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with eng._lock:
                idle = (not eng._requests
                        and all(r is None for r in eng._slot_req))
            if idle:
                break
            time.sleep(0.05)
        assert idle, "engine never released the aborted request's slot"
        assert eng.blocks.num_free() == free0
        # terminal aborted lifecycle row at the ingress
        rows = [r for r in slo.get_ledger().recent()
                if r["deployment"] == "paged-stream"]
        assert rows and rows[-1]["status"] == "aborted", rows
    finally:
        try:
            h._instance.server.shutdown()  # stop the llm-engine-loop thread
        except Exception:  # noqa: BLE001
            pass
        serve.shutdown()
        slo.reset_ledger()
