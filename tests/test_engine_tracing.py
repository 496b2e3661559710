"""The engine's own measurement (ISSUE 24): profiler regions on the XPlane's
host plane, first-token stages that partition enqueue -> first yield, and
cumulative engine counters in ``utilization()`` and the replica's ledger
row.  Toy engine on the CPU; one parametrised test per group."""

import glob
import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
from ray_tpu.models.llama import LlamaConfig, init_params

STAGES = ("enqueue_wait", "queue_wait", "prefill", "first_emit", "stream_out")


@pytest.fixture(scope="module")
def tiny():
    mcfg = LlamaConfig.tiny()
    return mcfg, init_params(mcfg, jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    mcfg, params = tiny
    kw = {"max_batch_size": 4, "max_seq_len": 128, "block_size": 8,
          "prefill_chunk": 16, "num_blocks": 40, "decode_chunk": 4, **kw}
    return PagedJaxLLMEngine(LLMConfig(model_config=mcfg, **kw),
                             params=params)


def _prompts(n, size):
    return [list(np.random.RandomState(s).randint(1, 255, size=size))
            for s in range(n)]


# -- (a) profiler regions ------------------------------------------------------


@pytest.fixture(scope="module")
def host_events(tiny, tmp_path_factory):
    """``{line name: [(name, start_ns, end_ns, stats)]}`` of the region
    events of a capture around an engine's steps: three requests join and
    decode in a pool that holds two, so a step preempts one (a drain and an
    upload of the mirrors, ``engine.refresh``: a join or a finish causes
    neither), and the last step finds the engine idle (a drain again)."""
    from jax.profiler import ProfileData

    eng = _engine(tiny, num_blocks=14, enable_prefix_caching=False)
    eng.generate(_prompts(1, 12), GenerationConfig(max_new_tokens=4))  # compile
    logdir = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(logdir)
    try:
        for prompt in _prompts(3, 16):
            eng.add_request(prompt, GenerationConfig(max_new_tokens=40))
        while eng.has_work():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    assert eng.counters()["preemptions"] >= 1
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.split(".")[0] in ("engine", "kv", "serve")]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
    return lines


@pytest.mark.parametrize("name", ["engine.step", "engine.admit",
                                  "engine.prefill_chunk", "engine.join",
                                  "engine.ensure_blocks", "engine.refresh",
                                  "engine.decode_dispatch", "engine.collect",
                                  "engine.drain"])
def test_region_lands_in_the_xplane_inside_a_step(host_events, name):
    found = [(line, ev) for line, evs in host_events.items() for ev in evs
             if ev[0] == name]
    assert found, sorted({e[0] for evs in host_events.values() for e in evs})
    if name == "engine.step":
        assert all({"pending", "active", "inflight"} <= set(ev[3])
                   for _, ev in found)
        return
    for line, (_, t0, t1, _stats) in found:
        assert any(s0 <= t0 and t1 <= s1 for n, s0, s1, _ in host_events[line]
                   if n == "engine.step"), (name, t0, t1)


@pytest.fixture(scope="module")
def grouped_chunks():
    """``(counters, [attributes of every engine.prefill_chunk region])`` of a
    latent-family engine (kernels in the interpreter, as a TPU runs them)
    that prefilled one prompt in a chunk of ``GROUPED_MIN_ROWS`` tokens and
    one of 64 (44 more, in their power-of-two bucket).  The regions are
    heard at ``tracing.region`` itself: a capture of a kernel under the
    interpreter is all interpreter."""
    from ray_tpu.llm import paged
    from ray_tpu.models import pangu_moe as pm

    wide = pm.GROUPED_MIN_ROWS
    mcfg = pm.PanguMoEConfig.tiny(n_routed_experts=128, kv_lora_rank=128,
                                  experts_held=(16, 32), max_seq_len=2 * wide)
    eng = PagedJaxLLMEngine(LLMConfig(
        model_config=mcfg, max_batch_size=2, max_seq_len=2 * wide,
        block_size=8, num_blocks=wide // 4, prefill_chunk=wide,
        paged_attention_kernel="interpret"),
        params=pm.init_params(mcfg, jax.random.PRNGKey(1)))
    heard, region = [], paged.tracing.region

    def spy(name, /, **attrs):
        if name == "engine.prefill_chunk":
            heard.append(attrs)
        return region(name, **attrs)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged.tracing, "region", spy)
        eng.generate(_prompts(1, wide + 44),
                     GenerationConfig(max_new_tokens=2))
    return eng.counters(), heard


@pytest.mark.parametrize("case", ["latent_family", "llama"])
def test_prefill_grouped_chunks(grouped_chunks, host_events, tiny, case):
    """``prefill_grouped_chunks`` beside ``prefill_chunks``: the chunks wide
    enough that the family's expert layers ran as a grouped product
    (``ModelFamily.prefill_grouped_from``); the dispatch region says which
    (``grouped``).  A family without expert layers books none."""
    from ray_tpu.models import pangu_moe as pm
    from ray_tpu.models.family import family_of

    if case == "latent_family":
        counters, stats = grouped_chunks
        assert counters["prefill_chunks"] == 2
        assert counters["prefill_grouped_chunks"] == 1
        assert sorted((int(s["bucket"]), int(s["grouped"])) for s in stats) \
            == [(64, 0), (pm.GROUPED_MIN_ROWS, 1)]
        return
    assert family_of(tiny[0]).prefill_grouped_from is None
    eng = _engine(tiny, prefill_chunk=64, max_seq_len=512, num_blocks=80)
    eng.generate(_prompts(1, 300), GenerationConfig(max_new_tokens=2))
    c = eng.counters()
    assert c["prefill_chunks"] == 5 and c["prefill_grouped_chunks"] == 0
    chunks = [ev[3] for evs in host_events.values() for ev in evs
              if ev[0] == "engine.prefill_chunk"]
    assert chunks and all(int(s["grouped"]) == 0 for s in chunks)


# -- (b) first-token stages ----------------------------------------------------


@pytest.fixture(scope="module")
def staged(tiny):
    """A labelled server that streamed six requests: per request the
    engine's enqueue stamp, the clock at its first yield, and its ledger
    row; and the stage sketches' counts."""
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.serve._private import slo

    mcfg, params = tiny
    dep = "stage-partition"
    slo.reset_ledger()
    server = LLMServer(
        LLMConfig(model_config=mcfg, max_batch_size=4, decode_chunk=4,
                  block_size=8, prefill_chunk=16,
                  max_seq_len=128, num_blocks=40), params)
    server.set_slo_label(dep)
    yields = {}
    first_yield = server._note_first_yield

    def spy(wkey):
        req = server._engine.tracked_request(wkey[2])
        out = first_yield(wkey)
        yields[wkey[2]] = (req.t_enqueue, time.monotonic())
        return out

    server._note_first_yield = spy
    got = {}

    def client(i, prompt):
        got[i] = [t for chunk in server.generate_stream(
            prompt, max_new_tokens=6 + i) for t in chunk]

    try:
        threads = [threading.Thread(target=client, args=(i, p))
                   for i, p in enumerate(_prompts(6, 40))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        ledger = slo.get_ledger()
        rows = {r["engine_rid"]: r for r in ledger.recent()
                if r.get("kind") == "engine" and r["deployment"] == dep}
        counts = {p["tags"]["stage"]: p["count"]
                  for p in ledger.row()["points"]
                  if p["name"] == "ray_tpu_serve_stage_seconds"
                  and p["tags"].get("deployment") == dep}
        return {"got": got, "yields": yields, "rows": rows, "counts": counts,
                "published": ledger.row().get("engine", {}).get(dep),
                "counters": server.utilization()["counters"]}
    finally:
        server.shutdown()
        slo.reset_ledger()


@pytest.mark.parametrize("what", ("partition",) + STAGES)
def test_first_token_stages_partition_enqueue_to_first_yield(staged, what):
    rows = staged["rows"]
    assert sorted(len(v) for v in staged["got"].values()) == list(range(6, 12))
    assert len(rows) == 6 and set(rows) == set(staged["yields"])
    if what != "partition":
        # one sample of every stage per request
        assert staged["counts"].get(what) == 6, staged["counts"]
        return
    for rid, row in rows.items():
        t_enqueue, t_yield = staged["yields"][rid]
        parts = (row["queue_wait_s"] + row["prefill_s"] + row["first_emit_s"]
                 + row["stream_out_s"])
        assert abs(parts - (t_yield - t_enqueue)) < 1e-3, (row, parts)
        assert min(row["enqueue_wait_s"], row["queue_wait_s"],
                   row["prefill_s"], row["first_emit_s"],
                   row["stream_out_s"]) >= 0.0
        assert row["prompt_tokens"] == 40 and row["prefill_chunks"] == 3
        assert row["decode_tokens"] == len(staged["got"][rid - 1])
        assert row["preempted"] == 0


# -- (c) counters ----------------------------------------------------------------


@pytest.fixture(scope="module")
def counted(tiny):
    """Counter reads after each phase of one engine's life: a batch with a
    shared prefix, a late arrival that joins a pipelined row, a cancel."""
    eng = _engine(tiny)
    reads = [eng.counters()]
    shared = list(range(1, 25))  # three full blocks
    prompts = [shared + [50 + i] * 6 for i in range(3)]
    gen = GenerationConfig(max_new_tokens=9)
    # the first registers the shared blocks, the others then hit them
    outs = eng.generate(prompts[:1], gen) + eng.generate(prompts[1:], gen)
    reads.append(eng.counters())
    # a request decoding alone pipelines; a late arrival's final prefill
    # chunk joins it on the device and drains nothing; the cancel that
    # follows drains the chunk in flight, once
    eng.add_request([7] * 12, GenerationConfig(max_new_tokens=40))
    while eng.counters()["decode_dispatches_pipelined"] \
            == reads[-1]["decode_dispatches_pipelined"]:
        eng.step()
    mid = eng.counters()
    rid = eng.add_request([9] * 12, GenerationConfig(max_new_tokens=40))
    eng.step()
    reads.append(eng.counters())
    eng.cancel_request(rid)
    while eng.has_work():
        eng.step()
    eng.flush()
    reads.append(eng.counters())
    return {"reads": reads, "mid": mid, "prompts": prompts, "outs": outs}


@pytest.fixture(scope="module")
def preempted(tiny):
    eng = _engine(tiny, num_blocks=14, enable_prefix_caching=False)
    outs = eng.generate(_prompts(3, 16), GenerationConfig(max_new_tokens=40))
    return eng.counters(), outs


@pytest.fixture(scope="module")
def dispatched(tiny):
    """One row per decode dispatch of a ragged batch: how far the two page
    counters moved, the decoding slots' block counts, the table's width."""
    from ray_tpu.llm.paged import _bucket_pow2

    eng = _engine(tiny)
    rows, inner = [], eng._dispatch_decode_locked

    def spy(active, chunk):
        blocks = [len(eng._slot_req[s].blocks) for s in active]
        before = eng.counters()
        out = inner(active, chunk)
        after = eng.counters()
        rows.append({k: after[k] - before[k]
                     for k in ("decode_table_pages", "decode_live_pages")}
                    | {"blocks": blocks, "w": _bucket_pow2(max(blocks))})
        return out

    eng._dispatch_decode_locked = spy
    prompts = [p[:n] for p, n in zip(_prompts(3, 40), (9, 22, 40))]
    eng.generate(prompts, GenerationConfig(max_new_tokens=30))
    return eng, rows


@pytest.mark.parametrize("counter", ["decode_table_pages",
                                     "decode_live_pages"])
def test_decode_page_counters_follow_each_dispatch(dispatched, counter):
    eng, rows = dispatched
    assert len(rows) > 4 and len({r["w"] for r in rows}) > 1
    assert any(len(r["blocks"]) < eng.max_batch for r in rows)
    for r in rows:
        want = (eng.max_batch * r["w"] if counter == "decode_table_pages"
                else sum(r["blocks"]))
        assert r[counter] == want, r
    assert eng.counters()[counter] == sum(r[counter] for r in rows)


def _flat(counters):
    out = {k: v for k, v in counters.items() if not isinstance(v, dict)}
    out.update({f"drains.{k}": v for k, v in counters["drains"].items()})
    return out


@pytest.mark.parametrize("case", ["prefill_tokens", "tokens_emitted",
                                  "forced_drain", "forced_preemption",
                                  "never_decrease", "ledger_row"])
def test_engine_counters(counted, preempted, staged, case):
    reads = counted["reads"]
    first = {k: reads[1][k] - reads[0][k] for k in _flat(reads[1])
             if k in reads[0] and not isinstance(reads[1][k], dict)}
    if case == "prefill_tokens":
        asked = sum(len(p) for p in counted["prompts"])
        assert first["prefix_hit_tokens"] > 0
        assert first["prefill_tokens"] == asked - first["prefix_hit_tokens"]
        assert first["prefill_padded_tokens"] >= 0
        assert first["prefill_chunks"] >= 3
    elif case == "tokens_emitted":
        assert first["tokens_emitted"] == sum(len(o) for o in counted["outs"])
        assert first["decode_token_steps"] == 4 * first["decode_dispatches"]
        assert first["steps"] > 0 and first["host_s"] > 0
        assert first["device_wait_s"] > 0
    elif case == "forced_drain":
        mid, joined, end = counted["mid"], reads[2], reads[3]
        assert joined["drains"] == mid["drains"]
        assert joined["decode_joins"] == mid["decode_joins"] + 1
        assert (joined["decode_dispatches_pipelined"]
                == mid["decode_dispatches_pipelined"] + 1)
        # the cancel, and the step that found the engine idle at the end
        assert {k: n - mid["drains"].get(k, 0)
                for k, n in end["drains"].items()} \
            == {"flush": 0, "cancel": 1, "idle": 1}
    elif case == "forced_preemption":
        got, outs = preempted
        assert all(len(o) == 40 for o in outs)
        assert got["preemptions"] >= 1
        assert got["drains"].get("preempt", 0) <= got["preemptions"]
        assert got["tokens_emitted"] == 120  # recompute re-emits nothing
        assert got["prefill_tokens"] > 3 * 16  # the victim's recompute
    elif case == "never_decrease":
        for a, b in zip(reads, reads[1:]):
            fa, fb = _flat(a), _flat(b)
            assert all(fb[k] >= v for k, v in fa.items()), (fa, fb)
        assert reads[-1]["compiles"] >= 0
    else:
        # the server's engine: utilization() and the published ledger row
        pub, live = staged["published"], staged["counters"]
        assert pub is not None
        assert set(pub) == set(live)
        assert pub["tokens_emitted"] == sum(
            len(v) for v in staged["got"].values())
        assert pub["prefill_tokens"] + pub["prefix_hit_tokens"] == 6 * 40
        assert live["loop_idle_s"] >= 0.0


# -- (d) the capture: one mode, no Python tracer, the .xplane.pb alone ------------

RID_CHAIN = ("serve.add_request", "engine.admit", "engine.prefill_chunk",
             "engine.join", "serve.iter_tokens")


def _events(path):
    """``[(line name, event name, start_ns, stats)]`` of a trace file's host
    planes."""
    from jax.profiler import ProfileData

    return [(line.name, e.name, e.start_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.fixture(scope="module")
def served_capture(tiny, tmp_path_factory):
    """``tracing.capture`` (what ``HandleJaxProfile`` runs) around a server
    that streams three requests to three client threads, answers a
    ``utilization()`` and handles one RPC frame: the reply's dict, the files
    under the log directory, the trace's host events and the engine's id of
    each request."""
    import os

    from ray_tpu._private.rpc import RpcClient, RpcServer
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.util import tracing

    mcfg, params = tiny
    server = LLMServer(
        LLMConfig(model_config=mcfg, max_batch_size=4, decode_chunk=4,
                  block_size=8, prefill_chunk=16, max_seq_len=128,
                  num_blocks=40), params)
    rpc = RpcServer()
    rpc.register("Echo", lambda req: req)
    logdir = str(tmp_path_factory.mktemp("capture"))
    got_tokens = {}

    def client(i, prompt):
        got_tokens[i] = [t for chunk in server.generate_stream(
            prompt, max_new_tokens=10) for t in chunk]

    try:
        server.generate(_prompts(1, 24)[0], max_new_tokens=6)  # compile
        rid0 = server._engine._req_counter
        with tracing.capture(logdir) as got:
            threads = [threading.Thread(target=client, args=(i, p))
                       for i, p in enumerate(_prompts(3, 24))]
            for t in threads:
                t.start()
            server.utilization()
            cli = RpcClient(rpc.address)
            assert cli.call("Echo", {"x": 1}, timeout=30) == {"x": 1}
            cli.close()
            for t in threads:
                t.join(timeout=120)
        files = sorted(os.path.join(dp, f) for dp, _, fs in os.walk(logdir)
                       for f in fs)
        assert sorted(len(v) for v in got_tokens.values()) == [10, 10, 10]
        return {"got": got, "files": files, "events": _events(got["files"][0]),
                "rids": list(range(rid0 + 1, rid0 + 4)),
                "last": tracing.last_capture()}
    finally:
        rpc.shutdown()
        server.shutdown()


@pytest.mark.parametrize("case", [
    "private_names", "reply", "one_file", "no_python_frames", "engine.step",
    "serve.add_request", "serve.iter_tokens", "serve.rpc",
    "serve.utilization", "rid_chain", "tracer_off_after_a_failure"])
def test_capture(served_capture, tmp_path, case):
    got, events = served_capture["got"], served_capture["events"]
    names = {e[1] for e in events}
    if case == "private_names":
        # ``tracing.capture`` stops the session without the export through
        # PRIVATE names of jax 0.9.0; if an upgrade moves one, this fails
        # here and not inside a serving process
        import jaxlib._profiler as lib
        from jax._src import profiler as private

        state = private._profile_state
        assert hasattr(state, "lock") and callable(state.reset), state
        assert state.profile_session is None  # none was left on
        assert callable(lib.ProfilerSession.stop)
        opts = jax.profiler.ProfileOptions()
        assert {"python_tracer_level", "host_tracer_level"} <= set(dir(opts))
    elif case == "reply":
        assert {"files", "traced_s", "write_s", "bytes"} <= set(got)
        assert got["traced_s"] > 0 and got["write_s"] > 0
        assert served_capture["last"] == {
            k: got[k] for k in ("traced_s", "write_s", "bytes")}
    elif case == "one_file":
        import os

        from jax.profiler import ProfileData

        assert served_capture["files"] == got["files"]  # no trace.json.gz
        (path,) = got["files"]
        assert path.endswith(".xplane.pb") and "/plugins/profile/" in path
        assert os.path.getsize(path) == got["bytes"]
        assert any(p.lines for p in ProfileData.from_file(path).planes)
    elif case == "no_python_frames":
        # the Python tracer names its events ``$file.py:line function``
        assert not [n for n in names if n.startswith("$")]
        assert any(n.startswith("PjitFunction(") for n in names), names
    elif case == "rid_chain":
        # one request's regions share its id from the server's add_request
        # to its first tokens handed out
        for rid in served_capture["rids"]:
            mine = [e for e in events if e[3].get("rid") == rid]
            assert set(RID_CHAIN) <= {e[1] for e in mine}, (rid, mine)
            first = {n: min(e[2] for e in mine if e[1] == n)
                     for n in RID_CHAIN}
            assert [first[n] for n in RID_CHAIN] == sorted(first.values())
        tokens = [e[3].get("tokens") for e in events
                  if e[1] == "serve.iter_tokens"]
        assert sum(tokens) == 30, tokens
    elif case == "tracer_off_after_a_failure":
        from jax._src import profiler as private

        from ray_tpu.util import tracing

        with pytest.raises(ZeroDivisionError):
            with tracing.capture(str(tmp_path)):
                1 / 0
        assert private._profile_state.profile_session is None
        with tracing.capture(str(tmp_path / "again")) as again:
            pass
        assert again["bytes"] > 0
    else:
        assert case in names, sorted(n for n in names if "." in n)
        if case == "serve.rpc":
            assert any(e[3].get("method") == "Echo" for e in events
                       if e[1] == case)


# -- (e) a worker's capture through state.jax_profile -----------------------------


@pytest.fixture(scope="module")
def worker_capture():
    """The whole path (``state.jax_profile`` -> raylet -> the worker's
    ``HandleJaxProfile``) on an actor that streams items and answers a call
    meanwhile: the reply and the trace's host events."""
    import ray_tpu
    from ray_tpu.util import state

    @ray_tpu.remote(max_concurrency=2)
    class Streamer:
        def pid(self):
            import os

            return os.getpid()

        def stream(self, seconds):
            import jax.numpy as jnp

            f = jax.jit(lambda x: (x @ x).sum())
            x = jnp.ones((64, 64))
            end = time.monotonic() + seconds
            n = 0
            while time.monotonic() < end:
                f(x).block_until_ready()
                time.sleep(0.05)
                n += 1
                yield n

    ray_tpu.init(num_cpus=2)
    try:
        a = Streamer.remote()
        pid = ray_tpu.get(a.pid.remote(), timeout=60)
        gen = a.stream.options(num_returns="streaming").remote(6.0)
        first = ray_tpu.get(next(gen), timeout=60)
        out = {}

        def profile():
            out["reply"] = state.jax_profile(pid, duration_s=1.5)

        t = threading.Thread(target=profile)
        t.start()
        while t.is_alive():  # calls inside the traced seconds
            ray_tpu.get(a.pid.remote(), timeout=60)
            time.sleep(0.2)
        t.join(timeout=120)
        items = [first] + [ray_tpu.get(r, timeout=60) for r in gen]
        assert items == list(range(1, len(items) + 1))
        return out["reply"], _events(out["reply"]["files"][0])
    finally:
        ray_tpu.shutdown()


@pytest.mark.timeout(240)
@pytest.mark.parametrize("case", ["reply", "serve.task", "serve.rpc",
                                  "no_python_frames"])
def test_worker_capture(worker_capture, case):
    reply, events = worker_capture
    if case == "reply":
        assert {"pid", "logdir", "files", "traced_s", "write_s",
                "bytes"} <= set(reply)
        assert 1.4 < reply["traced_s"] < 3.0
        assert len(reply["files"]) == 1
    elif case == "no_python_frames":
        assert events and not [e for e in events if e[1].startswith("$")]
    elif case == "serve.task":
        mine = [e[3] for e in events if e[1] == case]
        assert any(s.get("item", 0) > 0 for s in mine), mine  # a stream's
        assert any("pid" in str(s.get("name")) for s in mine), mine
    else:
        methods = {e[3].get("method") for e in events if e[1] == case}
        assert "PushActorTask" in methods or any(
            "Task" in str(m) for m in methods), methods


# -- (f) device_empty_s: the idle share by the engine's own clocks ----------------


@pytest.mark.parametrize("case", ["late_arrival", "pipelined_steps",
                                  "adds_up"])
def test_device_empty_clock(tiny, case):
    eng = _engine(tiny, decode_chunk=1)
    gen = GenerationConfig(max_new_tokens=100)
    warm, late = _prompts(2, 12)  # nothing shared: the same programs run
    eng.generate([warm], GenerationConfig(max_new_tokens=4))
    if case == "late_arrival":
        # the last step drained an idle engine: the device is known to be
        # empty, and no request is live, so the wait for one books nothing;
        # the pause between an arrival and the step that serves it does
        time.sleep(0.3)
        before = eng.counters()["device_empty_s"]
        eng.add_request(late, gen)
        time.sleep(0.2)
        eng.step()
        grew = eng.counters()["device_empty_s"] - before
        assert 0.2 <= grew < 0.45, grew
        return
    eng.add_request(late, gen)
    warm_up = eng.counters()["decode_dispatches_pipelined"]
    while eng.counters()["decode_dispatches_pipelined"] < warm_up + 2:
        eng.step()
    before = eng.counters()
    t0 = time.monotonic()
    for _ in range(50):
        eng.step()
    wall = time.monotonic() - t0
    after = eng.counters()
    if case == "pipelined_steps":
        assert (after["decode_dispatches_pipelined"]
                - before["decode_dispatches_pipelined"]) == 50
        assert after["device_empty_s"] == before["device_empty_s"]
    else:
        booked = (after["host_s"] + after["device_wait_s"]
                  - before["host_s"] - before["device_wait_s"])
        assert after["steps"] - before["steps"] == 50
        assert 0.9 * wall <= booked <= wall, (booked, wall)
