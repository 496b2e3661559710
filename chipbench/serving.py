"""Bringing a serving configuration up through the actor path, loading it,
and collecting the evidence the metric readers take their numbers from.

The harness process is the DRIVER: ``ray_tpu.init`` ->
``serve.run(build_openai_app(cfg, params=None))`` with the replica ACTOR in a
worker process -> HTTP proxy (in this process, as ``serve.start_http_proxy``
puts it) -> ``/v1/completions``, streamed.  It never initialises a JAX
backend (checked at the end of every run).  The load comes from a child
process (``loadgen.py``).  The deploy steps and the chip-holder scan are
copied from ``chip_smoke.py``, which this benchmark does not import.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from chipbench import spec
from chipbench.spec import BenchError, log

# A served greedy token may give up at most this much float32-reference
# logit against the reference's own argmax (bf16 forward against float32;
# logit std about 1.3 with these random weights, and bf16 logits near 5
# are 0.03 apart).  Stated in chip_smoke.py (PR 21), which measured 0.03
# to 0.08; computing in a lower precision than bf16 would not stay inside.
REF_LOGIT_TOL = 0.25
# the probes of the reference check: (prompt tokens, greedy tokens).  The
# second crosses the 256-token prefill chunk.  Fixed shapes, so that the
# reference's programs are in the compile cache after a cell's first run.
PROBES = ((48, 16), (320, 16))
DEPLOYMENT = "chipbench"


class IdTokenizer:
    """``"12 7 300"`` <-> ``[12, 7, 300]``: random weights speak no language,
    and the client has to count and compare the served TOKENS."""

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return "".join(f"{int(i)} " for i in ids)


# -- what holds the chip, read from /proc (no JAX) ---------------------------


def chip_files():
    return sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*"))


def chip_holders() -> dict:
    """pid -> chip device files it has open."""
    files = set(chip_files())
    out: dict = {}
    for fd_dir in glob.glob("/proc/[0-9]*/fd"):
        try:
            held = {os.readlink(os.path.join(fd_dir, f))
                    for f in os.listdir(fd_dir)} & files
        except OSError:
            continue
        if held:
            out[int(fd_dir.split("/")[2])] = sorted(held)
    return out


def wait_gone(pids, what: str, timeout: float = 90.0) -> None:
    """The worker has exited and its chip is free."""
    deadline = time.monotonic() + timeout
    alive = list(pids)
    while time.monotonic() < deadline:
        alive = [p for p in pids if os.path.exists(f"/proc/{p}")
                 and "Z" not in open(f"/proc/{p}/stat").read().split(")")[-1][:3]]
        if not alive and not (set(chip_holders()) & set(pids)):
            return
        time.sleep(0.25)
    raise BenchError(f"{what}: worker(s) {alive} still alive after {timeout}s")


# -- the cluster ---------------------------------------------------------------


def cache_dir() -> str:
    return os.path.join(spec.ROOT, ".jax_cache")


def cache_files() -> int:
    return sum(len(fs) for _, _, fs in os.walk(cache_dir()))


def start_cluster(chips: int, rehearse: bool) -> int:
    """``ray_tpu.init`` on this machine; returns the chips found.  The
    compile cache is at ``<checkout>/.jax_cache`` whatever the environment
    says: a directory named from outside is dropped, and the program's own
    ``compile_cache.configure`` then places the cache in the checkout (and
    hands it to the workers through the environment)."""
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from ray_tpu._private import compile_cache

    if compile_cache.configure() != cache_dir():
        raise BenchError("the program did not place its compile cache at "
                         f"{cache_dir()}")
    os.environ.setdefault("RAY_TPU_DISABLE_METADATA_SERVER", "1")
    # replica start-up (weights, warm-up compiles) outlasts the 120 s default
    os.environ.setdefault("RAY_TPU_actor_creation_timeout_s", "1100")
    os.environ.setdefault("RAY_TPU_WORKER_QUIET", "1")
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("RAY_TPU_NUM_CHIPS", str(chips))
        if chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={chips}")
    import ray_tpu
    from ray_tpu._private.accelerators import get_accelerator_manager

    found = get_accelerator_manager("TPU").get_current_node_num_accelerators()
    if found < chips:
        raise BenchError(f"this machine has {found} TPU chip(s) "
                         f"({chip_files()}), the cell needs {chips}")
    ray_tpu.init()
    return found


def stop_cluster() -> None:
    import ray_tpu

    ray_tpu.shutdown()
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise BenchError("the harness process initialised a JAX backend")


def llm_config(config: dict, rehearse: bool):
    """The configuration file -> ``LLMConfig``.  Engine options the file
    does not list stay at the program's defaults."""
    import jax.numpy as jnp  # dtype objects only: touches no backend

    from ray_tpu.llm import LLMConfig
    from ray_tpu.models.llama import LlamaConfig

    eng = dict(config["engine"])
    if rehearse:
        mcfg = LlamaConfig.tiny(vocab_size=512, dim=256, n_heads=8,
                                n_kv_heads=4, ffn_dim=512, n_layers=2,
                                max_seq_len=eng["max_seq_len"])
        eng.update(num_blocks=2048, max_batch_size=min(16, eng["max_batch_size"]))
    else:
        if config["hidden_size"] != config["num_attention_heads"] * config["head_dim"]:
            raise BenchError("LlamaConfig derives head_dim as hidden_size / "
                             "heads; this configuration's differs")
        mcfg = LlamaConfig(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            n_layers=config["num_hidden_layers"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            ffn_dim=config["intermediate_size"],
            max_seq_len=eng["max_seq_len"], rope_theta=config["rope_theta"],
            rms_norm_eps=config["rms_norm_eps"],
            tie_embeddings=config["tie_word_embeddings"],
            param_dtype=jnp.bfloat16)
    return LLMConfig(model_config=mcfg, **eng)


class Replica:
    """One deployed configuration: its handle, its address, its report."""

    def __init__(self, cell, rehearse: bool):
        from ray_tpu import serve
        from ray_tpu.llm import build_openai_app

        self.cell, self.rehearse = cell, rehearse
        self.cfg = llm_config(cell.config, rehearse)
        m = self.cfg.model_config
        self.vocab = m.vocab_size
        log(f"deploying {cell.config_entry['name']}: dim={m.dim} "
            f"layers={m.n_layers} heads={m.n_heads}/{m.n_kv_heads} "
            f"vocab={m.vocab_size} tp={self.cfg.tensor_parallel_size} "
            f"blocks={self.cfg.num_blocks}x{self.cfg.block_size} "
            f"batch={self.cfg.max_batch_size} "
            f"resources={self.cfg.resources_per_replica()}")
        t0 = time.monotonic()
        app = build_openai_app(self.cfg, params=None, tokenizer=IdTokenizer(),
                               model_id=DEPLOYMENT, name=DEPLOYMENT)
        self.handle = serve.run(app, name=DEPLOYMENT, route_prefix="/v1")
        self.base = serve.start_http_proxy(port=0)
        self.report = self.handle.device_report.remote().result(timeout_s=600)
        self.up_s = time.monotonic() - t0
        rep = self.report
        log(f"replica pid {rep['pid']} up in {self.up_s:.1f}s on "
            f"{rep['device_count']} x {rep['device_kind']} ({rep['platform']}), "
            f"attention={rep['paged_attention']}, warmup={rep['warmup']}, "
            f"memory={rep['memory']}")
        if rep["pid"] == os.getpid():
            raise BenchError("the replica runs in the harness process")
        if rehearse:
            return
        if rep["platform"] != "tpu":
            raise BenchError(f"replica's platform is {rep['platform']!r}")
        if rep["device_count"] != cell.chips:
            raise BenchError(f"replica sees {rep['device_count']} devices, "
                             f"the cell asks {cell.chips}")
        if rep["warmup"] is None:
            raise BenchError("warmup() did not run")
        holders = chip_holders()
        if set(holders) != {rep["pid"]}:
            raise BenchError(f"chip device files are held by {holders}, not "
                             f"only by the replica {rep['pid']}")

    def utilization(self) -> dict:
        return self.handle.utilization.remote().result(timeout_s=30)

    def ledger_rows(self) -> list:
        """Every reporter's published ledger row, raw (sketch buckets)."""
        from ray_tpu.util.state import api

        try:
            return api._client()._slo_rows()
        except Exception as e:  # noqa: BLE001 - a reader then finds nothing
            log(f"ledger rows not readable: {type(e).__name__}: {e}")
            return []

    def load(self, seed: int, seconds: float, traffic: dict, window_t0: float,
             workdir: str) -> subprocess.Popen:
        plan = {"base": list(self.base), "model": DEPLOYMENT, "seed": seed,
                "seconds": seconds, "vocab": self.vocab, "traffic": traffic,
                "window_t0": window_t0}
        plan_path = os.path.join(workdir, "plan.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        return subprocess.Popen(
            [sys.executable, os.path.join(spec.BENCH, "loadgen.py"),
             "--plan", plan_path, "--out", os.path.join(workdir, "rows.json")],
            env=env)

    def check_reference(self, seed: int) -> dict:
        """Seeded probes, greedy, held against the float32 reference of the
        same weights inside the replica (``LLMServer.reference_check``: the
        weights live there and nowhere else)."""
        from chipbench import loadgen

        worst, rows = 0.0, []
        for i, (plen, n) in enumerate(PROBES):
            if self.rehearse:
                plen = min(plen, 40)
            ids = loadgen.prompt_ids(seed, 9_000_000 + i, plen, self.vocab)
            got = loadgen.send(tuple(self.base), DEPLOYMENT, ids, n,
                               self.vocab, 300.0)
            if not got["ok"]:
                return {"ok": False, "why": f"probe {i}: {got['error']}"}
            ref = self.handle.reference_check.remote(ids, got["ids"]).result(
                timeout_s=900)
            rows.append({"prompt": plen, "tokens": n,
                         "max_logit_gap": ref["max_logit_gap"],
                         "first_divergent": ref["first_divergent"],
                         "logit_std": ref["logit_std"]})
            if not ref["finite"]:
                return {"ok": False, "why": f"probe {i}: reference not finite"}
            worst = max(worst, ref["max_logit_gap"])
        ok = worst <= REF_LOGIT_TOL
        log(f"float32 reference: {rows}; worst gap {worst:.4f} "
            f"(tolerance {REF_LOGIT_TOL})")
        return {"ok": ok, "max_logit_gap": worst, "probes": rows,
                "compared": [["max_logit_gap", worst, REF_LOGIT_TOL]],
                "why": None if ok else f"served tokens give up {worst} "
                                       "reference logit"}

    def down(self) -> None:
        from ray_tpu import serve

        serve.shutdown()
        wait_gone([self.report["pid"]], "replica")


# -- one run ---------------------------------------------------------------------


def toy_traffic(traffic: dict) -> dict:
    """The same mix at a size a CPU can serve in a rehearsal."""
    toy = json.loads(json.dumps(traffic))
    toy["prompt_len"].update(min=8, max=96, median=32)
    toy["output_len"].update(min=4, max=24, median=12)
    toy.update(ramp_s=3, drain_s=60, trace_s=2.0)
    if toy["loop"] == "open":
        toy["arrivals"]["rate_per_s"] = 2.0
    else:
        toy.update(clients=8, request_pool=32)
    return toy


def run_cell(cell, args) -> dict:
    """Set-up, ramp, window, drain, check, teardown: returns the evidence."""
    traffic = cell.traffic
    if args.rehearse:
        traffic = toy_traffic(traffic)
    seconds = float(args.seconds)
    start_cluster(cell.chips, args.rehearse)
    try:
        replica = Replica(cell, args.rehearse)
        try:
            return _measure(cell, args, replica, traffic, seconds)
        finally:
            replica.down()
    finally:
        stop_cluster()


def _measure(cell, args, replica, traffic, seconds) -> dict:
    trace = bool(args.trace)
    workdir = tempfile.mkdtemp(prefix="chipbench_")
    ramp_s = float(traffic.get("ramp_s", 0))
    window_t0 = time.monotonic() + 2.0 + ramp_s
    files_before = cache_files()
    child = replica.load(args.seed, seconds, traffic, window_t0, workdir)
    setup_s = window_t0 - spec.T0
    log(f"set-up {setup_s:.1f}s (replica up {replica.up_s:.1f}s); ramp "
        f"{ramp_s:.0f}s, window {seconds:.0f}s")

    evidence = {"kind": cell.kind, "traffic": traffic, "config": cell.config,
                "decode_chunk": replica.cfg.decode_chunk,
                "seconds": seconds, "setup_s": setup_s,
                "replica_up_s": replica.up_s, "report": replica.report,
                "util_samples": [], "trace": None,
                "ledger_before": None, "ledger_after": None}

    def side():  # the traced run's reads, beside the load
        time.sleep(max(0.0, window_t0 - time.monotonic()))
        evidence["ledger_before"] = replica.ledger_rows()
        trace_at = window_t0 + 0.4 * seconds
        trace_s = min(float(traffic.get("trace_s", 4.0)), 0.4 * seconds)
        traced = False
        while time.monotonic() < window_t0 + seconds:
            if not traced and time.monotonic() >= trace_at:
                traced = True
                evidence["trace"] = capture_trace(
                    replica.report["pid"], trace_s, workdir)
            try:
                u = replica.utilization()
                evidence["util_samples"].append(
                    {"t": time.monotonic() - window_t0,
                     "slots_active": u["slots"]["active"],
                     "slots_max": u["slots"]["max"],
                     "kv_used": u["kv_blocks"]["used"],
                     "kv_total": u["kv_blocks"]["total"],
                     "pending": u["pending"]})
            except Exception as e:  # noqa: BLE001 - one sample lost
                log(f"utilization sample failed: {type(e).__name__}: {e}")
            time.sleep(1.0)

    sider = None
    if trace:
        sider = threading.Thread(target=side, daemon=True)
        sider.start()
    limit = (window_t0 - time.monotonic()) + seconds + \
        float(traffic.get("drain_s", 60)) + 60
    try:
        rc = child.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        raise BenchError("the load generator did not end") from None
    if rc != 0:
        raise BenchError(f"the load generator exited {rc}")
    if sider is not None:
        sider.join(timeout=120)
    gained = cache_files() - files_before
    log(f"compile cache gained {gained} file(s) from the ramp's start to the "
        "drain's end (a compile inside the window is a finding)")
    if trace:
        time.sleep(2.5)  # the ledger's publishing lag
        evidence["ledger_after"] = replica.ledger_rows()
    with open(os.path.join(workdir, "rows.json")) as f:
        evidence["rows"] = json.load(f)["rows"]
    # one line a later reader of the log can take any statistic from
    # (steady.py does): got is what tpot divides by
    log("window rows [key, prompt, asked, due s, ttft ms, last ms, got]: "
        + json.dumps(
            [[r["key"], r["prompt_len"], r["max_tokens"], round(r["due"], 3),
              round((r["first"] - r["due"]) * 1e3, 1),
              round((r["last"] - r["due"]) * 1e3, 1), r["got"]]
             for r in evidence["rows"] if r["phase"] == "window" and r["ok"]
             and r.get("due") is not None]))
    gap = spec.stream_gap(evidence["rows"], seconds)
    if gap is not None:
        # a run that stalls says so (steady.py prints it beside the run)
        log("stream gap [longest pause ms, at s, stream key, streams paused "
            "within 50 ms of it, streams live then]: " + json.dumps(
                [round(gap["max_ms"], 1), round(gap["at_s"], 3), gap["key"],
                 gap["paused"], gap["live"]]))
    if evidence["trace"]:
        t0 = time.monotonic()
        try:
            evidence["trace"] = parse_trace(evidence["trace"])
            log(f"trace parsed in {time.monotonic() - t0:.1f}s, after the "
                "drain")
        except Exception as e:  # noqa: BLE001 - the readers then find nothing
            log(f"trace not readable: {type(e).__name__}: {e}")
            evidence["trace"] = None
    evidence["cache_files_gained"] = gained
    evidence["reference"] = replica.check_reference(args.seed)
    after = replica.handle.device_report.remote().result(timeout_s=120)
    evidence["report_after"] = after
    evidence["deployment"] = DEPLOYMENT
    shutil.rmtree(workdir, ignore_errors=True)  # plan, rows, the trace file
    return evidence


def capture_trace(pid: int, duration_s: float, workdir: str):
    """One XPlane capture of the worker that holds the chip, through the
    program's own ``state.jax_profile``; returns ``{"path": the .xplane.pb}``
    (or None, and says why).  The file is NOT parsed here: the HTTP proxy
    lives in this process, and seconds of parsing inside the window are
    seconds in which the window's streams wait (``parse_trace``)."""
    from ray_tpu.util import state

    logdir = os.path.join(workdir, "trace")
    try:
        got = state.jax_profile(pid, duration_s=duration_s, logdir=logdir)
        files = [f for f in got.get("files", ()) if f.endswith(".xplane.pb")]
        if not files:
            log(f"trace capture wrote no .xplane.pb: {got}")
            return None
        log(f"trace: {files[0]} ({os.path.getsize(files[0])} bytes, "
            f"{duration_s:.1f}s asked)")
        return {"path": files[0]}
    except Exception as e:  # noqa: BLE001 - the readers then find nothing
        log(f"trace capture failed: {type(e).__name__}: {e}")
        return None


def parse_trace(got: dict) -> dict:
    """The captured file -> ``planes`` as ``trace_reduce.load`` gives them.
    Called once the load has drained and the ledger is read: nothing of the
    window waits for it."""
    from chipbench import trace_reduce

    got["planes"] = trace_reduce.load(got["path"])
    return got


def compared(evidence: dict) -> list:
    """``[name, number, limit]`` of every number ``serving_correct`` holds
    to a limit, for the result line and the log's last lines: the failed
    requests, and what the reference check's verdict says it compared (the
    check that judged states them, ``compared`` in its verdict: one list;
    a check that broke off before it had a number states none)."""
    return [["failed_requests", sum(
        1 for r in evidence["rows"] if r["phase"] == "window"
        and not r.get("cut") and not r["ok"]), 0]] + [
            list(c) for c in evidence["reference"].get("compared", ())]


def serving_correct(evidence: dict, rehearse: bool):
    """``(correct, attempted, failed, why)`` of a serving run."""
    rows = [r for r in evidence["rows"] if r["phase"] == "window"
            and not r.get("cut")]
    failed = [r for r in rows if not r["ok"]]
    rep = evidence["report"]
    why = []
    if not rehearse:
        if rep["platform"] != "tpu":
            why.append(f"platform {rep['platform']}")
        if rep["paged_attention"] != "kernel":
            why.append(f"attention path {rep['paged_attention']}, not the "
                       "paged-attention kernel")
    if failed:
        why.append(f"{len(failed)} request(s) failed, first: "
                   f"{failed[0]['error']}")
    if not evidence["reference"]["ok"]:
        why.append(evidence["reference"]["why"])
    return not why, len(rows), len(failed), "; ".join(why)


def device_block(evidence: dict) -> dict:
    rep = evidence.get("report_after") or evidence["report"]
    peak = max((m.get("peak_bytes_in_use", 0) for m in rep["memory"]),
               default=0)
    return {"platform": rep["platform"], "kind": rep["device_kind"],
            "count": rep["device_count"], "memory_peak_bytes": int(peak)}
