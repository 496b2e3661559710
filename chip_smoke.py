#!/usr/bin/env python3
"""Proof that the program starts on the chip, through the entry points a
user calls.

    python chip_smoke.py            # one TPU chip: serve phase, then train phase
    python chip_smoke.py --chips 4  # the four-chip paths and what they are compared with

This process is the DRIVER: it never initialises a JAX backend (asserted at
the end).  Each phase's device work happens in exactly one worker process,
and that worker has exited, chip released, before the next phase starts.

  serve  ray_tpu.init -> serve.run(build_openai_app(cfg, params=None)) with a
         replica ACTOR in a worker process -> HTTP proxy -> /v1/completions.
         Llama-3-8B at published widths (dim 4096, 32/8 heads, head_dim 128,
         ffn 14336, vocab 128256, bf16), depth cut to fit one chip beside a
         KV pool that fills most of the rest; random weights from seed 0,
         made inside the replica.
  train  JaxTrainer(train_func, ScalingConfig(num_workers=1, use_tpu=True)):
         a few make_train_step steps of the 1.14 B shape (dim 2048, 16
         layers, 16/8 heads, ffn 8192, vocab 32768, batch 8 x 2048, bf16).

Exit code 0 and a last line {"ok": true, "device": {...}} only when every
phase passed AND the workers ran on a TPU.  ``--rehearse`` runs the same
control flow at toy size on whatever backend the workers get (the CPU, in a
sandbox) and can never print that line: it always exits non-zero.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

# Stated tolerances (measured values are printed next to them):
# a served greedy token may give up at most this much reference logit
# against the float32 reference's own argmax (bf16 forward vs float32)
REF_LOGIT_TOL = 0.25
# largest |logit| difference, first decode step, TP=4 against one device
# (same bf16 weights, changed reduction order)
TP_LOGIT_TOL = 0.25
# loss(fsdp=2 x tensor=2) against loss(one chip) at the same seed and step:
# before any update (the same weights, the forward alone) absolutely, and
# relatively once six bf16 adamw steps on one repeated batch (loss 10.8 ->
# 5.2) have carried each run along its own rounding
TRAIN_LOSS_TOL_STEP0 = 0.002
TRAIN_LOSS_RTOL = 0.03

SERVE_DEPTH = 8          # of Llama-3-8B's 32 layers: 5.6 GB of bf16 weights
SERVE_NUM_BLOCKS = 14336  # x16 tokens x 32 KiB/token at depth 8 = 7.5 GB of KV
SERVE_MAX_SEQ = 1024
TRAIN_STEPS = 6


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


class Failed(SystemExit):
    def __init__(self, msg: str):
        print(f"[chip_smoke] FAILED: {msg}", flush=True)
        super().__init__(1)


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


# -- token-id "tokenizer": the text of a request IS its token ids ----------


class IdTokenizer:
    """``"12 7 300"`` <-> ``[12, 7, 300]``: random weights speak no language,
    and the driver has to count and compare the served TOKENS."""

    def encode(self, text):
        return [int(t) for t in text.split()]

    def decode(self, ids):
        return "".join(f"{int(i)} " for i in ids)


def _ids_text(ids) -> str:
    return " ".join(str(i) for i in ids)


def _prompt(n: int, salt: int, vocab: int):
    return [(salt * 7919 + 31 * j * j + 17 * j) % vocab for j in range(n)]


# -- what holds the chip, read from /proc (no JAX) --------------------------


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
    """The phase's worker has exited: its chip is free for the next one."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = [p for p in pids if os.path.exists(f"/proc/{p}")
                 and "Z" not in open(f"/proc/{p}/stat").read().split(")")[-1][:3]]
        if not alive and not (set(chip_holders()) & set(pids)):
            log(f"{what}: worker(s) {sorted(pids)} exited, chip released")
            return
        time.sleep(0.5)
    raise Failed(f"{what}: worker(s) {alive} still alive after {timeout}s")


# -- HTTP ----------------------------------------------------------------------


def post_completion(base: str, prompt_ids, max_tokens: int, stream: bool):
    """One /v1/completions request; returns the served token ids."""
    body = {"model": "smoke", "prompt": _ids_text(prompt_ids),
            "max_tokens": max_tokens, "temperature": 0.0, "stream": stream}
    req = urllib.request.Request(
        f"{base}/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        if not stream:
            out = json.load(resp)
            toks = IdTokenizer().encode(out["choices"][0]["text"])
            check(out["usage"]["completion_tokens"] == len(toks),
                  f"usage says {out['usage']} but text holds {len(toks)} ids")
            return toks
        text, frames, done = "", 0, False
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            text += json.loads(line[6:])["choices"][0].get("text") or ""
            frames += 1
        check(done, "stream ended without [DONE]")
        check(frames >= 2, f"stream had {frames} frame(s)")
        return IdTokenizer().encode(text)


# -- serve phase -----------------------------------------------------------------


def serve_config(args, *, tp: int, depth: int):
    import jax.numpy as jnp  # dtype objects only: touches no backend

    from ray_tpu.llm import LLMConfig
    from ray_tpu.models.llama import LlamaConfig

    if args.rehearse:
        mcfg = LlamaConfig.tiny(vocab_size=512, dim=256, n_heads=8,
                                n_kv_heads=4, ffn_dim=512, n_layers=2,
                                max_seq_len=SERVE_MAX_SEQ)
        blocks = 256
    else:
        mcfg = LlamaConfig.llama3_8b(param_dtype=jnp.bfloat16, n_layers=depth,
                                     max_seq_len=SERVE_MAX_SEQ)
        blocks = SERVE_NUM_BLOCKS
    return LLMConfig(model_config=mcfg, max_batch_size=8,
                     max_seq_len=SERVE_MAX_SEQ, num_blocks=blocks,
                     tensor_parallel_size=tp)


def serve_phase(args, name: str, *, tp: int = 1, depth: int = SERVE_DEPTH,
                reference: bool = True, probe_prompt=None) -> dict:
    """Deploy, ask over HTTP, check, tear down.  Returns what was seen."""
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    cfg = serve_config(args, tp=tp, depth=depth)
    mcfg = cfg.model_config
    log(f"{name}: deploying dim={mcfg.dim} layers={mcfg.n_layers} "
        f"heads={mcfg.n_heads}/{mcfg.n_kv_heads} vocab={mcfg.vocab_size} "
        f"tp={tp} blocks={cfg.num_blocks}x{cfg.block_size} "
        f"resources={cfg.resources_per_replica()}")
    t0 = time.monotonic()
    app = build_openai_app(cfg, params=None, tokenizer=IdTokenizer(),
                           model_id="smoke", name=name)
    handle = serve.run(app, name=name, route_prefix="/v1")  # registers /v1
    host, port = serve.start_http_proxy(port=0)
    base = f"http://{host}:{port}"
    rep = handle.device_report.remote().result(timeout_s=300)
    up_s = time.monotonic() - t0
    log(f"{name}: replica pid {rep['pid']} up in {up_s:.1f}s on "
        f"{rep['device_count']} x {rep['device_kind']} ({rep['platform']}), "
        f"TPU_VISIBLE_CHIPS={rep['visible_chips']}, "
        f"attention={rep['paged_attention']}, warmup={rep['warmup']}")
    log(f"{name}: compile cache {rep['compile_cache_dir']}; replica native "
        f"components {rep['native']}; chip holders {chip_holders()}")
    check(rep["pid"] != os.getpid(), "replica runs in the driver process")
    check(rep["device_count"] == tp or args.rehearse,
          f"replica sees {rep['device_count']} devices, wants {tp}")
    if not args.rehearse:
        check(rep["platform"] == "tpu",
              f"replica's platform is {rep['platform']!r}, not 'tpu'")
        check(rep["paged_attention"] == "kernel",
              f"engine took the {rep['paged_attention']} path, not the "
              "paged-attention kernel")
        check(rep["warmup"] is not None, "warmup() did not run")
        holders = chip_holders()
        check(set(holders) == {rep["pid"]},
              f"chip device files are held by {holders}, not only by the "
              f"replica {rep['pid']}")

    v = mcfg.vocab_size
    short, mid = _prompt(24, 1, v), _prompt(40, 2, v)
    # 500 tokens: crosses the 256-token prefill chunk; +24 generated crosses
    # 512 tokens = 32 blocks, the edge of a power-of-two table bucket
    long = _prompt(500, 3, v)
    asked = {"greedy": (short, 16, False), "stream": (mid, 24, True),
             "long": (long, 24, False), "beside": (_prompt(30, 4, v), 16, True)}
    got: dict = {}

    def ask(key):
        p, n, stream = asked[key]
        got[key] = post_completion(base, p, n, stream)

    t0 = time.monotonic()
    ask("greedy")
    ask("stream")
    threads = [threading.Thread(target=ask, args=(k,)) for k in ("long", "beside")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for key, (p, n, stream) in asked.items():
        check(key in got, f"request {key!r} failed")
        check(len(got[key]) == n,
              f"request {key!r} asked {n} tokens, got {len(got[key])}")
        check(all(0 <= t < v for t in got[key]), f"{key!r}: id out of vocab")
    log(f"{name}: 4 requests over HTTP (2 streamed, 2 concurrent, one "
        f"{len(long)}-token prompt) returned {sum(map(len, got.values()))} "
        f"tokens in {time.monotonic() - t0:.1f}s")

    seen = {"report": rep, "tokens": got, "up_seconds": up_s}
    if reference:
        ref = handle.reference_check.remote(short, got["greedy"]).result(
            timeout_s=600)
        log(f"{name}: float32 reference over {len(got['greedy'])} greedy "
            f"tokens: max logit gap {ref['max_logit_gap']:.4f} (tolerance "
            f"{REF_LOGIT_TOL}, logit std {ref['logit_std']:.3f}), first "
            f"token off the reference argmax: {ref['first_divergent']}")
        check(ref["finite"], "reference logits are not finite")
        check(ref["max_logit_gap"] <= REF_LOGIT_TOL,
              f"served tokens give up {ref['max_logit_gap']} reference logit")
        seen["reference"] = ref
    if probe_prompt is not None:
        seen["first_decode_logits"] = handle.first_decode_logits.remote(
            probe_prompt).result(timeout_s=600)
    after = handle.device_report.remote().result(timeout_s=120)
    peaks = [m.get("peak_bytes_in_use") for m in after["memory"]]
    log(f"{name}: peak device bytes {peaks}; utilization "
        f"{json.dumps(after['utilization'], default=str)[:600]}")
    seen["after"] = after
    serve.shutdown()
    wait_gone([rep["pid"]], name)
    return seen


# -- train phase -----------------------------------------------------------------


def train_func(config):
    """Runs in the train worker: the only process of this phase on the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.parallel import MeshSpec, make_train_step

    devices = jax.devices()
    if config["rehearse"]:
        cfg = LlamaConfig.tiny(n_heads=4, n_kv_heads=2)
        batch, seq = 4, 128
    else:
        cfg = LlamaConfig(vocab_size=32768, dim=2048, n_layers=16, n_heads=16,
                          n_kv_heads=8, ffn_dim=8192, max_seq_len=2048,
                          param_dtype=jnp.bfloat16)
        batch, seq = 8, 2048
    optimizer = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1,
                            mu_dtype=jnp.bfloat16)
    mesh = None
    if config["mesh"] is not None:
        mesh = MeshSpec(**config["mesh"]).build(devices)
    init_fn, step_fn = make_train_step(cfg, mesh, optimizer=optimizer)
    state = init_fn(jax.random.PRNGKey(config["seed"]))
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (batch, seq), 0, cfg.vocab_size)
    t0 = time.monotonic()
    compiled = step_fn.lower(state, tokens).compile()
    compile_s = time.monotonic() - t0
    for step in range(config["steps"]):
        state, metrics = compiled(state, tokens)
        row = {"step": step, "loss": float(metrics["loss"]),
               "grad_norm": float(metrics["grad_norm"])}
        if step == 0:
            stats = [d.memory_stats() or {} for d in devices]
            row.update(
                pid=os.getpid(), platform=devices[0].platform,
                device_kind=devices[0].device_kind, device_count=len(devices),
                visible_chips=os.environ.get("TPU_VISIBLE_CHIPS"),
                compile_seconds=compile_s,
                flash_kernel="tpu_custom_call" in compiled.as_text(),
                params=cfg.num_params,
                compile_cache_dir=jax.config.jax_compilation_cache_dir)
        if step == config["steps"] - 1:
            row["peak_bytes"] = [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devices]
        train.report(row)


def train_phase(args, name: str, *, devices: int, all_chips: bool,
                mesh=None) -> dict:
    """``all_chips``: plain ``use_tpu=True``, which asks for every chip the
    node has; otherwise the worker is carved ``devices`` chips."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    scaling = ScalingConfig(num_workers=1, use_tpu=True,
                            chips_per_worker=None if all_chips else devices)
    log(f"{name}: JaxTrainer.fit, worker resources "
        f"{scaling.worker_resources()}, mesh {mesh}")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as store:
        result = JaxTrainer(
            train_func,
            train_loop_config={"rehearse": args.rehearse, "seed": args.seed,
                               "steps": TRAIN_STEPS, "mesh": mesh},
            scaling_config=scaling,
            run_config=RunConfig(name=name, storage_path=store),
        ).fit()
    check(result.error is None, f"{name}: {result.error}")
    rows = result.metrics_history
    check(len(rows) == TRAIN_STEPS,
          f"{name}: {len(rows)} of {TRAIN_STEPS} train.report rows reached "
          "the driver")
    first, losses = rows[0], [r["loss"] for r in rows]
    log(f"{name}: worker pid {first['pid']} on {first['device_count']} x "
        f"{first['device_kind']} ({first['platform']}), TPU_VISIBLE_CHIPS="
        f"{first['visible_chips']}; {first['params'] / 1e9:.2f} B params; "
        f"step compiled in {first['compile_seconds']:.1f}s; flash kernel in "
        f"the compiled step: {first['flash_kernel']}")
    log(f"{name}: losses {[round(x, 4) for x in losses]}; peak device bytes "
        f"{rows[-1]['peak_bytes']}")
    check(first["pid"] != os.getpid(), "train worker is the driver process")
    check(all(x == x and abs(x) < 1e9 for x in losses), "loss is not finite")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if not args.rehearse:
        check(first["platform"] == "tpu",
              f"train worker's platform is {first['platform']!r}")
        check(first["device_count"] == devices,
              f"train worker sees {first['device_count']} devices, "
              f"wants {devices}")
        check(first["flash_kernel"],
              "no tpu_custom_call in the compiled train step: the flash "
              "attention kernel was not selected")
    wait_gone([first["pid"]], name)
    return {"first": first, "losses": losses}


# -- four chips --------------------------------------------------------------------


def four_actors(args) -> None:
    """Four actors asking {"TPU": 1}: one device each, four distinct chips."""
    import ray_tpu

    @ray_tpu.remote(resources={"TPU": 1})
    class OneChip:
        def look(self):
            import jax
            import jax.numpy as jnp

            x = jnp.ones((1024, 1024), jnp.bfloat16)
            d = jax.devices()
            return {"pid": os.getpid(), "n": len(d), "platform": d[0].platform,
                    "kind": d[0].device_kind,
                    "visible": os.environ.get("TPU_VISIBLE_CHIPS"),
                    "sum": float((x @ x).sum())}

    actors = [OneChip.remote() for _ in range(4)]
    looks = ray_tpu.get([a.look.remote() for a in actors], timeout=300)
    holders = chip_holders()
    log(f"four one-chip actors: {looks}; chip holders {holders}")
    check(len({r["visible"] for r in looks}) == 4,
          "the four actors were not bound to four distinct chips")
    if not args.rehearse:
        check(all(r["n"] == 1 and r["platform"] == "tpu" for r in looks),
              "an actor with a one-chip lease sees other than one TPU device")
        held = [tuple(holders.get(r["pid"], ())) for r in looks]
        check(all(len(h) == 1 for h in held) and len(set(held)) == 4,
              f"the four actors hold {held}: not one distinct chip each")
    for a in actors:
        ray_tpu.kill(a)
    wait_gone([r["pid"] for r in looks], "four one-chip actors")


def four_chip_path(args) -> dict:
    import numpy as np

    four_actors(args)
    probe = _prompt(48, 9, 512 if args.rehearse else 128256)
    one = serve_phase(args, "serve-1of4", tp=1, probe_prompt=probe)
    tp4 = serve_phase(args, "serve-tp4", tp=4, probe_prompt=probe)
    a, b = (np.asarray(s["first_decode_logits"], np.float32) for s in (one, tp4))
    diff = float(np.abs(a - b).max())
    diverge = {k: next((i for i, (x, y) in enumerate(
        zip(one["tokens"][k], tp4["tokens"][k])) if x != y), None)
        for k in sorted(one["tokens"])}
    log(f"TP=4 against one device, same weights and prompts: largest logit "
        f"difference of the first decode step {diff:.3e} over "
        f"{int((a != b).sum())} of {a.size} logits that differ (tolerance "
        f"{TP_LOGIT_TOL}, logit std {float(a.std()):.3f}), argmax equal: "
        f"{int(a.argmax()) == int(b.argmax())}; first divergent greedy token "
        f"of each request (None: all equal): {diverge}")
    check(np.isfinite(a).all() and np.isfinite(b).all(), "logits not finite")
    check(diff <= TP_LOGIT_TOL, f"TP=4 logits differ by {diff}")
    mem = tp4["after"]["memory"]
    tpu = tp4["after"]["utilization"].get("tp") or {}
    log(f"TP=4 bytes per device: in use {[m.get('bytes_in_use') for m in mem]}"
        f", weights {tpu.get('weights_bytes_per_device')}, kv "
        f"{tpu.get('kv_bytes_per_device')}")
    if not args.rehearse:
        share = tpu["weights_bytes_per_device"] + tpu["kv_bytes_per_device"]
        check(len(mem) == 4 and all(
            0.9 * share <= m["bytes_in_use"] <= 1.5 * share for m in mem),
            f"weights and KV are not spread over the four devices: {mem}")
    full = serve_phase(args, "serve-tp4-full", tp=4,
                       depth=2 if args.rehearse else 32, reference=False)
    t1 = train_phase(args, "train-1of4", devices=1, all_chips=False)
    t4 = train_phase(args, "train-fsdp2xtp2", devices=4, all_chips=True,
                     mesh={"fsdp": 2, "tensor": 2})
    dl = [abs(x - y) for x, y in zip(t1["losses"], t4["losses"])]
    rel = [d / x for d, x in zip(dl, t1["losses"])]
    log(f"fsdp=2 x tensor=2 against one chip, same seed: |loss difference| "
        f"per step {[round(d, 5) for d in dl]}, relative "
        f"{[round(r, 4) for r in rel]} (tolerance: {TRAIN_LOSS_TOL_STEP0} at "
        f"step 0, {TRAIN_LOSS_RTOL} relative at every step)")
    check(dl[0] <= TRAIN_LOSS_TOL_STEP0,
          f"mesh loss differs by {dl[0]} before any update")
    check(max(rel) <= TRAIN_LOSS_RTOL, f"mesh loss differs by {max(rel)}")
    return full["report"]


# -- main ----------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="train data/weights seed (serving weights: seed 0)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on whatever backend the workers get; "
                         "never prints the result line, always exits non-zero")
    args = ap.parse_args()

    # no metadata server on a sealed machine; replica start-up (weights +
    # warmup compiles) outlasts the 120 s default actor-creation deadline
    os.environ.setdefault("RAY_TPU_DISABLE_METADATA_SERVER", "1")
    os.environ.setdefault("RAY_TPU_actor_creation_timeout_s", "1100")
    os.environ.setdefault("RAY_TPU_WORKER_QUIET", "1")
    if args.rehearse:
        # pretend chips so the lease/carving path is the real one
        os.environ.setdefault("RAY_TPU_NUM_CHIPS", str(args.chips))
        if args.chips > 1:
            os.environ.setdefault(
                "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.chips}")

    import ray_tpu
    from ray_tpu._private import compile_cache
    from ray_tpu._private.accelerators import get_accelerator_manager

    outside = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    cache_dir = compile_cache.configure()  # workers inherit the variable
    found = get_accelerator_manager("TPU").get_current_node_num_accelerators()
    log(f"chip device files {chip_files()} -> {found} chip(s); compile cache "
        f"at {cache_dir} "
        f"({'set from outside' if outside else 'the checkout default'})")
    check(found >= args.chips,
          f"this node has {found} TPU chip(s), the run needs {args.chips}")

    ray_tpu.init()
    try:
        return run(args, found)
    finally:
        ray_tpu.shutdown()  # a failed phase leaves no process behind either


def run(args, found: int) -> int:
    import ray_tpu
    from ray_tpu import _native

    raylet_tpu = ray_tpu.cluster_resources().get("TPU", 0)
    for name in ("plasma_store", "sched_policy", "stack_dump"):
        _native.load(name)
    log(f"raylet advertises TPU={raylet_tpu}; native components in the "
        f"driver/raylet process: {_native.status()}")
    check(raylet_tpu == found, f"raylet advertises {raylet_tpu} chips, "
          f"{found} device files are present")

    if args.chips == 1:
        serve = serve_phase(args, "serve")
        train = train_phase(args, "train", devices=1, all_chips=True)
        rep, count = serve["report"], 1
        check(train["first"]["device_kind"] == rep["device_kind"],
              "phases ran on different devices")
    else:
        rep, count = four_chip_path(args), 4

    ray_tpu.shutdown()
    import jax
    from jax._src import xla_bridge

    check(not xla_bridge.backends_are_initialized(),
          "the driver process initialised a JAX backend")
    log(f"driver never initialised a JAX backend (jax {jax.__version__}); "
        "every phase passed")
    if args.rehearse:
        print("[chip_smoke] rehearsal complete: no result line", flush=True)
        return 3
    check(rep["platform"] == "tpu" and rep["device_count"] == count,
          f"workers saw {rep['device_count']} x {rep['platform']}")
    print(json.dumps({"ok": True, "device": {
        "platform": rep["platform"], "kind": rep["device_kind"],
        "count": rep["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
