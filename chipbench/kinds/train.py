"""Kind ``train``: training steps through ``JaxTrainer`` until the window is
spent.

The harness is the driver; the one train worker holds the chip and runs
``train_func`` below: ``parallel.make_train_step`` on the configuration at
its file's depth, adamw as the file states, one seeded batch made on the
device, ``train.report`` every step as users do.  The worker times every
step on its own clock (a step ends when its loss has been read on the host)
and sends the timings with its last report.

``correct``: the worker ran on a TPU, the compiled step holds the flash
kernel (a ``tpu_custom_call``), every loss is finite, and the step-0 loss
(the forward of the seed's weights, before any update) is within
``LOSS_TOL`` of the benchmark's float32 reference on ``REF_SEQUENCES``
seeded sequences of the same batch.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time

from chipbench import spec
from chipbench.spec import BenchError, log

# |bf16 step-0 loss of a sequence - float32 reference loss of the same
# sequence and weights|.  With random weights the loss is near ln(vocab) =
# 10.4 and a bf16 forward rounds logits by about 0.4%: per-token errors of
# about 0.03 average out over 2,047 positions, so the mean moves by a few
# thousandths at most.  chip_smoke.py (PR 21) saw 1.1e-4 between two bf16
# layouts.  A forward in a lower precision than bf16, or a wrong mask or
# rope, moves the loss by far more than this.
LOSS_TOL = 0.01
REF_SEQUENCES = 2


def train_func(config):
    """Runs in the train worker: the only process on the chip."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig, loss_fn
    from ray_tpu.parallel import make_train_step

    c, t = config["config"], config["trainer"]
    devices = jax.devices()
    if config["rehearse"]:
        cfg = LlamaConfig.tiny(n_heads=4, n_kv_heads=2)
        batch, seq = 4, 128
        c = dict(c, hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
                 num_attention_heads=cfg.n_heads,
                 num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                 intermediate_size=cfg.ffn_dim, vocab_size=cfg.vocab_size,
                 rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
                 tie_word_embeddings=cfg.tie_embeddings)
    else:
        cfg = LlamaConfig(
            vocab_size=c["vocab_size"], dim=c["hidden_size"],
            n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], ffn_dim=c["intermediate_size"],
            max_seq_len=t["seq_len"], rope_theta=c["rope_theta"],
            rms_norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], param_dtype=jnp.bfloat16)
        batch, seq = t["batch"], t["seq_len"]
    optimizer = optax.adamw(t["learning_rate"], b1=t["b1"], b2=t["b2"],
                            weight_decay=t["weight_decay"],
                            mu_dtype=jnp.bfloat16)
    init_fn, step_fn = make_train_step(cfg, None, optimizer=optimizer)
    # weights and data from the seed, made on the device
    state = init_fn(jax.random.PRNGKey(config["seed"] % (2 ** 31)))
    tokens = jax.random.randint(
        jax.random.PRNGKey((config["seed"] + 1) % (2 ** 31)), (batch, seq), 0,
        cfg.vocab_size)
    t0 = time.monotonic()
    compiled = step_fn.lower(state, tokens).compile()
    compile_s = time.monotonic() - t0
    flash = "tpu_custom_call" in compiled.as_text()

    # correctness, part one, before any update: the program's bf16 loss of
    # each sampled sequence (the float32 reference of the same weights is
    # taken after the window, on weights made again from the seed)
    params = state.params
    bf16_loss = jax.jit(lambda p, x: loss_fn(cfg, p, x))
    sampled = range(min(config["ref_sequences"], batch))
    got = [float(bf16_loss(params, tokens[i:i + 1])) for i in sampled]
    del params

    losses, steps = [], []

    def one():
        nonlocal state
        a = time.monotonic()
        state, metrics = compiled(state, tokens)
        loss = float(metrics["loss"])  # the device has finished the step
        b = time.monotonic()
        losses.append(loss)
        train.report({"step": len(losses) - 1, "loss": loss})
        return a, b

    for _ in range(config["warmup_steps"]):
        one()
    window_t0 = time.monotonic()
    traced, trace_dir = 0, None
    deadline = window_t0 + config["seconds"]
    trace_from = config["trace_after_steps"] if config["trace"] else None
    tracing = False
    while time.monotonic() < deadline:
        n = len(steps)
        if trace_from is not None and n == trace_from and not tracing:
            trace_dir = os.path.join(config["workdir"], "trace")
            jax.profiler.start_trace(trace_dir)
            tracing = True
        a, b = one()
        steps.append({"start": a - window_t0, "end": b - window_t0})
        if tracing and len(steps) == trace_from + config["trace_steps"]:
            jax.profiler.stop_trace()
            tracing, traced, trace_from = False, config["trace_steps"], None
    if tracing:
        jax.profiler.stop_trace()
        traced = len(steps) - trace_from
    stats = [d.memory_stats() or {} for d in devices]
    # correctness, part two, outside the window: the trained state goes, the
    # seed's weights are made again, and the benchmark's own float32
    # reference gives its loss on the same sequences
    from chipbench import reference

    del state
    fresh = init_fn(jax.random.PRNGKey(config["seed"] % (2 ** 31))).params
    checks = [{"sequence": i, "loss": got[i],
               "reference": reference.sequence_loss(c, fresh, tokens[i])}
              for i in sampled]
    train.report({
        "final": True, "pid": os.getpid(), "platform": devices[0].platform,
        "device_kind": devices[0].device_kind, "device_count": len(devices),
        "window_t0": window_t0, "steps": steps, "losses": losses,
        "compile_s": compile_s, "flash_kernel": flash, "checks": checks,
        "params": cfg.num_params, "traced_steps": traced,
        "trace_dir": trace_dir, "batch": batch, "seq_len": seq,
        "peak_bytes": [s.get("peak_bytes_in_use", 0) for s in stats],
        "bytes_limit": [s.get("bytes_limit", 0) for s in stats],
        "compile_cache_dir": jax.config.jax_compilation_cache_dir})


def run(cell, args) -> dict:
    from chipbench import serving, trace_reduce

    serving.start_cluster(cell.chips, args.rehearse)
    try:
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

        traffic = cell.traffic
        workdir = tempfile.mkdtemp(prefix="chipbench_")
        files_before = serving.cache_files()
        loop_config = {
            "config": cell.config, "trainer": cell.config["trainer"],
            "seed": args.seed, "seconds": float(args.seconds),
            "rehearse": args.rehearse, "trace": bool(args.trace),
            "warmup_steps": int(traffic.get("warmup_steps", 2)),
            "trace_after_steps": 2, "trace_steps": int(traffic.get("trace_steps", 3)),
            "ref_sequences": REF_SEQUENCES, "workdir": workdir}
        scaling = ScalingConfig(num_workers=1, use_tpu=True)
        log(f"JaxTrainer.fit, worker resources {scaling.worker_resources()}")
        result = JaxTrainer(
            train_func, train_loop_config=loop_config, scaling_config=scaling,
            run_config=RunConfig(name="chipbench", storage_path=workdir)).fit()
        if result.error is not None:
            raise BenchError(f"training failed: {result.error}")
        rows = result.metrics_history
        final = rows[-1]
        if not final.get("final"):
            raise BenchError("the worker's last report did not reach the driver")
        serving.wait_gone([final["pid"]], "train worker")
        # CLOCK_MONOTONIC is one clock for this machine's processes
        setup_s = final["window_t0"] - spec.T0
        log(f"worker pid {final['pid']} on {final['device_count']} x "
            f"{final['device_kind']} ({final['platform']}); "
            f"{final['params'] / 1e9:.3f} B params; compiled in "
            f"{final['compile_s']:.1f}s; flash kernel: {final['flash_kernel']}; "
            f"set-up {setup_s:.1f}s; {len(final['steps'])} window steps; "
            f"peak bytes {final['peak_bytes']} of {final['bytes_limit']}")
        log(f"losses {[round(x, 4) for x in final['losses'][:4]]} ... "
            f"{[round(x, 4) for x in final['losses'][-2:]]}; reference "
            f"checks {final['checks']} (tolerance {LOSS_TOL})")
        log(f"compile cache gained {serving.cache_files() - files_before} "
            "file(s) during the run (the first run of a checkout compiles)")
        trace = None
        if args.trace and final["trace_dir"]:
            files = [os.path.join(dp, f) for dp, _, fs in
                     os.walk(final["trace_dir"]) for f in fs
                     if f.endswith(".xplane.pb")]
            if files:
                log(f"trace: {files[0]} ({os.path.getsize(files[0])} bytes)")
                trace = {"path": files[0],
                         "planes": trace_reduce.load(files[0])}
        shutil.rmtree(workdir, ignore_errors=True)  # run storage, trace file
        if final["pid"] == os.getpid():
            raise BenchError("the train worker is the harness process")
        report = {"platform": final["platform"],
                  "device_kind": final["device_kind"],
                  "device_count": final["device_count"],
                  "memory": [{"peak_bytes_in_use": p}
                             for p in final["peak_bytes"]]}
        return {"kind": cell.kind, "traffic": traffic, "config": cell.config,
                "seconds": float(args.seconds), "setup_s": setup_s,
                "window_steps": final["steps"], "losses": final["losses"],
                "tokens_per_step": final["batch"] * final["seq_len"],
                "batch": final["batch"], "seq_len": final["seq_len"],
                "compile_s": final["compile_s"], "checks": final["checks"],
                "flash_kernel": final["flash_kernel"],
                "reports_received": len(rows), "trace": trace,
                "traced_steps": final["traced_steps"], "report": report}
    finally:
        serving.stop_cluster()


def correct(evidence: dict, rehearse: bool):
    losses, why = evidence["losses"], []
    if not rehearse:
        if evidence["report"]["platform"] != "tpu":
            why.append(f"platform {evidence['report']['platform']}")
        if not evidence["flash_kernel"]:
            why.append("no tpu_custom_call in the compiled step: the flash "
                       "attention kernel was not selected")
    bad = [x for x in losses if not (x == x and abs(x) < 1e9)]
    if bad:
        why.append(f"{len(bad)} loss(es) not finite")
    if evidence["reports_received"] != len(losses) + 1:
        why.append(f"{evidence['reports_received']} reports reached the "
                   f"driver for {len(losses)} steps")
    for c in evidence["checks"]:
        if not abs(c["loss"] - c["reference"]) <= LOSS_TOL:
            why.append(f"sequence {c['sequence']}: loss {c['loss']} against "
                       f"the float32 reference's {c['reference']}")
    steps = len(evidence["window_steps"])
    return not why, steps, len(bad), "; ".join(why)


def compared(evidence: dict) -> list:
    """``[name, number, limit]`` of what ``correct`` holds to a limit."""
    return [[f"loss_gap_sequence_{c['sequence']}",
             abs(c["loss"] - c["reference"]), LOSS_TOL]
            for c in evidence["checks"]]


def device(evidence: dict) -> dict:
    from chipbench import serving

    return serving.device_block(evidence)
