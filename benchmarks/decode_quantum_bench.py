"""The decode quantum's cost curve: what one decode dispatch costs beyond its
token-steps, on the engine alone (no server, no proxy), at the shapes of the
benchmark's serving cell (`m7b-d16.chat_steady`: Mistral-7B widths, 16
layers, batch 64, a pool of 6,000 blocks).

One engine, stepped by hand; ``LLMConfig.decode_chunk`` is set between
passes (the decode program takes the step count as a static argument, so
every quantum is its own compiled program over the same weights and pool).
Per quantum and per number of decoding rows it prints

- ``ms_token_step``: host-clock ms a token-step in steady pipelined decode
  (the rows hold 256-token prompts and decode 208 token-steps, so every
  dispatch uses the 32-block table);
- ``host_ms_step``: the engine's own ``host_s`` a step over that stretch
  (a step's wall time less its blocked device reads);
- ``join1_ms`` / ``join2_ms``: a request of 256 (one chunk) or 512 (two)
  prompt tokens added at a step boundary while the rows decode: ms until
  the step that returns its first token has returned, and the steps taken.

Every pass runs twice and the second is printed, so no compile is timed
(``compiles`` over the printed pass must read 0).  The prefix cache is off:
the passes would fill it with their prompts, and its evictions (a device
read each) are not what a dispatch costs.

``--profile`` adds one profiler capture of 24 steady steps a (quantum, rows)
pair: the decode program's device ms a run, the idle ms between two runs,
and its operations by self time a run with their events a run (an operation
that runs once a dispatch, and not once a token-step, is the fixed cost).

    python benchmarks/decode_quantum_bench.py [--quanta 8,4,2,1] [--rows 5,64]

Needs a TPU.  ``--rehearse`` walks the same control flow at toy size on the
CPU, prints no time and exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

PROMPT, STEADY, WARM = 256, 208, 16  # tokens, token-steps, token-steps


def build_engine(rehearse: bool):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.paged import PagedJaxLLMEngine
    from ray_tpu.models import llama

    if rehearse:
        mcfg = llama.LlamaConfig.tiny(max_seq_len=1024)
        cfg = LLMConfig(model_config=mcfg, max_batch_size=8, max_seq_len=1024,
                        block_size=16, num_blocks=600,
                        enable_prefix_caching=False)
    else:
        mcfg = llama.LlamaConfig(
            vocab_size=32768, dim=4096, n_layers=16, n_heads=32, n_kv_heads=8,
            ffn_dim=14336, max_seq_len=4096, rope_theta=1e6,
            param_dtype=jnp.bfloat16)
        cfg = LLMConfig(model_config=mcfg, max_batch_size=64, max_seq_len=4096,
                        block_size=16, num_blocks=6000,
                        enable_prefix_caching=False)
    # made on the device by one program: eager jax.random calls take minutes
    params = jax.jit(lambda: llama.init_params(mcfg, jax.random.PRNGKey(0)))()
    return PagedJaxLLMEngine(cfg, params=params)


def profile_steps(eng, steps: int) -> dict:
    """One capture of ``steps`` steady steps, reduced to the decode program's
    runs, the gaps between them, and its operations a run."""
    import glob
    import tempfile

    import jax

    from chipbench import trace_reduce as tr

    logdir = tempfile.mkdtemp(prefix="quantum_trace_")
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            eng.step()
    eng.flush()
    planes = tr.load(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))[0])
    plane = tr.first_device(planes)
    if plane is None:
        return {"error": "the capture holds no device plane"}
    runs = sorted((s, d) for n, s, d, _ in tr._line(plane, tr.MODULES_LINE)
                  if n.startswith("jit__decode_chunk_impl"))
    gaps = [b[0] - (a[0] + a[1]) for a, b in zip(runs, runs[1:])]
    events = tr.op_events(plane)
    by_name: dict = {}
    for (name, own, text), (_, start, _, _) in zip(tr.self_times(events),
                                                   events):
        if not any(s <= start < s + d for s, d in runs):
            continue
        key = name + (" [tpu_custom_call]" if "tpu_custom_call" in text else "")
        sec, n = by_name.get(key, (0.0, 0))
        by_name[key] = (sec + own, n + 1)
    n = max(1, len(runs))
    ops = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:18]
    return {"runs": len(runs),
            "run_ms": round(statistics.median(d for _, d in runs) * 1e3, 4),
            "gap_ms_median": round(statistics.median(gaps) * 1e3, 4),
            "gap_ms_max": round(max(gaps) * 1e3, 4),
            "ops_ms_a_run": [[k, round(v[0] * 1e3 / n, 4), round(v[1] / n, 2)]
                             for k, v in ops]}


def one_pass(eng, quantum: int, rows: int, rng, timed: bool,
             profile: bool = False) -> dict:
    """``rows`` requests decode; then (or, with ``profile``, a capture
    instead) four requests join, one after the other, of one and of two
    prompt chunks by turns.  Returns the pass's readings (``{}`` unless
    ``timed``: the pass that only compiles)."""
    from ray_tpu.llm import GenerationConfig

    eng.config.decode_chunk = quantum
    vocab = eng.cfg.vocab_size
    gen = GenerationConfig(max_new_tokens=eng.max_seq - 2 * PROMPT - 8)

    def prompt(n):
        return rng.integers(1, vocab, n).tolist()

    def join(n_prompt, limit=64):
        rid = eng.add_request(prompt(n_prompt), gen)
        t0 = time.perf_counter()
        for n in range(1, limit + 1):
            if rid in eng.step():
                return (time.perf_counter() - t0) * 1e3, n, rid
        raise RuntimeError(f"no first token in {limit} steps")

    held = [eng.add_request(prompt(PROMPT), gen) for _ in range(rows)]
    got = set()
    while len(got) < len(held):  # every row has its first token
        got.update(r for r in eng.step() if r in held)
    for _ in range(-(-WARM // quantum)):
        eng.step()
    c0 = eng.counters()
    n_steps = -(-STEADY // quantum)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        eng.step()
    steady_s = time.perf_counter() - t0
    c1 = eng.counters()
    if profile:
        prof = profile_steps(eng, 24)
        for rid in held:
            eng.cancel_request(rid)
        eng.flush()
        return {"quantum": quantum, "rows": rows, **prof}
    if len(held) == eng.max_batch:  # a joining request needs a slot
        eng.cancel_request(held.pop())
        eng.step()
    joins = []
    for n_prompt in (PROMPT, 2 * PROMPT, PROMPT, 2 * PROMPT):
        ms, n, rid = join(n_prompt)
        joins.append((n_prompt, ms, n))
        eng.cancel_request(rid)
        for _ in range(2):  # back to steady pipelined decode
            eng.step()
    c2 = eng.counters()
    for rid in held:
        eng.cancel_request(rid)
    eng.flush()
    if not timed:
        return {}
    d = {k: c1[k] - c0[k] for k in ("steps", "host_s", "decode_token_steps",
                                    "decode_dispatches_pipelined")}

    def of(n_prompt, i):
        return statistics.mean(j[i] for j in joins if j[0] == n_prompt)

    return {
        "quantum": quantum, "rows": rows,
        "ms_token_step": round(steady_s * 1e3 / d["decode_token_steps"], 4),
        "ms_step": round(steady_s * 1e3 / d["steps"], 3),
        "host_ms_step": round(d["host_s"] * 1e3 / d["steps"], 3),
        "pipelined": d["decode_dispatches_pipelined"], "steps": d["steps"],
        "join1_ms": round(of(PROMPT, 1), 2), "join1_steps": of(PROMPT, 2),
        "join2_ms": round(of(2 * PROMPT, 1), 2),
        "join2_steps": of(2 * PROMPT, 2),
        "compiles": c2["compiles"] - c0["compiles"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quanta", default="8,4,2,1")
    ap.add_argument("--rows", default="5,64")
    ap.add_argument("--repeats", type=int, default=2,
                    help="timed passes a (quantum, rows) pair")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("decode_quantum_bench needs a TPU", file=sys.stderr)
        return 2
    global PROMPT, STEADY, WARM
    if args.rehearse:
        PROMPT, STEADY, WARM = 32, 16, 4
    t0 = time.monotonic()
    eng = build_engine(args.rehearse)
    print(f"engine up in {time.monotonic() - t0:.1f}s on {dev.device_kind}",
          file=sys.stderr)
    rng = np.random.default_rng(0)
    for rows in (int(r) for r in args.rows.split(",")):
        rows = min(rows, eng.max_batch)
        for quantum in (int(q) for q in args.quanta.split(",")):
            one_pass(eng, quantum, rows, rng, timed=False)  # compiles
            for _ in range(1 if args.rehearse else args.repeats):
                row = one_pass(eng, quantum, rows, rng, timed=True)
                if args.rehearse:
                    row = {k: row[k] for k in ("quantum", "rows", "steps",
                                               "join1_steps", "join2_steps")}
                print(json.dumps(row), flush=True)
            if args.profile:
                print(json.dumps(one_pass(eng, quantum, rows, rng, timed=True,
                                          profile=True)), flush=True)
    if args.rehearse:
        print("rehearsal only: no time printed", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
