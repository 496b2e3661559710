"""The sampler alone, at the serving cells' vocabularies.

``llm/engine.py _sample`` over ``[64, V]`` logits for the four cells' widths
(pangu 19,200, Mistral 32,768, granite 100,352 in float32, kimi 163,840), for
four batches: every row greedy; every row with a temperature and no top-k; one
row with a temperature and ``top_k=50`` among greedy ones; every row with
both.  Beside it the formula it replaced (PR 48), which ran the top-64 over
the vocabulary and the categorical draw every token-step whatever the rows
asked: kept here as it stood, and held to the gated one token for token from
the same keys.

One program a reading, shaped like a decode chunk: ``--steps`` token-steps in
a scan, each one an embedding of the last tokens, a toy head of depth
``--depth`` whose product IS the logits (so nothing is loop-invariant and the
sampler reads a product's output, as it does in the engine), the sampler, and
the key split outside it.  ``floor`` is the same loop with an argmax alone.
The median of ``--reps`` runs, in ms a token-step.

    python benchmarks/sampler_bench.py [--steps 64] [--reps 7]

Prints ``SAMPLER_BENCH {json}`` a reading.  A time comes only from a chip: on
another backend it exits 2 (``--rehearse`` walks it at toy size and exits 3).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# (cell, vocabulary, the head's output precision)
WIDTHS = (("pangu-ep16", 19200, "bfloat16"), ("m7b-d16", 32768, "bfloat16"),
          ("granite-h-micro", 100352, "float32"),
          ("kimi-linear-ep16", 163840, "bfloat16"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--depth", type=int, default=256)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.engine import _MAX_TOP_K, _sample

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({platform}): no time is a device's", flush=True)
        return 2
    b = 8 if args.rehearse else 64
    widths = (("toy", 512, "float32"),) if args.rehearse else WIDTHS
    steps = 4 if args.rehearse else args.steps

    def ungated(logits, key, temps, top_ks):
        """``_sample`` as it stood before PR 48."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.where(temps > 0.0, temps, 1.0)[:, None]
        kmax = min(_MAX_TOP_K, logits.shape[-1])
        topv, _ = jax.lax.top_k(scaled, kmax)
        idx = jnp.clip(top_ks - 1, 0, kmax - 1)
        kth = jnp.take_along_axis(topv, idx[:, None], axis=-1)
        masked = jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30,
                           scaled)
        sampled = jax.random.categorical(key, masked, axis=-1)
        return jnp.where(temps <= 0.0, greedy, sampled.astype(jnp.int32))

    def floor(logits, key, temps, top_ks):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def program(sampler, dtype):
        def run(embed, head, ids, key, temps, top_ks):
            def one(carry, _):
                ids, key = carry
                logits = jnp.dot(embed[ids], head,
                                 preferred_element_type=dtype)
                key, sub = jax.random.split(key)
                ids = sampler(logits, sub, temps, top_ks)
                return (ids, key), ids

            return jax.lax.scan(one, (ids, key), None, length=steps)[1]

        return jax.jit(run)

    one_row = np.zeros(b, np.float32)
    one_row[b // 2] = 1.0
    batches = (  # name, temperatures, top-ks
        ("all_greedy", np.zeros(b, np.float32), np.zeros(b, np.int32)),
        ("temperature_no_top_k", np.ones(b, np.float32),
         np.zeros(b, np.int32)),
        ("one_top_k_row", one_row, (one_row * 50).astype(np.int32)),
        ("all_top_k", np.ones(b, np.float32), np.full(b, 50, np.int32)))
    forms = (("floor", floor), ("parent", ungated), ("gated", _sample))

    for cell, v, dtype in widths:
        dtype = jnp.dtype(dtype)
        ke, kh = jax.random.split(jax.random.PRNGKey(v))
        embed = jax.random.normal(ke, (v, args.depth), jnp.bfloat16)
        head = (jax.random.normal(kh, (args.depth, v), jnp.float32)
                * 0.3).astype(jnp.bfloat16)
        ids0 = jnp.arange(b, dtype=jnp.int32)
        key = jax.random.PRNGKey(7)
        progs = {name: program(fn, dtype) for name, fn in forms}
        for batch, temps, top_ks in batches:
            operands = (embed, head, ids0, key, jnp.asarray(temps),
                        jnp.asarray(top_ks))
            ms, tokens = {}, {}
            for name, prog in progs.items():
                tokens[name] = np.asarray(prog(*operands))  # compiles
                times = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(prog(*operands))
                    times.append(time.perf_counter() - t0)
                ms[name] = statistics.median(times) / steps * 1e3
            row = {"cell": cell, "vocab": v, "logits": dtype.name,
                   "rows": b, "batch": batch, "steps": steps,
                   "same_tokens": bool(
                       (tokens["parent"] == tokens["gated"]).all())}
            if not args.rehearse:  # a CPU's time is no device's
                row.update(
                    floor_ms=ms["floor"], parent_ms=ms["parent"],
                    gated_ms=ms["gated"],
                    parent_sampler_ms=ms["parent"] - ms["floor"],
                    gated_sampler_ms=ms["gated"] - ms["floor"],
                    gated_over_parent_pct=100.0 * (ms["gated"] / ms["parent"]
                                                   - 1.0))
            print("SAMPLER_BENCH " + json.dumps(row), flush=True)
            if not row["same_tokens"]:
                print("the gated sampler's tokens are not the parent's",
                      flush=True)
                return 1
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
