"""The state-space decode kernel alone, at granite-4.0-h-micro's shapes.

``ops/ssm_state_update.py`` on the engine's leaf (36 Mamba layers x 64 slots
x [32, 128, 128] float32, 4.8 GB) with 8, 20, 40 and 64 of the 64 rows
decoding: ms a layer-call and the share of the HBM peak its bytes (a live
row's 2 MB state read and written) are moved at.  One program a reading: a
loop over the 36 layers, ``--reps`` times, each call taking the state the
last one returned, so nothing overlaps and nothing is elided.  Beside it the
``jax.numpy`` form at 20 live rows, which moves every slot's state whoever
decodes.

    python benchmarks/ssm_kernel_bench.py [--reps 8] [--slots 64]

Prints ``SSM_KERNEL {json}`` a reading.  A time comes only from a chip: on
another backend it exits 2 (``--rehearse`` walks it at toy size in interpret
mode and exits 3).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM_BYTES_PER_S = 819e9  # chipbench/peaks.json, TPU v5 lite


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import ssm_state_update as ops

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({platform}): a kernel's time comes only from "
              "the chip")
        return 2
    layers, slots, heads, p, n = 36, args.slots, 64, 64, 128
    if args.rehearse:
        layers, slots, heads, p, n, args.reps = 2, 8, 4, 32, 16, 1
    shape = ops.state_shape(layers, slots, heads, p, n)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    decay = jax.random.uniform(ks[0], (slots, heads * p), minval=0.5)
    xdt = jax.random.normal(ks[1], (slots, heads * p)) * 0.1
    b = jax.random.normal(ks[2], (slots, n))
    c = jax.random.normal(ks[3], (slots, n))
    row_bytes = 2 * 4 * heads * p * n

    def program(step):
        def run(state, active):
            def rep(_, carry):
                def layer(li, carry):
                    state, acc = carry
                    y, state = step(state, li, decay, xdt, b, c, active)
                    return state, acc + y[:, :8].sum()
                return jax.lax.fori_loop(0, layers, layer, carry)
            return jax.lax.fori_loop(0, args.reps, rep,
                                     (state, jnp.float32(0)))
        return jax.jit(run, donate_argnums=0)

    kernel = program(lambda *a: ops.ssm_state_update(
        *a, interpret=args.rehearse))
    plain = program(ops.ssm_state_update_jnp)
    state = jnp.zeros(shape, jnp.float32)
    readings = [("kernel", kernel, live) for live in
                sorted({slots // 8, slots * 5 // 16, slots * 5 // 8, slots})]
    readings.append(("jnp", plain, slots * 5 // 16))
    for name, fn, live in readings:
        rng = np.random.default_rng(live)
        active = np.zeros(slots, np.int32)
        active[rng.choice(slots, live, replace=False)] = 1
        active = jnp.asarray(active)
        state, acc = fn(state, active)          # compile, warm
        jax.block_until_ready(acc)
        t0 = time.perf_counter()
        state, acc = fn(state, active)
        jax.block_until_ready(acc)
        call_s = (time.perf_counter() - t0) / (layers * args.reps)
        print("SSM_KERNEL " + json.dumps({
            "form": name, "live_rows": live, "slots": slots,
            "ms_per_layer_call": call_s * 1e3,
            "us_per_live_row": call_s * 1e6 / live,
            "hbm_peak_pct": 100 * live * row_bytes / call_s / HBM_BYTES_PER_S,
            "finite": bool(np.isfinite(float(acc))),
            "platform": platform}), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
