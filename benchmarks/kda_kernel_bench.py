"""The KDA decode kernel and the chunked (WY) form alone, at Kimi-Linear's
shapes.

``ops/kda_state_update.py`` on the engine's leaf (20 KDA layers x 64 slots x
[32, 128, 128] float32, 2.7 GB) with 8, 16, 32 and 64 of the 64 rows
decoding: ms a layer-call, the share of the HBM peak its bytes (a live row's
2 MB state read and written) are moved at, and its largest gap to the
``jax.numpy`` form from the same inputs.  One program a reading: a loop over
the 20 layers, ``--reps`` times, each call taking the state the last one
returned, so nothing overlaps and nothing is elided.  Beside it the
``jax.numpy`` form at 16 live rows, which moves every slot's state whoever
decodes.

A second reading, ``KDA_CHUNKED``: ``kimi_linear.kda_chunked`` over a prompt
chunk of 256 positions at 32 heads of 128 x 128 (steps of 16, 32 and 64
positions): ms a layer-call, and the gap of its outputs and of its state to
the token-by-token recurrence from the same inputs, as shares of their
largest value.

    python benchmarks/kda_kernel_bench.py [--reps 8] [--slots 64]

Prints ``KDA_KERNEL {json}`` and ``KDA_CHUNKED {json}`` a reading.  A time
comes only from a chip: on another backend it exits 2 (``--rehearse`` walks
it at toy size in interpret mode and exits 3).
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
    from jax import lax

    from ray_tpu.models import kimi_linear as kl
    from ray_tpu.ops import kda_state_update as ops

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"no TPU here ({platform}): no time is a device's", flush=True)
        return 2
    interpret = platform != "tpu"
    layers, slots, heads, d = ((2, 4, 8, 128) if args.rehearse
                               else (20, args.slots, 32, 128))
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    state0 = jax.random.normal(next(ks), (layers, slots, heads, d, d), f32)
    q, k, v = (jax.random.normal(next(ks), (slots, heads, d)).astype(bf16)
               for _ in range(3))
    g = -jax.random.uniform(next(ks), (slots, heads, d), f32)
    beta = jax.nn.sigmoid(jax.random.normal(next(ks), (slots, heads)))

    def loop(form, reps):
        def body(state, active):
            def layer(li, carry):
                state, acc = carry
                o, state = form(state, li, q, k, v, g, beta, active)
                return state, acc + o

            def rep(_, carry):
                return lax.fori_loop(0, layers, layer, carry)

            return lax.fori_loop(0, reps, rep, (state, jnp.zeros(
                (slots, heads, d), f32)))

        return jax.jit(body, donate_argnums=0)

    kernel = lambda *a: ops.kda_state_update(*a, interpret=interpret)  # noqa: E731
    for live in ((2, 4) if args.rehearse else (8, 16, 32, slots)):
        active = jnp.asarray(np.arange(slots) % (slots // live) == 0,
                             jnp.int32)
        # the two forms from the same state, one layer-call each
        o0, s0 = jax.jit(ops.kda_state_update_jnp)(
            state0, 1, q, k, v, g, beta, active)
        o1, s1 = jax.jit(kernel)(state0 + 0, 1, q, k, v, g, beta, active)
        gap_o = float(jnp.abs(o1 - o0).max() / jnp.abs(o0).max())
        gap_s = float(jnp.abs(s1 - s0).max() / jnp.abs(s0).max())
        row = {"live_rows": live, "slots": slots, "o_gap": gap_o,
               "state_gap": gap_s}
        for name, form in (("kernel", kernel),
                           ("jnp", ops.kda_state_update_jnp)):
            if name == "jnp" and live != (2 if args.rehearse else 16):
                continue
            run = loop(form, args.reps)
            state, acc = run(state0 + 0, active)   # compile, warm
            jax.block_until_ready(acc)
            t0 = time.perf_counter()
            state, acc = run(state, active)
            jax.block_until_ready(acc)
            ms = (time.perf_counter() - t0) * 1e3 / (args.reps * layers)
            moved = live * 2 * heads * d * d * 4
            row[name + "_ms_a_layer_call"] = ms
            row[name + "_hbm_share_pct"] = 100.0 * moved / (
                ms * 1e-3 * HBM_BYTES_PER_S)
            del state, acc
        print("KDA_KERNEL " + json.dumps(row), flush=True)

    # -- the chunked form against the recurrence -------------------------------
    c = 32 if args.rehearse else 256
    qc = ops.l2_normalize(jax.random.normal(next(ks), (c, heads, d))) * d ** -0.5
    kc = ops.l2_normalize(jax.random.normal(next(ks), (c, heads, d)))
    vc = jax.random.normal(next(ks), (c, heads, d))
    gc = -jax.random.uniform(next(ks), (c, heads, d)) * 0.5
    bc = jax.nn.sigmoid(jax.random.normal(next(ks), (c, heads)))
    s_in = state0[0, 0]

    def recurrence(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum(
            "hkv,hk->hv", s, k_t, precision="highest"))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    want_s, want_o = jax.jit(lambda s: lax.scan(
        recurrence, s, (qc, kc, vc, gc, bc)))(s_in)
    for step in ((8, 16) if args.rehearse else (16, 32, 64)):
        run = jax.jit(lambda s, step=step: kl.kda_chunked(
            qc, kc, vc, gc, bc, s, step))
        o, s = run(s_in)
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            o, s = run(s_in)
        jax.block_until_ready(o)
        print("KDA_CHUNKED " + json.dumps({
            "positions": c, "step": step,
            "ms_a_layer_call": (time.perf_counter() - t0) * 1e3 / args.reps,
            "o_gap": float(jnp.abs(o - want_o).max() / jnp.abs(want_o).max()),
            "state_gap": float(jnp.abs(s - want_s).max()
                               / jnp.abs(want_s).max())}), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
