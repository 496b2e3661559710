"""The state-space decode kernels alone, at granite-4.0-h-micro's shapes.

``ops/ssm_state_update.py`` on the engine's leaf (36 Mamba layers x 64 slots
x [32, 128, 128] float32, 4.8 GB) with 8, 20, 40 and 64 of the 64 rows
decoding: ms a layer-call and the share of the HBM peak its bytes (a live
row's 2 MB state read and written) are moved at.  One program a reading: a
loop over the 36 layers, ``--reps`` times, each call taking the state the
last one returned, so nothing overlaps and nothing is elided.  Beside it the
``jax.numpy`` form at 20 live rows, which moves every slot's state whoever
decodes.

A second reading, ``SSM_LAYER_STEP``: a whole layer-step's work between the
in-projection's output and the out-projection's input at the same live
rows, as the model ran it before the fused call (``unfused``: convolution,
``silu``, ``delta``, ``decay``, the window's write-back and the gated norm in
``jax.numpy`` over every slot, round the state-update kernel) and as it runs
it now (``fused``: ``ssm_layer_step``, one call); with the fused form's
largest gap to the unfused one from the same inputs, as a share of the
largest value.

    python benchmarks/ssm_kernel_bench.py [--reps 8] [--slots 64]

Prints ``SSM_KERNEL {json}`` and ``SSM_LAYER_STEP {json}`` a reading.  A time comes only from a chip: on
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


def layer_step_readings(ops, jax, jnp, np, args, dims, platform):
    """The ``SSM_LAYER_STEP`` readings (the module's docstring)."""
    layers, slots, heads, p, n = dims
    i, k = heads * p, 4
    cw = i + 2 * n
    bf16, f32 = jnp.bfloat16, jnp.float32
    ks = iter(jax.random.split(jax.random.PRNGKey(1), 12))

    def rnd(*shape, scale=1.0):
        return (jax.random.normal(next(ks), shape) * scale).astype(bf16)

    mp = {"conv_w": rnd(layers, k, cw, scale=0.5), "conv_b": rnd(layers, cw),
          "dt_bias": rnd(layers, heads) - 3, "norm": 1 + rnd(layers, i) / 8,
          "a_log": jnp.log(jax.random.uniform(
              next(ks), (layers, heads), minval=1.0, maxval=16.0)).astype(bf16),
          "d": 1 + rnd(layers, heads) / 8}
    proj, dt = rnd(slots, i + cw), rnd(slots, heads)

    def forms(mp):
        """``{form: step(state, win, li, active, live) -> (y, state, win)}``
        over the stacked parameters ``mp``."""
        small = ops.prepare_layer_params(
            mp["conv_w"], mp["conv_b"], mp["dt_bias"], mp["a_log"], mp["d"],
            mp["norm"], p)

        def fused(state, win, li, active, live):
            return ops.ssm_layer_step(
                state, win, li, proj, dt, small, active, live, eps=1e-5,
                interpret=args.rehearse)

        def unfused(state, win, li, active, live):
            lp = {name: jax.lax.dynamic_index_in_dim(a, li, 0, keepdims=False)
                  for name, a in mp.items()}
            held = jax.lax.dynamic_index_in_dim(win, li, 0, keepdims=False)
            z, xbc = proj[:, :i], proj[:, i:]
            seq = jnp.concatenate([held, xbc], axis=1)
            w = lp["conv_w"].astype(f32)
            acc = lp["conv_b"].astype(f32)[None, :]
            for j in range(k):
                acc = acc + w[j][None, :] * seq[
                    :, j * cw:(j + 1) * cw].astype(f32)
            held = jnp.where((active != 0)[:, None], seq[:, cw:], held)
            xbc = jax.nn.silu(acc).astype(bf16)
            xm, bm, cm = xbc[:, :i], xbc[:, i:i + n], xbc[:, i + n:]
            delta = jax.nn.softplus(dt.astype(f32)
                                    + lp["dt_bias"].astype(f32))
            a = -jnp.exp(lp["a_log"].astype(f32))
            xh = xm.astype(f32).reshape(slots, heads, p)
            decay = jnp.broadcast_to(jnp.exp(delta * a)[..., None],
                                     (slots, heads, p)).reshape(slots, i)
            xdt = (delta[..., None] * xh).reshape(slots, i)
            y, state = ops.ssm_state_update(
                state, li, decay, xdt, bm, cm, active, live,
                interpret=args.rehearse)
            y = y + (lp["d"].astype(f32)[None, :, None] * xh
                     ).reshape(slots, i)
            g = y * jax.nn.silu(z.astype(f32))
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
            y = (g * lp["norm"].astype(f32)).astype(bf16)
            return y, state, jax.lax.dynamic_update_index_in_dim(
                win, held, li, 0)

        return {"fused": fused, "unfused": unfused}

    def windows(flat):
        return {"fused": ops.pack_window(flat),
                "unfused": flat.reshape(*flat.shape[:2], -1)}

    # the two forms from the same inputs: one layer-call on a leaf of two
    # layers (a whole leaf a form does not fit beside the other's), every
    # row live
    every = jnp.ones(slots, jnp.int32)
    s0 = jax.random.normal(next(ks), ops.state_shape(2, slots, heads, p, n))
    flat = jax.random.normal(next(ks), (layers, slots, k - 1, cw)).astype(bf16)
    two = forms({name: a[:2] for name, a in mp.items()})
    one = {name: jax.jit(step)(s0, windows(flat[:2])[name], 1, every,
                               ops.live_rows(every))
           for name, step in two.items()}
    top = float(jnp.abs(one["unfused"][0].astype(f32)).max())
    gaps = {
        "y": float(jnp.abs(one["fused"][0].astype(f32)
                           - one["unfused"][0].astype(f32)).max()) / top,
        "state": float(jnp.abs(one["fused"][1] - one["unfused"][1]).max()
                       / jnp.abs(one["unfused"][1]).max()),
        "window": float(jnp.abs(
            ops.unpack_window(one["fused"][2], cw).reshape(2, slots, -1)
            .astype(f32) - one["unfused"][2].astype(f32)).max())}
    del one, s0
    steps, wins = forms(mp), windows(flat)
    shape = ops.state_shape(layers, slots, heads, p, n)

    def program(step):
        def run(state, win, active):
            live = ops.live_rows(active)

            def layer(li, carry):
                state, win, acc = carry
                y, state, win = step(state, win, li % layers, active, live)
                return state, win, acc + y[:, :8].astype(f32).sum()
            return jax.lax.fori_loop(0, layers * args.reps, layer,
                                     (state, win, jnp.float32(0)))
        return jax.jit(run, donate_argnums=(0, 1))

    for live in sorted({slots // 8, slots * 5 // 16, slots * 5 // 8, slots}):
        rng = np.random.default_rng(live)
        active = np.zeros(slots, np.int32)
        active[rng.choice(slots, live, replace=False)] = 1
        active = jnp.asarray(active)
        for name, step in steps.items():
            fn = program(step)
            state, win, acc = fn(jnp.zeros(shape, f32), jnp.copy(wins[name]),
                                 active)
            jax.block_until_ready(acc)          # compiled, warm
            t0 = time.perf_counter()
            state, win, acc = fn(state, win, active)
            jax.block_until_ready(acc)
            call_s = (time.perf_counter() - t0) / (layers * args.reps)
            print("SSM_LAYER_STEP " + json.dumps({
                "form": name, "live_rows": live, "slots": slots,
                "us_per_layer_step": call_s * 1e6,
                "finite": bool(np.isfinite(float(acc))),
                "gap_to_unfused": gaps if name == "fused" else None,
                "platform": platform}), flush=True)


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
    del state  # 4.8 GB: the next readings hold a leaf of their own
    layer_step_readings(ops, jax, jnp, np, args,
                        (layers, slots, heads, p, n), platform)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
