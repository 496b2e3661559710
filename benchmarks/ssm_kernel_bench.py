"""The state-space decode kernel alone, at granite-4.0-h-micro's shapes.

``SSM_LAYER_STEP``: a whole layer-step's work between the in-projection's
output and the out-projection's input on the engine's leaves (36 Mamba layers
x 64 slots x [32, 128, 128] float32, 4.8 GB, and the windows) with 8, 20, 40
and 64 of the 64 rows decoding, as the model runs it on the chip (``fused``:
``ops/ssm_state_update.py ssm_layer_step``, one call, the live rows alone)
and as it runs it anywhere else (``jnp``:
``granite_hybrid.recurrent_step_jnp``: convolution, ``silu``, ``delta``,
``decay``, the window's write-back and the gated norm in ``jax.numpy`` over
every slot, round ``ssm_state_update_jnp``, which moves every slot's state
whoever decodes); with the fused form's largest gap to the ``jnp`` one from
the same inputs, as a share of the largest value.  One program a reading: a
loop over the 36 layers, ``--reps`` times, each call taking the leaves the
last one returned, so nothing overlaps and nothing is elided.

``SSM_STATE_JNP``: ``ssm_state_update_jnp`` alone at 20 live rows: ms a
layer-call and the share of the HBM peak the LIVE rows' bytes (2 MB read and
written a row) are moved at.  (The kernel of the state's update alone, which
this reading stood beside, went at PR 50; PERF.md section 6, PRs 36 and 44,
keeps what it measured.)

    python benchmarks/ssm_kernel_bench.py [--reps 8] [--slots 64]

Prints ``SSM_STATE_JNP {json}`` and ``SSM_LAYER_STEP {json}`` a reading.  A
time comes only from a chip: on another backend it exits 2 (``--rehearse``
walks it at toy size in interpret mode and exits 3).
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
    from ray_tpu.models import granite_hybrid as gh

    layers, slots, heads, p, n = dims
    # the model's own CPU branch is the ``jnp`` form: a config of these widths
    cfg = gh.GraniteHybridConfig(dim=heads * p // 2, mamba_n_heads=heads,
                                 mamba_d_head=p, mamba_d_state=n)
    i, k, cw = cfg.d_inner, cfg.mamba_d_conv, cfg.conv_width
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
                state, win, li, proj, dt, small, active, live,
                eps=cfg.rms_norm_eps, interpret=args.rehearse)

        def plain(state, win, li, active, live):
            del live  # every slot's state moves
            lp, held = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, li, 0,
                                                       keepdims=False),
                (mp, win))
            y, state, held = gh.recurrent_step_jnp(
                cfg, lp, proj[:, :i], proj[:, i:], dt, state, held, li,
                active)
            return y, state, jax.lax.dynamic_update_index_in_dim(
                win, held, li, 0)

        return {"fused": fused, "jnp": plain}

    # the two forms from the same inputs: one layer-call on a leaf of two
    # layers (a whole leaf a form does not fit beside the other's), every
    # row live
    every = jnp.ones(slots, jnp.int32)
    s0 = jax.random.normal(next(ks), ops.state_shape(2, slots, heads, p, n))
    wins = ops.pack_window(jax.random.normal(
        next(ks), (layers, slots, k - 1, cw)).astype(bf16))
    two = forms({name: a[:2] for name, a in mp.items()})
    one = {name: jax.jit(step)(s0, wins[:2], 1, every, ops.live_rows(every))
           for name, step in two.items()}
    top = float(jnp.abs(one["jnp"][0].astype(f32)).max())
    gaps = {
        "y": float(jnp.abs(one["fused"][0].astype(f32)
                           - one["jnp"][0].astype(f32)).max()) / top,
        "state": float(jnp.abs(one["fused"][1] - one["jnp"][1]).max()
                       / jnp.abs(one["jnp"][1]).max()),
        "window": float(jnp.abs(one["fused"][2].astype(f32)
                                - one["jnp"][2].astype(f32)).max())}
    del one, s0
    steps = forms(mp)
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
            state, win, acc = fn(jnp.zeros(shape, f32), jnp.copy(wins),
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
                "gap_to_jnp": gaps if name == "fused" else None,
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

    def run(state, active):
        def layer(i, carry):
            state, acc = carry
            y, state = ops.ssm_state_update_jnp(state, i % layers, decay, xdt,
                                                b, c, active)
            return state, acc + y[:, :8].sum()
        return jax.lax.fori_loop(0, layers * args.reps, layer,
                                 (state, jnp.float32(0)))

    fn = jax.jit(run, donate_argnums=0)
    live = slots * 5 // 16
    active = np.zeros(slots, np.int32)
    active[np.random.default_rng(live).choice(slots, live, replace=False)] = 1
    active = jnp.asarray(active)
    state, acc = fn(jnp.zeros(shape, jnp.float32), active)  # compile, warm
    jax.block_until_ready(acc)
    t0 = time.perf_counter()
    state, acc = fn(state, active)
    jax.block_until_ready(acc)
    call_s = (time.perf_counter() - t0) / (layers * args.reps)
    print("SSM_STATE_JNP " + json.dumps({
        "live_rows": live, "slots": slots,
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
