"""Time the expert layer alone on the chip, router to summed output, at the
widths of the benchmark's cell ``pangu-ep16.docqa_warm`` (hidden 7680, 16 of
256 experts of width 2048 held, 8 a token, one shared expert, bf16): the
DENSE form of the held experts' part (every token times every held expert:
``pangu_moe._routed_dense``) against the GROUPED form (the chosen (token,
expert) pairs sorted by expert, ``ops/moe_grouped_ffn.py``), at 64, 128, 256,
384, 512 and 1,024 rows.

1. Both forms against each other on the same weights for four routings:
   the router's own, every token on one held expert, every token on 8 held
   experts (more pairs than the buffers hold: the dense fallback must
   answer), no token on a held expert (the routed part exactly zero).
2. ms a layer-call of ``pangu_moe.moe_ffn`` in each form, every call's input
   depending on the last call's output (identical independent calls of a
   kernel bench were merged by the compiler: PERF.md section 6, PR 31), over
   two layers' weights in turn; the crossing is the least width from which
   on grouped is the faster.  ``pangu_moe.GROUPED_MIN_ROWS`` cites this table.
3. The grouped form's parts at each width (the counting sort, the gather,
   the gated up kernel, the down kernel, the rows added to their tokens as
   the program does it and as XLA's scatter-add), and with ``--tiles`` the
   two kernels at other tiles.
4. ``--decode``: a decode token-step's layer-call instead, at both expert
   families' widths (pangu's above; ``kimi-linear-ep16.reason_steady``'s:
   hidden 2304, 16 of 256 experts of width 1024): 64 rows of which 4, 8,
   16, 32 or 64 decode, the dense form over all 64 (what the decode program
   ran until PR 49) against ``moe_ffn(..., live=...)``, the grouped product
   over the live rows' pairs, with the held experts those rows hit beside
   it: what a hit share costs.  And the grouped form at other row tiles
   (the program's is ``pangu_moe._row_tile``).

    python benchmarks/moe_prefill_bench.py [--tiles | --decode]

Needs a TPU: a time from the Pallas interpreter says nothing.  ``--rehearse``
walks the same control flow on the CPU (interpret mode, toy sizes, no time
printed) and exits 3.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LAYERS = 2
CALLS = 6
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # v5e, chipbench/peaks.json


def _time(fn, *args, reps=5):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _weights(cfg):
    """The expert layers' stack of ``pangu_moe.init_params``, alone."""
    import jax

    from ray_tpu.models import pangu_moe as pm

    d, e, f, dt = cfg.dim, cfg.n_held, cfg.moe_ffn_dim, cfg.param_dtype
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    shapes = {"router": (d, cfg.n_routed_experts), "ws_gate": (d, f),
              "ws_up": (d, f), "ws_down": (f, d), "we_gate": (d, e * f),
              "we_up": (d, e * f), "we_down": (e * f, d)}
    return {k: pm._normal(ks[i], (LAYERS,) + s, 0.02,
                          "float32" if k == "router" else dt)
            for i, (k, s) in enumerate(shapes.items())}


def _layer_chain(cfg, rows_from: int, interpret: bool):
    """``CALLS`` layer-calls of ``moe_ffn`` in the form ``rows_from`` selects
    (``GROUPED_MIN_ROWS``: 1 grouped, above every width dense), each input
    the last one's plus a trace of its output."""
    import jax

    from ray_tpu.models import pangu_moe as pm

    def chain(h, stack):
        pm.GROUPED_MIN_ROWS = rows_from  # read while this traces
        lp = {k: v for k, v in stack.items()
              if k not in pm.HELD_EXPERT_LEAVES}
        for i in range(CALLS):
            li = i % LAYERS
            y, _, _ = pm.moe_ffn(
                cfg, h, {**{k: v[li] for k, v in lp.items()},
                         **{k: stack[k] for k in pm.HELD_EXPERT_LEAVES}},
                interpret, layer=li)
            h = h + (y * 1e-3).astype(h.dtype)
        return h

    return jax.jit(chain)


def decode_rows(cfg, stack, interpret: bool, rows: int = 64,
                tiles=(16, 32, 64, 128)):
    """One line a count of live rows: ms a layer-call of a decode
    token-step's expert layer, dense over all ``rows`` against grouped over
    the live rows' pairs (at the program's row tile, then at ``tiles``),
    the held experts hit and the layer-calls that took the grouped product
    (of ``CALLS``) beside them, and the largest gap between the two forms'
    live rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import pangu_moe as pm

    def chain(form, tile=None):
        def run(h, live, stack):
            pm.GROUPED_MIN_ROWS = 1 << 30  # a chunk's rule never answers
            if tile:
                pm._row_tile = lambda t: tile  # read while this traces
            lp = {k: v for k, v in stack.items()
                  if k not in pm.HELD_EXPERT_LEAVES}
            hit = took = 0
            for i in range(CALLS):
                li = i % LAYERS
                y, g, grouped = pm.moe_ffn(
                    cfg, h, {**{k: v[li] for k, v in lp.items()},
                             **{k: stack[k] for k in pm.HELD_EXPERT_LEAVES}},
                    interpret, layer=li,
                    live=live if form == "grouped" else None)
                hit += ((g > 0) & (live[:, None] > 0)).any(0).sum()
                took += grouped
                h = h + (y * 1e-3).astype(h.dtype)
            return h, y, hit, took

        return jax.jit(run)

    threshold, row_tile = pm.GROUPED_MIN_ROWS, pm._row_tile
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim),
                          cfg.compute_dtype)
    out = []
    for n in (4, 8, 16, 32, 64):
        # the live rows scattered over the slots, as a serving batch's are
        live = jnp.zeros((rows,), jnp.int32).at[
            jax.random.permutation(jax.random.PRNGKey(n), rows)[:n]].set(1)
        row = {"rows": rows, "live": n}
        last = {}
        for name, form, tile in [("dense", "dense", None),
                                 ("grouped", "grouped", None)] + [
                (f"grouped_tile_{t}", "grouped", t) for t in tiles]:
            fn = chain(form, tile)
            _, y, hit, took = jax.block_until_ready(fn(h, live, stack))
            pm._row_tile = row_tile
            last[name] = y
            if name == "grouped":
                row["experts_hit_of_%d" % cfg.n_held] = round(
                    float(hit) / CALLS, 2)
                row["grouped_calls_of_%d" % CALLS] = int(took)
            if not interpret:
                row[f"{name}_ms"] = round(
                    _time(fn, h, live, stack) / CALLS * 1e3, 4)
        pm.GROUPED_MIN_ROWS = threshold
        alive = live > 0
        row["gap_live_rows"] = float(
            jnp.abs(jnp.where(alive[:, None],
                              last["grouped"] - last["dense"], 0)).max()
            / jnp.abs(last["dense"]).max())
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def check(cfg, stack, t: int, interpret: bool):
    """Largest gap between the two forms over the four routings, as a share
    of the dense form's largest value."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import pangu_moe as pm

    h = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.dim),
                          cfg.compute_dtype)
    # weights are arguments everywhere: a jitted function that closes over
    # them lowers 3 GB of constants through the host
    lp = {k: stack[k] for k in pm.HELD_EXPERT_LEAVES}
    own = pm.held_gates(cfg, *pm.route(cfg, h, stack["router"][1]))
    z = jnp.zeros_like(own)
    cases = {"router": own, "one_expert": z.at[:, 3].set(1.3),
             "eight_held": z.at[:, :8].set(0.31), "none": z}
    dense = jax.jit(lambda h, g, lp: pm._routed_dense(cfg, h, g, lp, 1))
    grouped = jax.jit(
        lambda h, g, lp: pm._routed_grouped(cfg, h, g, lp, 1, interpret)[0])
    out = {}
    for name, g in cases.items():
        want, got = dense(h, g, lp), grouped(h, g, lp)
        out[name] = {
            "pairs": int((g > 0).sum()),
            "gap": float(jnp.abs(got - want).max()
                         / jnp.maximum(jnp.abs(want).max(), 1e-30)),
            "largest": float(jnp.abs(got).max())}
    return out


def parts(cfg, stack, t: int, interpret: bool, tiles=None):
    """ms of each part of the grouped form at ``t`` rows, each chained."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import pangu_moe as pm
    from ray_tpu.ops.moe_grouped_ffn import group_visits, grouped_matmul

    tm = pm._row_tile(t)
    m = -(-t // tm) * tm
    h = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.dim),
                          cfg.compute_dtype)
    g = pm.held_gates(cfg, *pm.route(cfg, h, stack["router"][0]))
    tok, gates, sizes, pairs = pm.sort_pairs(g, m)
    xs = jnp.take(h, tok, axis=0)
    act = jnp.ones((m, cfg.moe_ffn_dim), cfg.compute_dtype)
    out = jnp.ones((m, cfg.dim), jnp.float32)
    live = jnp.arange(m) < pairs

    def rep(step, x):
        def chain(x, stack):
            for i in range(CALLS):
                x = step(x, i % LAYERS, stack)
            return x
        return jax.jit(chain), x

    def sort(g, li, stack):
        tok, gates, sizes, pairs = pm.sort_pairs(g, m)
        v = group_visits(sizes, m, tm)[3]
        return g + (gates[:t, None] + tok[:t, None] + v) * 1e-9

    def up(tm_, tn):
        return lambda xs, li, stack: xs + grouped_matmul(
            xs, stack["we_gate"], li, sizes, group_axis=1,
            rhs2=stack["we_up"], tm=tm_, tn=tn, interpret=interpret,
            name="moe_grouped_ffn_up")[:, :1] * 1e-3

    def down(tm_, tn):
        return lambda act, li, stack: act + (grouped_matmul(
            act, stack["we_down"], li, sizes, group_axis=0, tm=tm_, tn=tn,
            out_dtype=jnp.float32, interpret=interpret,
            name="moe_grouped_ffn_down")[:, :1] * 1e-3).astype(act.dtype)

    steps = {
        "sort": (sort, g),
        "gather": (lambda h, li, stack:
                   h + jnp.take(h, tok, axis=0)[:t] * 1e-3, h),
        "up": (up(tm, 1024), xs), "down": (down(tm, 1920), act),
        "add_to_tokens": (lambda out, li, stack: out + jnp.pad(
            pm._add_to_tokens(out, tok, live, t),
            ((0, m - t), (0, 0))) * 1e-3, out),
        # what XLA's scatter-add of the same rows costs
        "scatter_add": (lambda out, li, stack: out + jnp.pad(
            jnp.zeros((t, cfg.dim), jnp.float32).at[tok].add(
                jnp.where(live[:, None], out, 0.0)),
            ((0, m - t), (0, 0))) * 1e-3, out),
    }
    if tiles:
        steps = {}
        for tm_ in (64, 128, 256):
            if m % tm_:
                continue
            for tn in (256, 512, 1024):
                steps[f"up tm={tm_} tn={tn}"] = (up(tm_, tn), xs)
            for tn in (1280, 1920, 3840):
                steps[f"down tm={tm_} tn={tn}"] = (down(tm_, tn), act)
    # the rows added to their tokens as float32 sums, against XLA's own
    # scatter-add of the same float32 rows
    vals = jax.random.normal(jax.random.PRNGKey(1), out.shape, jnp.float32)
    want = jax.jit(lambda v: jnp.zeros((t, cfg.dim), jnp.float32).at[tok].add(
        jnp.where(live[:, None], v, 0.0)))(vals)
    got = jax.jit(lambda v: pm._add_to_tokens(v, tok, live, t))(vals)
    row = {"rows": t, "pairs": int(pairs),
           "add_to_tokens_gap": float(jnp.abs(got - want).max())}
    for name, (step, x) in steps.items():
        fn, x = rep(step, x)
        try:
            if interpret:
                jax.block_until_ready(fn(x, stack))
                row[name] = "rehearsed"
            else:
                row[name] = round(_time(fn, x, stack) / CALLS * 1e3, 4)
        except Exception as e:  # noqa: BLE001 — a tile the chip refuses
            if not tiles:
                raise
            row[name] = f"refused: {type(e).__name__}"
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tiles", action="store_true",
                    help="time the two kernels at other tiles too")
    ap.add_argument("--decode", action="store_true",
                    help="a decode token-step's layer-call by its live rows")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import pangu_moe as pm

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    global CALLS
    if args.rehearse:
        CALLS = 2
        cfg = pm.PanguMoEConfig.tiny(
            dim=128, moe_ffn_dim=128, n_routed_experts=128,
            experts_held=(16, 32), param_dtype=jnp.bfloat16,
            compute_dtype=jnp.bfloat16)
        widths = (128, 256)
    else:
        cfg = pm.PanguMoEConfig()
        widths = (64, 128, 256, 384, 512, 1024)
    if args.decode:
        # the second family's widths (models/kimi_linear.py), under the same
        # expert layer
        kimi = (dataclasses.replace(cfg, dim=256) if args.rehearse else
                dataclasses.replace(cfg, dim=2304, moe_ffn_dim=1024,
                                    routed_scaling_factor=2.446))
        for name, c in (("pangu", cfg), ("kimi", kimi)):
            print(json.dumps({"device": {"platform": dev.platform,
                                         "kind": dev.device_kind},
                              "widths": name, "dim": c.dim,
                              "held": c.n_held,
                              "expert_width": c.moe_ffn_dim,
                              "weights_read_ms": round(
                                  3 * c.n_held * c.dim * c.moe_ffn_dim * 2
                                  / PEAK_BYTES * 1e3, 3)}), flush=True)
            stack = _weights(c)
            decode_rows(c, stack, args.rehearse)
            del stack
        if args.rehearse:
            print("rehearsal only: no time was measured", file=sys.stderr)
            return 3
        return 0
    stack = _weights(cfg)
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind},
                      "dim": cfg.dim, "held": cfg.n_held,
                      "expert_width": cfg.moe_ffn_dim}), flush=True)
    weights_s = 3 * cfg.n_held * cfg.dim * cfg.moe_ffn_dim * 2 / PEAK_BYTES
    rows, crossing = [], None
    threshold = pm.GROUPED_MIN_ROWS
    for t in widths:
        row = {"rows": t, "check": check(cfg, stack, t, args.rehearse)}
        h = jax.random.normal(jax.random.PRNGKey(t), (t, cfg.dim),
                              cfg.compute_dtype)
        for form, rows_from in (("dense", 1 << 30), ("grouped", 1)):
            fn = _layer_chain(cfg, rows_from, args.rehearse)
            if args.rehearse:
                jax.block_until_ready(fn(h, stack))
                continue
            row[f"{form}_ms"] = round(_time(fn, h, stack) / CALLS * 1e3, 4)
        pm.GROUPED_MIN_ROWS = threshold
        if not args.rehearse:
            # the held experts' products alone, at the chip's peaks
            row["dense_at_peak_ms"] = round(
                3 * 2 * t * cfg.n_held * cfg.dim * cfg.moe_ffn_dim
                / PEAK_FLOPS * 1e3, 3)
            row["weights_read_ms"] = round(weights_s * 1e3, 3)
            if row["grouped_ms"] < row["dense_ms"]:
                crossing = crossing or t
            else:
                crossing = None
        print(json.dumps(row), flush=True)
        rows.append(row)
        print(json.dumps({"parts_ms": parts(cfg, stack, t, args.rehearse,
                                            tiles=args.tiles)}), flush=True)
    worst = max(c["gap"] for r in rows for c in r["check"].values())
    print(json.dumps({"largest_gap_between_the_forms": worst,
                      "crossing_rows": crossing,
                      "GROUPED_MIN_ROWS": threshold}), flush=True)
    if args.rehearse:
        print("rehearsal only: no time was measured", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
