"""Time the latent (MLA) attention paths alone on the chip, at the shapes of
the benchmark's cell ``pangu-ep16.docqa_warm`` (128 heads, a 576-value latent
row stored 640 wide, 16-position pages, bf16).

1. The decode kernel (``ops/mla_paged_attention.py``): ms a call for 16 and
   64 decoding rows of 8,500 positions in a 64-row table, and 64 rows of 100,
   checked against the gather path, with each row's roofline share (a live
   position: 128 x (576 + 512) x 2 operations and 1,152 B; the larger of the
   two bounds).
2. A prefill chunk's attention, ms a layer-call for chunks of 64, 256, 512
   and 1,024 queries against an 8,192-position prefix, each call depending
   on the last: the Pallas kernel (``ops/mla_prefill_attention.py``, the
   program's on a TPU) beside the two ``jax.numpy`` forms (expanded a KV tile
   at a time: ``models/pangu_moe.py``, the program's elsewhere and what the
   kernel is held against; absorbed: this file's, the form the program does
   not keep), with each row's share of the chip's peak by the benchmark's own
   operation count (``chipbench/model_math_mla_moe.py``).

    python benchmarks/mla_kernel_bench.py

Needs a TPU: a time from the Pallas interpreter says nothing.  ``--rehearse``
walks the same control flow on the CPU (interpret mode, toy sizes, no time
printed) and exits 3.
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

BS, LAYERS = 16, 2
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9  # v5e, chipbench/peaks.json


def _time(fn, *args, reps):
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def decode_rows(cfg, rehearse: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import pangu_moe as pm
    from ray_tpu.ops.mla_paged_attention import mla_paged_decode_attention

    b = 64
    cases = ([("4 rows of 40", 4, 40)] if rehearse else
             [("16 rows of 8500", 16, 8500), ("64 rows of 8500", 64, 8500),
              ("64 rows of 100", 64, 100)])
    wt = 8 if rehearse else 1024
    nb = 64 if rehearse else 36000
    calls = 2 if rehearse else 20
    key = jax.random.PRNGKey(0)
    pool = jax.jit(lambda k: jnp.pad(
        jax.random.normal(k, (LAYERS, nb, BS, cfg.latent_width),
                          cfg.compute_dtype),
        ((0, 0),) * 3 + ((0, cfg.cache_width - cfg.latent_width),)))(key)
    q = jax.jit(lambda k: jnp.pad(
        jax.random.normal(k, (b, cfg.n_heads, cfg.latent_width),
                          cfg.compute_dtype),
        ((0, 0),) * 2 + ((0, cfg.cache_width - cfg.latent_width),)))(
            jax.random.PRNGKey(1))
    scale = cfg.qk_head_dim ** -0.5
    def chain(q, pool, table, lengths, active):
        # each call's queries depend on the last call's result, so the
        # compiler can neither merge the calls nor reorder them
        for li in range(calls):
            o = mla_paged_decode_attention(
                q, pool, li % LAYERS, table, lengths, active,
                value_width=cfg.kv_lora_rank, scale=scale,
                interpret=rehearse)
            q = q + (o[..., :1] * 1e-6).astype(q.dtype)
        return q

    kern = jax.jit(chain)
    one = jax.jit(lambda q, pool, table, lengths, active:
                  mla_paged_decode_attention(
                      q, pool, 1, table, lengths, active,
                      value_width=cfg.kv_lora_rank, scale=scale,
                      interpret=rehearse))
    rng = np.random.RandomState(0)
    for name, rows, length in cases:
        lengths = np.zeros(b, np.int32)
        active = np.zeros(b, np.int32)
        table = np.zeros((b, wt), np.int32)
        free = iter(rng.permutation(np.arange(1, nb)))
        for r in range(rows):
            lengths[r], active[r] = length - 1, 1
            for j in range(-(-length // BS)):
                table[r, j] = next(free)
        args = (q, pool, jnp.asarray(table), jnp.asarray(lengths),
                jnp.asarray(active))
        got = np.asarray(one(*args))[:rows]
        span = pool[1][table[:rows]].reshape(rows, wt * BS, cfg.cache_width)
        mask = (np.arange(wt * BS)[None, None, :]
                <= lengths[:rows, None, None])
        want = np.asarray(jax.jit(lambda q, s, m: pm._attend_absorbed(
            cfg, q[:, None], s, m))(q[:rows], span, jnp.asarray(mask)))[:, 0]
        err = float(np.abs(got - want).max())
        row = {"case": name, "max_abs_err_vs_gather": round(err, 5)}
        if not rehearse:
            sec = _time(kern, *args, reps=5) / calls
            live = rows * length
            least = max(live * cfg.n_heads * 2 * (cfg.latent_width
                                                  + cfg.kv_lora_rank)
                        / PEAK_FLOPS,
                        live * cfg.latent_width * 2 / PEAK_BYTES)
            row.update(ms_a_call=round(sec * 1e3, 4),
                       roofline_pct=round(100 * least / sec, 1))
        print("MLA_DECODE " + json.dumps(row), flush=True)
        assert err < 0.05, row


def _attend_tiles_absorbed(cfg, q_nope, q_rope, pool, li, row, positions, lp,
                           tile: int):
    """``pangu_moe._attend_tiles_expanded``'s attention in ABSORBED form:
    queries carried into the latent space, scored against the cached rows as
    they lie, the up-projection of the values applied once to the result.  No
    tile is expanded; each score costs ``latent_width`` multiplies and each
    value ``kv_lora_rank``.  The program does not use it (slower at both
    chunk sizes: PERF.md section 6, PR 31); it lives here to be timed."""
    import math

    import jax.numpy as jnp
    from jax import lax

    from ray_tpu.models import pangu_moe as pm

    c = q_nope.shape[0]
    pages = tile // pool.shape[2]
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    offs = jnp.arange(tile)
    q_abs = pm._absorb_queries(cfg, q_nope, q_rope, lp)

    def fold(i, state):
        blocks = lax.dynamic_slice(row, (i * pages,), (pages,))
        lat = pool[li, blocks].reshape(tile, cfg.cache_width)
        s = jnp.einsum("chw,sw->hcs", q_abs, lat,
                       preferred_element_type=jnp.float32) * scale
        visible = (i * tile + offs)[None, :] <= positions[:, None]
        return pm._fold(state, s, lat[:, :cfg.kv_lora_rank], visible,
                        "hcs,sr->hcr")

    _, l, acc = lax.fori_loop(0, positions[-1] // tile + 1, fold,
                              pm._tile_state(cfg, c, cfg.kv_lora_rank))
    return pm._unabsorb(cfg, (acc / l[..., None]).transpose(1, 0, 2), lp)


def _attention_flops(chunk: int, prefix: int) -> float:
    """Operations one layer-call of a chunk's attention needs, by the
    benchmark's own count (``chipbench/model_math_mla_moe.prefill_flops`` at
    one layer, less its matrix multiplies and its head): every (query, key)
    pair a head in expanded form and the expansion of every position
    visited."""
    from chipbench import model_math_mla_moe as mm

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "openpangu-ultra-moe-ep16.json")) as f:
        one = {**json.load(f), "num_hidden_layers": 1,
               "first_k_dense_replace": 1}
    return (mm.prefill_flops(one, chunk, prefix, chunk)
            - 2.0 * mm.prefill_matmul_params(one) * chunk
            - 2.0 * one["hidden_size"] * one["vocab_size"])


def prefill_rows(cfg, rehearse: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import pangu_moe as pm

    prefix = 64 if rehearse else 8192
    chunks = (16,) if rehearse else (64, 256, 512, 1024)
    # the jax.numpy forms at the tile PR 31 measured them at
    tile = 16 if rehearse else 512
    kernel_tile = 16 if rehearse else pm.PREFILL_KV_TILE
    calls = 2 if rehearse else 4
    nb = (prefix + max(chunks)) // BS + 8
    key = jax.random.PRNGKey(0)
    pool = jax.jit(lambda k: jnp.pad(
        jax.random.normal(k, (LAYERS, nb, BS, cfg.latent_width),
                          cfg.compute_dtype),
        ((0, 0),) * 3 + ((0, cfg.cache_width - cfg.latent_width),)))(key)
    lp = jax.jit(lambda k: {
        "w_uk": jax.random.normal(
            k, (cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank),
            cfg.compute_dtype) * 0.02,
        "w_uv": jax.random.normal(
            k, (cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim),
            cfg.compute_dtype) * 0.02})(jax.random.PRNGKey(2))
    row = jnp.arange(1, nb)
    row = jnp.pad(row, (0, -row.shape[0] % (kernel_tile // BS)))

    def kernel(cfg, qn, qr, pool, li, row, pos, lp, tile):
        return pm._attend_kernel(cfg, qn, qr, pool, li, row, pos[0], lp,
                                 tile, rehearse)

    forms = (("expanded", pm._attend_tiles_expanded, tile),
             ("absorbed", _attend_tiles_absorbed, tile),
             ("kernel", kernel, kernel_tile))
    for c in chunks:
        kq = jax.random.split(jax.random.PRNGKey(3))
        q_nope = jax.random.normal(
            kq[0], (c, cfg.n_heads, cfg.qk_nope_head_dim), cfg.compute_dtype)
        q_rope = jax.random.normal(
            kq[1], (c, cfg.n_heads, cfg.qk_rope_head_dim), cfg.compute_dtype)
        positions = prefix + jnp.arange(c)
        args = (q_nope, q_rope, pool, row, positions, lp)
        least = 0.0 if rehearse else _attention_flops(c, prefix) / PEAK_FLOPS
        outs = {}
        for form, fn, t in forms:
            def chain(qn, qr, pool, row, pos, lp, fn=fn, t=t):
                # each call's queries depend on the last call's result
                for li in range(calls):
                    o = fn(cfg, qn, qr, pool, li % LAYERS, row, pos, lp, t)
                    qn = qn + (o[:, :1, None] * 1e-6).astype(qn.dtype)
                return qn

            one = jax.jit(lambda *a, fn=fn, t=t: fn(
                cfg, a[0], a[1], a[2], 1, *a[3:], t))
            outs[form] = np.asarray(one(*args), np.float32)
            out = {"form": form, "chunk": c, "prefix": prefix}
            if rehearse:
                jax.block_until_ready(jax.jit(chain)(*args))
            else:
                sec = _time(jax.jit(chain), *args, reps=3) / calls
                out["ms_a_layer_call"] = round(sec * 1e3, 3)
                if form != "absorbed":  # the count is the expanded form's
                    out["peak_pct"] = round(100 * least / sec, 1)
            print("MLA_PREFILL " + json.dumps(out), flush=True)
        diffs = {f"max_abs_diff_{form}_vs_expanded": round(float(np.abs(
            outs[form] - outs["expanded"]).max()), 5)
            for form in ("absorbed", "kernel")}
        print("MLA_PREFILL " + json.dumps({"chunk": c, **diffs}), flush=True)
        assert max(diffs.values()) < 0.01, diffs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    from ray_tpu.models.pangu_moe import PanguMoEConfig

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("mla_kernel_bench needs a TPU", file=sys.stderr)
        return 2
    # the CPU has no bf16 x bf16 -> float32 product: a rehearsal is float32
    cfg = (PanguMoEConfig.tiny(kv_lora_rank=128) if args.rehearse
           else PanguMoEConfig())
    decode_rows(cfg, args.rehearse)
    prefill_rows(cfg, args.rehearse)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
