"""Time the paged-attention decode kernel alone on the chip, at the shapes of
the benchmark's serving cell (`m7b-d16.chat_steady`: batch 64, 32 query / 8
KV heads of 128, 16-token pages, bf16 pool).

The question it answers: does a call's time follow the padded table (rows x
table width) or the live pages?  Per table width it times rows that all hold
100 tokens, rows that all fill the table, and the cell's own mix (20 decoding
rows of 450 tokens among 44 idle ones), and checks the mix against the gather
path.  ``--root DIR`` times another checkout's kernel (the parent commit,
unpacked under a git-ignored directory) in the same call.

    python benchmarks/paged_kernel_bench.py [--root DIR] [--widths 64,128,256]

Needs a TPU: a time from the Pallas interpreter says nothing.  ``--rehearse``
walks the same control flow on the CPU (interpret mode, a few calls, no time
printed) and exits 3.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

B, NH, KV, HD, BS = 64, 32, 8, 128, 16
LAYERS, NB = 4, 4096
CALLS = 64 * LAYERS  # kernel calls in one timed program


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None)
    ap.add_argument("--widths", default="64,128,256")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.models import llama
    from ray_tpu.ops.paged_attention import paged_decode_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("paged_kernel_bench needs a TPU", file=sys.stderr)
        return 2
    calls = 2 * LAYERS if args.rehearse else CALLS
    takes_active = "active" in inspect.signature(
        paged_decode_attention).parameters
    cfg = llama.LlamaConfig(vocab_size=256, dim=NH * HD, n_layers=LAYERS,
                            n_heads=NH, n_kv_heads=KV, ffn_dim=256,
                            max_seq_len=4096, param_dtype=jnp.bfloat16)

    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, NH, HD), jnp.bfloat16)
    pk = jax.random.normal(kk, (LAYERS, NB, BS, KV * HD), jnp.bfloat16)
    pv = jax.random.normal(kv_, (LAYERS, NB, BS, KV * HD), jnp.bfloat16)

    # the pools are arguments everywhere: a closed-over array is compiled
    # into the program as a constant (2 GB a program here)
    def kernel(q, pk, pv, li, table, lengths, active):
        extra = (active,) if takes_active else ()
        return paged_decode_attention(q, pk, pv, li, table, lengths, *extra,
                                      interpret=args.rehearse)

    @jax.jit
    def many(q, pk, pv, table, lengths, active):
        def body(q, li):
            out = kernel(q, pk, pv, li, table, lengths, active)
            # the next call depends on this one: nothing overlaps or folds
            return q + (out.reshape(q.shape) * 1e-3).astype(q.dtype), None

        return lax.scan(body, q, jnp.tile(jnp.arange(LAYERS), calls // LAYERS))[0]

    @jax.jit
    def empty(q, pk, pv, table, lengths, active):
        def body(q, li):
            return q + (q.astype(jnp.float32) * 1e-3).astype(q.dtype) * li, None

        return lax.scan(body, q, jnp.tile(jnp.arange(LAYERS), calls // LAYERS))[0]

    def ms_per_call(fn, *a, reps=5):
        fn(*a).block_until_ready()
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn(*a).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / calls * 1e3

    @jax.jit
    def gather_ref(q, pk, pv, table, lengths):
        w = table.shape[1]
        ck = pk[0, table].reshape(B, w * BS, KV, HD)
        cv = pv[0, table].reshape(B, w * BS, KV, HD)
        mask = jnp.arange(w * BS)[None, None, :] <= lengths[:, None, None]
        return llama._paged_attend(cfg, q[:, None], ck, cv, mask)[:, 0]

    rng = np.random.default_rng(0)
    for w in [int(x) for x in args.widths.split(",")]:
        full = rng.integers(1, NB, size=(B, w)).astype(np.int32)
        mix_len = np.zeros(B, np.int32)
        mix_act = np.zeros(B, np.int32)
        live = rng.choice(B, size=20, replace=False)
        mix_len[live], mix_act[live] = 450, 1
        mix_tab = np.zeros((B, w), np.int32)
        nblk = 450 // BS + 1
        mix_tab[live, :nblk] = full[live, :nblk]
        cases = {
            "rows64_tok100": (full, np.full(B, 99, np.int32), np.ones(B, np.int32)),
            "rows64_full": (full, np.full(B, w * BS - 9, np.int32), np.ones(B, np.int32)),
            "rows20_tok450_idle44": (mix_tab, mix_len - mix_act, mix_act),
        }
        for name, (tab, lens, act) in cases.items():
            a = (q, pk, pv, jnp.asarray(tab), jnp.asarray(lens),
                 jnp.asarray(act))
            row = {"tag": args.tag, "w": w, "case": name,
                   "live_pages": int(((lens + 1 + BS - 1) // BS)[act > 0].sum()),
                   "table_pages": B * w,
                   "ms_per_call": None if args.rehearse else (
                       ms_per_call(many, *a) - ms_per_call(empty, *a)),
                   "device": dev.device_kind}
            if args.rehearse:
                many(*a).block_until_ready()
            if name == "rows20_tok450_idle44":
                got = jax.jit(kernel)(*a[:3], jnp.int32(0), *a[3:])
                want = gather_ref(*a[:5])
                keep = act > 0
                row["max_abs_err_vs_gather"] = float(
                    jnp.max(jnp.abs(got[keep] - want[keep])))
                row["idle_rows_finite"] = bool(jnp.isfinite(got[~keep]).all())
            print(json.dumps(row), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
