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

``--prefill`` times the paged prefill chunk program instead
(``models/llama.py prefill_chunk_paged``: Mistral-7B widths, 16 layers, a pool
of 6,000 blocks, the engine's fixed table of 264 blocks): ms a call for chunks
of 64 and 256 tokens at ``p0`` 0, 256, 1792 and 3840, so live prefixes from a
sixteenth of the table to all of it.  The question is the same: does a call's
time follow the table's width or the live prefix?  ``--tiles 256,512,1024``
times the loop at other KV tiles than the code's constant (where the checkout
under test takes one).  ``--profile`` adds, for the 256-token chunk at ``p0``
0 and 1792, one profiler capture of a few calls: the program's device time a
call by scope (``attention`` / ``ffn`` / ``head``, from the compiled
instructions' ``op_name``) and its largest operations by name.

    python benchmarks/paged_kernel_bench.py --prefill [--root DIR] [--tiles ...]

Needs a TPU: a time from the Pallas interpreter says nothing.  ``--rehearse``
walks the same control flow on the CPU (interpret mode, a few calls, toy
sizes for ``--prefill``, no time printed) and exits 3.
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


def prefill_main(args) -> int:
    """One row a (tile, chunk, p0): ms a call of the whole chunk program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.ops.rope import rope_frequencies

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("paged_kernel_bench needs a TPU", file=sys.stderr)
        return 2
    if args.rehearse:
        cfg = llama.LlamaConfig.tiny(max_seq_len=4096,
                                     param_dtype=jnp.bfloat16,
                                     compute_dtype=jnp.bfloat16)
        nb, reps = 300, 1
    else:
        cfg = llama.LlamaConfig(
            vocab_size=32768, dim=NH * HD, n_layers=16, n_heads=NH,
            n_kv_heads=KV, ffn_dim=14336, max_seq_len=4096, rope_theta=1e6,
            param_dtype=jnp.bfloat16)
        nb, reps = 6000, 10
    width = 264
    takes_tile = "kv_tile" in inspect.signature(
        llama.prefill_chunk_paged).parameters
    tiles = [int(t) for t in args.tiles.split(",") if t] if takes_tile else []
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    rope = (jnp.asarray(cos), jnp.asarray(sin))
    # made on the device by one program: eager jax.random calls take minutes
    params = jax.jit(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))()
    kk, kv_ = jax.random.split(jax.random.PRNGKey(1))
    shape = (cfg.n_layers, nb, BS, cfg.n_kv_heads * cfg.head_dim)
    pool = {"k": jax.random.normal(kk, shape, jnp.bfloat16),
            "v": jax.random.normal(kv_, shape, jnp.bfloat16)}
    rng = np.random.default_rng(0)
    for tile in [None] + tiles:
        extra = {} if tile is None else {"kv_tile": tile}
        fn = jax.jit(
            lambda p, t, pl, tb, p0, extra=extra: llama.prefill_chunk_paged(
                cfg, p, t, pl, tb, p0, rope_cache=rope, **extra)[:2],
            donate_argnums=2)
        for c in (64, 256):
            tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, c)),
                                 jnp.int32)
            for p0 in (0, 256, 1792, 3840):
                live = (p0 + c) // BS
                table = np.zeros((1, width), np.int32)
                table[0, :live] = rng.permutation(np.arange(1, nb))[:live]
                a = (jnp.asarray(table), jnp.int32(p0))
                logits, pool = fn(params, tokens, pool, *a)
                logits.block_until_ready()
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    logits, pool = fn(params, tokens, pool, *a)
                    logits.block_until_ready()
                    times.append(time.perf_counter() - t0)
                print(json.dumps({
                    "tag": args.tag, "mode": "prefill", "kv_tile": tile,
                    "chunk": c, "p0": p0, "live_pages": live,
                    "table_pages": width,
                    "ms_per_call": None if args.rehearse else (
                        sorted(times)[len(times) // 2] * 1e3),
                    "ms_min": None if args.rehearse else min(times) * 1e3,
                    "logits_finite": bool(jnp.isfinite(logits).all()),
                    "logits_abs_mean": float(jnp.abs(logits).mean()),
                    "device": dev.device_kind}), flush=True)
                if args.profile and tile is None and c == 256 and p0 in (
                        0, 1792):
                    row, pool = _profile_chunk(
                        fn, (params, tokens, pool, *a),
                        calls=1 if args.rehearse else 4)
                    print(json.dumps({"tag": args.tag, "mode": "prefill_ops",
                                      "chunk": c, "p0": p0, **row}),
                          flush=True)
    return 3 if args.rehearse else 0


def _profile_chunk(fn, call_args, calls):
    """One capture of ``calls`` runs of the chunk program: device ms a call
    by scope and the twelve largest operations, self time (a ``while`` does
    not count its body).  An operation's scope is the first of ``attention``,
    ``ffn``, ``head`` in its compiled instruction's ``op_name``."""
    import glob
    import re
    import tempfile

    import jax

    from chipbench import trace_reduce

    text = fn.lower(*call_args).compile().as_text()
    scope_of = {}
    for m in re.finditer(r"%([\w.\-]+) = [^\n]*?op_name=\"([^\"]*)\"", text):
        hit = re.search(r"/(attention|ffn|head)(/|$)", m.group(2))
        scope_of[m.group(1)] = hit.group(1) if hit else "other"
    params, tokens, pool, table, p0 = call_args
    logdir = tempfile.mkdtemp(prefix="prefill_ops_")
    with jax.profiler.trace(logdir):
        for _ in range(calls):
            logits, pool = fn(params, tokens, pool, table, p0)
        logits.block_until_ready()
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    plane = trace_reduce.first_device(trace_reduce.load(files[0]))
    if plane is None:  # the rehearsal: a CPU trace has no device plane
        return {"ms_per_call": None, "by_scope_ms": {}, "top_ops_ms": []}, pool
    by_scope, by_name = {}, {}
    for name, own, _ in trace_reduce.self_times(trace_reduce.op_events(plane)):
        scope = scope_of.get(name.split(" ")[0], "other")
        by_scope[scope] = by_scope.get(scope, 0.0) + own
        key = f"{scope}: {name}"
        by_name[key] = by_name.get(key, 0.0) + own
    per_call = 1e3 / calls
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:12]
    return {"ms_per_call": sum(by_scope.values()) * per_call,
            "by_scope_ms": {k: round(v * per_call, 3)
                            for k, v in sorted(by_scope.items())},
            "top_ops_ms": [[k, round(v * per_call, 3)] for k, v in top]}, pool


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None)
    ap.add_argument("--widths", default="64,128,256")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--prefill", action="store_true")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--profile", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if args.prefill:
        return prefill_main(args)

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
