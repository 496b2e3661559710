"""Ablate the paged-decode step's pool operations on the real chip.

Round-5 profiling for VERDICT item 1: the paged engine ran at 14.7% of
roofline (31.1 ms/step at b32) vs the static engine's 75.6%.  This script
times each pool operation (gather, scatter, ys-restack) in isolation and
under alternative layouts, pipelined with a scalar-readback fence.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

L, KV, NB, BS, HD = 16, 8, 512, 32, 128
B, W = 32, 8  # decode batch, bucketed blocks/slot (mean span 256)
SPAN = W * BS
STEPS = 32  # one decode chunk


def fence(x):
    return float(jnp.ravel(x)[0])


def timeit(fn, *args, steps=STEPS, warm=2):
    for _ in range(warm):
        out = fn(*args)
    fence(out[0] if isinstance(out, tuple) else out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    fence(out[0] if isinstance(out, tuple) else out)
    return (time.perf_counter() - t0) / steps * 1000  # ms per step


def main():
    key = jax.random.PRNGKey(0)
    # current layout: [L, kv, NB, bs, hd]
    pool = jax.random.normal(key, (L, KV, NB, BS, HD), jnp.bfloat16)
    # NB-leading per-layer layout: [L, NB, bs, kv, hd]
    poolL = jnp.transpose(pool, (0, 2, 3, 1, 4))
    table = jnp.asarray(
        np.stack([np.arange(1 + i * W, 1 + (i + 1) * W) for i in range(B)]),
        jnp.int32)  # [B, W] distinct blocks
    k_new = jax.random.normal(key, (B, KV, HD), jnp.bfloat16)
    cur_blk = table[:, -1]
    cur_off = jnp.full((B,), 7, jnp.int32)
    q = jax.random.normal(key, (B, 16, HD), jnp.bfloat16)  # [B, nh, hd]

    baseline = timeit(jax.jit(lambda x: x + 1.0), jnp.zeros((8, 128)))
    print(f"dispatch floor        : {baseline:7.3f} ms")

    # -- gather: all L layers, current layout ---------------------------
    @jax.jit
    def gather_cur(pool, table):
        acc = jnp.zeros((), jnp.float32)
        def body(acc, pk):
            ck = pk[:, table].reshape(KV, B, SPAN, HD)
            return acc + jnp.sum(ck[..., 0, 0].astype(jnp.float32)), None
        acc, _ = jax.lax.scan(body, acc, pool)
        return acc

    print(f"gather [kv,NB,..] x{L} : {timeit(gather_cur, pool, table):7.3f} ms")

    # -- gather: NB-leading layout --------------------------------------
    @jax.jit
    def gather_lead(poolL, table):
        acc = jnp.zeros((), jnp.float32)
        def body(acc, pk):
            ck = pk[table]  # [B, W, bs, kv, hd] contiguous 64KB rows
            return acc + jnp.sum(ck[..., 0, 0, 0].astype(jnp.float32)), None
        acc, _ = jax.lax.scan(body, acc, poolL)
        return acc

    print(f"gather [NB,...]  x{L}  : {timeit(gather_lead, poolL, table):7.3f} ms")

    # -- gather + real attention einsum, both layouts -------------------
    @jax.jit
    def attend_cur(pool, table, q):
        def body(x, pk):
            ck = pk[:, table].reshape(KV, B, SPAN, HD)
            qg = x.reshape(B, KV, 2, HD)
            s = jnp.einsum("bkgd,kbsd->bkgs", qg, ck,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, -1)
            o = jnp.einsum("bkgs,kbsd->bkgd", p.astype(ck.dtype), ck,
                           preferred_element_type=jnp.float32)
            return x + o.reshape(B, 16, HD).astype(x.dtype), None
        x, _ = jax.lax.scan(body, q, pool)
        return x

    print(f"attend cur-layout x{L} : {timeit(attend_cur, pool, table, q):7.3f} ms")

    @jax.jit
    def attend_lead(poolL, table, q):
        def body(x, pk):
            ck = pk[table].reshape(B, SPAN, KV, HD)
            qg = x.reshape(B, KV, 2, HD)
            s = jnp.einsum("bkgd,bskd->bkgs", qg, ck,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, -1)
            o = jnp.einsum("bkgs,bskd->bkgd", p.astype(ck.dtype), ck,
                           preferred_element_type=jnp.float32)
            return x + o.reshape(B, 16, HD).astype(x.dtype), None
        x, _ = jax.lax.scan(body, q, poolL)
        return x

    print(f"attend NB-lead    x{L} : {timeit(attend_lead, poolL, table, q):7.3f} ms")

    # -- scatter write: current vs NB-leading ---------------------------
    @jax.jit
    def scatter_cur(pool, k_new, cur_blk, cur_off):
        def body(pool, li):
            pk = pool[li]
            pk = pk.at[:, cur_blk, cur_off].set(
                k_new.transpose(1, 0, 2))
            return pool.at[li].set(pk), None
        pool, _ = jax.lax.scan(body, pool, jnp.arange(L))
        return pool

    print(f"scatter cur+liDUS x{L} : "
          f"{timeit(scatter_cur, pool, k_new, cur_blk, cur_off):7.3f} ms")

    @jax.jit
    def scatter_ys(pool, k_new, cur_blk, cur_off):
        def body(_, pk):
            pk = pk.at[:, cur_blk, cur_off].set(k_new.transpose(1, 0, 2))
            return None, pk
        _, pool = jax.lax.scan(body, None, pool)
        return pool

    print(f"scatter ys-restack x{L}: "
          f"{timeit(scatter_ys, pool, k_new, cur_blk, cur_off):7.3f} ms")

    @jax.jit
    def scatter_lead(poolL, k_new, cur_blk, cur_off):
        def body(_, pk):
            pk = pk.at[cur_blk, cur_off].set(k_new)
            return None, pk
        _, poolL = jax.lax.scan(body, None, poolL)
        return poolL

    print(f"scatter NB-lead ys x{L}: "
          f"{timeit(scatter_lead, poolL, k_new, cur_blk, cur_off):7.3f} ms")

    # -- pure ys restack (no modification) ------------------------------
    @jax.jit
    def restack(pool):
        def body(_, pk):
            return None, pk * 1.0001
        _, pool = jax.lax.scan(body, None, pool)
        return pool

    print(f"ys restack alone  x{L} : {timeit(restack, pool):7.3f} ms")

    # -- pallas paged_attention kernel, per layer -----------------------
    try:
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention,
        )

        lengths = jnp.full((B,), SPAN - 1, jnp.int32)

        @jax.jit
        def kern(pool, table, q):
            def body(x, inp):
                pk = inp
                o = paged_attention(x / math.sqrt(HD), pk, pk,
                                    lengths + 1, table,
                                    pages_per_compute_block=min(W, 4))
                return x + o.astype(x.dtype), None
            x, _ = jax.lax.scan(body, q, pool)
            return x

        print(f"pallas kernel x{L}     : {timeit(kern, pool, table, q):7.3f} ms")

        @jax.jit
        def kern8(pool, table, q):
            def body(x, inp):
                pk = inp
                o = paged_attention(x / math.sqrt(HD), pk, pk,
                                    lengths + 1, table,
                                    pages_per_compute_block=W)
                return x + o.astype(x.dtype), None
            x, _ = jax.lax.scan(body, q, pool)
            return x

        print(f"pallas kernel ppcb=W  : {timeit(kern8, pool, table, q):7.3f} ms")
    except ImportError:
        print("pallas kernel          : unavailable")


if __name__ == "__main__":
    main()
