"""Pallas attention of one prefill chunk over a sequence's LATENT rows (MLA,
expanded form).

A latent-attention cache keeps ``[c_kv | k_rope | 0]`` a position a layer,
shared by every head (``ops/mla_paged_attention.py`` has the layout).  A
prefill chunk of C queries attends causally to the ``p0`` cached positions
before it and to itself.  At C of a few hundred the form with the fewest
operations EXPANDS each cached position a head to ``k_nope_i = c_kv W_uk_i^T``
and ``v_i = c_kv W_uv_i`` (a score then costs ``nope + rope`` multiplies, not
the latent row's width; ``models/pangu_moe.py`` has the mathematics).

The kernel does that expansion and the whole online softmax in VMEM.  Grid
``(head groups, KV tiles)``, KV tiles innermost: a step takes the latent tile
``[T, W]`` and a group's ``W_uk`` / ``W_uv``, expands the tile to that
group's keys and values (never written to HBM), and folds it into the running
max, sum and accumulator of each head, which live in VMEM scratch from the
group's first tile to its last.  A score tile ``[block_q, T]`` is float32 in
VMEM and nowhere else; only ``[C, H v]`` leaves the kernel.  The same
precisions as the ``jax.numpy`` form it replaces
(``pangu_moe._attend_tiles_expanded``): operands in the compute dtype, scores
and accumulation in float32, probabilities cast to the compute dtype for the
value product.

Work follows the live prefix: the grid is as wide as the buffer of latent
rows, but a tile past ``p0 + C`` is never fetched (its block index is clamped
to the last live tile's, which is already in VMEM) and its step does nothing;
inside the chunk a (query block, KV tile) pair above the diagonal is skipped,
and only the pairs the diagonal crosses pay for the mask.

The rotary part of a key is the row's last ``W - r`` columns as they lie
(``[k_rope | 0]``, a whole lane tile at the published widths), so a query is
``[q_nope_i | q_rope_i | 0]`` and a head's key ``[k_nope_i | k_rope | 0]``: one
product of depth ``nope + W - r``.

In a profiler trace the kernel's instruction is named
``mla_prefill_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# q k^T and c w^T: contract the minor dimension of both
_NT = (((1,), (1,)), ((), ()))


def _kernel(s_ref, q_ref, lat_ref, wuk_ref, wuv_ref, o_ref,
            m_ref, l_ref, acc_ref, *, r, scale, block_q):
    """One grid step = one head group x one KV tile.  s_ref: [2] SMEM,
    (p0, live tiles); m_ref, l_ref: [G, C, 1]; acc_ref: [G, C, v]."""
    t = pl.program_id(1)
    p0, n_live = s_ref[0], s_ref[1]
    g_heads, nope = wuk_ref.shape[:2]
    dv = wuv_ref.shape[2]
    c = q_ref.shape[0]
    tile = lat_ref.shape[0]
    dq = q_ref.shape[1] // g_heads
    k_lo = t * tile

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def fold(g, rows, k, v, q_lo, masked):
        q = q_ref[rows, g * dq:(g + 1) * dq]
        s = lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
        if masked:
            qpos = q_lo + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_lo + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, -1e30)
        m = m_ref[g, rows]
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[g, rows] = alpha * l_ref[g, rows] + jnp.sum(p, -1,
                                                          keepdims=True)
        acc_ref[g, rows] = alpha * acc_ref[g, rows] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[g, rows] = m_new

    @pl.when(t < n_live)
    def _():
        c_kv = lat_ref[:, :r]
        k_rope = lat_ref[:, r:]
        for g in range(g_heads):
            k_nope = lax.dot_general(
                c_kv, wuk_ref[g], _NT,
                preferred_element_type=jnp.float32).astype(c_kv.dtype)
            k = jnp.concatenate([k_nope, k_rope], axis=1)
            v = jnp.dot(c_kv, wuv_ref[g],
                        preferred_element_type=jnp.float32).astype(c_kv.dtype)
            for iq in range(c // block_q):
                rows = pl.ds(iq * block_q, block_q)
                q_lo = p0 + iq * block_q
                # the tile's first key against the block's last query, its
                # last key against the block's first
                seen = k_lo <= q_lo + (block_q - 1)
                whole = k_lo + (tile - 1) <= q_lo

                @pl.when(whole)
                def _():
                    fold(g, rows, k, v, q_lo, masked=False)

                @pl.when(jnp.logical_and(seen, jnp.logical_not(whole)))
                def _():
                    fold(g, rows, k, v, q_lo, masked=True)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        for g in range(g_heads):
            o_ref[:, g * dv:(g + 1) * dv] = (
                acc_ref[g] / l_ref[g]).astype(o_ref.dtype)


def mla_prefill_attention(q, lat, w_uk, w_uv, p0, *, scale: float,
                          kv_tile: int = 1024, block_q: int = 512,
                          heads_per_step: int = 4, interpret: bool = False):
    """Causal attention of one chunk's queries over a sequence's latent rows.

    q ``[C, H * (nope + W - r)]``, a head ``[q_nope | q_rope | 0]`` (unscaled),
    the queries at positions ``p0 .. p0 + C - 1``; lat ``[S, W]`` the
    sequence's cache rows ``[c_kv (r) | k_rope | 0]`` in position order, S a
    multiple of ``kv_tile`` and at least ``p0 + C``, the chunk's own rows
    among them (rows past ``p0 + C`` are never read); w_uk ``[H, nope, r]``;
    w_uv ``[H, r, v]``; p0 a scalar.  Returns ``[C, H * v]`` in q's dtype:
    ``softmax(scale * q k^T) v`` a head, float32 inside.

    ``block_q`` query rows share a score tile (the chunk, where it is
    narrower); ``heads_per_step`` heads share a grid step and its latent
    tile.  The defaults are what a v5e ran fastest at 128 heads of 128 + 64
    against 8,192 positions (PERF.md, PR 32): a tile of 1,024 keys against
    512 halves what a tile costs whatever its width (the rescaling of the
    accumulator, the max and the sum, a grid step).
    """
    c = q.shape[0]
    s_len, w = lat.shape
    h, nope, r = w_uk.shape
    dv = w_uv.shape[2]
    dq = nope + w - r
    if q.shape[1] != h * dq or w_uv.shape[:2] != (h, r):
        raise ValueError(
            f"queries of {q.shape[1]} columns for {h} heads of {nope} + "
            f"{w - r}, values {w_uv.shape}: not this cache row ({w}, of "
            f"which {r} latent)")
    if s_len % kv_tile:
        raise ValueError(f"{s_len} latent rows are not whole tiles of "
                         f"{kv_tile}")
    block_q = min(block_q, c)
    g_heads = heads_per_step
    while h % g_heads:
        g_heads -= 1
    if c % block_q:
        raise ValueError(f"a chunk of {c} is not whole blocks of {block_q}")
    p0 = jnp.asarray(p0, jnp.int32)
    scalars = jnp.stack([p0, (p0 + c - 1) // kv_tile + 1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h // g_heads, s_len // kv_tile),
        in_specs=[
            pl.BlockSpec((c, g_heads * dq), lambda i, t, s: (0, i)),
            # a tile past the live prefix: the block already here
            pl.BlockSpec((kv_tile, w),
                         lambda i, t, s: (jnp.minimum(t, s[1] - 1), 0)),
            pl.BlockSpec((g_heads, nope, r), lambda i, t, s: (i, 0, 0)),
            pl.BlockSpec((g_heads, r, dv), lambda i, t, s: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((c, g_heads * dv), lambda i, t, s: (0, i)),
        scratch_shapes=[
            pltpu.VMEM((g_heads, c, 1), jnp.float32),
            pltpu.VMEM((g_heads, c, 1), jnp.float32),
            pltpu.VMEM((g_heads, c, dv), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, r=r, scale=scale, block_q=block_q)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, h * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="mla_prefill_attention",  # the kernel's name in a profiler trace
    )(scalars, q, lat.astype(q.dtype), w_uk.astype(q.dtype),
      w_uv.astype(q.dtype))
