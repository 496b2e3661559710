"""Pallas decode attention over a paged LATENT cache (MLA, absorbed form).

A latent-attention cache keeps one row a position a layer, ``[c_kv | k_rope |
0]``, shared by every query head: the key of a position IS that row, and its
value is the row's first ``value_width`` columns.  Decode carries each head's
query into the latent space (``q~_i = [q_nope_i W_uk_i^T | q_rope_i | 0]``,
``models/pangu_moe.py``), so a token-step's attention is one "KV head" of the
cache's width read by all heads: ``s = q~ c^T``, softmax, ``o~ = p c[:, :value
width]``.

The kernel is ``ops/paged_attention.py``'s (PR 25) with that one head: a grid
step a batch row, the row's pages DMA'd HBM -> VMEM off the block table into
a double buffer, an online softmax over chunks of pages.  Every bound comes
from the row's own operands: a row with ``active == 0`` starts no DMA and
writes zeros, the chunk loop runs ``cdiv(nvalid, chunk tokens)`` times, inside
a chunk only the pages below ``cdiv(nvalid, bs)`` are fetched, and a row's
last chunk hides the fetch of the next decoding row's first.  One buffer
serves scores and values (the page is read once), and the scores' contraction
runs over the whole padded row, so nothing is sliced off a lane tile but the
value columns, which end on one (``value_width % 128 == 0``).

Pool layout: ``[L, NB, bs, W]``, W a multiple of 128 (576 values padded to
640).  What the algorithm must move a live position a layer is the 576 values
(1,152 B in bf16; the benchmark's ``model_math_mla_moe`` counts that), what
the kernel reads is the padded row (1,280 B).

In a profiler trace the kernel's instruction is named ``mla_paged_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(li_ref, tbl_ref, len_ref, act_ref, q_ref, c_hbm, o_ref,
            cbuf, sems, nxt_ref, *, dv, bs, cw, scale):
    """One grid step = one batch row.  cbuf: [2, CW, bs, W] double buffer;
    sems: [2, CW] DMA semaphores (buffer slot, page); nxt_ref: [2] SMEM,
    carried from row to row (the grid runs in order)."""
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    li = li_ref[0]
    nvalid = len_ref[b] + 1  # the freshly written token attends to itself
    nh = q_ref.shape[1]
    span_c = cw * bs
    n_pages = jnp.minimum(lax.div(nvalid + (bs - 1), bs), tbl_ref.shape[1])
    n_chunks = lax.div(n_pages + (cw - 1), cw)

    def decodes(row):
        return jnp.logical_and(act_ref[row] != 0, len_ref[row] >= 0)

    def page_copy(page, slot, j):
        return pltpu.make_async_copy(
            c_hbm.at[li, page], cbuf.at[slot, j], sems.at[slot, j])

    def each_page(lo, hi, fn):
        if isinstance(lo, int) and isinstance(hi, int):
            for j in range(lo, hi):
                fn(j)
        else:
            def body(j, carry):
                fn(j)
                return carry

            lax.fori_loop(lo, hi, body, 0)

    def start_chunk(row, c, slot, n):
        each_page(0, n, lambda j: page_copy(
            tbl_ref[row, c * cw + j], slot, j).start())

    def land_chunk(slot, n):
        # a wait needs the semaphore and the size, not the source
        each_page(0, n, lambda j: page_copy(0, slot, j).wait())

        def zero(j):
            # a page not fetched holds whatever an earlier chunk or row left
            # (NaN at worst); its positions get p == 0 exactly, and 0 * NaN
            # is NaN, so its rows (keys and values are one) are zeroed
            cbuf[slot, j] = jnp.zeros(cbuf.shape[2:], cbuf.dtype)

        each_page(n, cw, zero)

    def live_pages(c):
        return jnp.clip(n_pages - c * cw, 0, cw)

    def attend(c, slot, carry):
        m, l, acc = carry
        kc = cbuf[slot].reshape(span_c, cbuf.shape[-1])
        pos = c * span_c + lax.broadcasted_iota(jnp.int32, (1, span_c), 1)
        s = lax.dot_general(
            q_ref[0], kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, span_c]
        s = jnp.where(pos < nvalid, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, -1, keepdims=True)
        pv = lax.dot_general(
            p.astype(kc.dtype), kc[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [H, dv]
        return m_new, l, acc * corr + pv

    @pl.when(b == 0)
    def _():
        nxt_ref[0] = 0
        nxt_ref[1] = -1

    # a row's chunk 0 lands in the slot the decoding row before it left
    # free: nxt_ref[0] is that slot, nxt_ref[1] the row whose chunk 0 is
    # already on its way
    base = nxt_ref[0]

    def chunk(c, carry):
        slot = (c + base) & 1

        def all_live():
            start_chunk(b, c + 1, 1 - slot, cw)
            land_chunk(slot, cw)

        def row_end():
            start_chunk(b, c + 1, 1 - slot, live_pages(c + 1))

            @pl.when(c + 1 == n_chunks)
            def _():
                last = nrows - 1
                nb = lax.while_loop(
                    lambda r: jnp.logical_and(
                        r < nrows,
                        jnp.logical_not(decodes(jnp.minimum(r, last)))),
                    lambda r: r + 1, b + 1)

                @pl.when(nb < nrows)
                def _():
                    row = jnp.minimum(nb, last)
                    pages = lax.div(len_ref[row] + bs, bs)
                    start_chunk(row, 0, 1 - slot, jnp.minimum(pages, cw))
                    nxt_ref[1] = row

            land_chunk(slot, live_pages(c))

        lax.cond((c + 2) * cw <= n_pages, all_live, row_end)
        return attend(c, slot, carry)

    @pl.when(jnp.logical_not(decodes(b)))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(decodes(b))
    def _():
        @pl.when(nxt_ref[1] != b)
        def _():
            start_chunk(b, 0, base, live_pages(0))

        init = (jnp.full((nh, 1), -1e30, jnp.float32),
                jnp.zeros((nh, 1), jnp.float32),
                jnp.zeros((nh, dv), jnp.float32))
        _, l, acc = lax.fori_loop(0, n_chunks, chunk, init)
        nxt_ref[0] = (base + n_chunks) & 1
        o_ref[0] = acc / l


def mla_paged_decode_attention(q, pool, li, table, lengths, active=None, *,
                               value_width: int, scale: float,
                               interpret: bool = False):
    """Absorbed latent decode attention.

    q ``[B, H, W]`` (unscaled, zero in the pool's padding columns); pool
    ``[L, NB, bs, W]``; li scalar layer id; table ``[B, Wt]`` block ids;
    lengths ``[B]``: valid span = lengths + 1; active ``[B]``, nonzero for the
    rows that decode (None: all).  A row with ``active == 0`` (or a negative
    length) costs a grid step and returns zeros.  Returns ``o~ [B, H,
    value_width]`` float32: ``softmax(scale * q c^T) c[:, :value_width]`` over
    each row's live positions.
    """
    b, nh, w = q.shape
    bs = pool.shape[2]
    wt = table.shape[1]
    if pool.shape[3] != w or w % 128 or value_width % 128:
        raise ValueError(
            f"latent rows of {pool.shape[3]} (queries of {w}, values of "
            f"{value_width}) are not whole 128-lane tiles")
    # pages per compute chunk: span <= 256 tokens, and at least 2 chunks so
    # page DMA for chunk c+1 overlaps chunk c's compute (double buffer)
    cw = min(max(1, wt // 2), max(1, 256 // bs))
    while wt % cw:
        cw //= 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nh, w), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nh, value_width), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, cw, bs, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2, cw)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kern = functools.partial(_kernel, dv=value_width, bs=bs, cw=cw,
                             scale=scale)
    if active is None:
        active = jnp.ones_like(lengths)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, value_width), jnp.float32),
        # rows in order: each hands the next its buffer slot and first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_attention",  # the kernel's name in a profiler trace
    )(jnp.asarray(li, jnp.int32).reshape(1), table, lengths,
      active.astype(jnp.int32), q.astype(pool.dtype), pool)
