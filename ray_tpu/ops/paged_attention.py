"""Fused pallas paged-attention for decode: read ONLY each sequence's live
pages, no gather materialization.

The XLA fallback path in `models/llama.py:_paged_attend` materializes the
gathered span ([B, W*bs, kv, hd] twice, k and v) in HBM before the
attention einsums read it back — ~3x the span bytes of the information-
theoretic floor.  This kernel DMAs each sequence's pages HBM -> VMEM
directly off the block table (double-buffered, page-granular) and runs
flash-style GQA attention in VMEM, so the span is read exactly once for k
and once for v.

Its time follows the live pages of the decoding rows, not batch x table
width: the engine's table is ``max_batch_size`` rows by a power-of-two
bucket of the longest row, and in a serving step most of that is padding
(``decode_table_live_pct``).  A row with ``active == 0`` starts no DMA and
runs no chunk; a row's chunk loop runs ``cdiv(nvalid, chunk tokens)`` times;
inside a chunk only the pages below ``cdiv(nvalid, bs)`` are fetched and
waited for (the v rows of the others are zeroed, their scores masked before
exp); and a row's last chunk hides the fetch of the next decoding row's
first.  On a v5e at batch 64, 32 / 8 heads of 128, 16-token pages, table
width 128 (my chip run, PR 25; `benchmarks/paged_kernel_bench.py`): 20
decoding rows of 450 tokens among 44 idle ones 0.100 ms a call (0.955 before
this), 64 rows of 100 tokens 0.141 (0.945), 64 rows of 2,040 tokens 1.041
(1.051).  The static unroll over every chunk of every row that this
replaced skipped only the DMA of a dead chunk, and its masked compute was
what a call cost: 1.85 us a chunk, 512 chunks.

Pool layout (canonical, see `models/llama.py init_paged_kv_cache`):
[L, NB, bs, kv*hd] — one page is a contiguous [bs, kv*hd] slab whose
(sublane, lane) tiling is exact for bs % 8 == 0 and hd % 128 == 0, and a
kv head is a lane-aligned column slice.  Heads of 64 (a cached position
half as wide) are read two a tile, the kernel's body unchanged
(``_pair_heads``).

Reference capability boundary: the paged-attention kernel Ray LLM inherits
from vLLM (llm/_internal/serve/deployments/llm/vllm/vllm_models.py:177-186);
here a TPU pallas kernel over the native pool layout.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(li_ref, tbl_ref, len_ref, act_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, nxt_ref, *, kv, hd, bs, cw, scale):
    """One grid step = one batch row: DMA its live pages, flash-attend.

    kbuf/vbuf: [2, CW, bs, kv*hd] double buffers; sems: [2, 2, CW] DMA sems
    (dims: k/v, buffer slot, page); nxt_ref: [2] SMEM, carried from row to
    row (the grid runs in order).  Every bound comes from the row's own
    operands: a row with ``active == 0`` does nothing but write zeros, the
    chunk loop runs ``cdiv(nvalid, CW*bs)`` times, and inside a chunk only
    pages below ``cdiv(nvalid, bs)`` are fetched.
    """
    b = pl.program_id(0)
    nrows = pl.num_programs(0)
    li = li_ref[0]
    nvalid = len_ref[b] + 1  # freshly written token at position lengths[b]
    group = q_ref.shape[1] // kv
    span_c = cw * bs
    # never past the table, whatever `lengths` holds
    n_pages = jnp.minimum(lax.div(nvalid + (bs - 1), bs), tbl_ref.shape[1])
    n_chunks = lax.div(n_pages + (cw - 1), cw)

    def decodes(row):
        return jnp.logical_and(act_ref[row] != 0, len_ref[row] >= 0)

    def page_copies(page, slot, j):
        return [pltpu.make_async_copy(
            src.at[li, page], buf.at[slot, j], sems.at[i, slot, j])
            for src, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1))]

    def each_page(lo, hi, fn):
        """``fn(j)`` for pages ``lo <= j < hi`` of a chunk: unrolled where
        both bounds are static, a loop where they come from the row."""
        if isinstance(lo, int) and isinstance(hi, int):
            for j in range(lo, hi):
                fn(j)
        else:
            def body(j, carry):
                fn(j)
                return carry

            lax.fori_loop(lo, hi, body, 0)

    def start_chunk(row, c, slot, n):
        def start(j):
            for dma in page_copies(tbl_ref[row, c * cw + j], slot, j):
                dma.start()

        each_page(0, n, start)

    def land_chunk(slot, n):
        def wait(j):
            # a wait needs the semaphore and the size, not the source
            for dma in page_copies(0, slot, j):
                dma.wait()

        def zero(j):
            # a page not fetched holds whatever an earlier chunk or row
            # left (NaN at worst): its lanes get p == 0 exactly, and
            # 0 * NaN is NaN, so its v rows are zeroed.  Its k rows may
            # stay: their scores are replaced before exp.
            vbuf[slot, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

        each_page(0, n, wait)
        each_page(n, cw, zero)

    def live_pages(c):
        return jnp.clip(n_pages - c * cw, 0, cw)

    def attend(c, slot, carry):
        m, l, acc = (list(x) for x in carry)
        kc = kbuf[slot]  # [CW, bs, kv*hd]
        vc = vbuf[slot]
        pos = c * span_c + lax.broadcasted_iota(jnp.int32, (1, span_c), 1)
        mask = pos < nvalid
        for h in range(kv):
            kh = kc[:, :, h * hd:(h + 1) * hd].reshape(span_c, hd)
            vh = vc[:, :, h * hd:(h + 1) * hd].reshape(span_c, hd)
            qh = q_ref[0, h * group:(h + 1) * group, :]
            s = lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, span_c]
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m[h], jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m[h] - m_new)
            l[h] = l[h] * corr + jnp.sum(p, -1, keepdims=True)
            pv = lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [G, hd]
            acc[h] = acc[h] * corr + pv
            m[h] = m_new
        return tuple(m), tuple(l), tuple(acc)

    @pl.when(b == 0)
    def _():
        nxt_ref[0] = 0
        nxt_ref[1] = -1

    # a row's chunk 0 lands in the slot the decoding row before it left
    # free, so that row's last chunk can hide the fetch: nxt_ref[0] is that
    # slot, nxt_ref[1] the row whose chunk 0 is already on its way
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

        # this chunk and the next all live (every chunk of a long row but
        # its last two): one branch, every page unrolled
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

        init = (tuple(jnp.full((group, 1), -1e30, jnp.float32)
                      for _ in range(kv)),
                tuple(jnp.zeros((group, 1), jnp.float32) for _ in range(kv)),
                tuple(jnp.zeros((group, hd), jnp.float32) for _ in range(kv)))
        # nvalid >= 1 here: at least one chunk, and l >= 1
        _, l, acc = lax.fori_loop(0, n_chunks, chunk, init)
        nxt_ref[0] = (base + n_chunks) & 1
        for h in range(kv):
            o_ref[0, h * group:(h + 1) * group, :] = acc[h] / l[h]


def _odd_kv_head(nh: int, kv: int):
    """[nh] bool: the query head reads an odd kv head."""
    return (jnp.arange(nh) // (nh // kv)) % 2 == 1


def _pair_heads(q, kv: int):
    """Heads of 64 for a kernel that reads 128-lane heads: ``q [B, nh, 64]``
    -> ``[B, nh, 128]``, a query of an EVEN kv head in lanes 0..63 and one of
    an ODD kv head in lanes 64..127, zeros in the other half.  Two kv heads of
    64 lie side by side in the pool's ``kv * 64`` lanes, so the kernel, told
    the heads are 128 wide, sees ``kv / 2`` pair heads of twice the group: the
    zeros cancel the neighbour's keys in the scores, and of the 128 output
    lanes a query's own half is its answer (:func:`_unpair_heads`).  The pool
    stays 64 lanes a head (no padded byte at rest or in a handoff), the
    kernel's body is untouched and every tile it loads is whole; the price is
    score and value products twice as wide, on an MXU that decode leaves
    idle."""
    zeros = jnp.zeros_like(q)
    odd = _odd_kv_head(q.shape[1], kv)[None, :, None]
    return jnp.where(odd, jnp.concatenate([zeros, q], -1),
                     jnp.concatenate([q, zeros], -1))


def _unpair_heads(out, kv: int):
    """``[B, nh, 128]`` -> ``[B, nh, 64]``: each query's own half."""
    hd = out.shape[-1] // 2
    odd = _odd_kv_head(out.shape[1], kv)[None, :, None]
    return jnp.where(odd, out[..., hd:], out[..., :hd])


def paged_decode_attention(q, pk_all, pv_all, li, table, lengths,
                           active=None, interpret=False, scale=None,
                           name="paged_attention"):
    """GQA paged decode attention.

    q [B, nh, hd] (unscaled); pk/pv [L, NB, bs, kv*hd]; li scalar layer id;
    table [B, W] block ids; lengths [B] — valid span = lengths + 1 (the
    freshly written token attends to itself); active [B], nonzero for the
    rows that decode (None: all).  A row with ``active == 0`` (or a negative
    length) costs a grid step and returns zeros whatever its ``lengths``
    and table row hold.
    kv-head count is derived from the pool's folded last dim, so per-shard
    calls under shard_map (kv heads sharded over "tensor") need no extra
    plumbing.  ``scale`` multiplies the scores (None: ``1 / sqrt(hd)``).
    Heads of 64 are read two a 128-lane tile (:func:`_pair_heads`).  ``name``:
    the call's name in a profiler trace, for a caller whose layers of two
    kinds are to be told apart there.
    Returns [B, nh*hd] fp32, numerically matching
    `_paged_attend` on the active rows.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[2])
    if q.shape[2] == 64:
        kv64 = pk_all.shape[3] // 64
        if kv64 % 2:
            raise ValueError("heads of 64 are read in pairs: an even number "
                             f"of kv heads, not {kv64}")
        out = paged_decode_attention(
            _pair_heads(q, kv64), pk_all, pv_all, li, table, lengths, active,
            interpret, scale, name)
        b, nh = q.shape[:2]
        return _unpair_heads(out.reshape(b, nh, 128), kv64).reshape(b, nh * 64)
    b, nh, hd = q.shape
    kv = pk_all.shape[3] // hd  # per-shard kv heads under shard_map
    bs = pk_all.shape[2]
    w = table.shape[1]
    # pages per compute chunk: span <= 256 tokens, and at least 2 chunks so
    # page DMA for chunk c+1 overlaps chunk c's compute (double buffer)
    cw = min(max(1, w // 2), max(1, 256 // bs))
    while w % cw:
        cw //= 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nh, hd), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nh, hd), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, cw, bs, kv * hd), pk_all.dtype),
            pltpu.VMEM((2, cw, bs, kv * hd), pv_all.dtype),
            pltpu.SemaphoreType.DMA((2, 2, cw)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kern = functools.partial(
        _kernel, kv=kv, hd=hd, bs=bs, cw=cw, scale=scale)
    if active is None:
        active = jnp.ones_like(lengths)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), jnp.float32),
        # rows in order: each hands the next its buffer slot and first fetch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,  # the kernel's name in a profiler trace
    )(jnp.asarray(li, jnp.int32).reshape(1), table, lengths,
      active.astype(jnp.int32), q, pk_all, pv_all)
    return out.reshape(b, nh * hd)
