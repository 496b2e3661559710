"""Fused pallas paged-attention for decode: read ONLY each sequence's live
pages, no gather materialization.

The XLA fallback path in `models/llama.py:_paged_attend` materializes the
gathered span ([B, W*bs, kv, hd] twice, k and v) in HBM before the
attention einsums read it back — ~3x the span bytes of the information-
theoretic floor.  This kernel DMAs each sequence's pages HBM -> VMEM
directly off the block table (double-buffered, page-granular) and runs
flash-style GQA attention in VMEM, so the span is read exactly once for k
and once for v.  Rows shorter than the bucketed table width skip the DMA
of chunks wholly beyond their live span (compute over those lanes still
runs, masked — it is VPU-cheap; the HBM traffic is what the skip saves).

Pool layout (canonical, see `models/llama.py init_paged_kv_cache`):
[L, NB, bs, kv*hd] — one page is a contiguous [bs, kv*hd] slab whose
(sublane, lane) tiling is exact for bs % 8 == 0 and hd % 128 == 0, and a
kv head is a lane-aligned column slice.

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


def _kernel(li_ref, tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, *, kv, hd, bs, cw, n_chunks, scale):
    """One grid step = one batch row: DMA its pages, flash-attend.

    kbuf/vbuf: [2, CW, bs, kv*hd] double buffers; sems: [2, 2, CW] DMA sems
    (dims: k/v, buffer slot, page).
    """
    b = pl.program_id(0)
    li = li_ref[0]
    nvalid = len_ref[b] + 1  # freshly written token at position lengths[b]
    group = q_ref.shape[1] // kv
    span_c = cw * bs

    def chunk_live(c):
        # chunk c holds positions [c*span_c, (c+1)*span_c): it has data to
        # fetch iff its first position is inside the row's live span.  Rows
        # shorter than the bucketed table width skip the dead pages' DMA
        # entirely (their lanes are masked in compute, so stale VMEM is
        # harmless: masked scores are replaced by -1e30 before exp).
        return c * span_c < nvalid

    def start_chunk(c, slot):
        dmas = []
        for j in range(cw):
            page = tbl_ref[b, c * cw + j]
            for src, buf, i in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                dmas.append(pltpu.make_async_copy(
                    src.at[li, page], buf.at[slot, j], sems.at[i, slot, j]))

        @pl.when(chunk_live(c))
        def _():
            for dma in dmas:
                dma.start()

        return dmas

    inflight = start_chunk(0, 0)
    m = [jnp.full((group, 1), -1e30, jnp.float32) for _ in range(kv)]
    l = [jnp.zeros((group, 1), jnp.float32) for _ in range(kv)]
    acc = [jnp.zeros((group, hd), jnp.float32) for _ in range(kv)]

    for c in range(n_chunks):
        slot = c % 2
        done, inflight = inflight, []
        if c + 1 < n_chunks:
            inflight = start_chunk(c + 1, (c + 1) % 2)

        @pl.when(chunk_live(c))
        def _():
            for dma in done:
                dma.wait()

        kc = kbuf[slot]  # [CW, bs, kv*hd]
        vc = vbuf[slot]
        pos = c * span_c + lax.broadcasted_iota(
            jnp.int32, (1, span_c), 1)
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
            # a DMA-skipped chunk's buffer may hold NaN garbage: p is
            # exactly 0 there, but 0 * NaN = NaN — zero the contribution
            pv = jnp.where(chunk_live(c), pv, 0.0)
            acc[h] = acc[h] * corr + pv
            m[h] = m_new

    for h in range(kv):
        o_ref[0, h * group:(h + 1) * group, :] = acc[h] / l[h]


def _paged_decode_attention(q, pk_all, pv_all, li, table, lengths,
                            interpret=False):
    b, nh, hd = q.shape
    kv = pk_all.shape[3] // hd  # per-shard kv heads under shard_map
    bs = pk_all.shape[2]
    w = table.shape[1]
    # pages per compute chunk: span <= 256 tokens, and at least 2 chunks so
    # page DMA for chunk c+1 overlaps chunk c's compute (double buffer)
    cw = min(max(1, w // 2), max(1, 256 // bs))
    while w % cw:
        cw //= 2
    n_chunks = w // cw
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
        ],
    )
    kern = functools.partial(
        _kernel, kv=kv, hd=hd, bs=bs, cw=cw, n_chunks=n_chunks,
        scale=1.0 / math.sqrt(hd))
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, hd), jnp.float32),
        interpret=interpret,
        name="paged_attention",  # the kernel's name in a profiler trace
    )(jnp.asarray(li, jnp.int32).reshape(1), table, lengths,
      q, pk_all, pv_all)
    return out.reshape(b, nh * hd)


def paged_decode_attention(q, pk_all, pv_all, li, table, lengths,
                           interpret=False):
    """GQA paged decode attention.

    q [B, nh, hd] (unscaled); pk/pv [L, NB, bs, kv*hd]; li scalar layer id;
    table [B, W] block ids; lengths [B] — valid span = lengths + 1 (the
    freshly written token attends to itself).  kv-head count is derived
    from the pool's folded last dim, so per-shard calls under shard_map
    (kv heads sharded over "tensor") need no extra plumbing.
    Returns [B, nh*hd] fp32, numerically matching `_paged_attend`.
    """
    return _paged_decode_attention(
        q, pk_all, pv_all, li, table, lengths, interpret=interpret)
