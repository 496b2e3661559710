"""Pallas TPU flash attention (forward + backward), GQA-aware.

Design (TPU-first, not a port of any CUDA kernel):
  - The grid is (batch*q_heads, num_q_blocks); K and V for the whole sequence
    are kept resident in VMEM per (batch, head) — at S=8k, D=128, bf16 that is
    4 MiB for K+V, well within the ~16 MiB VMEM budget. This removes the k-block
    grid dimension entirely: the online-softmax loop over key blocks is a
    `lax.fori_loop` inside the kernel, with a *dynamic* trip count that stops
    at the causal diagonal (no wasted passes over masked blocks).
  - TPU pallas grids execute sequentially, so the backward pass accumulates
    dK/dV directly into output refs that are revisited across q-block (and,
    for GQA, across the q-heads sharing a kv head) iterations.
  - Longer-than-VMEM sequences are the job of ring attention
    (ray_tpu.ops.ring_attention), which wraps this kernel per shard.

The matching capability in the reference framework is delegated to external
torch engines (SURVEY.md §5 "long-context: absent natively").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k, causal, seq_len, block_q):
    j = pl.program_id(1)
    q = q_ref[:]
    d = q.shape[-1]
    nk = seq_len // block_k
    if causal:
        # highest key block that intersects rows [j*bq, (j+1)*bq)
        hi = lax.div((j + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, nk)
    else:
        hi = nk

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    def body(kb, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    acc, m, l = lax.fori_loop(0, hi, body, (acc0, m0, l0))
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    # lse replicated across the 128-lane minor dim (TPU block tiling needs a
    # 128-multiple minor axis; same layout as the in-tree kernel's residuals)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape).astype(lse_ref.dtype)


def _bwd_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
    dq_ref, dk_ref, dv_ref,
    *, scale, block_k, causal, seq_len, block_q, n_rep,
):
    bh = pl.program_id(0)
    j = pl.program_id(1)
    d = q_ref.shape[-1]

    @pl.when((j == 0) & (bh % n_rep == 0))
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    q = q_ref[:]
    do = do_ref[:].astype(jnp.float32)
    o = o_ref[:].astype(jnp.float32)
    lse = lse_ref[:, 0:1]  # [bq, 1] (replicated across lanes; take lane 0)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)  # [bq, 1]

    nk = seq_len // block_k
    if causal:
        hi = lax.div((j + 1) * block_q + block_k - 1, block_k)
        hi = jnp.minimum(hi, nk)
    else:
        hi = nk

    def body(kb, dq_acc):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]; masked entries underflow to 0
        # dV[kb] += P^T @ dO
        dv_c = jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dv_ref[pl.ds(kb * block_k, block_k), :] += dv_c
        # dP = dO @ V^T ; dS = P * (dP - delta) * scale
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale  # [bq, bk]
        # dQ += dS @ K
        dq_acc = dq_acc + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dK[kb] += dS^T @ Q
        dk_c = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_ref[pl.ds(kb * block_k, block_k), :] += dk_c
        return dq_acc

    dq = lax.fori_loop(0, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = dq


def _flash_fwd(q3, k3, v3, *, scale, causal, block_q, block_k, n_rep, interpret):
    bh, s, d = q3.shape
    bh_kv = k3.shape[0]
    nq = s // block_q
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_k=block_k, causal=causal, seq_len=s, block_q=block_q
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, 128), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q3, k3, v3)
    return o, lse


def _flash_bwd(q3, k3, v3, o, lse, do, *, scale, causal, block_q, block_k, n_rep, interpret):
    bh, s, d = q3.shape
    bh_kv = k3.shape[0]
    nq = s // block_q
    kernel = functools.partial(
        _bwd_kernel, scale=scale, block_k=block_k, causal=causal,
        seq_len=s, block_q=block_q, n_rep=n_rep,
    )
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, 128), lambda b, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b // n_rep, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh_kv, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh_kv, s, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd",  # one fused kernel: dq, dk and dv
    )(q3, k3, v3, o, do, lse)
    return dq, dk, dv


@functools.lru_cache(maxsize=64)
def _make_flash(scale, causal, block_q, block_k, n_rep, interpret):
    @jax.custom_vjp
    def f(q3, k3, v3):
        o, _ = _flash_fwd(
            q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, n_rep=n_rep, interpret=interpret,
        )
        return o

    def f_fwd(q3, k3, v3):
        o, lse = _flash_fwd(
            q3, k3, v3, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, n_rep=n_rep, interpret=interpret,
        )
        return o, (q3, k3, v3, o, lse)

    def f_bwd(res, do):
        q3, k3, v3, o, lse = res
        dq, dk, dv = _flash_bwd(
            q3, k3, v3, o, lse, do, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_rep=n_rep, interpret=interpret,
        )
        return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention. q: [B, S, Hq, D]; k, v: [B, S, Hkv, D] -> [B, S, Hq, D].

    Requires S divisible by the block sizes (blocks are clipped to S first).
    Differentiable (custom VJP with a pallas backward kernel).
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq len {s} must be divisible by block sizes ({block_q}, {block_k})")

    # [B, S, H, D] -> [B*H, S, D] with heads-major layout
    q3 = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    k3 = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    v3 = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    f = _make_flash(float(scale), bool(causal), block_q, block_k, n_rep, interpret)
    o = f(q3, k3, v3)
    return o.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
