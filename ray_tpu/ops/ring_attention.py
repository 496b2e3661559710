"""Ring attention: exact causal attention over a context-parallel mesh axis.

Long-context support is a first-class capability of this framework (the
reference has none natively — SURVEY.md §5 "Long-context / sequence
parallelism: Absent"). The design is the TPU-idiomatic one: each device in
the ``axis_name`` ring holds a sequence shard of Q, K, V; K/V shards rotate
around the ring via ``lax.ppermute`` (which XLA compiles to ICI
neighbour-to-neighbour sends), and partial attention outputs are merged with
the online-softmax (log-sum-exp) rule. Compute of step i overlaps with the
communication of step i+1 thanks to XLA's async collective scheduling.

The function is pure jnp + ppermute, so it is differentiable end-to-end
(ppermute's transpose is the inverse ppermute) and can be used directly
inside a `shard_map`-ped training step under `jax.checkpoint`.

Use ``ray_tpu.parallel`` mesh helpers to build the mesh; the conventional
context axis name is "context".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _chunk_attention(q, k, v, q_offset, k_offset, causal, scale):
    """Partial attention of a Q shard against one K/V shard.

    q: [B, Sq, H, D]; k, v: [B, Sk, Hkv, D]. Returns (o_unnorm, m, l) with
    o_unnorm: [B, Sq, H, D] fp32 (sum of exp(s - m) @ v), m/l: [B, Sq, H, 1].
    Offsets are the global sequence positions of element 0 of each shard
    (traced values — they depend on the ring step and device index).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    n_rep = hq // hkv
    if n_rep > 1:
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, k.shape[1], hkv, n_rep, d)).reshape(
            b, k.shape[1], hq, d
        )
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, v.shape[1], hkv, n_rep, d)).reshape(
            b, v.shape[1], hq, d
        )
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = k_offset + jnp.arange(k.shape[1])[None, :]
        mask = (qpos >= kpos)[None, None]  # [1, 1, Sq, Sk]
        s = jnp.where(mask, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        # mask-aware exp: fully-masked rows get p == 0 (not exp(0))
        p = jnp.where(mask, jnp.exp(s - m), 0.0)
    else:
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    # -> m, l to [B, Sq, H, 1]
    m = jnp.transpose(m, (0, 2, 1, 3))
    l = jnp.transpose(l, (0, 2, 1, 3))
    return o, m, l


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jnp.ndarray:
    """Exact attention over sequence shards distributed on ``axis_name``.

    Must be called inside `shard_map` (or `pjit`-manual) with ``axis_name``
    bound. q, k, v: local shards [B, S_local, H(:kv), D]; the global sequence
    is the concatenation over the ring in axis order. Returns the local
    output shard [B, S_local, H, D].
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, hq, d = q.shape
    if scale is None:
        scale = d ** -0.5
    q_offset = idx * s_local

    o = jnp.zeros((b, s_local, hq, d), jnp.float32)
    m = jnp.full((b, s_local, hq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, s_local, hq, 1), jnp.float32)

    kv = (k, v)
    perm = [(i, (i + 1) % n) for i in range(n)]
    for step in range(n):
        src = (idx - step) % n  # whose K/V shard we hold this step
        k_offset = src * s_local
        o_p, m_p, l_p = _chunk_attention(q, kv[0], kv[1], q_offset, k_offset, causal, scale)
        m_new = jnp.maximum(m, m_p)
        alpha = jnp.exp(m - m_new)
        alpha_p = jnp.exp(m_p - m_new)
        o = o * alpha + o_p * alpha_p
        l = l * alpha + l_p * alpha_p
        m = m_new
        if step != n - 1:
            kv = lax.ppermute(kv, axis_name, perm)
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)
