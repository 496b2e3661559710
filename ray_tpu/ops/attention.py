"""Attention ops: GQA scaled-dot-product attention.

Two paths behind one API:
  - reference jnp path (any backend; XLA fuses the softmax chain) — also the
    recompute path for the pallas kernel's backward,
  - pallas TPU flash-attention forward (``ray_tpu.ops.flash_attention``),
    selected automatically on TPU for supported shapes.

The reference framework has no attention op of its own (it delegates compute
to vLLM/torch engines — see SURVEY.md §2.3 Ray LLM); in a TPU-native stack
attention is a first-class framework op because the trainer, the serving
engine, and the long-context path all share it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv * n_rep, D] for grouped-query attention."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: jnp.ndarray | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Plain jnp attention. q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D].

    Softmax in fp32; logits materialized (O(S^2) memory) — use the flash path
    for long sequences. Supports GQA (Hq a multiple of Hkv) and optional
    segment masking (tokens attend only within equal segment ids — used for
    sequence packing).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    if scale is None:
        scale = d ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        # query i (at absolute position skv - sq + i) sees keys <= that position
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        mask = qpos >= kpos
    if segment_ids is not None:
        seg = segment_ids[:, :, None] == segment_ids[:, None, :]  # [B, Sq, Skv]
        seg = seg[:, None, :, :]
        mask = seg if mask is None else (mask[None, None] & seg)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "use_flash", "block_q", "block_k")
)
def multi_head_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: jnp.ndarray | None = None,
    scale: float | None = None,
    use_flash: bool | None = None,
    block_q: int = 512,
    block_k: int = 512,
) -> jnp.ndarray:
    """GQA attention, auto-selecting the pallas flash kernel on TPU.

    q: [B, Sq, Hq, D]; k, v: [B, Skv, Hkv, D]. Returns [B, Sq, Hq, D].
    """
    if use_flash is None:
        use_flash = (
            jax.default_backend() == "tpu"
            and segment_ids is None
            and q.shape[1] == k.shape[1]
            and q.shape[1] % 128 == 0
            and q.shape[-1] % 128 == 0
        )
    if use_flash:
        from ray_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k
        )
    return reference_attention(q, k, v, causal=causal, segment_ids=segment_ids, scale=scale)


def mesh_attention(q, k, v, *, mesh, batch_axes, causal: bool = True):
    """``multi_head_attention`` for activations sharded over ``mesh``
    (batch over ``batch_axes``, heads over ``"tensor"``).

    A Mosaic kernel has no GSPMD partitioning rule ("Mosaic kernels cannot
    be automatically partitioned"), so on a TPU backend, where the flash
    kernel is what gets selected, the call runs per shard under
    ``shard_map``: attention is independent per batch row and per kv-head
    group, so each shard's result is its slice of the whole.  Without a
    mesh, or off-TPU (the jnp path, which XLA partitions by itself), this
    is the plain call."""
    if mesh is None or jax.default_backend() != "tpu":
        return multi_head_attention(q, k, v, causal=causal)
    spec = P(batch_axes, None, "tensor", None)
    return jax.shard_map(
        functools.partial(multi_head_attention, causal=causal), mesh=mesh,
        in_specs=(spec,) * 3, out_specs=spec, check_vma=False)(q, k, v)
