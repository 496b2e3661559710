"""Llama-family decoder LM, TPU-first.

Design choices (vs the reference's torch/CUDA delegation):
  - pure-functional params pytree; layers *stacked* on a leading axis and
    iterated with `lax.scan` — one compiled layer body, O(1) compile time in
    depth, and `jax.checkpoint` inside the scan body gives per-layer
    rematerialisation (HBM ⇄ FLOPs trade, SURVEY.md "HBM bandwidth").
  - GQA attention via ray_tpu.ops (pallas flash kernel on TPU; ring
    attention over the "context" mesh axis for long sequences).
  - sharding expressed as a PartitionSpec tree (param_specs) over the
    canonical mesh axes (data/fsdp/context/tensor); XLA inserts all
    collectives (all-gather for fsdp params, psum for tensor partials).
  - matmuls in bf16 with fp32 accumulation (MXU native); norms/softmax fp32.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import mesh_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies
from ray_tpu.parallel.mesh import BATCH_AXES

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    # what the per-layer checkpoint keeps for the backward pass:
    #   "full" — nothing_saveable: minimum HBM, one extra fwd of recompute
    #   "attn" — keep the attention block's output (checkpoint_name'd):
    #            +B*S*D bf16 per layer of HBM buys skipping the flash-
    #            attention recompute in bwd — the best FLOPs/byte trade here
    #   "dots" — dots_with_no_batch_dims_saveable: every GEMM output kept;
    #            fastest bwd, fits only when activations are small vs HBM
    remat_policy: str = "full"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_dim, self.vocab_size
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f + 2 * d
        head = 0 if self.tie_embeddings else d * v
        return v * d + self.n_layers * per_layer + d + head

    # ---- presets ----
    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized config: runs in milliseconds on a CPU mesh."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 128)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("ffn_dim", 256)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


def init_params(cfg: LlamaConfig, key: jax.Array) -> Params:
    """Initialize a stacked-layers params pytree."""
    d, f = cfg.dim, cfg.ffn_dim
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim
    L = cfg.n_layers
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 10)
    dt = cfg.param_dtype

    def norm_(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    params: Params = {
        "embed": norm_(ks[0], (cfg.vocab_size, d), std),
        "layers": {
            "attn_norm": jnp.ones((L, d), dt),
            "wq": norm_(ks[1], (L, d, hq), std),
            "wk": norm_(ks[2], (L, d, hkv), std),
            "wv": norm_(ks[3], (L, d, hkv), std),
            "wo": norm_(ks[4], (L, hq, d), out_std),
            "mlp_norm": jnp.ones((L, d), dt),
            "w_gate": norm_(ks[5], (L, d, f), std),
            "w_up": norm_(ks[6], (L, d, f), std),
            "w_down": norm_(ks[7], (L, f, d), out_std),
        },
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_(ks[8], (d, cfg.vocab_size), std)
    return params


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec tree matching init_params' structure.

    Megatron-style TP over the "tensor" axis; parameters additionally sharded
    over "fsdp" on their non-tensor dim (XLA all-gathers per layer).
    """
    specs: Params = {
        "embed": P("tensor", "fsdp"),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, "fsdp", "tensor"),
            "wk": P(None, "fsdp", "tensor"),
            "wv": P(None, "fsdp", "tensor"),
            "wo": P(None, "tensor", "fsdp"),
            "mlp_norm": P(None, None),
            "w_gate": P(None, "fsdp", "tensor"),
            "w_up": P(None, "fsdp", "tensor"),
            "w_down": P(None, "tensor", "fsdp"),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("fsdp", "tensor")
    return specs


def inference_param_specs(cfg: LlamaConfig) -> Params:
    """TP-only PartitionSpec tree for serving (no fsdp axis: inference has no
    optimizer state to shard, and per-layer fsdp all-gathers would serialize
    the latency-critical decode step).

    Megatron layout over the "tensor" axis: attention/FFN projections are
    column-sharded on their output dim and row-sharded back (XLA inserts the
    psum), the embedding table is vocab-sharded, and the LM head column-
    sharded so logits come out vocab-sharded too.
    reference: llm/_internal/serve/deployments/llm/vllm/vllm_models.py:177-186
    (TP degree wired from engine_kwargs into the vLLM engine).
    """
    specs: Params = {
        "embed": P("tensor", None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tensor"),
            "wk": P(None, None, "tensor"),
            "wv": P(None, None, "tensor"),
            "wo": P(None, "tensor", None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "tensor"),
            "w_up": P(None, None, "tensor"),
            "w_down": P(None, "tensor", None),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tensor")
    return specs


def _constraint(x, spec, mesh):
    if mesh is None:
        return x
    return lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def _remat_policy(cfg):
    policies = {
        "full": jax.checkpoint_policies.nothing_saveable,
        "attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    }
    try:
        return policies[cfg.remat_policy]
    except KeyError:
        raise ValueError(
            f"remat_policy={cfg.remat_policy!r} — must be one of {sorted(policies)}"
        ) from None


def _layer(cfg: LlamaConfig, x, lp, cos, sin, mesh, context_parallel):
    """One transformer block. x: [B, S, D]."""
    b, s, d = x.shape
    cdt = cfg.compute_dtype
    seq_axis = "context" if context_parallel else None

    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = (h @ lp["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
        q = _constraint(q, P(BATCH_AXES, seq_axis, "tensor", None), mesh)
        k = _constraint(k, P(BATCH_AXES, seq_axis, "tensor", None), mesh)
        if context_parallel:
            # positions are global: offset by this shard's slot in the ring.
            # rope is applied inside the shard_map so positions line up.
            def attn_fn(q_, k_, v_):
                idx = lax.axis_index("context")
                s_local = q_.shape[1]
                pos = idx * s_local + jnp.arange(s_local)
                q_r = apply_rope(q_, cos, sin, positions=pos)
                k_r = apply_rope(k_, cos, sin, positions=pos)
                return ring_attention(q_r, k_r, v_, "context", causal=True)

            attn = jax.shard_map(
                attn_fn,
                mesh=mesh,
                axis_names={"context"},
                in_specs=(P(None, "context"),) * 3,
                out_specs=P(None, "context"),
            )(q, k, v)
        else:
            q = apply_rope(q, cos[:s], sin[:s])
            k = apply_rope(k, cos[:s], sin[:s])
            attn = mesh_attention(q, k, v, mesh=mesh, batch_axes=BATCH_AXES)
        attn = attn.reshape(b, s, cfg.n_heads * cfg.head_dim)
        attn = checkpoint_name(attn, "attn_out")
        x = x + (attn @ lp["wo"].astype(cdt))
        x = _constraint(x, P(BATCH_AXES, seq_axis, None), mesh)

    with jax.named_scope("ffn"):
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        gate = h @ lp["w_gate"].astype(cdt)
        up = h @ lp["w_up"].astype(cdt)
        ffn = (jax.nn.silu(gate) * up) @ lp["w_down"].astype(cdt)
        x = x + ffn
    return _constraint(x, P(BATCH_AXES, seq_axis, None), mesh)


def forward(
    cfg: LlamaConfig,
    params: Params,
    tokens: jnp.ndarray,
    *,
    mesh: Optional[Mesh] = None,
    context_parallel: bool = False,
    rope_cache: Optional[tuple] = None,
) -> jnp.ndarray:
    """Token ids [B, S] -> logits [B, S, V] (fp32)."""
    if rope_cache is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    else:
        cos, sin = rope_cache
    seq_axis = "context" if context_parallel else None
    # See models/moe.py: the table's fsdp sharding must not propagate through
    # the token gather (involuntary-full-remat reshard otherwise). Vocab dim
    # stays TP-sharded; the embed dim is all-gathered over fsdp for the gather.
    emb = _constraint(params["embed"], P("tensor", None), mesh)
    x = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    x = _constraint(x, P(BATCH_AXES, seq_axis, None), mesh)

    layer = partial(_layer, cfg, cos=cos, sin=sin, mesh=mesh, context_parallel=context_parallel)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=_remat_policy(cfg))

    def body(x, lp):
        return layer(x, lp), None

    x, _ = lax.scan(body, x, params["layers"])
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(cfg.compute_dtype)).astype(jnp.float32)
    return _constraint(logits, P(BATCH_AXES, seq_axis, "tensor"), mesh)


def loss_fn(
    cfg: LlamaConfig,
    params: Params,
    tokens: jnp.ndarray,
    *,
    loss_mask: Optional[jnp.ndarray] = None,
    mesh: Optional[Mesh] = None,
    context_parallel: bool = False,
    rope_cache: Optional[tuple] = None,
) -> jnp.ndarray:
    """Next-token cross-entropy (mean over unmasked positions)."""
    logits = forward(
        cfg, params, tokens, mesh=mesh, context_parallel=context_parallel,
        rope_cache=rope_cache,
    )
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if loss_mask is not None:
        m = loss_mask[:, 1:].astype(nll.dtype)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Paged KV cache programs (reference capability boundary: the paged-attention
# engine Ray LLM gets by delegating to vLLM, vllm_models.py:177-186 — here
# TPU-native).  The cache is a POOL of fixed-size blocks laid out
# [L, num_blocks, block_size, kv*hd]: block-major, so one block is a
# contiguous [bs, kv*hd] slab — a table gather moves whole slabs, a pallas
# page DMA lands on perfect (sublane, lane) tiles with zero padding, and a
# kv head is a lane-aligned column slice; each sequence owns a host-side
# list of block ids, shipped to the device as a padded block TABLE [B, W].
# All shapes static: W is bucketed, so programs recompile only per (B, W)
# bucket.
#
# The pool rides the layer scan as CARRY; every per-layer touch is a SINGLE
# fused XLA gather/scatter whose leading index is the (scalar) layer id —
# `pool[li, table]` / `pool.at[li, blk, off].set(...)` — so no layer slice
# is ever materialized and the pool is never restacked.  (The previous
# xs/ys design restacked the full pool every token-step: 6.8 ms of the
# 11.5 ms/token-step at b32 on v5e in round 5's notes, a toy model and a
# script since removed; not reproduced on this round's code.)
# Sharding: the folded kv-head axis shards over "tensor" (as wk/wv's
# columns do), layer axis over "pipeline", block/table axes replicated.
# ---------------------------------------------------------------------------


def init_paged_kv_cache(cfg: LlamaConfig, num_blocks: int, block_size: int,
                        dtype=None) -> Dict[str, jnp.ndarray]:
    """Block-pool KV cache shared by all sequences; HBM ∝ blocks in use."""
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, num_blocks, block_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_kv_cache_spec() -> Dict[str, P]:
    # the folded kv*hd dim shards over "tensor" as contiguous head groups
    spec = P(None, None, None, "tensor")
    return {"k": spec, "v": spec}


@dataclasses.dataclass(frozen=True)
class TPPlan:
    """Planner-routed tensor-parallel collectives for the paged inference
    programs.

    When a ``TPPlan`` is passed to ``decode_step_paged`` /
    ``decode_window_paged`` / ``prefill_chunk_paged``, the two per-layer
    partial-sum reductions (attention output @ wo and FFN @ w_down) run as
    EXPLICIT shard_map programs executing the α-β planner's chosen
    algorithm instead of GSPMD's implicit psum: ``flat`` (one fused psum —
    the latency-bound small-message winner), ``ring`` (psum_scatter +
    all_gather, bandwidth-optimal), or ``tree`` (recursive
    halving-doubling via ppermute, pow2 worlds).  ``flat`` and ``ring``
    are bit-identical to the implicit-psum path (same per-rank partials,
    same summation order); ``tree`` pairs ranks differently and may
    differ in float ULPs.

    ``overlap`` chains each collective's output through a scalar token
    with ``lax.optimization_barrier`` — identity numerics, but the
    explicit stage boundary lets XLA's latency-hiding scheduler start the
    next layer's compute under the allreduce, exactly as
    ``make_train_step`` does for bucketed gradient syncs.
    """

    mesh: Any
    algorithm: str = "flat"
    overlap: bool = True
    axis: str = "tensor"


def _tp_allreduce_local(v, axis: str, world: int, algorithm: str):
    """In-shard_map allreduce of a partial sum ``v`` by the planned
    algorithm.  Ring/tree operate on the trailing (feature) dim, which the
    engine-mesh validation guarantees divides by the world size."""
    if world <= 1:
        return v
    if algorithm == "ring":
        s = lax.psum_scatter(v, axis, scatter_dimension=v.ndim - 1,
                             tiled=True)
        return lax.all_gather(s, axis, axis=v.ndim - 1, tiled=True)
    if algorithm == "tree" and not (world & (world - 1)):
        # recursive halving-doubling over the flattened payload (adapted
        # from xla_group.build_tree_allreduce): log2(n) pairwise halving
        # rounds, then doubling in bit order
        shp = v.shape
        cur = v.reshape(-1)
        idx = lax.axis_index(axis)
        mask = world // 2
        perms = []
        while mask >= 1:
            perms.append([(i, i ^ mask) for i in range(world)])
            mask //= 2
        for perm in perms:
            m = perm[0][0] ^ perm[0][1]
            half = cur.shape[0] // 2
            lo, hi = cur[:half], cur[half:]
            bit = (idx & m) != 0
            send = jnp.where(bit, lo, hi)
            keep = jnp.where(bit, hi, lo)
            cur = keep + lax.ppermute(send, axis, perm)
        for perm in reversed(perms):
            m = perm[0][0] ^ perm[0][1]
            bit = (idx & m) != 0
            recv = lax.ppermute(cur, axis, perm)
            cur = jnp.where(bit, jnp.concatenate([recv, cur]),
                            jnp.concatenate([cur, recv]))
        return cur.reshape(shp)
    return lax.psum(v, axis)


def _tp_out_proj(a, w, tp_plan: Optional["TPPlan"], token):
    """Output projection ``a @ w`` with the contraction dim sharded over
    the tensor axis.  ``tp_plan=None``: plain matmul (GSPMD inserts the
    psum implicitly).  Otherwise the per-rank partial matmul + planned
    allreduce run explicitly under shard_map, and when overlapping the
    result is chained through ``token`` (optimization_barrier — identity
    numerics, explicit stage boundary).  Returns (out, token)."""
    if tp_plan is None:
        return a @ w, token
    mesh, axis = tp_plan.mesh, tp_plan.axis
    world = int(mesh.shape.get(axis, 1))
    if world <= 1:
        return a @ w, token
    def body(a_, w_):
        return _tp_allreduce_local(a_ @ w_, axis, world, tp_plan.algorithm)

    a_spec = P(*([None] * (a.ndim - 1) + [axis]))
    out = jax.shard_map(body, mesh=mesh, in_specs=(a_spec, P(axis, None)),
                        out_specs=P(*([None] * a.ndim)),
                        check_vma=False)(a, w)
    if token is not None:
        out, token = lax.optimization_barrier((out, token))
    return out, token


def _paged_attend(cfg: LlamaConfig, q, ck, cv, span_mask, scale=None):
    """GQA attention of q [B, T, nh, hd] against gathered spans ck/cv
    [B, S, kv, hd]; span_mask [B, T, S] True = visible.  ``scale``: what
    the scores are multiplied by (None: ``1 / sqrt(head_dim)``)."""
    b, t = q.shape[:2]
    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, t, cfg.n_kv_heads, group, cfg.head_dim)
    # bf16 operands, fp32 accumulate: no full-span fp32 cache copies
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck,
                        preferred_element_type=jnp.float32)
    if scale is None:
        scores = scores / math.sqrt(cfg.head_dim)
    else:
        scores = scores * scale
    scores = jnp.where(span_mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bkgts,bskd->btkgd", probs.astype(ck.dtype), cv,
                      preferred_element_type=jnp.float32)
    return attn.reshape(b, t, cfg.n_heads * cfg.head_dim)


# KV positions one iteration of the prefill chunk's attention loop attends
# (whole pages of the table).  Chosen on the chip among 256, 512 and 1024;
# PERF.md section 6 (PR 28) has the readings.
PREFILL_KV_TILE = 512


def _prefill_attend_tiles(cfg: LlamaConfig, q, pk_all, pv_all, li, row,
                          positions, tile: int, scale=None):
    """Causal GQA attention of one chunk's queries q [C, nh, hd], at global
    ``positions`` [C] (rising), over the sequence's KV in the pool.

    ``row`` [n * tile/bs] is the sequence's block table, whole tiles wide.
    A device loop with the DYNAMIC trip count cdiv(positions[-1] + 1, tile)
    gathers one tile's pages of layer ``li`` an iteration and folds it into
    an online softmax (float32 scores from bf16 operands, float32 running
    max, sum and accumulator): work and HBM traffic follow the live prefix,
    and table entries in tiles past it are never read.  Inside a visited
    tile, positions past a query's own are masked to exactly zero weight;
    position 0 is visible to every query, so after the first tile every
    running max is a real score.  ``scale`` multiplies the scores (None:
    ``1 / sqrt(head_dim)``; ``cfg`` is read for its head counts and widths
    alone, so another family's config with the same names does).  Returns
    [C, nh * hd] float32.
    """
    c = q.shape[0]
    bs = pk_all.shape[2]
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    group = cfg.n_heads // kv
    pages = tile // bs
    qg = q.reshape(c, kv, group, hd)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    offs = jnp.arange(tile)

    def fold(i, state):
        m, l, acc = state
        blocks = lax.dynamic_slice(row, (i * pages,), (pages,))
        ck = pk_all[li, blocks].reshape(tile, kv, hd)
        cv = pv_all[li, blocks].reshape(tile, kv, hd)
        s = jnp.einsum("ckgd,skd->kgcs", qg, ck,
                       preferred_element_type=jnp.float32) * scale
        visible = (i * tile + offs)[None, :] <= positions[:, None]  # [C, T]
        s = jnp.where(visible, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = alpha * l + p.sum(axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "kgcs,skd->kgcd", p.astype(cv.dtype), cv,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    state = (jnp.full((kv, group, c), -1e30, jnp.float32),
             jnp.zeros((kv, group, c), jnp.float32),
             jnp.zeros((kv, group, c, hd), jnp.float32))
    _, l, acc = lax.fori_loop(0, positions[-1] // tile + 1, fold, state)
    attn = acc / l[..., None]
    return attn.transpose(2, 0, 1, 3).reshape(c, cfg.n_heads * hd)


def paged_kernel_supported(cfg: LlamaConfig) -> bool:
    """Whether the fused pallas paged-attention kernel applies: TPU backend
    and lane-aligned head_dim.  On a TPU backend a kernel that cannot be
    imported is an error, never a reason to take the gather path."""
    if jax.default_backend() != "tpu":
        return False
    if cfg.head_dim % 128:
        return False
    from ray_tpu.ops.paged_attention import (  # noqa: F401
        paged_decode_attention,
    )

    return True


def decode_step_paged(cfg: LlamaConfig, params: Params, tokens: jnp.ndarray,
                      pool: Dict[str, jnp.ndarray], table: jnp.ndarray,
                      lengths: jnp.ndarray, *,
                      rope_cache: Optional[tuple] = None,
                      use_kernel: bool = False, mesh=None,
                      kernel_interpret: bool = False,
                      tp_plan: Optional[TPPlan] = None,
                      active: Optional[jnp.ndarray] = None, slot_state=None):
    """One-token decode for every slot, KV in a paged pool.

    tokens [B] int32; table [B, W] block ids covering each slot's sequence
    (host guarantees coverage through position lengths[b]); lengths [B].
    ``use_kernel`` (static): pallas fused paged-attention — reads ONLY each
    sequence's live pages instead of materializing the XLA block gather
    (0.10 ms a layer-call for 20 decoding rows of 450 tokens in a 64 x 128
    table on a v5e, 1.04 for 64 full rows: ops/paged_attention.py has the
    record; against the gather path: not measured).  ``active`` [B] (kernel
    path only; None: all): rows with 0 do no attention work and get zeros
    for it, so their logits mean nothing; the engine's decode chunk passes
    its scan carry, in which a row that finished mid-chunk is already 0.
    With ``mesh``, the kernel runs under shard_map with kv heads sharded
    over the "tensor" axis, so it composes with TP.  With ``tp_plan``, the per-layer partial-sum
    reductions route through the planner's chosen algorithm explicitly
    (see :class:`TPPlan`).  Returns (logits [B, V] fp32, updated pool, ``{}``,
    None): the family seam's four (models/family.py), of which this family
    has no slot state (``slot_state`` is not read) and books no counters.
    """
    del slot_state
    if rope_cache is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    else:
        cos, sin = rope_cache
    b = tokens.shape[0]
    bs = pool["k"].shape[2]
    w = table.shape[1]
    cdt = cfg.compute_dtype
    bidx = jnp.arange(b)
    cur_blk = table[bidx, lengths // bs]  # [B] physical block of the write
    cur_off = lengths % bs
    if use_kernel:  # masks from `lengths` internally
        active = jnp.ones_like(lengths) if active is None else active
    else:
        span_mask = (jnp.arange(w * bs)[None, None, :]
                     <= lengths[:, None, None])  # [B, 1, W*bs]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    overlap = tp_plan is not None and tp_plan.overlap

    def body(carry, inp):
        # pool rides the CARRY; the scalar layer id fuses into every
        # gather/scatter's index vector, so no [li] slice is materialized
        # and no per-step restack happens (see module comment)
        if overlap:
            x, pk_all, pv_all, tok = carry
        else:
            (x, pk_all, pv_all), tok = carry, None
        lp, li = inp
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            # the barrier keeps the two projections plain [B, dim] x [dim, n]
            # products: without it the compiler folds the head reshape and
            # the rotation's split into them as one product a head, which
            # wants wq and wk transposed, and it transposes the whole
            # stacked weights once a dispatch (PERF.md section 6, PR 30)
            q, k = lax.optimization_barrier(
                (h @ lp["wq"].astype(cdt), h @ lp["wk"].astype(cdt)))
            q = q.reshape(b, 1, cfg.n_heads, cfg.head_dim)
            k = k.reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"].astype(cdt)).reshape(b, 1, cfg.n_kv_heads, cfg.head_dim)
            q = apply_rope(q, cos, sin, positions=lengths[:, None])
            k = apply_rope(k, cos, sin, positions=lengths[:, None])[:, 0]
            pk_all = pk_all.at[li, cur_blk, cur_off].set(
                k.reshape(b, -1).astype(pk_all.dtype))
            pv_all = pv_all.at[li, cur_blk, cur_off].set(
                v[:, 0].reshape(b, -1).astype(pv_all.dtype))
            if use_kernel:
                from ray_tpu.ops.paged_attention import paged_decode_attention

                kern = partial(paged_decode_attention,
                               interpret=kernel_interpret)
                if mesh is not None and mesh.shape.get("tensor", 1) > 1:
                    t = P(None, None, None, "tensor")
                    kern = jax.shard_map(
                        kern, mesh=mesh,
                        in_specs=(P(None, "tensor", None), t, t,
                                  P(), P(), P(), P()),
                        out_specs=P(None, "tensor"), check_vma=False)
                attn = kern(q[:, 0], pk_all, pv_all, li, table, lengths,
                            active)
            else:
                ck = pk_all[li, table].reshape(b, w * bs, cfg.n_kv_heads,
                                               cfg.head_dim)
                cv = pv_all[li, table].reshape(b, w * bs, cfg.n_kv_heads,
                                               cfg.head_dim)
                attn = _paged_attend(cfg, q, ck, cv, span_mask)[:, 0]
            out, tok = _tp_out_proj(attn.astype(cdt), lp["wo"].astype(cdt),
                                    tp_plan, tok)
            x = x + out
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            gated = (jax.nn.silu(h @ lp["w_gate"].astype(cdt))
                     * (h @ lp["w_up"].astype(cdt)))
            ffn, tok = _tp_out_proj(gated, lp["w_down"].astype(cdt),
                                    tp_plan, tok)
            carry = (x + ffn, pk_all, pv_all)
        return (carry + (tok,) if overlap else carry), None

    carry0 = (x, pool["k"], pool["v"])
    if overlap:
        carry0 = carry0 + (jnp.zeros((), cfg.compute_dtype),)
    carry, _ = lax.scan(
        body, carry0, (params["layers"], jnp.arange(cfg.n_layers)))
    x, ks, vs = carry[:3]
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(cdt)).astype(jnp.float32)
    return logits, {"k": ks, "v": vs}, {}, None


def decode_window_paged(cfg: LlamaConfig, params: Params,
                        tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                        table: jnp.ndarray, lengths: jnp.ndarray,
                        rope_cache: Optional[tuple] = None,
                        pos_limit: Optional[int] = None,
                        tp_plan: Optional[TPPlan] = None):
    """Multi-token decode window for every slot (speculative verification).

    tokens [B, T]: per-slot window starting at positions ``lengths[b]``
    (token j lands at global position lengths[b] + j).  Writes each
    window token's KV into the pool at its position — positions at or
    past ``pos_limit`` (the engine's max_seq) redirect to sink block 0
    instead of clamping, so a near-the-end slot can never clobber its own
    live KV with a duplicate scatter index — then attends causally over
    the table span (window KV is read back from the pool at its global
    flat index, exactly like chunked prefill).  The host guarantees
    table coverage of positions < pos_limit through lengths + T.

    Gather path only: the pallas paged-attention kernel is single-query
    decode, and T here is the small speculative window (k+1 <= ~8) — the
    gather's overhead is one chunk-sized span read, the same trade
    chunked prefill already makes.  Returns (logits [B, T, V] fp32,
    updated pool).
    """
    if rope_cache is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    else:
        cos, sin = rope_cache
    b, t = tokens.shape
    bs = pool["k"].shape[2]
    w = table.shape[1]
    cdt = cfg.compute_dtype
    limit = pos_limit if pos_limit is not None else w * bs
    positions = lengths[:, None] + jnp.arange(t)[None, :]  # [B, T] global
    ok = positions < limit
    safe = jnp.minimum(positions, limit - 1)  # rope-table safe
    bidx = jnp.arange(b)[:, None]
    blk = jnp.where(ok, table[bidx, safe // bs], 0)  # invalid -> sink
    off = safe % bs
    # flat span index == global position (the table row is the sequence's
    # blocks in order); window token j sees prefix + window tokens <= j
    span_mask = (jnp.arange(w * bs)[None, None, :]
                 <= positions[:, :, None])  # [B, T, W*bs] causal
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    overlap = tp_plan is not None and tp_plan.overlap

    def body(carry, inp):
        if overlap:
            x, pk_all, pv_all, tok = carry
        else:
            (x, pk_all, pv_all), tok = carry, None
        lp, li = inp
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = (h @ lp["wq"].astype(cdt)).reshape(b, t, cfg.n_heads,
                                                   cfg.head_dim)
            k = (h @ lp["wk"].astype(cdt)).reshape(b, t, cfg.n_kv_heads,
                                                   cfg.head_dim)
            v = (h @ lp["wv"].astype(cdt)).reshape(b, t, cfg.n_kv_heads,
                                                   cfg.head_dim)
            q = apply_rope(q, cos, sin, positions=safe)
            k = apply_rope(k, cos, sin, positions=safe)
            # [B, T] fancy-index scatter; duplicate sink indices collide with
            # garbage values only (no slot's table references block 0 inside
            # its live span)
            pk_all = pk_all.at[li, blk, off].set(
                k.reshape(b, t, -1).astype(pk_all.dtype))
            pv_all = pv_all.at[li, blk, off].set(
                v.reshape(b, t, -1).astype(pv_all.dtype))
            ck = pk_all[li, table].reshape(b, w * bs, cfg.n_kv_heads,
                                           cfg.head_dim)
            cv = pv_all[li, table].reshape(b, w * bs, cfg.n_kv_heads,
                                           cfg.head_dim)
            attn = _paged_attend(cfg, q, ck, cv, span_mask)
            out, tok = _tp_out_proj(attn.astype(cdt), lp["wo"].astype(cdt),
                                    tp_plan, tok)
            x = x + out
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            gated = (jax.nn.silu(h @ lp["w_gate"].astype(cdt))
                     * (h @ lp["w_up"].astype(cdt)))
            ffn, tok = _tp_out_proj(gated, lp["w_down"].astype(cdt),
                                    tp_plan, tok)
            carry = (x + ffn, pk_all, pv_all)
        return (carry + (tok,) if overlap else carry), None

    carry0 = (x, pool["k"], pool["v"])
    if overlap:
        carry0 = carry0 + (jnp.zeros((), cfg.compute_dtype),)
    carry, _ = lax.scan(
        body, carry0, (params["layers"], jnp.arange(cfg.n_layers)))
    x, ks, vs = carry[:3]
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(cdt)).astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def prefill_chunk_paged(cfg: LlamaConfig, params: Params, tokens: jnp.ndarray,
                        pool: Dict[str, jnp.ndarray], table: jnp.ndarray,
                        p0: jnp.ndarray, *,
                        rope_cache: Optional[tuple] = None,
                        tp_plan: Optional[TPPlan] = None,
                        use_kernel: bool = False,
                        kernel_interpret: bool = False, slot_state=None,
                        slot=None, take=None,
                        kv_tile: int = PREFILL_KV_TILE):
    """Prefill ONE chunk of a single sequence into its pool blocks.

    tokens [1, C] (C a multiple of block_size; tail garbage-padded — padded
    positions write blocks the sequence owns and are masked by length
    thereafter); p0 = global position of tokens[0, 0] (multiple of
    block_size); table [1, W] covers positions [0, p0 + C), W whatever
    fixed width the caller compiles for (padded here to whole tiles).
    Attention is causal over the whole prefix: earlier chunks' KV is read
    back from the pool, so chunked prefill needs no growing-activation state
    between chunks.  Chunk compute is O(C * (p0 + C)), not O(C * W):
    ``_prefill_attend_tiles`` visits the table's first cdiv(p0 + C, kv_tile)
    tiles and no others.  ``kv_tile`` is for tests (several tiles at a tiny
    ``max_seq_len``); every caller in the tree leaves the default.
    Returns (logits [1, C, V] fp32, updated pool, ``{}``): the family seam's
    three (models/family.py).  The chunk has no kernel of its own and the
    family no slot state: ``use_kernel``, ``kernel_interpret``,
    ``slot_state``, ``slot`` and ``take`` are not read.
    """
    del use_kernel, kernel_interpret, slot_state, slot, take
    if rope_cache is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    else:
        cos, sin = rope_cache
    b, c = tokens.shape
    bs = pool["k"].shape[2]
    cdt = cfg.compute_dtype
    if kv_tile % bs:
        raise ValueError(f"kv_tile ({kv_tile}) must be a multiple of the "
                         f"block size ({bs})")
    positions = p0 + jnp.arange(c)  # [C] global positions
    # the C/bs physical blocks this chunk writes
    chunk_blocks = lax.dynamic_slice(table[0], (p0 // bs,), (c // bs,))
    # whole tiles: a tile's slice of the row never clamps.  The padding
    # lies past p0 + C, in tiles the loop never visits
    row = jnp.pad(table[0], (0, -table.shape[1] % (kv_tile // bs)))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    overlap = tp_plan is not None and tp_plan.overlap

    def body(carry, inp):
        # pools [L, NB, bs, kv*hd] ride the carry
        if overlap:
            x, pk_all, pv_all, tok = carry
        else:
            (x, pk_all, pv_all), tok = carry, None
        lp, li = inp
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = (h @ lp["wq"].astype(cdt)).reshape(b, c, cfg.n_heads, cfg.head_dim)
            k = (h @ lp["wk"].astype(cdt)).reshape(b, c, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"].astype(cdt)).reshape(b, c, cfg.n_kv_heads, cfg.head_dim)
            q = apply_rope(q, cos, sin, positions=positions[None, :])
            k = apply_rope(k, cos, sin, positions=positions[None, :])
            # [1, C, kv, hd] -> [C/bs, bs, kv*hd] block-major slab writes
            pk_all = pk_all.at[li, chunk_blocks].set(
                k[0].reshape(c // bs, bs, -1).astype(pk_all.dtype))
            pv_all = pv_all.at[li, chunk_blocks].set(
                v[0].reshape(c // bs, bs, -1).astype(pv_all.dtype))
            # the chunk's own KV is in the pool now: its tiles are read
            # back like any other
            attn = _prefill_attend_tiles(cfg, q[0], pk_all, pv_all, li, row,
                                         positions, kv_tile)[None]
            out, tok = _tp_out_proj(attn.astype(cdt), lp["wo"].astype(cdt),
                                    tp_plan, tok)
            x = x + out
        with jax.named_scope("ffn"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            gated = (jax.nn.silu(h @ lp["w_gate"].astype(cdt))
                     * (h @ lp["w_up"].astype(cdt)))
            ffn, tok = _tp_out_proj(gated, lp["w_down"].astype(cdt),
                                    tp_plan, tok)
            carry = (x + ffn, pk_all, pv_all)
        return (carry + (tok,) if overlap else carry), None

    carry0 = (x, pool["k"], pool["v"])
    if overlap:
        carry0 = carry0 + (jnp.zeros((), cfg.compute_dtype),)
    carry, _ = lax.scan(
        body, carry0, (params["layers"], jnp.arange(cfg.n_layers)))
    x, ks, vs = carry[:3]
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = (x @ head.astype(cdt)).astype(jnp.float32)
    return logits, {"k": ks, "v": vs}, {}


def flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (6N + attention term) for MFU math."""
    n = cfg.num_params
    attn = 12 * cfg.n_layers * cfg.dim * seq_len  # 2*2*3 * L * d * s (fwd+bwd, causal half)
    return 6.0 * n + attn


# ---------------------------------------------------------------------------
# The family seam (models/family.py): what the paged engine takes from here.
# ---------------------------------------------------------------------------


def _rope_cache(cfg: LlamaConfig, max_seq: int):
    cos, sin = rope_frequencies(cfg.head_dim, max_seq, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def _prefill_visited_pages(p0: int, chunk: int, block_size: int) -> int:
    """Pages of the whole KV tiles ``_prefill_attend_tiles`` visits."""
    tile = PREFILL_KV_TILE
    return math.ceil((p0 + chunk) / tile) * tile // block_size


def _reference_logits(cfg, params, tokens, first_row: int = 0):
    from ray_tpu.models.llama_reference import reference_logits

    return reference_logits(cfg, params, tokens)[first_row:]


def _family():
    from ray_tpu.models.family import ModelFamily

    return ModelFamily(
        name="llama", config_type=LlamaConfig, init_params=init_params,
        init_paged_cache=init_paged_kv_cache, rope_cache=_rope_cache,
        prefill_chunk=prefill_chunk_paged, decode_step=decode_step_paged,
        kernel_supported=paged_kernel_supported,
        prefill_visited_pages=_prefill_visited_pages,
        reference_logits=_reference_logits,
        param_specs=inference_param_specs,
        paged_cache_spec=paged_kv_cache_spec,
        decode_window=decode_window_paged)


FAMILY = _family()
