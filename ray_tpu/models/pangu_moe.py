"""openPangu-Ultra-MoE-style decoder, as one chip's share of an expert-parallel
deployment: latent attention (MLA) over a paged LATENT cache, sandwich norms,
256-way sigmoid routing of which this chip holds a range of experts.

The layer, as computed (``h`` the normed input, every norm RMSNorm):

- block (sandwich norm): ``x = x + N_post_attn(Attn(N_in(x)))`` then
  ``x = x + N_post_mlp(FFN(N_pre_mlp(x)))``: four norms a layer, the second
  of each pair on the sublayer's OUTPUT before the residual add.
- attention (MLA): ``c_q = N(h W_dq)``; ``[q_nope_i | q_rope_i] = c_q W_uq``
  a head, ``q_rope_i`` rotated; ``[c_kv | k_r] = h W_dkv``, ``c_kv = N(c_kv)``,
  ``k_rope = RoPE(k_r)`` shared by all heads; ``k_nope_i = c_kv W_uk_i``,
  ``v_i = c_kv W_uv_i``; scores ``(q_nope_i . k_nope_i + q_rope_i . k_rope) /
  sqrt(nope + rope)``, causal softmax, ``o_i = sum p v_i``, output
  ``concat(o_i) W_o``.  **The cache holds ``[c_kv | k_rope]``** (512 + 64
  values a position a layer, zero-padded to a lane multiple), not K and V.
  Decode ABSORBS the up-projections: ``q~_i = q_nope_i W_uk_i^T``, scores
  against the cached latent and rotary part, ``o~_i = sum p c_kv``,
  ``o_i = o~_i W_uv_i``: one shared "KV head" read by every query head, through
  the Pallas kernel ``ops/mla_paged_attention.py``.  A prefill chunk EXPANDS
  each visited KV tile to per-head keys and values and attends in that form
  (faster than the absorbed form at 256 and at 1,024 queries against 8k
  positions: ``benchmarks/mla_kernel_bench.py`` holds that form and times
  both).
- feed-forward: the first ``first_k_dense`` layers are SwiGLU of width
  ``ffn_dim``.  The rest: ``s = sigmoid(h W_r)`` over ALL ``n_routed_experts``
  in float32, the ``n_experts_per_tok`` largest (no expert groups),
  ``g_i = scale * s_i / (sum of the chosen s + 1e-20)``,
  ``y = SwiGLU_shared(h) + sum_{chosen i} g_i SwiGLU_i(h)``.

**The share.**  ``experts_held`` is the range of the routed experts whose
weights live here.  The router keeps its full width and its experts per token;
this chip computes the shared expert and its own experts' part of the sum, and
what absent experts would add is left out (in the reference alike): that
partial result goes on to the next layer.  Nothing here stands in for the
other chips or their exchange.  The held experts' part has two forms of one
sum.  DENSE: one gated feed-forward of width ``held x moe_ffn_dim`` whose
hidden units are scaled by their expert's gate (zero where the token did not
choose it): exact at any number of rows an expert, at the price of reading
every held expert's weights whether or not a token chose it and of every
token times every held expert.  GROUPED: the chosen (token, expert) pairs
sorted by expert and multiplied a group at a time against their expert's
block of the same weights (``ops/moe_grouped_ffn.py``), whose grid visits the
experts that have rows and fetches no other.  ``moe_ffn`` takes the grouped
form wherever its kernel applies and there is something to win by it: a
prompt chunk from ``GROUPED_MIN_ROWS`` rows on (below, a chunk hits every
held expert and the weights' read bounds both forms), and EVERY decode
token-step (``live`` given), whose rows hit a part of the held experts only
(``moe_experts_hit`` over ``moe_experts_held``; ``moe_grouped_calls`` counts
the layer-calls that went so).  **In decode the live mask comes first**: the
batch's slots that do not decode still hold a stale token that routes
somewhere (64 slots hit 87% of 16 held experts, the 6 to 25 that decode a
fifth to two fifths), so their held gates are zeroed BEFORE the pairs are
sorted and no expert is read for them.  A dead row's routed part is then
zero; nothing reads a dead row's hidden state (the engine's
``_decode_chunk_impl`` emits -1 and keeps the old token where ``active`` is
0, a dead row's cache write lands where no live row reads, and a slot state
is kept bit for bit).  ``vocab_slice`` is the
range of the published vocabulary's rows held: a sliced vocabulary is a
smaller vocabulary, ids and logits are over the slice.

The multi-token-prediction module of the published model is a draft head, no
part of the forward pass, and is not built.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

Params = Dict[str, Any]

# engine counters the decode program books a token-step (family seam's
# ``decode_counters``), summed over the expert layers: held experts (where any
# row decodes), held experts that at least one decoding row chose, (row, held
# expert) pairs, and the layer-calls (with a decoding row) whose held experts
# ran as the grouped product: ``moe_experts_held / n_held`` where none took
# the dense one for want of room in the pairs' buffer
DECODE_COUNTERS = ("moe_experts_held", "moe_experts_hit", "moe_pairs_here",
                   "moe_grouped_calls")

# KV positions one step of a prefill chunk's attention attends (a grid step of
# the kernel, an iteration of the ``jax.numpy`` loop)
PREFILL_KV_TILE = 1024

# rows from which on a PROMPT CHUNK's expert layers run their routed part as a
# grouped product over the chosen pairs (``moe_ffn``; a decode token-step
# does at any width), and the row tile of its kernel.  ms a layer-call on a
# v5e, dense / grouped (benchmarks/moe_prefill_bench.py;
# PERF.md section 6, PR 40): 128 rows 2.32 / 2.33, 256 rows 2.51 / 2.42, 384
# rows 3.43 / 2.52, 512 rows 4.46 / 2.60, 1,024 rows 8.86 / 3.23
GROUPED_MIN_ROWS = 256
GROUPED_ROW_TILE = 128
# the held experts' weights, in the order of their three products
HELD_EXPERT_LEAVES = ("we_gate", "we_up", "we_down")


@dataclasses.dataclass(frozen=True)
class PanguMoEConfig:
    # rows [start, stop) of the published vocabulary held here
    vocab_slice: Tuple[int, int] = (0, 19200)
    dim: int = 7680
    n_layers: int = 5
    first_k_dense: int = 1
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 18432
    moe_ffn_dim: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    # experts [start, stop) of the n_routed_experts whose weights live here
    experts_held: Tuple[int, int] = (0, 16)
    max_seq_len: int = 9216
    rope_theta: float = 25.6e6
    rms_norm_eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    @property
    def vocab_size(self) -> int:
        return self.vocab_slice[1] - self.vocab_slice[0]

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values a position a layer keeps: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """``latent_width`` padded to whole 128-lane tiles (576 -> 640):
        the device lays the minor dimension out in such tiles anyway, and
        the zero tail lets the decode kernel score against a whole page."""
        return -(-self.latent_width // 128) * 128

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(jax.eval_shape(
            lambda: init_params(self, jax.random.PRNGKey(0)))))

    @classmethod
    def tiny(cls, **kw) -> "PanguMoEConfig":
        """Test-sized: every mechanism present, milliseconds on a CPU."""
        kw.setdefault("vocab_slice", (0, 256))
        kw.setdefault("dim", 64)
        kw.setdefault("n_layers", 3)
        kw.setdefault("first_k_dense", 1)
        kw.setdefault("n_heads", 4)
        kw.setdefault("q_lora_rank", 48)
        kw.setdefault("kv_lora_rank", 32)
        kw.setdefault("qk_nope_head_dim", 16)
        kw.setdefault("qk_rope_head_dim", 8)
        kw.setdefault("v_head_dim", 16)
        kw.setdefault("ffn_dim", 128)
        kw.setdefault("moe_ffn_dim", 32)
        kw.setdefault("n_routed_experts", 16)
        kw.setdefault("n_experts_per_tok", 4)
        kw.setdefault("experts_held", (0, 16))
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("param_dtype", jnp.float32)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    """Seeded normal weights of a stacked leaf, a leading-axis slice at a
    time: the float32 temporaries are one layer's, not the leaf's (the held
    experts of four layers are 2 GB in bf16)."""
    return lax.map(
        lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                   * std).astype(dtype),
        jax.random.split(key, shape[0]))


def _attn_params(cfg: PanguMoEConfig, key, n: int) -> Params:
    d, h, dt = cfg.dim, cfg.n_heads, cfg.param_dtype
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    ks = jax.random.split(key, 6)
    return {
        "attn_norm": jnp.ones((n, d), dt),
        "w_dq": _normal(ks[0], (n, d, cfg.q_lora_rank), std, dt),
        "q_norm": jnp.ones((n, cfg.q_lora_rank), dt),
        # per head [q_nope | q_rope]
        "w_uq": _normal(ks[1], (n, cfg.q_lora_rank, h * cfg.qk_head_dim),
                        std, dt),
        # [c_kv | k_r]
        "w_dkv": _normal(ks[2], (n, d, cfg.latent_width), std, dt),
        "kv_norm": jnp.ones((n, cfg.kv_lora_rank), dt),
        # k_nope_i = c_kv @ w_uk[i].T ; v_i = c_kv @ w_uv[i]: the two halves
        # of the checkpoint's W_ukv, a head at a time, in the layout the
        # absorbed decode multiplies by without a transpose
        "w_uk": _normal(ks[3], (n, h, cfg.qk_nope_head_dim, cfg.kv_lora_rank),
                        std, dt),
        "w_uv": _normal(ks[4], (n, h, cfg.kv_lora_rank, cfg.v_head_dim),
                        std, dt),
        "w_o": _normal(ks[5], (n, h * cfg.v_head_dim, d), out_std, dt),
        "post_attn_norm": jnp.ones((n, d), dt),
        "mlp_norm": jnp.ones((n, d), dt),
        "post_mlp_norm": jnp.ones((n, d), dt),
    }


def init_params(cfg: PanguMoEConfig, key: jax.Array) -> Params:
    """Seeded random weights: the dense layers and the expert layers as two
    stacks (their feed-forwards differ), each scanned."""
    d, dt = cfg.dim, cfg.param_dtype
    nd, nm = cfg.first_k_dense, cfg.n_moe_layers
    e, f = cfg.n_held, cfg.moe_ffn_dim
    fs = cfg.n_shared_experts * f
    std = 0.02
    out_std = std / math.sqrt(2 * cfg.n_layers)
    ks = jax.random.split(key, 16)
    params: Params = {
        "embed": _normal(ks[0], (1, cfg.vocab_size, d), std, dt)[0],
        "final_norm": jnp.ones((d,), dt),
        "lm_head": _normal(ks[1], (1, d, cfg.vocab_size), std, dt)[0],
    }
    if nd:
        params["dense"] = {
            **_attn_params(cfg, ks[2], nd),
            "w_gate": _normal(ks[3], (nd, d, cfg.ffn_dim), std, dt),
            "w_up": _normal(ks[4], (nd, d, cfg.ffn_dim), std, dt),
            "w_down": _normal(ks[5], (nd, cfg.ffn_dim, d), out_std, dt),
        }
    if nm:
        params["moe"] = {
            **_attn_params(cfg, ks[6], nm),
            # the router keeps every published output, in float32
            "router": _normal(ks[7], (nm, d, cfg.n_routed_experts), std,
                              jnp.float32),
            "ws_gate": _normal(ks[8], (nm, d, fs), std, dt),
            "ws_up": _normal(ks[9], (nm, d, fs), std, dt),
            "ws_down": _normal(ks[10], (nm, fs, d), out_std, dt),
            # the held experts side by side as ONE feed-forward of width
            # held x f: expert j of the range is columns [j f, (j + 1) f) of
            # we_gate and we_up and those rows of we_down.  (Kept [d, e, f]
            # and reshaped in the step, the compiler copied every expert's
            # weights a layer-call to change their tiling.)
            "we_gate": _normal(ks[11], (nm, d, e * f), std, dt),
            "we_up": _normal(ks[12], (nm, d, e * f), std, dt),
            "we_down": _normal(ks[13], (nm, e * f, d), out_std, dt),
        }
    return params


def init_paged_cache(cfg: PanguMoEConfig, num_blocks: int, block_size: int,
                     dtype=None) -> Dict[str, jnp.ndarray]:
    """The latent block pool: one leaf, ``[layers, blocks, block_size,
    cache_width]`` of ``[c_kv | k_rope | 0]``."""
    dtype = dtype or cfg.compute_dtype
    return {"ckv": jnp.zeros(
        (cfg.n_layers, num_blocks, block_size, cfg.cache_width), dtype)}


def make_rope_cache(cfg: PanguMoEConfig, max_seq: int):
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, max_seq, cfg.rope_theta)
    return jnp.asarray(cos), jnp.asarray(sin)


def kernel_supported(cfg: PanguMoEConfig) -> bool:
    """Whether the latent decode kernel applies: a TPU backend and a latent
    whose value part (the first ``kv_lora_rank`` columns) ends on a lane
    tile.  On a TPU a kernel that cannot be imported is an error."""
    if jax.default_backend() != "tpu":
        return False
    if cfg.kv_lora_rank % 128:
        return False
    from ray_tpu.ops.mla_paged_attention import (  # noqa: F401
        mla_paged_decode_attention,
    )

    return True


def prefill_kernel_fits(cfg: PanguMoEConfig) -> bool:
    """Whether a prefill chunk's attention takes its kernel
    (``ops/mla_prefill_attention.py``) where the decode kernel is on: on a
    TPU a head's key and value parts must end on a lane tile (the
    interpreter takes any width)."""
    return jax.default_backend() != "tpu" or not (
        cfg.qk_nope_head_dim % 128 or cfg.v_head_dim % 128)


# ---------------------------------------------------------------------------
# the layer's parts
# ---------------------------------------------------------------------------


def _queries(cfg: PanguMoEConfig, h, lp, cos, sin, positions):
    """``(q_nope [.., H, nope], q_rope [.., H, rope])`` of normed inputs
    ``h [B, T, d]`` at ``positions [B, T]``."""
    cdt = cfg.compute_dtype
    b, t = h.shape[:2]
    c_q = rms_norm(h @ lp["w_dq"].astype(cdt), lp["q_norm"], cfg.rms_norm_eps)
    # the barrier keeps the projection a plain [rows, rank] x [rank, n]
    # product: without it the compiler folds the head reshape and the
    # nope/rope split into it, wants W_uq in another layout, and copies the
    # layer's 75 MB of it every call (as llama.decode_step_paged's wq did)
    q = lax.optimization_barrier(c_q @ lp["w_uq"].astype(cdt)).reshape(
        b, t, cfg.n_heads, cfg.qk_head_dim)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin,
                        positions=positions)
    return q_nope, q_rope


def _latent(cfg: PanguMoEConfig, h, lp, cos, sin, positions):
    """The cache's rows ``[B, T, cache_width]`` of normed inputs ``h``:
    ``[N(c_kv) | RoPE(k_r) | 0]``."""
    cdt = cfg.compute_dtype
    b, t = h.shape[:2]
    kv = h @ lp["w_dkv"].astype(cdt)
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"],
                    cfg.rms_norm_eps)
    k_rope = apply_rope(kv[..., None, cfg.kv_lora_rank:], cos, sin,
                        positions=positions)[..., 0, :]
    pad = jnp.zeros((b, t, cfg.cache_width - cfg.latent_width), cdt)
    return jnp.concatenate([c_kv, k_rope, pad], axis=-1)


def _absorb_queries(cfg: PanguMoEConfig, q_nope, q_rope, lp):
    """``q~ [.., H, cache_width]``: ``[q_nope_i W_uk_i^T | q_rope_i | 0]``,
    the query that scores against a cached row directly."""
    cdt = cfg.compute_dtype
    q_lat = jnp.einsum("...hn,hnc->...hc", q_nope, lp["w_uk"].astype(cdt))
    pad = jnp.zeros(q_lat.shape[:-1] + (cfg.cache_width - cfg.latent_width,),
                    cdt)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def _unabsorb(cfg: PanguMoEConfig, o_lat, lp):
    """``o~ [.., H, kv_lora_rank]`` (attention over the latent) ->
    ``concat_i(o~_i W_uv_i) [.., H * v]``."""
    cdt = cfg.compute_dtype
    o = jnp.einsum("...hc,hcv->...hv", o_lat.astype(cdt),
                   lp["w_uv"].astype(cdt))
    return o.reshape(o.shape[:-2] + (cfg.n_heads * cfg.v_head_dim,))


def _attend_absorbed(cfg: PanguMoEConfig, q_abs, span, span_mask):
    """Absorbed attention of ``q~ [B, T, H, W]`` against gathered latent
    rows ``span [B, S, W]``; ``span_mask [B, T, S]`` True = visible.
    Returns ``o~ [B, T, H, kv_lora_rank]`` float32.  The gather path of
    decode (no kernel) and the form every other path is tested against."""
    s = jnp.einsum("bthw,bsw->bhts", q_abs, span,
                   preferred_element_type=jnp.float32)
    s = s / math.sqrt(cfg.qk_head_dim)
    s = jnp.where(span_mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bsc->bthc", p.astype(span.dtype),
                      span[..., :cfg.kv_lora_rank],
                      preferred_element_type=jnp.float32)


def _tile_state(cfg, c, width):
    h = cfg.n_heads
    return (jnp.full((h, c), -1e30, jnp.float32),
            jnp.zeros((h, c), jnp.float32),
            jnp.zeros((h, c, width), jnp.float32))


def _fold(state, s, values, visible, eq):
    """One tile of an online softmax: scores ``s [H, C, T]`` float32."""
    m, l, acc = state
    s = jnp.where(visible[None], s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l = alpha * l + p.sum(axis=-1)
    acc = alpha[..., None] * acc + jnp.einsum(
        eq, p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def _attend_tiles_expanded(cfg: PanguMoEConfig, q_nope, q_rope, pool, li,
                           row, positions, lp, tile: int):
    """Causal attention of one chunk's queries (``q_nope [C, H, nope]``,
    ``q_rope [C, H, rope]`` at rising ``positions [C]``) over the sequence's
    latent rows in ``pool``, EXPANDED a KV tile at a time to per-head keys
    and values.  ``row`` is the sequence's block table, whole tiles wide; the
    loop's trip count is ``positions[-1] // tile + 1``, so work and traffic
    follow the live prefix.  Returns ``[C, H * v]`` float32."""
    cdt = cfg.compute_dtype
    c = q_nope.shape[0]
    bs = pool.shape[2]
    pages = tile // bs
    r = cfg.kv_lora_rank
    scale = 1.0 / math.sqrt(cfg.qk_head_dim)
    offs = jnp.arange(tile)
    w_uk, w_uv = lp["w_uk"].astype(cdt), lp["w_uv"].astype(cdt)

    def fold(i, state):
        blocks = lax.dynamic_slice(row, (i * pages,), (pages,))
        lat = pool[li, blocks].reshape(tile, cfg.cache_width)
        c_kv = lat[:, :r]
        k_rope = lat[:, r:cfg.latent_width]
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, w_uk)
        v = jnp.einsum("sc,hcv->shv", c_kv, w_uv)
        s = (jnp.einsum("chn,shn->hcs", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("chr,sr->hcs", q_rope, k_rope,
                          preferred_element_type=jnp.float32)) * scale
        visible = (i * tile + offs)[None, :] <= positions[:, None]
        return _fold(state, s, v, visible, "hcs,shv->hcv")

    _, l, acc = lax.fori_loop(0, positions[-1] // tile + 1, fold,
                              _tile_state(cfg, c, cfg.v_head_dim))
    attn = acc / l[..., None]
    return attn.transpose(1, 0, 2).reshape(c, cfg.n_heads * cfg.v_head_dim)


def _attend_kernel(cfg: PanguMoEConfig, q_nope, q_rope, pool, li, row, p0,
                   lp, tile: int, interpret: bool):
    """``_attend_tiles_expanded``'s attention through the Pallas kernel: the
    latent rows of the table's whole width gathered into one buffer (14 MB a
    layer-call at the cell's 641 blocks; the kernel reads the live tiles of
    it), the queries laid out a head ``[q_nope | q_rope | 0]`` to meet a key
    ``[k_nope_i | k_rope | 0]``.  Returns ``[C, H * v]`` in the compute
    dtype."""
    from ray_tpu.ops.mla_prefill_attention import mla_prefill_attention

    c = q_nope.shape[0]
    pad = jnp.zeros((c, cfg.n_heads, cfg.cache_width - cfg.latent_width),
                    q_nope.dtype)
    q = jnp.concatenate([q_nope, q_rope, pad], axis=-1).reshape(c, -1)
    lat = pool[li, row].reshape(-1, cfg.cache_width)
    return mla_prefill_attention(
        q, lat, lp["w_uk"], lp["w_uv"], p0,
        scale=1.0 / math.sqrt(cfg.qk_head_dim), kv_tile=tile,
        interpret=interpret)


def route(cfg: PanguMoEConfig, h, router):
    """The router, over ALL ``n_routed_experts``: ``(gates [T, k] float32,
    experts [T, k])`` of inputs ``h [T, d]``: scores in float32 (a sigmoid
    each, or where the config says ``router_score = "softmax"`` a softmax
    over all of them), the k largest (no groups), normalised over the
    chosen, times the scaling factor."""
    logits = h.astype(jnp.float32) @ router
    s = (jax.nn.softmax(logits, axis=-1)
         if getattr(cfg, "router_score", "sigmoid") == "softmax"
         else jax.nn.sigmoid(logits))
    top, idx = lax.top_k(s, cfg.n_experts_per_tok)
    gates = cfg.routed_scaling_factor * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    return gates, idx


def held_gates(cfg: PanguMoEConfig, gates, idx):
    """``[T, n_held]``: each held expert's gate for each token, zero where
    the token did not choose it."""
    held = jnp.arange(cfg.experts_held[0], cfg.experts_held[1])
    return jnp.sum(
        jnp.where(idx[:, :, None] == held[None, None, :],
                  gates[:, :, None], 0.0), axis=1)


def grouped_ffn_from(cfg: PanguMoEConfig,
                     interpret: bool = False) -> Optional[int]:
    """The row count from which on ``moe_ffn`` computes a prompt chunk's held
    experts' part as a grouped product over the chosen (token, expert) pairs;
    None: never, in no program (no expert layer, or no kernel for this
    backend and these widths: on a TPU an expert's block must be whole lane
    tiles; the interpreter takes any width)."""
    if not cfg.n_moe_layers:
        return None
    if not interpret and (jax.default_backend() != "tpu"
                          or cfg.dim % 128 or cfg.moe_ffn_dim % 128):
        return None
    return GROUPED_MIN_ROWS


def takes_grouped(cfg: PanguMoEConfig, rows: int, interpret: bool,
                  decode: bool) -> bool:
    """Whether ``rows`` rows' held experts run as the grouped product: where
    its kernel applies, a decode token-step always (its rows choose a part
    of the held experts, and the kernel reads no other), a prompt chunk from
    ``grouped_ffn_from`` rows on."""
    first = grouped_ffn_from(cfg, interpret)
    return first is not None and (decode or rows >= first)


def _routed_dense(cfg: PanguMoEConfig, h, g, lp, layer=None):
    """The held experts' part as ONE feed-forward of width ``held x f`` whose
    hidden units are scaled by their expert's gate: every token times every
    held expert.  ``layer``: ``moe_ffn``'s.  Returns ``[T, d]`` float32."""
    cdt = cfg.compute_dtype
    t = h.shape[0]
    e, f = cfg.n_held, cfg.moe_ffn_dim
    w_gate, w_up, w_down = (
        (lp[k] if layer is None else lp[k][layer]).astype(cdt)
        for k in HELD_EXPERT_LEAVES)
    act = (jax.nn.silu(h @ w_gate) * (h @ w_up)).reshape(t, e, f)
    act = (act * g[:, :, None].astype(cdt)).reshape(t, e * f)
    return jnp.dot(act, w_down, preferred_element_type=jnp.float32)


def sort_pairs(g, m: int):
    """The (token, held expert) pairs of gates ``g [T, e]`` (non-zero =
    chosen), sorted by expert and within an expert by token, by counting (a
    cumulative sum of the choices; no sort runs).  Returns ``(tokens [m],
    gates [m], group_sizes [e], pairs)``: slot ``r < pairs`` holds a pair's
    token and gate, the slots past them token 0 and gate 0."""
    t, e = g.shape
    chosen = (g > 0).T
    within = jnp.cumsum(chosen, axis=1, dtype=jnp.int32)
    sizes = within[:, -1]
    ends = jnp.cumsum(sizes)
    slot = jnp.arange(m, dtype=jnp.int32)
    grp = jnp.minimum(jnp.sum(slot[:, None] >= ends[None, :], axis=1), e - 1)
    rank = slot - (ends - sizes)[grp]
    # the token whose choice of expert ``grp`` is that expert's rank-th
    tok = jnp.sum(within[grp] <= rank[:, None], axis=1)
    live = slot < ends[-1]
    tok = jnp.where(live, tok, 0)
    return tok, jnp.where(live, g[tok, grp], 0.0), sizes, ends[-1]


def _add_to_tokens(rows, tok, live, t: int):
    """``y[tok[r]] += rows[r]`` over the ``live`` rows of ``rows [m, d]``
    float32 (the others were never written), as float32 sums: ``[t, d]``.
    A one-hot ``[t, m]`` product over the rows split into three bfloat16
    parts (8 + 8 + 8 bits of a float32's 24: each part's product with a 0 /
    1 matrix is exact, the sums are float32), because XLA's scatter-add
    takes 2.8 us a row on a v5e (benchmarks/moe_prefill_bench.py: 1.4 ms for
    512 rows of 7,680, as long as the products themselves).  The parts are
    cut by ``reduce_precision``: a float32 -> bfloat16 -> float32 round trip
    the compiler may drop, and the second and third part with it."""
    rows = jnp.where(live[:, None], rows, 0.0)
    onehot = ((tok[None, :] == jnp.arange(t)[:, None])
              & live[None, :]).astype(jnp.bfloat16)
    y = jnp.zeros((t, rows.shape[1]), jnp.float32)
    for _ in range(3):
        part = lax.reduce_precision(rows, exponent_bits=8, mantissa_bits=7)
        y = y + jnp.dot(onehot, part.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        rows = rows - part
    return y


def _row_tile(t: int) -> int:
    """The grouped kernel's row tile for ``t`` rows: ``GROUPED_ROW_TILE``,
    or the rows themselves (in whole 16-row tiles of bfloat16) where they
    are fewer: decode's 64 rows are one tile."""
    return min(GROUPED_ROW_TILE, -(-t // 16) * 16)


def _routed_grouped(cfg: PanguMoEConfig, h, g, lp, layer, interpret: bool):
    """``_routed_dense``'s sum over the pairs the router chose alone: the
    pairs sorted by expert, their tokens' rows gathered, the three products
    a group at a time against that expert's block of the weights as they lie
    (``ops/moe_grouped_ffn.py``), each pair's row added to its token in
    float32 (``_add_to_tokens``).  The buffers hold ``T`` pairs (in whole row
    tiles), twice what uniform routing sends to a sixteenth of the experts;
    rows with more take the dense product: no pair is ever dropped.  Returns
    ``([T, d] float32, whether the grouped product answered)``."""
    from ray_tpu.ops.moe_grouped_ffn import moe_grouped_ffn

    t, d = h.shape
    tm = _row_tile(t)
    m = -(-t // tm) * tm
    tok, gates, sizes, pairs = sort_pairs(g, m)

    if layer is None:
        lp = {k: lp[k][None] for k in HELD_EXPERT_LEAVES}
        layer = 0

    def grouped(h, g):
        del g
        out = moe_grouped_ffn(
            jnp.take(h, tok, axis=0), *(lp[k] for k in HELD_EXPERT_LEAVES),
            layer, sizes, gates, tm=tm, interpret=interpret)
        return _add_to_tokens(out, tok, jnp.arange(m) < pairs, t)

    fits = pairs <= m
    return lax.cond(fits, grouped,
                    lambda h, g: _routed_dense(cfg, h, g, lp, layer),
                    h, g), fits


def moe_ffn(cfg: PanguMoEConfig, h, lp, interpret: bool = False, layer=None,
            live=None):
    """The expert layer's feed-forward of ``h [T, d]``: the shared expert
    plus this chip's experts' part of the routed sum.  Returns ``(y [T, d]
    float32, g [T, n_held], grouped)``: ``grouped`` a boolean scalar, whether
    the held experts' part ran as the grouped product.  ``layer``: ``lp``'s
    ``HELD_EXPERT_LEAVES`` are whole STACKS of layers and this scalar picks
    the one meant (a kernel cannot read a layer sliced out of its stack
    without a copy of it); None: they are one layer's, as every other leaf
    is.  ``live [T]``: the rows are a decode token-step's and those with 0
    do not decode: their held gates are zeroed (in ``g`` too) before
    anything is made of them, so their routed part is zero and no expert is
    read on their account.

    The routed part has two forms of one sum (``takes_grouped``): a decode
    token-step's rows, and a prompt chunk's from ``grouped_ffn_from`` rows on
    (where every-token-times-every-held-expert costs more than reading the
    experts' weights), take a grouped product over the chosen pairs
    (``interpret``: its kernel in the interpreter); a narrower chunk, and
    everything where that kernel does not apply, the dense product."""
    cdt = cfg.compute_dtype
    gates, idx = route(cfg, h, lp["router"])
    g = held_gates(cfg, gates, idx)
    if live is not None:
        g = jnp.where(live[:, None] > 0, g, 0.0)
    # the two down-projections leave the matrix unit in float32 and are
    # summed there: the sum of up to nine experts' terms is rounded once, by
    # the norm that follows
    shared = jnp.dot(jax.nn.silu(h @ lp["ws_gate"].astype(cdt))
                     * (h @ lp["ws_up"].astype(cdt)),
                     lp["ws_down"].astype(cdt),
                     preferred_element_type=jnp.float32)
    if takes_grouped(cfg, h.shape[0], interpret, live is not None):
        routed, grouped = _routed_grouped(cfg, h, g, lp, layer, interpret)
    else:
        routed = _routed_dense(cfg, h, g, lp, layer)
        grouped = jnp.zeros((), bool)
    return shared + routed, g, grouped


def _dense_ffn(cfg: PanguMoEConfig, h, lp):
    cdt = cfg.compute_dtype
    return (jax.nn.silu(h @ lp["w_gate"].astype(cdt))
            * (h @ lp["w_up"].astype(cdt))) @ lp["w_down"].astype(cdt)


def decode_booking(cfg: PanguMoEConfig, g, live, grouped):
    """``DECODE_COUNTERS`` of one expert layer's token-step: ``g [T,
    n_held]`` the held experts' gates and ``grouped`` the form they took
    (``moe_ffn``'s), ``live [T]`` the rows that decode."""
    chose = (g > 0) & (live[:, None] > 0)
    any_live = live.max() > 0
    return jnp.stack([
        cfg.n_held * any_live.astype(jnp.int32),
        chose.any(axis=0).sum().astype(jnp.int32),
        chose.sum().astype(jnp.int32),
        (grouped & any_live).astype(jnp.int32)])


def _ffn_sublayer(cfg: PanguMoEConfig, x, lp, is_moe: bool, live=None,
                  interpret: bool = False, layer=None):
    """``x + N_post(FFN(N_pre(x)))`` of ``x [B, T, d]``; for an expert layer
    of a decode token-step (``live [B * T]``: the rows that decode; None: a
    prompt chunk) also its decode counters.  ``interpret``, ``layer``:
    ``moe_ffn``'s."""
    b, t, d = x.shape
    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).reshape(b * t, d)
    booked = None
    if is_moe:
        with jax.named_scope("moe"):
            y, g, grouped = moe_ffn(cfg, h, lp, interpret, layer, live)
        if live is not None:
            booked = decode_booking(cfg, g, live, grouped)
    else:
        y = _dense_ffn(cfg, h, lp)
    y = rms_norm(y.reshape(b, t, d), lp["post_mlp_norm"], cfg.rms_norm_eps)
    return x + y.astype(x.dtype), booked


def _head(cfg: PanguMoEConfig, params, x):
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return (x @ params["lm_head"].astype(cfg.compute_dtype)).astype(
            jnp.float32)


def _held_whole(cfg: PanguMoEConfig, stack, is_moe: bool, rows: int,
                interpret: bool, decode: bool = False):
    """``(the stack's leaves a layer scan slices, the held experts' leaves it
    must leave whole)``: where ``rows`` rows (``decode``: of a token-step)
    take the grouped form, its kernel reads a layer's experts out of their
    stack in place (``moe_ffn``'s ``layer``); else everything is scanned and
    the second is empty."""
    if not is_moe or not takes_grouped(cfg, rows, interpret, decode):
        return stack, {}
    return ({k: v for k, v in stack.items() if k not in HELD_EXPERT_LEAVES},
            {k: stack[k] for k in HELD_EXPERT_LEAVES})


def _stacks(cfg: PanguMoEConfig, params):
    """``[(layer stack, first layer id, is_moe)]`` in order."""
    out = []
    if cfg.first_k_dense:
        out.append((params["dense"], 0, False))
    if cfg.n_moe_layers:
        out.append((params["moe"], cfg.first_k_dense, True))
    return out


# ---------------------------------------------------------------------------
# the paged programs (the family seam's prefill_chunk and decode_step)
# ---------------------------------------------------------------------------


def prefill_chunk_paged(cfg: PanguMoEConfig, params: Params,
                        tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                        table: jnp.ndarray, p0: jnp.ndarray, *,
                        rope_cache: Optional[tuple] = None, tp_plan=None,
                        use_kernel: bool = False,
                        kernel_interpret: bool = False, slot_state=None,
                        slot=None, take=None,
                        kv_tile: int = PREFILL_KV_TILE):
    """Prefill ONE chunk of a single sequence into its pool blocks.

    The contract of ``llama.prefill_chunk_paged``: tokens ``[1, C]`` (C a
    multiple of the block size, tail padded), ``p0`` the global position of
    the first (a multiple of the block size), table ``[1, W]`` covering
    ``[0, p0 + C)``.  The chunk's latent rows are written to the pool and
    attention reads the whole prefix back a tile at a time: ``use_kernel``
    (the engine's choice of the decode kernel) where ``prefill_kernel_fits``:
    inside the Pallas kernel (``_attend_kernel``), else in ``jax.numpy``
    (``_attend_tiles_expanded``).  ``kv_tile`` is for tests (a toy prefix
    spans several tiles only at a small one); every caller in the tree
    leaves the default.  Returns (logits [1, C, V] float32, pool, ``{}``):
    the family has no slot state.
    """
    # no tensor-parallel layout, no slot state
    del tp_plan, slot_state, slot, take
    use_kernel = use_kernel and prefill_kernel_fits(cfg)
    cos, sin = (rope_cache if rope_cache is not None
                else make_rope_cache(cfg, cfg.max_seq_len))
    b, c = tokens.shape
    ckv = pool["ckv"]
    bs = ckv.shape[2]
    if kv_tile % bs:
        raise ValueError(f"kv_tile ({kv_tile}) must be a multiple of the "
                         f"block size ({bs})")
    positions = p0 + jnp.arange(c)
    chunk_blocks = lax.dynamic_slice(table[0], (p0 // bs,), (c // bs,))
    row = jnp.pad(table[0], (0, -table.shape[1] % (kv_tile // bs)))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)

    for stack, first, is_moe in _stacks(cfg, params):
        stack, whole = _held_whole(cfg, stack, is_moe, c, kernel_interpret)

        def body(carry, inp, is_moe=is_moe, whole=whole, first=first):
            x, ckv = carry
            lp, li = inp
            with jax.named_scope("attention"):
                h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
                q_nope, q_rope = _queries(cfg, h, lp, cos, sin,
                                          positions[None])
                lat = _latent(cfg, h, lp, cos, sin, positions[None])
                ckv = ckv.at[li, chunk_blocks].set(
                    lat[0].reshape(c // bs, bs, -1).astype(ckv.dtype))
                if use_kernel:
                    attn = _attend_kernel(
                        cfg, q_nope[0], q_rope[0], ckv, li, row, p0, lp,
                        kv_tile, kernel_interpret)[None]
                else:
                    attn = _attend_tiles_expanded(
                        cfg, q_nope[0], q_rope[0], ckv, li, row, positions,
                        lp, kv_tile)[None]
                out = attn.astype(cfg.compute_dtype) @ lp["w_o"].astype(
                    cfg.compute_dtype)
                x = x + rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps)
            with jax.named_scope("ffn"):
                x, _ = _ffn_sublayer(cfg, x, {**lp, **whole}, is_moe,
                                     interpret=kernel_interpret,
                                     layer=li - first if whole else None)
            return (x, ckv), None

        n = jax.tree.leaves(stack)[0].shape[0]
        (x, ckv), _ = lax.scan(body, (x, ckv),
                               (stack, first + jnp.arange(n)))
    return _head(cfg, params, x), {"ckv": ckv}, {}


def decode_step_paged(cfg: PanguMoEConfig, params: Params,
                      tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      table: jnp.ndarray, lengths: jnp.ndarray, *,
                      rope_cache: Optional[tuple] = None,
                      use_kernel: bool = False, mesh=None,
                      kernel_interpret: bool = False, tp_plan=None,
                      active: Optional[jnp.ndarray] = None, slot_state=None):
    """One-token decode for every slot over the latent pool, in absorbed
    form.  The contract of ``llama.decode_step_paged``; ``use_kernel``: the
    Pallas kernel over the latent pool (the live pages of the decoding rows
    only), else a gather of the table's span.  Returns (logits [B, V]
    float32, pool, ``{}`` (no slot state), counters int32:
    ``DECODE_COUNTERS`` of this
    token-step over the rows with ``active`` != 0 (None: all)).  The expert
    layers compute the held experts' part for those rows alone
    (``moe_ffn``'s ``live``): a row with ``active`` 0 comes out with its
    routed part zero, and its logits mean nothing (they never did: its token
    is stale).
    """
    # no tensor-parallel layout, no slot state
    del mesh, tp_plan, slot_state
    cos, sin = (rope_cache if rope_cache is not None
                else make_rope_cache(cfg, cfg.max_seq_len))
    b = tokens.shape[0]
    ckv = pool["ckv"]
    bs = ckv.shape[2]
    w = table.shape[1]
    cur_blk = table[jnp.arange(b), lengths // bs]
    cur_off = lengths % bs
    live = jnp.ones_like(lengths) if active is None else active
    if not use_kernel:
        span_mask = (jnp.arange(w * bs)[None, None, :]
                     <= lengths[:, None, None])
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        cfg.compute_dtype)[:, None]
    booked = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)

    for stack, first, is_moe in _stacks(cfg, params):
        stack, whole = _held_whole(cfg, stack, is_moe, b, kernel_interpret,
                                   decode=True)

        def body(carry, inp, is_moe=is_moe, whole=whole, first=first):
            x, ckv, booked = carry
            lp, li = inp
            with jax.named_scope("attention"):
                h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
                q_nope, q_rope = _queries(cfg, h, lp, cos, sin,
                                          lengths[:, None])
                lat = _latent(cfg, h, lp, cos, sin, lengths[:, None])
                ckv = ckv.at[li, cur_blk, cur_off].set(
                    lat[:, 0].astype(ckv.dtype))
                q_abs = _absorb_queries(cfg, q_nope, q_rope, lp)
                if use_kernel:
                    from ray_tpu.ops.mla_paged_attention import (
                        mla_paged_decode_attention,
                    )

                    o_lat = mla_paged_decode_attention(
                        q_abs[:, 0], ckv, li, table, lengths, live,
                        value_width=cfg.kv_lora_rank,
                        scale=1.0 / math.sqrt(cfg.qk_head_dim),
                        interpret=kernel_interpret)[:, None]
                else:
                    span = ckv[li, table].reshape(b, w * bs, cfg.cache_width)
                    o_lat = _attend_absorbed(cfg, q_abs, span, span_mask)
                out = _unabsorb(cfg, o_lat, lp) @ lp["w_o"].astype(
                    cfg.compute_dtype)
                x = x + rms_norm(out, lp["post_attn_norm"], cfg.rms_norm_eps)
            with jax.named_scope("ffn"):
                x, got = _ffn_sublayer(cfg, x, {**lp, **whole}, is_moe,
                                       live=live, interpret=kernel_interpret,
                                       layer=li - first if whole else None)
            return (x, ckv, booked if got is None else booked + got), None

        n = jax.tree.leaves(stack)[0].shape[0]
        (x, ckv, booked), _ = lax.scan(
            body, (x, ckv, booked), (stack, first + jnp.arange(n)))
    return _head(cfg, params, x[:, 0]), {"ckv": ckv}, {}, booked


def _prefill_visited_pages(p0: int, chunk: int, block_size: int) -> int:
    tile = PREFILL_KV_TILE
    return math.ceil((p0 + chunk) / tile) * tile // block_size


def _reference_logits(cfg, params, tokens, first_row: int = 0):
    from ray_tpu.models.pangu_moe_reference import reference_logits

    return reference_logits(cfg, params, tokens, first_row=first_row)


def _family():
    from ray_tpu.models.family import ModelFamily

    return ModelFamily(
        name="pangu_moe", config_type=PanguMoEConfig,
        init_params=init_params, init_paged_cache=init_paged_cache,
        rope_cache=make_rope_cache, prefill_chunk=prefill_chunk_paged,
        decode_step=decode_step_paged, kernel_supported=kernel_supported,
        prefill_visited_pages=_prefill_visited_pages,
        reference_logits=_reference_logits,
        prefill_kernel_fits=prefill_kernel_fits,
        prefill_grouped_from=grouped_ffn_from,
        decode_counters=DECODE_COUNTERS)


FAMILY = _family()
