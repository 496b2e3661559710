"""Kimi-Linear-style decoder, as one chip's share of an expert-parallel
deployment: Kimi Delta Attention (KDA) layers whose matrix state is a SLOT's,
a few latent-attention (MLA) layers WITHOUT rotation over a paged latent
cache, a dense feed-forward after the first layer and 256-way routed experts
(of which this chip holds a range) after every other.

The equations, as computed (``models/kimi_linear_reference.py`` computes the
same in float32, position by position).  ``N``: RMSNorm, eps
``rms_norm_eps``, a weight of its own each use.

- ``x_0 = E[token]``; for ``l = 1..n_layers``: ``x <- x + Mix_l(N(x))``,
  ``x <- x + FFN_l(N(x))`` (pre-norm: ``assumed``); logits ``= N(x) W_head``
  (untied).  ``Mix_l`` is KDA for ``l`` in ``kda_layers`` and MLA for ``l`` in
  ``full_attn_layers`` (numbered from 1, as published); ``FFN_l`` is a gated
  SiLU feed-forward of width ``ffn_dim`` for ``l <= first_k_dense`` and the
  expert layer after.
- **KDA** (``H`` heads, ``d_k = d_v = kda_head_dim``), input ``h [T, d]``:
  ``[q | k | v] = silu(conv(h W_qkv))``, a depthwise causal convolution over a
  channel's last ``kda_conv`` positions, no bias (``W_q``, ``W_k``, ``W_v``
  side by side as ONE matrix, their three windows ONE slot leaf); a head:
  ``q^ = q / |q| * d_k^-0.5``, ``k^ = k / |k|`` (``|x| = sqrt(sum x^2 +
  1e-6)``: a departure, so that a row of zeros stays zeros).  Decay PER KEY
  CHANNEL: ``g = -exp(A_log[head]) * softplus((h W_fa) W_fb + dt_bias)``
  float32, ``alpha = exp(g)``; ``beta = sigmoid(h W_b)`` a head.  A head's
  state ``S [d_k, d_v]`` float32, zeros at position 0: **``S <- Diag(alpha_t)
  S``; ``S <- S + beta_t k^_t (v_t - S^T k^_t)^T``; ``o_t = S^T q^_t``**.
  Output: ``y = N_head(o_t) * sigmoid((h W_ga) W_gb)`` (RMSNorm over a head's
  values, one weight vector of ``d_v``), ``out = concat(y) W_o``.  (``W_fa``
  and ``W_ga`` side by side as ONE matrix ``w_lr``.)
- **MLA, no rotation** (``mla_use_nope``, no query down-projection):
  ``[q_nope_i | q_pe_i] = h W_q`` a head; ``[c_kv | k_pe] = h W_dkv``, ``c =
  N(c_kv)``; ``k_nope_i = c W_uk_i``, ``v_i = c W_uv_i``; scores ``(q_nope_i .
  k_nope_i + q_pe_i . k_pe) / sqrt(nope + pe)``, causal softmax, ``out =
  concat(sum p v_i) W_o``.  The ``qk_rope_head_dim`` columns are kept and NOT
  rotated.  **The pool holds ``[c | k_pe]``** a position for the MLA layers
  ONLY (leaf ``ckv``, the row zero-padded to a lane multiple as
  ``models/pangu_moe.py``'s).  Everything after the two projections is
  ``pangu_moe``'s: the absorbed decode through ``ops/mla_paged_attention.py``,
  a chunk's expanded attention through ``ops/mla_prefill_attention.py``.
- **Expert layer**: ``pangu_moe.moe_ffn``: sigmoid scores in float32 over all
  ``n_routed_experts``, the ``n_experts_per_tok`` largest (one group), gates
  ``routed_scaling_factor * s_e / sum_chosen s``; ``y = Shared(h) + sum_{e
  chosen and HELD} gate_e Expert_e(h)``: what absent experts would add is
  left out, in the reference alike, and the partial result goes on.  A
  decode token-step hands it ``active``: the held experts are multiplied
  for the decoding rows' pairs alone (``moe_ffn``'s ``live``).

**Two kinds of state** (models/family.py).  The pool pages the MLA layers'
latent rows.  A KDA layer's state does not page: the engine keeps it a SLOT
(``init_slot_state``: ``kda`` ``[KDA layers, max_batch, H, d_k, d_v]`` float32
and ``conv`` ``[KDA layers, max_batch, (kda_conv - 1) x 3 H d_k]``, a slot's
last inputs to the convolution, oldest tap first).

**Two forms that must agree.**  A prompt chunk runs the chunked (WY) form
(:func:`kda_chunked`) from the slot's state (zeros where ``p0 == 0``) to the
state after its last REAL token: a position past ``take`` gets ``g = 0`` and
``beta = 0``, which neither decays the state nor adds to it.  A decode
token-step runs the one-step recurrence: ONE call of the Pallas kernel
``kda_state_update`` (``ops/kda_state_update.py``) a layer for the rows with
``active != 0`` and nothing for the others.

**The layer scan.**  Weights are stacked by KIND (``kda``, ``mla``, ``dense``,
``moe``; the norms by layer).  The ``first_k_dense`` leading layers run one
by one (their feed-forward differs).  The rest is PERIODS, each some KDA
layers and then one MLA layer, an expert layer after every one: an outer scan
over the periods whose body is a loop over the period's KDA layers with a
trip count that is DATA (the published stack after its first layer is ``K K
M``, five times ``K K K M``, ``K K M``: no whole number of one period) and
then the MLA layer, so a program holds three layer bodies whatever the depth
and the pattern.  A layer's weights are indexed out of the whole stacks by
the layer's own number, and the held experts' stacks are never sliced before
a layer's (``pangu_moe.moe_ffn``'s ``layer``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import pangu_moe as pm
from ray_tpu.ops import kda_state_update as kda_ops
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.ssm_state_update import live_rows

Params = Dict[str, Any]

DECODE_COUNTERS = pm.DECODE_COUNTERS
PREFILL_KV_TILE = pm.PREFILL_KV_TILE
_HIGHEST = lax.Precision.HIGHEST


def _published_layers(n_layers: int = 27) -> Tuple[str, ...]:
    """``K K K M`` repeated, the last layer MLA whatever the count."""
    return tuple("mla" if (i + 1) % 4 == 0 or i + 1 == n_layers else "kda"
                 for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    dim: int = 2304
    layer_types: Tuple[str, ...] = _published_layers()
    first_k_dense: int = 1
    # KDA
    kda_n_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    # width of the two low-rank gates (``assumed``: the head width)
    kda_gate_rank: int = 128
    # positions a step of the chunked (WY) form takes
    kda_chunk_size: int = 32
    # MLA (the names ``pangu_moe``'s functions read)
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # feed-forwards (likewise)
    ffn_dim: int = 9216
    moe_ffn_dim: int = 1024
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    n_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.446
    # experts [start, stop) of the n_routed_experts whose weights live here
    experts_held: Tuple[int, int] = (0, 16)
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 6144
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_types) - {"kda", "mla"}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.layer_types[-1] != "mla":
            raise ValueError("the stack ends in a full-attention layer: the "
                             "layer scan's periods each end in one")
        if not 0 <= self.first_k_dense < self.n_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        """Layers whose mixer is ``kind`` in the whole model."""
        return self.layer_types.count(kind)

    @property
    def kda_inner(self) -> int:
        """Channels of each of ``q``, ``k``, ``v``: heads x head width."""
        return self.kda_n_heads * self.kda_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 3 * self.kda_inner

    @property
    def periods(self) -> Tuple[int, ...]:
        """KDA layers before each MLA layer, past the leading dense layers:
        ``(2, 3, 3, 3, 3, 3, 2)`` as published."""
        out, n = [], 0
        for kind in self.layer_types[self.first_k_dense:]:
            if kind == "kda":
                n += 1
            else:
                out.append(n)
                n = 0
        return tuple(out)

    # what ``pangu_moe``'s functions read of a config
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
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return -(-self.latent_width // 128) * 128

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(jax.eval_shape(
            lambda: init_params(self, jax.random.PRNGKey(0)))))

    @classmethod
    def from_published(cls, config: dict, **kw) -> "KimiLinearConfig":
        """The config for a published ``config.json`` (``model_type``
        ``kimi_linear``) under its own key names, ``linear_attn_config``
        nested as published.  ``num_experts`` is the count of experts HELD
        where the file gives ``router_outputs`` (the router's width) and
        ``experts_held``.  ``kw``: fields the file does not give
        (``max_seq_len``, the dtypes).  What this family does not compute is
        refused here, by key."""
        refused = {
            "q_lora_rank": None, "mla_use_nope": True, "hidden_act": "silu",
            "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
            "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
            "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
        for k, want in refused.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this family computes "
                                 f"{want!r}")
        la = config["linear_attn_config"]
        n = config["num_hidden_layers"]
        kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
        if kda & full or kda | full != set(range(1, n + 1)):
            raise ValueError("kda_layers and full_attn_layers do not share "
                             f"out layers 1..{n}")
        held = tuple(config.get("experts_held", (0, config["num_experts"])))
        if held[1] - held[0] != config["num_experts"]:
            raise ValueError(f"experts_held {held} is not num_experts "
                             f"{config['num_experts']} experts")
        return cls(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_types=tuple("kda" if i in kda else "mla"
                              for i in range(1, n + 1)),
            first_k_dense=config["first_k_dense_replace"],
            kda_n_heads=la["num_heads"], kda_head_dim=la["head_dim"],
            kda_conv=la["short_conv_kernel_size"],
            kda_gate_rank=la["head_dim"],
            n_heads=config["num_attention_heads"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            ffn_dim=config["intermediate_size"],
            moe_ffn_dim=config["moe_intermediate_size"],
            n_routed_experts=config.get("router_outputs",
                                        config["num_experts"]),
            n_shared_experts=config["num_shared_experts"],
            n_experts_per_tok=config["num_experts_per_token"],
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            experts_held=held, rms_norm_eps=float(config["rms_norm_eps"]),
            **kw)

    @classmethod
    def tiny(cls, **kw) -> "KimiLinearConfig":
        """Test-sized, the published pattern: a dense first layer under a KDA
        mixer, then periods of 2, 3 and 2 KDA layers before an MLA layer; KDA
        heads of 128 x 128 and a latent of 128 + 8, which both decode kernels
        compute (in the interpreter)."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 64)
        kw.setdefault("layer_types",
                      ("kda",) * 3 + ("mla",) + ("kda",) * 3 + ("mla",)
                      + ("kda",) * 2 + ("mla",))
        kw.setdefault("kda_n_heads", 8)
        kw.setdefault("kda_chunk_size", 16)
        kw.setdefault("n_heads", 4)
        kw.setdefault("kv_lora_rank", 128)
        kw.setdefault("qk_nope_head_dim", 16)
        kw.setdefault("qk_rope_head_dim", 8)
        kw.setdefault("v_head_dim", 16)
        kw.setdefault("ffn_dim", 128)
        kw.setdefault("moe_ffn_dim", 32)
        kw.setdefault("n_routed_experts", 32)
        kw.setdefault("n_experts_per_tok", 4)
        kw.setdefault("experts_held", (0, 8))
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("param_dtype", jnp.float32)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def init_params(cfg: KimiLinearConfig, key: jax.Array) -> Params:
    """Seeded random weights, stacked by kind: ``kda`` ``[KDA layers, ...]``,
    ``mla`` ``[MLA layers, ...]``, ``dense`` ``[first_k_dense, ...]``, ``moe``
    ``[expert layers, ...]`` and the two norms ``[layers, ...]``.  Matrices
    N(0, 0.02), output projections N(0, 0.02 / sqrt(2 x layers)).  The decay's
    parameters as the family initialises them, so that random weights decay
    as a trained model's do: ``A_log = log(U(1, 16))`` a head, ``dt_bias``
    the inverse softplus of a step log-uniform in [0.001, 0.1] a channel; the
    convolution U(+-1/sqrt(taps)) (a depthwise kernel's default)."""
    dt = cfg.param_dtype
    d, nl = cfg.dim, cfg.n_layers
    nk, na = cfg.count("kda"), cfg.count("mla")
    nd, nm = cfg.first_k_dense, cfg.n_moe_layers
    h, i, r = cfg.kda_n_heads, cfg.kda_inner, cfg.kda_gate_rank
    e, f = cfg.n_held, cfg.moe_ffn_dim
    fs = cfg.n_shared_experts * f
    std = 0.02
    out_std = std / math.sqrt(2 * nl)
    keys = iter(jax.random.split(key, 40))

    def mat(*shape, std=std, dtype=dt):
        return pm._normal(next(keys), shape, std, dtype)

    bound = 1.0 / math.sqrt(cfg.kda_conv)
    step = jnp.exp(jax.random.uniform(
        next(keys), (nk, i), jnp.float32, math.log(0.001), math.log(0.1)))
    params: Params = {
        "embed": mat(1, cfg.vocab_size, d)[0],
        "final_norm": jnp.ones((d,), dt),
        "lm_head": mat(1, d, cfg.vocab_size)[0],
        "norms": {"mixer": jnp.ones((nl, d), dt),
                  "ffn": jnp.ones((nl, d), dt)},
        "kda": {
            "w_qkv": mat(nk, d, 3 * i),          # [W_q | W_k | W_v]
            "conv_w": jax.random.uniform(
                next(keys), (nk, cfg.kda_conv, 3 * i), jnp.float32,
                -bound, bound).astype(dt),
            "w_lr": mat(nk, d, 2 * r),           # [W_fa | W_ga]
            "w_fb": mat(nk, r, i),
            "w_gb": mat(nk, r, i),
            "w_b": mat(nk, d, h),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (nk, h), jnp.float32, 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "o_norm": jnp.ones((nk, cfg.kda_head_dim), dt),
            "w_o": mat(nk, i, d, std=out_std),
        },
        "mla": {
            # a head [q_nope | q_pe]
            "w_q": mat(na, d, cfg.n_heads * cfg.qk_head_dim),
            "w_dkv": mat(na, d, cfg.latent_width),   # [c_kv | k_pe]
            "kv_norm": jnp.ones((na, cfg.kv_lora_rank), dt),
            # W_ukv's two halves a head, as ``pangu_moe`` keeps them
            "w_uk": mat(na, cfg.n_heads, cfg.qk_nope_head_dim,
                        cfg.kv_lora_rank),
            "w_uv": mat(na, cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim),
            "w_o": mat(na, cfg.n_heads * cfg.v_head_dim, d, std=out_std),
        },
        "moe": {
            # the router keeps every published output, in float32
            "router": mat(nm, d, cfg.n_routed_experts, dtype=jnp.float32),
            "ws_gate": mat(nm, d, fs), "ws_up": mat(nm, d, fs),
            "ws_down": mat(nm, fs, d, std=out_std),
            # the held experts side by side (``pangu_moe.init_params``)
            "we_gate": mat(nm, d, e * f), "we_up": mat(nm, d, e * f),
            "we_down": mat(nm, e * f, d, std=out_std),
        },
    }
    if nd:
        params["dense"] = {
            "w_gate": mat(nd, d, cfg.ffn_dim), "w_up": mat(nd, d, cfg.ffn_dim),
            "w_down": mat(nd, cfg.ffn_dim, d, std=out_std)}
    return params


def init_paged_cache(cfg: KimiLinearConfig, num_blocks: int,
                     block_size: int) -> Dict[str, jnp.ndarray]:
    """The latent block pool of the MLA layers ONLY: one leaf, ``[MLA layers,
    blocks, block_size, cache_width]`` of ``[c | k_pe | 0]``."""
    return {"ckv": jnp.zeros(
        (cfg.count("mla"), num_blocks, block_size, cfg.cache_width),
        cfg.compute_dtype)}


def init_slot_state(cfg: KimiLinearConfig,
                    max_batch: int) -> Dict[str, jnp.ndarray]:
    """The state a slot holds (family seam): every leaf ``[KDA layers,
    max_batch, ...]``."""
    nk = cfg.count("kda")
    return {
        # float32 at rest (``assumed``: the published config says only
        # bfloat16 for the model)
        "kda": jnp.zeros(kda_ops.state_shape(
            nk, max_batch, cfg.kda_n_heads, cfg.kda_head_dim,
            cfg.kda_head_dim), jnp.float32),
        # the last kda_conv - 1 inputs [q | k | v], oldest tap first, flat:
        # as [.., taps, channels] the device would pad 3 taps to a 16-row
        # memory tile, five times the bytes
        "conv": jnp.zeros((nk, max_batch,
                           (cfg.kda_conv - 1) * cfg.conv_width),
                          cfg.compute_dtype),
    }


# -- the pieces both programs share ---------------------------------------------


def _at_layer(tree, i):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _kda_project(cfg, lp, u):
    """``u [T, d]`` -> ``(qkv [T, 3I] before the convolution, g's and the
    output gate's low-rank inputs [T, 2r], beta [T, H] float32)``.  The
    barrier keeps the wide projection ONE product in its own layout
    (``granite_hybrid._project``)."""
    cdt = cfg.compute_dtype
    qkv = lax.optimization_barrier(u @ lp["w_qkv"].astype(cdt))
    beta = jax.nn.sigmoid((u @ lp["w_b"].astype(cdt)).astype(jnp.float32))
    return qkv, u @ lp["w_lr"].astype(cdt), beta


def _kda_heads(cfg, x):
    return x.reshape(x.shape[:-1] + (cfg.kda_n_heads, cfg.kda_head_dim))


def _kda_decay(cfg, lp, lr):
    """``g [T, H, d_k]`` float32, the log of the decay (<= 0)."""
    f32 = jnp.float32
    r = cfg.kda_gate_rank
    dt = (lr[..., :r] @ lp["w_fb"].astype(cfg.compute_dtype)).astype(f32)
    sp = _kda_heads(cfg, jax.nn.softplus(dt + lp["dt_bias"].astype(f32)))
    return -jnp.exp(lp["a_log"].astype(f32))[:, None] * sp


def _kda_output(cfg, lp, o, lr):
    """``N_head(o) * sigmoid(gate)`` then ``W_o``: ``o [T, H, d_v]``
    float32 -> ``[T, d]`` in the compute dtype."""
    cdt = cfg.compute_dtype
    r = cfg.kda_gate_rank
    gate = jax.nn.sigmoid(_kda_heads(cfg, (
        lr[..., r:] @ lp["w_gb"].astype(cdt)).astype(jnp.float32)))
    y = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps) * gate
    return y.reshape(y.shape[:-2] + (cfg.kda_inner,)).astype(cdt) @ lp[
        "w_o"].astype(cdt)


def _mla_queries(cfg, h, lp):
    """``(q_nope [.., H, nope], q_pe [.., H, pe])`` of normed inputs ``h
    [.., d]``: no down-projection, no rotation.  The barrier keeps the
    projection a plain product (``pangu_moe._queries``)."""
    q = lax.optimization_barrier(
        h @ lp["w_q"].astype(cfg.compute_dtype)).reshape(
            h.shape[:-1] + (cfg.n_heads, cfg.qk_head_dim))
    return q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]


def _mla_latent(cfg, h, lp):
    """The cache's rows ``[.., cache_width]`` of normed inputs ``h``:
    ``[N(c_kv) | k_pe | 0]``, ``k_pe`` as projected."""
    cdt = cfg.compute_dtype
    kv = h @ lp["w_dkv"].astype(cdt)
    c = rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_norm"], cfg.rms_norm_eps)
    pad = jnp.zeros(kv.shape[:-1] + (cfg.cache_width - cfg.latent_width,),
                    cdt)
    return jnp.concatenate([c, kv[..., cfg.kv_lora_rank:], pad], axis=-1)


def _ffn(cfg, params, x, li, dense: bool, live=None, interpret=False):
    """``x + FFN(N(x))`` of ``x [T, d]`` for layer ``li`` (its own number,
    from 0); for an expert layer of a decode token-step (``live [T]``: the
    rows that decode, which alone reach the held experts; None: a prompt
    chunk) also its decode counters."""
    with jax.named_scope("ffn"):
        h = rms_norm(x, lax.dynamic_index_in_dim(
            params["norms"]["ffn"], li, 0, keepdims=False), cfg.rms_norm_eps)
        if dense:
            return x + pm._dense_ffn(
                cfg, h, _at_layer(params["dense"], li)).astype(x.dtype), None
        mi = li - cfg.first_k_dense
        mp = params["moe"]
        # the held experts' stacks go in whole, ``mi`` picks the layer's
        lp = {k: (v if k in pm.HELD_EXPERT_LEAVES
                  else lax.dynamic_index_in_dim(v, mi, 0, keepdims=False))
              for k, v in mp.items()}
        with jax.named_scope("moe"):
            y, g, grouped = pm.moe_ffn(cfg, h, lp, interpret, layer=mi,
                                       live=live)
        booked = (None if live is None
                  else pm.decode_booking(cfg, g, live, grouped))
        return x + y.astype(x.dtype), booked


def _run_layers(cfg, params, carry, kda_mixer, mla_mixer, ffn):
    """Every layer over ``carry`` (a tuple whose first element is the hidden
    rows): the leading dense layers one by one, then an outer scan over the
    periods, inside it a loop over the period's KDA layers whose trip count
    is scanned-over DATA, then the period's MLA layer (module docstring).
    ``kda_mixer(carry, lp, ki)`` / ``mla_mixer(carry, lp, ai)`` return the
    carry after the mixer (residual added), ``lp`` the layer's own weights
    and its input norm's, ``ki`` / ``ai`` its number among its kind;
    ``ffn(carry, li, dense)`` the carry after layer ``li``'s feed-forward."""

    def mixer(carry, kind, idx, li):
        lp = dict(_at_layer(params[kind], idx),
                  in_norm=lax.dynamic_index_in_dim(
                      params["norms"]["mixer"], li, 0, keepdims=False))
        return (kda_mixer if kind == "kda" else mla_mixer)(carry, lp, idx)

    at = {"kda": 0, "mla": 0}
    for li in range(cfg.first_k_dense):
        kind = cfg.layer_types[li]
        carry = ffn(mixer(carry, kind, at[kind], li), li, True)
        at[kind] += 1

    n_k = np.asarray(cfg.periods, np.int32)
    k_lo = at["kda"] + np.concatenate([[0], np.cumsum(n_k)[:-1]])
    l_lo = cfg.first_k_dense + np.concatenate(
        [[0], np.cumsum(n_k + 1)[:-1]])

    def period(carry, inp):
        n, k0, l0, ai = inp

        def kda_layer(j, carry):
            return ffn(mixer(carry, "kda", k0 + j, l0 + j), l0 + j, False)

        carry = lax.fori_loop(0, n, kda_layer, carry)
        return ffn(mixer(carry, "mla", ai, l0 + n), l0 + n, False), None

    carry, _ = lax.scan(period, carry, (
        jnp.asarray(n_k), jnp.asarray(k_lo, jnp.int32),
        jnp.asarray(l_lo, jnp.int32),
        at["mla"] + jnp.arange(len(n_k), dtype=jnp.int32)))
    return carry


# -- the chunked (WY) form ------------------------------------------------------------


def kda_chunked(q, k, v, g, beta, s0, chunk: int):
    """The delta-rule recurrence over ``C`` positions as matrix products.

    q, k ``[C, H, d_k]`` float32, normalised (``q^``, ``k^``); v ``[C, H,
    d_v]``; g ``[C, H, d_k]`` float32, the log decay (<= 0; 0 at a position
    that must not advance the state); beta ``[C, H]`` (0 there); s0 ``[H,
    d_k, d_v]`` float32, the state before position 0.  Inside a step of
    ``Q = chunk`` positions, with ``G_r`` the running sum of ``g``:
    ``A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])`` for ``i <
    r``; ``(I + A) [W | U] = Diag(beta) [K * exp(G) | V]`` (a unit lower
    triangular solve); ``V' = U - W S_0``; ``o_r = (q_r * exp(G_r)) S_0 +
    sum_{i <= r} (sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])) V'_i``; ``S_Q =
    Diag(exp(G_Q)) S_0 + sum_i (k_i * exp(G_Q - G_i)) V'_i^T``.  Only
    differences ``G_r - G_i`` with ``i <= r`` are ever exponentiated (masked
    BEFORE the exponential), never ``exp(-G)`` alone.  Between steps the
    state is carried.  Returns ``(o [C, H, d_v] float32, the state after
    position C - 1)``."""
    f32 = jnp.float32
    c, h, dk = q.shape
    qn = min(chunk, c)
    if c % qn:
        raise ValueError(f"{c} positions are not whole steps of {qn}")
    nc = c // qn

    def steps(x):  # [C, H, ..] -> [nc, H, Q, ..]
        return jnp.swapaxes(x.astype(f32).reshape(nc, qn, *x.shape[1:]), 1, 2)

    q, k, v, g = steps(q), steps(k), steps(v), steps(g)
    beta = steps(beta)[..., None]                             # [nc, H, Q, 1]
    gs = jnp.cumsum(g, axis=2)                                # G, <= 0
    upto = jnp.tril(jnp.ones((qn, qn), bool))                 # i <= r
    decay = jnp.exp(jnp.where(
        upto[:, :, None], gs[:, :, :, None, :] - gs[:, :, None, :, :],
        -jnp.inf))                                            # [.., r, i, c]
    kd = k[:, :, None, :, :] * decay
    a = beta * jnp.sum(k[:, :, :, None, :] * kd, -1) * jnp.tril(
        jnp.ones((qn, qn), f32), -1)
    p = jnp.sum(q[:, :, :, None, :] * kd, -1)                 # i <= r by mask
    eg = jnp.exp(gs)
    wu = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(qn, dtype=f32),
        beta * jnp.concatenate([k * eg, v], -1), lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    to_end = k * jnp.exp(gs[:, :, -1:, :] - gs)               # k_i e^{G_Q-G_i}

    def carry_on(s, inp):
        w_c, u_c, qe_c, p_c, to_end_c, through_c = inp
        vp = u_c - jnp.einsum("hqd,hde->hqe", w_c, s, precision=_HIGHEST)
        o = (jnp.einsum("hqd,hde->hqe", qe_c, s, precision=_HIGHEST)
             + jnp.einsum("hqi,hie->hqe", p_c, vp, precision=_HIGHEST))
        s = through_c[..., None] * s + jnp.einsum(
            "hqd,hqe->hde", to_end_c, vp, precision=_HIGHEST)
        return s, o

    s_out, o = lax.scan(carry_on, s0.astype(f32),
                        (w, u, q * eg, p, to_end, eg[:, :, -1, :]))
    return jnp.swapaxes(o, 1, 2).reshape(c, h, -1), s_out


# -- a prompt chunk -----------------------------------------------------------------


def prefill_chunk_paged(cfg: KimiLinearConfig, params: Params,
                        tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                        table: jnp.ndarray, p0: jnp.ndarray, *,
                        rope_cache=None, tp_plan=None,
                        use_kernel: bool = False,
                        kernel_interpret: bool = False, slot_state, slot,
                        take, kv_tile: int = PREFILL_KV_TILE):
    """One chunk of one sequence (``pangu_moe.prefill_chunk_paged``'s
    contract: the chunk's latent rows written to the pool, attention over
    the whole prefix a tile at a time, ``use_kernel``: inside the Pallas
    kernel) plus the slot's state (``granite_hybrid.prefill_chunk_paged``'s:
    ``slot`` the engine's slot, ``take`` the count of REAL tokens in ``tokens
    [1, C]``; the state comes in from the slot, zeros where ``p0 == 0``, and
    the state after token ``take - 1`` goes back; ``take == 0`` (warm-up)
    leaves the slot as it was).  ``kv_tile`` is for tests.  Returns ``(logits
    [1, C, V] float32, pool, slot_state)``."""
    del rope_cache, tp_plan
    use_kernel = use_kernel and pm.prefill_kernel_fits(cfg)
    _, c = tokens.shape
    bs = pool["ckv"].shape[2]
    if kv_tile % bs:
        raise ValueError(f"kv_tile ({kv_tile}) must be a multiple of the "
                         f"block size ({bs})")
    cdt, f32 = cfg.compute_dtype, jnp.float32
    cw, taps = cfg.conv_width, cfg.kda_conv - 1
    i = cfg.kda_inner
    positions = p0 + jnp.arange(c)
    real = jnp.arange(c) < take
    fresh, keep = p0 == 0, take > 0
    chunk_blocks = lax.dynamic_slice(table[0], (p0 // bs,), (c // bs,))
    row = jnp.pad(table[0], (0, -table.shape[1] % (kv_tile // bs)))
    x = jnp.take(params["embed"], tokens[0], axis=0).astype(cdt)

    def kda_mixer(carry, lp, ki):
        x, ckv, states, wins = carry
        s_old = lax.dynamic_index_in_dim(states, ki, 0, keepdims=False)
        win_old = lax.dynamic_index_in_dim(wins, ki, 0, keepdims=False)
        with jax.named_scope("kda"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            qkv, lr, beta = _kda_project(cfg, lp, u)
            win_in = jnp.where(fresh, jnp.zeros_like(win_old), win_old)
            seq = jnp.concatenate(
                [win_in.reshape(taps, cw), qkv.astype(win_old.dtype)], axis=0)
            w = lp["conv_w"].astype(f32)
            acc = jnp.zeros((c, cw), f32)
            for j in range(cfg.kda_conv):
                acc = acc + w[j][None, :] * seq[j:j + c].astype(f32)
            # the window after the last REAL token
            win_new = lax.dynamic_slice(seq, (take, 0), (taps, cw)).reshape(-1)
            act = _kda_heads(cfg, jax.nn.silu(acc).astype(cdt).reshape(
                c, 3, i))                                    # [C, 3, H, d]
            g = jnp.where(real[:, None, None], _kda_decay(cfg, lp, lr), 0.0)
            beta = jnp.where(real[:, None], beta, 0.0)
            s0 = jnp.where(fresh, jnp.zeros_like(s_old), s_old)
            o, s_new = kda_chunked(
                kda_ops.l2_normalize(act[:, 0]) * cfg.kda_head_dim ** -0.5,
                kda_ops.l2_normalize(act[:, 1]), act[:, 2], g, beta, s0,
                cfg.kda_chunk_size)
            x = x + _kda_output(cfg, lp, o, lr).astype(x.dtype)
            states = lax.dynamic_update_index_in_dim(
                states, jnp.where(keep, s_new.astype(s_old.dtype), s_old),
                ki, 0)
            wins = lax.dynamic_update_index_in_dim(
                wins, jnp.where(keep, win_new, win_old), ki, 0)
        return x, ckv, states, wins

    def mla_mixer(carry, lp, ai):
        x, ckv, *mine = carry
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q_nope, q_pe = _mla_queries(cfg, h, lp)
            lat = _mla_latent(cfg, h, lp)
            ckv = ckv.at[ai, chunk_blocks].set(
                lat.reshape(c // bs, bs, -1).astype(ckv.dtype))
            if use_kernel:
                attn = pm._attend_kernel(cfg, q_nope, q_pe, ckv, ai, row, p0,
                                         lp, kv_tile, kernel_interpret)
            else:
                attn = pm._attend_tiles_expanded(
                    cfg, q_nope, q_pe, ckv, ai, row, positions, lp, kv_tile)
            x = x + (attn.astype(cdt) @ lp["w_o"].astype(cdt)).astype(x.dtype)
        return (x, ckv, *mine)

    def ffn(carry, li, dense):
        x, _ = _ffn(cfg, params, carry[0], li, dense,
                    interpret=kernel_interpret)
        return (x,) + tuple(carry[1:])

    # the slot's own state, every layer's, out of the leaves BEFORE the layer
    # loops and back AFTER them (``granite_hybrid.prefill_chunk_paged``)
    kda, conv = slot_state["kda"], slot_state["conv"]
    x, ckv, states, wins = _run_layers(
        cfg, params,
        (x, pool["ckv"],
         lax.dynamic_index_in_dim(kda, slot, 1, keepdims=False),
         lax.dynamic_index_in_dim(conv, slot, 1, keepdims=False)),
        kda_mixer, mla_mixer, ffn)
    kda = lax.dynamic_update_index_in_dim(kda, states, slot, 1)
    conv = lax.dynamic_update_index_in_dim(conv, wins, slot, 1)
    return (pm._head(cfg, params, x)[None], {"ckv": ckv},
            {"kda": kda, "conv": conv})


# -- a decode token-step --------------------------------------------------------------


def kernel_supported(cfg: KimiLinearConfig) -> bool:
    """Both decode kernels apply: a TPU backend, a latent whose value part
    ends on a lane tile, a KDA head of one 128 x 128 tile."""
    if not pm.kernel_supported(cfg):
        return False
    return kda_ops.unsupported(cfg.kda_n_heads, cfg.kda_head_dim,
                               cfg.kda_head_dim) is None


def decode_step_paged(cfg: KimiLinearConfig, params: Params,
                      tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      table: jnp.ndarray, lengths: jnp.ndarray, *,
                      rope_cache=None, use_kernel: bool = False, mesh=None,
                      kernel_interpret: bool = False, tp_plan=None,
                      active: Optional[jnp.ndarray] = None, slot_state):
    """One token for every slot (``pangu_moe.decode_step_paged``'s contract
    over the latent pool, in absorbed form) plus the slots' state: a row with
    ``active == 0`` keeps its KDA state and its convolution window bit for
    bit, whatever its token is.  ``use_kernel``: ``kda_state_update`` for the
    decoding rows and the latent decode kernel over their live pages; else
    ``jax.numpy`` over every row.  Returns ``(logits [B, V] float32, pool,
    slot_state, counters int32: DECODE_COUNTERS)``."""
    del rope_cache, mesh, tp_plan
    b = tokens.shape[0]
    bs = pool["ckv"].shape[2]
    w = table.shape[1]
    cdt, f32 = cfg.compute_dtype, jnp.float32
    cw, taps, i = cfg.conv_width, cfg.kda_conv - 1, cfg.kda_inner
    active = jnp.ones_like(lengths) if active is None else active
    live_list = live_rows(active) if use_kernel else None
    cur_blk = table[jnp.arange(b), lengths // bs]
    cur_off = lengths % bs
    if not use_kernel:
        span_mask = (jnp.arange(w * bs)[None, None, :]
                     <= lengths[:, None, None])
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)

    def kda_mixer(carry, lp, ki):
        x, ckv, kda, conv, booked = carry
        with jax.named_scope("kda"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            qkv, lr, beta = _kda_project(cfg, lp, u)
            win = lax.dynamic_index_in_dim(conv, ki, 0, keepdims=False)
            cur = qkv.astype(win.dtype)
            cw_ = lp["conv_w"].astype(f32)
            acc = cw_[taps][None, :] * cur.astype(f32)
            for j in range(taps):
                acc = acc + cw_[j][None, :] * win[:, j * cw:(j + 1) * cw
                                                  ].astype(f32)
            # the decoding rows' windows move on; the others stay
            conv = lax.dynamic_update_index_in_dim(conv, jnp.where(
                (active != 0)[:, None],
                jnp.concatenate([win[:, cw:], cur], axis=1), win), ki, 0)
            act = _kda_heads(cfg, jax.nn.silu(acc).astype(cdt).reshape(
                b, 3, i))                                    # [B, 3, H, d]
            g = _kda_decay(cfg, lp, lr)
            if use_kernel:  # ONE call for the rows that decode
                o, kda = kda_ops.kda_state_update(
                    kda, ki, act[:, 0], act[:, 1], act[:, 2], g, beta,
                    active, live_list, interpret=kernel_interpret)
            else:
                o, kda = kda_ops.kda_state_update_jnp(
                    kda, ki, act[:, 0], act[:, 1], act[:, 2], g, beta, active)
            x = x + _kda_output(cfg, lp, o, lr).astype(x.dtype)
        return x, ckv, kda, conv, booked

    def mla_mixer(carry, lp, ai):
        x, ckv, *rest = carry
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q_nope, q_pe = _mla_queries(cfg, h, lp)
            ckv = ckv.at[ai, cur_blk, cur_off].set(
                _mla_latent(cfg, h, lp).astype(ckv.dtype))
            q_abs = pm._absorb_queries(cfg, q_nope, q_pe, lp)
            if use_kernel:
                from ray_tpu.ops.mla_paged_attention import (
                    mla_paged_decode_attention,
                )

                o_lat = mla_paged_decode_attention(
                    q_abs, ckv, ai, table, lengths, active,
                    value_width=cfg.kv_lora_rank,
                    scale=1.0 / math.sqrt(cfg.qk_head_dim),
                    interpret=kernel_interpret)
            else:
                span = ckv[ai, table].reshape(b, w * bs, cfg.cache_width)
                o_lat = pm._attend_absorbed(cfg, q_abs[:, None], span,
                                            span_mask)[:, 0]
            out = pm._unabsorb(cfg, o_lat, lp) @ lp["w_o"].astype(cdt)
            x = x + out.astype(x.dtype)
        return (x, ckv, *rest)

    def ffn(carry, li, dense):
        x, got = _ffn(cfg, params, carry[0], li, dense, live=active,
                      interpret=kernel_interpret)
        booked = carry[-1] if got is None else carry[-1] + got
        return (x,) + tuple(carry[1:-1]) + (booked,)

    # every slot's state rides the carry, updated in place
    x, ckv, kda, conv, booked = _run_layers(
        cfg, params,
        (x, pool["ckv"], slot_state["kda"], slot_state["conv"],
         jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)),
        kda_mixer, mla_mixer, ffn)
    return (pm._head(cfg, params, x), {"ckv": ckv},
            {"kda": kda, "conv": conv}, booked)


# -- the family seam (models/family.py) -------------------------------------------------


def _no_rope(cfg, max_seq):
    return None


def _reference_logits(cfg, params, tokens, first_row: int = 0):
    from ray_tpu.models.kimi_linear_reference import reference_logits

    return reference_logits(cfg, params, tokens, first_row=first_row)


def _reference_slot_state(cfg, params, tokens, slot_state):
    from ray_tpu.models.kimi_linear_reference import reference_state

    return {"kda": (jnp.asarray(slot_state["kda"], jnp.float32),
                    reference_state(cfg, params, tokens))}


def _family():
    from ray_tpu.models.family import ModelFamily

    return ModelFamily(
        name="kimi_linear", config_type=KimiLinearConfig,
        init_params=init_params, init_paged_cache=init_paged_cache,
        rope_cache=_no_rope, prefill_chunk=prefill_chunk_paged,
        decode_step=decode_step_paged, kernel_supported=kernel_supported,
        prefill_visited_pages=pm._prefill_visited_pages,
        reference_logits=_reference_logits,
        prefill_kernel_fits=pm.prefill_kernel_fits,
        prefill_grouped_from=pm.grouped_ffn_from,
        decode_counters=DECODE_COUNTERS,
        init_slot_state=init_slot_state,
        reference_slot_state=_reference_slot_state)


FAMILY = _family()
