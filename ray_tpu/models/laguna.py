"""Laguna-style decoder, as one chip's share of an expert-parallel
deployment: WINDOW and FULL attention layers in one stack, whose head counts,
rotary tables and caches differ by layer type, a dense feed-forward after the
first layer and 256-way softmax-routed experts (of which this chip holds a
range) beside a shared expert after every other.

The equations, as computed (``models/laguna_reference.py`` computes the same
in float32 with a plain ``[S, S]`` mask a layer).  ``N``: RMSNorm, eps
``rms_norm_eps``, a weight of its own each use.

- ``x_0 = E[token]``; for every layer ``x <- x + Attn_t(N(x))``, ``x <- x +
  FFN(N(x))`` (pre-norm: ``assumed``); logits ``= N(x) W_head`` (untied).
  ``t`` is the layer's entry in ``layer_types``: ``"full"`` or ``"window"``.
- **Attn_t**, ``H_t`` query heads (``n_heads_full``, ``n_heads_window``) over
  ``n_kv_heads`` key/value heads of ``head_dim``, no biases: ``[q | k | v] =
  h W_qkv`` (``W_q``, ``W_k``, ``W_v`` side by side as ONE matrix); ``q, k <-
  rope_t(q, k, position)``; scores ``q k^T / sqrt(head_dim)``, causal, and on
  a window layer only keys with ``0 <= p_q - p_k < window``; softmax in
  float32; **``o_head <- sigmoid(h W_g)_head * o_head``** (one gate a head,
  of the layer's normed input, before ``W_o``: ``assumed``); output
  ``concat(o) W_o``.
- **rope_window**: plain rotation, ``rope_theta_window``, every column of a
  head.  **rope_full**: YaRN (``ops/rope.py yarn_inverse_frequencies``:
  ``rope_full`` holds its parameters) over a head's FIRST
  ``rotary_dim_full`` columns, split-half inside them; the others pass
  unrotated; ``cos`` and ``sin`` carry YaRN's ``attention_factor``.
- **FFN**: a gated SiLU feed-forward of width ``ffn_dim`` for the
  ``first_k_dense`` leading layers; after them ``pangu_moe.moe_ffn`` under
  this config: ``p = softmax(h W_r)`` over all ``n_routed_experts`` in float32
  (``router_score``), the ``n_experts_per_tok`` largest, gates
  ``routed_scaling_factor * p_e / sum_chosen p``; ``y = Shared(h) + sum_{e
  chosen and HELD} gate_e Expert_e(h)``: what absent experts would add is left
  out, in the reference alike.

**Two caches** (models/family.py).  A full layer's keys and values live in the
paged pool (leaves ``k``, ``v``: ``[full layers, blocks, block_size, kv *
head_dim]``), written and read as ``models/llama.py``'s.  A window layer needs
its sequence's last ``window`` positions and no more: they live in the SLOT
STATE as a ring (leaves ``wk``, ``wv``: ``[window layers, max_batch, window,
kv * head_dim]``), position ``p`` at row ``p mod window``, written after the
rotation.  Attention is a sum over keys, so their order in the ring does not
matter, and which rows are live follows from the sequence's length alone: rows
``0 .. min(length, window) - 1`` hold its last positions, so a re-used slot's
older rows are never read and nothing is ever cleared.

- A decode token-step writes row ``length mod window`` for the rows with
  ``active != 0`` (the others keep their ring bit for bit) and reads
  ``min(length + 1, window)`` rows: seen as a pool of ``max_batch x (window /
  page)`` pages under a constant table and a clamped length, that is what
  ``ops/paged_attention.py`` computes, under a name of its own in a device
  trace (``WINDOW_KERNEL_NAME``).
- A prompt chunk's window layer attends its own ``C`` keys and the ring's
  earlier ones (row ``r`` holds the largest position below ``p0`` that is
  ``r mod window``; none where that is negative, so ``p0 == 0`` reads
  nothing) under the mask above, then writes its last ``min(take, window)``
  REAL positions: padding never reaches the ring, and ``take == 0`` (warm-up)
  leaves it as it was.

**The layer scan.**  Weights are stacked by KIND (``window``, ``full``,
``dense``, ``moe``; the norms by layer).  The ``first_k_dense`` leading layers
run one by one.  The rest is PERIODS, each some window layers and then one
full layer (``W W W F`` as published): an outer scan over the periods whose
body is a loop over the period's window layers with a trip count that is DATA,
then the full layer; window layers after the last full one (the published
stack ends ``W W W``) are one more such loop.  A program holds one body a
kind whatever the depth.
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
from ray_tpu.models.kimi_linear import _at_layer, _ffn
from ray_tpu.models.llama import (
    PREFILL_KV_TILE,
    _paged_attend,
    _prefill_attend_tiles,
    _prefill_visited_pages,
)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import (
    apply_rope,
    rope_at,
    split_rope_tables,
    yarn_inverse_frequencies,
)

Params = Dict[str, Any]

# what ``pangu_moe`` books a token-step, then, ONCE a token-step: the positions
# one window layer and one full layer read, summed over the rows that decode
DECODE_COUNTERS = pm.DECODE_COUNTERS + ("decode_window_positions",
                                        "decode_full_positions")
# the decode kernel's name in a device trace, by the kind of layer that calls
FULL_KERNEL_NAME = "paged_attention"
WINDOW_KERNEL_NAME = "window_paged_attention"
# ring rows one page of the decode kernel's view of it holds, at most
RING_PAGE = 128

_YARN_PUBLISHED = (("theta", 500000.0), ("factor", 128.0),
                   ("original_max_position", 8192), ("beta_fast", 32.0),
                   ("beta_slow", 1.0),
                   ("attention_factor", 1.4852030263919618))


def _published_layers(n_layers: int = 48) -> Tuple[str, ...]:
    """Every fourth layer full, from layer 0; the others window."""
    return tuple("window" if i % 4 else "full" for i in range(n_layers))


@dataclasses.dataclass(frozen=True)
class _Heads:
    """What ``models/llama.py``'s attention functions read of a config."""
    n_heads: int
    n_kv_heads: int
    head_dim: int


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    dim: int = 3072
    layer_types: Tuple[str, ...] = _published_layers()
    first_k_dense: int = 1
    n_heads_full: int = 48
    n_heads_window: int = 72
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    rope_theta_window: float = 10000.0
    # columns of a head the full layers rotate (its first), and YaRN's
    # parameters as ``ops/rope.py yarn_inverse_frequencies`` names them
    rotary_dim_full: int = 64
    rope_full: Tuple[Tuple[str, float], ...] = _YARN_PUBLISHED
    # feed-forwards (the names ``pangu_moe``'s functions read)
    ffn_dim: int = 12288
    moe_ffn_dim: int = 1024
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    n_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    router_score: str = "softmax"
    # experts [start, stop) of the n_routed_experts whose weights live here
    experts_held: Tuple[int, int] = (0, 16)
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 17408
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if set(self.layer_types) - {"window", "full"}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if not 0 <= self.first_k_dense < self.n_layers:
            raise ValueError(f"first_k_dense {self.first_k_dense}")
        if self.window % self.ring_page:
            raise ValueError(f"a window of {self.window} positions is not "
                             f"whole pages of {self.ring_page}")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        """Layers whose attention is ``kind`` in the whole model."""
        return self.layer_types.count(kind)

    def heads(self, kind: str) -> _Heads:
        return _Heads(self.n_heads_window if kind == "window"
                      else self.n_heads_full, self.n_kv_heads, self.head_dim)

    @property
    def kv_width(self) -> int:
        """Values a cached position's keys (or values) are, a layer."""
        return self.n_kv_heads * self.head_dim

    @property
    def ring_page(self) -> int:
        """Rows a page of the decode kernel's view of the ring holds: two
        pages at least, so that one's fetch hides under the other's work."""
        return min(RING_PAGE, max(1, self.window // 2))

    @property
    def periods(self) -> Tuple[Tuple[int, ...], int]:
        """``(window layers before each full layer past the leading dense
        layers, window layers after the last full one)``: ``((3,) * 11, 3)``
        as published."""
        out, n = [], 0
        for kind in self.layer_types[self.first_k_dense:]:
            if kind == "window":
                n += 1
            else:
                out.append(n)
                n = 0
        return tuple(out), n

    # what ``pangu_moe``'s functions read of a config
    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(jax.eval_shape(
            lambda: init_params(self, jax.random.PRNGKey(0)))))

    @classmethod
    def from_published(cls, config: dict, **kw) -> "LagunaConfig":
        """The config for a published ``config.json`` (``model_type``
        ``laguna``) under its own key names, ``rope_parameters`` nested as
        published.  ``num_experts`` is the count of experts HELD where the
        file gives ``router_outputs`` (the router's width) and
        ``experts_held``; the per-layer lists are as long as
        ``num_hidden_layers``.  ``kw``: fields the file does not give
        (``max_seq_len``, the dtypes).  What this family does not compute is
        refused here, by key."""
        refused = {
            "attention_bias": False, "norm_topk_prob": True,
            "decoder_sparse_step": 1, "tie_word_embeddings": False,
            "gating": "per-head", "moe_apply_router_weight_on_input": False,
            "moe_router_logit_softcapping": 0}
        for k, want in refused.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this family computes "
                                 f"{want!r}")
        n = config["num_hidden_layers"]
        names = {"sliding_attention": "window", "full_attention": "full"}
        kinds = tuple(names[t] for t in config["layer_types"])
        heads = config["num_attention_heads_per_layer"]
        dense = config["mlp_only_layers"]
        by_kind = {k: {h for h, t in zip(heads, kinds) if t == k}
                   for k in ("window", "full")}
        if (len(kinds) != n or len(heads) != n
                or dense != list(range(len(dense)))
                or any(len(v) > 1 for v in by_kind.values())):
            raise ValueError(
                f"{n} layers need {n} layer_types and head counts, one head "
                "count a layer type, and mlp_only_layers the leading layers")
        if (config["shared_expert_intermediate_size"]
                % config["moe_intermediate_size"]):
            raise ValueError("the shared expert is not whole experts wide")
        held = tuple(config.get("experts_held", (0, config["num_experts"])))
        if held[1] - held[0] != config["num_experts"]:
            raise ValueError(f"experts_held {held} is not num_experts "
                             f"{config['num_experts']} experts")
        rope = config["rope_parameters"]
        rw, rf = rope["sliding_attention"], rope["full_attention"]
        if (rw["rope_type"], rw["partial_rotary_factor"],
                rf["rope_type"]) != ("default", 1, "yarn"):
            raise ValueError(f"rope_parameters {rope}: this family computes "
                             "plain rotation of whole heads in the window "
                             "layers and YaRN in the full ones")
        hd = config["head_dim"]
        return cls(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_types=kinds, first_k_dense=len(dense),
            n_heads_full=by_kind["full"].pop() if by_kind["full"]
            else config["num_attention_heads"],
            n_heads_window=by_kind["window"].pop() if by_kind["window"]
            else config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"], head_dim=hd,
            window=config["sliding_window"],
            rope_theta_window=float(rw["rope_theta"]),
            rotary_dim_full=int(hd * rf["partial_rotary_factor"]),
            rope_full=(
                ("theta", float(rf["rope_theta"])),
                ("factor", float(rf["factor"])),
                ("original_max_position",
                 int(rf["original_max_position_embeddings"])),
                ("beta_fast", float(rf["beta_fast"])),
                ("beta_slow", float(rf["beta_slow"])),
                ("attention_factor", rf.get("attention_factor"))),
            ffn_dim=config["intermediate_size"],
            moe_ffn_dim=config["moe_intermediate_size"],
            n_routed_experts=config.get("router_outputs",
                                        config["num_experts"]),
            n_shared_experts=(config["shared_expert_intermediate_size"]
                              // config["moe_intermediate_size"]),
            n_experts_per_tok=config["num_experts_per_tok"],
            routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
            experts_held=held, rms_norm_eps=float(config["rms_norm_eps"]),
            **kw)

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """Test-sized, the published pattern: a dense first layer under full
        attention, one period ``W W W F`` and a tail ``W W``; 6 window heads
        against 4 full ones over 2 KV heads, a window of 8 positions, YaRN
        over half a head with a trained context of 16, so that its blend
        shows at test lengths."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 64)
        kw.setdefault("layer_types", _published_layers(7))
        kw.setdefault("n_heads_full", 4)
        kw.setdefault("n_heads_window", 6)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("head_dim", 16)
        kw.setdefault("window", 8)
        kw.setdefault("rotary_dim_full", 8)
        kw.setdefault("rope_full", (
            ("theta", 500000.0), ("factor", 4.0),
            ("original_max_position", 16), ("beta_fast", 32.0),
            ("beta_slow", 1.0), ("attention_factor", None)))
        kw.setdefault("ffn_dim", 128)
        kw.setdefault("moe_ffn_dim", 32)
        kw.setdefault("n_routed_experts", 16)
        kw.setdefault("n_experts_per_tok", 4)
        kw.setdefault("experts_held", (0, 4))
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("param_dtype", jnp.float32)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


# -- parameters, caches, tables ------------------------------------------------


def init_params(cfg: LagunaConfig, key: jax.Array) -> Params:
    """Seeded random weights, stacked by kind: ``window`` ``[window layers,
    ...]``, ``full`` ``[full layers, ...]``, ``dense`` ``[first_k_dense,
    ...]``, ``moe`` ``[expert layers, ...]`` and the two norms ``[layers,
    ...]``.  Matrices N(0, 0.02), output projections N(0, 0.02 / sqrt(2 x
    layers))."""
    dt = cfg.param_dtype
    d, nl = cfg.dim, cfg.n_layers
    nd, nm = cfg.first_k_dense, cfg.n_moe_layers
    e, f = cfg.n_held, cfg.moe_ffn_dim
    fs = cfg.n_shared_experts * f
    std = 0.02
    out_std = std / math.sqrt(2 * nl)
    keys = iter(jax.random.split(key, 24))

    def mat(*shape, std=std, dtype=dt):
        return pm._normal(next(keys), shape, std, dtype)

    def attention(kind):
        n, h = cfg.count(kind), cfg.heads(kind)
        return {
            # [W_q | W_k | W_v], a head's columns side by side
            "w_qkv": mat(n, d, (h.n_heads + 2 * h.n_kv_heads) * h.head_dim),
            "w_g": mat(n, d, h.n_heads),
            "w_o": mat(n, h.n_heads * h.head_dim, d, std=out_std)}

    params: Params = {
        "embed": mat(1, cfg.vocab_size, d)[0],
        "final_norm": jnp.ones((d,), dt),
        "lm_head": mat(1, d, cfg.vocab_size)[0],
        "norms": {"mixer": jnp.ones((nl, d), dt),
                  "ffn": jnp.ones((nl, d), dt)},
        "window": attention("window"),
        "full": attention("full"),
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


def init_paged_cache(cfg: LagunaConfig, num_blocks: int,
                     block_size: int) -> Dict[str, jnp.ndarray]:
    """The block pool of the FULL layers only, as ``llama``'s: ``k`` and
    ``v`` ``[full layers, blocks, block_size, kv * head_dim]``."""
    shape = (cfg.count("full"), num_blocks, block_size, cfg.kv_width)
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def init_slot_state(cfg: LagunaConfig,
                    max_batch: int) -> Dict[str, jnp.ndarray]:
    """The ring a slot holds (module docstring): ``wk`` and ``wv`` ``[window
    layers, max_batch, window, kv * head_dim]``."""
    shape = (cfg.count("window"), max_batch, cfg.window, cfg.kv_width)
    return {"wk": jnp.zeros(shape, cfg.compute_dtype),
            "wv": jnp.zeros(shape, cfg.compute_dtype)}


def make_rope_cache(cfg: LagunaConfig, max_seq: int):
    """``{kind: split tables}`` (``ops/rope.py split_rope_tables``: a few KB
    each, where a table a position would be 13 MB of constants in every
    program): the window layers' plain rotation over a whole head, the full
    layers' YaRN over their rotated columns, its factor in the tables."""
    inv_full, scale = yarn_inverse_frequencies(cfg.rotary_dim_full,
                                               **dict(cfg.rope_full))
    inv_window = 1.0 / cfg.rope_theta_window ** (
        np.arange(0, cfg.head_dim, 2, dtype=np.float64) / cfg.head_dim)
    return {"full": split_rope_tables(inv_full, max_seq, scale),
            "window": split_rope_tables(inv_window, max_seq)}


# -- the pieces both programs share ---------------------------------------------


def _project(cfg, kind, lp, h):
    """``(q [T, H, hd], k [T, kv, hd], v [T, kv, hd], gate [T, H] float32)``
    of normed inputs ``h [T, d]``.  The barrier keeps the wide projection ONE
    plain product in its own layout (``llama.decode_step_paged``'s)."""
    cdt = cfg.compute_dtype
    hs = cfg.heads(kind)
    t = h.shape[0]
    qkv = lax.optimization_barrier(h @ lp["w_qkv"].astype(cdt))
    nq = hs.n_heads * hs.head_dim
    q = qkv[:, :nq].reshape(t, hs.n_heads, hs.head_dim)
    k = qkv[:, nq:nq + cfg.kv_width].reshape(t, hs.n_kv_heads, hs.head_dim)
    v = qkv[:, nq + cfg.kv_width:].reshape(t, hs.n_kv_heads, hs.head_dim)
    gate = jax.nn.sigmoid((h @ lp["w_g"].astype(cdt)).astype(jnp.float32))
    return q, k, v, gate


def _rotate(cfg, kind, x, angles):
    """``rope_t`` of ``x [T, heads, hd]`` by ``angles``, the ``(cos, sin) [T,
    r / 2]`` of its positions: the first ``r`` columns of a head rotate."""
    cos, sin = angles[kind]
    r = 2 * cos.shape[-1]
    out = apply_rope(x[None, ..., :r], cos[None], sin[None])[0]
    return out if r == cfg.head_dim else jnp.concatenate(
        [out, x[..., r:]], axis=-1)


def _gated_out(cfg, lp, attn, gate):
    """``concat(gate_head * o_head) W_o``: ``attn [T, H * hd]`` float32."""
    cdt = cfg.compute_dtype
    t, h = gate.shape
    o = attn.reshape(t, h, cfg.head_dim) * gate[:, :, None]
    return o.reshape(t, -1).astype(cdt) @ lp["w_o"].astype(cdt)


def _window_visible(cfg, p0, c: int):
    """``[C, window + C]``: which keys a prompt chunk's window layer counts
    for each of its ``C`` queries at positions ``p0 ..``, the slot's ring's
    rows first and the chunk's own keys after them.  Ring row ``r`` holds the
    largest position below ``p0`` that is ``r mod window``; a key counts where
    ``0 <= p_q - p_k < window`` and ``p_k >= 0``."""
    w = cfg.window
    ring_pos = p0 - 1 - jnp.mod(p0 - 1 - jnp.arange(w), w)
    key_pos = jnp.concatenate([ring_pos, p0 + jnp.arange(c)])
    ago = (p0 + jnp.arange(c))[:, None] - key_pos[None, :]
    return (ago >= 0) & (ago < w) & (key_pos >= 0)[None, :]


def _window_attend(cfg, q, k_new, v_new, ring_k, ring_v, visible):
    """A prompt chunk's window attention: queries ``q [C, H, hd]`` over the
    slot's ring ``[window, kv * hd]`` as the chunks before left it and the
    chunk's own keys and values ``[C, kv, hd]``, under ``_window_visible``'s
    mask.  Returns ``[C, H * hd]`` float32."""
    hs = cfg.heads("window")
    kv_shape = (cfg.window, hs.n_kv_heads, hs.head_dim)
    keys = jnp.concatenate([ring_k.reshape(kv_shape), k_new.astype(
        ring_k.dtype)])
    values = jnp.concatenate([ring_v.reshape(kv_shape), v_new.astype(
        ring_v.dtype)])
    return _paged_attend(hs, q[None], keys[None], values[None],
                         visible[None])[0]


def _run_layers(cfg, params, carry, mixer, ffn):
    """Every layer over ``carry`` (a tuple whose first element is the hidden
    rows): the leading dense layers one by one, then an outer scan over the
    periods, inside it a loop over the period's window layers whose trip
    count is scanned-over DATA, then the period's full layer, then the window
    layers after the last full one (module docstring).  ``mixer(carry, kind,
    lp, idx)`` returns the carry after the attention (residual added), ``lp``
    the layer's own weights and its input norm's, ``idx`` its number among its
    kind; ``ffn(carry, li, dense)`` the carry after layer ``li``'s
    feed-forward."""

    def layer(carry, kind, idx, li, dense=False):
        lp = dict(_at_layer(params[kind], idx),
                  in_norm=lax.dynamic_index_in_dim(
                      params["norms"]["mixer"], li, 0, keepdims=False))
        return ffn(mixer(carry, kind, lp, idx), li, dense)

    at = {"window": 0, "full": 0}
    for li in range(cfg.first_k_dense):
        kind = cfg.layer_types[li]
        carry = layer(carry, kind, at[kind], li, True)
        at[kind] += 1

    def window_layers(carry, n, w0, l0):
        return lax.fori_loop(
            0, n, lambda j, c: layer(c, "window", w0 + j, l0 + j), carry)

    periods, tail = cfg.periods
    n_w = np.asarray(periods, np.int32)
    w_lo = at["window"] + np.concatenate([[0], np.cumsum(n_w)])
    l_lo = cfg.first_k_dense + np.concatenate([[0], np.cumsum(n_w + 1)])

    def period(carry, inp):
        n, w0, l0, fi = inp
        carry = window_layers(carry, n, w0, l0)
        return layer(carry, "full", fi, l0 + n), None

    if len(periods):
        carry, _ = lax.scan(period, carry, (
            jnp.asarray(n_w), jnp.asarray(w_lo[:-1], jnp.int32),
            jnp.asarray(l_lo[:-1], jnp.int32),
            at["full"] + jnp.arange(len(n_w), dtype=jnp.int32)))
    if tail:
        carry = window_layers(carry, tail, int(w_lo[-1]), int(l_lo[-1]))
    return carry


# -- a prompt chunk -----------------------------------------------------------------


def prefill_chunk_paged(cfg: LagunaConfig, params: Params,
                        tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                        table: jnp.ndarray, p0: jnp.ndarray, *,
                        rope_cache=None, tp_plan=None, use_kernel: bool = False,
                        kernel_interpret: bool = False, slot_state, slot,
                        take, kv_tile: int = PREFILL_KV_TILE):
    """One chunk of one sequence (``llama.prefill_chunk_paged``'s contract
    for the full layers: the chunk's keys and values written to the pool,
    attention over the whole prefix a tile at a time) plus the slot's ring
    (module docstring: ``slot`` the engine's slot, ``take`` the count of REAL
    tokens in ``tokens [1, C]``).  The chunk has no kernel of its own:
    ``use_kernel`` is not read; ``kernel_interpret`` is the grouped expert
    product's.  ``kv_tile`` is for tests.  Returns ``(logits [1, C, V]
    float32, pool, slot_state)``."""
    del tp_plan, use_kernel
    rope = rope_cache or make_rope_cache(cfg, cfg.max_seq_len)
    _, c = tokens.shape
    bs = pool["k"].shape[2]
    if kv_tile % bs:
        raise ValueError(f"kv_tile ({kv_tile}) must be a multiple of the "
                         f"block size ({bs})")
    cdt, w = cfg.compute_dtype, cfg.window
    local = jnp.arange(c)
    positions = p0 + local
    chunk_blocks = lax.dynamic_slice(table[0], (p0 // bs,), (c // bs,))
    row = jnp.pad(table[0], (0, -table.shape[1] % (kv_tile // bs)))
    # the ring takes the chunk's last min(take, window) REAL positions, each
    # at its own row; every other token's row is past the ring and dropped
    ring_rows = jnp.where((local < take) & (local >= take - w),
                          jnp.mod(positions, w), w)
    visible = _window_visible(cfg, p0, c)
    angles = {kind: rope_at(rope[kind], positions) for kind in rope}
    x = jnp.take(params["embed"], tokens[0], axis=0).astype(cdt)

    def mixer(carry, kind, lp, idx):
        x, pk, pv, wk, wv = carry
        with jax.named_scope(f"{kind}_attention"):
            h = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q, k, v, gate = _project(cfg, kind, lp, h)
            q = _rotate(cfg, kind, q, angles)
            k = _rotate(cfg, kind, k, angles)
            if kind == "full":
                pk = pk.at[idx, chunk_blocks].set(
                    k.reshape(c // bs, bs, -1).astype(pk.dtype))
                pv = pv.at[idx, chunk_blocks].set(
                    v.reshape(c // bs, bs, -1).astype(pv.dtype))
                attn = _prefill_attend_tiles(
                    cfg.heads(kind), q, pk, pv, idx, row, positions, kv_tile)
            else:
                # the barrier keeps the slot's rows a slice of the leaf as it
                # lies: without it the compiler lays the WHOLE leaf out keys
                # minor for the score product, 0.8 GB copied a layer-call
                ring_k, ring_v = lax.optimization_barrier(
                    (wk[idx, slot], wv[idx, slot]))
                attn = _window_attend(cfg, q, k, v, ring_k, ring_v, visible)
                wk = wk.at[idx, slot, ring_rows].set(
                    k.reshape(c, -1).astype(wk.dtype), mode="drop")
                wv = wv.at[idx, slot, ring_rows].set(
                    v.reshape(c, -1).astype(wv.dtype), mode="drop")
            x = x + _gated_out(cfg, lp, attn, gate).astype(x.dtype)
        return x, pk, pv, wk, wv

    def ffn(carry, li, dense):
        x, _ = _ffn(cfg, params, carry[0], li, dense,
                    interpret=kernel_interpret)
        return (x,) + tuple(carry[1:])

    x, pk, pv, wk, wv = _run_layers(
        cfg, params,
        (x, pool["k"], pool["v"], slot_state["wk"], slot_state["wv"]),
        mixer, ffn)
    return (pm._head(cfg, params, x)[None], {"k": pk, "v": pv},
            {"wk": wk, "wv": wv})


# -- a decode token-step --------------------------------------------------------------


def kernel_supported(cfg: LagunaConfig) -> bool:
    """Whether the paged decode kernel applies to both kinds of layer: a TPU
    backend, a lane-aligned head, ring pages of whole bfloat16 tiles."""
    if jax.default_backend() != "tpu":
        return False
    if cfg.head_dim % 128 or cfg.ring_page % 16:
        return False
    from ray_tpu.ops.paged_attention import (  # noqa: F401
        paged_decode_attention,
    )

    return True


def decode_step_paged(cfg: LagunaConfig, params: Params, tokens: jnp.ndarray,
                      pool: Dict[str, jnp.ndarray], table: jnp.ndarray,
                      lengths: jnp.ndarray, *, rope_cache=None,
                      use_kernel: bool = False, mesh=None,
                      kernel_interpret: bool = False, tp_plan=None,
                      active: Optional[jnp.ndarray] = None, slot_state):
    """One token for every slot (``llama.decode_step_paged``'s contract over
    the full layers' pool) plus the slots' rings: a row with ``active == 0``
    keeps its ring bit for bit, whatever its token is.  ``use_kernel``: the
    paged decode kernel for both kinds of layer, the ring seen as pages under
    a constant table (module docstring); else ``jax.numpy`` over every row.
    Returns ``(logits [B, V] float32, pool, slot_state, counters int32:
    DECODE_COUNTERS)``."""
    del mesh, tp_plan
    rope = rope_cache or make_rope_cache(cfg, cfg.max_seq_len)
    b = tokens.shape[0]
    bs = pool["k"].shape[2]
    cdt, w, page = cfg.compute_dtype, cfg.window, cfg.ring_page
    active = jnp.ones_like(lengths) if active is None else active
    live = active != 0
    bidx = jnp.arange(b)
    cur_blk = table[bidx, lengths // bs]
    cur_off = lengths % bs
    # a decoding row's ring row; past the ring, and dropped, for the others
    ring_row = jnp.where(live, jnp.mod(lengths, w), w)
    # the last position a window layer reads, as the kernel counts: a row
    # reads min(length + 1, window) ring rows
    ring_len = jnp.minimum(lengths, w - 1)
    ring_table = (bidx[:, None] * (w // page)
                  + jnp.arange(w // page)[None, :]).astype(jnp.int32)
    if not use_kernel:
        span_mask = (jnp.arange(table.shape[1] * bs)[None, None, :]
                     <= lengths[:, None, None])
        ring_mask = jnp.arange(w)[None, None, :] <= ring_len[:, None, None]
    angles = {kind: rope_at(rope[kind], lengths) for kind in rope}
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)

    def attend(kind, q, ck, cv, idx, tbl, lens, mask):
        """``q [B, H, hd]`` over layer ``idx`` of a pool ``[.., pages, page
        size, kv * hd]``."""
        hs = cfg.heads(kind)
        if use_kernel:
            from ray_tpu.ops.paged_attention import paged_decode_attention

            return paged_decode_attention(
                q, ck, cv, idx, tbl, lens, active, interpret=kernel_interpret,
                name=(WINDOW_KERNEL_NAME if kind == "window"
                      else FULL_KERNEL_NAME))
        span = (b, -1, hs.n_kv_heads, hs.head_dim)
        return _paged_attend(hs, q[:, None], ck[idx, tbl].reshape(span),
                             cv[idx, tbl].reshape(span), mask)[:, 0]

    def mixer(carry, kind, lp, idx):
        x, pk, pv, wk, wv, booked = carry
        with jax.named_scope(f"{kind}_attention"):
            h = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q, k, v, gate = _project(cfg, kind, lp, h)
            q = _rotate(cfg, kind, q, angles)
            k = _rotate(cfg, kind, k, angles)
            if kind == "full":
                pk = pk.at[idx, cur_blk, cur_off].set(
                    k.reshape(b, -1).astype(pk.dtype))
                pv = pv.at[idx, cur_blk, cur_off].set(
                    v.reshape(b, -1).astype(pv.dtype))
                attn = attend(kind, q, pk, pv, idx, table, lengths,
                              None if use_kernel else span_mask)
            else:
                wk = wk.at[idx, bidx, ring_row].set(
                    k.reshape(b, -1).astype(wk.dtype), mode="drop")
                wv = wv.at[idx, bidx, ring_row].set(
                    v.reshape(b, -1).astype(wv.dtype), mode="drop")
                paged = (wk.shape[0], b * (w // page), page, cfg.kv_width)
                attn = attend(kind, q, wk.reshape(paged), wv.reshape(paged),
                              idx, ring_table, ring_len,
                              None if use_kernel else ring_mask)
            x = x + _gated_out(cfg, lp, attn, gate).astype(x.dtype)
        return x, pk, pv, wk, wv, booked

    def ffn(carry, li, dense):
        x, got = _ffn(cfg, params, carry[0], li, dense, live=active,
                      interpret=kernel_interpret)
        booked = carry[-1] if got is None else carry[-1].at[:len(got)].add(got)
        return (x,) + tuple(carry[1:-1]) + (booked,)

    booked = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32).at[-2:].set(
        jnp.stack([jnp.sum(jnp.where(live, ring_len + 1, 0)),
                   jnp.sum(jnp.where(live, lengths + 1, 0))]).astype(
                       jnp.int32))
    x, pk, pv, wk, wv, booked = _run_layers(
        cfg, params,
        (x, pool["k"], pool["v"], slot_state["wk"], slot_state["wv"], booked),
        mixer, ffn)
    return (pm._head(cfg, params, x), {"k": pk, "v": pv},
            {"wk": wk, "wv": wv}, booked)


# -- the family seam (models/family.py) -------------------------------------------------


def ring_in_order(cfg: LagunaConfig, ring, positions: int):
    """A slot's ring leaf ``[window layers, window, width]`` with its rows in
    the order of their positions: the last ``min(positions, window)`` of a
    sequence of ``positions``, oldest first, the rows not yet written (a
    sequence shorter than the window) zeros at the end."""
    w = cfg.window
    n = min(positions, w)
    rows = np.arange(positions - n, positions) % w
    out = jnp.zeros(ring.shape, jnp.float32)
    return out.at[:, :n].set(jnp.asarray(ring, jnp.float32)[:, rows])


def _reference_logits(cfg, params, tokens, first_row: int = 0):
    from ray_tpu.models.laguna_reference import reference_logits

    return reference_logits(cfg, params, tokens, first_row=first_row)


def _reference_slot_state(cfg, params, tokens, slot_state):
    from ray_tpu.models.laguna_reference import reference_window

    want = reference_window(cfg, params, tokens)
    return {name: (ring_in_order(cfg, slot_state[name], len(tokens)),
                   want[name]) for name in ("wk", "wv")}


def _family():
    from ray_tpu.models.family import ModelFamily

    return ModelFamily(
        name="laguna", config_type=LagunaConfig,
        init_params=init_params, init_paged_cache=init_paged_cache,
        rope_cache=make_rope_cache, prefill_chunk=prefill_chunk_paged,
        decode_step=decode_step_paged, kernel_supported=kernel_supported,
        prefill_visited_pages=_prefill_visited_pages,
        reference_logits=_reference_logits,
        prefill_grouped_from=pm.grouped_ffn_from,
        decode_counters=DECODE_COUNTERS,
        init_slot_state=init_slot_state,
        reference_slot_state=_reference_slot_state)


FAMILY = _family()
