"""The benchmark's own copy of the plain float32 reference of the Laguna-style
forward, as one chip's share of an expert-parallel deployment.

Copied from ``ray_tpu/models/laguna_reference.py`` (PR 51) so that later PRs
to the program cannot change the yardstick; ``tests/test_chipbench_laguna.py``
holds the two equal on the same weights.  ``cfg`` is a configuration file's
dict (the published keys as run, ``rope_parameters`` nested as published,
``layer_types`` and ``num_attention_heads_per_layer`` as long as
``num_hidden_layers``, ``experts_held``, ``router_outputs``); ``params`` is
the program's pytree (``window``, ``full``, ``dense`` and ``moe`` stacked by
kind, ``norms`` by layer; ``w_qkv`` is ``[W_q | W_k | W_v]`` side by side).

The equations (``N``: RMSNorm, eps ``rms_norm_eps``, a weight of its own each
use): ``x <- x + Attn_t(N(x))``, ``x <- x + FFN(N(x))``, logits ``= N(x)
W_head``.  ``Attn_t``, ``t`` the layer's ``layer_types`` entry, ``H_t`` its
``num_attention_heads_per_layer`` entry over ``num_key_value_heads`` heads of
``head_dim``: ``[q | k | v] = h W_qkv``; ``q, k <- rope_t``; scores ``q k^T /
sqrt(head_dim)`` under a plain ``[S, S]`` mask, causal and on a
``sliding_attention`` layer ``p_q - p_k < sliding_window``; softmax;
``o_head <- sigmoid(h W_g)_head o_head``; ``concat(o) W_o``.
``rope_sliding``: plain, its ``rope_theta``, every column.  ``rope_full``:
YaRN as ``transformers`` 4.57.6 ``_compute_yarn_parameters`` writes it, over a
head's first ``head_dim x partial_rotary_factor`` columns, split-half, ``cos``
and ``sin`` times ``attention_factor``.  ``FFN``: a gated SiLU of
``intermediate_size`` in ``mlp_only_layers``; else ``p = softmax(h W_r)`` over
all ``router_outputs``, the ``num_experts_per_tok`` largest, gates
``moe_routed_scaling_factor p_e / sum of the chosen``, the shared expert plus
the terms whose expert is held here.  No ring, no pool, no kernel, no
batching, no bf16; highest-precision matmuls; weights upcast a slice at a
time; attention one key/value head and one block of query rows at a time; a
layer's pieces one compiled function each (a forward run operation by
operation compiles a hundred small programs for every new length).

``lowp_weights``: the reading the comparison's limits are set against.  A
function applied to every layer's matrices (the attention projections, the
head gate, the feed-forwards and the experts; not the embedding, the head, the
norms or the router) and to nothing else: ``to_float8`` stands for weights
kept in 8 bits, and must come out as not correct.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference_pangu_moe import (
    _COLS,
    _QUERY_BLOCK,
    _rms,
    to_float8,  # noqa: F401 - the control's rounding, for its callers
)
from chipbench.reference_pangu_moe import layer_weights as _pangu_weights

_F32 = jnp.float32
_HIGHEST = functools.partial(jax.default_matmul_precision, "highest")
_MATRICES = ("w_qkv", "w_g", "w_o", "w_gate", "w_up", "w_down", "ws_gate",
             "ws_up", "ws_down", "we_gate", "we_up", "we_down")
_KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_weights(stack, j, lowp=None):
    """``reference_pangu_moe.layer_weights`` (``w(name, *index)``:
    ``stack[name][j, *index]`` in float32, taken from the stacked leaf in one
    step), a MATRIX's slice going through ``lowp``."""
    take = _pangu_weights(stack, j)
    if lowp is None:
        return take
    return lambda name, *index: (lowp(take(name, *index))
                                 if name in _MATRICES else take(name, *index))


def _angles(cfg, kind: str, s: int):
    """``(cos, sin) [S, r / 2]`` float32 of positions ``0 .. S - 1``, ``r``
    the columns of a head that ``kind``'s layers rotate; from float64."""
    pos = np.arange(s, dtype=np.float64)[:, None]
    hd = cfg["head_dim"]
    if kind == "window":
        y = cfg["rope_parameters"]["sliding_attention"]
        inv = 1.0 / y["rope_theta"] ** (np.arange(0, hd, 2) / hd)
        return jnp.asarray(np.cos(pos * inv), _F32), jnp.asarray(
            np.sin(pos * inv), _F32)
    y = cfg["rope_parameters"]["full_attention"]
    r, base = int(hd * y["partial_rotary_factor"]), y["rope_theta"]

    def correction_dim(rotations):
        return (r * math.log(y["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))) / (
                                 2 * math.log(base))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (np.arange(0, r, 2) / r)
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    extrapolation_factor = 1.0 - ramp
    inv = (1.0 / (y["factor"] * pos_freqs) * (1.0 - extrapolation_factor)
           + 1.0 / pos_freqs * extrapolation_factor)
    scale = y.get("attention_factor")
    if scale is None:
        scale = (0.1 * math.log(y["factor"]) + 1.0 if y["factor"] > 1
                 else 1.0)
    return (jnp.asarray(np.cos(pos * inv) * scale, _F32),
            jnp.asarray(np.sin(pos * inv) * scale, _F32))


def _rope(x, cos, sin):
    """``x [S, heads, hd]``: its first ``2 x cos.shape[1]`` columns rotated
    split-half, the others as they are."""
    half = cos.shape[1]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:]], -1)


@functools.partial(jax.jit, static_argnames=("window",))
def _kv_head(h, wq, wk, wv, wo, gates, j, cos, sin, window):
    """Key/value head ``j`` and its ``G`` query heads over normed inputs ``h
    [S, d]``: ``(their part of the layer's output [S, d], the head's rotated
    keys [S, hd], its values [S, hd])``.  Attention a block of
    ``_QUERY_BLOCK`` query rows at a time under those rows of the layer's
    ``[S, S]`` mask (``window`` None: causal alone); ``gates [S, H]``, the
    layer's, of which heads ``[j G, (j + 1) G)`` are these."""
    with _HIGHEST():
        s, hd = h.shape[0], wk.shape[1]
        g = wq.shape[1] // hd
        gate = lax.dynamic_slice_in_dim(gates, j * g, g, 1)
        q = _rope((h @ wq).reshape(s, g, hd), cos, sin)
        k = _rope((h @ wk)[:, None, :], cos, sin)[:, 0]
        v = h @ wv
        blocks = jnp.pad(q, ((0, -s % _QUERY_BLOCK), (0, 0), (0, 0))).reshape(
            -1, _QUERY_BLOCK, g, hd)

        def attend(inp):
            qb, q0 = inp
            sc = jnp.einsum("qgd,kd->gqk", qb, k) / math.sqrt(hd)
            ago = (q0 + jnp.arange(_QUERY_BLOCK))[:, None] - jnp.arange(
                s)[None, :]
            mask = ago >= 0 if window is None else (ago >= 0) & (ago < window)
            p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", p, v)

        o = lax.map(attend, (blocks, jnp.arange(len(blocks)) * _QUERY_BLOCK))
        o = o.reshape(-1, g, hd)[:s] * gate[:, :, None]
        return o.reshape(s, g * hd) @ wo, k, v


@jax.jit
def _head_gates(h, w_g):
    with _HIGHEST():
        return jax.nn.sigmoid(h @ w_g)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, w, eps):
    return _rms(x, w, eps)


@functools.partial(jax.jit, static_argnames=("width",))
def _head_cols(x, head, start, width):
    """Logits of the vocabulary rows ``[start, start + width)``."""
    with _HIGHEST():
        return x @ lax.dynamic_slice_in_dim(head, start, width, 1).astype(
            _F32)


def _attention(cfg, kind: str, nh: int, h, w, angles):
    """``Attn_t`` of normed inputs ``h [S, d]`` with ``nh`` query heads: its
    output ``[S, d]`` and the layer's rotated keys and its values ``[S, kv *
    hd]``."""
    hd, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    group, nq = nh // kv, nh * hd
    gates = _head_gates(h, w("w_g"))                             # [S, H]
    out, keys, values = 0.0, [], []
    for j in range(kv):
        mine = slice(j * group * hd, (j + 1) * group * hd)
        o, k, v = _kv_head(
            h, w("w_qkv", slice(None), mine),
            w("w_qkv", slice(None), slice(nq + j * hd, nq + (j + 1) * hd)),
            w("w_qkv", slice(None),
              slice(nq + (kv + j) * hd, nq + (kv + j + 1) * hd)),
            w("w_o", mine), gates, j, *angles[kind],
            window=cfg["sliding_window"] if kind == "window" else None)
        out = out + o
        out.block_until_ready()  # no running ahead of the device
        keys.append(k)
        values.append(v)
    return out, jnp.concatenate(keys, -1), jnp.concatenate(values, -1)


@jax.jit
def _swiglu_add(acc, g, h, w_gate, w_up, w_down):
    """``acc + g * W_down(silu(h W_gate) * (h W_up))``: a block of a gated
    feed-forward's hidden units, its rows scaled by ``g [S]``."""
    with _HIGHEST():
        return acc + g[:, None] * (
            (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)


def _swiglu(acc, g, h, w, gate, up, down, width, first=0):
    """``_swiglu_add`` over the hidden units ``[first, first + width)`` of the
    weights ``w(gate)``, ``w(up)``, ``w(down)``, ``_COLS`` columns at a
    time."""
    for c in range(first, first + width, _COLS):
        cols = slice(c, min(c + _COLS, first + width))
        acc = _swiglu_add(acc, g, h, w(gate, slice(None), cols),
                          w(up, slice(None), cols), w(down, cols))
    return acc.block_until_ready()


@functools.partial(jax.jit, static_argnames=("k", "held"))
def _held_gates(h, router, scale, k: int, held):
    """``[S, held experts]``: each held expert's gate for each token, zero
    where the token did not choose it."""
    with _HIGHEST():
        p = jax.nn.softmax(h @ router, axis=-1)
    top, idx = lax.top_k(p, k)
    gates = scale * top / top.sum(-1, keepdims=True)
    experts = jnp.arange(*held)
    return jnp.where(idx[:, :, None] == experts[None, None, :],
                     gates[:, :, None], 0.0).sum(1)


def _moe(cfg, h, w):
    """The expert layer's feed-forward of normed inputs ``h [S, d]``: the
    shared expert plus the terms of the routed sum whose expert is held."""
    held = tuple(cfg["experts_held"])
    gates = _held_gates(h, w("router"), cfg["moe_routed_scaling_factor"],
                        cfg["num_experts_per_tok"], held)
    f = cfg["moe_intermediate_size"]
    one = jnp.ones((h.shape[0],), _F32)
    y = _swiglu(jnp.zeros_like(h), one, h, w, "ws_gate", "ws_up", "ws_down",
                cfg["shared_expert_intermediate_size"])
    for j in range(held[1] - held[0]):
        y = _swiglu(y, lax.dynamic_index_in_dim(gates, j, 1, keepdims=False),
                    h, w, "we_gate", "we_up", "we_down", f, first=j * f)
    return y


def _layers(cfg, params, tokens, lowp):
    """The hidden rows ``[S, d]`` after the last layer, and every window
    layer's rotated keys and its values ``[window layers, S, kv * hd]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    norm = functools.partial(_norm, eps=cfg["rms_norm_eps"])
    dense = len(cfg["mlp_only_layers"])
    angles = {kind: _angles(cfg, kind, len(tokens))
              for kind in ("window", "full")}
    at = {"window": 0, "full": 0}
    keys, values = [], []
    x = params["embed"][tokens].astype(_F32)
    one = jnp.ones((len(tokens),), _F32)
    for li in range(cfg["num_hidden_layers"]):
        kind = _KINDS[cfg["layer_types"][li]]
        w = layer_weights(params[kind], at[kind], lowp)
        at[kind] += 1
        norms = layer_weights(params["norms"], li)
        a, k, v = _attention(
            cfg, kind, cfg["num_attention_heads_per_layer"][li],
            norm(x, norms("mixer")), w, angles)
        if kind == "window":
            keys.append(k)
            values.append(v)
        x = x + a
        u = norm(x, norms("ffn"))
        if li < dense:
            x = _swiglu(x, one, u, layer_weights(params["dense"], li, lowp),
                        "w_gate", "w_up", "w_down", cfg["intermediate_size"])
        else:
            x = x + _moe(cfg, u, layer_weights(params["moe"], li - dense,
                                               lowp))
    return x, jnp.stack(keys), jnp.stack(values)


def reference_logits(cfg, params, tokens: Sequence[int], first_row: int = 0,
                     lowp_weights=None) -> jnp.ndarray:
    """Causal logits ``[S - first_row, V]`` float32 for one sequence."""
    x, _, _ = _layers(cfg, params, tokens, lowp_weights)
    x = _norm(x[first_row:], params["final_norm"], cfg["rms_norm_eps"])
    head = params["lm_head"]
    # the head in float32 would be the largest thing held: a block of columns
    # at a time
    return jnp.concatenate(
        [_head_cols(x, head, i, min(_COLS, head.shape[1] - i))
         for i in range(0, head.shape[1], _COLS)], axis=-1)


def reference_window(cfg, params, tokens: Sequence[int],
                     lowp_weights=None) -> dict:
    """``{"wk", "wv"}``, each ``[window layers, window, kv * hd]`` float32:
    every window layer's rotated keys and its values at the last
    ``min(S, window)`` of ``tokens``' positions, oldest first, zeros after
    them: what a slot's ring holds once the engine has taken that many
    positions in."""
    _, keys, values = _layers(cfg, params, tokens, lowp_weights)
    window = cfg["sliding_window"]
    n = min(len(tokens), window)
    pad = ((0, 0), (0, window - n), (0, 0))
    return {"wk": jnp.pad(keys[:, -n:], pad), "wv": jnp.pad(values[:, -n:],
                                                            pad)}
