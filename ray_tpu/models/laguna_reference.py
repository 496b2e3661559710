"""Plain float32 reference of the Laguna-style forward (``models/laguna.py``
has the equations).

Independent of the code it checks: ``jax.numpy`` at float32 with
highest-precision matmuls, one Python loop over ``layer_types``, a plain ``[S,
S]`` mask a layer (causal; on a window layer also ``p_q - p_k < window``), no
ring, no pool, no kernel, no batching, no layer scan; both rotary tables are
computed here from the config's numbers in float64 (YaRN as ``transformers``
4.57.6's ``_compute_yarn_parameters`` writes it), not taken from
``ops/rope.py``; the router is a softmax and the held experts a loop.  To run
beside a serving engine's weights, pool and rings at several thousand
positions, weights are upcast one slice at a time
(``pangu_moe_reference.layer_weights``) and attention runs one key/value head
and one block of query rows at a time: the same arithmetic as the whole mask
at once.  The pieces of a layer (a key/value head's attention, a block of a
feed-forward's columns, an expert's term, a norm) are each ONE compiled
function: run operation by operation, a forward compiles a hundred small
programs for every new sequence length, which on the chip is most of a probe's
minute.

Departures from the published model, the same as the program's and stated in
the benchmark's configuration file: pre-norm placement; the head gate a
sigmoid of the layer's normed input, applied before ``W_o``; softmax router
scores; the shared expert ungated; no q/k norm; the rotated columns of a full
layer's head its first ``rotary_dim_full``, split-half.  ``experts_held``: the
router scores all ``n_routed_experts`` and picks its k; only the held experts'
terms are added.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.pangu_moe_reference import (
    _COLS,
    _QUERY_BLOCK,
    _rms,
    layer_weights,
)

_F32 = jnp.float32
_HIGHEST = functools.partial(jax.default_matmul_precision, "highest")


def _angles(cfg, kind: str, s: int):
    """``(cos, sin) [S, r / 2]`` float32 of positions ``0 .. S - 1``, ``r``
    the columns of a head that ``kind``'s layers rotate; from float64."""
    pos = np.arange(s, dtype=np.float64)[:, None]
    if kind == "window":
        r = cfg.head_dim
        inv = 1.0 / cfg.rope_theta_window ** (np.arange(0, r, 2) / r)
        return jnp.asarray(np.cos(pos * inv), _F32), jnp.asarray(
            np.sin(pos * inv), _F32)
    y = dict(cfg.rope_full)
    r, base = cfg.rotary_dim_full, y["theta"]

    def correction_dim(rotations):
        return (r * math.log(y["original_max_position"]
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
    scale = y["attention_factor"]
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


def _attention(cfg, kind: str, h, w, angles):
    """``Attn_t`` of normed inputs ``h [S, d]``: its output ``[S, d]`` and
    the layer's rotated keys and its values ``[S, kv * hd]``."""
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    nh = cfg.n_heads_window if kind == "window" else cfg.n_heads_full
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
            window=cfg.window if kind == "window" else None)
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
    gates = _held_gates(h, w("router"), cfg.routed_scaling_factor,
                        cfg.n_experts_per_tok, tuple(cfg.experts_held))
    f = cfg.moe_ffn_dim
    one = jnp.ones((h.shape[0],), _F32)
    y = _swiglu(jnp.zeros_like(h), one, h, w, "ws_gate", "ws_up", "ws_down",
                cfg.n_shared_experts * f)
    for j in range(cfg.n_held):
        y = _swiglu(y, lax.dynamic_index_in_dim(gates, j, 1, keepdims=False),
                    h, w, "we_gate", "we_up", "we_down", f, first=j * f)
    return y


def _layers(cfg, params, tokens):
    """The hidden rows ``[S, d]`` after the last layer, and every window
    layer's rotated keys and its values ``[window layers, S, kv * hd]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    norm = functools.partial(_norm, eps=cfg.rms_norm_eps)
    angles = {kind: _angles(cfg, kind, len(tokens))
              for kind in ("window", "full")}
    at = {"window": 0, "full": 0}
    keys, values = [], []
    x = params["embed"][tokens].astype(_F32)
    one = jnp.ones((len(tokens),), _F32)
    for li, kind in enumerate(cfg.layer_types):
        w = layer_weights(params[kind], at[kind])
        at[kind] += 1
        norms = layer_weights(params["norms"], li)
        a, k, v = _attention(cfg, kind, norm(x, norms("mixer")), w, angles)
        if kind == "window":
            keys.append(k)
            values.append(v)
        x = x + a
        u = norm(x, norms("ffn"))
        if li < cfg.first_k_dense:
            x = _swiglu(x, one, u, layer_weights(params["dense"], li),
                        "w_gate", "w_up", "w_down", cfg.ffn_dim)
        else:
            x = x + _moe(cfg, u, layer_weights(params["moe"],
                                               li - cfg.first_k_dense))
    return x, jnp.stack(keys), jnp.stack(values)


def reference_logits(cfg, params, tokens: Sequence[int],
                     first_row: int = 0) -> jnp.ndarray:
    """Causal logits ``[S - first_row, V]`` float32 for one sequence."""
    x, _, _ = _layers(cfg, params, tokens)
    x = _norm(x[first_row:], params["final_norm"], cfg.rms_norm_eps)
    head = params["lm_head"]
    # the head in float32 would be the largest thing held: a block of columns
    # at a time
    return jnp.concatenate(
        [_head_cols(x, head, i, min(_COLS, head.shape[1] - i))
         for i in range(0, head.shape[1], _COLS)], axis=-1)


def reference_window(cfg, params, tokens: Sequence[int]) -> dict:
    """``{"wk", "wv"}``, each ``[window layers, window, kv * hd]`` float32:
    every window layer's rotated keys and its values at the last
    ``min(S, window)`` of ``tokens``' positions, oldest first, zeros after
    them: what a slot's ring holds (``laguna.ring_in_order``'s layout) once
    the engine has taken that many positions in."""
    _, keys, values = _layers(cfg, params, tokens)
    n = min(len(tokens), cfg.window)
    pad = ((0, 0), (0, cfg.window - n), (0, 0))
    return {"wk": jnp.pad(keys[:, -n:], pad), "wv": jnp.pad(values[:, -n:],
                                                            pad)}
