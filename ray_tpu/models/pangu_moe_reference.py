"""Plain float32 reference of the openPangu-Ultra-MoE-style forward
(``models/pangu_moe.py`` has the equations).

Independent of the code it checks: no scan, no kernel, no cache, no
absorption, no bf16: one Python loop over layers in ``jax.numpy`` at float32
with highest-precision matmuls (a TPU's default float32 matmul rounds its
operands to bf16).  Keys and values are expanded per head from the latent
(``k_nope_i = c_kv W_uk_i``, ``v_i = c_kv W_uv_i``) and attention is the
textbook causal softmax.  To run beside a serving engine's weights and pool
at 8.5k positions, weights are upcast one matrix (one expert, one group of
heads, one block of columns) at a time and attention runs a group of heads
and a block of queries at a time.

Departures from the published model, the same as the program's and stated in
the benchmark's configuration file: sandwich-norm placement (the config
carries only the flag), sigmoid scoring without expert groups, split-half
rotation (a fixed permutation of the checkpoint's rotary columns), W_ukv kept
as its two halves W_uk and W_uv a head.  ``experts_held``: the router scores
all ``n_routed_experts`` and picks its k; only the held experts' terms of the
sum are added (what absent experts would add is left out); ``vocab_slice``:
the embedding and the head have the slice's rows.  The multi-token-prediction
module is not part of the forward pass.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

_F32 = jnp.float32
_HEAD_GROUP = 8      # heads attended at a time
_QUERY_BLOCK = 256   # queries attended at a time
_COLS = 2304         # columns of a wide matrix upcast at a time


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(_F32)


def _rope(x, theta):
    """Split-half rotary embedding of x [S, ..., D] at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes", "squeeze"))
def _take(leaf, starts, sizes, squeeze):
    """``leaf[starts : starts + sizes]`` in float32, the axes in ``squeeze``
    dropped.  The starts are operands, so one program serves every layer,
    expert and block of columns of a leaf (a slice by constants is a
    program of its own each time)."""
    return lax.dynamic_slice(leaf, starts, sizes).astype(_F32).squeeze(squeeze)


def layer_weights(stack, j):
    """``w(name, *index)``: ``stack[name][j, *index]`` in float32 (``index``:
    integers and slices), taken from the stacked leaf in one step (a layer's
    experts are never copied whole)."""
    def w(name, *index):
        leaf = stack[name]
        index = (j,) + index + (slice(None),) * (leaf.ndim - 1 - len(index))
        starts, sizes, squeeze = [], [], []
        for axis, (i, n) in enumerate(zip(index, leaf.shape)):
            if isinstance(i, slice):
                lo, hi, _ = i.indices(n)
                starts.append(lo)
                sizes.append(hi - lo)
            else:
                starts.append(i)
                sizes.append(1)
                squeeze.append(axis)
        return _take(leaf, jnp.asarray(starts, jnp.int32), tuple(sizes),
                     tuple(squeeze))

    return w


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_block(q_nope, q_rope, k_nope, k_rope, v, q0, scale):
    """Causal softmax attention of one block of queries (at positions ``q0``
    on) over every key, the later ones masked: one shape, so one program,
    for all blocks; compiled, so the scores are never held twice."""
    with jax.default_matmul_precision("highest"):
        sc = (jnp.einsum("qhn,khn->hqk", q_nope, k_nope)
              + jnp.einsum("qhr,kr->hqk", q_rope, k_rope))
        mask = (jnp.arange(k_nope.shape[0])[None, :]
                <= q0 + jnp.arange(q_nope.shape[0])[:, None])
        p = jax.nn.softmax(jnp.where(mask[None], sc * scale, -jnp.inf), -1)
        return jnp.einsum("hqk,khv->qhv", p, v)


def _swiglu(h, w, gate, up, down, width, first=0):
    """SwiGLU of h [S, d] over the hidden units ``[first, first + width)`` of
    the weights ``w(gate)``, ``w(up)``, ``w(down)``, a block of columns at
    a time."""
    out = 0.0
    for c in range(first, first + width, _COLS):
        cols = slice(c, min(c + _COLS, first + width))
        a = (jax.nn.silu(h @ w(gate, slice(None), cols))
             * (h @ w(up, slice(None), cols)))
        out = out + a @ w(down, cols)
    return out.block_until_ready()


def _attention(cfg, h, w):
    """Expanded causal MLA of normed inputs h [S, d]: [S, d]."""
    s = h.shape[0]
    nope, rope, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    c_q = _rms(h @ w("w_dq"), w("q_norm"), cfg.rms_norm_eps)
    kv = h @ w("w_dkv")
    c_kv = _rms(kv[:, :r], w("kv_norm"), cfg.rms_norm_eps)
    k_rope = _rope(kv[:, r:], cfg.rope_theta)                    # [S, rope]
    scale = 1.0 / float(nope + rope) ** 0.5
    qd = cfg.qk_head_dim
    out = 0.0
    for g in range(0, cfg.n_heads, _HEAD_GROUP):
        heads = slice(g, min(g + _HEAD_GROUP, cfg.n_heads))
        n = heads.stop - g
        q = (c_q @ w("w_uq", slice(None),
                     slice(g * qd, heads.stop * qd))).reshape(s, n, qd)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg.rope_theta)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, w("w_uk", heads))
        v = jnp.einsum("sc,hcv->shv", c_kv, w("w_uv", heads))
        rows = [_attend_block(q_nope[q0:q0 + _QUERY_BLOCK],
                              q_rope[q0:q0 + _QUERY_BLOCK], k_nope, k_rope, v,
                              q0, scale)
                for q0 in range(0, s, _QUERY_BLOCK)]
        o = jnp.concatenate(rows, 0).reshape(s, n * cfg.v_head_dim)
        out = out + o @ w("w_o", slice(g * cfg.v_head_dim,
                                       heads.stop * cfg.v_head_dim))
        # eager dispatch runs ahead of the device and allocates every
        # result as it goes: without a wait a group, the groups' scores
        # pile up to 3 GB beside a serving engine's 13.6
        out.block_until_ready()
    return out


def moe_layer(cfg, h, w, shared: bool = True):
    """The expert layer's feed-forward of normed inputs h [S, d] float32:
    the shared expert (``shared``) plus the terms of the routed sum whose
    expert lies in the held range ``experts_held``; ``w`` (``layer_weights``) holds
    those experts' weights side by side, in order (expert j of the range:
    hidden units ``[j f, (j + 1) f)``)."""
    scores = jax.nn.sigmoid(h @ w("router"))
    top, idx = jax.lax.top_k(scores, cfg.n_experts_per_tok)
    gates = cfg.routed_scaling_factor * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    f = cfg.moe_ffn_dim
    y = (_swiglu(h, w, "ws_gate", "ws_up", "ws_down",
                 cfg.n_shared_experts * f) if shared else 0.0)
    for j, e in enumerate(range(*cfg.experts_held)):
        g = jnp.where(idx == e, gates, 0.0).sum(-1)              # [S]
        y = y + g[:, None] * _swiglu(h, w, "we_gate", "we_up", "we_down", f,
                                     first=j * f)
        y.block_until_ready()  # as in _attention: no running ahead
    return y


def reference_logits(cfg, params, tokens: Sequence[int],
                     first_row: int = 0) -> jnp.ndarray:
    """Causal logits float32 ``[S - first_row, V]`` (the rows from
    ``first_row`` on) for one sequence of token ids."""
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(_F32)
        for li in range(cfg.n_layers):
            is_moe = li >= cfg.first_k_dense
            w = layer_weights(params["moe" if is_moe else "dense"],
                              li - cfg.first_k_dense if is_moe else li)
            a = _attention(cfg, _rms(x, w("attn_norm"), eps), w)
            x = x + _rms(a, w("post_attn_norm"), eps)
            h = _rms(x, w("mlp_norm"), eps)
            y = (moe_layer(cfg, h, w) if is_moe else
                 _swiglu(h, w, "w_gate", "w_up", "w_down", cfg.ffn_dim))
            x = x + _rms(y, w("post_mlp_norm"), eps)
        x = _rms(x[first_row:], params["final_norm"], eps)
        head = params["lm_head"]
        return jnp.concatenate(
            [x @ head[:, i:i + _COLS].astype(_F32)
             for i in range(0, head.shape[1], _COLS)], axis=-1)
