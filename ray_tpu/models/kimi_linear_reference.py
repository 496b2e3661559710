"""Plain float32 reference of the Kimi-Linear-style forward
(``models/kimi_linear.py`` has the equations).

Independent of the code it checks: ``jax.numpy`` at float32 with
highest-precision matmuls, one Python loop over ``layer_types``, no cache, no
kernel, no chunked form, no absorption.  The delta rule is run as it is
DEFINED, position by position (``lax.scan`` over positions, the state
``[heads, d_k, d_v]`` its carry): the program's chunked (WY) form and its
one-step kernel are both held against the definition, not against each other.
Latent attention is expanded a head (``k_nope_i = c W_uk_i``, ``v_i = c
W_uv_i``) and is the textbook causal softmax; the held experts are a loop.
Weights are upcast a matrix (an expert, a group of heads, a block of columns)
at a time, so the reference runs beside a serving engine's weights, pool and
slot state (``pangu_moe_reference``'s ``layer_weights``, ``_swiglu``,
``moe_layer`` and ``_attend_block``, which this file shares: they are
references too).

Departures from the published model, the same as the program's and stated in
the benchmark's configuration file: pre-norm placement, the two low-rank
gates' width, the decay's initialisation, no convolution bias, a sigmoid
output gate, ``beta`` in (0, 1), the L2 norm after ``silu`` with 1e-6 under
its root, no expert groups, the 64 un-rotated columns.  ``experts_held``: the
router scores all ``n_routed_experts`` and picks its k; only the held
experts' terms are added.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ray_tpu.models.pangu_moe_reference import (
    _COLS,
    _HEAD_GROUP,
    _QUERY_BLOCK,
    _attend_block,
    _rms,
    _swiglu,
    layer_weights,
    moe_layer,
)

_F32 = jnp.float32
_L2_EPS = 1e-6


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _kda(cfg, u, w):
    """The KDA mixer over normed inputs ``u [S, d]``, the recurrence position
    by position; returns its output and the state after the last position
    ``[H, d_k, d_v]``."""
    s = u.shape[0]
    h, dk, k = cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_conv
    i, r = cfg.kda_inner, cfg.kda_gate_rank
    # causal depthwise convolution, zeros before position 0, no bias
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, 3 * i), _F32), u @ w("w_qkv")], 0)
    cw = w("conv_w")
    conv = sum(cw[j][None, :] * padded[j:j + s] for j in range(k))
    q, kk, v = (x.reshape(s, h, dk) for x in jnp.split(
        jax.nn.silu(conv), 3, axis=1))
    q, kk = _l2(q) * dk ** -0.5, _l2(kk)
    lr = u @ w("w_lr")
    g = -jnp.exp(w("a_log"))[None, :, None] * jax.nn.softplus(
        lr[:, :r] @ w("w_fb") + w("dt_bias")[None, :]).reshape(s, h, dk)
    beta = jax.nn.sigmoid(u @ w("w_b"))                       # [S, H]

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    last, o = jax.lax.scan(step, jnp.zeros((h, dk, dk), _F32),
                           (q, kk, v, g, beta))
    gate = jax.nn.sigmoid(lr[:, r:] @ w("w_gb")).reshape(s, h, dk)
    y = _rms(o, w("o_norm"), cfg.rms_norm_eps) * gate
    return y.reshape(s, i) @ w("w_o"), last


def _mla(cfg, h, w):
    """Expanded causal latent attention of normed inputs ``h [S, d]``, no
    rotation: ``[S, d]``."""
    s = h.shape[0]
    nope, pe, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    kv = h @ w("w_dkv")
    c = _rms(kv[:, :r], w("kv_norm"), cfg.rms_norm_eps)
    k_pe = kv[:, r:]
    scale = 1.0 / float(nope + pe) ** 0.5
    qd = cfg.qk_head_dim
    out = 0.0
    for g in range(0, cfg.n_heads, _HEAD_GROUP):
        heads = slice(g, min(g + _HEAD_GROUP, cfg.n_heads))
        n = heads.stop - g
        q = (h @ w("w_q", slice(None),
                   slice(g * qd, heads.stop * qd))).reshape(s, n, qd)
        k_nope = jnp.einsum("sc,hnc->shn", c, w("w_uk", heads))
        v = jnp.einsum("sc,hcv->shv", c, w("w_uv", heads))
        rows = [_attend_block(q[q0:q0 + _QUERY_BLOCK, :, :nope],
                              q[q0:q0 + _QUERY_BLOCK, :, nope:], k_nope, k_pe,
                              v, q0, scale)
                for q0 in range(0, s, _QUERY_BLOCK)]
        o = jnp.concatenate(rows, 0).reshape(s, n * cfg.v_head_dim)
        out = out + o @ w("w_o", slice(g * cfg.v_head_dim,
                                       heads.stop * cfg.v_head_dim))
        out.block_until_ready()  # no running ahead of the device
    return out


def _layers(cfg, params, tokens):
    """The hidden rows ``[S, d]`` after the last layer, and every KDA
    layer's state after the last position ``[KDA layers, H, d_k, d_v]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = cfg.rms_norm_eps
    at = {"kda": 0, "mla": 0}
    states = []
    x = params["embed"][tokens].astype(_F32)
    for li, kind in enumerate(cfg.layer_types):
        w = layer_weights(params[kind], at[kind])
        at[kind] += 1
        norms = layer_weights(params["norms"], li)
        u = _rms(x, norms("mixer"), eps)
        if kind == "kda":
            mix, last = _kda(cfg, u, w)
            states.append(last)
        else:
            mix = _mla(cfg, u, w)
        x = x + mix
        u = _rms(x, norms("ffn"), eps)
        if li < cfg.first_k_dense:
            x = x + _swiglu(u, layer_weights(params["dense"], li), "w_gate",
                            "w_up", "w_down", cfg.ffn_dim)
        else:
            x = x + moe_layer(cfg, u, layer_weights(
                params["moe"], li - cfg.first_k_dense))
    return x, jnp.stack(states)


def reference_logits(cfg, params, tokens: Sequence[int],
                     first_row: int = 0) -> jnp.ndarray:
    """Causal logits ``[S - first_row, V]`` float32 for one sequence."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(cfg, params, tokens)
        x = _rms(x[first_row:], params["final_norm"], cfg.rms_norm_eps)
        head = params["lm_head"]
        # the head in float32 would be the largest thing held: a block of
        # columns at a time
        return jnp.concatenate(
            [x @ head[:, i:i + _COLS].astype(_F32)
             for i in range(0, head.shape[1], _COLS)], axis=-1)


def reference_state(cfg, params, tokens: Sequence[int]) -> jnp.ndarray:
    """Every KDA layer's state after the last of ``tokens``, ``[KDA layers,
    heads, d_k, d_v]`` float32: what a slot holds once the engine has taken
    that many positions in."""
    with jax.default_matmul_precision("highest"):
        return _layers(cfg, params, tokens)[1]
