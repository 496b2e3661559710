"""Plain float32 reference of the granite-hybrid forward.

Independent of the code it checks: ``jax.numpy`` at float32 with
highest-precision matmuls, one Python loop over ``layer_types``, no cache, no
kernel, no chunked form.  The state-space recurrence is run as it is DEFINED,
position by position (``lax.scan`` over positions, the state ``[heads, head
width, state width]`` its carry): the program's chunked form and its one-step
kernel are both held against the definition, not against each other.  Only
one layer's weights are held in float32 at a time, so the reference runs
beside a serving engine's weights, pool and slot state.

The equations are those of ``models/granite_hybrid.py``'s docstring.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_COLS = 16384


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(cfg, lp, u):
    """The mixer over ``u [S, d]``, the recurrence position by position;
    returns its output and the state after the last position."""
    s = u.shape[0]
    i, n = cfg.d_inner, cfg.mamba_d_state
    h, p, k = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_conv
    cw = i + 2 * n
    proj = u @ lp["w_in"]                   # W_in's columns [z | x | B | C]
    z, xbc, dt = proj[:, :i], proj[:, i:], u @ lp["w_dt"]  # ... and [dt]
    # causal depthwise convolution, zeros before position 0
    padded = jnp.concatenate([jnp.zeros((k - 1, cw), _F32), xbc], 0)
    conv = lp["conv_b"][None, :]
    for j in range(k):
        conv = conv + lp["conv_w"][j][None, :] * padded[j:j + s]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :i].reshape(s, h, p)
    bm, cm = xbc[:, i:i + n], xbc[:, i + n:]
    delta = jax.nn.softplus(dt + lp["dt_bias"][None, :])       # [S, H]
    a = -jnp.exp(lp["a_log"])                                  # [H]

    def step(state, inp):
        x_t, b_t, c_t, d_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, (state * c_t[None, None, :]).sum(-1)

    last, y = jax.lax.scan(step, jnp.zeros((h, p, n), _F32),
                           (x, bm, cm, delta))
    y = y + lp["d"][None, :, None] * x
    y = _rms(y.reshape(s, i) * jax.nn.silu(z), lp["norm"], cfg.rms_norm_eps)
    return y @ lp["w_out"], last


def _attention(cfg, lp, u):
    s = u.shape[0]
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (u @ lp["wq"]).reshape(s, nh, hd)
    k = jnp.repeat((u @ lp["wk"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((u @ lp["wv"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg.attention_multiplier
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * hd) @ lp["wo"]


def _layers(cfg, params, tokens):
    """The hidden rows ``[S, d]`` after the last layer, and every Mamba
    layer's state after the last position ``[mamba layers, H, P, N]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    at = {"mamba": 0, "attention": 0}
    states = []

    def layer_params(tree, i):
        return jax.tree.map(lambda a: a[i].astype(_F32), tree)

    x = params["embed"][tokens].astype(_F32) * cfg.embedding_multiplier
    for li, kind in enumerate(cfg.layer_types):
        lp = layer_params(
            params["mamba" if kind == "mamba" else "attn"], at[kind])
        at[kind] += 1
        norms = layer_params(params["norms"], li)
        u = _rms(x, norms["mixer"], cfg.rms_norm_eps)
        if kind == "mamba":
            mix, last = _mamba(cfg, lp, u)
            states.append(last)
        else:
            mix = _attention(cfg, lp, u)
        x = x + cfg.residual_multiplier * mix
        fp = layer_params(params["ffn"], li)
        u = _rms(x, norms["ffn"], cfg.rms_norm_eps)
        gv = u @ fp["w_in"]
        g, v = gv[:, :cfg.ffn_dim], gv[:, cfg.ffn_dim:]
        x = x + cfg.residual_multiplier * (
            (jax.nn.silu(g) * v) @ fp["w_out"])
    return x, jnp.stack(states)


def reference_logits(cfg, params, tokens: Sequence[int],
                     first_row: int = 0) -> jnp.ndarray:
    """Causal logits ``[S - first_row, V]`` float32 for one sequence."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(cfg, params, tokens)
        x = _rms(x[first_row:], params["final_norm"].astype(_F32),
                 cfg.rms_norm_eps)
        head = params["embed"].T
        # the head in float32 would be the largest thing held: a slice at a
        # time
        logits = jnp.concatenate(
            [x @ head[:, i:i + _HEAD_COLS].astype(_F32)
             for i in range(0, head.shape[1], _HEAD_COLS)], axis=-1)
        return logits / cfg.logits_scaling


def reference_state(cfg, params, tokens: Sequence[int]) -> jnp.ndarray:
    """Every Mamba layer's recurrent state after the last of ``tokens``,
    ``[mamba layers, heads, head width, state width]`` float32: what a slot
    holds once the engine has taken that many positions in."""
    with jax.default_matmul_precision("highest"):
        return _layers(cfg, params, tokens)[1]
