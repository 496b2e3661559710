"""The benchmark's own copy of the plain float32 reference of the
granite-hybrid forward (Mamba-2 layers and a few attention layers, a gated
feed-forward after each).

Copied from ``ray_tpu/models/granite_hybrid_reference.py`` (PR 36) so that
later PRs to the program cannot change the yardstick; ``chipbench/tests``
holds the two equal on the same weights.  ``cfg`` is a configuration file's
dict under the published key names; ``params`` is the program's pytree
(``mamba`` / ``attn`` stacked by kind, ``norms`` and ``ffn`` by layer;
``mamba.w_in`` and ``mamba.w_dt`` are the in-projection's column blocks ``[z
| x | B | C]`` and ``[dt]``).

The equations, with ``d`` hidden, ``I = mamba_expand x d = H x P``, state
width ``N``, one group, convolution width ``K``: ``h0 = E[ids] *
embedding_multiplier``; a layer: ``u = RMSNorm(h)``, ``h = h +
residual_multiplier * Mixer(u)``, ``u = RMSNorm(h)``, ``h = h +
residual_multiplier * W_o(silu(g) * v)``, ``[g, v] = u W_i``; ``logits =
RMSNorm(h) E^T / logits_scaling``.  Mamba mixer: ``[z, xBC, dt] = u W_in``;
``xBC <- silu(causal depthwise convolution + bias)``; ``[x, B, C] =
split(xBC)``; ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t =
exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t``; ``y_t = S_t C_t + D x_t``;
``y <- RMSNorm(y * silu(z))``; ``out = y W_out``.  Attention mixer: GQA, no
rotation, scores times ``attention_multiplier``, causal softmax.  The
recurrence is run position by position (``lax.scan``): no chunked form, no
cache, no kernel, no bf16; highest-precision matmuls; one layer's weights in
float32 at a time.

The reading the comparison's limits are set against: ``lowp_weights``, a
function applied to every layer's matrices (the projections of both mixers and
the feed-forward; not the embedding, the norms, the convolution or the
state-space scalars) and to nothing else.  ``to_float8`` stands for weights
kept in 8 bits, and must come out as not correct.  (A recurrent state kept in
bf16 at rest was the other reading tried, and no limit tells it from float32:
PERF.md section 6, PR 36.)
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_COLS = 16384


def to_float8(x):
    return x.astype(jnp.float8_e4m3fn).astype(_F32)


_MATRICES = ("w_in", "w_dt", "w_out", "wq", "wk", "wv", "wo")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(cfg, lp, u):
    s = u.shape[0]
    n, h, p = cfg["mamba_d_state"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    k = cfg["mamba_d_conv"]
    i = cfg["mamba_expand"] * cfg["hidden_size"]
    cw = i + 2 * n
    proj = u @ lp["w_in"]
    z, xbc, dt = proj[:, :i], proj[:, i:], u @ lp["w_dt"]
    padded = jnp.concatenate([jnp.zeros((k - 1, cw), _F32), xbc], 0)
    conv = lp["conv_b"][None, :]
    for j in range(k):
        conv = conv + lp["conv_w"][j][None, :] * padded[j:j + s]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :i].reshape(s, h, p)
    bm, cm = xbc[:, i:i + n], xbc[:, i + n:]
    delta = jax.nn.softplus(dt + lp["dt_bias"][None, :])
    a = -jnp.exp(lp["a_log"])

    def step(state, inp):
        x_t, b_t, c_t, d_t = inp
        state = (jnp.exp(d_t * a)[:, None, None] * state
                 + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return state, (state * c_t[None, None, :]).sum(-1)

    last, y = jax.lax.scan(step, jnp.zeros((h, p, n), _F32),
                           (x, bm, cm, delta))
    y = y + lp["d"][None, :, None] * x
    y = _rms(y.reshape(s, i) * jax.nn.silu(z), lp["norm"],
             cfg["rms_norm_eps"])
    return y @ lp["w_out"], last


def _attention(cfg, lp, u):
    s = u.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    q = (u @ lp["wq"]).reshape(s, nh, hd)
    k = jnp.repeat((u @ lp["wk"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    v = jnp.repeat((u @ lp["wv"]).reshape(s, nkv, hd), nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    mask = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * hd) @ lp["wo"]


def _layers(cfg: dict, params, tokens, lowp_weights):
    """The hidden rows after the last layer, and every Mamba layer's state
    after the last position ``[mamba layers, H, P, N]``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    f = cfg["shared_intermediate_size"]
    at = {"mamba": 0, "attention": 0}
    states = []

    def layer_params(tree, i):
        out = {k: a[i].astype(_F32) for k, a in tree.items()}
        if lowp_weights is not None:
            out.update({k: lowp_weights(a) for k, a in out.items()
                        if k in _MATRICES})
        return out

    x = params["embed"][tokens].astype(_F32) * cfg["embedding_multiplier"]
    for li, kind in enumerate(cfg["layer_types"]):
        lp = layer_params(
            params["mamba" if kind == "mamba" else "attn"], at[kind])
        at[kind] += 1
        norms = layer_params(params["norms"], li)
        u = _rms(x, norms["mixer"], eps)
        if kind == "mamba":
            mix, last = _mamba(cfg, lp, u)
            states.append(last)
        else:
            mix = _attention(cfg, lp, u)
        x = x + res * mix
        fp = layer_params(params["ffn"], li)
        gv = _rms(x, norms["ffn"], eps) @ fp["w_in"]
        x = x + res * ((jax.nn.silu(gv[:, :f]) * gv[:, f:]) @ fp["w_out"])
    return x, jnp.stack(states)


def reference_logits(cfg: dict, params, tokens: Sequence[int],
                     first_row: int = 0, lowp_weights=None) -> jnp.ndarray:
    """Causal logits ``[S - first_row, V]`` float32 for one sequence."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(cfg, params, tokens, lowp_weights)
        x = _rms(x[first_row:], params["final_norm"].astype(_F32),
                 cfg["rms_norm_eps"])
        head = params["embed"].T
        logits = jnp.concatenate(
            [x @ head[:, i:i + _HEAD_COLS].astype(_F32)
             for i in range(0, head.shape[1], _HEAD_COLS)], axis=-1)
        return logits / cfg["logits_scaling"]


def reference_state(cfg: dict, params, tokens: Sequence[int],
                    lowp_weights=None) -> jnp.ndarray:
    """Every Mamba layer's recurrent state after the last of ``tokens``,
    ``[mamba layers, heads, head width, state width]`` float32."""
    with jax.default_matmul_precision("highest"):
        return _layers(cfg, params, tokens, lowp_weights)[1]
