"""The benchmark's own copy of the plain float32 reference of the
Kimi-Linear-style forward, as one chip's share of an expert-parallel
deployment.

Copied from ``ray_tpu/models/kimi_linear_reference.py`` (PR 47) so that later
PRs to the program cannot change the yardstick;
``tests/test_chipbench_kimi_linear.py`` holds the two equal on the same
weights.  ``cfg`` is a configuration file's dict (the published keys as run,
``linear_attn_config`` nested as published, ``experts_held``,
``router_outputs``); ``params`` is the program's pytree (``kda``, ``mla``,
``dense`` and ``moe`` stacked by kind, ``norms`` by layer; ``kda.w_qkv`` is
``[W_q | W_k | W_v]`` and ``kda.w_lr`` ``[W_fa | W_ga]`` side by side).

The equations (``N``: RMSNorm, eps ``rms_norm_eps``, a weight of its own each
use): ``x <- x + Mix_l(N(x))``, ``x <- x + FFN_l(N(x))``, logits ``= N(x)
W_head``.  KDA (layers ``kda_layers``, numbered from 1): ``[q | k | v] =
silu(conv4(h W_qkv))`` (depthwise, causal, no bias), ``q^ = q / |q| x
d^-0.5``, ``k^ = k / |k|`` a head (1e-6 under the root), ``g = -exp(A_log)
softplus((h W_fa) W_fb + dt_bias)`` a key channel, ``beta = sigmoid(h W_b)`` a
head; a head's state ``S [d_k, d_v]``, zeros at position 0: ``S <-
Diag(exp(g_t)) S``; ``S <- S + beta_t k^_t (v_t - S^T k^_t)^T``; ``o_t = S^T
q^_t``; ``y = N_head(o_t) sigmoid((h W_ga) W_gb)``; ``out = y W_o``.  MLA
(layers ``full_attn_layers``), no rotation: ``[q_nope | q_pe] = h W_q`` a
head, ``[c_kv | k_pe] = h W_dkv``, ``c = N(c_kv)``, ``k_nope_i = c W_uk_i``,
``v_i = c W_uv_i``, scores over ``sqrt(nope + pe)``, causal softmax.  The
first ``first_k_dense_replace`` layers' feed-forward is a gated SiLU of width
``intermediate_size``; the others' ``s = sigmoid(h W_r)`` over all
``router_outputs``, the ``num_experts_per_token`` largest, gates normalised
over the chosen times ``routed_scaling_factor``, the shared expert plus the
terms whose expert is held here.  The delta rule is run position by position
(``lax.scan``): no chunked form, no cache, no kernel, no absorption, no bf16;
highest-precision matmuls; weights upcast a slice at a time.

``lowp_weights``: the reading the comparison's limits are set against.  A
function applied to every layer's matrices (the projections of both mixers,
the gates' low-rank pairs, the feed-forwards and the experts; not the
embedding, the head, the norms, the router, the convolution or the decay's
scalars) and to nothing else: ``to_float8`` stands for weights kept in 8
bits, and must come out as not correct.  ``state_carry``: the second
control, a KDA state KEPT in that dtype (rounded to it after every position,
float32 inside a position as the kernel is): what a bf16 state at rest would
read (``benchmarks/kimi_lowp_reading.py --control bf16_state``).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from chipbench.reference_pangu_moe import (
    _COLS,
    _HEAD_GROUP,
    _QUERY_BLOCK,
    _attend_block,
    _rms,
    _swiglu,
    to_float8,  # noqa: F401 - the control's rounding, for its callers
)
from chipbench.reference_pangu_moe import layer_weights as _pangu_weights

_F32 = jnp.float32
_L2_EPS = 1e-6
_MATRICES = ("w_qkv", "w_lr", "w_fb", "w_gb", "w_b", "w_o", "w_q", "w_dkv",
             "w_uk", "w_uv", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
             "ws_down", "we_gate", "we_up", "we_down")


def layer_weights(stack, j, lowp=None):
    """``reference_pangu_moe.layer_weights`` (``w(name, *index)``:
    ``stack[name][j, *index]`` in float32, taken from the stacked leaf in one
    step), a MATRIX's slice going through ``lowp``."""
    take = _pangu_weights(stack, j)
    if lowp is None:
        return take
    return lambda name, *index: (lowp(take(name, *index))
                                 if name in _MATRICES else take(name, *index))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def _kda(cfg, u, w, carry=None):
    """The KDA mixer over normed inputs ``u [S, d]``; returns its output and
    the state after the last position ``[H, d_k, d_v]``.  ``carry``: a dtype
    the state is rounded to after every position (the second control)."""
    la = cfg["linear_attn_config"]
    s = u.shape[0]
    h, dk, k = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    i = h * dk
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, 3 * i), _F32), u @ w("w_qkv")], 0)
    cw = w("conv_w")
    conv = sum(cw[j][None, :] * padded[j:j + s] for j in range(k))
    q, kk, v = (x.reshape(s, h, dk) for x in jnp.split(
        jax.nn.silu(conv), 3, axis=1))
    q, kk = _l2(q) * dk ** -0.5, _l2(kk)
    lr = u @ w("w_lr")
    r = lr.shape[1] // 2
    g = -jnp.exp(w("a_log"))[None, :, None] * jax.nn.softplus(
        lr[:, :r] @ w("w_fb") + w("dt_bias")[None, :]).reshape(s, h, dk)
    beta = jax.nn.sigmoid(u @ w("w_b"))

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        if carry is not None:
            state = state.astype(carry).astype(_F32)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    last, o = jax.lax.scan(step, jnp.zeros((h, dk, dk), _F32),
                           (q, kk, v, g, beta))
    gate = jax.nn.sigmoid(lr[:, r:] @ w("w_gb")).reshape(s, h, dk)
    y = _rms(o, w("o_norm"), cfg["rms_norm_eps"]) * gate
    return y.reshape(s, i) @ w("w_o"), last


def _mla(cfg, h, w):
    s = h.shape[0]
    nope, pe = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, dv, nh = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg[
        "num_attention_heads"]
    kv = h @ w("w_dkv")
    c = _rms(kv[:, :r], w("kv_norm"), cfg["rms_norm_eps"])
    k_pe = kv[:, r:]
    scale = 1.0 / float(nope + pe) ** 0.5
    qd = nope + pe
    out = 0.0
    for g in range(0, nh, _HEAD_GROUP):
        heads = slice(g, min(g + _HEAD_GROUP, nh))
        n = heads.stop - g
        q = (h @ w("w_q", slice(None),
                   slice(g * qd, heads.stop * qd))).reshape(s, n, qd)
        k_nope = jnp.einsum("sc,hnc->shn", c, w("w_uk", heads))
        v = jnp.einsum("sc,hcv->shv", c, w("w_uv", heads))
        rows = [_attend_block(q[q0:q0 + _QUERY_BLOCK, :, :nope],
                              q[q0:q0 + _QUERY_BLOCK, :, nope:], k_nope, k_pe,
                              v, q0, scale)
                for q0 in range(0, s, _QUERY_BLOCK)]
        o = jnp.concatenate(rows, 0).reshape(s, n * dv)
        out = out + o @ w("w_o", slice(g * dv, heads.stop * dv))
        out.block_until_ready()
    return out


def _moe(cfg, h, w):
    scores = jax.nn.sigmoid(h @ w("router"))
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_token"])
    gates = cfg["routed_scaling_factor"] * top / (
        top.sum(-1, keepdims=True) + 1e-20)
    f = cfg["moe_intermediate_size"]
    y = _swiglu(h, w, "ws_gate", "ws_up", "ws_down",
                cfg["num_shared_experts"] * f)
    for j, e in enumerate(range(*cfg["experts_held"])):
        g = jnp.where(idx == e, gates, 0.0).sum(-1)
        y = y + g[:, None] * _swiglu(h, w, "we_gate", "we_up", "we_down", f,
                                     first=j * f)
        y.block_until_ready()
    return y


def _layers(cfg, params, tokens, lowp, carry=None):
    tokens = jnp.asarray(tokens, jnp.int32)
    eps = cfg["rms_norm_eps"]
    kda = set(cfg["linear_attn_config"]["kda_layers"])
    dense = cfg["first_k_dense_replace"]
    at = {"kda": 0, "mla": 0}
    states = []
    x = params["embed"][tokens].astype(_F32)
    for li in range(cfg["num_hidden_layers"]):
        kind = "kda" if li + 1 in kda else "mla"
        w = layer_weights(params[kind], at[kind], lowp)
        at[kind] += 1
        norms = layer_weights(params["norms"], li)
        u = _rms(x, norms("mixer"), eps)
        if kind == "kda":
            mix, last = _kda(cfg, u, w, carry)
            states.append(last)
        else:
            mix = _mla(cfg, u, w)
        x = x + mix
        u = _rms(x, norms("ffn"), eps)
        if li < dense:
            x = x + _swiglu(u, layer_weights(params["dense"], li, lowp),
                            "w_gate", "w_up", "w_down",
                            cfg["intermediate_size"])
        else:
            x = x + _moe(cfg, u, layer_weights(params["moe"], li - dense,
                                               lowp))
    return x, jnp.stack(states)


def reference_logits(cfg, params, tokens: Sequence[int], first_row: int = 0,
                     lowp_weights=None, state_carry=None) -> jnp.ndarray:
    """Causal logits float32 ``[S - first_row, V]`` for one sequence."""
    with jax.default_matmul_precision("highest"):
        x, _ = _layers(cfg, params, tokens, lowp_weights, state_carry)
        x = _rms(x[first_row:], params["final_norm"], cfg["rms_norm_eps"])
        head = params["lm_head"]
        return jnp.concatenate(
            [x @ head[:, i:i + _COLS].astype(_F32)
             for i in range(0, head.shape[1], _COLS)], axis=-1)


def reference_state(cfg, params, tokens: Sequence[int],
                    lowp_weights=None, state_carry=None) -> jnp.ndarray:
    """Every KDA layer's state after the last of ``tokens``, ``[KDA layers,
    heads, d_k, d_v]`` float32."""
    with jax.default_matmul_precision("highest"):
        return _layers(cfg, params, tokens, lowp_weights, state_carry)[1]
