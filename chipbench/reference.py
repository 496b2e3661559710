"""The benchmark's own copy of the plain float32 reference of the
Llama-family forward (Mistral-7B-v0.3 is computed by the same equations: no
sliding window in v0.3), and the next-token loss on it.

Copied from ``ray_tpu/models/llama_reference.py`` (PR 21) so that later PRs to
the program cannot change the yardstick.  Independent of the code it checks:
no scan, no kernels, no cache, no bf16: one Python loop over layers in
``jax.numpy`` at float32 with highest-precision matmuls (a TPU's default
float32 matmul rounds its operands to bf16).  Only one layer's weights are
ever held in float32.  ``cfg`` is a configuration file's dict (published
keys); ``params`` is the program's stacked-layers pytree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_COLS = 16384


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Split-half rotary embedding of x [S, H, D] at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=_F32) / d))
    ang = jnp.arange(s, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(cfg: dict, params, tokens) -> jnp.ndarray:
    """Causal logits [S, V] float32 for one sequence of token ids."""
    tokens = jnp.asarray(tokens, jnp.int32)
    s = tokens.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    mask = jnp.tril(jnp.ones((s, s), bool))
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(_F32)
        for li in range(cfg["num_hidden_layers"]):
            lp = {k: v[li].astype(_F32) for k, v in params["layers"].items()}
            h = _rms(x, lp["attn_norm"], eps)
            q = _rope((h @ lp["wq"]).reshape(s, nh, hd), theta)
            k = _rope((h @ lp["wk"]).reshape(s, nkv, hd), theta)
            v = (h @ lp["wv"]).reshape(s, nkv, hd)
            k = jnp.repeat(k, nh // nkv, axis=1)
            v = jnp.repeat(v, nh // nkv, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / (hd ** 0.5)
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
            attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * hd)
            x = x + attn @ lp["wo"]
            h = _rms(x, lp["mlp_norm"], eps)
            x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
        x = _rms(x, params["final_norm"].astype(_F32), eps)
        head = (params["embed"].T if cfg["tie_word_embeddings"]
                else params["lm_head"])
        return jnp.concatenate(
            [x @ head[:, i:i + _HEAD_COLS].astype(_F32)
             for i in range(0, head.shape[1], _HEAD_COLS)], axis=-1)


def sequence_loss(cfg: dict, params, tokens) -> float:
    """Mean next-token cross-entropy of one sequence, float32."""
    tokens = jnp.asarray(tokens, jnp.int32)
    lg = logits(cfg, params, tokens)[:-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return float(jnp.mean(logz - tgt))
