"""Rotary position embeddings (RoPE), Llama-3 style.

Frequencies are precomputed once (host-side, outside jit) and passed in as
an array so the jitted step has static shapes and no trig recomputation.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    max_seq_len: int,
    theta: float = 500000.0,
    scaling: dict | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Return (cos, sin), each [max_seq_len, head_dim // 2], float32.

    ``scaling`` optionally applies Llama-3.1-style NTK frequency scaling:
    {"factor": 8.0, "low_freq_factor": 1.0, "high_freq_factor": 4.0,
     "original_max_position": 8192}.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    if scaling:
        factor = scaling["factor"]
        low = scaling["low_freq_factor"]
        high = scaling["high_freq_factor"]
        orig = scaling["original_max_position"]
        wavelen = 2 * np.pi / inv_freq
        # three bands: leave high-freq alone, divide low-freq by factor,
        # smoothly interpolate between.
        smooth = (orig / wavelen - low) / (high - low)
        smooth = np.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = np.where(
            wavelen < orig / high,
            inv_freq,
            np.where(wavelen > orig / low, scaled, (1 - smooth) * scaled + smooth * inv_freq),
        )
    t = np.arange(max_seq_len, dtype=np.float64)
    freqs = np.outer(t, inv_freq)
    return np.cos(freqs).astype(np.float32), np.sin(freqs).astype(np.float32)


def yarn_inverse_frequencies(
    rotary_dim: int,
    theta: float,
    factor: float,
    original_max_position: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    attention_factor: float | None = None,
) -> tuple[np.ndarray, float]:
    """YaRN's ``(inverse frequencies [rotary_dim // 2] float64, the factor
    its cos and sin are multiplied by)`` (None: ``0.1 ln(factor) + 1``).

    A blend of two frequency tables by column: column ``i``'s wavelength
    turns ``original_max_position / wavelength`` times over the trained
    context; columns that turn ``beta_fast`` times or more keep ``theta^(-2i /
    d)`` (extrapolation), those that turn ``beta_slow`` times or fewer take it
    divided by ``factor`` (interpolation), and a linear ramp over the column
    index joins them, its ends rounded outwards to whole columns.
    """
    d = rotary_dim

    def column(turns):  # the (real) column whose wavelength turns that often
        return d * np.log(original_max_position / (turns * 2 * np.pi)) / (
            2 * np.log(theta))

    low = max(int(np.floor(column(beta_fast))), 0)
    high = min(int(np.ceil(column(beta_slow))), d - 1)
    extrapolated = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    if attention_factor is None:
        attention_factor = 0.1 * np.log(factor) + 1.0 if factor > 1 else 1.0
    return (extrapolated / factor * ramp + extrapolated * (1.0 - ramp),
            float(attention_factor))


# positions a low table of ``split_rope_tables`` holds
ROPE_SPLIT = 128


def split_rope_tables(inv_freq: np.ndarray, max_seq_len: int,
                      scale: float = 1.0) -> tuple[np.ndarray, ...]:
    """``(cos_hi, sin_hi, cos_lo, sin_lo)`` float32 for :func:`rope_at`: the
    angles of positions ``0, 128, 256, ..`` (``[ceil(max_seq_len / 128), d /
    2]``) and of ``0 .. 127`` (``[128, d / 2]``, times ``scale``), from
    float64.  A table a position (:func:`rope_frequencies`) is a constant of
    every program that closes over it: 9 MB at 17k positions of 128 columns,
    in each of an engine's programs and of their cache entries; these are 70
    KB and give the same values to a float32 rounding."""
    hi = np.outer(np.arange(0, max_seq_len, ROPE_SPLIT, dtype=np.float64),
                  inv_freq)
    lo = np.outer(np.arange(ROPE_SPLIT, dtype=np.float64), inv_freq)
    return tuple(t.astype(np.float32) for t in (
        np.cos(hi), np.sin(hi), np.cos(lo) * scale, np.sin(lo) * scale))


def rope_at(tables, positions: jnp.ndarray):
    """``(cos, sin) [..., d / 2]`` float32 at ``positions`` from
    :func:`split_rope_tables`: ``cos(a + b) = cos a cos b - sin a sin b`` with
    ``a`` the position's multiple of 128 and ``b`` the rest."""
    cos_hi, sin_hi, cos_lo, sin_lo = (jnp.asarray(t) for t in tables)
    a, b = positions // ROPE_SPLIT, positions % ROPE_SPLIT
    ch, sh, cl, sl = cos_hi[a], sin_hi[a], cos_lo[b], sin_lo[b]
    return ch * cl - sh * sl, sh * cl + ch * sl


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, positions: jnp.ndarray | None = None
) -> jnp.ndarray:
    """Rotate pairs (x[..., :d/2], x[..., d/2:]) by position-dependent angles.

    x: [batch, seq, heads, head_dim]. cos/sin: [max_seq, head_dim/2],
    gathered at ``positions`` [batch, seq] where given; or, with no
    ``positions``, already a position's own: [batch, seq, head_dim/2].
    Split-half convention (matches the neox/llama weight layout used by
    ray_tpu.models.llama).
    """
    if positions is not None:
        cos = jnp.take(cos, positions, axis=0)
        sin = jnp.take(sin, positions, axis=0)
    else:
        seq = x.shape[1]
        cos = cos[:seq]
        sin = sin[:seq]
    # broadcast to [*, seq, 1(heads), head_dim/2] against x [B, S, H, D/2]
    if cos.ndim == 2:  # [S, half]
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    elif cos.ndim == 3:  # [B, S, half] (positions gathered per batch)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    dtype = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = x1f * cos - x2f * sin
    out2 = x2f * cos + x1f * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(dtype)
