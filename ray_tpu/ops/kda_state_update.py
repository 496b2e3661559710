"""The delta-rule decode step of a Kimi-Delta-Attention (KDA) layer: one
layer's matrix state, updated in place for the rows that decode and for no
others.

A KDA layer keeps, a sequence and a head, a state ``S [d_k, d_v]`` float32
(Kimi-Linear: 32 heads of 128 x 128, 2 MB a layer, 20 layers).  A decode
token-step is, a head,

    S <- Diag(alpha) S;  u = beta (v - S^T k^);  S <- S + k^ u^T;  o = S^T q^

(``S_t = (I - beta k^ k^T) Diag(alpha) S_{t-1} + beta k^ v^T``) with ``alpha =
exp(g)`` a decay PER KEY CHANNEL, ``k^ = k / |k|`` and ``q^ = q / |q| *
d_k^-0.5``.  Every live row reads and writes its whole state once a layer and
computes a few operations a value: the step is HBM traffic, as the Mamba-2
step of ``ops/ssm_state_update.py`` is, and the kernel is built on that
file's loop over the live rows (``_each_live_row``: the leaf taken WHOLE and
aliased to its output, a row's state copied HBM -> VMEM under the row before
it, nothing for a row that does not decode).

**The state's layout at rest** is ``[layers, rows, heads, d_k, d_v]``: the key
channel down the sublanes, the value channel along the lanes.  The two sums
over the key channel (``S^T k^`` and ``S^T q^``) are then sums down the
sublanes (vector adds), ``v``, ``u`` and ``o`` are rows of 128 lanes, and
``alpha``, ``k^``, ``q^`` are wanted as columns: one ``[128, 128]`` transpose
each a head (``ssm_state_update._columns``).

**What the call does** for a live row, all in float32: the L2 norms of ``q``
and ``k`` (after the caller's convolution and ``silu``), ``alpha = exp(g)``,
the decay, the rank-one correction, the output.  The caller keeps the
convolution windows (shifted by a ``where`` over the layer's slice), the
gates' projections and the head norm.  :func:`kda_state_update_jnp` is the
same step in ``jax.numpy`` over every row (the CPU's form and the kernel's
test).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.ssm_state_update import (
    LANES,
    _columns,
    _each_live_row,
    _whole,
    live_rows,
)

# added to a squared norm before its root: a channel vector of zeros (a
# padded row) stays zeros instead of NaN
L2_EPS = 1e-6


def state_shape(layers: int, rows: int, heads: int, d_k: int,
                d_v: int) -> tuple:
    """The leaf's shape for ``layers`` KDA layers and ``rows`` slots."""
    return (layers, rows, heads, d_k, d_v)


def unsupported(heads: int, d_k: int, d_v: int):
    """Why :func:`kda_state_update` does not compute this shape, or None."""
    if d_k != LANES or d_v != LANES:
        return (f"a head's state of {d_k} x {d_v} (one {LANES} x {LANES} "
                "tile a head is computed)")
    if heads % 8:
        return f"{heads} heads (a row's vectors are whole 8-sublane tiles)"
    return None


def l2_normalize(x):
    """``x / |x|`` over the last axis in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_state_update_jnp(state, layer, q, k, v, g, beta, active):
    """The step in ``jax.numpy`` over the layer's whole slice.

    state ``[L, R, H, d_k, d_v]`` float32; q, k ``[R, H, d_k]`` (after the
    convolution and ``silu``, not normalised); v ``[R, H, d_v]``; g ``[R, H,
    d_k]`` float32 (the log decay, <= 0); beta ``[R, H]``; active ``[R]``.
    Returns ``(o [R, H, d_v] float32, state)``; a row with ``active == 0``
    keeps its state and gets ``o == 0``."""
    f32 = jnp.float32
    d_k = q.shape[-1]
    qn = l2_normalize(q) * d_k ** -0.5
    kn = l2_normalize(k)
    old = state[layer].astype(f32)
    sd = old * jnp.exp(g.astype(f32))[..., None]
    u = beta.astype(f32)[..., None] * (
        v.astype(f32) - jnp.sum(sd * kn[..., None], axis=-2))
    new = sd + kn[..., None] * u[..., None, :]
    o = jnp.sum(new * qn[..., None], axis=-2)
    live = active != 0
    state = state.at[layer].set(
        jnp.where(live[:, None, None, None], new.astype(state.dtype),
                  state[layer]))
    return jnp.where(live[:, None, None], o, 0.0), state


def _kernel(rows_ref, n_ref, layer_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
            s_in, o_ref, s_out, ibuf, obuf, qn, kn, al, vv, isem, osem, *,
            unroll):
    """One grid step: a loop over the live rows.  ibuf / obuf ``[2, H, d_k,
    d_v]``: a row's state as it came and as it leaves; qn, kn, al, vv ``[H,
    128]`` float32: the row's ``q^``, ``k^``, ``alpha`` and ``v``, a head a
    sublane row (one row of a 16-bit operand cannot be read by itself)."""
    f32 = jnp.float32
    li = layer_ref[0]
    heads, d_k = ibuf.shape[1], ibuf.shape[2]
    o_ref[...] = jnp.zeros_like(o_ref)   # a row that does not decode: zeros

    def fetch(i, slot):
        return [pltpu.make_async_copy(
            s_in.at[li, rows_ref[i]], ibuf.at[slot], isem.at[slot])]

    def store(i, slot):
        return [pltpu.make_async_copy(
            obuf.at[slot], s_out.at[li, rows_ref[i]], osem.at[slot])]

    def normalized(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + L2_EPS)

    def compute(r, slot):
        qn[...] = normalized(q_ref[r].astype(f32)) * d_k ** -0.5
        kn[...] = normalized(k_ref[r].astype(f32))
        al[...] = jnp.exp(g_ref[r].astype(f32))
        vv[...] = v_ref[r].astype(f32)

        def head(h):
            at = pl.ds(h, 1)
            kcol = _columns(kn[at, :])                    # [d_k, 128]
            sd = ibuf[slot, h].astype(f32) * _columns(al[at, :])
            u = b_ref[r, at, :] * (
                vv[at, :] - jnp.sum(sd * kcol, axis=0, keepdims=True))
            new = sd + kcol * u
            obuf[slot, h] = new.astype(obuf.dtype)
            o_ref[r, at, :] = jnp.sum(new * _columns(qn[at, :]), axis=0,
                                      keepdims=True)

        def some_heads(i, carry):  # `unroll` heads an iteration, by hand
            for j in range(unroll):
                head(i * unroll + j)
            return carry

        lax.fori_loop(0, heads // unroll, some_heads, 0)

    _each_live_row(rows_ref, n_ref[0], fetch, store, compute)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_state_update(state, layer, q, k, v, g, beta, active, live=None, *,
                     interpret=False):
    """:func:`kda_state_update_jnp` as ONE Pallas call, the leaf updated in
    place (input and output aliased: the caller donates it).  ``live``:
    ``ssm_state_update.live_rows`` of ``active``, where the caller has it
    already (once a token-step, not once a layer).  A row with ``active ==
    0`` moves no byte and keeps its state bit for bit.

    Jitted by itself, as ``ssm_layer_step`` is: a decode program calls it
    from two layer loops and an engine compiles that program at every table
    width."""
    _, r, h, d_k, d_v = state.shape
    why = unsupported(h, d_k, d_v)
    if why:
        raise NotImplementedError(f"kda_state_update: {why}")
    rows, n_live = live_rows(active) if live is None else live
    f32 = jnp.float32
    vec = (r, h, LANES)
    # a head's beta along its row's lanes: it scales a row of 128 values
    beta = jnp.broadcast_to(beta.astype(f32)[..., None], vec)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[_whole(vec)] * 5 + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_whole(vec), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, h, d_k, d_v), state.dtype),
            pltpu.VMEM((2, h, d_k, d_v), state.dtype),
            pltpu.VMEM((h, LANES), f32), pltpu.VMEM((h, LANES), f32),
            pltpu.VMEM((h, LANES), f32), pltpu.VMEM((h, LANES), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    o, state = pl.pallas_call(
        functools.partial(_kernel, unroll=4 if h % 4 == 0 else 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(vec, f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},  # the leaf, after 3 prefetched + 5
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="kda_state_update",  # the name the trace's readers find it by
    )(rows, n_live.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      q, k, v, g.astype(f32), beta, state)
    return o, state
