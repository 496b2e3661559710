"""The state-space decode step: one layer's recurrent state, updated in place
for the rows that decode and for no others.

A Mamba-2 layer keeps, a sequence, a state ``S`` of ``[heads, head width,
state width]`` float32 (granite-4.0-h-micro: 64 x 64 x 128, 2 MB a layer, 36
layers).  A decode token-step is ``S <- decay * S + (dt * x) (outer) B`` and
``y = S C`` a head, so every live row reads and writes its whole state once a
layer and computes almost nothing: the step is HBM traffic.  Written as
``jax.numpy`` over the engine's leaf (``[layers, max_batch, ...]``) it moves
every slot's state whoever decodes (:func:`ssm_state_update_jnp`, the form the
CPU and the tests use).  This kernel takes the leaf WHOLE, aliased to its
output, and a list of the live rows: one loop over that list, a row an
iteration, the row's state copied HBM -> VMEM under the row's own turn
(double-buffered: row ``k + 1`` lands and row ``k - 1`` leaves while row ``k``
is computed), nothing for a row that does not decode.  Its time follows the
decoding rows, as ``ops/paged_attention.py``'s follows the live pages; a row
with ``active == 0`` moves no byte and keeps its state bit for bit.

**The state's layout at rest** is chosen for the kernel: ``[layers, rows,
tiles, state width, 128]`` where a tile's 128 lanes are 128 consecutive
``(head, p)`` pairs of the flattened ``heads x head width`` axis (two heads of
64 a tile) and the state width runs down the sublanes.  In that layout the
update needs no relayout: ``decay`` and ``dt * x`` are rows of 128 lanes
(broadcast down the sublanes), ``B`` and ``C`` (one group: shared by every
head) are columns (one ``[128, 128]`` transpose a row, then broadcast along
the lanes), and ``y`` is a sum down the sublanes, vector adds and no lane
reduction.  With the state as ``[head, p, n]`` the same sum is 4,096 lane
reductions a row, several times what the row's 5 us of HBM traffic allows.
:func:`pack_state` / :func:`unpack_state` convert.

The convolution's window (the last 3 inputs, bf16, 26 KB a row a layer) is
not this kernel's: the model keeps it for rows that do not decode with a
``where`` over the layer's slice, 1.7 MB a layer-call at 64 slots beside the
82 MB the kernel moves for 20 live rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def state_shape(layers: int, rows: int, heads: int, head_dim: int,
                d_state: int) -> tuple:
    """The leaf's shape for ``layers`` layers and ``rows`` slots."""
    if (heads * head_dim) % LANES:
        raise ValueError(f"heads x head width ({heads} x {head_dim}) must be "
                         f"a multiple of {LANES}")
    return (layers, rows, heads * head_dim // LANES, d_state, LANES)


def pack_state(s: jnp.ndarray) -> jnp.ndarray:
    """``[..., heads, head width, state width]`` -> ``[..., tiles, state
    width, 128]``."""
    *lead, h, p, n = s.shape
    s = jnp.swapaxes(s.reshape(*lead, h * p, n), -1, -2)  # [..., n, h*p]
    s = s.reshape(*lead, n, h * p // LANES, LANES)
    return jnp.swapaxes(s, -2, -3)


def unpack_state(s: jnp.ndarray, heads: int) -> jnp.ndarray:
    """:func:`pack_state`'s inverse."""
    *lead, t, n, _ = s.shape
    s = jnp.swapaxes(s, -2, -3).reshape(*lead, n, t * LANES)
    return jnp.swapaxes(s, -1, -2).reshape(*lead, heads, t * LANES // heads, n)


def live_rows(active: jnp.ndarray):
    """``(rows, n_live)``: the rows with ``active != 0`` first, in order."""
    dead = (active == 0).astype(jnp.int32)
    rows = jnp.argsort(dead, stable=True).astype(jnp.int32)
    return rows, (active.shape[0] - dead.sum()).astype(jnp.int32)


def ssm_state_update_jnp(state, layer, decay, xdt, b, c, active):
    """The same step in ``jax.numpy`` over the layer's whole slice.

    state ``[L, R, T, N, 128]``; decay, xdt ``[R, T * 128]`` float32 (a lane
    each: ``exp(dt * A)`` and ``dt * x``); b, c ``[R, N]``; active ``[R]``.
    Returns ``(y [R, T * 128] float32, state)``; a row with ``active == 0``
    keeps its state and gets ``y == 0``."""
    _, r, t, n, _ = state.shape
    old = state[layer]
    f32 = jnp.float32
    new = (old.astype(f32) * decay.astype(f32).reshape(r, t, 1, LANES)
           + b.astype(f32)[:, None, :, None]
           * xdt.astype(f32).reshape(r, t, 1, LANES))
    new = new.astype(state.dtype)
    y = (new.astype(f32) * c.astype(f32)[:, None, :, None]).sum(2)
    live = (active != 0)
    state = state.at[layer].set(
        jnp.where(live[:, None, None, None], new, old))
    return jnp.where(live[:, None], y.reshape(r, t * LANES), 0.0), state


def _kernel(rows_ref, n_ref, layer_ref, decay_ref, xdt_ref, b_ref, c_ref,
            s_in, y_ref, s_out, ibuf, obuf, isem, osem, *, unroll):
    """One grid step: a loop over the live rows.  ibuf / obuf ``[2, T, N,
    128]``: a row's state as it came and as it leaves; isem / osem ``[2]``."""
    li = layer_ref[0]
    n = n_ref[0]
    tiles, nstate = ibuf.shape[1], ibuf.shape[2]

    def fetch(k, slot):
        return pltpu.make_async_copy(
            s_in.at[li, rows_ref[k]], ibuf.at[slot], isem.at[slot])

    def store(k, slot):
        return pltpu.make_async_copy(
            obuf.at[slot], s_out.at[li, rows_ref[k]], osem.at[slot])

    @pl.when(n > 0)
    def _():
        fetch(0, 0).start()

    def row(k, carry):
        slot = k & 1
        r = rows_ref[k]

        @pl.when(k + 1 < n)
        def _():
            fetch(k + 1, 1 - slot).start()

        fetch(k, slot).wait()

        @pl.when(k >= 2)  # the row before last has left this buffer
        def _():
            store(k - 2, slot).wait()

        # B and C: rows of the operands, needed as columns broadcast along
        # the lanes: broadcast down the sublanes, then one transpose each
        bmat = jnp.broadcast_to(b_ref[pl.ds(r, 1), :], (LANES, nstate)).T
        cmat = jnp.broadcast_to(c_ref[pl.ds(r, 1), :], (LANES, nstate)).T

        def tile(t):
            s = ibuf[slot, t].astype(jnp.float32)            # [N, 128]
            new = (s * decay_ref[r, pl.ds(t, 1), :]
                   + bmat * xdt_ref[r, pl.ds(t, 1), :]).astype(obuf.dtype)
            obuf[slot, t] = new
            y_ref[r, pl.ds(t, 1), :] = jnp.sum(
                new.astype(jnp.float32) * cmat, axis=0, keepdims=True)

        def some_tiles(g, carry):  # `unroll` tiles an iteration, by hand:
            for j in range(unroll):  # the loop's own unroll is all or none
                tile(g * unroll + j)
            return carry

        lax.fori_loop(0, tiles // unroll, some_tiles, 0)
        store(k, slot).start()
        return carry

    lax.fori_loop(0, n, row, 0)

    @pl.when(n >= 2)
    def _():
        store(n - 2, n & 1).wait()

    @pl.when(n >= 1)
    def _():
        store(n - 1, (n - 1) & 1).wait()


def ssm_state_update(state, layer, decay, xdt, b, c, active, live=None, *,
                     interpret=False):
    """:func:`ssm_state_update_jnp` as a Pallas kernel, the leaf updated in
    place (input and output aliased: the caller donates it).  ``live``:
    :func:`live_rows` of ``active``, where the caller has it already (once a
    token-step, not once a layer)."""
    _, r, t, n, lanes = state.shape
    rows, n_live = live_rows(active) if live is None else live
    f32 = jnp.float32

    def full(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[full((r, t, lanes)), full((r, t, lanes)), full((r, n)),
                  full((r, n)), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[full((r, t, lanes)), pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, t, n, lanes), state.dtype),
            pltpu.VMEM((2, t, n, lanes), state.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_kernel, unroll=4 if t % 4 == 0 else 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, t, lanes), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},  # the leaf, after the 3 prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssm_state_update",  # the kernel's name in a profiler trace
    )(rows, n_live.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      decay.astype(f32).reshape(r, t, lanes),
      xdt.astype(f32).reshape(r, t, lanes), b.astype(f32), c.astype(f32),
      state)
    # a row that did not decode was never written: whatever its lanes hold
    y = jnp.where((active != 0)[:, None], y.reshape(r, t * lanes), 0.0)
    return y, state
