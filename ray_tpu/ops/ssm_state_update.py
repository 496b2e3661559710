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

**One call a layer.**  :func:`ssm_layer_step` is what the model's decode step
calls: the loop over the live rows, and inside a row's turn EVERYTHING a
Mamba layer does between its in-projection and its out-projection: the
four-tap convolution over the slot's window and ``silu``, ``delta`` and
``decay``, the state's update, ``D x``, the gate and its norm.  As
``jax.numpy`` that was some twenty operations a layer on arrays that fit in
fast memory, a launch each, over all 64 slots, and the layer's windows rebuilt
whoever decoded.  (A kernel of the state's update alone, the rest left to
``jax.numpy``, came first and went at PR 50: PERF.md section 6, PRs 36 and
44, has its measurements.)
The window (the last 3 inputs, bf16) is the second leaf the call takes whole
and aliases: **at rest ``[layers, rows, taps, tiles, 128]``**, a tap's
channels 128 a sublane row and the rows padded to whole 16-row memory tiles
(4,352 channels: 34 rows in 48), because a copy can move nothing smaller: in
``[layers, rows, taps x channels]`` two slots' values share every 32-bit
word and ONE slot's window cannot be read or written alone.
:func:`pack_window` / :func:`unpack_window` convert.
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


def _each_live_row(rows_ref, n, fetches, stores, compute):
    """The loop this kernel and ``ops/kda_state_update.py``'s run:
    ``compute(r, slot)`` for the ``n`` live rows ``r = rows_ref[k]``, row
    ``k + 1``'s copies in (``fetches(k, slot)``: a list of async copies into
    buffer ``slot``) started before row ``k`` is computed and row ``k``'s
    copies out (``stores(k, slot)``) waited for only when row ``k + 2`` needs
    the buffer."""

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    @pl.when(n > 0)
    def _():
        start(fetches(0, 0))

    def row(k, carry):
        slot = k & 1

        @pl.when(k + 1 < n)
        def _():
            start(fetches(k + 1, 1 - slot))

        wait(fetches(k, slot))

        @pl.when(k >= 2)  # the row before last has left this buffer
        def _():
            wait(stores(k - 2, slot))

        compute(rows_ref[k], slot)
        start(stores(k, slot))
        return carry

    lax.fori_loop(0, n, row, 0)

    @pl.when(n >= 2)
    def _():
        wait(stores(n - 2, n & 1))

    @pl.when(n >= 1)
    def _():
        wait(stores(n - 1, (n - 1) & 1))


def _columns(row):
    """A row ``[1, W]`` as columns ``[W, 128]``: its values down the
    sublanes, each broadcast along the lanes (broadcast down the sublanes,
    then one transpose)."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T


def _update_tiles(ibuf, obuf, slot, decay_at, xdt_at, bmat, cmat, y_at,
                  unroll):
    """``S <- decay * S + B (outer) xdt`` and ``y = S C`` over the tiles of
    the row in buffer ``slot``: ``decay_at(t)`` / ``xdt_at(t)`` give tile
    ``t``'s ``[1, 128]`` lanes, ``y_at(t, value)`` takes its ``y``."""
    tiles = ibuf.shape[1]

    def tile(t):
        s = ibuf[slot, t].astype(jnp.float32)            # [N, 128]
        new = (s * decay_at(t) + bmat * xdt_at(t)).astype(obuf.dtype)
        obuf[slot, t] = new
        y_at(t, jnp.sum(new.astype(jnp.float32) * cmat, axis=0,
                        keepdims=True))

    def some_tiles(g, carry):  # `unroll` tiles an iteration, by hand:
        for j in range(unroll):  # the loop's own unroll is all or none
            tile(g * unroll + j)
        return carry

    lax.fori_loop(0, tiles // unroll, some_tiles, 0)


def _whole(shape):
    """The BlockSpec of an operand taken whole into fast memory."""
    return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))


# -- the whole layer-step between the two projections ------------------------------


def _window_width(width: int) -> int:
    """``width`` channels as rows of 128 lanes, 16 rows a memory tile of a
    16-bit window (a row's copy has to be whole tiles)."""
    return -(-width // (16 * LANES)) * 16 * LANES


def window_shape(layers: int, rows: int, d_conv: int, width: int) -> tuple:
    """The window leaf's shape: a slot's last ``d_conv - 1`` inputs, each tap
    its ``width`` channels 128 a sublane row (zeros past ``width``)."""
    return (layers, rows, d_conv - 1, _window_width(width) // LANES, LANES)


def pack_window(w: jnp.ndarray) -> jnp.ndarray:
    """``[..., taps, width]`` -> ``[..., taps, tiles, 128]``."""
    w = jnp.pad(w, [(0, 0)] * (w.ndim - 1)
                + [(0, _window_width(w.shape[-1]) - w.shape[-1])])
    return w.reshape(*w.shape[:-1], -1, LANES)


def unpack_window(w: jnp.ndarray, width: int) -> jnp.ndarray:
    """:func:`pack_window`'s inverse."""
    return w.reshape(*w.shape[:-2], -1)[..., :width]


def layer_step_unsupported(heads: int, head_dim: int, d_state: int,
                           n_groups: int = 1):
    """Why :func:`ssm_layer_step` does not compute this shape, or None."""
    if n_groups != 1:
        return f"{n_groups} B/C groups (one is computed)"
    if (heads * head_dim) % LANES or LANES % head_dim:
        return (f"heads of {head_dim} ({heads} of them) are not whole "
                f"shares of {LANES}-lane tiles")
    if 2 * heads > LANES:
        return f"{heads} heads (a row's delta and decay share {LANES} lanes)"
    if d_state % LANES and 2 * d_state > LANES:
        return f"a state width of {d_state}"
    return None


def prepare_layer_params(conv_w, conv_b, dt_bias, a_log, d, norm,
                         head_dim: int) -> dict:
    """The layers' small parameters as :func:`ssm_layer_step` reads them,
    float32 and stacked over the layers (once a token-step, outside the layer
    loop): ``conv`` ``[L, K + 1, tiles, 128]`` (the taps, then the bias),
    ``heads`` ``[L, 2, H]`` (``dt_bias``, ``A = -exp(A_log)``), ``lanes``
    ``[L, 2, T, 128]`` (``D`` a lane of its head, the gated norm's weight)."""
    f32 = jnp.float32
    conv = pack_window(jnp.concatenate(
        [conv_w.astype(f32), conv_b.astype(f32)[:, None]], axis=1))
    heads = jnp.stack([dt_bias.astype(f32), -jnp.exp(a_log.astype(f32))], 1)
    lanes = jnp.stack([jnp.repeat(d.astype(f32), head_dim, axis=-1),
                       norm.astype(f32)], 1)
    return {"conv": conv, "heads": heads,
            "lanes": lanes.reshape(*lanes.shape[:-1], -1, LANES)}


def _layer_kernel(rows_ref, n_ref, layer_ref, proj_ref, dt_ref, conv_ref,
                  heads_ref, lanes_ref, s_in, w_in, y_ref, s_out, w_out,
                  ibuf, obuf, wibuf, wobuf, xin_ref, dd, cols, dec, xdt, ytm,
                  isem, osem, wisem, wosem, *, unroll, eps):
    """One grid step.  First, for every row at once, ``delta`` and ``decay``
    a head (``dd [R, 128]``: delta in lanes ``[0, H)``, decay in ``[H,
    2H)``); then the loop over the live rows: the row's state and window
    come in under the row before it, and per row the convolution, ``silu``,
    the state's update and the gated norm, everything ``[tiles, 128]`` (128
    channels a sublane row).  ``xin_ref [CT, 128]``: the row's new input to
    the window; ``cols [128, 128]``: its delta and decay down the sublanes;
    ``dec``, ``xdt``, ``ytm`` ``[T, 128]``: decay, ``delta * x`` and ``y`` a
    tile."""
    f32 = jnp.float32
    li = layer_ref[0]
    tiles, d_state = ibuf.shape[1], ibuf.shape[2]
    taps = wibuf.shape[1]                                   # K - 1
    heads = dt_ref.shape[1]
    per_tile = heads // tiles                               # heads a tile
    cdt = y_ref.dtype

    delta = jax.nn.softplus(dt_ref[...].astype(f32) + heads_ref[0, 0:1, :])
    both = [delta, jnp.exp(delta * heads_ref[0, 1:2, :])]
    if 2 * heads < LANES:
        both.append(jnp.zeros((delta.shape[0], LANES - 2 * heads), f32))
    dd[...] = jnp.concatenate(both, axis=1)
    y_ref[...] = jnp.zeros_like(y_ref)   # a row that does not decode: zeros
    # channels past the real ones (the window's rows are whole memory tiles,
    # the projection's are not): zeros, once
    xin_ref[...] = jnp.zeros_like(xin_ref)
    lane_head = lax.broadcasted_iota(
        jnp.int32, (tiles, LANES), 1) // (LANES // per_tile)

    def of_tiles(first):
        """``cols``' rows ``first + h`` as ``[T, 128]``: tile ``t``'s lane
        ``l`` takes head ``per_tile * t + l // head width``."""
        out = cols[pl.ds(first, tiles, stride=per_tile), :]
        for j in range(1, per_tile):
            out = jnp.where(
                lane_head == j,
                cols[pl.ds(first + j, tiles, stride=per_tile), :], out)
        return out

    def fetch(k, slot):
        at = (li, rows_ref[k])
        return [pltpu.make_async_copy(s_in.at[at], ibuf.at[slot],
                                      isem.at[slot]),
                pltpu.make_async_copy(w_in.at[at], wibuf.at[slot],
                                      wisem.at[slot])]

    def store(k, slot):
        at = (li, rows_ref[k])
        return [pltpu.make_async_copy(obuf.at[slot], s_out.at[at],
                                      osem.at[slot]),
                pltpu.make_async_copy(wobuf.at[slot], w_out.at[at],
                                      wosem.at[slot])]

    def compute(r, slot):
        pr = proj_ref[r].astype(f32)                        # [T + real, 128]
        z = pr[:tiles]
        xin_ref[:pr.shape[0] - tiles] = pr[tiles:]
        xin = xin_ref[...]                                  # [CT, 128]
        acc = conv_ref[0, taps + 1]                         # the bias
        for j in range(taps):
            acc = acc + conv_ref[0, j] * wibuf[slot, j].astype(f32)
            wobuf[slot, j] = (wibuf[slot, j + 1] if j + 1 < taps
                              else xin.astype(wobuf.dtype))
        acc = acc + conv_ref[0, taps] * xin
        xbc = jax.nn.silu(acc).astype(cdt)                  # as the model rounds
        xs = xbc[:tiles].astype(f32)                        # [T, 128]
        bc = xbc[tiles:].astype(f32)                        # [B | C] flat
        if d_state % LANES == 0:
            nb = d_state // LANES
            brow = jnp.concatenate([bc[i:i + 1] for i in range(nb)], axis=1)
            crow = jnp.concatenate(
                [bc[nb + i:nb + i + 1] for i in range(nb)], axis=1)
        else:
            brow, crow = bc[0:1, :d_state], bc[0:1, d_state:2 * d_state]
        cols[...] = _columns(dd[pl.ds(r, 1), :])
        dec[...] = of_tiles(heads)
        xdt[...] = of_tiles(0) * xs

        def y_at(t, value):
            ytm[pl.ds(t, 1), :] = value

        _update_tiles(ibuf, obuf, slot, lambda t: dec[pl.ds(t, 1), :],
                      lambda t: xdt[pl.ds(t, 1), :], _columns(brow),
                      _columns(crow), y_at, unroll)
        g = (ytm[...] + lanes_ref[0, 0] * xs) * jax.nn.silu(z)
        var = jnp.sum(jnp.sum(g * g, axis=0, keepdims=True), axis=1,
                      keepdims=True) / (tiles * LANES)
        y_ref[r] = (g * jnp.reciprocal(jnp.sqrt(var + eps))
                    * lanes_ref[0, 1]).astype(cdt)

    _each_live_row(rows_ref, n_ref[0], fetch, store, compute)


@functools.partial(jax.jit, static_argnames=("eps", "n_groups", "interpret"))
def ssm_layer_step(state, window, layer, proj, dt, prep, active, live=None,
                   *, eps, n_groups=1, interpret=False):
    """Everything a Mamba layer's decode step does between its in-projection
    and its out-projection, for the rows with ``active != 0`` and for no
    others, as ONE kernel call.

    state ``[L, R, T, N, 128]`` and window ``[L, R, K - 1, tiles, 128]``
    (:func:`window_shape`): the engine's leaves WHOLE, both aliased to their
    outputs (the caller donates them).  proj ``[R, I + W]``: the
    in-projection's ``[z | x | B | C]`` as the product leaves it; dt ``[R,
    H]``; prep: :func:`prepare_layer_params` of the stacked parameters.  Per
    live row: the four-tap convolution and ``silu`` in float32, rounded to
    ``proj``'s dtype where the model rounds; ``delta = softplus(dt +
    dt_bias)``, ``decay = exp(delta A)``; the state's update
    (:func:`ssm_state_update_jnp`'s); ``y + D x`` gated by ``silu(z)`` and
    normalised.  Returns ``(y [R, I] in proj's dtype, state, window)``; a
    row with ``active == 0`` moves no byte: its state and its window stay
    bit for bit and its ``y`` is zeros.

    Jitted by itself: a decode program calls it from two layer loops and an
    engine compiles that program at nine table widths, and the kernel's
    body, some 500 operations, is then traced once a process and lowered
    once a program (by itself it was traced and lowered eighteen times a
    warm-up, seconds that no compile cache gives back)."""
    _, r, t, n, lanes = state.shape
    _, _, taps, ct, _ = window.shape
    heads = dt.shape[1]
    why = layer_step_unsupported(heads, t * lanes // heads, n, n_groups)
    if why:
        raise NotImplementedError(f"ssm_layer_step: {why}")
    rows, n_live = live_rows(active) if live is None else live
    # a row's channels 128 a sublane row, as the window keeps them (nothing
    # to pad at lane-aligned widths: a relayout of 1 MB, no more)
    proj = jnp.pad(proj, ((0, 0), (0, -proj.shape[1] % lanes)))
    proj = proj.reshape(r, -1, lanes)

    def of_layer(a):
        return pl.BlockSpec((1,) + a.shape[1:], lambda i, rows, n, layer: (
            layer[0],) + (0,) * (a.ndim - 1))

    tile = (t, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[_whole(proj.shape), _whole(dt.shape), of_layer(prep["conv"]),
                  of_layer(prep["heads"]), of_layer(prep["lanes"]),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[_whole((r,) + tile), pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((2, t, n, lanes), state.dtype),
            pltpu.VMEM((2, t, n, lanes), state.dtype),
            pltpu.VMEM((2, taps, ct, lanes), window.dtype),
            pltpu.VMEM((2, taps, ct, lanes), window.dtype),
            pltpu.VMEM((ct, lanes), jnp.float32),
            pltpu.VMEM((r, lanes), jnp.float32),
            pltpu.VMEM((lanes, lanes), jnp.float32),
            pltpu.VMEM(tile, jnp.float32), pltpu.VMEM(tile, jnp.float32),
            pltpu.VMEM(tile, jnp.float32),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    y, state, window = pl.pallas_call(
        functools.partial(_layer_kernel, unroll=4 if t % 4 == 0 else 1,
                          eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r,) + tile, proj.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(window.shape, window.dtype)],
        input_output_aliases={8: 1, 9: 2},  # the leaves, after 3 prefetched
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssm_state_update",  # the name the trace's readers find it by
    )(rows, n_live.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      proj, dt, prep["conv"], prep["heads"], prep["lanes"], state, window)
    return y.reshape(r, t * lanes), state, window
