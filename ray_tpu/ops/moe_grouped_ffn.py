"""Pallas grouped product of an expert layer's feed-forward: rows sorted by
expert against the experts' weights as they lie, side by side in ONE matrix
a layer.

``models/pangu_moe.py`` keeps its held experts side by side: expert ``j`` is
columns ``[j f, (j + 1) f)`` of ``we_gate`` / ``we_up`` ``[layers, d, e f]``
and rows ``[j f, (j + 1) f)`` of ``we_down`` ``[layers, e f, d]`` (the dense
form multiplies by a layer of them as one feed-forward of width ``e f``;
a ``[d, e, f]`` view costs a copy of every expert a layer-call, and so does a
layer sliced out of its stack ahead of a kernel).  A prompt chunk of a few
hundred tokens chooses a few rows an expert, so every-row-times-every-expert
does ``e`` times the chosen products.  Here the (row, expert) pairs the
router chose lie sorted by expert, ``group_sizes[j]`` rows for expert ``j``,
and a row meets its own expert's block alone.  A decode token-step runs it
too, for the other reason: its few live rows choose a PART of the held
experts, the grid visits the groups that have rows, and an expert no live
row chose is never fetched, so the step reads the hit share of the weights
where the dense form reads them all.  That only holds because the caller
zeroes the gates of the batch's slots that do not decode BEFORE it sorts
the pairs (``pangu_moe.moe_ffn``'s ``live``): a dead slot's stale token
routes somewhere too, and 64 slots between them choose nearly every held
expert.

The installed ``megablox.gmm`` wants ``[groups, k, n]``; its group metadata,
dynamic count of grid steps and mask at a group's edge are followed here,
with two differences.  The right-hand block index is ``(layer, 0, j n / tn +
n tile)`` (``group_axis=1``: the expert's columns) or ``(layer, j, n tile)``
(``group_axis=0``: its rows).  And the contraction is not tiled: a block is
the expert's whole depth, so a group that spans several row tiles keeps its
block in VMEM from one grid step to the next and **an expert's weights are
read once whatever the rows' layout**; the product is then bound by that
read (on a v5e 80 to 85% of the HBM's peak: PERF.md section 6, PR 40).  Grid
``(n tiles, visits)``, visits innermost: a visit is one (row tile, group)
overlap, ``tm`` rows against a ``[k, tn]`` block, stored under the mask of
the rows that belong to the group.  Rows past the last group are never
written: the caller masks them.

``rhs2``: the gated form ``silu(x W) * (x W2)``, both products rounded to the
output dtype as a plain ``x @ W`` in that dtype rounds them, the activation
in float32, rounded once.  In a profiler trace the instructions are named
``moe_grouped_ffn_up`` (the gated pair) and ``moe_grouped_ffn_down``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def group_visits(group_sizes, m: int, tm: int):
    """The grid's second dimension: every (row tile, group) overlap of rows
    ``[0, m)`` in tiles of ``tm``, the groups lying one after another from
    row 0.  Returns ``(bounds [2, G]: each group's first row and end, group
    [V], tile [V], visits)``, ``V = m // tm + G - 1`` the most there can be
    and ``visits`` how many there are (an empty group has none)."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    v_end = jnp.cumsum(count)
    v = jnp.arange(m // tm + g - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= v_end[None, :], axis=1), g - 1).astype(jnp.int32)
    tile = first[group] + v - (v_end - count)[group]
    tile = jnp.clip(tile, 0, m // tm - 1).astype(jnp.int32)
    return jnp.stack([starts, ends]), group, tile, v_end[-1]


def _kernel(layer_ref, bounds_ref, group_ref, tile_ref, x_ref, w_ref, *rest,
            tm):
    """One grid step = one n tile x one visit.  ``rest``: ``(o_ref,)`` or
    ``(w2_ref, o_ref)``."""
    del layer_ref  # the index maps' alone
    o_ref = rest[-1]
    v = pl.program_id(1)
    grp = group_ref[v]
    x = x_ref[...]
    y = jnp.dot(x, w_ref[...].astype(x.dtype),
                preferred_element_type=jnp.float32)
    if len(rest) == 2:
        up = jnp.dot(x, rest[0][...].astype(x.dtype),
                     preferred_element_type=jnp.float32)
        y = y.astype(o_ref.dtype).astype(jnp.float32)
        y = y * jax.nn.sigmoid(y) * up.astype(o_ref.dtype).astype(jnp.float32)
    rows = tile_ref[v] * tm + lax.broadcasted_iota(jnp.int32, y.shape, 0)
    own = jnp.logical_and(rows >= bounds_ref[0, grp], rows < bounds_ref[1, grp])
    o_ref[...] = jnp.where(own, y.astype(o_ref.dtype), o_ref[...])


def _tile(n: int, want: int) -> int:
    """The widest tile of whole 128-lane columns that divides ``n`` and is no
    wider than ``want``; ``n`` itself where it has none."""
    for t in range(min(want, n) // 128 * 128, 0, -128):
        if n % t == 0:
            return t
    return n


def grouped_matmul(lhs, rhs, layer, group_sizes, *, group_axis: int,
                   rhs2: Optional[jnp.ndarray] = None, tm: int = 128,
                   tn: int = 512, out_dtype=None, interpret: bool = False,
                   name: str = "moe_grouped_ffn"):
    """``out[r] = lhs[r] @ W_j`` for the rows ``r`` of group ``j``.

    lhs ``[m, k]``, its rows sorted by group: the first ``group_sizes[0]``
    belong to group 0 and so on; ``m`` a multiple of ``tm`` and at least the
    sizes' sum (rows past it come back UNWRITTEN, whatever the buffer held).
    rhs is a STACK of layers and ``layer`` (a scalar) the one meant: a
    layer sliced out of its stack ahead of a kernel is a copy of it, so the
    block index takes the layer too.  ``group_axis=1``: ``[L, k, G n]``,
    ``W_j`` the layer's columns ``[j n, (j + 1) n)``; ``group_axis=0``:
    ``[L, G k, n]``, ``W_j`` its rows ``[j k, (j + 1) k)``.  ``rhs2`` (same
    layout): returns ``silu(lhs W_j) * (lhs W2_j)``.  Returns ``[m, n]`` in
    ``out_dtype`` (lhs's), float32 inside.
    """
    m, k = lhs.shape
    g = group_sizes.shape[0]
    out_dtype = out_dtype or lhs.dtype
    if group_axis == 1:
        n = rhs.shape[2] // g
        ok = rhs.shape[1:] == (k, g * n)
    else:
        n = rhs.shape[2]
        ok = rhs.shape[1:] == (g * k, n)
    if not ok or (rhs2 is not None and rhs2.shape != rhs.shape):
        raise ValueError(
            f"rows of {k} against {rhs.shape} for {g} groups along axis "
            f"{group_axis}: not {g} blocks of depth {k}")
    if m % tm:
        raise ValueError(f"{m} rows are not whole tiles of {tm}")
    tn = _tile(n, tn)
    tiles_n = n // tn
    bounds, group, tile, visits = group_visits(group_sizes, m, tm)

    if group_axis == 1:
        def rhs_index(i, v, lay, b, grp, til):
            return lay[0], 0, grp[v] * tiles_n + i
    else:
        def rhs_index(i, v, lay, b, grp, til):
            return lay[0], grp[v], i

    rhs_spec = pl.BlockSpec((None, k, tn), rhs_index)
    weights = (rhs,) if rhs2 is None else (rhs, rhs2)
    # every block twice (the pipeline's two buffers), the float32 products
    need = (2 * len(weights) * k * tn * jnp.dtype(rhs.dtype).itemsize
            + 2 * tm * k * jnp.dtype(lhs.dtype).itemsize
            + 2 * tm * tn * jnp.dtype(out_dtype).itemsize
            + (len(weights) + 1) * tm * tn * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tiles_n, visits),
        in_specs=[pl.BlockSpec((tm, k),
                               lambda i, v, lay, b, grp, til: (til[v], 0))]
        + [rhs_spec] * len(weights),
        out_specs=pl.BlockSpec((tm, tn),
                               lambda i, v, lay, b, grp, til: (til[v], i)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(need + (16 << 20), 100 << 20)),
        interpret=interpret,
        name=name,  # the kernel's name in a profiler trace
    )(jnp.asarray(layer, jnp.int32).reshape(1), bounds, group, tile, lhs,
      *weights)


def moe_grouped_ffn(xs, w_gate, w_up, w_down, layer, group_sizes, row_gates,
                    *, tm: int = 128, tn_up: int = 1024, tn_down: int = 1920,
                    interpret: bool = False):
    """The routed experts' feed-forward of rows sorted by expert.

    xs ``[m, d]`` (row ``r`` of group ``j`` is a token that chose held expert
    ``j``); w_gate, w_up ``[L, d, e f]``; w_down ``[L, e f, d]`` (multiplied
    in xs's dtype); layer a scalar; group_sizes ``[e]``; row_gates ``[m]``
    each row's gate.  Returns ``[m, d]`` float32: ``(gate_r * silu(x_r Wg_j)
    * (x_r Wu_j)) Wd_j``, the hidden units scaled in xs's dtype before the
    down-projection as the dense form scales them.  Rows past the sizes' sum
    are unwritten.  The tiles are what a v5e ran fastest at 16 experts of
    7680 x 2048 (``benchmarks/moe_prefill_bench.py --tiles``: the two
    kernels' time moves by 6% over row tiles of 64 to 256 and column tiles
    of 256 to 1,024 and 1,280 to 3,840).
    """
    act = grouped_matmul(xs, w_gate, layer, group_sizes, group_axis=1,
                         rhs2=w_up, tm=tm, tn=tn_up, interpret=interpret,
                         name="moe_grouped_ffn_up")
    act = act * row_gates[:, None].astype(act.dtype)
    return grouped_matmul(act, w_down, layer, group_sizes, group_axis=0, tm=tm,
                          tn=tn_down, out_dtype=jnp.float32,
                          interpret=interpret, name="moe_grouped_ffn_down")
