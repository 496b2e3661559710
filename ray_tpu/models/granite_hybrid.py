"""granite-4.0-h-style hybrid decoder: Mamba-2 layers whose state is a SLOT's,
a few attention layers over a paged KV cache, a gated feed-forward after each.

The equations, as computed (``models/granite_hybrid_reference.py`` computes
the same in float32, position by position).  With ``d`` the hidden width,
``I = mamba_expand x d`` = ``H`` heads of ``P``, state width ``N``, one group,
convolution width ``K``:

- ``h0 = E[ids] * embedding_multiplier``.  For each layer ``u = RMSNorm(h)``;
  ``h = h + residual_multiplier * Mixer(u)``; ``u = RMSNorm(h)``;
  ``h = h + residual_multiplier * W_o(silu(g) * v)``, ``[g, v] = u W_i``.
  After the last layer ``logits = RMSNorm(h) E^T / logits_scaling``.
- Mamba mixer: ``[z, xBC, dt] = u W_in``; ``xBC <- silu(causal depthwise
  convolution of width K over positions, with bias)``; ``[x, B, C] =
  split(xBC)``; ``delta = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` a
  head; **``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (outer) B_t``**;
  ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * silu(z))`` over all ``I``;
  ``out = y W_out``.  No projection bias.
- Attention mixer: GQA without rotation (``position_embedding_type: nope``),
  scores scaled by ``attention_multiplier``, not by ``1 / sqrt(head width)``.

**Two kinds of state** (models/family.py).  The attention layers' keys and
values page like any other family's (pool leaves ``k`` and ``v``, over the
attention layers ONLY).  A Mamba layer's state does not page: it is one
fixed-size value a sequence, so the engine keeps it a SLOT (``init_slot_state``:
``ssm`` ``[mamba layers, max_batch, tiles, N, 128]`` float32 in the layout
``ops/ssm_state_update.py`` explains, and ``conv`` ``[mamba layers, max_batch,
K - 1, tiles, 128]``, the convolution's last inputs, ``I + 2N`` channels a tap
as rows of 128).

**Two forms that must agree.**  A prompt chunk runs the chunked
(matrix-product) form at ``mamba_chunk_size``: inside a chunk a masked
``[Q, Q]`` decay product, between chunks the carried state.  It takes the
slot's state in (zeros where ``p0 == 0``: a re-used slot needs no clearing)
and writes the state after the chunk's last REAL token: positions past
``take`` (the engine pads every chunk to a power of two) get ``delta = 0``,
which neither decays the state nor adds to it, and the convolution's window
is cut at ``take``.  A decode token-step runs the one-step recurrence: between
a layer's two projections ONE call of the Pallas kernel ``ssm_state_update``
(``ops/ssm_state_update.py ssm_layer_step``) for the rows with ``active !=
0``, nothing for the others (for keys and values a masked row is harmless; for a
recurrent state it would be a wrong answer).

Layers are stacked by kind and scanned by PERIOD of ``layer_types`` (the
published 40 layers: 4 periods of 5 Mamba, 1 attention, 4 Mamba), so a
program's size does not grow with depth.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import ssm_state_update as ssm_ops
from ray_tpu.ops.norms import rms_norm

Params = Dict[str, Any]

# a period of the published layer_types (granite-4.0-h-micro: four of them)
PUBLISHED_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    dim: int = 2048
    layer_types: Tuple[str, ...] = PUBLISHED_PERIOD * 4
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 8192
    mamba_expand: int = 2
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 4096
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_n_groups != 1:
            raise ValueError("one B/C group is computed (mamba_n_groups "
                             f"{self.mamba_n_groups})")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError("mamba_n_heads x mamba_d_head must be "
                             "mamba_expand x dim")
        if set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        p = len(self.period)
        if self.layer_types != self.period * (len(self.layer_types) // p):
            raise ValueError("layer_types is not a whole number of periods")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` that repeats to it."""
        lt = self.layer_types
        for p in range(1, len(lt) + 1):
            if len(lt) % p == 0 and lt[:p] * (len(lt) // p) == lt:
                return lt[:p]
        return lt

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.period)

    @property
    def runs(self) -> Tuple[Tuple[str, int], ...]:
        """A period as runs of one kind: ``(("mamba", 5), ("attention", 1),
        ("mamba", 4))``."""
        out = []
        for kind in self.period:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return tuple((k, n) for k, n in out)

    def count(self, kind: str) -> int:
        """Layers of ``kind`` in the whole model."""
        return self.layer_types.count(kind)

    @property
    def num_params(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(jax.eval_shape(
            lambda: init_params(self, jax.random.PRNGKey(0)))))

    @classmethod
    def from_published(cls, config: dict, **kw) -> "GraniteHybridConfig":
        """The config for a published ``config.json`` (``model_type``
        ``granitemoehybrid``) under its own key names; ``kw``: fields the
        file does not give (``max_seq_len``, the dtypes).  What this family
        does not compute is refused here, by key."""
        refused = {
            "num_local_experts": 0, "position_embedding_type": "nope",
            "mamba_proj_bias": False, "attention_bias": False,
            "mamba_conv_bias": True, "tie_word_embeddings": True,
            "hidden_act": "silu", "normalization_function": "rmsnorm"}
        for k, want in refused.items():
            if config.get(k, want) != want:
                raise ValueError(f"{k} = {config[k]!r}: this family computes "
                                 f"{want!r}")
        if config["intermediate_size"] != config["shared_intermediate_size"]:
            raise ValueError("without routed experts the feed-forward is the "
                             "shared one: the two widths must agree")
        return cls(
            vocab_size=config["vocab_size"], dim=config["hidden_size"],
            layer_types=tuple(config["layer_types"]),
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_key_value_heads"],
            ffn_dim=config["shared_intermediate_size"],
            mamba_expand=config["mamba_expand"],
            mamba_n_heads=config["mamba_n_heads"],
            mamba_d_head=config["mamba_d_head"],
            mamba_d_state=config["mamba_d_state"],
            mamba_n_groups=config["mamba_n_groups"],
            mamba_d_conv=config["mamba_d_conv"],
            mamba_chunk_size=config["mamba_chunk_size"],
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            attention_multiplier=float(config["attention_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
            rms_norm_eps=float(config["rms_norm_eps"]), **kw)

    @classmethod
    def tiny(cls, **kw) -> "GraniteHybridConfig":
        """Test-sized, the published shape: one period of 10 layers,
        attention heads of 64."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("dim", 128)
        kw.setdefault("layer_types", PUBLISHED_PERIOD)
        kw.setdefault("n_heads", 2)
        kw.setdefault("n_kv_heads", 2)
        kw.setdefault("ffn_dim", 256)
        kw.setdefault("mamba_n_heads", 8)
        kw.setdefault("mamba_d_head", 32)
        kw.setdefault("mamba_d_state", 16)
        kw.setdefault("mamba_chunk_size", 16)
        kw.setdefault("attention_multiplier", 1.0 / 64)
        kw.setdefault("max_seq_len", 256)
        kw.setdefault("param_dtype", jnp.float32)
        kw.setdefault("compute_dtype", jnp.float32)
        return cls(**kw)


# -- parameters ------------------------------------------------------------------


def init_params(cfg: GraniteHybridConfig, key: jax.Array) -> Params:
    """Random weights, stacked by kind: ``mamba`` ``[mamba layers, ...]``,
    ``attn`` ``[attention layers, ...]``, and the norms and the feed-forward
    (one after EVERY layer) ``[layers, ...]``.  Matrices N(0, 0.02), the
    embedding N(0, 0.02 / embedding_multiplier): at 0.02 the multiplier
    (12) makes the input token's own row the largest logit of the tied head
    by five standard deviations, a random model then answers every token
    with itself, and greedy tokens say nothing of the layers (seen on the
    chip: of 1,120 served tokens 4 were not the reference's, and a bf16 or
    an 8-bit control none).  The
    state-space parameters as the family initialises them, so that random
    weights decay as a trained model's do: ``A_log = log(U(1, 16))``,
    ``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1],
    ``D = 1``; the convolution U(+-1/sqrt(K)) (a depthwise kernel's
    default)."""
    dt = cfg.param_dtype
    d, i, h, n = cfg.dim, cfg.d_inner, cfg.mamba_n_heads, cfg.mamba_d_state
    k = cfg.mamba_d_conv
    nl, nm, na = cfg.n_layers, cfg.count("mamba"), cfg.count("attention")
    keys = iter(jax.random.split(key, 16))

    def mat(*shape):
        return (jax.random.normal(next(keys), shape, jnp.float32) * 0.02
                ).astype(dt)

    def ones(*shape):
        return jnp.ones(shape, dt)

    bound = 1.0 / math.sqrt(k)
    step = jnp.exp(jax.random.uniform(
        next(keys), (nm, h), jnp.float32, math.log(0.001), math.log(0.1)))
    return {
        "embed": mat(cfg.vocab_size, d) / jnp.asarray(
            cfg.embedding_multiplier, dt),
        "final_norm": ones(d),
        "norms": {"mixer": ones(nl, d), "ffn": ones(nl, d)},
        "ffn": {"w_in": mat(nl, d, 2 * cfg.ffn_dim),
                "w_out": mat(nl, cfg.ffn_dim, d)},
        "mamba": {
            # W_in (d -> 2I + 2N + H) as its two column blocks [z | x | B |
            # C] and [dt]: the same matrix, split where its columns stop
            # being whole lane tiles (8,512 = 66.5 x 128: the device keeps
            # such a stack in a layout of its own and the programs copied
            # all of it, 1.25 GB, once a dispatch)
            "w_in": mat(nm, d, 2 * i + 2 * n),
            "w_dt": mat(nm, d, h),
            "conv_w": jax.random.uniform(
                next(keys), (nm, k, cfg.conv_width), jnp.float32,
                -bound, bound).astype(dt),
            "conv_b": jax.random.uniform(
                next(keys), (nm, cfg.conv_width), jnp.float32,
                -bound, bound).astype(dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (nm, h), jnp.float32, 1.0, 16.0)).astype(dt),
            "d": ones(nm, h),
            "norm": ones(nm, i),
            "w_out": mat(nm, i, d),
        },
        "attn": {
            "wq": mat(na, d, cfg.n_heads * cfg.head_dim),
            "wk": mat(na, d, cfg.n_kv_heads * cfg.head_dim),
            "wv": mat(na, d, cfg.n_kv_heads * cfg.head_dim),
            "wo": mat(na, cfg.n_heads * cfg.head_dim, d),
        },
    }


def init_paged_cache(cfg: GraniteHybridConfig, num_blocks: int,
                     block_size: int) -> Dict[str, jnp.ndarray]:
    """Keys and values of the ATTENTION layers only: ``[attention layers,
    blocks, block_size, kv heads x head width]``.  A head of 64 is stored as
    it is (8,192 B a position at the published widths); the decode kernel
    reads two heads a 128-lane tile (ops/paged_attention.py)."""
    shape = (cfg.count("attention"), num_blocks, block_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def init_slot_state(cfg: GraniteHybridConfig,
                    max_batch: int) -> Dict[str, jnp.ndarray]:
    """The state a slot holds (family seam): every leaf ``[mamba layers,
    max_batch, ...]``."""
    nm = cfg.count("mamba")
    return {
        "ssm": jnp.zeros(ssm_ops.state_shape(
            nm, max_batch, cfg.mamba_n_heads, cfg.mamba_d_head,
            # float32 at rest: the published config says only ``bfloat16``
            # for the model, and this is the program's choice (``assumed``)
            cfg.mamba_d_state), jnp.float32),
        # the last K - 1 inputs, oldest first, each tap's channels 128 a
        # sublane row: a slot's window is whole memory tiles, which the
        # decode kernel copies in and out a row at a time
        "conv": jnp.zeros(ssm_ops.window_shape(
            nm, max_batch, cfg.mamba_d_conv, cfg.conv_width),
            cfg.compute_dtype),
    }


# -- the pieces both programs share ---------------------------------------------


def _project(cfg, lp, u):
    """``u W_in`` -> ``([z | xBC], dt)``.  The barrier keeps the wide
    projection ONE product in its own layout: what is cut or laid out anew is
    its result, not the weight (a layer's 35 MB, copied a layer-call)."""
    cdt = cfg.compute_dtype
    return (lax.optimization_barrier(u @ lp["w_in"].astype(cdt)),
            u @ lp["w_dt"].astype(cdt))


def _project_in(cfg, lp, u):
    """``u W_in`` -> ``(z, xBC, dt)``."""
    proj, dt = _project(cfg, lp, u)
    return proj[..., :cfg.d_inner], proj[..., cfg.d_inner:], dt


def _split_xbc(cfg, xbc):
    i, n = cfg.d_inner, cfg.mamba_d_state
    return xbc[..., :i], xbc[..., i:i + n], xbc[..., i + n:]


def _delta(lp, dt):
    """``softplus(dt + dt_bias)`` float32 a head."""
    return jax.nn.softplus(dt.astype(jnp.float32)
                           + lp["dt_bias"].astype(jnp.float32))


def _gated(cfg, lp, y, z):
    """``RMSNorm(y * silu(z))`` in the compute dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rms_norm(g, lp["norm"], cfg.rms_norm_eps).astype(cfg.compute_dtype)


def _ffn(cfg, x, norm_w, fp):
    cdt = cfg.compute_dtype
    with jax.named_scope("ffn"):
        u = rms_norm(x, norm_w, cfg.rms_norm_eps)
        gv = u @ fp["w_in"].astype(cdt)
        g, v = gv[..., :cfg.ffn_dim], gv[..., cfg.ffn_dim:]
        out = (jax.nn.silu(g) * v) @ fp["w_out"].astype(cdt)
        return x + (cfg.residual_multiplier * out).astype(x.dtype)


def _head(cfg, params, x):
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        # contracted against the embedding's own columns: no transposed copy
        logits = lax.dot_general(
            x, params["embed"].astype(cfg.compute_dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling


def _embed(cfg, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, cfg.compute_dtype)


def _scan_periods(cfg, params, carry, mamba_layer, attn_layer, mamba_xs):
    """Run every layer over ``carry`` (whose first element is the hidden
    state): an outer scan over the periods, inside it one scan a run of
    layers of one kind.  ``mamba_layer(carry, lp, mi, xs) -> (carry, ys)``
    and ``attn_layer(carry, lp, ai) -> carry`` return the carry after the
    MIXER (residual added); the feed-forward that follows every layer is
    added here.  A layer's weights are indexed out of the whole stacks by the
    layer's own number: scanned over as ``xs``, the outer scan would copy a
    period's weights (1.6 GB) before the inner one sliced a layer of them.
    ``mamba_xs``: a tree of ``[mamba layers, ...]`` arrays a Mamba layer reads
    its own row of; what the layers return for it comes back stacked the same
    way (what is small enough to be scanned over and rebuilt; the recurrent
    state of every slot rides the carry)."""
    per = len(cfg.period)
    nm = cfg.period.count("mamba")
    na = cfg.period.count("attention")

    def by_period(a):
        return a.reshape(cfg.n_periods, nm, *a.shape[1:])

    def at_layer(tree, i):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)

    def period(carry, inp):
        pxs, pi = inp
        at = {"mamba": 0, "attention": 0, "layer": 0}
        out = []
        for kind, n in cfg.runs:
            is_mamba = kind == "mamba"
            lo, j0 = at[kind], at["layer"]
            steps = jnp.arange(n)
            xs = (jax.tree.map(lambda a: a[lo:lo + n], pxs) if is_mamba
                  else None)

            def body(carry, inp, is_mamba=is_mamba):
                idx, li, xs = inp
                lp = dict(
                    at_layer(params["mamba" if is_mamba else "attn"], idx),
                    in_norm=at_layer(params["norms"]["mixer"], li))
                if is_mamba:
                    carry, ys = mamba_layer(carry, lp, idx, xs)
                else:
                    carry, ys = attn_layer(carry, lp, idx), None
                x = _ffn(cfg, carry[0], at_layer(params["norms"]["ffn"], li),
                         at_layer(params["ffn"], li))
                return (x,) + tuple(carry[1:]), ys

            carry, ys = lax.scan(
                body, carry,
                (pi * (nm if is_mamba else na) + lo + steps,
                 pi * per + j0 + steps, xs))
            if is_mamba:
                out.append(ys)
            at[kind] += n
            at["layer"] += n
        ys = jax.tree.map(lambda *a: jnp.concatenate(a, 0), *out)
        return carry, ys

    carry, ys = lax.scan(
        period, carry,
        (jax.tree.map(by_period, mamba_xs), jnp.arange(cfg.n_periods)))
    return carry, jax.tree.map(
        lambda a: a.reshape(cfg.n_periods * nm, *a.shape[2:]), ys)


# -- the chunked (matrix-product) form ----------------------------------------------


def ssd_chunked(cfg: GraniteHybridConfig, x, delta, a, bm, cm, s0):
    """The recurrence over ``C`` positions as matrix products.

    x ``[C, H, P]``; delta ``[C, H]`` float32 (0 at a position that must not
    advance the state); a ``[H]`` (negative); bm, cm ``[C, N]``; s0 ``[H, P,
    N]`` float32, the state before position 0.  Inside a chunk of ``Q =
    mamba_chunk_size`` positions ``y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t .
    B_s) delta_s x_s`` with ``cs`` the running sum of ``delta A``: a masked
    ``[Q, Q]`` decay product a head; between chunks the state is carried.
    Returns ``(y [C, H, P] float32, state after position C - 1)``."""
    c, h, p = x.shape
    f32 = jnp.float32
    q = min(cfg.mamba_chunk_size, c)
    if c % q:
        raise ValueError(f"{c} positions are not whole chunks of {q}")
    nc = c // q
    cdt = x.dtype
    da = (delta * a.astype(f32)).reshape(nc, q, h)          # <= 0
    cs = jnp.cumsum(da, axis=1)                              # [nc, Q, H]
    cs_h = cs.transpose(0, 2, 1)                             # [nc, H, Q]
    xs = x.reshape(nc, q, h, p)
    dl = delta.reshape(nc, q, h)
    bs_, cs_ = bm.reshape(nc, q, -1), cm.reshape(nc, q, -1)
    # inside a chunk
    g = jnp.einsum("ctn,csn->cts", cs_, bs_, preferred_element_type=f32)
    diff = cs_h[:, :, :, None] - cs_h[:, :, None, :]         # [nc, H, t, s]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    m = g[:, None] * decay * dl.transpose(0, 2, 1)[:, :, None, :]
    y = jnp.einsum("chts,cshp->cthp", m.astype(cdt), xs,
                   preferred_element_type=f32)
    # what each chunk alone leaves behind, and what it lets through
    to_end = jnp.exp(cs[:, -1:, :] - cs) * dl                # [nc, Q, H]
    left = jnp.einsum("csh,cshp,csn->chpn", to_end, xs.astype(f32),
                      bs_.astype(f32))
    through = jnp.exp(cs[:, -1, :])                          # [nc, H]

    def carry_on(s, inp):
        left_c, through_c = inp
        return through_c[:, None, None] * s + left_c, s

    s_out, s_in = lax.scan(carry_on, s0.astype(f32), (left, through))
    y = y + jnp.einsum("ctn,chpn->cthp", cs_.astype(f32), s_in
                       ) * jnp.exp(cs)[..., None]
    return y.reshape(c, h, p), s_out


# -- a prompt chunk -----------------------------------------------------------------


def prefill_chunk_paged(cfg: GraniteHybridConfig, params: Params,
                        tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                        table: jnp.ndarray, p0: jnp.ndarray, *,
                        rope_cache=None, tp_plan=None,
                        use_kernel: bool = False,
                        kernel_interpret: bool = False, slot_state, slot,
                        take):
    """One chunk of one sequence (the Llama chunk's contract, models/llama.py)
    plus the slot's state: ``slot`` is the engine's slot, ``take`` the count
    of REAL tokens in ``tokens [1, C]``.  The state comes in from the slot
    (zeros where ``p0 == 0``) and the state after token ``take - 1`` goes
    back; ``take == 0`` (warm-up) leaves the slot as it was.  The chunk has
    no kernel of its own (``use_kernel`` is not read).  Returns
    ``(logits [1, C, V] float32, pool, slot_state)``."""
    from ray_tpu.models.llama import PREFILL_KV_TILE, _prefill_attend_tiles

    del rope_cache, tp_plan, use_kernel, kernel_interpret
    _, c = tokens.shape
    bs = pool["k"].shape[2]
    cdt = cfg.compute_dtype
    f32 = jnp.float32
    h, p = cfg.mamba_n_heads, cfg.mamba_d_head
    kw = cfg.mamba_d_conv - 1
    positions = p0 + jnp.arange(c)
    real = jnp.arange(c) < take
    fresh = p0 == 0
    chunk_blocks = lax.dynamic_slice(table[0], (p0 // bs,), (c // bs,))
    row = jnp.pad(table[0], (0, -table.shape[1] % (PREFILL_KV_TILE // bs)))
    x = _embed(cfg, params, tokens[0])

    def mamba_layer(carry, lp, mi, xs):
        x, pk, pv = carry
        s_old, win_old = xs                      # this layer's, this slot's
        with jax.named_scope("ssm"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            z, xbc, dt = _project_in(cfg, lp, u)
            win_in = jnp.where(fresh, jnp.zeros_like(win_old), win_old)
            seq = jnp.concatenate(
                [ssm_ops.unpack_window(win_in, cfg.conv_width),
                 xbc.astype(win_old.dtype)], axis=0)
            w = lp["conv_w"].astype(f32)
            acc = lp["conv_b"].astype(f32)[None, :]
            for k in range(cfg.mamba_d_conv):
                acc = acc + w[k][None, :] * seq[k:k + c].astype(f32)
            xbc = jax.nn.silu(acc).astype(cdt)
            # the window after the last REAL token
            win_new = ssm_ops.pack_window(lax.dynamic_slice(
                seq, (take, 0), (kw, cfg.conv_width)))
            xm, bm, cm = _split_xbc(cfg, xbc)
            delta = jnp.where(real[:, None], _delta(lp, dt), 0.0)
            a = -jnp.exp(lp["a_log"].astype(f32))
            s0 = ssm_ops.unpack_state(s_old.astype(f32), h)
            s0 = jnp.where(fresh, jnp.zeros_like(s0), s0)
            xh = xm.reshape(c, h, p)
            y, s_new = ssd_chunked(cfg, xh, delta, a, bm, cm, s0)
            y = y + lp["d"].astype(f32)[None, :, None] * xh.astype(f32)
            out = _gated(cfg, lp, y.reshape(c, h * p), z) @ lp[
                "w_out"].astype(cdt)
            x = x + (cfg.residual_multiplier * out).astype(x.dtype)
            keep = take > 0
            s_new = jnp.where(keep, ssm_ops.pack_state(s_new).astype(
                s_old.dtype), s_old)
            win_new = jnp.where(keep, win_new, win_old)
        return (x, pk, pv), (s_new, win_new)

    def attn_layer(carry, lp, ai):
        x, pk, pv = carry
        with jax.named_scope("attention"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q = (u @ lp["wq"].astype(cdt)).reshape(c, cfg.n_heads,
                                                   cfg.head_dim)
            k = u @ lp["wk"].astype(cdt)
            v = u @ lp["wv"].astype(cdt)
            pk = pk.at[ai, chunk_blocks].set(
                k.reshape(c // bs, bs, -1).astype(pk.dtype))
            pv = pv.at[ai, chunk_blocks].set(
                v.reshape(c // bs, bs, -1).astype(pv.dtype))
            attn = _prefill_attend_tiles(
                cfg, q, pk, pv, ai, row, positions, PREFILL_KV_TILE,
                scale=cfg.attention_multiplier)
            out = attn.astype(cdt) @ lp["wo"].astype(cdt)
            x = x + (cfg.residual_multiplier * out).astype(x.dtype)
        return x, pk, pv

    # the slot's own state, every layer's, out of the leaves BEFORE the layer
    # scans and back AFTER them: carried through the scans, the whole leaf
    # (every slot's state) is copied once a chunk to whatever layout the
    # chunked form's transposes prefer (seen in the compiler's memory report)
    ssm, conv = slot_state["ssm"], slot_state["conv"]
    mine = (lax.dynamic_index_in_dim(ssm, slot, 1, keepdims=False),
            lax.dynamic_index_in_dim(conv, slot, 1, keepdims=False))
    (x, pk, pv), (s_new, win_new) = _scan_periods(
        cfg, params, (x, pool["k"], pool["v"]), mamba_layer, attn_layer,
        mine)
    ssm = lax.dynamic_update_index_in_dim(ssm, s_new, slot, 1)
    conv = lax.dynamic_update_index_in_dim(conv, win_new, slot, 1)
    return (_head(cfg, params, x)[None], {"k": pk, "v": pv},
            {"ssm": ssm, "conv": conv})


# -- a decode token-step --------------------------------------------------------------


def kernel_supported(cfg: GraniteHybridConfig) -> bool:
    """Both decode kernels apply: a TPU backend, attention heads the paged
    kernel reads (128 wide, or 64 wide two a tile), and a state whose lanes
    and sublanes are whole tiles."""
    if jax.default_backend() != "tpu":
        return False
    hd = cfg.head_dim
    if not (hd % 128 == 0 or (hd == 64 and cfg.n_kv_heads % 2 == 0)):
        return False
    if cfg.mamba_d_state % 8 or ssm_ops.layer_step_unsupported(
            cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups):
        return False
    from ray_tpu.ops.paged_attention import (  # noqa: F401
        paged_decode_attention,
    )

    return True


def recurrent_step_jnp(cfg: GraniteHybridConfig, lp, z, xbc, dt, ssm, win,
                       mi, active):
    """A Mamba layer's decode step between its two projections, in
    ``jax.numpy`` over every slot (the CPU's form and the tests'; what
    ``ops/ssm_state_update.py ssm_layer_step`` computes for the rows that
    decode).  z, xbc, dt: the in-projection's, ``[B, ...]``; ssm: the state
    leaf whole; win ``[B, K - 1, tiles, 128]``: this layer's windows.
    Returns ``(RMSNorm(y * silu(z)) [B, I], ssm, win)``."""
    f32 = jnp.float32
    b = z.shape[0]
    h, p = cfg.mamba_n_heads, cfg.mamba_d_head
    # the last inputs, oldest first, then this token's
    held = ssm_ops.unpack_window(win, cfg.conv_width)
    seq = jnp.concatenate([held, xbc.astype(win.dtype)[:, None]], axis=1)
    w = lp["conv_w"].astype(f32)
    acc = lp["conv_b"].astype(f32)[None, :]
    for k in range(cfg.mamba_d_conv):
        acc = acc + w[k][None, :] * seq[:, k].astype(f32)
    win = jnp.where((active != 0)[:, None, None, None],
                    ssm_ops.pack_window(seq[:, 1:]), win)
    xm, bm, cm = _split_xbc(
        cfg, jax.nn.silu(acc).astype(cfg.compute_dtype))
    delta = _delta(lp, dt)                                  # [B, H]
    a = -jnp.exp(lp["a_log"].astype(f32))
    xh = xm.astype(f32).reshape(b, h, p)
    decay = jnp.broadcast_to(jnp.exp(delta * a)[..., None],
                             (b, h, p)).reshape(b, h * p)
    xdt = (delta[..., None] * xh).reshape(b, h * p)
    y, ssm = ssm_ops.ssm_state_update_jnp(ssm, mi, decay, xdt, bm, cm, active)
    y = y + (lp["d"].astype(f32)[None, :, None] * xh).reshape(b, h * p)
    return _gated(cfg, lp, y, z), ssm, win


def decode_step_paged(cfg: GraniteHybridConfig, params: Params,
                      tokens: jnp.ndarray, pool: Dict[str, jnp.ndarray],
                      table: jnp.ndarray, lengths: jnp.ndarray, *,
                      rope_cache=None, use_kernel: bool = False, mesh=None,
                      kernel_interpret: bool = False, tp_plan=None,
                      active: Optional[jnp.ndarray] = None, slot_state):
    """One token for every slot (the Llama step's contract) plus the slots'
    state: a row with ``active == 0`` keeps its recurrent state and its
    convolution window bit for bit, whatever its token is.  Returns
    ``(logits [B, V] float32, pool, slot_state, None)``: the family books
    no counters."""
    from ray_tpu.models.llama import _paged_attend

    del rope_cache, mesh, tp_plan
    b = tokens.shape[0]
    bs = pool["k"].shape[2]
    w = table.shape[1]
    cdt = cfg.compute_dtype
    active = jnp.ones_like(lengths) if active is None else active
    if use_kernel:
        live_list = ssm_ops.live_rows(active)
        mp = params["mamba"]
        small = ssm_ops.prepare_layer_params(
            mp["conv_w"], mp["conv_b"], mp["dt_bias"], mp["a_log"], mp["d"],
            mp["norm"], cfg.mamba_d_head)
    bidx = jnp.arange(b)
    cur_blk = table[bidx, lengths // bs]
    cur_off = lengths % bs
    if not use_kernel:
        span_mask = (jnp.arange(w * bs)[None, None, :]
                     <= lengths[:, None, None])
    x = _embed(cfg, params, tokens)

    def mamba_layer(carry, lp, mi, win):
        x, pk, pv, ssm, conv = carry
        with jax.named_scope("ssm"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            if use_kernel:  # the products, and ONE call between them
                g, ssm, conv = ssm_ops.ssm_layer_step(
                    ssm, conv, mi, *_project(cfg, lp, u), small, active,
                    live_list, eps=cfg.rms_norm_eps,
                    n_groups=cfg.mamba_n_groups, interpret=kernel_interpret)
            else:
                g, ssm, win = recurrent_step_jnp(
                    cfg, lp, *_project_in(cfg, lp, u), ssm, win, mi, active)
            out = g @ lp["w_out"].astype(cdt)
            x = x + (cfg.residual_multiplier * out).astype(x.dtype)
        return (x, pk, pv, ssm, conv), win

    def attn_layer(carry, lp, ai):
        x, pk, pv, *state = carry
        with jax.named_scope("attention"):
            u = rms_norm(x, lp["in_norm"], cfg.rms_norm_eps)
            q, k = lax.optimization_barrier(
                (u @ lp["wq"].astype(cdt), u @ lp["wk"].astype(cdt)))
            v = u @ lp["wv"].astype(cdt)
            q = q.reshape(b, cfg.n_heads, cfg.head_dim)
            pk = pk.at[ai, cur_blk, cur_off].set(k.astype(pk.dtype))
            pv = pv.at[ai, cur_blk, cur_off].set(v.astype(pv.dtype))
            if use_kernel:
                from ray_tpu.ops.paged_attention import paged_decode_attention

                attn = paged_decode_attention(
                    q, pk, pv, ai, table, lengths, active,
                    interpret=kernel_interpret,
                    scale=cfg.attention_multiplier)
            else:
                ck = pk[ai, table].reshape(b, w * bs, cfg.n_kv_heads,
                                           cfg.head_dim)
                cv = pv[ai, table].reshape(b, w * bs, cfg.n_kv_heads,
                                           cfg.head_dim)
                attn = _paged_attend(cfg, q[:, None], ck, cv, span_mask,
                                     scale=cfg.attention_multiplier)[:, 0]
            out = attn.astype(cdt) @ lp["wo"].astype(cdt)
            x = x + (cfg.residual_multiplier * out).astype(x.dtype)
        return (x, pk, pv, *state)

    # every slot's recurrent state rides the carry (updated in place).  With
    # the kernel so do the windows, and it writes the decoding rows' alone;
    # without it they are scanned over and rebuilt
    conv = slot_state["conv"]
    carry = (x, pool["k"], pool["v"], slot_state["ssm"],
             conv if use_kernel else None)
    (x, pk, pv, ssm, kept), rebuilt = _scan_periods(
        cfg, params, carry, mamba_layer, attn_layer,
        None if use_kernel else conv)
    return (_head(cfg, params, x), {"k": pk, "v": pv},
            {"ssm": ssm, "conv": kept if use_kernel else rebuilt}, None)


# -- the family seam (models/family.py) -------------------------------------------------


def _no_rope(cfg, max_seq):
    return None


def _prefill_visited_pages(p0: int, chunk: int, block_size: int) -> int:
    from ray_tpu.models.llama import PREFILL_KV_TILE as tile

    return math.ceil((p0 + chunk) / tile) * tile // block_size


def _reference_logits(cfg, params, tokens, first_row: int = 0):
    from ray_tpu.models.granite_hybrid_reference import reference_logits

    return reference_logits(cfg, params, tokens, first_row=first_row)


def _reference_slot_state(cfg, params, tokens, slot_state):
    from ray_tpu.models.granite_hybrid_reference import reference_state

    held = ssm_ops.unpack_state(
        jnp.asarray(slot_state["ssm"], jnp.float32), cfg.mamba_n_heads)
    return {"ssm": (held, reference_state(cfg, params, tokens))}


def _family():
    from ray_tpu.models.family import ModelFamily

    return ModelFamily(
        name="granite_hybrid", config_type=GraniteHybridConfig,
        init_params=init_params, init_paged_cache=init_paged_cache,
        rope_cache=_no_rope, prefill_chunk=prefill_chunk_paged,
        decode_step=decode_step_paged, kernel_supported=kernel_supported,
        prefill_visited_pages=_prefill_visited_pages,
        reference_logits=_reference_logits,
        init_slot_state=init_slot_state,
        reference_slot_state=_reference_slot_state)


FAMILY = _family()
