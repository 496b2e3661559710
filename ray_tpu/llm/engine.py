"""What every LLM engine program shares, and the engine factory.

The reference delegates serving to vLLM and reserves matching placement
groups (reference: llm/_internal/serve/deployments/llm/vllm/vllm_models.py
:177-186, :241-259).  Here the engine is framework-native and TPU-first;
there is one, ``PagedJaxLLMEngine`` (llm/paged.py).  This module holds
what its jitted programs and its constructor import:

  - the request record (`_Request`) and the stop-id / top-k widths the
    device state is padded to
  - sampling (greedy / temperature / top-k) with PER-SLOT traced
    parameters, run inside the jitted program so only sampled token ids
    cross the host boundary each step (`_sample`), and the distribution
    it draws from, which the speculative verifier needs (`_sample_dist`)
  - the `pipeline` x `tensor` mesh an engine shards over, and the
    pipeline-axis sharding of stacked params and KV pools
  - `make_engine`, the public constructor the serving layers call
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.llm.config import GenerationConfig, LLMConfig


# stop-token ids travel to the device as a fixed-width padded row per slot
_MAX_STOP_IDS = 8
# top-k sampling cap: the kth threshold comes from lax.top_k(logits, 64)
# instead of a full [B, V] sort.  On a v5e the top-64 over [64, V] is no
# small thing either: 0.2 ms + 10.9 ns a column (0.42 ms a token-step at
# V = 19,200, 0.56 at 32,768, 1.29 at 100,352, 1.94 at 163,840), and the
# categorical draw's noise and argmax 1.6 ns a column more (0.03 to 0.26 ms;
# traced decode programs, PERF.md section 5, PR 48), which is why both run
# only in a token-step some row of which asks for them (`_sampler_gates`)
_MAX_TOP_K = 64


@dataclasses.dataclass
class _Request:
    request_id: int
    prompt: List[int]
    gen: GenerationConfig
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    slot: int = -1
    error: Optional[str] = None


def _sampler_gates(temps, top_ks, live=None):
    """``(draws, top_k)``: whether some row draws its token (``temps`` > 0),
    and whether some row that draws asks for top-k — the two scalars the
    conditionals of ``_sample`` and ``_masked_scaled`` branch on.  ``live``
    [B] (> 0: the row decodes; None: every row does) keeps a slot whose
    request has left, and whose ``temps`` / ``top_ks`` are whatever that
    request asked, from holding either true.  A greedy row's top-k is
    never read, so it asks for none."""
    draws = temps > 0.0
    if live is not None:
        draws = draws & (live > 0)
    return draws.any(), (draws & (top_ks > 0)).any()


def _scaled(logits, temps):
    """Logits over their row's temperature.  temps <= 0 rows divide by 1.0
    (a benign placeholder — those rows are greedy and never read the scaled
    value; the old ``max(temps, 1e-6)`` scaled logits by 1e6, a needless
    overflow hazard on the never-used branch)."""
    return logits / jnp.where(temps > 0.0, temps, 1.0)[:, None]


def _kth_largest(scaled, top_ks):
    """[B, 1]: each row's ``top_ks``-th largest value, from a capped top-k
    (see _MAX_TOP_K)."""
    kmax = min(_MAX_TOP_K, scaled.shape[-1])
    topv, _ = jax.lax.top_k(scaled, kmax)
    idx = jnp.clip(top_ks - 1, 0, kmax - 1)
    return jnp.take_along_axis(topv, idx[:, None], axis=-1)


def _mask_below(scaled, top_ks, kth):
    return jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)


def _masked_scaled(logits, temps, top_ks, live=None):
    """Temperature-scaled, top-k-masked logits [B, V] — the categorical
    branch's pre-softmax shape, as the speculative verifier needs it
    (target/draft distributions MUST match what non-speculative sampling
    would draw from: ``_sample`` builds its own from the same three
    functions).

    The kth-largest value is computed only where ``_sampler_gates`` says a
    row asks for it: otherwise the threshold is -inf, under which the mask
    is the identity.  The conditional yields the [B, 1] threshold alone."""
    kth = jax.lax.cond(
        _sampler_gates(temps, top_ks, live)[1],
        # scaled again inside, from the head's own output: closing over the
        # scaled logits would make them a second [B, V] array to write
        lambda: _kth_largest(_scaled(logits, temps), top_ks),
        lambda: jnp.full((logits.shape[0], 1), -jnp.inf,
                         jnp.result_type(logits.dtype, temps.dtype)))
    return _mask_below(_scaled(logits, temps), top_ks, kth)


@jax.named_scope("sample")
def _sample(logits, key, temps, top_ks, live=None):
    """Sample [B] token ids from [B, V] logits with *per-slot* traced
    sampling params — one compiled program serves any mix of greedy /
    temperature / top-k callers sharing the decode batch, and pays for
    what its rows ask (``_sampler_gates``, ``live`` as there): a
    token-step whose rows are all greedy is one argmax; where some row
    draws, the scaling and the categorical draw come on top; only where
    such a row asks for top-k does the top-k over the vocabulary run.
    Each is a branch of ONE conditional that yields the [B] ids, so no
    [B, V] array leaves a branch and each fuses as straight-line code
    (two nested conditionals, the inner one yielding the threshold, read
    5 to 15% slower than the ungated formula on an all-top-k batch: the
    compiler moved the mask into the inner one and wrote it out).

    temps [B] float32 (<= 0 -> greedy); top_ks [B] int32 (<= 0 -> off).

    temperature <= 0 is EXACT argmax of the raw logits: no temperature
    scaling, no top-k perturbation, and no dependence on ``key`` (the
    categorical draw happens on the other branch of the select; greedy
    rows ignore it entirely) — the enabling precondition for speculative
    decoding's greedy bit-parity pin (tests/test_specdec.py).  A row that
    draws gets the draw of ``key`` over its own masked logits whatever its
    neighbours ask: the key is the caller's, split outside the conditional,
    and without a top-k of its own the mask is the identity for it.
    """
    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(masked):
        sampled = jax.random.categorical(key, masked, axis=-1)
        return jnp.where(temps <= 0.0, greedy(), sampled.astype(jnp.int32))

    def draw_top_k():
        scaled = _scaled(logits, temps)
        return draw(_mask_below(scaled, top_ks, _kth_largest(scaled, top_ks)))

    draws, top_k = _sampler_gates(temps, top_ks, live)
    return jax.lax.switch(
        draws.astype(jnp.int32) + top_k.astype(jnp.int32),
        (greedy, lambda: draw(_scaled(logits, temps)), draw_top_k))


def _sample_dist(logits, temps, top_ks, live=None):
    """The probability distribution [B, V] that ``_sample`` draws from:
    post temperature/top-k softmax for temps > 0 rows, an exact one-hot
    at the argmax for greedy rows.  The one-hot form makes speculative
    rejection sampling COLLAPSE to exact greedy verification — accept iff
    the draft token is the target argmax, corrections/bonus tokens are
    the argmax — with no separate greedy branch in the verifier."""
    probs = jax.nn.softmax(_masked_scaled(logits, temps, top_ks, live),
                           axis=-1)
    one_hot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                             dtype=probs.dtype)
    return jnp.where(temps[:, None] <= 0.0, one_hot, probs)


def build_tp_mesh(cfg, tp: int):
    return build_engine_mesh(cfg, tp, 1)


def build_engine_mesh(cfg, tp: int, pp: int, mesh=None):
    """Validate the TP × PP degrees and build a `pipeline`×`tensor` mesh.

    TP=PP=1 stays mesh-free (single-device fast path).  PP shards the
    STACKED layer dim of params and KV cache over `pipeline`
    (vllm_models.py:181-191 folds the degree into placement; here it is a
    real mesh axis): each stage holds L/pp layers' weights + cache — the
    way to serve a model whose layers don't fit one chip/slice.  The
    layer scan crosses stage boundaries with XLA-inserted transfers of the
    [B, D] activation (tiny for decode); stages run sequentially within
    one step — PP here buys MEMORY reach, microbatch overlap is the
    training path's job (parallel/pipeline.py).

    ``mesh`` (LLMConfig.mesh): a caller-built mesh pinning WHICH devices
    the replica shards over — validated against the degrees (axis sizes
    must match) and the model's divisibility, then used as-is."""
    if tp <= 1 and pp <= 1 and mesh is None:
        return None
    if mesh is not None:
        shape = dict(mesh.shape)
        if shape.get("tensor", 1) != max(tp, 1):
            raise ValueError(
                f"config.mesh tensor axis is {shape.get('tensor', 1)} but "
                f"tensor_parallel_size={tp} — the degrees must agree")
        if shape.get("pipeline", 1) != max(pp, 1):
            raise ValueError(
                f"config.mesh pipeline axis is {shape.get('pipeline', 1)} "
                f"but pipeline_parallel_size={pp}")
    devices = jax.devices()
    if mesh is None and len(devices) < tp * pp:
        raise ValueError(
            f"tensor_parallel_size={tp} x pipeline_parallel_size={pp} needs "
            f"{tp * pp} devices but only {len(devices)} visible device(s) — "
            f"an engine must never silently compute on fewer chips than it "
            f"reserves")
    if cfg.n_layers % max(pp, 1):
        raise ValueError(
            f"pipeline_parallel_size={pp} does not divide n_layers={cfg.n_layers}")
    if tp > 1:
        for name, dim in (("n_heads", cfg.n_heads),
                          ("n_kv_heads", cfg.n_kv_heads),
                          ("ffn_dim", cfg.ffn_dim),
                          ("vocab_size", cfg.vocab_size)):
            if dim % tp:
                raise ValueError(
                    f"tensor_parallel_size={tp} does not divide model "
                    f"{name}={dim}")
    if mesh is not None:
        return mesh
    from ray_tpu.parallel.mesh import MeshSpec

    return MeshSpec(pipeline=pp, tensor=tp).build(devices[:tp * pp])


def pp_param_specs(specs: dict, pp: int) -> dict:
    """Shard the stacked-layer dim of inference params over `pipeline`."""
    if pp <= 1:
        return specs
    from jax.sharding import PartitionSpec as P

    specs = dict(specs)
    specs["layers"] = jax.tree.map(
        lambda s: P(*(("pipeline",) + tuple(s)[1:])), specs["layers"],
        is_leaf=lambda x: isinstance(x, P))
    return specs


def pp_cache_spec(spec: dict, pp: int) -> dict:
    """KV caches/pools are [L, ...]: shard dim 0 over `pipeline` too."""
    if pp <= 1:
        return spec
    from jax.sharding import PartitionSpec as P

    return {k: P(*(("pipeline",) + tuple(s)[1:])) for k, s in spec.items()}


def make_engine(config: "LLMConfig", params=None, *, key=None,
                draft_params=None):
    """Build the serving engine for ``config``.

    ``draft_params``: params for ``config.speculative_config``'s draft
    model (None with speculation configured random-initializes the draft
    — fine for tests, acceptance-rate ~0 in prod).
    """
    from ray_tpu.llm.paged import PagedJaxLLMEngine

    return PagedJaxLLMEngine(config, params, key=key,
                             draft_params=draft_params)
