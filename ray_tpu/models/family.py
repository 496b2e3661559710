"""The family seam: what a model family supplies to the paged engine.

``PagedJaxLLMEngine`` (``llm/paged.py``) owns scheduling, the block manager,
the prefix cache, sampling and the two device programs' framing
(``_decode_chunk_impl`` / ``_prefill_chunk_impl``).  Everything that depends
on the architecture comes through one :class:`ModelFamily`: the parameters,
the paged cache's pytree, the per-chunk and per-token-step forward functions,
whether a decode kernel exists, and the plain float32 reference the served
tokens are held against.  ``LLMConfig.model_config``'s TYPE picks the family
(:func:`family_of`); no option names one.

The paged cache is a dict of arrays, every leaf ``[layers, blocks,
block_size, width]``: the engine copies, demotes, exports and imports it leaf
by leaf and never looks inside a block.  A Llama block is keys and values
(leaves ``k`` and ``v``); a latent-attention block is one leaf ``ckv``.

A family that leaves ``decode_window`` or ``param_specs`` empty has no
speculative verification window, or no tensor/pipeline-parallel layout: the
engine refuses such a configuration at construction and names the family.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    config_type: type
    # (cfg, key) -> params
    init_params: Callable
    # (cfg, num_blocks, block_size) -> {leaf: [L, NB, bs, width]}
    init_paged_cache: Callable
    # (cfg, max_seq) -> (cos, sin) device arrays the forward functions take
    rope_cache: Callable
    # (cfg, params, tokens [1, C], pool, table [1, W], p0, *, rope_cache,
    #  tp_plan[, use_kernel, kernel_interpret: where prefill_kernel_fits])
    #  -> (logits [1, C, V] f32, pool)
    prefill_chunk: Callable
    # (cfg, params, tokens [B], pool, table [B, W], lengths [B], *,
    #  rope_cache, use_kernel, mesh, kernel_interpret, tp_plan, active)
    #  -> (logits [B, V] f32, pool[, counters i32[len(decode_counters)]])
    decode_step: Callable
    # (cfg) -> bool: the decode kernel applies on this backend
    kernel_supported: Callable
    # (p0, chunk, block_size) -> pages the chunk's attention visits
    prefill_visited_pages: Callable
    # (cfg, params, tokens, first_row=0) -> float32 logits [S - first_row, V]
    reference_logits: Callable
    # (cfg) -> parameter PartitionSpecs over ("tensor",); None: one device
    param_specs: Optional[Callable] = None
    # () -> {leaf: PartitionSpec}; None with param_specs
    paged_cache_spec: Optional[Callable] = None
    # the speculative verification window (llama.decode_window_paged's
    # signature); None: no speculative decoding
    decode_window: Optional[Callable] = None
    # (cfg) -> bool: ``prefill_chunk``'s attention runs in a kernel of its
    # own where the decode kernel is on and the shapes fit; None: it has none
    prefill_kernel_fits: Optional[Callable] = None
    # engine counters a decode token-step books: names of the int32 vector
    # ``decode_step`` returns as its third value (summed over the chunk)
    decode_counters: Tuple[str, ...] = ()


def family_of(model_config: Any) -> ModelFamily:
    """The family whose config type ``model_config`` is an instance of."""
    from ray_tpu.models import llama, pangu_moe

    families = (llama.FAMILY, pangu_moe.FAMILY)
    for fam in families:
        if isinstance(model_config, fam.config_type):
            return fam
    raise TypeError(
        f"no model family serves a {type(model_config).__name__}: "
        f"LLMConfig.model_config is one of "
        f"{[f.config_type.__name__ for f in families]}")
