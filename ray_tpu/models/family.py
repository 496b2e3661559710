"""The family seam: what a model family supplies to the paged engine.

``PagedJaxLLMEngine`` (``llm/paged.py``) owns scheduling, the block manager,
the prefix cache, sampling and the two device programs' framing.  Everything
that depends on the architecture comes through one :class:`ModelFamily`;
``LLMConfig.model_config``'s TYPE picks it (:func:`family_of`), no option
names one.

**Two kinds of state.**  The paged cache is a dict of arrays, every leaf
``[layers, blocks, block_size, width]`` (Llama: ``k`` and ``v``; latent
attention: ``ckv``): what grows with a sequence, a POSITION at a time.  The
engine copies, demotes, exports and imports it leaf by leaf and never looks
inside a block.  A family may also declare a **slot state**
(``init_slot_state``): a dict of arrays, every leaf ``[layers, max_batch,
...]``, one fixed-size value a sequence (a state-space layer's recurrent
state and its convolution's window; a window-attention layer's ring of its
sequence's last positions); a family without one has ``{}``.  For a
family with one the engine refuses a prefix hit whatever
``enable_prefix_caching`` says (no snapshot of the state exists at a block
boundary), rebuilds the state by recompute after a preemption and carries
the slot's leaves in ``export_request`` / ``import_request``.

**One signature.**  Both forward functions take and return the same things
for every family (the fields' comments).  A state a family does not have is
``{}`` and counters it does not book are None: empty pytrees, no parameter
and no result of the engine's programs.  A keyword a family has no use for
it ignores.  ``slot`` is the engine's slot of the chunk's sequence, ``take``
the chunk's count of REAL tokens (the rest of its bucket is padding and must
not advance the state); the state starts from zeros where ``p0 == 0`` (a
re-used slot is never cleared), and ``decode_step`` leaves a row with
``active == 0`` untouched: decode dispatches run between a sequence's prompt
chunks.
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
    #  tp_plan, use_kernel, kernel_interpret, slot_state, slot, take)
    #  -> (logits [1, C, V] f32, pool, slot_state)
    # ``use_kernel`` is the engine's choice of the decode kernel: the family
    # combines it with its own ``prefill_kernel_fits``
    prefill_chunk: Callable
    # (cfg, params, tokens [B], pool, table [B, W], lengths [B], *,
    #  rope_cache, use_kernel, mesh, kernel_interpret, tp_plan, active,
    #  slot_state)
    #  -> (logits [B, V] f32, pool, slot_state,
    #      counters i32[len(decode_counters)] or None)
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
    # (cfg, interpret) -> the chunk width from which on ``prefill_chunk``'s
    # expert layers multiply a token by the experts it chose alone (a grouped
    # product; ``interpret``: the engine runs kernels in the interpreter), or
    # None where they never do; None: the family has no expert layers
    prefill_grouped_from: Optional[Callable] = None
    # engine counters a decode token-step books: names of the int32 vector
    # ``decode_step`` returns as its fourth value (summed over the chunk)
    decode_counters: Tuple[str, ...] = ()
    # (cfg, max_batch) -> {leaf: [layers, max_batch, ...]}: the state a SLOT
    # holds (module docstring); None: the family's only state is the pool
    init_slot_state: Optional[Callable] = None
    # (cfg, params, tokens, slot_state: one slot's leaves) -> {leaf: (held,
    # reference)}, both float32 [layers, ...] in one layout: what the slot
    # holds beside what the plain float32 recurrence holds after ``tokens``,
    # for the leaves it defines
    reference_slot_state: Optional[Callable] = None


def family_of(model_config: Any) -> ModelFamily:
    """The family whose config type ``model_config`` is an instance of."""
    from ray_tpu.models import (
        granite_hybrid,
        kimi_linear,
        laguna,
        llama,
        pangu_moe,
    )

    families = (llama.FAMILY, pangu_moe.FAMILY, granite_hybrid.FAMILY,
                kimi_linear.FAMILY, laguna.FAMILY)
    for fam in families:
        if isinstance(model_config, fam.config_type):
            return fam
    raise TypeError(
        f"no model family serves a {type(model_config).__name__}: "
        f"LLMConfig.model_config is one of "
        f"{[f.config_type.__name__ for f in families]}")
