"""The family seam: what a model family supplies to the paged engine.

``PagedJaxLLMEngine`` (``llm/paged.py``) owns scheduling, the block manager,
the prefix cache, sampling and the two device programs' framing
(``_decode_chunk_impl`` / ``_prefill_chunk_impl``).  Everything that depends
on the architecture comes through one :class:`ModelFamily`: the parameters,
the paged cache's pytree, the per-chunk and per-token-step forward functions,
whether a decode kernel exists, and the plain float32 reference the served
tokens are held against.  ``LLMConfig.model_config``'s TYPE picks the family
(:func:`family_of`); no option names one.

**Two kinds of state.**  The paged cache is a dict of arrays, every leaf
``[layers, blocks, block_size, width]``: the engine copies, demotes, exports
and imports it leaf by leaf and never looks inside a block.  A Llama block is
keys and values (leaves ``k`` and ``v``); a latent-attention block is one leaf
``ckv``.  It holds what grows with a sequence, a POSITION at a time.

A family may also declare a **slot state** (``init_slot_state``): a dict of
arrays, every leaf ``[layers, max_batch, ...]``, one fixed-size value a
sequence that does not page (a state-space layer's recurrent state and its
convolution's window).  The engine owns it beside the pool and gives it to
both forward functions: ``prefill_chunk`` also takes ``slot_state``, ``slot``
(the engine's slot of the sequence) and ``take`` (the chunk's count of REAL
tokens: the rest of its power-of-two bucket is padding and must not advance
the state), starts from zeros where ``p0 == 0`` (a re-used slot is never
cleared) and returns the state as its third value; ``decode_step`` also takes
``slot_state`` and returns it third, and must leave a row with ``active ==
0`` untouched, because decode dispatches run between a sequence's prompt
chunks.  For such a family the engine refuses a prefix hit whatever
``enable_prefix_caching`` says (no snapshot of the state exists at a block
boundary), rebuilds the state by recompute after a preemption, and carries
the slot's leaves in ``export_request`` / ``import_request``.  Where the
family also gives ``reference_slot_state``, ``LLMServer.reference_state_check``
holds a live slot's leaves against the plain float32 recurrence, as
``reference_check`` holds served tokens against ``reference_logits``.

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
    #  tp_plan[, use_kernel, kernel_interpret: where prefill_kernel_fits]
    #  [, slot_state, slot, take: where init_slot_state])
    #  -> (logits [1, C, V] f32, pool[, slot_state])
    prefill_chunk: Callable
    # (cfg, params, tokens [B], pool, table [B, W], lengths [B], *,
    #  rope_cache, use_kernel, mesh, kernel_interpret, tp_plan, active
    #  [, slot_state: where init_slot_state])
    #  -> (logits [B, V] f32, pool[, slot_state]
    #      [, counters i32[len(decode_counters)]])
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
    # ``decode_step`` returns as its third value (summed over the chunk)
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
    from ray_tpu.models import granite_hybrid, kimi_linear, llama, pangu_moe

    families = (llama.FAMILY, pangu_moe.FAMILY, granite_hybrid.FAMILY,
                kimi_linear.FAMILY)
    for fam in families:
        if isinstance(model_config, fam.config_type):
            return fam
    raise TypeError(
        f"no model family serves a {type(model_config).__name__}: "
        f"LLMConfig.model_config is one of "
        f"{[f.config_type.__name__ for f in families]}")
