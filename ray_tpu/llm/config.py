"""LLM configs.

reference: python/ray/llm/_internal (LLMConfig, engine config). The
reference reads TP/PP degrees out of vLLM engine_kwargs
(serve/deployments/llm/vllm/vllm_models.py:177-186); here the engine is the
framework's own JAX engine and the degrees are mesh axes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass
class GenerationConfig:
    max_new_tokens: int = 64
    temperature: float = 0.0  # 0 → greedy
    top_k: int = 0  # 0 → disabled
    seed: int = 0
    stop_token_ids: tuple = ()


@dataclasses.dataclass
class SpeculativeConfig:
    """Draft-model speculative decoding.

    A small draft model proposes ``num_speculative_tokens`` tokens per
    slot per step; the target model batch-verifies all of them in ONE
    forward pass (standard rejection sampling at temperature > 0; exact
    longest-agreeing-prefix at temperature 0 — greedy output is
    bit-identical to non-speculative decode).  The draft's KV lives in
    its own block pool sharing the BlockManager machinery; draft-pool
    exhaustion degrades the affected request to non-speculative decode
    (zero drops).
    """

    # a models.llama.LlamaConfig for the draft model (same vocab as the
    # target; typically far fewer layers / smaller dim)
    draft_model_config: Any = None
    # k: drafted tokens verified per target forward (per slot per step).
    # Each step emits between 1 (all rejected) and k+1 (all accepted +
    # the bonus token) tokens per slot.
    num_speculative_tokens: int = 4
    # draft KV pool size in blocks; None → the target pool's block count
    # (draft blocks are much smaller — draft layers/kv dims)
    draft_num_blocks: Optional[int] = None
    # multi-LoRA extension: per-adapter draft choice.  Maps a serve
    # model id to overrides applied when that adapter's engine is built:
    #   {"enabled": False}              — this adapter decodes without
    #                                     speculation
    #   {"num_speculative_tokens": k}   — per-adapter k
    #   {"draft_adapter": <lora tree>}  — a LoRA adapter (llm/lora.py)
    #                                     merged into the DRAFT model for
    #                                     this id (draft tracks the tuned
    #                                     target, keeping acceptance up)
    per_adapter: Optional[Dict[str, Dict[str, Any]]] = None


@dataclasses.dataclass
class LLMConfig:
    """reference analog: llm/_internal LLMConfig + vLLM engine_kwargs."""

    # the config dataclass of a served model family; its TYPE picks the
    # family (models/family.py family_of): llama.LlamaConfig,
    # pangu_moe.PanguMoEConfig
    model_config: Any = None
    max_batch_size: int = 8
    # the decode quantum: token-steps one decode dispatch carries.  The
    # whole chunk is ONE device program with stop/budget handling
    # in-program, and step() pipelines at every value (it dispatches chunk
    # N+1, then reads chunk N), so the host's work a step hides behind the
    # device as long as one chunk outlasts it.  What the quantum trades: a
    # request that arrives waits for the chunk in flight and the one queued
    # behind it before any of its own work runs, and for one more per
    # further prompt chunk, so time to first token counts quanta; against
    # that, whatever a dispatch costs beyond its token-steps is paid once a
    # quantum, and a model whose token-step is shorter than the host's step
    # needs a longer quantum to keep the device fed.  The measured curve
    # (ms a token-step at 1, 2, 4, 8) is in PERF.md section 6, PR 30.
    decode_chunk: int = 2
    max_seq_len: Optional[int] = None  # default: model_config.max_seq_len
    # --- KV cache layout (reference capability boundary: paged attention /
    # chunked prefill / prefix caching come from vLLM engine_kwargs,
    # vllm_models.py:177-186; here the engine provides them natively) ---
    # a pool of `num_blocks` blocks of `block_size` positions: HBM ∝ actual
    # request lengths, memory-based admission, chunked prefill, prefix
    # caching.
    block_size: int = 16
    # pool size in blocks; None → max_batch_size x max_seq_len / 2
    # positions (every slot at half its full length)
    num_blocks: Optional[int] = None
    # prompt tokens prefilled per step (multiple of block_size); long
    # prompts interleave with decode instead of stalling it
    prefill_chunk: int = 256
    # prompt tokens the engine may prefill per STEP across all slots (the
    # vLLM max_num_batched_tokens analog): chunked-prefill scheduling
    # interleaves bounded prefill chunks with decode steps under this
    # budget, so a long prompt cannot starve decode ITL inside continuous
    # batching. None = prefill_chunk (one chunk's worth). Raise for
    # burst-arrival serving: a 32-client burst otherwise ramps one chunk
    # per step, serializing admission.
    prefill_token_budget: Optional[int] = None
    # draft-model speculative decoding (see SpeculativeConfig). None
    # disables — the disabled path is untouched: no draft pool, no extra
    # device programs, no metrics booked.
    speculative_config: Optional[SpeculativeConfig] = None
    enable_prefix_caching: bool = True
    # --- tiered prefix cache ---
    # host-RAM tier under the HBM chain-hash pool: full prompt blocks
    # evicted from HBM under pressure demote here (one small device
    # readback per eviction) and revive without recompute on a later
    # match.  0 disables the tier ladder entirely.
    host_kv_cache_bytes: int = 64 * 1024**2
    # third tier: blocks evicted from host RAM spill to the plasma object
    # store (cluster-visible, survives engine HBM churn), capped at this
    # many blocks.  0 (default) disables; requires an initialized ray_tpu
    # worker — without one the host tier simply drops its evictions.
    plasma_kv_cache_blocks: int = 0
    # True -> the family's pallas TPU decode-attention kernel (where its
    # kernel_supported holds, pp == 1). None = auto: ON where supported. Its
    # time follows the decoding rows' live pages (0.10 ms a layer-call at 20
    # rows of 450 tokens in a 64 x 128 table, v5e; PERF.md, PR 25); against
    # the XLA block-gather it has not been measured on this round's code
    # (chip_smoke.py shows it selected and agreeing with the float32
    # reference). True forces it (raises
    # off-TPU); False forces the gather path; "interpret" is a test hook
    # that runs the kernel in pallas interpret mode off-TPU.
    paged_attention_kernel: Optional[Any] = None
    # parallelism degrees (mesh axes; the vllm_models.py:177-186 analog —
    # pipeline degree folded into placement sizing per vllm_models.py:181-191)
    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    # pre-built jax.sharding.Mesh override for the engine.  None (default)
    # builds one from tensor/pipeline_parallel_size over the first visible
    # devices; pass a mesh to pin WHICH devices a replica shards over
    # (e.g. a placement-group slice).  Must carry a "tensor" axis of size
    # tensor_parallel_size (and "pipeline" of pipeline_parallel_size).
    mesh: Optional[Any] = None
    # --- tensor-parallel collective routing (tp > 1) ---
    # route the per-layer decode allreduces through the α-β collective
    # planner as EXPLICIT shard_map programs (flat psum / ring / tree
    # chosen per message size and link class, decision metered into
    # ray_tpu_collective_plan_total).  False = GSPMD's implicit psum
    # (identical numerics for flat/ring; no plan metrics, no overlap).
    tp_planned_collectives: bool = True
    # chain each planned collective through lax.optimization_barrier so
    # XLA's scheduler overlaps it with the next layer's compute (identity
    # numerics — the A/B is bit-equal; same mechanism as make_train_step's
    # bucketed gradient sync).  Only meaningful with planned collectives.
    tp_overlap_collectives: bool = True
    # force one algorithm ("flat" | "ring" | "tree") instead of planning —
    # a test/bench hook; None = plan per message size.
    tp_collective_algorithm: Optional[str] = None
    # serving
    num_replicas: int = 1
    chips_per_replica: Optional[int] = None

    def resources_per_replica(self) -> Dict[str, float]:
        chips = self.chips_per_replica
        if chips is None:
            chips = (self.tensor_parallel_size * self.pipeline_parallel_size
                     * self.data_parallel_size)
            # a one-chip replica holds its chip through the lease wherever
            # there are chips: the lease is what binds TPU_VISIBLE_CHIPS,
            # and without it four such replicas on a four-chip host would
            # each open all four.  Where there is no chip (the CPU lanes)
            # it asks for none and still schedules.
            if chips == 1 and not _cluster_has_tpu():
                chips = 0
        res: Dict[str, float] = {"CPU": 1.0}
        if chips > 0:
            res["TPU"] = float(chips)
        return res


def _cluster_has_tpu() -> bool:
    """Chips the connected cluster advertises; this machine's own (device
    files, no JAX) when no cluster is connected yet."""
    import ray_tpu

    if ray_tpu.is_initialized():
        return ray_tpu.cluster_resources().get("TPU", 0) > 0
    from ray_tpu._private.accelerators import get_accelerator_manager

    return get_accelerator_manager(
        "TPU").get_current_node_num_accelerators() > 0
