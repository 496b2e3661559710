"""Batch LLM inference over datasets: Processor + stages.

reference: python/ray/llm/_internal/batch/processor/ + stages/ — a
Processor turns a Dataset through preprocess -> engine inference ->
postprocess stages, with the engine stage running on an autoscaling actor
pool (one engine per actor, chips bound via the "TPU" resource).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

from ray_tpu.llm.config import GenerationConfig, LLMConfig


@dataclasses.dataclass
class ProcessorConfig:
    """reference analog: batch/processor config (concurrency + batch size)."""

    llm_config: LLMConfig = None
    batch_size: int = 8
    concurrency: int = 1
    max_new_tokens: int = 32
    temperature: float = 0.0


class _EngineStage:
    """Actor-pool stage: owns one engine (``make_engine``), maps prompt batches."""

    def __init__(self, llm_config: LLMConfig, max_new_tokens: int,
                 temperature: float):
        from ray_tpu.llm.engine import make_engine

        self._engine = make_engine(llm_config)
        self._gen = GenerationConfig(max_new_tokens=max_new_tokens,
                                     temperature=temperature)

    def __call__(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        prompts = [list(p) for p in batch["prompt_tokens"]]
        outs = self._engine.generate(prompts, self._gen)
        out = dict(batch)
        out["generated_tokens"] = outs
        return out


class Processor:
    """``processor(dataset) -> dataset`` (reference: batch/processor/base).

    Stages: optional row-wise preprocess -> engine map_batches on an actor
    pool -> optional row-wise postprocess.
    """

    def __init__(self, config: ProcessorConfig,
                 preprocess: Optional[Callable[[dict], dict]] = None,
                 postprocess: Optional[Callable[[dict], dict]] = None):
        if config.llm_config is None:
            raise ValueError("ProcessorConfig.llm_config is required")
        self.config = config
        self.preprocess = preprocess
        self.postprocess = postprocess

    def __call__(self, dataset):
        from ray_tpu.data.dataset import ActorPoolStrategy

        ds = dataset
        if self.preprocess is not None:
            ds = ds.map(self.preprocess)
        ds = ds.map_batches(
            _EngineStage,
            batch_size=self.config.batch_size,
            batch_format="pydict",
            compute=ActorPoolStrategy(size=self.config.concurrency),
            fn_constructor_args=(self.config.llm_config,
                                 self.config.max_new_tokens,
                                 self.config.temperature),
            resources=self.config.llm_config.resources_per_replica(),
        )
        if self.postprocess is not None:
            ds = ds.map(self.postprocess)
        return ds


def build_llm_processor(config: ProcessorConfig,
                        preprocess: Optional[Callable] = None,
                        postprocess: Optional[Callable] = None) -> Processor:
    """reference: ray.data.llm.build_llm_processor."""
    return Processor(config, preprocess, postprocess)
