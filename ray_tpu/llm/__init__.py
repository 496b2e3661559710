"""LLM toolkit: batch inference + serving on the framework's JAX engine.

reference: python/ray/llm/ (~20.8k LoC) — batch Processor/stages and
LLMServer deployments on vLLM.  Here the engine is framework-native:
KV-cache decode with continuous batching, jitted prefill/decode, mesh-based
parallelism degrees.  One engine, built by ``make_engine``:
PagedJaxLLMEngine (block-pool KV, chunked prefill, prefix caching).
"""

from ray_tpu.llm.batch import Processor, ProcessorConfig, build_llm_processor
from ray_tpu.llm.config import GenerationConfig, LLMConfig, SpeculativeConfig
from ray_tpu.llm.disagg import (
    DecodeServer,
    DisaggLLMServer,
    PrefillServer,
    build_disagg_llm_deployment,
)
from ray_tpu.llm.engine import make_engine
from ray_tpu.llm.paged import BlockAllocator, BlockManager, PagedJaxLLMEngine
from ray_tpu.llm.lora import LoRAConfig, LoRAManager, init_lora, merge_lora
from ray_tpu.llm.openai_api import ByteTokenizer, OpenAICompatServer, build_openai_app
from ray_tpu.llm.serve import LLMServer, build_llm_deployment

__all__ = [
    "BlockAllocator",
    "BlockManager",
    "DecodeServer",
    "DisaggLLMServer",
    "PrefillServer",
    "build_disagg_llm_deployment",
    "GenerationConfig",
    "LLMConfig",
    "PagedJaxLLMEngine",
    "make_engine",
    "LLMServer",
    "LoRAConfig",
    "LoRAManager",
    "init_lora",
    "merge_lora",
    "Processor",
    "ProcessorConfig",
    "SpeculativeConfig",
    "build_llm_deployment",
    "build_openai_app",
    "OpenAICompatServer",
    "ByteTokenizer",
    "build_llm_processor",
]
