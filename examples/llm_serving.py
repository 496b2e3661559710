"""LLM serving: the paged engine end to end.

Paged KV cache (HBM proportional to actual request lengths), chunked
prefill, prefix caching, and memory-based admission — the serving
economics the reference gets by delegating to vLLM, native here
(ray_tpu/llm/paged.py).
"""

import jax.numpy as jnp

from ray_tpu.llm import GenerationConfig, LLMConfig, make_engine
from ray_tpu.models.llama import LlamaConfig


def main():
    cfg = LLMConfig(
        model_config=LlamaConfig.tiny(compute_dtype=jnp.float32),
        max_batch_size=4, max_seq_len=128,
        block_size=8, prefill_chunk=16, enable_prefix_caching=True)
    engine = make_engine(cfg)

    shared_prefix = list(range(1, 33))  # 32 tokens, 3 full blocks shareable
    prompts = [shared_prefix + [100 + i] for i in range(4)]
    outs = engine.generate(prompts, GenerationConfig(max_new_tokens=8))
    assert all(len(o) == 8 for o in outs)

    # the second wave shares the prompt prefix: its full blocks are served
    # from the prefix cache instead of being re-prefilled
    matched, n = engine.blocks.match_prefix(shared_prefix + [999])
    engine.blocks.release(matched)
    assert n == 32, n  # all 4 full prefix blocks are shared
    again = engine.generate(prompts, GenerationConfig(max_new_tokens=8))
    assert again == outs  # identical through the shared path

    print(f"paged serving OK: {len(outs)} requests, "
          f"{engine.blocks.num_free()} free blocks after drain, "
          f"prefix cache covered {n} tokens")
    print("OK: llm_serving")


if __name__ == "__main__":
    main()
