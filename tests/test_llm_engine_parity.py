"""The serving engine against the model itself: greedy tokens equal argmax
over a full re-run of ``llama.forward`` (conftest's ``greedy_reference``),
over the decode and prefill chunk sizes that change how the engine cuts the
same arithmetic into programs.  Tier-1 lane: runs on every commit.
"""

import jax
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig, make_engine
from ray_tpu.models.llama import LlamaConfig, init_params


@pytest.fixture(scope="module")
def tiny_cfg():
    # fp32 end to end: token identity must not hinge on bf16 rounding order
    return LlamaConfig.tiny(compute_dtype=jax.numpy.float32)


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("decode_chunk", [1, 4, 8])
def test_paged_matches_reference(tiny_cfg, tiny_params, greedy_reference,
                                 decode_chunk):
    """Same params, same prompts, greedy: the paged gather/scatter, the
    chunked prefill and the multi-step decode scan are data-movement and
    scheduling changes, not math changes."""
    prompts = [list(np.random.RandomState(s).randint(1, 255, size=n))
               for s, n in [(0, 7), (1, 19), (2, 33), (3, 4)]]
    paged = make_engine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=128,
                  block_size=8, prefill_chunk=16, decode_chunk=decode_chunk),
        params=tiny_params)
    got = paged.generate(prompts, GenerationConfig(max_new_tokens=10))
    assert got == greedy_reference(tiny_cfg, tiny_params, prompts, 10)


@pytest.mark.parametrize("prefill_chunk", [16, 32])
def test_chunked_prefill_long_prompt(tiny_cfg, tiny_params, greedy_reference,
                                     prefill_chunk):
    """A prompt longer than prefill_chunk accretes over multiple steps and
    still matches the full forward."""
    prompt = list(np.random.RandomState(7).randint(1, 255, size=70))
    paged = make_engine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=2, max_seq_len=128,
                  block_size=8, prefill_chunk=prefill_chunk),
        params=tiny_params)
    got = paged.generate([prompt], GenerationConfig(max_new_tokens=6))
    assert got == greedy_reference(tiny_cfg, tiny_params, [prompt], 6)
    # prefill really was chunked: 70 tokens take 5 chunks of 16, 3 of 32
    assert paged.counters()["prefill_chunks"] == -(-70 // prefill_chunk)


@pytest.mark.parametrize("removed", ["kv_cache", "prefill_budget_tokens"])
def test_removed_options_raise(removed):
    """PR 29 removed both fields: there is one engine, and one name for the
    prefill budget (``prefill_token_budget``)."""
    with pytest.raises(TypeError, match=removed):
        LLMConfig(**{removed: None})
