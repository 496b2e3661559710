"""The decode quantum (``LLMConfig.decode_chunk``: token-steps a decode
dispatch carries) changes when the host looks, never what the model
computes: greedy tokens are the same at every quantum, through a join, a
finish inside a chunk and a preemption; a request that joins a decoding
batch waits a bounded number of token-steps, counted and never timed; and
``warmup()`` compiles the same programs by count whatever the quantum is.
Tier-1 lane: runs on every commit.
"""

import jax
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
from ray_tpu.models.llama import LlamaConfig, init_params


@pytest.fixture(scope="module")
def tiny_cfg():
    # fp32 end to end: token identity must not hinge on bf16 rounding order
    return LlamaConfig.tiny(compute_dtype=jax.numpy.float32,
                            max_seq_len=4096)


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0))


def _prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 255, n)]


def _run_until_done(eng, out):
    while eng.has_work():
        for rid, toks in eng.step().items():
            out[rid].extend(toks)
    for rid, toks in eng.flush().items():
        out[rid].extend(toks)


@pytest.mark.parametrize("decode_chunk", [1, 2, 8])
def test_greedy_tokens_do_not_depend_on_the_quantum(
        tiny_cfg, tiny_params, greedy_reference, decode_chunk):
    """Two requests decode, a third joins them mid-stream, one stops inside
    a chunk (12 tokens: 11 decode token-steps, odd), and the pool is too
    small for the two long ones, so the younger is preempted and recomputed:
    every request's tokens are the full forward's argmax at each quantum."""
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=128,
                  block_size=8, prefill_chunk=16, num_blocks=14,
                  decode_chunk=decode_chunk, enable_prefix_caching=False),
        params=tiny_params)
    prompts = [_prompt(0, 16), _prompt(1, 16), _prompt(2, 19)]
    asked = [40, 12, 40]
    rids = [eng.add_request(p, GenerationConfig(max_new_tokens=n))
            for p, n in zip(prompts[:2], asked)]
    out = {rid: [] for rid in rids}
    while len(out[rids[0]]) < 3:  # both decode before the third arrives
        for rid, toks in eng.step().items():
            out[rid].extend(toks)
    assert eng.counters()["decode_dispatches"] >= 1 and out[rids[1]]
    rids.append(eng.add_request(prompts[2],
                                GenerationConfig(max_new_tokens=asked[2])))
    out[rids[2]] = []
    _run_until_done(eng, out)
    c = eng.counters()
    assert c["preemptions"] >= 1, "the pool held both long requests"
    assert c["decode_token_steps"] == c["decode_dispatches"] * decode_chunk
    want = greedy_reference(tiny_cfg, tiny_params, prompts, max(asked))
    for rid, w, n in zip(rids, want, asked):
        assert out[rid] == w[:n], (decode_chunk, rid)
    assert eng.blocks.num_free() == 13  # everything returned


@pytest.mark.parametrize("prompt_len", [40, 300])
def test_first_token_waits_a_bounded_number_of_token_steps(
        tiny_cfg, tiny_params, prompt_len):
    """The default configuration, 4 slots in steady pipelined decode: a
    request added between two steps gets its first token after one step per
    prompt chunk, so after at most ``2 * decode_chunk`` further token-steps
    plus one dispatch for each chunk beyond its first."""
    cfg = LLMConfig(model_config=tiny_cfg, max_batch_size=8,
                    max_seq_len=1024, num_blocks=400)  # no decode_chunk named
    eng = PagedJaxLLMEngine(cfg, params=tiny_params)
    gen = GenerationConfig(max_new_tokens=200)
    for s in range(4):
        eng.add_request(_prompt(s, 24), gen)
    for _ in range(8):  # four prompt chunks, then steady decode
        eng.step()
    before = eng.counters()
    eng.step()
    c0 = eng.counters()
    assert (c0["decode_dispatches_pipelined"]
            - before["decode_dispatches_pipelined"]) == 1
    rid = eng.add_request(_prompt(9, prompt_len), gen)
    steps = 0
    while rid not in eng.step():
        steps += 1
        assert steps < 16
    c1 = eng.counters()
    chunks = -(-prompt_len // cfg.prefill_chunk)
    assert c1["prefill_chunks"] - c0["prefill_chunks"] == chunks
    waited = c1["decode_token_steps"] - c0["decode_token_steps"]
    assert waited <= (2 + chunks - 1) * cfg.decode_chunk, (waited, chunks)
    # and the quantum's own meaning: a dispatch carries that many steps
    assert waited == (c1["decode_dispatches"]
                      - c0["decode_dispatches"]) * cfg.decode_chunk


def test_warmup_compiles_the_same_programs_by_count(tiny_cfg, tiny_params):
    """The serving cell's geometry (4,096 positions in 16-token blocks,
    256-token chunks) at the default quantum: nine decode table widths and
    five prefill chunks, as at every quantum before, and the one join; a
    served batch that joins, grows across table widths and finishes
    compiles nothing more."""
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=4096,
                  num_blocks=600), params=tiny_params)
    eng.warmup()
    rep = eng.warmup_report
    assert rep["decode_table_widths"] == [1, 2, 4, 8, 16, 32, 64, 128, 256]
    assert rep["prefill_chunks"] == [16, 32, 64, 128, 256]
    assert eng._decode._cache_size() == 9
    assert eng._prefill_chunk._cache_size() == 5
    assert eng._join._cache_size() == 1
    out = eng.generate([_prompt(0, 20), _prompt(1, 300), _prompt(2, 70)],
                       GenerationConfig(max_new_tokens=40))
    assert [len(o) for o in out] == [40, 40, 40]
    assert eng.counters()["compiles"] == 0
    assert eng.counters()["decode_joins"] == 3
    assert eng._decode._cache_size() == 9
    assert eng._join._cache_size() == 1  # rows entered and left through it
