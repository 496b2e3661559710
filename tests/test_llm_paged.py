"""Paged KV cache engine: block manager, prefix caching, memory-based
admission, preemption.  (Token parity with the full forward, over decode
and prefill chunk sizes, runs on every commit: tests/test_llm_engine_parity.py.)

reference capability boundary: paged attention / chunked prefill / prefix
caching arrive via vLLM engine_kwargs (llm/_internal/serve/deployments/llm/
vllm/vllm_models.py:177-186); here they are native (ray_tpu/llm/paged.py).
"""

import jax
import numpy as np
import pytest

from ray_tpu.llm import (
    BlockManager,
    GenerationConfig,
    LLMConfig,
    PagedJaxLLMEngine,
    make_engine,
)
from ray_tpu.models.llama import LlamaConfig, init_params

pytestmark = pytest.mark.slow  # module lane: see pytest.ini


@pytest.fixture(scope="module")
def tiny_cfg():
    # fp32 end to end: token-identity between cache layouts must not hinge
    # on bf16 rounding order
    return LlamaConfig.tiny(compute_dtype=jax.numpy.float32)


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0))


def _gen(**kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationConfig(**kw)


# -- block manager (host-side, no device) -----------------------------------


def test_block_manager_alloc_release():
    bm = BlockManager(num_blocks=8, block_size=4)
    assert bm.num_free() == 7  # block 0 is the scatter sink
    a = bm.alloc(3)
    assert len(a) == 3 and 0 not in a
    assert bm.alloc(5) is None  # only 4 left
    bm.release(a)
    assert bm.num_free() == 7


def test_block_manager_prefix_match_and_revive():
    bm = BlockManager(num_blocks=16, block_size=4)
    prompt = list(range(1, 13))  # 3 full blocks
    blocks = bm.alloc(3)
    bm.register(prompt, blocks)
    # never matches the whole prompt: the last token must be recomputed
    ids, n = bm.match_prefix(prompt)
    assert n == 8 and ids == blocks[:2]
    bm.release(ids)
    # a longer prompt sharing the prefix matches all 3 registered blocks
    ids2, n2 = bm.match_prefix(prompt + [99] * 4)
    assert n2 == 12 and ids2 == blocks
    bm.release(ids2)
    # release the owner: blocks become free but stay cached (revivable)
    bm.release(blocks)
    free_before = bm.num_free()
    ids3, n3 = bm.match_prefix(prompt + [1])
    assert n3 == 12 and bm.num_free() == free_before - 3  # revived
    bm.release(ids3)
    # allocating everything repurposes cached blocks and drops their hashes
    all_blocks = bm.alloc(bm.num_free())
    assert bm.match_prefix(prompt + [1]) == ([], 0)
    bm.release(all_blocks)


# -- scheduling, prefix cache, admission, preemption -------------------------


def test_prefill_completes_while_decode_pipelines(tiny_cfg, tiny_params):
    """Regression (ADVICE r5 high, paged.py step() _dirty path): request B's
    final prefill chunk sets _dirty while request A has an IN-FLIGHT decode
    chunk.  The drain that follows advances A's lengths and trims A's blocks
    back to lengths+1 coverage — invalidating the margin the earlier ensure
    pass reserved.  Without re-running _ensure_decode_blocks_locked after
    the drain, A's next chunk dispatches with an under-sized table and any
    append crossing a block boundary scatters KV into sink block 0: silent
    KV loss, diverging tokens.  Greedy token parity with solo runs is the
    oracle."""
    def make():
        # block_size == decode_chunk == 4: every decode chunk crosses a
        # block boundary, so stale coverage cannot hide
        return PagedJaxLLMEngine(
            LLMConfig(model_config=tiny_cfg, max_batch_size=2,
                      max_seq_len=128, block_size=4, prefill_chunk=8,
                      decode_chunk=4), params=tiny_params)

    pa = list(np.random.RandomState(11).randint(1, 255, size=7))
    pb = list(np.random.RandomState(12).randint(1, 255, size=5))
    ref = make()
    want_a = ref.generate([pa], _gen(max_new_tokens=24))[0]
    want_b = ref.generate([pb], _gen(max_new_tokens=24))[0]

    eng = make()
    out = {}

    def drain_into(emitted):
        for rid, toks in emitted.items():
            out.setdefault(rid, []).extend(toks)

    ra = eng.add_request(pa, _gen(max_new_tokens=24))
    for _ in range(4):  # A prefills, then reaches pipelined steady state
        drain_into(eng.step())
    assert eng._inflight is not None  # the scenario requires pipelining
    rb = eng.add_request(pb, _gen(max_new_tokens=24))
    while eng.has_work():
        drain_into(eng.step())
    drain_into(eng.flush())
    assert out[ra] == want_a
    assert out[rb] == want_b


def test_prefix_cache_reuse(tiny_cfg, tiny_params):
    """A second request sharing a long prompt prefix skips prefill for the
    shared full blocks and still decodes the same tokens."""
    base = list(np.random.RandomState(9).randint(1, 255, size=32))
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=2, max_seq_len=128,
                  block_size=8, prefill_chunk=16), params=tiny_params)
    first = eng.generate([base], _gen(max_new_tokens=4))[0]
    # the finished request's full prompt blocks stayed hash-registered
    ids, n = eng.blocks.match_prefix(base)
    eng.blocks.release(ids)
    # 32 tokens, bs=8 -> match limit is (32-1)//8 = 3 blocks = 24 tokens
    assert n == 24
    # identical prompt again decodes identically through the shared path
    again = eng.generate([base], _gen(max_new_tokens=4))[0]
    assert again == first


def test_memory_based_admission_not_slot_count(tiny_cfg, tiny_params):
    """With a pool too small for all requests at once, admission is governed
    by free blocks: requests queue and complete as blocks free up."""
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=8, max_seq_len=128,
                  block_size=8, prefill_chunk=16, num_blocks=12,
                  enable_prefix_caching=False), params=tiny_params)
    prompts = [list(np.random.RandomState(s).randint(1, 255, size=20))
               for s in range(6)]
    outs = eng.generate(prompts, _gen(max_new_tokens=6))
    assert all(len(o) == 6 for o in outs)
    # pool: 11 usable blocks; each request needs ceil(26/8)+1 ~ 5 blocks, so
    # 6 requests could never be resident at once — admission had to wait
    assert eng.blocks.num_free() == 11


def test_preemption_recompute(tiny_cfg, tiny_params, greedy_reference):
    """When the pool runs dry mid-decode, the youngest request is evicted
    and recomputed — every request still finishes with full output and no
    token is ever re-emitted.  Streams match the full forward exactly up
    to each request's last preemption point; beyond it, recompute rewrites
    the victim's KV via chunked prefill whose reduction order differs in
    the last ulp from decode-written KV, so a later near-tie logit may
    legitimately flip (same recompute caveat as vLLM)."""
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=128,
                  block_size=8, prefill_chunk=16, num_blocks=14,
                  decode_chunk=4, enable_prefix_caching=False),
        params=tiny_params)
    prompts = [list(np.random.RandomState(s).randint(1, 255, size=16))
               for s in range(3)]
    want = greedy_reference(tiny_cfg, tiny_params, prompts, 40)

    preempted_at: dict = {}  # request_id -> emitted count at last eviction
    orig = eng._preempt_locked

    def spy(exclude_slot=-1):
        before = {r.request_id: len(r.out_tokens)
                  for r in eng._requests.values()}
        if orig(exclude_slot):
            victim = eng._pending[0]  # evicted requests requeue at the front
            preempted_at[victim.request_id] = before[victim.request_id]
            return True
        return False

    eng._preempt_locked = spy
    got = eng.generate(prompts, _gen(max_new_tokens=40))
    assert preempted_at, "pool was large enough that nothing preempted"
    assert all(len(o) == 40 for o in got)
    for i, (g, w) in enumerate(zip(got, want)):
        cut = preempted_at.get(i + 1, 40)  # request ids are 1-based
        assert g[:cut] == w[:cut], f"request {i} diverged BEFORE preemption"
    # non-preempted requests must match the reference exactly
    for i, (g, w) in enumerate(zip(got, want)):
        if (i + 1) not in preempted_at:
            assert g == w, f"non-preempted request {i} diverged"
    assert eng.blocks.num_free() == 13  # everything returned


def test_paged_hbm_economics(tiny_cfg):
    """The default pool holds half of max_batch x max_seq positions, and
    a batch of short requests fits easily."""
    cfg = LLMConfig(model_config=tiny_cfg, max_batch_size=32, max_seq_len=128)
    eng = make_engine(cfg)
    assert isinstance(eng, PagedJaxLLMEngine)
    pool_tokens = eng.num_blocks * eng.bs
    assert pool_tokens <= 32 * 128 // 2
    prompts = [[i + 1, i + 2, i + 3] for i in range(32)]
    outs = eng.generate(prompts, _gen(max_new_tokens=4))
    assert all(len(o) == 4 for o in outs)


def test_make_engine_factory(tiny_cfg):
    with pytest.raises(ValueError, match="multiple"):
        make_engine(LLMConfig(model_config=tiny_cfg, block_size=16,
                              prefill_chunk=24))


def test_prefill_table_width_covers_chunk_overhang(tiny_cfg, tiny_params):
    """Regression (ISSUE 2 satellite): the fixed prefill table width must
    cover the pow2 chunk bucket's overshoot.  At max_seq=992, bs=16,
    chunk=256, a plen=897 prompt's final chunk (pos=768) buckets to 256
    tokens and covers 65 blocks — past the old width
    bucket_pow2(max_blocks_per_seq + 2) = 64, which raised a broadcast
    ValueError mid-serve at the table-row write."""
    from ray_tpu.llm.paged import (
        _bucket_pow2,
        _prefill_plan,
        _prefill_table_width,
    )

    # the failing geometry, arithmetically: plan says 65 slots (cover+1),
    # the old formula provided 64 (max_blocks_per_seq = ceil(992/16) = 62)
    old_width = _bucket_pow2(62 + 2)
    assert _prefill_plan(897, 0, 256, 16) + 1 > old_width
    assert _prefill_table_width(992, 256, 16) >= _prefill_plan(897, 0, 256, 16) + 1

    # end to end at the failing geometry: generation must not raise
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=1, max_seq_len=992,
                  block_size=16, prefill_chunk=256, num_blocks=96,
                  enable_prefix_caching=False), params=tiny_params)
    prompt = list(np.random.RandomState(0).randint(1, 255, size=897))
    outs = eng.generate([prompt], _gen(max_new_tokens=2))
    assert len(outs[0]) == 2


def test_oversized_request_rejected(tiny_cfg, tiny_params):
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=2, max_seq_len=128,
                  block_size=8, num_blocks=4), params=tiny_params)
    with pytest.raises(ValueError, match="blocks"):
        eng.add_request(list(range(1, 60)), _gen(max_new_tokens=60))


def test_block_manager_evicts_cached_last():
    """Allocation drains plain free blocks before repurposing cached
    (prefix-registered) ones — LRU-preserving allocation, so cache entries
    die only under real pressure (the vLLM free-list policy)."""
    bm = BlockManager(num_blocks=10, block_size=4)
    prompt = list(range(1, 9))  # 2 full blocks
    owned = bm.alloc(2)
    bm.register(prompt, owned)
    bm.release(owned)  # cached-free now
    # plenty of plain free blocks remain: allocs must not touch the cache
    taken = bm.alloc(7)
    ids, n = bm.match_prefix(prompt + [99])
    assert n == 8, "cached blocks were repurposed despite plain free ones"
    bm.release(ids)
    bm.release(taken)
    # under REAL pressure the cached blocks are evictable
    everything = bm.alloc(9)
    assert everything is not None and bm.match_prefix(prompt + [99]) == ([], 0)
    bm.release(everything)
