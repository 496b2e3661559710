"""The paged prefill chunk's attention follows the live prefix (p0 + C): a
device loop over KV tiles of the block table with a dynamic trip count
(``models/llama.py _prefill_attend_tiles``), not a gather and a softmax over
the table's whole fixed width.

The judge throughout is ``models/llama_reference.py`` (plain float32, no
scan, no cache), the one the benchmark's ``correct`` uses.  The chunk-level
tests pass a small ``kv_tile`` so that a tiny model runs several tiles; the
engine-level tests run the tile the engine serves with.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.config import GenerationConfig, LLMConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import llama, llama_reference

BS, TILE, MAX_SEQ, NB = 16, 64, 512, 48
WIDTH = MAX_SEQ // BS + 1     # 33 entries: not whole tiles, so the pad runs
TOL = 2e-4                    # float32 against float32 at highest precision


@pytest.fixture(scope="module")
def model():
    cfg = llama.LlamaConfig.tiny(max_seq_len=MAX_SEQ)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def chunk_fn(model):
    return jax.jit(functools.partial(llama.prefill_chunk_paged, model[0]),
                   static_argnames=("kv_tile",))


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(1, 255, size=n).astype(np.int32)


def _table(n_blocks, seed=1):
    """A sequence's blocks, scattered over the pool (never its last block,
    which the poison test fills); the rest of the row is the sink (block
    0), as the engine leaves it."""
    row = np.zeros((1, WIDTH), np.int32)
    row[0, :n_blocks] = np.random.RandomState(seed).permutation(
        np.arange(1, NB - 1))[:n_blocks]
    return row


def _fill_prefix(chunk_fn, params, pool, table, tokens, upto):
    """Positions [0, upto) through the function under test, a block a call
    (one program); the reference judges what the chunk after it reads."""
    for p0 in range(0, upto, BS):
        _, pool, _ = chunk_fn(params, tokens[None, p0:p0 + BS], pool, table,
                              jnp.int32(p0), kv_tile=TILE)
    return pool


def _layer0_kv(cfg, params, tokens):
    """Layer 0's K (after rope) and V of a whole sequence, from the
    reference's own pieces: what the pool has to hold at those positions."""
    f32 = jnp.float32
    lp = {k: v[0].astype(f32) for k, v in params["layers"].items()}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens)].astype(f32)
        h = llama_reference._rms(x, lp["attn_norm"], cfg.rms_norm_eps)
        s = len(tokens)
        k = llama_reference._rope(
            (h @ lp["wk"]).reshape(s, cfg.n_kv_heads, cfg.head_dim),
            cfg.rope_theta)
        return np.asarray(k.reshape(s, -1)), np.asarray(h @ lp["wv"])


@pytest.mark.parametrize("c", [BS, 64, 256])
@pytest.mark.parametrize("p0", [0, BS, TILE - BS, TILE, 3 * TILE + BS])
def test_chunk_matches_float32_reference(model, chunk_fn, p0, c):
    """Logits of the chunk and the pool blocks it wrote, at chunk starts on
    both sides of a tile's edge and chunks that fit in a tile, fill one and
    span four."""
    cfg, params = model
    tokens = _tokens(p0 + c, seed=p0 + c)
    table = _table((p0 + c) // BS)
    pool = llama.init_paged_kv_cache(cfg, NB, BS)
    pool = _fill_prefix(chunk_fn, params, pool, table, tokens, p0)
    before = jax.tree.map(np.asarray, pool)
    logits, pool, _ = chunk_fn(params, tokens[None, p0:], pool, table,
                               jnp.int32(p0), kv_tile=TILE)
    want = np.asarray(llama_reference.reference_logits(cfg, params, tokens))
    np.testing.assert_allclose(np.asarray(logits[0]), want[p0:], atol=TOL)
    # the chunk's blocks hold its K and V; no other block was touched
    mine = table[0, p0 // BS:(p0 + c) // BS]
    k0, v0 = _layer0_kv(cfg, params, tokens)
    for name, ref in (("k", k0), ("v", v0)):
        got = np.asarray(pool[name])
        np.testing.assert_allclose(
            got[0, mine].reshape(c, -1), ref[p0:], atol=TOL)
        others = np.setdiff1d(np.arange(NB), mine)
        np.testing.assert_array_equal(got[:, others], before[name][:, others])


@pytest.mark.parametrize("p0,c", [(0, BS), (0, 64), (TILE, 64),
                                  (TILE - BS, 64), (2 * TILE, 256)])
def test_tiles_past_the_live_prefix_are_never_read(model, chunk_fn, p0, c):
    """Every table entry of a tile wholly past p0 + C points at a block of
    NaN: the output is finite and the same to the bit, because the loop's
    trip count ends before them."""
    cfg, params = model
    tokens = _tokens(p0 + c, seed=7)
    table = _table((p0 + c) // BS)
    pool = llama.init_paged_kv_cache(cfg, NB, BS)
    pool = _fill_prefix(chunk_fn, params, pool, table, tokens, p0)
    poison_block = NB - 1
    assert poison_block not in table
    pool = {n: a.at[:, poison_block].set(jnp.nan) for n, a in pool.items()}
    clean, _, _ = chunk_fn(params, tokens[None, p0:], pool, table,
                           jnp.int32(p0), kv_tile=TILE)
    poisoned = table.copy()
    first_dead = math.ceil((p0 + c) / TILE) * TILE // BS
    assert first_dead < WIDTH
    poisoned[0, first_dead:] = poison_block
    got, after, _ = chunk_fn(params, tokens[None, p0:], pool, poisoned,
                             jnp.int32(p0), kv_tile=TILE)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    mine = table[0, :(p0 + c) // BS]
    assert np.isfinite(np.asarray(after["k"])[:, mine]).all()


def test_prompt_that_fills_max_seq_runs_every_tile(model, chunk_fn):
    """The last chunk of a prompt of ``max_seq_len`` tokens attends all
    eight tiles of the table, and matches."""
    cfg, params = model
    tokens = _tokens(MAX_SEQ, seed=11)
    table = _table(MAX_SEQ // BS)
    pool = llama.init_paged_kv_cache(cfg, NB, BS)
    p0 = MAX_SEQ - 64
    pool = _fill_prefix(chunk_fn, params, pool, table, tokens, p0)
    logits, _, _ = chunk_fn(params, tokens[None, p0:], pool, table,
                            jnp.int32(p0), kv_tile=TILE)
    want = np.asarray(llama_reference.reference_logits(cfg, params, tokens))
    np.testing.assert_allclose(np.asarray(logits[0]), want[p0:], atol=TOL)


def test_tile_has_to_be_whole_pages(model):
    cfg, params = model
    pool = llama.init_paged_kv_cache(cfg, NB, BS)
    with pytest.raises(ValueError, match="kv_tile"):
        llama.prefill_chunk_paged(cfg, params, jnp.zeros((1, BS), jnp.int32),
                                  pool, _table(1), jnp.int32(0), kv_tile=24)


# -- through the engine, at the tile it serves with ------------------------

E_MAX_SEQ, E_CHUNK = 1536, 256
GEN = GenerationConfig(max_new_tokens=6)


@pytest.fixture(scope="module")
def engine_model():
    cfg = llama.LlamaConfig.tiny(max_seq_len=E_MAX_SEQ)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def served(engine_model):
    """An engine over a table of three tiles, warmed, then fed prompts of one
    to three tiles, a prefix hit past the first tile, and a request that is
    preempted mid-decode and recomputed.  Returns what it emitted and what
    its counters and the compile listener read."""
    tile = llama.PREFILL_KV_TILE
    assert E_MAX_SEQ == 3 * tile, "re-size the prompts to the tile"
    cfg, params = engine_model
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=cfg, max_batch_size=4, max_seq_len=E_MAX_SEQ,
                  block_size=BS, prefill_chunk=E_CHUNK, decode_chunk=2,
                  num_blocks=400), params=params)
    eng.warmup()
    programs = eng._prefill_chunk._cache_size()
    shared = list(_tokens(700, seed=21))
    prompts = [list(_tokens(20, seed=22)), list(_tokens(300, seed=23)),
               shared, list(_tokens(1100, seed=24))]
    out = eng.generate(prompts, GEN)
    before_hit = eng.counters()
    # 640 of the 700 tokens are 40 full blocks the cache still holds
    hit = shared[:640] + list(_tokens(90, seed=25))
    out += eng.generate([hit], GEN)
    hit_tokens = eng.counters()["prefix_hit_tokens"] - before_hit[
        "prefix_hit_tokens"]
    compiles = eng.counters()["compiles"]
    # all but 40 blocks held back: a short request and a younger one of two
    # tiles decode until the pool runs dry, the younger is evicted and, once
    # the older is done, prefilled again over prompt + what it had emitted
    held = eng.blocks.alloc(eng.blocks.num_free() - 40)
    pair = [list(_tokens(64, seed=26)), list(_tokens(530, seed=27))]
    out += eng.generate(pair, GenerationConfig(max_new_tokens=24))
    eng.blocks.release(held)
    return {"cfg": cfg, "params": params, "eng": eng,
            "prompts": prompts + [hit] + pair, "out": out,
            "hit_tokens": hit_tokens, "compiles": compiles,
            "programs": (programs, eng._prefill_chunk._cache_size()),
            "counters": eng.counters()}


def test_engine_greedy_tokens_are_the_references(served):
    """Teacher-forced over prompt + served, the float32 reference's argmax
    is the served token at every position (a gap under 1e-3 of logit is a
    tie it may break the other way)."""
    assert served["counters"]["preemptions"] == 1
    assert served["hit_tokens"] == 640
    for prompt, toks in zip(served["prompts"], served["out"]):
        assert len(toks) >= GEN.max_new_tokens
        seq = prompt + toks
        rows = np.asarray(llama_reference.reference_logits(
            served["cfg"], served["params"], seq[:-1]))[len(prompt) - 1:]
        gaps = rows.max(-1) - rows[np.arange(len(toks)), toks]
        assert gaps.max() < 1e-3, (len(prompt), gaps)


def test_engine_compiles_what_warmup_lists_and_nothing_in_serving(served):
    eng = served["eng"]
    assert eng.warmup_report["prefill_chunks"] == [16, 32, 64, 128, 256]
    # one prefill program a chunk width, before and after all of serving
    assert served["programs"] == (5, 5)
    # and no program of any kind through the prompts and the prefix hit (the
    # recompute's odd remainder makes decode tails warmup() does not list)
    assert served["compiles"] == 0


def test_prefill_page_counters_of_the_served_plan(served):
    """``prefill_live_pages`` and ``prefill_visited_pages`` against the plan
    worked out by hand from the engine's chunking rule."""
    tile, c = llama.PREFILL_KV_TILE, served["counters"]

    def plan(plen, start=0):
        live = visited = 0
        p0 = start
        while p0 < plen:
            rest = plen - p0
            bucket = BS
            while bucket < min(rest, E_CHUNK):
                bucket *= 2
            take = min(bucket, rest)
            live += math.ceil((p0 + take) / BS)
            visited += math.ceil((p0 + bucket) / tile) * tile // BS
            p0 += take
        return live, visited

    # 20 | 300 | 700 | 1100 | the hit from 640 | 64 | 530 | the victim again
    assert plan(20) == (2, 32) and plan(300) == (16 + 19, 64)
    assert plan(700) == (16 + 32 + 44, 32 + 32 + 64)
    runs = [plan(20), plan(300), plan(700), plan(1100), plan(730, 640),
            plan(64), plan(530)]
    live = sum(r[0] for r in runs)
    visited = sum(r[1] for r in runs)
    # the victim's recompute runs 530 + its 1 to 23 tokens so far, from 0 or
    # from the full blocks the cache kept: bounded from both sides
    assert live < c["prefill_live_pages"] <= live + plan(553)[0]
    assert visited < c["prefill_visited_pages"] <= visited + plan(553)[1]
    assert c["prefill_live_pages"] <= c["prefill_visited_pages"]
    assert c["prefill_chunks"] >= 1 + 2 + 3 + 5 + 1 + 1 + 3 + 1


@pytest.mark.parametrize("plen,live,visited", [
    (16, 1, 32), (256, 16, 32), (257, 16 + 17, 32 + 32),
    (520, 16 + 32 + 33, 32 + 32 + 64), (1024, 16 + 32 + 48 + 64, 32 * 6)])
def test_prefill_page_counters_exact(engine_model, plen, live, visited):
    """One prompt, no cache, no padding surprise: both counters exactly."""
    cfg, params = engine_model
    eng = PagedJaxLLMEngine(
        LLMConfig(model_config=cfg, max_batch_size=2, max_seq_len=E_MAX_SEQ,
                  block_size=BS, prefill_chunk=E_CHUNK,
                  enable_prefix_caching=False, num_blocks=160),
        params=params)
    eng.generate([list(_tokens(plen, seed=plen))],
                 GenerationConfig(max_new_tokens=1))
    c = eng.counters()
    assert (c["prefill_live_pages"], c["prefill_visited_pages"]) == (
        live, visited)
    assert llama.PREFILL_KV_TILE // BS == 32   # what the numbers assume
