"""The latent prefill kernel (ops/mla_prefill_attention.py) in interpret mode
on the CPU, at toy widths, against the ``jax.numpy`` form it replaces on a TPU
(``pangu_moe._attend_tiles_expanded``): float32 on both sides, so what
differs is the order of the sums."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import pangu_moe as pm
from ray_tpu.ops.mla_prefill_attention import mla_prefill_attention

BS, TILE, NB = 8, 16, 48
TOL = 2e-5

# name: (p0, chunk, live tokens of the chunk, table width in blocks)
CASES = {
    "no_prefix": (0, 16, 16, 2),
    "prefix_of_several_tiles": (48, 32, 32, 10),
    "tail_is_padding": (32, 32, 19, 8),
    "ends_inside_a_tile": (40, 16, 16, 7),
    "table_wider_than_live_prefix": (24, 16, 16, 30),
}


@pytest.fixture(scope="module")
def cfg():
    return pm.PanguMoEConfig.tiny(kv_lora_rank=128, qk_rope_head_dim=8)


def _inputs(cfg, p0, c, wt, seed=0):
    """A pool whose blocks past the sequence's live ones hold large finite
    garbage, the sequence's table (columns past the live blocks point at
    garbage), one layer's ``W_uk`` / ``W_uv`` and a chunk's queries."""
    rng = np.random.RandomState(seed)
    pool = 50.0 * rng.randn(2, NB, BS, cfg.cache_width).astype(np.float32)
    live = (p0 + c) // BS
    blocks = rng.permutation(np.arange(1, NB))
    row = blocks[:wt].astype(np.int32)
    pool[:, row[:live]] = rng.randn(2, live, BS, cfg.cache_width)
    pool[:, row[:live], :, cfg.latent_width:] = 0
    lp = {"w_uk": jnp.asarray(0.1 * rng.randn(
              cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank),
              jnp.float32),
          "w_uv": jnp.asarray(0.1 * rng.randn(
              cfg.n_heads, cfg.kv_lora_rank, cfg.v_head_dim), jnp.float32)}
    q_nope = jnp.asarray(rng.randn(c, cfg.n_heads, cfg.qk_nope_head_dim),
                         jnp.float32)
    q_rope = jnp.asarray(rng.randn(c, cfg.n_heads, cfg.qk_rope_head_dim),
                         jnp.float32)
    row = jnp.pad(jnp.asarray(row), (0, -wt % (TILE // BS)))
    return jnp.asarray(pool), row, lp, q_nope, q_rope


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_agrees_with_the_expanded_form(cfg, case):
    p0, c, take, wt = CASES[case]
    pool, row, lp, q_nope, q_rope = _inputs(cfg, p0, c, wt)
    if take < c:
        # the chunk's tail is padding: its rows hold whatever the padded
        # tokens wrote; the live queries must not see them
        pool = pool.at[:, row[(p0 + take) // BS + 1:(p0 + c) // BS]].mul(50.0)
    want = pm._attend_tiles_expanded(cfg, q_nope, q_rope, pool, 1, row,
                                     p0 + jnp.arange(c), lp, TILE)
    got = pm._attend_kernel(cfg, q_nope, q_rope, pool, 1, row, jnp.int32(p0),
                            lp, TILE, True)
    assert got.shape == (c, cfg.n_heads * cfg.v_head_dim)
    np.testing.assert_allclose(np.asarray(got)[:take],
                               np.asarray(want)[:take], atol=TOL)
    if case == "table_wider_than_live_prefix":
        # other garbage past p0 + C: the same result, bit for bit
        other = pool.at[:, row[(p0 + c) // BS:]].mul(-3.0)
        again = pm._attend_kernel(cfg, q_nope, q_rope, other, 1, row,
                                  jnp.int32(p0), lp, TILE, True)
        assert (np.asarray(again) == np.asarray(got)).all()


@pytest.mark.parametrize("block_q,heads,n_heads", [
    (8, 1, 4), (16, 2, 4), (32, 4, 4), (64, 3, 4), (32, 4, 32)])
def test_kernel_tilings_agree(cfg, block_q, heads, n_heads):
    """Query blocks narrower than the chunk (pairs above the diagonal are
    skipped, pairs on it masked), and head groups of each size: a group of 3
    does not divide 4 heads and falls to 2.  At the toy config's 4 heads and
    at 32 (``models/kimi_linear.py``'s; pangu's are 128)."""
    cfg = dataclasses.replace(cfg, n_heads=n_heads)
    p0, c, wt = 24, 32, 8
    pool, row, lp, q_nope, q_rope = _inputs(cfg, p0, c, wt, seed=1)
    want = pm._attend_tiles_expanded(cfg, q_nope, q_rope, pool, 0, row,
                                     p0 + jnp.arange(c), lp, TILE)
    pad = jnp.zeros((c, cfg.n_heads, cfg.cache_width - cfg.latent_width))
    q = jnp.concatenate([q_nope, q_rope, pad], -1).reshape(c, -1)
    got = mla_prefill_attention(
        q, pool[0, row].reshape(-1, cfg.cache_width), lp["w_uk"], lp["w_uv"],
        p0, scale=cfg.qk_head_dim ** -0.5, kv_tile=TILE, block_q=block_q,
        heads_per_step=heads, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_kernel_refuses_shapes_that_are_not_the_cache_rows(cfg):
    pool, row, lp, q_nope, q_rope = _inputs(cfg, 0, 16, 2)
    lat = pool[0, row].reshape(-1, cfg.cache_width)
    q = jnp.zeros((16, cfg.n_heads * cfg.qk_head_dim))  # no zero tail
    with pytest.raises(ValueError, match="not this cache row"):
        mla_prefill_attention(q, lat, lp["w_uk"], lp["w_uv"], 0, scale=1.0,
                              kv_tile=TILE, interpret=True)
    q = jnp.zeros((16, cfg.n_heads * (cfg.qk_nope_head_dim
                                      + cfg.cache_width - cfg.kv_lora_rank)))
    with pytest.raises(ValueError, match="whole tiles"):
        mla_prefill_attention(q, lat[:8], lp["w_uk"], lp["w_uv"], 0,
                              scale=1.0, kv_tile=TILE, interpret=True)


@pytest.mark.parametrize("kernel", [False, "interpret"])
def test_engine_books_the_chunks_that_took_the_kernel(cfg, kernel):
    """``prefill_kernel_chunks`` beside ``prefill_chunks``: every chunk where
    the family's kernels are on, none where they are off; the tokens served
    are the same either way."""
    params = pm.init_params(cfg, jax.random.PRNGKey(3))
    eng = PagedJaxLLMEngine(LLMConfig(
        model_config=cfg, max_batch_size=2, max_seq_len=64, block_size=BS,
        num_blocks=32, prefill_chunk=16, paged_attention_kernel=kernel),
        params=params)
    prompt = np.random.RandomState(5).randint(1, 256, size=37).tolist()
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=3))
    c = eng.counters()
    assert c["prefill_chunks"] == 3
    assert c["prefill_kernel_chunks"] == (3 if kernel else 0)
    if kernel:
        gather = PagedJaxLLMEngine(dataclasses.replace(
            eng.config, paged_attention_kernel=False), params=params)
        assert out == gather.generate([prompt],
                                      GenerationConfig(max_new_tokens=3))


def test_llama_family_has_no_prefill_kernel():
    from ray_tpu.models import llama

    assert llama.FAMILY.prefill_kernel_fits is None
    assert pm.FAMILY.prefill_kernel_fits(pm.PanguMoEConfig())
