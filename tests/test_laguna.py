"""The Laguna family: window and full attention layers in one stack, head
counts, rotary tables and caches that differ by layer type (a ring a slot for
the window layers, the paged pool for the full ones), softmax-routed experts
beside a shared one (models/laguna.py).

A tiny config of the published pattern (a dense first layer under full
attention, one period ``W W W F`` and a tail ``W W``; 6 window heads against
4 full ones over 2 KV heads; a window of 8 positions, blocks of 4; YaRN over
half a head; 16 router outputs, 4 a token, 4 held), float32, seeded random
weights, on the CPU.  Everything is held against
``models/laguna_reference.py``, which masks a plain ``[S, S]`` a layer.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.config import SpeculativeConfig
from ray_tpu.llm.engine import GenerationConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import laguna as lg
from ray_tpu.models import laguna_reference as ref
from ray_tpu.models import pangu_moe as pm
from ray_tpu.models.family import family_of
from ray_tpu.ops.rope import (
    rope_at,
    split_rope_tables,
    yarn_inverse_frequencies,
)

VOCAB = 256
# float32 program against the float32 reference: sums of a few hundred terms
# in another order (logits of these weights have a standard deviation of 0.16)
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = lg.LagunaConfig.tiny(vocab_size=VOCAB)
    return cfg, lg.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("block_size", 4)
    # chunks of two windows: every chunk's edge straddles the window
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("num_blocks", 160)
    return PagedJaxLLMEngine(LLMConfig(model_config=cfg, **kw), params=params)


def _assert_greedy(cfg, params, prompt, out, n):
    """Every served token is the reference's own, teacher-forced, to ``TOL``
    of reference logit."""
    assert len(out) == n
    rows = np.asarray(ref.reference_logits(
        cfg, params, (prompt + out)[:-1], first_row=len(prompt) - 1))
    gaps = rows.max(-1) - rows[np.arange(n), out]
    assert gaps.max() <= TOL, gaps
    assert len(set(out)) > 1, "a degenerate model proves nothing"


@pytest.fixture(scope="module")
def alone(model):
    """``alone(prompt, n)``: what a request gets with the engine to itself."""
    cfg, params = model
    eng = _engine(cfg, params)
    return lambda prompt, n: eng.generate(
        [prompt], GenerationConfig(max_new_tokens=n))[0]


# -- the config and its two rotary tables -------------------------------------------------


def test_the_layer_scan_takes_the_published_pattern():
    """48 layers, every fourth full from layer 0: a dense first layer, eleven
    periods of three window layers before a full one, and three window layers
    after the last; 72 window heads against 48 full ones."""
    cfg = lg.LagunaConfig()
    assert cfg.layer_types[:5] == ("full", "window", "window", "window",
                                   "full")
    assert (cfg.count("window"), cfg.count("full")) == (36, 12)
    assert cfg.periods == ((3,) * 11, 3)
    assert (cfg.heads("window").n_heads, cfg.heads("full").n_heads) == (72, 48)
    assert lg.LagunaConfig.tiny().periods == ((3,), 2)
    assert cfg.ring_page == 128 and lg.LagunaConfig.tiny().ring_page == 4
    with pytest.raises(ValueError, match="whole pages"):
        lg.LagunaConfig.tiny(window=300)


def _yarn_transcribed(dim, theta, factor, orig, beta_fast, beta_slow):
    """The inverse frequencies as arXiv:2309.00071 and ``transformers``
    4.57.6 (``_compute_yarn_parameters``, ``truncate`` true) give them,
    column by column in plain Python."""
    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(dim // 2):
        pos_freq = theta ** (2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        extrapolation = 1.0 - ramp
        out.append(1.0 / (factor * pos_freq) * (1.0 - extrapolation)
                   + 1.0 / pos_freq * extrapolation)
    return np.asarray(out)


@pytest.mark.parametrize("dim,theta,factor,orig,fast,slow,scale", [
    (64, 500000.0, 128.0, 8192, 32.0, 1.0, 1.4852030263919618),  # published
    (64, 500000.0, 64.0, 4096, 64.0, 1.0, None),      # the family's smaller
    (8, 500000.0, 4.0, 16, 32.0, 1.0, None),          # the tests' tiny config
    (32, 10000.0, 1.0, 2048, 32.0, 1.0, None),        # factor 1: plain rope
])
def test_yarn_frequencies_are_the_published_blend(dim, theta, factor, orig,
                                                  fast, slow, scale):
    """The blend against its transcription, and the split tables
    (``rope_at``: a position's angle as its multiple of 128 plus the rest)
    against float64 cosines at every position of the cell's longest
    sequence."""
    inv, got_scale = yarn_inverse_frequencies(dim, theta, factor, orig, fast,
                                              slow, scale)
    np.testing.assert_allclose(
        inv, _yarn_transcribed(dim, theta, factor, orig, fast, slow),
        rtol=1e-12)
    want = 0.1 * math.log(factor) + 1.0 if scale is None else scale
    assert got_scale == pytest.approx(want, rel=1e-12)
    if factor > 1:
        # the fastest column keeps its frequency, the slowest is interpolated
        assert inv[0] == 1.0
        np.testing.assert_allclose(
            inv[-1], theta ** (-(dim - 2) / dim) / factor, rtol=1e-12)
    n = 17408
    tables = split_rope_tables(inv, n, got_scale)
    assert sum(t.nbytes for t in tables) < 100_000
    pos = np.concatenate([np.arange(300), np.arange(n - 300, n),
                          np.random.default_rng(0).integers(0, n, 400)])
    cos, sin = rope_at(tables, jnp.asarray(pos))
    ang = pos[:, None] * inv[None, :]
    # products of float32 roundings of float64 cosines
    np.testing.assert_allclose(cos, np.cos(ang) * want, atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(ang) * want, atol=1e-6)
    assert cos.shape == (len(pos), dim // 2) and cos.dtype == jnp.float32


def test_published_attention_factor_is_yarns_own():
    """The published ``attention_factor`` is what YaRN derives from the
    factor: ``0.1 ln(128) + 1``."""
    assert dict(lg.LagunaConfig().rope_full)["attention_factor"] == (
        pytest.approx(0.1 * math.log(128.0) + 1.0, rel=1e-12))


def test_the_router_scores_by_the_configs_function(model):
    """``pangu_moe.route`` under this family's config is a softmax over all
    outputs; under a config that names no function it is the sigmoid it
    was."""
    cfg, _ = model
    h = jax.random.normal(jax.random.PRNGKey(1), (9, cfg.dim))
    router = jax.random.normal(jax.random.PRNGKey(2),
                               (cfg.dim, cfg.n_routed_experts))
    k = cfg.n_experts_per_tok
    gates, idx = pm.route(cfg, h, router)
    p = np.asarray(jax.nn.softmax(h @ router, -1))
    order = np.argsort(-p, -1)[:, :k]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(order, -1))
    top = np.take_along_axis(p, np.asarray(idx), -1)
    np.testing.assert_allclose(
        gates, cfg.routed_scaling_factor * top / top.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)
    sig, _ = pm.route(pm.PanguMoEConfig.tiny(dim=cfg.dim), h, router)
    s = np.sort(np.asarray(jax.nn.sigmoid(h @ router)), -1)[:, ::-1][:, :k]
    np.testing.assert_allclose(
        sig, 2.5 * s / s.sum(-1, keepdims=True), rtol=1e-5)


# -- prompt chunks, then decode, through pool and ring -----------------------------------


@pytest.fixture(scope="module")
def programs(model):
    """The family's two programs, compiled once for the module: a chunk of
    12 (a window and a half) of slot 1's sequence, a token-step of three rows
    of which row 1 decodes."""
    cfg, params = model
    table = jnp.arange(1, 33, dtype=jnp.int32)[None]
    tab = jnp.zeros((3, 32), jnp.int32).at[1].set(table[0])
    active = jnp.asarray([0, 1, 0], jnp.int32)
    chunk = jax.jit(lambda t, pool, p0, state, take: lg.prefill_chunk_paged(
        cfg, params, t, pool, table, p0, slot_state=state,
        slot=jnp.int32(1), take=take, kv_tile=8))
    step = jax.jit(lambda t, pool, n, state: lg.decode_step_paged(
        cfg, params, t, pool, tab, n, slot_state=state, active=active))
    return chunk, step


@pytest.mark.parametrize("plen", [
    5,     # shorter than the window: the ring never wraps in the prompt
    8,     # the window exactly
    12,    # one whole chunk, a window and a half
    37,    # four chunks, the last with a padded tail; the ring wraps 4 times
    70,    # six chunks; nearly nine windows
])
def test_prompt_chunks_then_decode_match_the_reference(model, programs, plen):
    """Logits of every prompt position (chunks of 12, whose edges straddle
    the window of 8) and of six decode steps against the reference's full
    forward; the slot's ring against the reference's last keys and values."""
    cfg, params = model
    chunk, step = programs
    toks = _tokens(plen + 6, seed=plen)
    want = np.asarray(ref.reference_logits(cfg, params, toks))
    pool = lg.init_paged_cache(cfg, 40, 4)
    # a re-used slot is never cleared: what it holds is never read
    state = jax.tree.map(lambda x: x + 1, lg.init_slot_state(cfg, 3))
    got = []
    for p0 in range(0, plen, 12):
        take = min(12, plen - p0)
        t = np.full((1, 12), 7, np.int32)  # the padding is a real token's id
        t[0, :take] = toks[p0:p0 + take]
        logits, pool, state = chunk(jnp.asarray(t), pool, jnp.int32(p0),
                                    state, jnp.int32(take))
        got.append(np.asarray(logits[0, :take]))
    np.testing.assert_allclose(np.concatenate(got), want[:plen], atol=TOL)
    others = jax.tree.map(lambda x: np.asarray(x[:, [0, 2]]), state)
    for i in range(6):
        logits, pool, state, booked = step(
            jnp.asarray([5, toks[plen + i], 9], jnp.int32), pool,
            jnp.asarray([3, plen + i, 8], jnp.int32), state)
        np.testing.assert_allclose(logits[1], want[plen + i], atol=TOL)
        assert booked.shape == (len(lg.DECODE_COUNTERS),)
        assert int(booked[0]) == cfg.n_held * cfg.n_moe_layers
        assert int(booked[3]) == 0  # no kernel here: the dense product
        # ONE window layer's and ONE full layer's positions, the live row's
        assert booked.tolist()[4:] == [min(plen + i + 1, cfg.window),
                                       plen + i + 1]
    # the rows that did not decode: bit for bit
    jax.tree.map(lambda x, o: np.testing.assert_array_equal(x[:, [0, 2]], o),
                 state, others)
    held = lg.FAMILY.reference_slot_state(
        cfg, params, toks, jax.tree.map(lambda x: x[:, 1], state))
    assert set(held) == {"wk", "wv"}
    for have, ref_rows in held.values():
        assert have.shape == (cfg.count("window"), cfg.window, cfg.kv_width)
        np.testing.assert_allclose(have, ref_rows, atol=TOL)


def test_a_chunk_of_padding_alone_leaves_the_ring_as_it_was(model, programs):
    """``take == 0`` (the engine's warm-up): nothing reaches the ring."""
    cfg, params = model
    chunk, _ = programs
    state = jax.tree.map(lambda x: x + 3, lg.init_slot_state(cfg, 3))
    _, _, after = chunk(jnp.full((1, 12), 7, jnp.int32),
                        lg.init_paged_cache(cfg, 40, 4), jnp.int32(0), state,
                        jnp.int32(0))
    jax.tree.map(np.testing.assert_array_equal, after, state)


def test_ring_in_order_puts_a_slots_rows_by_position():
    cfg = lg.LagunaConfig.tiny()
    ring = jnp.arange(8, dtype=jnp.float32)[None, :, None] + jnp.zeros(
        (2, 8, 3))
    # 5 positions: rows 0..4, then zeros; 21: positions 13..20 at rows 5..4
    np.testing.assert_array_equal(
        lg.ring_in_order(cfg, ring, 5)[0, :, 0], [0, 1, 2, 3, 4, 0, 0, 0])
    np.testing.assert_array_equal(
        lg.ring_in_order(cfg, ring, 21)[1, :, 0], [5, 6, 7, 0, 1, 2, 3, 4])


# -- the expert layer's shares ----------------------------------------------------------------


def test_shares_of_the_expert_layer_add_up_to_the_whole(model):
    """4 chips share the layer's 16 experts, 4 each.  The parts the four
    shares give (the program's expert layer: ``pangu_moe.moe_ffn`` under this
    family's config), the shared expert counted once, add up to the uncut
    reference's layer."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, experts_held=(0, 16))
    lp = jax.tree.map(lambda x: x[0],
                      lg.init_params(whole, jax.random.PRNGKey(3))["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.dim))
    want = ref._moe(whole, h, lambda n, *i: lp[n][i])
    shared = ref._moe(dataclasses.replace(whole, experts_held=(0, 0)), h,
                      lambda n, *i: lp[n][i])
    total, pairs = 0.0, 0
    f = cfg.moe_ffn_dim
    for lo in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        held = dict(lp, we_gate=lp["we_gate"][:, lo * f:(lo + 4) * f],
                    we_up=lp["we_up"][:, lo * f:(lo + 4) * f],
                    we_down=lp["we_down"][lo * f:(lo + 4) * f])
        y, g, _ = pm.moe_ffn(share, h, held)
        total = total + (y - shared)
        pairs += int((g > 0).sum())
    np.testing.assert_allclose(total + shared, want, atol=TOL)
    # every (token, expert) pair lands on exactly one share
    assert pairs == 24 * cfg.n_experts_per_tok


# -- continuous batching over a ring beside a pool ----------------------------------------------


def test_a_request_between_its_prompt_chunks_while_another_decodes(model,
                                                                  alone):
    """B's prompt takes five steps of one chunk each; A decodes all the
    while, in dispatches whose rows include B's slot with ``active == 0``.
    Each gets what it gets alone."""
    cfg, params = model
    a, b = _tokens(11, seed=11), _tokens(75, seed=12)
    eng = _engine(cfg, params)
    assert eng.family is family_of(cfg) and eng.cache_leaves == ("k", "v")
    ra = eng.add_request(a, GenerationConfig(max_new_tokens=40))
    got = {ra: []}
    while len(got[ra]) < 4:
        for rid, toks in eng.step().items():
            got[rid] += toks
    rb = eng.add_request(b, GenerationConfig(max_new_tokens=12))
    got[rb] = []
    between = 0
    while eng.has_work():
        req = eng._requests.get(rb)
        mid = req is not None and 0 < req.prefill_pos < len(b)
        before = len(got[ra])
        for rid, toks in eng.step().items():
            got[rid] += toks
        between += mid and len(got[ra]) > before
    eng.flush()
    assert between >= 2, "A never decoded between B's chunks"
    assert got[ra] == alone(a, 40)
    assert got[rb] == alone(b, 12)
    _assert_greedy(cfg, params, a, got[ra], 40)   # the ring wraps in decode
    _assert_greedy(cfg, params, b, got[rb], 12)
    # the counters book: the rows that decode, the experts they hit, the
    # positions one layer of each kind read
    c = eng.counters()
    assert c["decode_rows"] == 4 * c["decode_token_steps"]
    assert 0 < c["moe_experts_hit"] <= c["moe_experts_held"]
    assert c["moe_experts_hit"] <= c["moe_pairs_here"]
    assert 0 < c["decode_window_positions"] < c["decode_full_positions"]
    assert c["decode_window_positions"] <= cfg.window * c["decode_live_rows"]


def test_a_slot_reused_after_a_finish_reads_none_of_what_was_left(model):
    cfg, params = model
    first, second = _tokens(60, seed=21), _tokens(5, seed=22)
    eng = _engine(cfg, params, max_batch_size=1)
    eng.generate([first], GenerationConfig(max_new_tokens=9))
    assert float(jnp.abs(eng.slot_state["wk"]).min()) > 0  # a full ring left
    # shorter than the window: most of the ring still holds the first's rows
    out = eng.generate([second], GenerationConfig(max_new_tokens=9))[0]
    _assert_greedy(cfg, params, second, out, 9)


def test_preemption_by_recompute_rebuilds_ring_and_pool(model, alone):
    cfg, params = model
    prompts = [_tokens(30, seed=31), _tokens(30, seed=32)]
    eng = _engine(cfg, params, num_blocks=26, max_batch_size=2,
                  host_kv_cache_bytes=0)
    outs = eng.generate(prompts, GenerationConfig(max_new_tokens=40))
    assert eng.counters()["preemptions"] > 0
    for prompt, out in zip(prompts, outs):
        assert out == alone(prompt, 40)


def test_the_same_prompt_twice_is_no_prefix_hit(model):
    cfg, params = model
    prompt = _tokens(70, seed=41)
    eng = _engine(cfg, params, enable_prefix_caching=True)
    assert eng.utilization()["slot_state"] == {
        "slots": 4, "prefix_matching": False,
        "bytes": 2 * cfg.count("window") * 4 * cfg.window * cfg.kv_width * 4}
    one = eng.generate([prompt], GenerationConfig(max_new_tokens=8))[0]
    two = eng.generate([prompt], GenerationConfig(max_new_tokens=8))[0]
    assert one == two
    assert eng.counters()["prefix_hit_tokens"] == 0
    assert eng.counters()["prefill_tokens"] == 2 * len(prompt)


def test_export_then_import_mid_decode_continues_the_request(model, alone):
    cfg, params = model
    prompt = _tokens(50, seed=51)
    src, dst = _engine(cfg, params), _engine(cfg, params)
    # the destination's slot 0 is taken and dirty: the import lands elsewhere
    dst.generate([_tokens(20, seed=52)], GenerationConfig(max_new_tokens=3))
    rid = src.add_request(prompt, GenerationConfig(max_new_tokens=20))
    got = []
    while len(got) < 6:
        got += src.step().get(rid, [])
    h = src.export_request(rid)
    assert set(h["slot_state"]) == {"wk", "wv"}
    assert h["slot_state"]["wk"].shape == (cfg.count("window"), cfg.window,
                                           cfg.kv_width)
    assert h["k"].shape[0] == h["v"].shape[0] == cfg.count("full")
    pool = {"k": h["k"], "v": h["v"]}
    with pytest.raises(ValueError, match="laguna family resumes"):
        dst.import_request(h["prompt"], h["first_token"], pool,
                           gen=GenerationConfig(max_new_tokens=20),
                           emitted=h["emitted"])
    res = dst.import_request(
        h["prompt"], h["first_token"], pool,
        gen=GenerationConfig(max_new_tokens=20), emitted=h["emitted"],
        slot_state=h["slot_state"])
    assert res is not None and res["emitted"] == []
    rest = []
    while dst.has_work():
        rest += dst.step().get(res["request_id"], [])
    dst.flush()
    assert h["emitted"] + rest == alone(prompt, 20)


def test_the_server_holds_a_slots_ring_against_the_reference(model):
    """``reference_state_check``: the ring a sequence's slot holds mid-decode
    beside a neighbour that decodes is the reference's last keys and values
    over the prompt and every emitted token but the last."""
    from ray_tpu.llm.serve import LLMServer

    cfg, params = model
    # room to decode on while the export waits for the step lock
    cfg = dataclasses.replace(cfg, max_seq_len=512)
    server = LLMServer(LLMConfig(
        model_config=cfg, max_batch_size=4, max_seq_len=512, block_size=4,
        prefill_chunk=16, num_blocks=400), params=params)
    try:
        import threading

        other = threading.Thread(target=server.generate, args=(
            _tokens(30, seed=71),), kwargs={"max_new_tokens": 40})
        other.start()
        got = server.reference_state_check(_tokens(45, seed=72), 4)
        other.join(timeout=120)
        assert got["emitted"] >= 4
        assert got["positions"] == 45 + got["emitted"] - 1
        for leaf in ("wk", "wv"):
            row = got[leaf]
            assert row["finite"]
            assert len(row["layer_rel_err"]) == cfg.count("window")
            # float32 program against the float32 reference
            assert row["rel_err"] < 1e-4 and max(row["layer_rel_err"]) < 1e-4
        assert not server._engine.has_work()
    finally:
        server.shutdown()


def test_engine_with_the_kernels_interpreted_matches_the_jnp_path(model,
                                                                  alone):
    """The paged decode kernel in the interpreter, over the pool for the full
    layers and over the ring seen as pages for the window ones (groups of 3
    and of 2 query heads a KV head), and the expert layers' grouped product
    in every token-step, which the engine's counters say."""
    cfg, params = model
    prompt = _tokens(27, seed=81)
    eng = _engine(cfg, params, paged_attention_kernel="interpret",
                  max_batch_size=2)
    assert eng._use_kernel and eng._kernel_interpret
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=12))[0]
    assert out == alone(prompt, 12)
    c = eng.counters()
    assert c["moe_grouped_calls"] * cfg.n_held == c["moe_experts_held"] > 0


@pytest.mark.parametrize("option, match", [
    (dict(tensor_parallel_size=2), "laguna family supplies no tensor"),
    (dict(speculative_config=SpeculativeConfig(
        draft_model_config=lg.LagunaConfig.tiny(),
        num_speculative_tokens=2)),
     "laguna family supplies no decode window"),
])
def test_engine_refuses_what_the_family_does_not_supply(model, option, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_from_published_reads_the_benchmarks_configuration():
    """The benchmark's configuration file under its published key names gives
    the default config cut to 17 layers and 16 held experts, and a key this
    family does not compute is refused by name."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "laguna-s-2.1-ep16.json")) as f:
        published = json.load(f)
    cfg = lg.LagunaConfig.from_published(published, max_seq_len=17408)
    assert cfg == dataclasses.replace(
        lg.LagunaConfig(), layer_types=lg.LagunaConfig().layer_types[:17])
    assert cfg.periods == ((3,) * 4, 0) and cfg.n_moe_layers == 16
    with pytest.raises(ValueError, match="attention_bias"):
        lg.LagunaConfig.from_published(dict(published, attention_bias=True))
    with pytest.raises(ValueError, match="experts_held"):
        lg.LagunaConfig.from_published(dict(published, num_experts=32))
