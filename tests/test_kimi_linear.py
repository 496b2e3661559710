"""The Kimi-Linear family: KDA layers whose matrix state is a slot's, NoPE
latent attention over a paged latent pool, routed experts after every layer
but the first (models/kimi_linear.py).

A tiny config of the published pattern (a dense first layer under a KDA
mixer, then periods of 2, 3 and 2 KDA layers before an MLA layer; 32 router
outputs, 8 held), float32, seeded random weights, on the CPU.  Everything is
held against ``models/kimi_linear_reference.py``, which runs the delta rule
position by position.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.config import SpeculativeConfig
from ray_tpu.llm.engine import GenerationConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import kimi_linear as kl
from ray_tpu.models import kimi_linear_reference as ref
from ray_tpu.models import pangu_moe as pm
from ray_tpu.models import pangu_moe_reference as pm_ref
from ray_tpu.models.family import family_of
from ray_tpu.ops import kda_state_update as kda_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 256
# float32 program against the float32 definition: sums of a few hundred
# terms in another order (logits of these weights have a standard deviation
# of 0.16)
TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    cfg = kl.KimiLinearConfig.tiny(vocab_size=VOCAB)
    return cfg, kl.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("block_size", 16)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("num_blocks", 96)
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
    """``alone(prompt, n)``: what a request gets with the engine to itself
    (one engine for the module: a re-used slot starts from zeros, which
    ``test_a_slot_reused_after_a_finish_starts_from_zeros`` holds)."""
    cfg, params = model
    eng = _engine(cfg, params)
    return lambda prompt, n: eng.generate(
        [prompt], GenerationConfig(max_new_tokens=n))[0]


# -- the chunked (WY) form against the definition ---------------------------------------


def _recurrence(q, k, v, g, beta, s0):
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    with jax.default_matmul_precision("highest"):
        last, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, last


@pytest.mark.parametrize("c,chunk,take,decay,incoming", [
    (64, 16, 64, 1.0, False),    # four whole steps
    (64, 16, 37, 1.0, True),     # the last real token inside a step
    (64, 32, 48, 1.0, True),     # ... short of its bucket, two steps
    (16, 16, 16, 1.0, True),     # one step
    (32, 64, 21, 1.0, False),    # fewer positions than a step
    (64, 16, 64, 1e-3, True),    # decays near 1 (alpha = exp(-0.001 u))
    (64, 32, 64, 30.0, True),    # decays near 0 (alpha down to exp(-30))
])
def test_chunked_form_matches_the_recurrence(c, chunk, take, decay, incoming):
    h, d = 3, 16
    ks = jax.random.split(jax.random.PRNGKey(c + chunk + take), 6)
    q = kda_ops.l2_normalize(jax.random.normal(ks[0], (c, h, d))) * d ** -0.5
    k = kda_ops.l2_normalize(jax.random.normal(ks[1], (c, h, d)))
    v = jax.random.normal(ks[2], (c, h, d))
    real = (jnp.arange(c) < take)
    g = -decay * jax.random.uniform(ks[3], (c, h, d)) * real[:, None, None]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (c, h))) * real[:, None]
    s0 = (jax.random.normal(ks[5], (h, d, d)) if incoming
          else jnp.zeros((h, d, d)))
    o, last = kl.kda_chunked(q, k, v, g, beta, s0, chunk)
    want_o, want = _recurrence(q[:take], k[:take], v[:take], g[:take],
                               beta[:take], s0)
    # float32 sums in another order; the state's values are of order 1
    np.testing.assert_allclose(o[:take], want_o, atol=2e-5)
    np.testing.assert_allclose(last, want, atol=2e-5)
    assert np.isfinite(np.asarray(o)).all()


# -- prompt chunks, then decode, through pool and slots ------------------------------------


@pytest.fixture(scope="module")
def programs(model):
    """The family's two programs, compiled once for the module: a chunk of
    32 of slot 1's sequence, a token-step of three rows of which row 1
    decodes."""
    cfg, params = model
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    tab = jnp.zeros((3, 8), jnp.int32).at[1].set(table[0])
    active = jnp.asarray([0, 1, 0], jnp.int32)
    chunk = jax.jit(lambda t, pool, p0, state, take: kl.prefill_chunk_paged(
        cfg, params, t, pool, table, p0, slot_state=state,
        slot=jnp.int32(1), take=take, kv_tile=32))
    step = jax.jit(lambda t, pool, n, state: kl.decode_step_paged(
        cfg, params, t, pool, tab, n, slot_state=state, active=active))
    return chunk, step


@pytest.mark.parametrize("plen", [20, 32, 70, 97])
def test_prompt_chunks_then_decode_match_the_reference(model, programs, plen):
    """Logits of every prompt position (chunks of 32: under one chunk, one
    whole, over two, over three) and of four decode steps against the
    reference's full forward; the slot's state against the recurrence's."""
    cfg, params = model
    chunk, step = programs
    toks = _tokens(plen + 4, seed=plen)
    want = np.asarray(ref.reference_logits(cfg, params, toks))
    pool = kl.init_paged_cache(cfg, 16, 16)
    state = kl.init_slot_state(cfg, 3)
    # a re-used slot is never cleared: the chunk at p0 == 0 starts from zeros
    state = jax.tree.map(lambda x: x + 1, state)
    got = []
    for p0 in range(0, plen, 32):
        take = min(32, plen - p0)
        t = np.zeros((1, 32), np.int32)
        t[0, :take] = toks[p0:p0 + take]
        logits, pool, state = chunk(jnp.asarray(t), pool, jnp.int32(p0),
                                    state, jnp.int32(take))
        got.append(np.asarray(logits[0, :take]))
    np.testing.assert_allclose(np.concatenate(got), want[:plen], atol=TOL)
    others = jax.tree.map(lambda x: np.asarray(x[:, [0, 2]]), state)
    for i in range(4):
        logits, pool, state, booked = step(
            jnp.asarray([5, toks[plen + i], 9], jnp.int32), pool,
            jnp.asarray([3, plen + i, 8], jnp.int32), state)
        np.testing.assert_allclose(logits[1], want[plen + i], atol=TOL)
        assert booked.shape == (len(kl.DECODE_COUNTERS),)
        assert int(booked[0]) == cfg.n_held * cfg.n_moe_layers
        assert int(booked[3]) == 0  # no kernel here: the dense product
        assert 0 <= int(booked[1]) <= int(booked[2]) <= (
            cfg.n_experts_per_tok * cfg.n_moe_layers)
    # the rows that did not decode: bit for bit
    jax.tree.map(lambda x, o: np.testing.assert_array_equal(x[:, [0, 2]], o),
                 state, others)
    held = kl.FAMILY.reference_slot_state(
        cfg, params, toks, jax.tree.map(lambda x: x[:, 1], state))["kda"]
    np.testing.assert_allclose(held[0], held[1], atol=TOL)


def test_the_layer_scan_takes_the_published_pattern():
    """27 layers, ``K K K M`` six times and ``K K M``: a dense first layer,
    then periods of 2, 3, 3, 3, 3, 3 and 2 KDA layers before an MLA layer."""
    cfg = kl.KimiLinearConfig()
    assert cfg.layer_types == ("kda", "kda", "kda", "mla") * 6 + (
        "kda", "kda", "mla")
    assert cfg.periods == (2, 3, 3, 3, 3, 3, 2)
    assert kl.KimiLinearConfig.tiny().periods == (2, 3, 2)
    with pytest.raises(ValueError, match="ends in a full-attention layer"):
        kl.KimiLinearConfig.tiny(layer_types=("kda", "mla", "kda"))


# -- the decode kernel, in interpret mode ---------------------------------------------------


@pytest.mark.parametrize("active", [
    (1, 0, 1, 1, 0, 0), (0, 0, 0, 0, 0, 0), (1, 1, 1, 1, 1, 1),
    (0, 0, 0, 0, 0, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kda_state_update_kernel_matches_jnp(active, dtype):
    layers, rows, h, d = 3, 6, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(sum(active)), 6)
    state = jax.random.normal(ks[0], (layers, rows, h, d, d))
    q, k, v = (jax.random.normal(ks[i], (rows, h, d)).astype(dtype)
               for i in (1, 2, 3))
    g = -2.0 * jax.random.uniform(ks[4], (rows, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, h)))
    act = jnp.asarray(active, jnp.int32)
    o0, s0 = kda_ops.kda_state_update_jnp(state, 1, q, k, v, g, beta, act)
    o1, s1 = kda_ops.kda_state_update(state, 1, q, k, v, g, beta, act,
                                      interpret=True)
    # both forms upcast the same inputs and sum 128 terms in float32
    np.testing.assert_allclose(o1, o0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    live = np.asarray(active, bool)
    if dtype == jnp.float32 and live.any():  # the definition, a row a head
        r = int(np.flatnonzero(live)[0])
        want_o, want_s = _recurrence(
            (kda_ops.l2_normalize(q[r]) * d ** -0.5)[None],
            kda_ops.l2_normalize(k[r])[None], v[r][None], g[r][None],
            beta[r][None], state[1, r])
        np.testing.assert_allclose(o1[r], want_o[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s1[1, r], want_s, rtol=1e-4, atol=1e-4)
    # a row that does not decode, and every other layer: bit for bit
    np.testing.assert_array_equal(np.asarray(s1)[:, ~live],
                                  np.asarray(state)[:, ~live])
    np.testing.assert_array_equal(np.asarray(s1)[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    assert not np.asarray(o1)[~live].any()


def test_kda_state_update_refuses_what_it_does_not_compute():
    z = jnp.zeros
    with pytest.raises(NotImplementedError, match="64 x 64"):
        kda_ops.kda_state_update(
            z((1, 2, 8, 64, 64)), 0, z((2, 8, 64)), z((2, 8, 64)),
            z((2, 8, 64)), z((2, 8, 64)), z((2, 8)), jnp.ones(2, jnp.int32))
    assert "heads" in kda_ops.unsupported(4, 128, 128)
    assert kda_ops.unsupported(32, 128, 128) is None


# -- the expert layer's shares ----------------------------------------------------------------


def test_shares_of_the_expert_layer_add_up_to_the_whole(model):
    """4 chips share the layer's 32 experts, 8 each.  The parts the four
    shares give (the program's expert layer: ``pangu_moe.moe_ffn`` under this
    family's config), the shared expert counted once, add up to the uncut
    reference's layer."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, experts_held=(0, 32))
    lp = jax.tree.map(lambda x: x[0],
                      kl.init_params(whole, jax.random.PRNGKey(3))["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.dim))
    want = pm_ref.moe_layer(whole, h, lambda n, *i: lp[n][i])
    shared = pm_ref.moe_layer(dataclasses.replace(whole, experts_held=(0, 0)),
                              h, lambda n, *i: lp[n][i])
    total, pairs = 0.0, 0
    f = cfg.moe_ffn_dim
    for lo in range(0, 32, 8):
        share = dataclasses.replace(cfg, experts_held=(lo, lo + 8))
        held = dict(lp, we_gate=lp["we_gate"][:, lo * f:(lo + 8) * f],
                    we_up=lp["we_up"][:, lo * f:(lo + 8) * f],
                    we_down=lp["we_down"][lo * f:(lo + 8) * f])
        y, g, _ = pm.moe_ffn(share, h, held)
        total = total + (y - shared)
        pairs += int((g > 0).sum())
    np.testing.assert_allclose(total + shared, want, atol=TOL)
    # every (token, expert) pair lands on exactly one share
    assert pairs == 24 * cfg.n_experts_per_tok


# -- continuous batching over a slot state beside a latent pool ---------------------------------


def test_a_request_between_its_prompt_chunks_while_another_decodes(model,
                                                                  alone):
    """B's prompt takes five steps of one chunk each; A decodes all the
    while, in dispatches whose rows include B's slot with ``active == 0``.
    Each gets what it gets alone."""
    cfg, params = model
    a, b = _tokens(24, seed=11), _tokens(150, seed=12)
    eng = _engine(cfg, params)
    assert eng.family is family_of(cfg) and eng.cache_leaves == ("ckv",)
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
    _assert_greedy(cfg, params, b, got[rb], 12)
    # the counters book: the rows that decode, the experts they hit
    c = eng.counters()
    assert c["decode_rows"] == 4 * c["decode_token_steps"]
    assert 0 < c["decode_live_rows"] <= 2 * c["decode_token_steps"]
    assert c["moe_experts_held"] >= 30 * cfg.n_held * cfg.n_moe_layers
    assert 0 < c["moe_experts_hit"] <= c["moe_experts_held"]
    assert c["moe_experts_hit"] <= c["moe_pairs_here"]


def test_a_steps_chunk_budget_is_shared_between_the_waiting_prompts(model,
                                                                    alone):
    """The cell's ``prefill_token_budget`` (four chunks a step): a long
    prompt in the LOWER slot does not hold a short one in a higher slot back
    (at one chunk a step the lowest slot is served whole first), a lone
    prompt takes several chunks of ONE step through its slot's state, and
    every request gets what it gets alone."""
    cfg, params = model
    a = _tokens(24, seed=41)
    long, short = _tokens(200, seed=42), _tokens(60, seed=43)
    eng = _engine(cfg, params, prefill_token_budget=128)
    ra = eng.add_request(a, GenerationConfig(max_new_tokens=30))
    got = {ra: []}
    while len(got[ra]) < 3:
        for rid, toks in eng.step().items():
            got[rid] += toks
    rl = eng.add_request(long, GenerationConfig(max_new_tokens=8))
    rs = eng.add_request(short, GenerationConfig(max_new_tokens=8))
    got.update({rl: [], rs: []})
    chunks, most, first = eng.counters()["prefill_chunks"], 0, {}
    steps = 0
    while eng.has_work():
        steps += 1
        for rid, toks in eng.step().items():
            got[rid] += toks
            first.setdefault(rid, steps)
        now = eng.counters()["prefill_chunks"]
        most, chunks = max(most, now - chunks), now
    eng.flush()
    # 128 tokens a step: four chunks of 32, a fifth behind a short last one
    assert 4 <= most <= 5, most
    assert first[rs] < first[rl], first           # the short one was not held
    for rid, prompt, n in ((ra, a, 30), (rl, long, 8), (rs, short, 8)):
        assert got[rid] == alone(prompt, n)
    _assert_greedy(cfg, params, long, got[rl], 8)


def test_a_slot_reused_after_a_finish_starts_from_zeros(model):
    cfg, params = model
    first, second = _tokens(60, seed=21), _tokens(33, seed=22)
    eng = _engine(cfg, params, max_batch_size=1)
    eng.generate([first], GenerationConfig(max_new_tokens=9))
    assert float(jnp.abs(eng.slot_state["kda"]).max()) > 0  # left behind
    out = eng.generate([second], GenerationConfig(max_new_tokens=9))[0]
    _assert_greedy(cfg, params, second, out, 9)


def test_preemption_by_recompute_rebuilds_state_and_pool(model, alone):
    cfg, params = model
    prompts = [_tokens(30, seed=31), _tokens(30, seed=32)]
    eng = _engine(cfg, params, num_blocks=7, max_batch_size=2,
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
        "bytes": sum(int(x.nbytes) for x in eng.slot_state.values())}
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
    assert set(h["slot_state"]) == {"kda", "conv"} and "k" not in h
    assert h["slot_state"]["kda"].shape[0] == cfg.count("kda")
    assert h["ckv"].shape[0] == cfg.count("mla")
    assert h["ckv"].shape[-1] == cfg.cache_width
    with pytest.raises(ValueError, match="kimi_linear family resumes"):
        dst.import_request(h["prompt"], h["first_token"], {"ckv": h["ckv"]},
                           gen=GenerationConfig(max_new_tokens=20),
                           emitted=h["emitted"])
    res = dst.import_request(
        h["prompt"], h["first_token"], {"ckv": h["ckv"]},
        gen=GenerationConfig(max_new_tokens=20), emitted=h["emitted"],
        slot_state=h["slot_state"])
    assert res is not None and res["emitted"] == []
    rest = []
    while dst.has_work():
        rest += dst.step().get(res["request_id"], [])
    dst.flush()
    assert h["emitted"] + rest == alone(prompt, 20)


def test_the_server_holds_a_slots_state_against_the_recurrence(model):
    """``reference_state_check``: the state a sequence's slot holds
    mid-decode beside a neighbour that decodes is the float32 recurrence over
    the prompt and every emitted token but the last."""
    from ray_tpu.llm.serve import LLMServer

    cfg, params = model
    server = LLMServer(LLMConfig(
        model_config=cfg, max_batch_size=4, max_seq_len=256, block_size=16,
        prefill_chunk=32, num_blocks=96), params=params)
    try:
        import threading

        other = threading.Thread(target=server.generate, args=(
            _tokens(30, seed=71),), kwargs={"max_new_tokens": 40})
        other.start()
        got = server.reference_state_check(_tokens(70, seed=72), 4)
        other.join(timeout=120)
        assert got["emitted"] >= 4
        assert got["positions"] == 70 + got["emitted"] - 1
        kda = got["kda"]
        assert kda["finite"] and len(kda["layer_rel_err"]) == cfg.count("kda")
        # float32 program against the float32 definition
        assert kda["rel_err"] < 1e-4 and max(kda["layer_rel_err"]) < 1e-4
        assert not server._engine.has_work()
    finally:
        server.shutdown()


def test_engine_with_both_kernels_interpreted_matches_the_jnp_path(model,
                                                                   alone):
    """``kda_state_update`` and the latent decode kernel in the interpreter,
    a prompt chunk's attention through its kernel too; and the expert
    layers' grouped product in every token-step (one live row's pairs fit
    its buffer), which the engine's counters say."""
    cfg, params = model
    prompt = _tokens(40, seed=81)
    eng = _engine(cfg, params, paged_attention_kernel="interpret",
                  max_batch_size=2)
    assert eng._use_kernel and eng._kernel_interpret
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=6))[0]
    assert out == alone(prompt, 6)
    c = eng.counters()
    assert c["moe_grouped_calls"] * cfg.n_held == c["moe_experts_held"] > 0


@pytest.mark.parametrize("live", [[0, 1, 0, 1, 1, 0], [1] * 6])
def test_decode_step_with_grouped_expert_layers_equals_dense(model, live):
    """A token-step of six slots through the whole decode program, its
    expert layers (after every layer but the first, under KDA and MLA mixers
    alike) grouped and dense: the live rows' logits, state and cache rows
    agree whatever the dead slots hold, the dead rows' state is kept bit
    for bit, and ``moe_grouped_calls`` counts a layer-call exactly where the
    live rows' pairs fit the buffer (16 for six rows; a row lands on one of
    the 8 held experts of 32 on average: three live rows' 12 pairs at most
    always fit, six rows' need not)."""
    cfg, params = model
    b = len(live)
    active = jnp.asarray(live, jnp.int32)
    alive = np.asarray(live) > 0
    pool = {"ckv": jax.random.normal(
        jax.random.PRNGKey(3), kl.init_paged_cache(cfg, 4 * b + 1, 16)[
            "ckv"].shape) * 0.3}
    state = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(4), x.shape,
                                    jnp.float32).astype(x.dtype) * 0.1,
        kl.init_slot_state(cfg, b))
    table = jnp.arange(1, 4 * b + 1, dtype=jnp.int32).reshape(b, 4)
    lengths = jnp.asarray([5, 17, 40, 33, 9, 60][:b], jnp.int32)

    def run(dead, interpret):
        toks = jnp.where(active > 0, jnp.asarray(_tokens(b, seed=91)), dead)
        return jax.jit(lambda t: kl.decode_step_paged(
            cfg, params, t, pool, table, lengths, slot_state=state,
            active=active, kernel_interpret=interpret))(toks)

    want, pool_w, state_w, booked_w = run(7, False)
    got, pool_g, state_g, booked = run(7, True)
    np.testing.assert_allclose(got[alive], want[alive], atol=TOL)
    mine = np.asarray(table)[alive].ravel()
    np.testing.assert_allclose(pool_g["ckv"][:, mine], pool_w["ckv"][:, mine],
                               atol=TOL)
    for k in state:
        np.testing.assert_allclose(state_g[k][:, alive], state_w[k][:, alive],
                                   atol=TOL)
        np.testing.assert_array_equal(state_g[k][:, ~alive],
                                      state[k][:, ~alive])
    assert booked.tolist()[:3] == booked_w.tolist()[:3]
    assert booked_w.tolist()[3] == 0
    assert 0 < booked.tolist()[3] <= cfg.n_moe_layers
    if not alive.all():
        assert booked.tolist()[3] == cfg.n_moe_layers
        again, pool_a, state_a, booked_a = run(
            jnp.arange(200, 200 + b), True)
        np.testing.assert_array_equal(again[alive], got[alive])
        np.testing.assert_array_equal(pool_a["ckv"][:, mine],
                                      pool_g["ckv"][:, mine])
        assert booked_a.tolist() == booked.tolist()


@pytest.mark.parametrize("option, match", [
    (dict(tensor_parallel_size=2), "kimi_linear family supplies no tensor"),
    (dict(speculative_config=SpeculativeConfig(
        draft_model_config=kl.KimiLinearConfig.tiny(),
        num_speculative_tokens=2)),
     "kimi_linear family supplies no decode window"),
])
def test_engine_refuses_what_the_family_does_not_supply(model, option, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


# -- the configuration file -----------------------------------------------------------------------

# the published config.json of Kimi-Linear-48B-A3B-Instruct, the keys a
# forward pass reads
_PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "v_head_dim": 128, "vocab_size": 163840,
}


def test_configuration_file_is_the_published_model_but_for_its_experts():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kimi-linear-48b-a3b-ep16.json")) as f:
        conf = json.load(f)
    for k, v in _PUBLISHED.items():
        assert conf[k] == v, k
    la = conf["linear_attn_config"]
    assert la["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(la["kda_layers"] + la["full_attn_layers"]) == list(
        range(1, 28))
    assert (la["head_dim"], la["num_heads"],
            la["short_conv_kernel_size"]) == (128, 32, 4)
    # only the experts are cut: 16 of 256 held, the router at 256 outputs
    assert set(conf["reduced"]) == set(conf["published"]) == {"num_experts"}
    assert conf["published"]["num_experts"] == 256
    assert (conf["num_experts"], conf["router_outputs"],
            conf["experts_held"]) == (16, 256, [0, 16])
    cfg = kl.KimiLinearConfig.from_published(conf, max_seq_len=6144)
    assert cfg == kl.KimiLinearConfig()
    assert cfg.count("kda") == 20 and cfg.count("mla") == 7
    # the issue's table, at 2 bytes a parameter
    kda = (3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128)
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304 + 512
    moe = 2304 * 256 + 17 * 3 * 2304 * 1024
    dense = 3 * 2304 * 9216
    assert [round(x / 1e6, 1) for x in (kda, mla, moe, dense)] == [
        39.5, 29.1, 120.9, 63.7]
    assert cfg.num_params == (20 * kda + 7 * mla + 26 * moe + dense
                              + 2 * 163840 * 2304 + (2 * 27 + 1) * 2304)
    assert 4.95e9 < cfg.num_params < 4.97e9
    state = jax.eval_shape(lambda: kl.init_slot_state(cfg, 64))
    assert state["kda"].shape == (20, 64, 32, 128, 128)
    assert state["kda"].dtype == jnp.float32
    assert state["conv"].shape == (20, 64, 3 * 12288)
    pool = jax.eval_shape(lambda: kl.init_paged_cache(cfg, 8, 16))
    assert pool["ckv"].shape == (7, 8, 16, 640)  # 8,960 B a position
    with pytest.raises(ValueError, match="q_lora_rank"):
        kl.KimiLinearConfig.from_published(dict(conf, q_lora_rank=1536))
    with pytest.raises(ValueError, match="experts_held"):
        kl.KimiLinearConfig.from_published(dict(conf, num_experts=8))
