"""Draft-model speculative decoding + chunked-prefill scheduling (ISSUE 11).

Tier-1 pins:
  - greedy bit-parity: a speculative engine's temperature-0 output is
    IDENTICAL to non-speculative decode, across prompt lengths spanning
    prefill-chunk boundaries and regardless of draft quality;
  - rejection sampling emits tokens distributed exactly as the target
    distribution (the speculative-sampling guarantee, tested on the
    factored accept/correct core);
  - acceptance bookkeeping (engine stats, metric families, SLO fold) and
    the disabled path's books-NOTHING invariant;
  - draft-pool exhaustion degrades to non-speculative decode with zero
    drops;
  - chunked-prefill scheduling: a max-length prompt prefilling under the
    token budget cannot starve a decode-active request's ITL;
  - disagg composition: import_request seeds the draft KV, so handed-off
    requests don't silently decode at acceptance-rate ~0.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import (
    GenerationConfig,
    LLMConfig,
    PagedJaxLLMEngine,
    SpeculativeConfig,
)
from ray_tpu.llm.engine import _sample, _sample_dist
from ray_tpu.llm.paged import _spec_accept
from ray_tpu.models.llama import LlamaConfig, init_params

# fp32 micro model: token identity between the window program and
# single-token decode must not hinge on bf16 rounding order
_CFG_KW = dict(vocab_size=64, dim=64, n_layers=2, n_heads=2, n_kv_heads=2,
               ffn_dim=128, max_seq_len=96, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_cfg():
    return LlamaConfig.tiny(**_CFG_KW)


@pytest.fixture(scope="module")
def draft_cfg():
    return LlamaConfig.tiny(**{**_CFG_KW, "n_layers": 1})


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0))


def _lcfg(cfg, spec=None, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("decode_chunk", 4)
    return LLMConfig(model_config=cfg, speculative_config=spec, **kw)


def _gen(**kw):
    kw.setdefault("max_new_tokens", 10)
    return GenerationConfig(**kw)


def _prompts(lens, seed=3):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(1, 63, size=n)) for n in lens]


# -- the _sample precondition (satellite: engine.py fix) ---------------------


def test_sample_temperature_zero_exact_argmax():
    """temperature=0 is EXACT argmax of the raw logits: independent of
    the PRNG key and untouched by top-k masking — the precondition for
    the greedy bit-parity pin."""
    logits = jnp.asarray(np.random.RandomState(0).randn(6, 33) * 3.0)
    want = np.asarray(jnp.argmax(logits, axis=-1))
    for seed in (0, 1, 7):
        for top_k in (0, 1, 5):
            got = _sample(logits, jax.random.PRNGKey(seed),
                          jnp.zeros(6, jnp.float32),
                          jnp.full(6, top_k, jnp.int32))
            assert np.asarray(got).tolist() == want.tolist()
    # mixed batch: greedy rows stay argmax while sampling rows sample
    temps = jnp.asarray([0.0, 0.9, 0.0, 0.9, 0.0, 0.9], jnp.float32)
    got = _sample(logits, jax.random.PRNGKey(5), temps,
                  jnp.zeros(6, jnp.int32))
    got = np.asarray(got)
    assert got[0] == want[0] and got[2] == want[2] and got[4] == want[4]


def test_sample_dist_semantics():
    """_sample_dist: greedy rows are exact argmax one-hots; sampling rows
    are proper post-temperature/top-k distributions (zero outside the
    top-k support)."""
    logits = jnp.asarray(np.random.RandomState(1).randn(2, 16) * 2.0)
    temps = jnp.asarray([0.0, 0.7], jnp.float32)
    top_ks = jnp.asarray([0, 3], jnp.int32)
    dist = np.asarray(_sample_dist(logits, temps, top_ks))
    am = int(np.argmax(np.asarray(logits)[0]))
    assert dist[0, am] == 1.0 and dist[0].sum() == 1.0
    assert abs(dist[1].sum() - 1.0) < 1e-5
    assert (dist[1] > 1e-8).sum() == 3  # top-3 support only


# -- rejection-sampling core (distribution guarantee) ------------------------


def test_rejection_sampling_matches_target_distribution():
    """The speculative-sampling lemma, empirically: the emitted token at
    position 0 (accepted draft OR correction) is distributed exactly as
    the target distribution p_0, for an arbitrary draft q != p."""
    v = 8
    rs = np.random.RandomState(2)
    p = rs.dirichlet(np.ones(v)).astype(np.float32)
    q = rs.dirichlet(np.ones(v) * 0.5).astype(np.float32)
    n = 20000
    keys = jax.random.split(jax.random.PRNGKey(0), n)

    def one(key):
        kd, ka = jax.random.split(key)
        d = jax.random.categorical(kd, jnp.log(q)[None, :])  # [1] from q
        pdist = jnp.stack([p, p])[None]  # [1, k+1=2, V]
        a, corr = _spec_accept(pdist, jnp.asarray(q)[None, None], d[None],
                               ka)
        return jnp.where(a[0] >= 1, d[0], corr[0])

    toks = np.asarray(jax.vmap(one)(keys))
    emp = np.bincount(toks, minlength=v) / n
    tv = 0.5 * np.abs(emp - p).sum()
    assert tv < 0.03, (tv, emp, p)
    # degenerate q == p: everything accepted, never the correction path
    a, _ = jax.vmap(
        lambda key: _spec_accept(jnp.stack([p, p])[None],
                                 jnp.asarray(p)[None, None],
                                 jax.random.categorical(
                                     key, jnp.log(p)[None, :])[None],
                                 key))(keys[:500])
    assert int(np.asarray(a).min()) == 1
    # zeroed q (degraded slot): zero acceptances, correction ~ p exactly
    a, corr = jax.vmap(
        lambda key: _spec_accept(jnp.stack([p, p])[None],
                                 jnp.zeros((1, 1, v), jnp.float32),
                                 jnp.zeros((1, 1), jnp.int32), key))(keys)
    assert int(np.asarray(a).max()) == 0
    emp = np.bincount(np.asarray(corr).ravel(), minlength=v) / n
    assert 0.5 * np.abs(emp - p).sum() < 0.03


# -- greedy bit-parity (the tentpole pin) ------------------------------------


@pytest.mark.timeout(240)
def test_spec_greedy_bit_parity_across_chunk_boundaries(tiny_cfg,
                                                        tiny_params):
    """Speculative greedy output is bit-identical to non-speculative
    decode for prompt lengths below/at/above the prefill-chunk and
    block boundaries — with a PERFECT draft (same params: acceptance ~1,
    the fast path dominates) the pin proves verification emits exactly
    the argmax chain."""
    prompts = _prompts([5, 15, 16, 17, 31, 33])
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg), params=tiny_params)
    want = plain.generate(prompts, _gen())
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3)),
        params=tiny_params, draft_params=tiny_params)
    got = spec.generate(prompts, _gen())
    assert got == want
    stats = spec.specdec_stats()
    assert stats["proposed"] > 0
    # perfect draft: the only rejections are budget/stop truncations
    assert stats["acceptance_rate"] > 0.5, stats


@pytest.mark.timeout(240)
def test_spec_greedy_parity_mismatched_draft(tiny_cfg, draft_cfg,
                                             tiny_params):
    """Bit-parity is unconditional: an unrelated (random-init, smaller)
    draft model changes ONLY the speedup, never the tokens — rejections
    replace every wrong proposal with the target argmax."""
    prompts = _prompts([7, 19], seed=5)
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                              params=tiny_params)
    want = plain.generate(prompts, _gen())
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=draft_cfg,
                                          num_speculative_tokens=2),
              max_batch_size=2),
        params=tiny_params)  # draft random-initialized
    got = spec.generate(prompts, _gen())
    assert got == want
    stats = spec.specdec_stats()
    assert stats["accepted"] <= stats["proposed"]


@pytest.mark.timeout(240)
def test_spec_temperature_sampling_completes(tiny_cfg, tiny_params):
    """temperature>0 + top-k through the speculative path: full budgets,
    tokens in-vocab (distribution exactness is pinned on the factored
    core above; this is the end-to-end plumbing check)."""
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3),
              max_batch_size=2),
        params=tiny_params, draft_params=tiny_params)
    outs = spec.generate(_prompts([6, 11], seed=9),
                         _gen(max_new_tokens=8, temperature=0.8, top_k=8))
    assert all(len(o) == 8 for o in outs)
    assert all(0 <= t < 64 for o in outs for t in o)


# -- bookkeeping + metrics ---------------------------------------------------


@pytest.mark.timeout(240)
def test_acceptance_bookkeeping_and_metrics(tiny_cfg, tiny_params):
    """Engine stats and the ray_tpu_serve_specdec_* families agree; the
    deployment tag follows slo_label ("engine" for direct use)."""
    from ray_tpu._private import runtime_metrics

    before = runtime_metrics.specdec_snapshot().get("engine", {})
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3),
              max_batch_size=2),
        params=tiny_params, draft_params=tiny_params)
    spec.generate(_prompts([9, 13], seed=11), _gen())
    stats = spec.specdec_stats()
    assert stats["proposed"] > 0 and 0 < stats["accepted"] <= stats["proposed"]
    snap = runtime_metrics.specdec_snapshot()["engine"]
    assert snap.get("proposed", 0) - before.get("proposed", 0) == stats["proposed"]
    assert snap.get("accepted", 0) - before.get("accepted", 0) == stats["accepted"]
    # per-request stats retained for the serving layer's recent rows
    rids = sorted(spec._spec_finished)
    assert rids and all(
        0 <= spec.specdec_request_stats(r)[1] <= spec.specdec_request_stats(r)[0]
        for r in rids)
    # regression: acceptance is the verifier's TRUE count, not derived
    # from the truncated emission matrix — a perfect draft on a SHORT
    # generation (final cycle truncated by the token budget) must still
    # meter ~1.0, not be biased low by the truncation
    p0, a0 = spec._spec_proposed_total, spec._spec_accepted_total
    spec.generate(_prompts([7], seed=37), _gen(max_new_tokens=5))
    dp = spec._spec_proposed_total - p0
    da = spec._spec_accepted_total - a0
    assert dp > 0 and da == dp, (dp, da)


@pytest.mark.timeout(240)
def test_disabled_path_books_nothing(tiny_cfg, tiny_params):
    """speculative_config=None books NOTHING: no stats surface, no
    metric family points, no draft machinery (the PR 9 invariant)."""
    from ray_tpu._private import runtime_metrics

    before = runtime_metrics.specdec_snapshot()
    eng = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                            params=tiny_params)
    eng.generate(_prompts([6], seed=13), _gen(max_new_tokens=4))
    assert eng.specdec_stats() is None
    assert eng.specdec_request_stats(1) is None
    assert eng._spec is None and not hasattr(eng, "_draft_pool")
    assert runtime_metrics.specdec_snapshot() == before


def test_slo_specdec_fold_and_recent_row():
    """Ledger-side fold + the recent-row acceptance field (hermetic:
    injected clocks, no engine)."""
    from ray_tpu.serve._private import slo

    ledger = slo.ServingSLOLedger(clock=lambda: 1.0, wall=lambda: 1000.0)
    ledger.note_specdec("llm", 40, 30)
    ledger.note_specdec("llm", 10, 5)
    tr = ledger.start_request("llm", "tenant-a")
    tr.first_token()
    tr.specdec(12, 9)
    tr.finish("ok")
    row = ledger.row()
    assert row["specdec"] == {"llm": [50, 35]}
    assert row["recent"][-1]["specdec_accept_rate"] == 0.75
    fold = slo.fold_rows([row, {"specdec": {"llm": [10, 5]}}],
                         now_wall=1000.0)
    sd = fold["deployments"]["llm"]["specdec"]
    assert sd["proposed"] == 60 and sd["accepted"] == 40
    assert abs(sd["acceptance_rate"] - 40 / 60) < 1e-9
    # tracker hook: requests that never speculated carry no field
    tr2 = ledger.start_request("llm")
    tr2.finish("ok")
    assert "specdec_accept_rate" not in ledger.recent()[-1]


# -- degradation (zero drops) ------------------------------------------------


@pytest.mark.timeout(240)
def test_draft_pool_exhaustion_degrades_zero_drops(tiny_cfg, tiny_params):
    """A draft pool too small for the workload degrades requests to
    plain decode — every request completes with full, greedy-identical
    output (zero drops), and degraded slots book no proposals."""
    prompts = _prompts([17, 18, 19], seed=17)
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg), params=tiny_params)
    want = plain.generate(prompts, _gen(max_new_tokens=8))
    # 5 usable draft blocks: one 17..19-token prompt's chunk-padded draft
    # reserve (4+1) fits, a second cannot — later admissions degrade
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3,
                                          draft_num_blocks=6)),
        params=tiny_params, draft_params=tiny_params)
    got = spec.generate(prompts, _gen(max_new_tokens=8))
    assert got == want  # bit-parity through the mixed spec/degraded batch
    assert all(len(o) == 8 for o in got)
    # the pool really was the constraint: somebody degraded, somebody
    # (the first admit) speculated
    stats = spec.specdec_stats()
    assert stats["proposed"] > 0
    degraded = [r for r in spec._spec_finished
                if spec.specdec_request_stats(r) is not None]
    assert len(degraded) < len(prompts)
    # all draft blocks returned
    assert spec.draft_blocks.num_free() == spec._draft_num_blocks - 1


@pytest.mark.timeout(240)
def test_fully_degraded_batch_uses_chunked_decode(tiny_cfg, tiny_params):
    """When EVERY active request is degraded, the engine falls back to
    the ordinary chunked decode program (k+1 steps per dispatch) instead
    of paying the (k+1)-wide verify window for one token per slot —
    'degraded' must not be slower than plain decode.  Parity still
    holds, and no verify/propose dispatch happens."""
    prompts = _prompts([17, 18], seed=41)
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                              params=tiny_params)
    want = plain.generate(prompts, _gen(max_new_tokens=8))
    # a 2-block draft pool (1 usable) can never satisfy any admission
    spec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3,
                                          draft_num_blocks=2),
              max_batch_size=2),
        params=tiny_params, draft_params=tiny_params)
    verify_calls = []
    orig = spec._spec_verify
    spec._spec_verify = lambda *a, **kw: (verify_calls.append(1)
                                          or orig(*a, **kw))
    got = spec.generate(prompts, _gen(max_new_tokens=8))
    assert got == want
    assert not verify_calls, "fully degraded batch dispatched the verifier"
    stats = spec.specdec_stats()
    assert stats["proposed"] == 0 and stats["accepted"] == 0


# -- chunked-prefill scheduling ----------------------------------------------


@pytest.mark.timeout(240)
def test_chunked_prefill_no_starvation(tiny_cfg, tiny_params):
    """While a near-max-length prompt prefills chunk-by-chunk under the
    token budget, a decode-active request keeps emitting: its per-step
    emission gap stays bounded (decode ITL is never starved by prefill)."""
    eng = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, max_batch_size=2, num_blocks=32,
              prefill_chunk=16),
        params=tiny_params)
    short = eng.add_request(_prompts([5], seed=19)[0],
                            _gen(max_new_tokens=40))
    got: dict = {}
    for _ in range(3):  # short request reaches steady decode
        for rid, t in eng.step().items():
            got.setdefault(rid, []).extend(t)
    # 80-token prompt = 5 chunks of 16: prefill spans multiple steps
    long = eng.add_request(_prompts([80], seed=23)[0],
                           _gen(max_new_tokens=4))
    gaps, gap = [], 0
    while True:
        with eng._lock:
            lreq = eng._requests.get(long)
            prefilling = lreq is not None and lreq.prefill_pos < 80
        if not prefilling:
            break
        emitted = eng.step()
        for rid, t in emitted.items():
            got.setdefault(rid, []).extend(t)
        if emitted.get(short):
            gaps.append(gap)
            gap = 0
        else:
            gap += 1
    assert len(gaps) >= 2, "long prefill finished before decode could show"
    # pipelined collection lags one step; anything beyond ~2 silent steps
    # per emission would mean prefill monopolized the engine
    assert max(gaps) <= 2, gaps
    while eng.has_work():
        for rid, t in eng.step().items():
            got.setdefault(rid, []).extend(t)
    for rid, t in eng.flush().items():
        got.setdefault(rid, []).extend(t)
    assert len(got[short]) == 40 and len(got[long]) == 4


@pytest.mark.timeout(240)
def test_prefill_token_budget_knob(tiny_cfg, tiny_params):
    """config.prefill_token_budget bounds prefill tokens per STEP."""
    eng = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, max_batch_size=2, num_blocks=32, prefill_chunk=16),
        params=tiny_params)
    calls = []
    orig = eng._prefill_chunk

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    eng._prefill_chunk = spy
    eng.config.prefill_token_budget = 16
    eng.add_request(_prompts([64], seed=29)[0], _gen(max_new_tokens=2))
    eng.step(decode=False)
    assert sum(calls) == 1  # 16-token budget = one 16-token chunk
    eng.config.prefill_token_budget = 32
    calls.clear()
    eng.step(decode=False)
    assert sum(calls) == 2  # doubled budget = two chunks this step
    while eng.has_work():
        eng.step()


# -- disagg composition ------------------------------------------------------


@pytest.mark.timeout(240)
def test_import_request_seeds_draft_kv(tiny_cfg, tiny_params):
    """A handed-off request imported into a speculative decode engine
    seeds the DRAFT model's KV (recompute at draft size): post-handoff
    decode keeps greedy bit-parity AND a perfect draft's acceptance
    stays high — the regression was silent acceptance-rate ~0 on every
    disagg handoff."""
    prompt = _prompts([21], seed=31)[0]
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                              params=tiny_params)
    want = plain.generate([prompt], _gen(max_new_tokens=9))[0]

    exporter = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                                 params=tiny_params)
    rid = exporter.add_request(prompt, _gen(max_new_tokens=9))
    while True:
        exporter.step(decode=False)
        with exporter._lock:
            req = exporter._requests.get(rid)
            if req and req.slot >= 0 and req.prefill_pos >= len(prompt) \
                    and req.out_tokens:
                break
    h = exporter.export_request(rid)

    dec = PagedJaxLLMEngine(
        _lcfg(tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                          num_speculative_tokens=3),
              max_batch_size=2),
        params=tiny_params, draft_params=tiny_params)
    res = dec.import_request(h["prompt"], h["first_token"], h["k"], h["v"],
                             _gen(max_new_tokens=9))
    assert res is not None
    toks = list(res["emitted"])
    while dec.has_work():
        for _rid, t in dec.step().items():
            toks.extend(t)
    for _rid, t in dec.flush().items():
        toks.extend(t)
    assert toks == want
    stats = dec.specdec_stats()
    assert stats["proposed"] > 0
    # seeded draft == target params: acceptance high, not ~0
    assert stats["acceptance_rate"] > 0.5, stats


@pytest.mark.timeout(240)
def test_middecode_migration_of_speculating_stream(tiny_cfg, tiny_params):
    """Live-migration composition (ISSUE 19): a stream SPECULATING
    mid-decode exports with its full token history and resumes on
    another speculative engine with the draft KV re-seeded over
    prompt + history — greedy bit-parity holds across the move and the
    destination keeps speculating at high acceptance, not ~0."""
    spec = SpeculativeConfig(draft_model_config=tiny_cfg,
                             num_speculative_tokens=3)
    prompt = _prompts([19], seed=37)[0]
    plain = PagedJaxLLMEngine(_lcfg(tiny_cfg, max_batch_size=2),
                              params=tiny_params)
    want = plain.generate([prompt], _gen(max_new_tokens=14))[0]

    src = PagedJaxLLMEngine(_lcfg(tiny_cfg, spec, max_batch_size=2),
                            params=tiny_params, draft_params=tiny_params)
    rid = src.add_request(prompt, _gen(max_new_tokens=14))
    emitted = []
    while len(emitted) < 5:
        for _rid, t in src.step().items():
            emitted.extend(t)
    assert src.specdec_stats()["proposed"] > 0  # it WAS speculating
    h = src.export_request(rid)
    assert h["emitted"][:len(emitted)] == emitted
    with src._lock:
        assert rid not in src._requests  # slot freed at export

    dst = PagedJaxLLMEngine(_lcfg(tiny_cfg, spec, max_batch_size=2),
                            params=tiny_params, draft_params=tiny_params)
    res = dst.import_request(h["prompt"], h["first_token"], h["k"], h["v"],
                             _gen(max_new_tokens=14), emitted=h["emitted"])
    assert res is not None
    assert res["emitted"] == []  # history is never re-delivered
    toks = list(h["emitted"])
    while dst.has_work():
        for _rid, t in dst.step().items():
            toks.extend(t)
    for _rid, t in dst.flush().items():
        toks.extend(t)
    assert toks == want
    stats = dst.specdec_stats()
    assert stats["proposed"] > 0
    # draft KV re-seeded over prompt + history: acceptance stays high
    assert stats["acceptance_rate"] > 0.5, stats


# -- config / factory edges --------------------------------------------------


def test_adapter_speculation_overrides():
    from ray_tpu.llm.lora import adapter_speculation

    base = SpeculativeConfig(draft_model_config=object(),
                             num_speculative_tokens=4,
                             per_adapter={
                                 "off": {"enabled": False},
                                 "k0": {"num_speculative_tokens": 0},
                                 "k2": {"num_speculative_tokens": 2},
                                 "tuned": {"draft_adapter": {"x": 1}},
                             })
    assert adapter_speculation(None, "any") == (None, None)
    cfg, ad = adapter_speculation(base, None)
    assert cfg is base and ad is None
    assert adapter_speculation(base, "off") == (None, None)
    # explicit k=0 is "don't speculate", not a silently-ignored falsy
    assert adapter_speculation(base, "k0") == (None, None)
    cfg, ad = adapter_speculation(base, "k2")
    assert cfg.num_speculative_tokens == 2 and ad is None
    cfg, ad = adapter_speculation(base, "tuned")
    assert cfg is base and ad == {"x": 1}
    cfg, ad = adapter_speculation(base, "unknown")
    assert cfg is base and ad is None


def test_spec_config_validation(tiny_cfg):
    with pytest.raises(ValueError, match="draft_model_config"):
        PagedJaxLLMEngine(_lcfg(tiny_cfg, SpeculativeConfig()))
    bad_vocab = LlamaConfig.tiny(**{**_CFG_KW, "vocab_size": 32})
    with pytest.raises(ValueError, match="vocab"):
        PagedJaxLLMEngine(_lcfg(
            tiny_cfg, SpeculativeConfig(draft_model_config=bad_vocab)))
    with pytest.raises(ValueError, match="num_speculative_tokens"):
        PagedJaxLLMEngine(_lcfg(
            tiny_cfg, SpeculativeConfig(draft_model_config=tiny_cfg,
                                        num_speculative_tokens=0)))
