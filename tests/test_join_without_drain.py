"""A row enters and leaves the decode batch without emptying the device
(ISSUE 37): a request's final prompt chunk hands its first token to the
decode mirrors ON the device (``engine.join``), the same step's decode chunk
is queued behind it, and the step returns the token; a finish marks nothing
dirty.  Tokens are held against each family's full forward, never against
another run of the engine; what the engine did is read from ``counters()``.
Tiny float32 models on the CPU.  Tier-1 lane: runs on every commit.
"""

import jax
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine

FAMILIES = ("llama", "latent", "hybrid")


@pytest.fixture(scope="module")
def families(greedy_reference):
    """``get(family) -> (make_engine(**LLMConfig fields), check(prompt,
    out))``, each family built when first asked for: ``check`` holds ``out``
    to the family's reference, greedy."""

    def entry(cfg, params, ref=None, tol=None):
        def make(**kw):
            kw = {"max_batch_size": 8, "max_seq_len": 256, "block_size": 16,
                  "prefill_chunk": 32, "num_blocks": 96, **kw}
            return PagedJaxLLMEngine(LLMConfig(model_config=cfg, **kw),
                                     params=params)

        def check(prompt, out):
            if ref is None:  # the full forward, a token at a time
                want = greedy_reference(cfg, params, [prompt], len(out))[0]
                assert out == want
                return
            # teacher-forced: each token is the reference's argmax at its
            # position (one inside ``tol`` of it is a tie)
            rows = np.asarray(ref(cfg, params, (prompt + out)[:-1],
                                  first_row=len(prompt) - 1))
            gaps = rows.max(-1) - rows[np.arange(len(out)), out]
            assert gaps.max() <= tol * max(1.0, rows.std()), gaps

        return make, check

    def build(family):
        if family == "llama":
            from ray_tpu.models import llama

            cfg = llama.LlamaConfig.tiny(compute_dtype=jax.numpy.float32,
                                         max_seq_len=256)
            return entry(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)))
        if family == "latent":
            from ray_tpu.models import pangu_moe as pm
            from ray_tpu.models.pangu_moe_reference import reference_logits

            cfg = pm.PanguMoEConfig.tiny(experts_held=(4, 8))
            return entry(cfg, pm.init_params(cfg, jax.random.PRNGKey(7)),
                         reference_logits, 2e-4)
        from ray_tpu.models import granite_hybrid as gh
        from ray_tpu.models.granite_hybrid_reference import reference_logits

        # a small embedding multiplier: at the published 12 a tied head
        # echoes its input and greedy tokens would say nothing
        cfg = gh.GraniteHybridConfig.tiny(
            vocab_size=256, embedding_multiplier=1.0, logits_scaling=0.125)
        return entry(cfg, gh.init_params(cfg, jax.random.PRNGKey(7)),
                     reference_logits, 1e-4)

    built = {}
    return lambda family: built.get(family) or built.setdefault(
        family, build(family))


def _prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 255, n)]


def _gen(n, **kw):
    return GenerationConfig(max_new_tokens=n, **kw)


class _Run:
    """Drives an engine a step at a time and keeps what each step returned."""

    def __init__(self, eng):
        self.eng, self.out = eng, {}

    def add(self, prompt, gen):
        rid = self.eng.add_request(prompt, gen)
        self.out[rid] = []
        return rid

    def step(self):
        got = self.eng.step()
        for rid, toks in got.items():
            self.out[rid].extend(toks)
        return got

    def finish(self):
        while self.eng.has_work():
            self.step()
        for rid, toks in self.eng.flush().items():
            self.out[rid].extend(toks)

    def until_pipelined(self):
        base = self.eng.counters()["decode_dispatches_pipelined"]
        while self.eng.counters()["decode_dispatches_pipelined"] < base + 2:
            self.step()


def _delta(after, before, key):
    return after[key] - before[key]


def _no_drain_between(before, after):
    """Nothing between the two reads emptied the device, and every decode
    dispatch queued behind a chunk still in flight."""
    assert after["drains"] == before["drains"], (before["drains"],
                                                 after["drains"])
    assert (_delta(after, before, "decode_dispatches")
            == _delta(after, before, "decode_dispatches_pipelined"))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_new_engine_holds_empty_mirrors(families, family):
    """The mirrors are uploaded once, at construction, every row empty:
    the first request already enters through the join.  (First in the
    file: it also pays for building each family.)"""
    make, _ = families(family)
    eng = make()
    assert not eng._dirty and eng.counters()["decode_joins"] == 0
    for mirror in (eng._d_active, eng._d_lengths, eng._d_remaining):
        assert not np.asarray(mirror).any()
    out = eng.generate([_prompt(0, 20)], _gen(3))
    c = eng.counters()
    assert len(out[0]) == 3 and c["decode_joins"] == 1
    assert set(c["drains"]) <= {"idle", "flush"}


@pytest.mark.parametrize("family", FAMILIES)
def test_late_arrival_joins_four_decoding_rows(families, family):
    """Four rows decode, pipelined; a fifth arrives with a prompt of three
    chunks.  The step that dispatches its final chunk returns its first
    token; no step in between drained; every row decodes the reference's
    tokens.  (Every sequence ends 48 positions long: the references,
    eager, compile once for them.)"""
    make, check = families(family)
    run = _Run(make(prefill_chunk=16))
    prompts = [_prompt(s, 24) for s in range(4)] + [_prompt(9, 40)]
    rids = [run.add(p, _gen(24)) for p in prompts[:4]]
    while run.eng.counters()["decode_joins"] < 4:
        run.step()
    run.until_pipelined()
    before = run.eng.counters()
    assert before["decode_joins"] == 4 and before["drains"] == {}
    late = run.add(prompts[4], _gen(8))
    req = run.eng._requests[late]
    while not run.out[late]:
        chunks_before = req.prefill_chunks
        got = run.step()
    # the step that ran the third (final) chunk is the one that returned it,
    # and it returned the first token alone: the rest are the next chunk's
    assert (chunks_before, req.prefill_chunks) == (2, 3)
    assert got[late] == run.out[late] and len(got[late]) == 1
    after = run.eng.counters()
    assert _delta(after, before, "decode_joins") == 1
    _no_drain_between(before, after)
    assert np.asarray(run.eng._d_active)[req.slot] == 1
    run.finish()
    end = run.eng.counters()
    assert set(end["drains"]) <= {"idle", "flush"}
    assert end["decode_dispatches_pipelined"] == end["decode_dispatches"] - 1
    assert end["decode_joins"] == 5
    for rid, prompt, n in zip(rids + [late], prompts, [24] * 4 + [8]):
        assert len(run.out[rid]) == n
        check(prompt, run.out[rid])


def test_two_final_chunks_in_one_step(families):
    """A budget of two chunks a step: two one-chunk prompts arrive together
    beside a decoding row and both join in the step that admits them."""
    make, check = families("llama")
    run = _Run(make(prefill_token_budget=64))
    first = run.add(_prompt(0, 24), _gen(30))
    run.until_pipelined()
    before = run.eng.counters()
    pair = [run.add(_prompt(s, 20 + s), _gen(10)) for s in (1, 2)]
    got = run.step()
    after = run.eng.counters()
    assert all(len(got[rid]) == 1 for rid in pair)
    assert _delta(after, before, "decode_joins") == 2
    assert _delta(after, before, "prefill_chunks") == 2
    _no_drain_between(before, after)
    run.finish()
    check(_prompt(0, 24), run.out[first])
    for rid, s in zip(pair, (1, 2)):
        assert len(run.out[rid]) == 10
        check(_prompt(s, 20 + s), run.out[rid])


@pytest.mark.parametrize("family", ("llama", "hybrid"))
def test_finish_then_readmission_into_the_same_slot(families, family):
    """Two slots: one row decodes on, the other finishes and the queued
    request takes its slot in the next step.  The finish dirtied nothing
    (the device row is inactive, its length zeroed by the leave), the
    re-admission joined, and no dispatch lost its pipeline."""
    make, check = families(family)
    run = _Run(make(max_batch_size=2))
    prompts = [_prompt(0, 24), _prompt(1, 30), _prompt(2, 30)]
    asked = [24, 18, 18]  # 48 positions each: one shape for the references
    rids = [run.add(p, _gen(n)) for p, n in zip(prompts, asked)]
    run.until_pipelined()
    before = run.eng.counters()
    assert before["decode_joins"] == 2
    short = run.eng._requests[rids[1]]
    slot = short.slot
    while not short.done:
        run.step()
    # found finished by this step's collect: the row left on the device
    assert run.eng._slot_req[slot] is None
    assert np.asarray(run.eng._d_lengths)[slot] == 0
    assert np.asarray(run.eng._d_active)[slot] == 0
    queued = run.eng._requests[rids[2]]
    got = run.step()  # admits the queued request: one chunk, joined
    assert queued.slot == slot and len(got[rids[2]]) == 1
    after = run.eng.counters()
    assert _delta(after, before, "decode_joins") == 1
    _no_drain_between(before, after)
    run.finish()
    for rid, prompt, n in zip(rids, prompts, asked):
        assert len(run.out[rid]) == n
        check(prompt, run.out[rid])


@pytest.mark.parametrize("how", ("stop_token", "max_new_tokens_1"))
def test_a_request_that_ends_at_its_first_token(families, greedy_reference,
                                                how):
    """The join and ``_emit_locked`` apply one predicate: a request whose
    first token ends it never decodes (``active`` 0 on the device), is
    returned by the step that ran its prompt, frees its blocks, and the row
    beside it decodes on undisturbed."""
    make, check = families("llama")
    eng = make()
    run = _Run(eng)
    other = run.add(_prompt(0, 24), _gen(30))
    run.until_pipelined()
    prompt = _prompt(5, 20)
    first = greedy_reference(eng.cfg, eng.params, [prompt], 1)[0][0]
    gen = (_gen(8, stop_token_ids=(3, first)) if how == "stop_token"
           else _gen(1))
    before = eng.counters()
    rid = run.add(prompt, gen)
    got = run.step()
    assert got[rid] == [first]
    assert rid not in eng._requests and None in eng._slot_req
    slot = eng._slot_req.index(None)
    assert np.asarray(eng._d_active)[slot] == 0
    assert np.asarray(eng._d_lengths)[slot] == 0
    after = eng.counters()
    assert _delta(after, before, "decode_joins") == 1
    _no_drain_between(before, after)
    run.finish()
    assert run.out[rid] == [first]
    check(_prompt(0, 24), run.out[other])
    assert eng.blocks.num_free() == eng.num_blocks - 1


def test_preempted_before_its_first_token_surfaced(families):
    """Two requests run their final chunks in one step with nothing in
    flight, and the pool cannot cover both rows' decode margin: the younger
    is preempted in that step, before the host read its first token.  The
    token is never emitted; recompute samples it again, once."""
    make, check = families("llama")
    eng = make(max_batch_size=2, block_size=8, prefill_chunk=16, num_blocks=7,
               decode_chunk=8, prefill_token_budget=32,
               enable_prefix_caching=False)
    run = _Run(eng)
    prompts = [_prompt(0, 16), _prompt(1, 16)]
    rids = [run.add(p, _gen(12)) for p in prompts]
    got = run.step()
    c = eng.counters()
    assert c["preemptions"] == 1 and c["prefill_chunks"] == 2
    assert list(got) == [rids[0]] and len(got[rids[0]]) == 1
    victim = eng._requests[rids[1]]
    assert victim.out_tokens == [] and victim.slot == -1
    run.finish()
    for rid, prompt in zip(rids, prompts):
        assert len(run.out[rid]) == 12
        check(prompt, run.out[rid])
    assert eng.blocks.num_free() == 6


def test_slot_state_row_joins_between_another_rows_chunks(families):
    """The hybrid family, two chunks of 8 a step: B's prompt takes five;
    C's single chunk runs beside B's first, so C joins and decodes in
    dispatches that lie between B's chunks, B's slot inactive in them.  The
    join touches the mirrors alone: each sequence's slot state is its own."""
    make, check = families("hybrid")
    run = _Run(make(block_size=8, prefill_chunk=8, prefill_token_budget=16,
                    max_batch_size=4))
    a, b, c = _prompt(11, 8), _prompt(12, 40), _prompt(13, 8)
    ra = run.add(a, _gen(40))
    run.until_pipelined()
    before = run.eng.counters()
    rb = run.add(b, _gen(8))
    rc = run.add(c, _gen(40))
    req_b = run.eng._requests[rb]
    decoded_between = 0
    while req_b.prefill_pos < len(b):
        n = len(run.out[rc])
        run.step()
        decoded_between += len(run.out[rc]) > n > 0
    assert decoded_between >= 2, "C never decoded between B's chunks"
    after = run.eng.counters()
    assert _delta(after, before, "decode_joins") == 2
    _no_drain_between(before, after)
    run.finish()
    for rid, prompt, n in ((ra, a, 40), (rb, b, 8), (rc, c, 40)):
        assert len(run.out[rid]) == n
        check(prompt, run.out[rid])
