"""The granite-hybrid family: Mamba-2 layers whose state is a slot's, beside
a paged KV cache for the attention layers (models/granite_hybrid.py).

A tiny config of the published shape (periods of 5 Mamba, 1 attention, 4
Mamba; attention heads of 64), float32, on the CPU.  Everything is held
against ``models/granite_hybrid_reference.py``, which runs the recurrence
position by position.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMConfig
from ray_tpu.llm.engine import GenerationConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.granite_hybrid_reference import reference_logits
from ray_tpu.ops import ssm_state_update as ssm_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 256


@pytest.fixture(scope="module")
def model():
    # a small embedding multiplier: at the published 12 a tied head gives
    # every position its own token back, and greedy tokens would say nothing
    cfg = gh.GraniteHybridConfig.tiny(vocab_size=VOCAB,
                                      embedding_multiplier=1.0,
                                      logits_scaling=0.125)
    return cfg, gh.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, VOCAB, n).tolist()


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("block_size", 16)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("num_blocks", 96)
    return PagedJaxLLMEngine(LLMConfig(model_config=cfg, **kw), params=params)


def _gaps(cfg, params, prompt, out):
    """Reference logit each served token gives up (teacher-forced)."""
    rows = np.asarray(reference_logits(cfg, params, (prompt + out)[:-1],
                                       first_row=len(prompt) - 1))
    return rows.max(-1) - rows[np.arange(len(out)), out], rows


def _assert_greedy(cfg, params, prompt, out, n):
    assert len(out) == n
    gaps, rows = _gaps(cfg, params, prompt, out)
    assert gaps.max() <= 1e-4 * max(1.0, rows.std()), gaps
    assert len(set(out)) > 1, "a degenerate model proves nothing"


def _alone(cfg, params, prompt, n):
    return _engine(cfg, params).generate(
        [prompt], GenerationConfig(max_new_tokens=n))[0]


# -- the chunked form against the definition ---------------------------------------


@pytest.mark.parametrize("c,q,take", [
    (64, 16, 64),   # four whole chunks
    (64, 16, 37),   # the last real token inside a chunk, padding after it
    (64, 16, 48),   # ... at a chunk's end
    (16, 16, 16),   # one chunk
    (32, 64, 21),   # fewer positions than a chunk
])
def test_chunked_form_matches_the_recurrence(c, q, take):
    cfg = gh.GraniteHybridConfig.tiny(mamba_chunk_size=q)
    h, p, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
    ks = jax.random.split(jax.random.PRNGKey(c + take), 6)
    x = jax.random.normal(ks[0], (c, h, p))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (c, h)) - 2.0)
    delta = jnp.where(jnp.arange(c)[:, None] < take, delta, 0.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    bm = jax.random.normal(ks[3], (c, n))
    cm = jax.random.normal(ks[4], (c, n))
    s0 = jax.random.normal(ks[5], (h, p, n))

    def step(s, inp):
        x_t, b_t, c_t, d_t = inp
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None])
        return s, (s * c_t[None, None]).sum(-1)

    with jax.default_matmul_precision("highest"):
        s_ref, y_ref = jax.lax.scan(step, s0, (x, bm, cm, delta))
        y, s = gh.ssd_chunked(cfg, x, delta, a, bm, cm, s0)
    np.testing.assert_allclose(y[:take], y_ref[:take], rtol=2e-4, atol=2e-4)
    # padding neither decays the state nor adds to it
    s_take = jax.lax.scan(step, s0, jax.tree.map(
        lambda v: v[:take], (x, bm, cm, delta)))[0]
    np.testing.assert_allclose(s, s_take, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_ref, rtol=2e-4, atol=2e-4)


# -- the engine against the reference's full forward pass ---------------------------


@pytest.mark.parametrize("plen", [
    20,    # one padded chunk (20 -> 32)
    75,    # 32 + 32 + 11 (-> 16): unequal chunks, the last padded
    96,    # three whole chunks: the prompt ends at a chunk's end
    130,   # five chunks, the last of 2 tokens in a bucket of 16
])
def test_prompt_chunks_then_decode_match_the_reference(model, plen):
    cfg, params = model
    prompt = _tokens(plen, seed=plen)
    eng = _engine(cfg, params)
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=10))[0]
    _assert_greedy(cfg, params, prompt, out, 10)
    # the logits themselves, through the engine's own two programs
    got = eng.first_decode_logits(prompt[:40])
    want = np.asarray(reference_logits(cfg, params, prompt[:40]))[-1]
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3 * want.std())


def test_two_periods_scan_to_the_reference():
    cfg = gh.GraniteHybridConfig.tiny(
        vocab_size=VOCAB, layer_types=gh.PUBLISHED_PERIOD * 2,
        embedding_multiplier=1.0, logits_scaling=0.125)
    assert cfg.n_periods == 2 and cfg.count("mamba") == 18
    params = gh.init_params(cfg, jax.random.PRNGKey(3))
    prompt = _tokens(45, seed=5)
    out = _engine(cfg, params).generate(
        [prompt], GenerationConfig(max_new_tokens=8))[0]
    _assert_greedy(cfg, params, prompt, out, 8)


# -- continuous batching over a state that is a slot's --------------------------------


def test_a_request_between_its_prompt_chunks_while_another_decodes(model):
    """B's prompt takes five steps of one chunk each; A decodes all the
    while, in dispatches whose rows include B's slot with ``active == 0``.
    Each gets what it gets alone."""
    cfg, params = model
    a, b = _tokens(24, seed=11), _tokens(150, seed=12)
    eng = _engine(cfg, params)
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
    assert got[ra] == _alone(cfg, params, a, 40)
    assert got[rb] == _alone(cfg, params, b, 12)
    _assert_greedy(cfg, params, b, got[rb], 12)


def test_a_slot_reused_after_a_finish_starts_from_zeros(model):
    cfg, params = model
    first, second = _tokens(60, seed=21), _tokens(33, seed=22)
    eng = _engine(cfg, params, max_batch_size=1)
    eng.generate([first], GenerationConfig(max_new_tokens=9))
    assert float(jnp.abs(eng.slot_state["ssm"]).max()) > 0  # left behind
    out = eng.generate([second], GenerationConfig(max_new_tokens=9))[0]
    assert out == _alone(cfg, params, second, 9)


def test_preemption_by_recompute_rebuilds_the_state(model):
    cfg, params = model
    prompts = [_tokens(30, seed=31), _tokens(30, seed=32)]
    eng = _engine(cfg, params, num_blocks=7, max_batch_size=2)
    outs = eng.generate(prompts, GenerationConfig(max_new_tokens=40))
    assert eng.counters()["preemptions"] > 0
    for prompt, out in zip(prompts, outs):
        assert out == _alone(cfg, params, prompt, 40)


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
    np.testing.assert_array_equal(eng.first_decode_logits(prompt),
                                  eng.first_decode_logits(prompt))


def test_export_then_import_mid_decode_continues_the_request(model):
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
    assert set(h["slot_state"]) == {"ssm", "conv"}
    assert h["slot_state"]["ssm"].shape[0] == cfg.count("mamba")
    assert h["k"].shape[0] == cfg.count("attention")
    with pytest.raises(ValueError, match="granite_hybrid family resumes"):
        dst.import_request(h["prompt"], h["first_token"], h["k"], h["v"],
                           gen=GenerationConfig(max_new_tokens=20),
                           emitted=h["emitted"])
    res = dst.import_request(
        h["prompt"], h["first_token"], h["k"], h["v"],
        gen=GenerationConfig(max_new_tokens=20), emitted=h["emitted"],
        slot_state=h["slot_state"])
    assert res is not None and res["emitted"] == []
    rest = []
    while dst.has_work():
        rest += dst.step().get(res["request_id"], [])
    dst.flush()
    assert h["emitted"] + rest == _alone(cfg, params, prompt, 20)


@pytest.mark.parametrize("plen,tokens", [(20, 9), (70, 4)])
def test_the_server_holds_a_slots_state_against_the_recurrence(
        model, plen, tokens):
    """``reference_state_check``: the state a sequence's slot holds mid-decode
    beside a neighbour that decodes, exported, is the float32 recurrence over
    the prompt and every emitted token but the last; the stream ends there
    and the engine is left empty."""
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
        got = server.reference_state_check(_tokens(plen, seed=72), tokens)
        other.join(timeout=120)
        assert got["emitted"] >= tokens
        assert got["positions"] == plen + got["emitted"] - 1
        ssm = got["ssm"]
        assert ssm["finite"] and len(ssm["layer_rel_err"]) == cfg.count(
            "mamba")
        # float32 program against the float32 definition
        assert ssm["rel_err"] < 1e-4 and max(ssm["layer_rel_err"]) < 1e-4
        assert not server._engine.has_work()
    finally:
        server.shutdown()


def test_counters_book_the_rows_that_decode(model):
    cfg, params = model
    eng = _engine(cfg, params)
    eng.generate([_tokens(20, seed=61)], GenerationConfig(max_new_tokens=12))
    c = eng.counters()
    assert c["decode_rows"] == 4 * c["decode_token_steps"]
    assert 0 < c["decode_live_rows"] <= c["decode_token_steps"]


# -- the kernels, in interpret mode ------------------------------------------------------


@pytest.mark.parametrize("active", [
    (1, 1, 1, 1, 1, 1), (1, 0, 1, 1, 0, 0), (0, 0, 0, 0, 0, 0),
    (0, 0, 0, 0, 0, 1)], ids=["all", "some", "none", "last"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_layer_step_kernel_matches_the_jnp_branch(active, dtype):
    """The fused call (convolution, ``silu``, delta and decay, the state's
    update, ``D x``, the gate and its norm: everything between the two
    projections) against ``recurrent_step_jnp``, the model's own CPU branch,
    from the same projected rows."""
    cfg = gh.GraniteHybridConfig.tiny(param_dtype=dtype, compute_dtype=dtype)
    rows, li = len(active), 1
    nm, i = cfg.count("mamba"), cfg.d_inner
    mp = gh.init_params(cfg, jax.random.PRNGKey(11))["mamba"]
    mp = dict(mp, norm=1 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(12), mp["norm"].shape).astype(dtype),
        d=jax.random.uniform(jax.random.PRNGKey(13), mp["d"].shape,
                             minval=0.5, maxval=1.5).astype(dtype))
    lp = jax.tree.map(lambda a: a[li], mp)
    ks = jax.random.split(jax.random.PRNGKey(sum(active)), 5)
    state = gh.init_slot_state(cfg, rows)
    ssm = jax.random.normal(ks[0], state["ssm"].shape)
    win = ssm_ops.pack_window(jax.random.normal(
        ks[1], (nm, rows, cfg.mamba_d_conv - 1, cfg.conv_width))
    ).astype(dtype)
    assert win.shape == state["conv"].shape
    proj = jax.random.normal(ks[2], (rows, i + cfg.conv_width)).astype(dtype)
    dt = jax.random.normal(ks[3], (rows, cfg.mamba_n_heads)).astype(dtype)
    act = jnp.asarray(active, jnp.int32)
    y0, s0, w0 = gh.recurrent_step_jnp(
        cfg, lp, proj[:, :i], proj[:, i:], dt, ssm, win[li], li, act)
    small = ssm_ops.prepare_layer_params(
        mp["conv_w"], mp["conv_b"], mp["dt_bias"], mp["a_log"], mp["d"],
        mp["norm"], cfg.mamba_d_head)
    y1, s1, w1 = ssm_ops.ssm_layer_step(
        ssm, win, li, proj, dt, small, act, eps=cfg.rms_norm_eps,
        interpret=True)
    assert y1.dtype == y0.dtype == dtype and y1.shape == (rows, i)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    f32 = np.float32
    live = np.asarray(active, bool)
    np.testing.assert_allclose(np.asarray(y1, f32)[live],
                               np.asarray(y0, f32)[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(w1[li], f32),
                                  np.asarray(w0, f32))
    if live.any():  # the step did something, and the window moved on
        assert np.abs(np.asarray(y1, f32)[live]).max() > 0.1
        np.testing.assert_array_equal(
            np.asarray(ssm_ops.unpack_window(w1[li], cfg.conv_width))[live, -1],
            np.asarray(proj[:, i:])[live])
    # a row that does not decode, and every other layer: bit for bit
    np.testing.assert_array_equal(np.asarray(s1)[:, ~live],
                                  np.asarray(ssm)[:, ~live])
    np.testing.assert_array_equal(np.asarray(w1, f32)[:, ~live],
                                  np.asarray(win, f32)[:, ~live])
    others = [j for j in range(nm) if j != li]
    np.testing.assert_array_equal(np.asarray(s1)[others],
                                  np.asarray(ssm)[others])
    np.testing.assert_array_equal(np.asarray(w1, f32)[others],
                                  np.asarray(win, f32)[others])
    assert not np.asarray(y1, f32)[~live].any()


def test_ssm_layer_step_refuses_what_it_does_not_compute():
    with pytest.raises(NotImplementedError, match="8 B/C groups"):
        ssm_ops.ssm_layer_step(
            jnp.zeros((1, 2, 2, 16, 128)), jnp.zeros((1, 2, 3, 16, 128)), 0,
            jnp.zeros((2, 288)), jnp.zeros((2, 8)), {}, jnp.ones(2, jnp.int32),
            eps=1e-5, n_groups=8)
    assert "heads" in ssm_ops.layer_step_unsupported(128, 64, 128)
    assert ssm_ops.layer_step_unsupported(64, 64, 128) is None


@pytest.mark.parametrize("nh,kv", [(8, 2), (4, 4), (32, 8)])
def test_paged_attention_kernel_at_heads_of_64(nh, kv):
    from ray_tpu.models.llama import _paged_attend
    from ray_tpu.ops.paged_attention import paged_decode_attention

    hd, bs, nb, w, b = 64, 16, 24, 4, 3
    ks = jax.random.split(jax.random.PRNGKey(nh), 3)
    q = jax.random.normal(ks[0], (b, nh, hd))
    pk = jax.random.normal(ks[1], (2, nb, bs, kv * hd))
    pv = jax.random.normal(ks[2], (2, nb, bs, kv * hd))
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, nb))[:b * w].reshape(b, w), jnp.int32)
    lengths = jnp.asarray([5, 40, 63], jnp.int32)
    active = jnp.asarray([1, 1, 0], jnp.int32)
    scale = 0.015625
    got = paged_decode_attention(q, pk, pv, 1, table, lengths, active,
                                 interpret=True, scale=scale)
    cfg = gh.GraniteHybridConfig.tiny(dim=nh * hd, n_heads=nh, n_kv_heads=kv,
                                      mamba_n_heads=nh * 4)
    span = jnp.arange(w * bs)[None, None, :] <= lengths[:, None, None]
    want = _paged_attend(
        cfg, q[:, None], pk[1, table].reshape(b, w * bs, kv, hd),
        pv[1, table].reshape(b, w * bs, kv, hd), span, scale=scale)[:, 0]
    np.testing.assert_allclose(got[:2], want[:2], rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[2]).any()


def test_engine_with_both_kernels_interpreted_matches_the_jnp_path(model):
    cfg, params = model
    prompt = _tokens(40, seed=71)
    eng = _engine(cfg, params, paged_attention_kernel="interpret",
                  max_batch_size=2)
    assert eng._use_kernel and eng._kernel_interpret
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=6))[0]
    assert out == _alone(cfg, params, prompt, 6)


# -- what the engine refuses, by name -----------------------------------------------------


@pytest.mark.parametrize("option", ["speculative_config",
                                    "tensor_parallel_size"])
def test_engine_refuses_what_the_family_does_not_supply(model, option):
    from ray_tpu.llm.config import SpeculativeConfig

    cfg, params = model
    kw = ({"tensor_parallel_size": 2} if option == "tensor_parallel_size"
          else {"speculative_config": SpeculativeConfig(
              draft_model_config=cfg, num_speculative_tokens=2)})
    with pytest.raises(ValueError, match="granite_hybrid family"):
        _engine(cfg, params, **kw)


# -- the configuration file -----------------------------------------------------------------

# the published config.json of granite-4.0-h-micro, the keys a forward pass
# reads
_PUBLISHED = {
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "num_attention_heads": 32, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
    "model_type": "granitemoehybrid",
}


def test_configuration_file_is_the_published_model_whole():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf = json.load(f)
    for k, v in _PUBLISHED.items():
        assert conf[k] == v, k
    assert conf["layer_types"] == [
        "attention" if i % 10 == 5 else "mamba" for i in range(40)]
    assert conf["reduced"] == {} and conf["published"] == {}
    cfg = gh.GraniteHybridConfig.from_published(conf, max_seq_len=4096)
    assert cfg.n_layers == 40 and cfg.n_periods == 4
    assert cfg.runs == (("mamba", 5), ("attention", 1), ("mamba", 4))
    assert cfg.head_dim == 64 and cfg.d_inner == 4096
    assert cfg.num_params == 3_191_396_096
    state = jax.eval_shape(lambda: gh.init_slot_state(cfg, 64))
    assert state["ssm"].shape == (36, 64, 32, 128, 128)
    assert state["ssm"].dtype == jnp.float32
    # three taps of 4352 channels, 128 a row, the rows whole memory tiles
    assert state["conv"].shape == (36, 64, 3, 48, 128)
    pool = jax.eval_shape(lambda: gh.init_paged_cache(cfg, 8, 16))
    assert sum(int(np.prod(x.shape[2:])) * x.shape[0] * 2 // 16
               for x in pool.values()) == 8192  # bytes a cached position
    with pytest.raises(ValueError, match="num_local_experts"):
        gh.GraniteHybridConfig.from_published(
            dict(conf, num_local_experts=72))
