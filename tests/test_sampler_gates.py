"""The sampler pays for what its rows ask (``llm/engine.py``): the top-k over
the vocabulary and the categorical draw run under conditionals on what the
batch's rows hold, and give every caller what the ungated formulas gave,
bit for bit and for the same key.  The ungated formulas are kept here as
they stood before the gates.  Tier-1 lane: runs on every commit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig, PagedJaxLLMEngine
from ray_tpu.llm.engine import (
    _MAX_TOP_K,
    _sample,
    _sample_dist,
    _sampler_gates,
)
from ray_tpu.models.llama import LlamaConfig, init_params

# -- (a) the ungated formulas, as engine.py had them --------------------------


def _ungated_masked_scaled(logits, temps, top_ks):
    t = jnp.where(temps > 0.0, temps, 1.0)[:, None]
    scaled = logits / t
    kmax = min(_MAX_TOP_K, logits.shape[-1])
    topv, _ = jax.lax.top_k(scaled, kmax)
    idx = jnp.clip(top_ks - 1, 0, kmax - 1)
    kth = jnp.take_along_axis(topv, idx[:, None], axis=-1)
    return jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)


def _ungated_sample(logits, key, temps, top_ks):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _ungated_masked_scaled(logits, temps, top_ks)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _ungated_sample_dist(logits, temps, top_ks):
    probs = jax.nn.softmax(_ungated_masked_scaled(logits, temps, top_ks),
                           axis=-1)
    one_hot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                             dtype=probs.dtype)
    return jnp.where(temps[:, None] <= 0.0, one_hot, probs)


_B = 8
# (temperatures, top-ks) of the batch's rows, and the branches they engage
_BATCHES = {
    "all_greedy": ([0.0] * _B, [0] * _B, (False, False)),
    "temperature_no_top_k": ([0.7, 1.0, 1.5, 0.3] * 2, [0] * _B,
                             (True, False)),
    "greedy_and_temperature": ([0.0, 0.9] * 4, [0] * _B, (True, False)),
    "all_top_k": ([0.8, 1.2] * 4, [1, 5, 40, 64, 3, 50, 17, 2], (True, True)),
    "one_top_k_among_greedy": ([0.0] * 5 + [1.5] + [0.0] * 2,
                               [0] * 5 + [50] + [0] * 2, (True, True)),
    # a greedy row's top-k is never read: it asks for none
    "greedy_rows_with_top_k": ([0.0] * _B, [50] * _B, (False, False)),
}
# the head's two precisions: float32 (granite) and bf16 (the others)
_WIDTHS = {512: jnp.float32, 32768: jnp.bfloat16}


def _batch(name, width):
    temps, top_ks, gates = _BATCHES[name]
    logits = jnp.asarray(
        np.random.RandomState(width % 97).randn(_B, width) * 3.0,
        _WIDTHS[width])
    return (logits, jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32), gates)


@pytest.mark.parametrize("width", sorted(_WIDTHS))
@pytest.mark.parametrize("batch", sorted(_BATCHES))
@pytest.mark.parametrize("what", ["sample", "sample_dist"])
def test_gated_sampler_equals_the_ungated_formulas_bit_for_bit(
        what, batch, width):
    logits, temps, top_ks, gates = _batch(batch, width)
    assert tuple(bool(g) for g in _sampler_gates(temps, top_ks)) == gates
    if what == "sample":
        for seed in (0, 7):
            key = jax.random.PRNGKey(seed)
            got = jax.jit(_sample)(logits, key, temps, top_ks)
            want = jax.jit(_ungated_sample)(logits, key, temps, top_ks)
            assert got.dtype == want.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        got = jax.jit(_sample_dist)(logits, temps, top_ks)
        want = jax.jit(_ungated_sample_dist)(logits, temps, top_ks)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_row_that_left_holds_no_gate_and_moves_no_live_row():
    """``live`` takes the rows that do not decode out of both predicates;
    the rows that do decode get what they got without it."""
    logits, temps, top_ks, _ = _batch("one_top_k_among_greedy", 512)
    live = jnp.asarray([1, 1, 1, 1, 1, 0, 1, 1], jnp.int32)
    assert [bool(g) for g in _sampler_gates(temps, top_ks, live)] == \
        [False, False]
    assert [bool(g) for g in _sampler_gates(temps, top_ks, 1 - live)] == \
        [True, True]
    key = jax.random.PRNGKey(3)
    want = np.asarray(jax.jit(_ungated_sample)(logits, key, temps, top_ks))
    got = np.asarray(jax.jit(_sample)(logits, key, temps, top_ks, live))
    keep = np.asarray(live) > 0
    np.testing.assert_array_equal(got[keep], want[keep])
    # mixed: a live sampled row beside the one that left draws what it drew
    temps = temps.at[0].set(0.9)
    want = np.asarray(jax.jit(_ungated_sample)(logits, key, temps, top_ks))
    got = np.asarray(jax.jit(_sample)(logits, key, temps, top_ks, live))
    np.testing.assert_array_equal(got[keep], want[keep])
    dist = np.asarray(jax.jit(_sample_dist)(logits, temps, top_ks, live))
    np.testing.assert_array_equal(
        dist[keep],
        np.asarray(jax.jit(_ungated_sample_dist)(logits, temps,
                                                  top_ks))[keep])


# -- (b) where the expensive primitives sit in the program --------------------


def _primitives(jaxpr, in_cond=False, out=None):
    """``{(primitive name, inside some cond's branch)}`` over ``jaxpr`` and
    every jaxpr nested in it."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        out.add((eqn.primitive.name, in_cond))
        inner = in_cond or eqn.primitive.name == "cond"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, inner, out)
    return out


_GATED = ("top_k", "random_bits")


@pytest.mark.parametrize("what", ["sample", "sample_dist"])
def test_top_k_and_the_draw_sit_inside_conditionals_only(what):
    logits, temps, top_ks, _ = _batch("all_top_k", 512)
    if what == "sample":
        jaxpr = jax.make_jaxpr(_sample)(logits, jax.random.PRNGKey(0), temps,
                                        top_ks)
        gated = _GATED
    else:
        jaxpr = jax.make_jaxpr(_sample_dist)(logits, temps, top_ks)
        gated = _GATED[:1]
    found = _primitives(jaxpr.jaxpr)
    for name in gated:
        assert (name, True) in found, f"{name} is not in the program at all"
        assert (name, False) not in found, f"{name} runs outside a cond"
    # the walker does see such a primitive where it is at the top level
    ungated = _primitives(jax.make_jaxpr(_ungated_sample)(
        logits, jax.random.PRNGKey(0), temps, top_ks).jaxpr)
    assert all((name, False) in ungated for name in _GATED)


def test_the_verifiers_vmap_keeps_one_conditional():
    """``_spec_verify_impl`` maps ``_sample_dist`` over the window's
    positions with ``temps`` / ``top_ks`` closed over: the predicate is not
    batched, so the conditional stays one (a batched predicate would turn
    it into a select that runs both branches)."""
    logits, temps, top_ks, _ = _batch("all_top_k", 512)
    window = jnp.stack([logits, logits * 0.5, -logits], axis=1)  # [B, 3, V]
    fn = jax.vmap(lambda lg: _sample_dist(lg, temps, top_ks), in_axes=1,
                  out_axes=1)
    found = _primitives(jax.make_jaxpr(fn)(window).jaxpr)
    assert ("top_k", True) in found and ("top_k", False) not in found
    want = jax.vmap(lambda lg: _ungated_sample_dist(lg, temps, top_ks),
                    in_axes=1, out_axes=1)
    np.testing.assert_array_equal(np.asarray(jax.jit(fn)(window)),
                                  np.asarray(jax.jit(want)(window)))


# -- (c) the engine's counters and the device's predicate ---------------------


@pytest.fixture(scope="module")
def tiny_cfg():
    return LlamaConfig.tiny(compute_dtype=jnp.float32, max_seq_len=256)


@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return init_params(tiny_cfg, jax.random.PRNGKey(0))


def _engine(tiny_cfg, tiny_params):
    return PagedJaxLLMEngine(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=128,
                  block_size=8, prefill_chunk=16, decode_chunk=2,
                  enable_prefix_caching=False), params=tiny_params)


def _prompt(seed, n=12):
    return [int(t) for t in np.random.RandomState(seed).randint(1, 255, n)]


def _gates_on_device(eng):
    return [bool(g) for g in _sampler_gates(eng._d_temp, eng._d_topk,
                                            eng._d_active)]


def _sampler_counters(eng):
    c = eng.counters()
    return c["decode_sampled_token_steps"], c["decode_topk_token_steps"]


def test_a_greedy_run_books_no_sampled_and_no_top_k_token_step(
        tiny_cfg, tiny_params):
    eng = _engine(tiny_cfg, tiny_params)
    for seed in (0, 1):
        eng.add_request(_prompt(seed), GenerationConfig(max_new_tokens=9))
    # a greedy caller that names a top-k asks the sampler for nothing
    eng.add_request(_prompt(2), GenerationConfig(max_new_tokens=9, top_k=40))
    while eng.has_work():
        eng.step()
        assert _gates_on_device(eng) == [False, False]
    eng.flush()
    c = eng.counters()
    assert c["decode_token_steps"] > 0
    assert _sampler_counters(eng) == (0, 0)
    assert set(eng.utilization()["counters"]) >= {
        "decode_sampled_token_steps", "decode_topk_token_steps"}


@pytest.mark.parametrize("top_k", [50, 0])
def test_the_counters_grow_exactly_while_a_sampling_row_decodes(
        tiny_cfg, tiny_params, top_k):
    """Two greedy rows decode throughout; a ``temperature=1.5`` request
    (with and without ``top_k=50``) joins them and leaves.  Both counters
    (the first alone where it asks no top-k) grow while it is live; once
    its slot has left and the dispatch in flight is collected neither grows
    again, and the device's predicates, read from the mirrors the decode
    program reads, are false: the slot that left holds none."""
    eng = _engine(tiny_cfg, tiny_params)
    out = {}
    for seed in (0, 1):
        out[eng.add_request(_prompt(seed),
                            GenerationConfig(max_new_tokens=60))] = []

    def step():
        for rid, toks in eng.step().items():
            out[rid].extend(toks)

    while min(len(t) for t in out.values()) < 3:
        step()
    assert _sampler_counters(eng) == (0, 0)
    assert _gates_on_device(eng) == [False, False]

    rid = eng.add_request(_prompt(2), GenerationConfig(
        max_new_tokens=8, temperature=1.5, top_k=top_k))
    out[rid] = []
    seen_on_device = False
    while len(out[rid]) < 8:
        step()
        seen_on_device |= _gates_on_device(eng) == [True, top_k > 0]
    assert seen_on_device, "the sampling row never held the predicate"
    step()  # the dispatch that was in flight when its last token came
    sampled, topk = _sampler_counters(eng)
    assert sampled > 0 and (topk > 0) == (top_k > 0)
    assert topk <= sampled <= eng.counters()["decode_token_steps"]

    before = eng.counters()["decode_token_steps"]
    for _ in range(4):
        step()
        assert _gates_on_device(eng) == [False, False]
    assert eng.counters()["decode_token_steps"] > before, \
        "the greedy rows were to decode on"
    assert _sampler_counters(eng) == (sampled, topk)
    # an upload of every row (after a preemption, a cancel, an import) puts
    # what the host remembers of the slot back: its last request's
    # temperature.  The predicates count the rows that decode, so it holds
    # none all the same
    eng._mark_dirty("flush")
    step()
    assert float(np.asarray(eng._d_temp).max()) == 1.5
    assert _gates_on_device(eng) == [False, False]
    step()
    assert _sampler_counters(eng) == (sampled, topk)
    assert all(len(out[r]) < 60 for r in out if r != rid)
