"""The openPangu-Ultra-MoE family (models/pangu_moe.py) against its plain
float32 reference, at a small size on the CPU with seeded random weights:
the latent paged cache through chunked prefill, a prefix hit and decode; the
absorbed form against the expanded; the shares of the expert layer against
the whole; the router against a hand-written case; the latent kernel in
interpret mode; the grouped form of the expert layer against the dense one;
the engine's host tier, handoff and preemption on the latent pool; and the
family seam's refusals."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import GenerationConfig, LLMConfig
from ray_tpu.llm.config import SpeculativeConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import pangu_moe as pm
from ray_tpu.models import pangu_moe_reference as ref
from ray_tpu.models.family import family_of

# float32 program against float32 reference: what differs is the order of
# the sums (scan, tiles, online softmax, absorbed products).  Logits have a
# standard deviation of about 0.3 here; 2e-4 is a thousandth of that, and a
# bf16 cache or weight would miss it by two orders of magnitude.
TOL = 2e-4
BS = 8


@pytest.fixture(scope="module")
def model():
    # 4 of 16 routed experts held: absent experts are left out on both sides
    cfg = pm.PanguMoEConfig.tiny(experts_held=(4, 8))
    return cfg, pm.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(n, seed=0, vocab=256):
    return np.random.RandomState(seed).randint(1, vocab, size=n).tolist()


def _prefill(cfg, params, pool, toks, table, p0=0, chunk=16):
    """Chunked prefill of ``toks`` from position ``p0``: last chunk's logits."""
    rope = pm.make_rope_cache(cfg, cfg.max_seq_len)
    logits = None
    for i in range(0, len(toks), chunk):
        part = toks[i:i + chunk]
        pad = part + [0] * (chunk - len(part))
        logits, pool, _ = pm.prefill_chunk_paged(
            cfg, params, jnp.asarray([pad], jnp.int32), pool, table,
            jnp.int32(p0 + i), rope_cache=rope, kv_tile=16)
        logits = logits[0, :len(part)]
    return logits, pool


@pytest.mark.parametrize("prefix_hit", [False, True])
def test_chunked_prefill_then_decode_agrees_with_reference(model, prefix_hit):
    """(a) three prefill chunks, then four decode steps through the latent
    paged cache, logits against the reference's full forward; ``prefix_hit``:
    the first two chunks' blocks were written by ANOTHER sequence with the
    same first 32 tokens, and this one prefills only its suffix."""
    cfg, params = model
    toks, more = _tokens(44), _tokens(4, seed=9)
    pool = pm.init_paged_cache(cfg, 32, BS)
    table = jnp.asarray([[3, 9, 4, 11, 5, 17, 6, 2]], jnp.int32)
    # one full forward of the reference over prompt + the decoded tokens:
    # its rows from 32 on are what the last chunk and each decode step give
    want = np.asarray(ref.reference_logits(cfg, params, toks + more,
                                           first_row=32))
    if prefix_hit:
        other = toks[:32] + _tokens(8, seed=5)
        _, pool = _prefill(cfg, params, pool, other,
                           jnp.asarray([[3, 9, 4, 11, 20, 21, 22, 23]]))
        logits, pool = _prefill(cfg, params, pool, toks[32:], table, p0=32)
    else:
        logits, pool = _prefill(cfg, params, pool, toks, table)
    np.testing.assert_allclose(logits, want[:12], atol=TOL)
    # decode, teacher-forced with fixed tokens
    for step, tok in enumerate(more):
        logits, pool, _, booked = pm.decode_step_paged(
            cfg, params, jnp.asarray([tok], jnp.int32), pool, table,
            jnp.asarray([44 + step], jnp.int32))
        np.testing.assert_allclose(logits[0], want[12 + step], atol=TOL)
        assert int(booked[0]) == cfg.n_held * cfg.n_moe_layers


def test_absorbed_attention_equals_expanded_on_the_same_cache(model):
    """(b) a decode step (absorbed) over the cache the expanded prefill
    wrote gives the expanded prefill's logits of one more token."""
    cfg, params = model
    toks = _tokens(40, seed=3)
    table = jnp.asarray([[1, 2, 3, 4, 5, 6]], jnp.int32)
    pool = pm.init_paged_cache(cfg, 8, BS)
    _, pool_39 = _prefill(cfg, params, pool, toks[:32], table)
    more, _ = _prefill(cfg, params, pool_39, toks[32:], table, p0=32)
    _, pool_39 = _prefill(cfg, params, pool_39, toks[32:39] + [0], table,
                          p0=32, chunk=8)
    dec, _, _, _ = pm.decode_step_paged(
        cfg, params, jnp.asarray(toks[39:], jnp.int32), pool_39, table,
        jnp.asarray([39], jnp.int32))
    np.testing.assert_allclose(dec[0], more[-1], atol=TOL)


def test_shares_of_the_expert_layer_add_up_to_the_whole(model):
    """(c) 4 chips share the layer's 16 experts, 4 each.  The parts the four
    shares give (program's ``moe_ffn``), the shared expert counted once, add
    up to the uncut reference's layer."""
    cfg, _ = model
    whole = dataclasses.replace(cfg, experts_held=(0, 16))
    lp = jax.tree.map(lambda x: x[0],
                      pm.init_params(whole, jax.random.PRNGKey(3))["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.dim))
    want = ref.moe_layer(whole, h, lambda n, *i: lp[n][i])
    shared = ref.moe_layer(dataclasses.replace(whole, experts_held=(0, 0)),
                           h, lambda n, *i: lp[n][i])
    total, pairs = 0.0, 0
    for lo in range(0, 16, 4):
        share = dataclasses.replace(cfg, experts_held=(lo, lo + 4))
        f = cfg.moe_ffn_dim
        held = dict(lp, we_gate=lp["we_gate"][:, lo * f:(lo + 4) * f],
                    we_up=lp["we_up"][:, lo * f:(lo + 4) * f],
                    we_down=lp["we_down"][lo * f:(lo + 4) * f])
        y, g, _ = pm.moe_ffn(share, h, held)
        # one share's result in the reference too
        np.testing.assert_allclose(
            y, ref.moe_layer(share, h, lambda n, *i, w=held: w[n][i]),
            atol=TOL)
        total = total + (y - shared)
        pairs += int((g > 0).sum())
    np.testing.assert_allclose(total + shared, want, atol=TOL)
    # every (token, expert) pair lands on exactly one share
    assert pairs == 24 * cfg.n_experts_per_tok


# -- the grouped form of the held experts' part --------------------------------
# 16 of 128 experts held, 4 a token: a token lands on half a held expert, as
# at the published widths (16 of 256, 8 a token).


@pytest.fixture(scope="module")
def sparse():
    cfg = pm.PanguMoEConfig.tiny(n_routed_experts=128, experts_held=(16, 32),
                                 max_seq_len=512)
    return cfg, pm.init_params(cfg, jax.random.PRNGKey(5))


def _routing(cfg, h, router, name):
    """Gates ``[T, held]`` of one of four routings."""
    own = pm.held_gates(cfg, *pm.route(cfg, h, router))
    zero = jnp.zeros_like(own)
    return {"the_routers_own": own,
            "every_token_on_one_held_expert": zero.at[:, 3].set(1.3),
            # more pairs than the buffers hold: the dense product answers
            "every_token_on_8_held_experts": zero.at[:, 4:12].set(0.31),
            "no_token_on_a_held_expert": zero}[name]


_ROUTINGS = ("the_routers_own", "every_token_on_one_held_expert",
             "every_token_on_8_held_experts", "no_token_on_a_held_expert")


def _kimi_widths():
    """Two expert layers of two held experts at ``d`` 2304 and ``f`` 1024
    (``models/kimi_linear.py``'s widths; pangu's are 7680 and 2048): the
    down-projection's column tile of 1,920 does not divide 2,304 and falls
    to 1,152."""
    cfg = pm.PanguMoEConfig.tiny(dim=2304, moe_ffn_dim=1024,
                                 n_routed_experts=32, experts_held=(4, 6))
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    d, ef = cfg.dim, cfg.n_held * cfg.moe_ffn_dim
    return cfg, {
        "router": jax.random.normal(ks[0], (2, d, 32)) * 0.02,
        "we_gate": jax.random.normal(ks[1], (2, d, ef)) * 0.02,
        "we_up": jax.random.normal(ks[2], (2, d, ef)) * 0.02,
        "we_down": jax.random.normal(ks[3], (2, ef, d)) * 0.02}


@pytest.mark.parametrize("routing, rows, widths", [
    (routing, rows, "toy") for routing in _ROUTINGS
    for rows in (pm.GROUPED_MIN_ROWS, pm.GROUPED_MIN_ROWS + 136)] + [
        ("the_routers_own", pm.GROUPED_MIN_ROWS, "d2304_f1024")])
def test_grouped_form_equals_dense_form(sparse, routing, rows, widths):
    """The same sum over the chosen pairs alone, whatever the routing: groups
    that span row tiles, a chunk with more pairs than the buffers hold (none
    is dropped), a chunk with none (exactly zero).  Through a layer of the
    stack, as the prefill program reads it, and through one layer's own
    leaves.  At the toy widths, and once at the Kimi family's."""
    cfg, stack = ((sparse[0], sparse[1]["moe"]) if widths == "toy"
                  else _kimi_widths())
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, cfg.dim))
    g = _routing(cfg, h, stack["router"][1], routing)
    lp = {k: stack[k][1] for k in pm.HELD_EXPERT_LEAVES}
    want = pm._routed_dense(cfg, h, g, lp)
    got, fits = jax.jit(lambda h, g: pm._routed_grouped(
        cfg, h, g, stack, 1, True))(h, g)
    pairs = int((g > 0).sum())
    assert (pairs > rows) == (routing == "every_token_on_8_held_experts")
    assert bool(fits) == (pairs <= rows)
    if pairs == 0:
        assert not np.asarray(got).any() and not np.asarray(want).any()
    # float32 sums of 2,304 terms where the toy's are 64
    tol = 1e-6 if widths == "toy" else 1e-5
    np.testing.assert_allclose(got, want, atol=tol)
    assert float(jnp.abs(want).max()) > 1e-3 or pairs == 0
    np.testing.assert_allclose(
        pm._routed_grouped(cfg, h, g, lp, None, True)[0], want, atol=tol)


def test_sort_pairs_by_hand():
    """Tokens 0..3 over 3 held experts: expert 0 chosen by tokens 1 and 3,
    expert 1 by none, expert 2 by tokens 0, 1 and 2."""
    g = jnp.asarray([[0, 0, .5], [.25, 0, .75], [0, 0, 1.], [2., 0, 0]])
    tok, gates, sizes, pairs = pm.sort_pairs(g, 8)
    assert int(pairs) == 5 and sizes.tolist() == [2, 0, 3]
    assert tok.tolist() == [1, 3, 0, 1, 2, 0, 0, 0]
    assert gates.tolist() == [.25, 2., .5, .75, 1., 0, 0, 0]


@pytest.mark.parametrize("rows, decode, grouped", [
    (pm.GROUPED_MIN_ROWS - 128, False, False),
    (pm.GROUPED_MIN_ROWS, False, True), (64, True, True)])
def test_expert_layer_takes_the_grouped_form_by_its_rows_alone(sparse, rows,
                                                               decode,
                                                               grouped):
    """By its rows, or by ``live``.  A prompt chunk below the threshold is
    the dense product and lowers to no kernel; from it on, where the kernel
    applies (here: the interpreter), the grouped product, with the same
    result; a decode token-step's rows (``live`` given, all of them live
    here) take the grouped product at any width.  On this backend without
    the interpreter: dense at any width, in either program."""
    cfg, params = sparse
    lp = jax.tree.map(lambda x: x[0], params["moe"])
    h = jax.random.normal(jax.random.PRNGKey(9), (rows, cfg.dim))
    live = jnp.ones((rows,), jnp.int32) if decode else None
    assert pm.grouped_ffn_from(cfg) is None
    assert pm.grouped_ffn_from(cfg, interpret=True) == pm.GROUPED_MIN_ROWS
    plain = jax.jit(lambda h: pm.moe_ffn(cfg, h, lp, live=live))
    text = plain.lower(h).as_text()
    assert "custom_call" not in text and "pallas" not in text
    jaxpr = str(jax.make_jaxpr(
        lambda h: pm.moe_ffn(cfg, h, lp, True, live=live))(h))
    assert ("pallas_call" in jaxpr) == grouped
    y, g, took = pm.moe_ffn(cfg, h, lp, True, live=live)
    want, g_want, took_want = plain(h)
    np.testing.assert_allclose(y, want, atol=1e-6)
    np.testing.assert_array_equal(g, g_want)
    assert bool(took) == grouped and not bool(took_want)


def test_prefill_chunk_with_grouped_expert_layers_equals_dense(sparse):
    """A chunk at the threshold through the whole prefill program, its expert layers
    grouped (their weights read out of the layer stack by the kernel) and
    dense: the same logits and the same cache rows."""
    cfg, params = sparse
    c = pm.GROUPED_MIN_ROWS
    pool = pm.init_paged_cache(cfg, c // BS + 2, BS, dtype=jnp.float32)
    table = jnp.arange(1, c // BS + 1, dtype=jnp.int32)[None]
    toks = jnp.asarray([_tokens(c, seed=21)], jnp.int32)
    rope = pm.make_rope_cache(cfg, cfg.max_seq_len)
    run = lambda interpret: pm.prefill_chunk_paged(  # noqa: E731
        cfg, params, toks, pool, table, jnp.int32(0), rope_cache=rope,
        kernel_interpret=interpret)
    (want, pool_want, _), (got, pool_got, _) = run(False), run(True)
    np.testing.assert_allclose(got, want, atol=TOL)
    np.testing.assert_allclose(pool_got["ckv"], pool_want["ckv"], atol=TOL)


# -- the grouped form in the decode program: the live rows' pairs alone -----------


def _decode_batch(cfg, live, seed, dead_tokens):
    """A token-step of ``len(live)`` rows over a random latent pool, a row
    its own four blocks: ``(tokens, pool, table, lengths, active)``; the
    rows with ``live`` 0 hold ``dead_tokens``."""
    b = len(live)
    rng = np.random.RandomState(seed)
    pool = {"ckv": jax.random.normal(
        jax.random.PRNGKey(seed), (cfg.n_layers, 4 * b + 1, BS,
                                   cfg.cache_width)) * 0.3}
    table = jnp.arange(1, 4 * b + 1, dtype=jnp.int32).reshape(b, 4)
    active = np.asarray(live, np.int32)
    toks = np.where(active > 0, rng.randint(1, 256, size=b), dead_tokens)
    return (jnp.asarray(toks, jnp.int32), pool, table,
            jnp.asarray(rng.randint(3, 4 * BS - 1, size=b), jnp.int32),
            jnp.asarray(active))


def _decode(cfg, params, batch, interpret):
    toks, pool, table, lengths, active = batch
    return jax.jit(lambda t: pm.decode_step_paged(
        cfg, params, t, pool, table, lengths, active=active,
        kernel_interpret=interpret))(toks)


_LIVE = [1, 0, 0, 1, 1, 0, 0, 0, 1, 0, 0, 0]


def test_decode_step_with_grouped_expert_layers_equals_dense(sparse):
    """(a), (d) A token-step of twelve slots of which four decode, through
    the whole decode program with its expert layers grouped and dense: the
    live rows' logits and cache rows agree, every expert layer-call took the
    grouped product (``moe_grouped_calls``) and the other counters are the
    dense program's.  And what the dead slots hold (a stale token: any id,
    routed anywhere) reaches nothing a live row reads: bit for bit."""
    cfg, params = sparse
    live = np.asarray(_LIVE) > 0
    batch = _decode_batch(cfg, _LIVE, 31, dead_tokens=7)
    want, pool_want, _, booked_want = _decode(cfg, params, batch, False)
    got, pool_got, _, booked = _decode(cfg, params, batch, True)
    np.testing.assert_allclose(got[live], want[live], atol=TOL)
    mine = np.asarray(batch[2])[live].ravel()
    np.testing.assert_allclose(pool_got["ckv"][:, mine],
                               pool_want["ckv"][:, mine], atol=TOL)
    assert booked.tolist() == booked_want.tolist()[:3] + [cfg.n_moe_layers]
    assert booked_want.tolist()[3] == 0
    assert booked.tolist()[0] == cfg.n_held * cfg.n_moe_layers
    assert 0 < booked.tolist()[1] <= booked.tolist()[2] <= (
        4 * cfg.n_experts_per_tok * cfg.n_moe_layers)
    other = _decode_batch(cfg, _LIVE, 31, dead_tokens=np.arange(100, 112))
    again, pool_again, _, booked_again = _decode(cfg, params, other, True)
    np.testing.assert_array_equal(again[live], got[live])
    np.testing.assert_array_equal(pool_again["ckv"][:, mine],
                                  pool_got["ckv"][:, mine])
    assert booked_again.tolist() == booked.tolist()


def test_decode_group_sizes_count_the_live_rows_pairs_alone(sparse,
                                                            monkeypatch):
    """(b) The kernel is handed the live rows' pairs and no other: its
    ``group_sizes`` are the live rows' choices by expert, an expert that
    dead rows alone chose has size 0, and a dead row's routed part is
    exactly zero (its output is the shared expert's)."""
    from ray_tpu.ops import moe_grouped_ffn as kernel

    cfg, params = sparse
    lp = jax.tree.map(lambda x: x[0], params["moe"])
    h = jax.random.normal(jax.random.PRNGKey(17), (len(_LIVE), cfg.dim))
    live = jnp.asarray(_LIVE, jnp.int32)
    seen = []
    real = kernel.moe_grouped_ffn

    def spy(xs, w_gate, w_up, w_down, layer, group_sizes, row_gates, **kw):
        seen.append(np.asarray(group_sizes))
        return real(xs, w_gate, w_up, w_down, layer, group_sizes, row_gates,
                    **kw)

    monkeypatch.setattr(kernel, "moe_grouped_ffn", spy)
    y, g, took = pm.moe_ffn(cfg, h, lp, True, live=live)
    every = np.asarray(pm.held_gates(cfg, *pm.route(cfg, h, lp["router"])))
    alive = np.asarray(_LIVE) > 0
    assert bool(took) and len(seen) == 1
    np.testing.assert_array_equal(seen[0], (every[alive] > 0).sum(0))
    dead_only = (every[~alive] > 0).any(0) & ~(every[alive] > 0).any(0)
    assert dead_only.any() and not seen[0][dead_only].any()
    np.testing.assert_array_equal(np.asarray(g)[~alive], 0)
    shared_only = pm.moe_ffn(cfg, h, lp, True, live=jnp.zeros_like(live))[0]
    np.testing.assert_allclose(np.asarray(y)[~alive],
                               np.asarray(shared_only)[~alive], atol=1e-6)
    assert np.abs(np.asarray(y - shared_only)[alive]).max() > 1e-3


@pytest.mark.parametrize("live, fits", [([1] * 12, False),
                                        ([1, 0, 1, 0, 0, 1] + [0] * 6, True)])
def test_decode_rows_with_more_pairs_than_the_buffer_take_the_dense_product(
        sparse, monkeypatch, live, fits):
    """(c) Every row on four held experts (all it may choose): twelve live
    rows make 48 pairs for a buffer of 16, take the dense product with no
    pair dropped, and ``moe_grouped_calls`` does not count those
    layer-calls; with three live rows of the twelve the same routing fits
    (12 pairs): the dead rows' pairs are not there to overflow it."""
    cfg, params = sparse

    def all_held(cfg, h, router):
        idx = jnp.broadcast_to(jnp.arange(18, 22), (h.shape[0], 4))
        return jnp.full(idx.shape, 0.4, jnp.float32) + h[:, :4] * 0.01, idx

    monkeypatch.setattr(pm, "route", all_held)
    alive = np.asarray(live) > 0
    batch = _decode_batch(cfg, live, 41, dead_tokens=3)
    want, _, _, booked_want = _decode(cfg, params, batch, False)
    got, _, _, booked = _decode(cfg, params, batch, True)
    np.testing.assert_allclose(got[alive], want[alive], atol=TOL)
    pairs = 4 * int(alive.sum()) * cfg.n_moe_layers
    assert booked.tolist() == [cfg.n_held * cfg.n_moe_layers,
                               4 * cfg.n_moe_layers, pairs,
                               cfg.n_moe_layers if fits else 0]
    assert booked_want.tolist() == booked.tolist()[:3] + [0]


def test_router_against_a_hand_written_case():
    """(d) sigmoid, 2 of 6, normalised over the chosen, x 2.5, no groups."""
    cfg = pm.PanguMoEConfig.tiny(dim=2, n_routed_experts=6,
                                 n_experts_per_tok=2, experts_held=(1, 3))
    router = jnp.asarray([[1.0, 0.0, -1.0, 2.0, 0.5, -2.0],
                          [0.0, 1.0, 1.0, -2.0, 0.5, 2.0]])
    h = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    gates, idx = pm.route(cfg, h, router)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))
    # token 0: logits (1, 0, -1, 2, .5, -2): experts 3 and 0
    # token 1: logits (0, 1, 1, -2, .5, 2): expert 5, then 1 (first of a tie)
    # token 2: logits (1, 1, 0, 0, 1, 0): experts 0 and 1 (first of a tie)
    assert idx.tolist() == [[3, 0], [5, 1], [0, 1]]
    for row, zs in zip(np.asarray(gates), ([2.0, 1.0], [2.0, 1.0], [1.0, 1.0])):
        s = np.array([sig(z) for z in zs])
        np.testing.assert_allclose(row, 2.5 * s / s.sum(), rtol=1e-6)
    assert cfg.routed_scaling_factor == 2.5
    # the held range (experts 1 and 2) sees token 1's and token 2's expert 1
    g = np.asarray(pm.held_gates(cfg, gates, idx))
    assert g.shape == (3, 2) and (g[:, 1] == 0).all() and g[0, 0] == 0
    np.testing.assert_allclose(g[1:, 0], np.asarray(gates)[1:, 1])


@pytest.mark.parametrize("heads", [4, 32])
def test_latent_kernel_in_interpret_mode_against_the_gather_path(heads):
    """(e) the Pallas kernel over the latent pool, live pages only: rows of
    different lengths, an idle row, table columns past a row's pages that
    point at a block of NaN (never read).  At the toy config's 4 heads and
    at 32 (``models/kimi_linear.py`` calls it with 32, pangu with 128)."""
    from ray_tpu.ops.mla_paged_attention import mla_paged_decode_attention

    cfg = pm.PanguMoEConfig.tiny(kv_lora_rank=128, qk_rope_head_dim=8,
                                 n_heads=heads)
    assert cfg.cache_width == 256
    rng = np.random.RandomState(0)
    nb, b, wt = 24, 4, 8
    pool = rng.randn(2, nb, BS, cfg.cache_width).astype(np.float32)
    pool[..., cfg.latent_width:] = 0
    pool[:, nb - 1] = np.nan
    lengths = np.asarray([37, 5, 0, 50], np.int32)
    active = np.asarray([1, 1, 0, 1], np.int32)
    table = np.full((b, wt), nb - 1, np.int32)
    blocks = iter(rng.permutation(np.arange(1, nb - 1)))
    for r in range(b):
        for j in range(lengths[r] // BS + 1):
            table[r, j] = next(blocks)
    q = rng.randn(b, cfg.n_heads, cfg.cache_width).astype(np.float32)
    q[..., cfg.latent_width:] = 0
    got = mla_paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), 1, jnp.asarray(table),
        jnp.asarray(lengths), jnp.asarray(active),
        value_width=cfg.kv_lora_rank, scale=1.0 / cfg.qk_head_dim ** 0.5,
        interpret=True)
    clean = np.nan_to_num(pool[1])
    span = clean[table].reshape(b, wt * BS, cfg.cache_width)
    mask = np.arange(wt * BS)[None, None, :] <= lengths[:, None, None]
    want = pm._attend_absorbed(cfg, jnp.asarray(q)[:, None],
                               jnp.asarray(span), jnp.asarray(mask))[:, 0]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got)[active > 0],
                               np.asarray(want)[active > 0], atol=1e-4)
    assert (np.asarray(got)[2] == 0).all()


# -- the engine over the latent pool ---------------------------------------------


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", BS)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("max_seq_len", 96)
    return PagedJaxLLMEngine(LLMConfig(model_config=cfg, **kw), params=params)


def _assert_greedy(cfg, params, prompt, out, n):
    """``out`` is the reference's greedy continuation of ``prompt``: each
    token is the float32 reference's argmax at its position, teacher-forced
    (one forward pass; a token inside TOL of the argmax is a tie)."""
    assert len(out) == n
    rows = np.asarray(ref.reference_logits(
        cfg, params, list(prompt) + list(out[:-1]),
        first_row=len(prompt) - 1))
    gaps = rows.max(-1) - rows[np.arange(n), np.asarray(out)]
    assert gaps.max() <= TOL, gaps


def test_engine_serves_the_family_and_books_its_counters(model):
    cfg, params = model
    eng = _engine(cfg, params, num_blocks=40)
    assert eng.family is family_of(cfg) and eng.cache_leaves == ("ckv",)
    prompt = _tokens(37, seed=11)
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=6))[0]
    _assert_greedy(cfg, params, prompt, out, 6)
    c = eng.counters()
    assert c["moe_experts_held"] >= 5 * cfg.n_held * cfg.n_moe_layers
    assert 0 < c["moe_experts_hit"] <= c["moe_experts_held"]
    assert c["moe_experts_hit"] <= c["moe_pairs_here"]
    assert c["moe_grouped_calls"] == 0  # no kernel on this backend: dense
    # a second request with the same first 32 tokens is a prefix hit
    again = eng.generate([prompt[:32] + _tokens(5, seed=12)],
                         GenerationConfig(max_new_tokens=2))
    assert len(again[0]) == 2 and eng.counters()["prefix_hit_tokens"] == 32
    assert eng.utilization()["kv_blocks"]["cached"] > 0


def test_engine_kernel_interpret_path_matches_gather(model):
    cfg = pm.PanguMoEConfig.tiny(kv_lora_rank=128, experts_held=(4, 8))
    params = pm.init_params(cfg, jax.random.PRNGKey(2))
    prompt = _tokens(21, seed=13)
    gen = GenerationConfig(max_new_tokens=5)
    gather = _engine(cfg, params, paged_attention_kernel=False)
    kernel = _engine(cfg, params, paged_attention_kernel="interpret")
    assert kernel._use_kernel and kernel._kernel_interpret
    assert kernel.generate([prompt], gen) == gather.generate([prompt], gen)
    # one live row of the four: its pairs (four at most) fit the buffer of
    # 16, so every expert layer-call of every token-step went grouped
    c = kernel.counters()
    assert c["moe_grouped_calls"] * cfg.n_held == c["moe_experts_held"] > 0
    assert gather.counters()["moe_grouped_calls"] == 0


def test_host_tier_round_trip_on_the_latent_pool(model):
    """(f) a pool too small for two prompts' blocks: the first prompt's
    cached blocks are evicted to the host tier and revived for its second
    asking, and the answer is the one computed cold."""
    cfg, params = model
    gen = GenerationConfig(max_new_tokens=3)
    first, second = _tokens(40, seed=21), _tokens(40, seed=22)
    eng = _engine(cfg, params, num_blocks=9, max_batch_size=1)
    cold = eng.generate([first], gen)[0]
    eng.generate([second], gen)
    assert eng.counters()["kv_demotions"] > 0
    assert len(eng._host_cache) > 0
    assert eng.generate([first], gen)[0] == cold
    assert eng.counters()["prefix_hit_tokens"] > 0


def test_export_import_round_trip_on_the_latent_pool(model):
    """(f) a request exported mid-decode and imported into a second engine
    goes on to the tokens the first would have given."""
    cfg, params = model
    prompt = _tokens(29, seed=31)
    src, dst = _engine(cfg, params), _engine(cfg, params)
    rid = src.add_request(prompt, GenerationConfig(max_new_tokens=8))
    got = []
    while len(got) < 3:
        got += src.step().get(rid, [])
    h = src.export_request(rid)
    assert set(h) >= {"ckv", "emitted", "prompt"} and "k" not in h
    assert h["ckv"].shape[0] == cfg.n_layers
    assert h["ckv"].shape[-1] == cfg.cache_width
    res = dst.import_request(
        h["prompt"], h["first_token"], {"ckv": h["ckv"]},
        gen=GenerationConfig(max_new_tokens=8), emitted=h["emitted"])
    assert res is not None and res["emitted"] == []
    rest = []
    while dst.has_work():
        rest += dst.step().get(res["request_id"], [])
    dst.flush()
    _assert_greedy(cfg, params, prompt, h["emitted"] + rest, 8)
    with pytest.raises(ValueError, match="cache leaves"):
        dst.import_request(h["prompt"], h["first_token"], h["ckv"], h["ckv"])


def test_preemption_by_recompute_on_the_latent_pool(model):
    """(f) two requests outgrow a pool that holds one: the younger is
    preempted, recomputed, and both give the reference's tokens."""
    cfg, params = model
    prompts = [_tokens(30, seed=41), _tokens(30, seed=42)]
    eng = _engine(cfg, params, num_blocks=12, max_batch_size=2,
                  host_kv_cache_bytes=0)
    outs = eng.generate(prompts, GenerationConfig(max_new_tokens=24))
    assert eng.counters()["preemptions"] > 0
    for p, o in zip(prompts, outs):
        _assert_greedy(cfg, params, p, o, 24)


@pytest.mark.parametrize("option, match", [
    (dict(tensor_parallel_size=2), "pangu_moe family supplies no tensor"),
    (dict(speculative_config=SpeculativeConfig(
        draft_model_config=pm.PanguMoEConfig.tiny())),
     "pangu_moe family supplies no decode window"),
])
def test_engine_refuses_what_the_family_does_not_supply(model, option, match):
    cfg, params = model
    with pytest.raises(ValueError, match=match):
        _engine(cfg, params, **option)


def test_family_of_an_unknown_config_names_the_families():
    with pytest.raises(TypeError, match="LlamaConfig.*PanguMoEConfig"):
        family_of(object())


def test_parameter_count_at_the_published_widths():
    """The benchmark's cut: 1 dense layer, 4 expert layers of 16 held
    experts, 19,200 rows of vocabulary: 4.92 B parameters."""
    cfg = pm.PanguMoEConfig()
    attn = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
            + 2 * 128 * 128 * 512 + 128 * 128 * 7680)
    norms = 4 * 7680 + 1536 + 512
    dense = attn + norms + 3 * 7680 * 18432
    moe = attn + norms + 7680 * 256 + 17 * 3 * 7680 * 2048
    assert round(attn / 1e6, 1) == 196.6
    assert cfg.num_params == dense + 4 * moe + 2 * 19200 * 7680 + 7680
    assert 4.91e9 < cfg.num_params < 4.93e9
