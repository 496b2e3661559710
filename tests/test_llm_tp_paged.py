"""Tensor-parallel PAGED serving (ISSUE 20): the sharded engine must be a
pure data-layout change — bit-identical greedy tokens vs the single-device
engine — while the per-layer decode allreduces provably route through the
α-β collective planner (ISSUE 10) and the new TP metric families book only
on the sharded path.

tests/test_llm_tp.py is the slow lane (file-wide marker: pipeline stages,
mid-stream admission, sampling, validation); this file is the tier-1 lane
for the engine's explicit planned collectives, so the parity pins run on
every commit.
Engines are module-scoped — the 8-virtual-device CPU mesh compile is paid
once per variant, not per test.
"""

import jax
import numpy as np
import pytest

from ray_tpu._private import device_telemetry, runtime_metrics
from ray_tpu.llm import LoRAConfig, init_lora, merge_lora
from ray_tpu.llm.config import GenerationConfig, LLMConfig, SpeculativeConfig
from ray_tpu.llm.paged import PagedJaxLLMEngine
from ray_tpu.models import llama

# prompts straddle the prefill_chunk=16 boundary: one short, one exactly a
# block, one spanning three chunks (34 tokens → chunked prefill interleaves
# with decode, the scheduling path most likely to expose sharding drift)
PROMPTS = [[1, 2, 3, 4, 5], [9, 8, 7, 6, 5, 4, 3, 2],
           list(np.random.RandomState(20).randint(1, 255, size=34))]
GEN = GenerationConfig(max_new_tokens=12)


def _mk(cfg, params, tp, **kw):
    dp = kw.pop("dp", None)
    base = dict(model_config=cfg, tensor_parallel_size=tp, max_batch_size=4,
                max_seq_len=128, block_size=8, prefill_chunk=16)
    base.update(kw)
    return PagedJaxLLMEngine(LLMConfig(**base), params=params,
                             draft_params=dp)


@pytest.fixture(scope="module")
def setup():
    cfg = llama.LlamaConfig.tiny(n_kv_heads=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    e1 = _mk(cfg, params, 1)
    before = runtime_metrics.plan_snapshot()
    e2 = _mk(cfg, params, 2)
    after = runtime_metrics.plan_snapshot()
    plan_delta = {k: after.get(k, 0.0) - before.get(k, 0.0)
                  for k in after if after.get(k) != before.get(k, 0.0)}
    ref = e1.generate(PROMPTS, GEN)
    return cfg, params, e1, e2, ref, plan_delta


@pytest.mark.parametrize("tp", [2, 4])
def test_tp_greedy_bit_identical(setup, tp):
    """The acceptance gate: sharded decode (explicit planned collectives,
    overlap on — the defaults) emits exactly the single-device tokens,
    across chunked-prefill boundaries and continuous batching."""
    cfg, params, e1, e2, ref, _ = setup
    eng = e2 if tp == 2 else _mk(cfg, params, tp)
    assert eng.generate(PROMPTS, GEN) == ref


def test_plan_counters_name_algorithm_and_reason(setup):
    """Decode allreduces provably route through the planner: building the
    sharded engine books one flat/latency_bound decision per program kind
    (decode + prefill here) into ray_tpu_collective_plan_total — decode
    messages are KiB-scale, firmly in the planner's latency-bound regime."""
    *_, plan_delta = setup
    assert plan_delta.get("flat/latency_bound", 0.0) >= 2.0, plan_delta


def test_planned_rows_surface(setup):
    """plan_explain snapshot rides the engine (bench busbw column source):
    per-kind nbytes, chosen algorithm, and the modeled α-β costs."""
    rows = setup[3]._tp_collectives
    assert set(rows) == {"decode", "prefill"}
    for row in rows.values():
        assert row["chosen"] == "flat" and row["reason"] == "latency_bound"
        assert row["nbytes"] > 0
        assert set(row["modeled_cost_s"]) >= {"flat", "ring", "tree"}


def test_overlap_off_bit_equal(setup):
    """lax.optimization_barrier token-chaining is schedule-only: overlap
    off must be bit-identical (same pin make_train_step carries)."""
    cfg, params, _, _, ref, _ = setup
    assert _mk(cfg, params, 2, tp_overlap_collectives=False).generate(
        PROMPTS, GEN) == ref


@pytest.fixture(scope="module")
def tiles_ref(setup):
    """One device, a table of three KV tiles, a prompt that crosses one."""
    cfg, params, *_ = setup
    assert 3 * llama.PREFILL_KV_TILE == 1536
    prompts = [PROMPTS[0],
               list(np.random.RandomState(28).randint(1, 255, size=600))]
    kw = dict(max_seq_len=1536, block_size=16, prefill_chunk=256,
              num_blocks=128)
    gen = GenerationConfig(max_new_tokens=6)
    return kw, prompts, gen, _mk(cfg, params, 1, **kw).generate(prompts, gen)


@pytest.mark.parametrize("overlap", [True, False])
def test_tp4_prefill_tile_loop_bit_identical(setup, tiles_ref, overlap):
    """The prefill chunk's loop over KV tiles (dynamic trip count, online
    softmax state carried between tiles) compiles for four shards with the
    KV heads split over them, with the planned collectives chained through
    the token and without, and emits the single-device tokens."""
    cfg, params, *_ = setup
    kw, prompts, gen, ref = tiles_ref
    eng = _mk(cfg, params, 4, tp_overlap_collectives=overlap, **kw)
    assert eng.generate(prompts, gen) == ref
    c = eng.counters()
    # 5 tokens: one tile; 600: chunks ending at 256, 512 and 640 (two tiles)
    assert c["prefill_visited_pages"] == 32 * (1 + 1 + 1 + 2)
    assert c["prefill_live_pages"] == 1 + 16 + 32 + 38


def test_forced_ring_bit_equal(setup):
    """The tp_collective_algorithm force knob routes the ring program
    (psum_scatter + all_gather) — bitwise-equal to flat psum, so forcing
    the bandwidth algorithm at latency sizes only costs time."""
    cfg, params, _, _, ref, _ = setup
    eng = _mk(cfg, params, 2, tp_collective_algorithm="ring")
    assert eng._tp_collectives["decode"]["reason"] == "forced"
    assert eng.generate(PROMPTS, GEN) == ref


def test_tp_metrics_book_only_on_sharded_path(setup):
    """ray_tpu_serve_tp_collective_{seconds,bytes_total} book on the
    sharded engine and stay SILENT on the single-device one (the
    disabled-path byte-identity pin: tp=1 serving is untouched)."""
    cfg, params, e1, e2, _, _ = setup

    def flat_bytes():
        snap = runtime_metrics.tp_collective_snapshot()
        return sum(a.get("flat", {}).get("bytes", 0.0)
                   for a in snap.values())

    b0 = flat_bytes()
    e1.generate(PROMPTS[:1], GEN)
    assert flat_bytes() == b0  # unsharded books nothing
    e2.generate(PROMPTS[:1], GEN)
    assert flat_bytes() > b0  # sharded path books under the flat algorithm


def test_decode_compile_count_pinned(setup):
    """The sharded decode hot loop must not recompile per step: one warm
    round compiles one entry per distinct tail-chunk width (dispatch pads
    batch to max_batch, so widths are the only axis), and a second round
    over DIFFERENT prompt lengths adds zero new entries."""
    cfg, params, _, e2, _, _ = setup
    e2.generate([[5, 4, 3], [2, 2, 2, 2, 2, 2, 2]], GEN)
    warm = e2._decode._cache_size()
    e2.generate([[9, 9], [1, 2, 3, 4, 5, 6], [8, 8, 8]], GEN)
    assert e2._decode._cache_size() == warm, "sharded decode recompiled"


def test_utilization_mesh_aware(setup):
    """utilization() must report PER-DEVICE KV/weights bytes under TP —
    the chip-telemetry HBM digests otherwise over-report free HBM by the
    TP degree (each device holds 1/N of the pool, not all of it)."""
    _, _, e1, e2, _, _ = setup
    row = e2.utilization()
    tp = row["tp"]
    assert tp["degree"] == 2 and tp["mesh_shape"] == {"tensor": 2}
    assert tp["mesh_devices"] == 2
    # the pool shards its kv-head dim: per-device = global / 2, and the
    # single-device engine's pool is the global reference
    assert tp["kv_bytes_per_device"] * 2 == device_telemetry.tree_nbytes(
        e2.pool)
    assert tp["kv_bytes_per_device"] * 2 == device_telemetry.tree_nbytes(
        e1.pool)
    assert 0 < tp["weights_bytes_per_device"] < device_telemetry.tree_nbytes(
        e1.params)
    assert "tp" not in e1.utilization()


def test_specdec_tp2_bit_identical(setup):
    """Spec-dec composes: the draft stays replicated (zero collectives in
    draft programs) while decode_window_paged verifies sharded — greedy
    output bit-identical to the single-device speculative engine."""
    cfg, params, *_ = setup
    dcfg = llama.LlamaConfig.tiny(n_kv_heads=2, n_layers=1)
    dparams = llama.init_params(dcfg, jax.random.PRNGKey(8))
    spec = SpeculativeConfig(draft_model_config=dcfg,
                             num_speculative_tokens=3)
    ref = _mk(cfg, params, 1, speculative_config=spec,
              dp=dparams).generate(PROMPTS[:2], GEN)
    e2 = _mk(cfg, params, 2, speculative_config=spec, dp=dparams)
    assert e2._tp_collectives["verify"]["chosen"] == "flat"
    # draft params replicated, not sharded: full copy on every device
    wq = e2._draft_params["layers"]["wq"]
    assert wq.addressable_shards[0].data.shape == wq.shape
    assert e2.generate(PROMPTS[:2], GEN) == ref


def test_lora_merged_tp2_bit_identical(setup):
    """LoRA composes: an adapter merged into the base weights shards like
    any other params tree — merged tp=2 output bit-identical to merged
    tp=1 (the multi-LoRA serve path builds exactly these engines)."""
    cfg, params, *_ = setup
    adapter = init_lora(cfg, LoRAConfig(rank=4, alpha=32.0),
                        jax.random.PRNGKey(3))
    adapter["layers"]["wq"]["B"] = (
        jax.random.normal(jax.random.PRNGKey(4),
                          adapter["layers"]["wq"]["B"].shape) * 0.5)
    merged = merge_lora(params, adapter)
    ref = _mk(cfg, merged, 1).generate(PROMPTS[:2], GEN)
    assert _mk(cfg, merged, 2).generate(PROMPTS[:2], GEN) == ref


# -- sharded-pool disaggregated handoff (export/import) ---------------------


def _handoff(src, dst, prompt, gen):
    """Run 2 steps on src, export, import into dst, finish; returns the
    full token stream (export's drain resolves the in-flight chunk, so
    ex["emitted"] is the authoritative pre-handoff history)."""
    rid = src.add_request(prompt, gen)
    for _ in range(2):
        src.step()
    ex = src.export_request(rid)
    # geometry-invariant payload: FULL logical blocks on host, no trace
    # of the source's TP degree in the kv_dim axis
    assert ex["k"].shape[-1] == src.pool["k"].shape[-1]
    res = dst.import_request(ex["prompt"], ex["first_token"], ex["k"],
                             ex["v"], gen=gen, emitted=ex["emitted"])
    assert res is not None
    toks = list(ex["emitted"])
    while dst.has_work():
        for r, t in dst.step().items():
            if r == res["request_id"]:
                toks.extend(t)
    return toks


def test_handoff_sharded_to_single_and_back(setup):
    """export_request gathers the kv-head-sharded pool to full logical
    host blocks; import_request re-shards on entry.  Mixed single↔sharded
    migration must continue the stream bit-identically in BOTH
    directions."""
    cfg, params, e1, e2, _, _ = setup
    p = [3, 1, 4, 1, 5, 9, 2, 6]
    gen = GenerationConfig(max_new_tokens=48)
    solo = _mk(cfg, params, 1).generate([p], gen)[0]
    assert _handoff(e2, e1, p, gen) == solo  # tp=2 -> tp=1
    assert _handoff(e1, e2, p, gen) == solo  # tp=1 -> tp=2


def test_handoff_fallback_recompute_zero_drops(setup):
    """A sharded export into a full destination returns None (no queued
    imports) and the add_request recompute fallback still produces the
    right stream — mixed handoff never drops a request."""
    cfg, params, _, e2, _, _ = setup
    gen = GenerationConfig(max_new_tokens=48)
    p = [3, 1, 4, 1, 5, 9, 2, 6]
    solo = _mk(cfg, params, 1).generate([p], gen)[0]
    dst = _mk(cfg, params, 1, max_batch_size=1, num_blocks=32)
    blocker = dst.add_request([7, 7, 7], GenerationConfig(max_new_tokens=40))
    dst.step()  # blocker prefills and claims the only slot
    rid = e2.add_request(p, gen)
    for _ in range(2):
        e2.step()
    ex = e2.export_request(rid)
    assert dst.import_request(ex["prompt"], ex["first_token"], ex["k"],
                              ex["v"], gen=gen, emitted=ex["emitted"]) is None
    # fallback: recompute from the prompt on the destination
    toks = dst.generate([p], gen)[0]
    assert toks == solo
    assert blocker is not None
