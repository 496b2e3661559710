"""The Kimi-Linear cell's own benchmark files (PR 47), on the CPU: the
arithmetic of ``chipbench/model_math_kimi_linear.py`` against the model
file's own count, the five new readers on hand-made evidence, and the
benchmark's copy of the reference against the program's.  (``chipbench/tests``
is not part of tier-1; this file is.)"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.kimi_lowp_reading import toy_config  # noqa: E402
from chipbench import model_math_kimi_linear as math_  # noqa: E402
from chipbench import reference_kimi_linear as bench_ref  # noqa: E402
from chipbench import spec  # noqa: E402
from ray_tpu.models import kimi_linear as kl  # noqa: E402
from ray_tpu.models import kimi_linear_reference as ref  # noqa: E402

CELL = "kimi-linear-ep16.reason_steady"
NEW = ("kda_decode_roofline_pct", "kda_decode_share_pct",
       "kimi_decode_hbm_roofline_pct", "kimi_prefill_mxu_pct",
       "kimi_serve_mfu_pct")
DEP = "d"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "kimi-linear-48b-a3b-ep16.json")) as f:
        return json.load(f)


def _read(name):
    return spec.load_module("layer_metrics", name).read


def test_the_arithmetic_counts_the_model_files_parameters(cfg):
    """At the published widths (the issue's table) and at ``tiny()``."""
    assert math_.kda_mixer_params(cfg) == 39_514_272
    assert math_.mla_mixer_params(cfg) == 29_114_880
    assert math_.moe_ffn_params(cfg) == 120_913_920
    assert math_.dense_ffn_params(cfg) == 63_700_992
    mcfg = kl.KimiLinearConfig.from_published(cfg, max_seq_len=6144)
    assert math_.total_params(cfg) == mcfg.num_params == 4_956_653_952
    tiny = kl.KimiLinearConfig.tiny()
    assert math_.total_params(toy_config(cfg, tiny)) == tiny.num_params
    # a sequence's state: 20 layers of 32 x 128 x 128 float32 and of three
    # taps of 12,288 bf16 channels
    assert math_.slot_state_bytes(cfg) == 20 * (2_097_152 + 3 * 12288 * 2)
    state = jax.eval_shape(lambda: kl.init_slot_state(mcfg, 1))
    assert math_.slot_state_bytes(cfg) == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in state.values())
    # a row a layer of the kernel: its state both ways, q k v bf16, the log
    # decay and the output float32, beta a head
    assert math_.kda_kernel_bytes(cfg, 1) == 20 * (
        2 * 2_097_152 + 3 * 8192 + 16384 + 128 + 16384)
    # half a held expert a token: 8 of 256 outputs fall on 16 held
    assert math_.token_matmul_params(cfg) == (
        20 * math_.kda_matmul_params(cfg) + 7 * math_.mla_matmul_params(cfg)
        + 63_700_992 + 26 * (2304 * 256 + 1.5 * 3 * 2304 * 1024))
    assert math_.decode_step_bytes(cfg, 0, 0) == 2 * (
        4_956_653_952 - 163840 * 2304)
    assert math_.decode_step_bytes(cfg, 2, 100, 0.5) == (
        math_.decode_step_bytes(cfg, 0, 0)
        - 0.5 * 26 * 16 * 3 * 2304 * 1024 * 2
        + 2 * 2 * math_.slot_state_bytes(cfg) + 100 * 7 * 1152)


def test_benchmark_json_lists_the_cell_and_its_readers():
    bench = spec.benchmark()
    cell = spec.Cell(CELL)
    assert cell.kind == "serve_open_kda" and cell.chips == 1
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) <= mine
    assert {"decode_step_ms", "moe_expert_live_pct", "kv_pool_used_pct",
            "compiles_in_window"} <= mine
    # another family's arithmetic, or a program with one kernel
    assert not mine & {"paged_attn_share_pct", "mla_decode_roofline_pct",
                       "moe_ffn_share_pct", "decode_hbm_roofline_pct",
                       "ssm_decode_roofline_pct", "ssm_decode_share_pct",
                       "hybrid_decode_hbm_roofline_pct",
                       "hybrid_prefill_mxu_pct", "prefill_mxu_pct"}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"ttft_mean_ms", "tpot_mean_ms", "tpot_p85_ms", "setup_s"}
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-a3b-ep16")
    assert entry["reduced"] == ["num_experts"]
    assert len(bench["workloads"]) >= 5
    assert all(w["chips"] == 1 for w in bench["workloads"])


@pytest.mark.parametrize("name", NEW)
def test_a_reader_without_its_source_reads_nothing(cfg, name):
    """No trace, no ledger reads, a trace without dispatch regions (the
    parent commit's program under this benchmark): None, and nothing
    raised."""
    read = _read(name)
    base = {"config": cfg, "deployment": DEP, "decode_chunk": 2,
            "report": {"device_kind": "TPU v5 lite"}}
    assert read(dict(base, trace=None)) is None
    assert read(dict(base, trace={"planes": [], "regions": {
        "decode": [], "prefill": []}})) is None
    assert read(dict(base, trace=None, ledger_before=[], ledger_after=[])
                ) is None


def _ledger(time, **counters):
    return [{"time": time, "points": [], "engine": {DEP: counters}}]


def test_the_served_steps_share_of_the_peak_on_hand_made_evidence(cfg):
    """1,000 prompt tokens and 500 emitted in 10 s: 1,500 tokens through the
    layers and 500 through the head, over 10 s of 197 TFLOP/s."""
    evidence = {
        "config": cfg, "deployment": DEP,
        "report": {"device_kind": "TPU v5 lite"},
        "ledger_before": _ledger(100.0, prefill_tokens=50, tokens_emitted=7),
        "ledger_after": _ledger(110.0, prefill_tokens=1050,
                                tokens_emitted=507)}
    a_token = 2 * math_.token_matmul_params(cfg) + 7 * 20 * 32 * 128 * 128
    want = (1500 * a_token + 500 * 2 * 163840 * 2304) / (10 * 197e12) * 100
    assert _read("kimi_serve_mfu_pct")(evidence) == pytest.approx(want)
    assert 0 < want < 1  # 150 tokens a second leave the matrix unit idle
    assert _read("kimi_serve_mfu_pct")(dict(
        evidence, ledger_before=_ledger(100.0, steps=1))) is None


def test_the_decode_steps_share_of_the_hbm_peak_given_a_step_time(cfg):
    """32 rows over 28,800 live positions, 60% of the held experts hit, a
    token-step of 20 ms: the bytes by hand over 20 ms of 819 GB/s."""
    share = spec.load_module(
        "layer_metrics", "kimi_decode_hbm_roofline_pct").share_pct
    weights = 2 * (4_956_653_952 - 163840 * 2304) - 0.4 * (
        26 * 16 * 3 * 2304 * 1024 * 2)
    moved = (weights + 32 * 2 * 20 * (2_097_152 + 73728)
             + 28_800 * 7 * 1152)
    want = 100 * moved / (0.020 * 819e9)
    assert share(cfg, 0.020, 32, 28_800, 0.6, 819e9) == pytest.approx(want)
    assert 55 < want < 65
    # what no row asked for is no work: nothing left to skip reads over 100
    assert share(cfg, 0.020, 32, 28_800, 1.0, 819e9) > want


def test_the_benchmarks_copy_of_the_reference_is_the_programs(cfg):
    tiny = kl.KimiLinearConfig.tiny()
    params = kl.init_params(tiny, jax.random.PRNGKey(5))
    toks = np.random.default_rng(0).integers(1, tiny.vocab_size, 23).tolist()
    conf = toy_config(cfg, tiny)
    np.testing.assert_array_equal(
        bench_ref.reference_logits(conf, params, toks, first_row=3),
        ref.reference_logits(tiny, params, toks, first_row=3))
    np.testing.assert_array_equal(
        bench_ref.reference_state(conf, params, toks),
        ref.reference_state(tiny, params, toks))
    # the control moves the logits, and only through the layers' matrices
    low = bench_ref.reference_logits(conf, params, toks,
                                     lowp_weights=bench_ref.to_float8)
    assert 1e-4 < float(np.abs(
        low - ref.reference_logits(tiny, params, toks)).max()) < 0.5


def test_a_state_carried_in_bf16_is_off_by_more_than_one_rounding(cfg):
    """The second control (``state_carry``): rounded after every position a
    KDA state drifts further than the same state rounded once at the end,
    which is only the least it could be off; no carry, no change."""
    import jax.numpy as jnp

    tiny = kl.KimiLinearConfig.tiny()
    params = kl.init_params(tiny, jax.random.PRNGKey(5))
    toks = np.random.default_rng(1).integers(1, tiny.vocab_size, 60).tolist()
    conf = toy_config(cfg, tiny)
    want = np.asarray(bench_ref.reference_state(conf, params, toks))
    carried = np.asarray(bench_ref.reference_state(
        conf, params, toks, state_carry=jnp.bfloat16))
    once = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)

    def off(x):
        return float(np.linalg.norm(x - want) / np.linalg.norm(want))

    assert 2 * off(once) < off(carried) < 0.05, (off(once), off(carried))
    np.testing.assert_array_equal(
        bench_ref.reference_state(conf, params, toks, state_carry=None), want)


def test_the_cells_engine_shares_a_steps_chunks_between_waiting_prompts(cfg):
    """The configuration's ``engine`` block goes to ``LLMConfig`` whole: the
    budget of four chunks a step is data of the cell, not a default."""
    from chipbench.kinds import serve_open_kda as kind
    from benchmarks.kimi_lowp_reading import cut_config

    eng = cfg["engine"]
    assert eng["prefill_token_budget"] == 4 * eng["prefill_chunk"] == 1024
    llm = kind.llm_config(cut_config(cfg, 4, 512), rehearse=False)
    assert llm.prefill_token_budget == 1024 and llm.prefill_chunk == 256
    assert llm.model_config.layer_types == ("kda", "kda", "kda", "mla")
    # the probes and their background fit the slots
    assert kind.BACKGROUND + kind.AT_ONCE <= eng["max_batch_size"]
    assert (kind.BACKGROUND_SIZE[1] > kind.STATE_PROBE[1]
            and sum(kind.BACKGROUND_SIZE) <= eng["max_seq_len"])
