"""The Laguna cell's own benchmark files (PR 51), on the CPU: the arithmetic
of ``chipbench/model_math_laguna.py`` against the model file's own count and
against hand counts, the six new readers on hand-made evidence, the
benchmark's copy of the reference against the program's, and the cell's
entries in ``BENCHMARK.json`` against the files they name and against what the
parent commit's file had.
(``chipbench/tests`` is not part of tier-1; this file is.)"""

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.laguna_lowp_reading import toy_config  # noqa: E402
from chipbench import model_math_laguna as math_  # noqa: E402
from chipbench import reference_laguna as bench_ref  # noqa: E402
from chipbench import spec  # noqa: E402
from ray_tpu.models import laguna as lg  # noqa: E402
from ray_tpu.models import laguna_reference as ref  # noqa: E402

CELL = "laguna-s-ep16.mixed_queue"
NEW = ("window_span_read_pct", "window_attn_decode_roofline_pct",
       "full_attn_decode_roofline_pct", "laguna_decode_hbm_roofline_pct", "laguna_prefill_mxu_pct",
       "laguna_serve_mfu_pct")
DEP = "d"


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "laguna-s-2.1-ep16.json")) as f:
        return json.load(f)


def _read(name):
    return spec.load_module("layer_metrics", name).read


def test_the_arithmetic_counts_the_model_files_parameters(cfg):
    """At the published widths (the issue's table) and at ``tiny()``."""
    assert math_.attention_params(cfg, "window") == 63_135_744
    assert math_.attention_params(cfg, "full") == 44_187_648
    assert math_.moe_ffn_params(cfg) == 161_218_560
    assert math_.dense_ffn_params(cfg) == 113_246_208
    assert (len(math_.layers(cfg, "window")),
            len(math_.layers(cfg, "full"))) == (12, 5)
    mcfg = lg.LagunaConfig.from_published(cfg, max_seq_len=17408)
    assert math_.matrix_params(cfg) == 4_287_873_024
    # the 35 norms' 3,072 each beside the matrices
    assert math_.total_params(cfg) == mcfg.num_params == (
        4_287_873_024 + 35 * 3072)
    shapes = jax.eval_shape(
        lambda: lg.init_params(mcfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)
               if x.ndim > 1 and x.shape[-2] > 17) == 4_287_873_024
    tiny = lg.LagunaConfig.tiny()
    assert math_.total_params(toy_config(cfg, tiny)) == tiny.num_params
    # a sequence's ring: 12 layers of 512 positions of 4 KiB
    assert math_.position_bytes(cfg) == 4096
    assert math_.ring_bytes(cfg) == 12 * 512 * 4096 == 24 << 20
    ring = jax.eval_shape(lambda: lg.init_slot_state(mcfg, 1))
    assert math_.ring_bytes(cfg) == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in ring.values())
    pool = jax.eval_shape(lambda: lg.init_paged_cache(mcfg, 3, 16))
    assert 5 * math_.position_bytes(cfg) * 3 * 16 == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in pool.values())


def test_flops_and_bytes_against_hand_counts_at_a_small_size(cfg):
    """A stack ``F W W`` of dim 8: 2 query heads full, 3 window, 1 KV head of
    4; window 4; dense first layer of 16; then 2 held experts of 8 of 4
    outputs, 2 a token, a shared expert of 8; 10 vocabulary rows."""
    small = dict(
        cfg, hidden_size=8, num_hidden_layers=3, vocab_size=10, head_dim=4,
        num_key_value_heads=1, sliding_window=4, intermediate_size=16,
        moe_intermediate_size=8, shared_expert_intermediate_size=8,
        num_experts=2, router_outputs=4, num_experts_per_tok=2,
        layer_types=["full_attention"] + ["sliding_attention"] * 2,
        num_attention_heads_per_layer=[2, 3, 3], mlp_only_layers=[0])
    full = 8 * 8 + 2 * 8 * 4 + 8 * 2 + 8 * 8            # q, k v, gate, o
    window = 8 * 12 + 2 * 8 * 4 + 8 * 3 + 12 * 8
    assert math_.attention_params(small, "full") == full == 208
    assert math_.attention_params(small, "window") == window == 280
    moe = 8 * 4 + 3 * 8 * 8 + 2 * 3 * 8 * 8             # router, shared, held
    dense = 3 * 8 * 16
    assert math_.matrix_params(small) == (full + 2 * window + dense + 2 * moe
                                          + 2 * 80)
    assert math_.total_params(small) == math_.matrix_params(small) + 7 * 8
    # a token: its matrices, the held experts it chose (2 of 4 fall on 2
    # held: one expert a token)
    a_token = full + 2 * window + dense + 2 * (8 * 4 + 192 + 1.0 * 192)
    assert math_.token_matmul_params(small) == a_token
    assert math_.token_flops(small) == 2 * a_token
    # a (query, key) pair: a score and a weighted sum over 4 values a head
    assert math_.pair_flops(small, "full") == 2 * 2 * 2 * 4
    assert math_.pair_flops(small, "window") == 2 * 3 * 2 * 4
    # 6 tokens from position 2: full layers see 3 .. 8 keys (33), a window
    # layer min(p + 1, 4): 3 + 4 x 5 = 23
    assert math_.window_keys(2, 6, 4) == 23
    assert math_.chunk_flops(small, 2, 6, True) == (
        6 * 2 * a_token + 1 * 32 * 33 + 2 * 48 * 23 + 2 * 80)
    assert math_.chunk_flops(small, 2, 6, False) == (
        math_.chunk_flops(small, 2, 6, True) - 160)
    # a prompt within the window: windowed work is causal work
    assert math_.window_keys(0, 4, 4) == 10
    # a token-step: every weight but the table once, half the held experts
    # hit, 7 ring positions in 2 window layers and 30 in 1 full, 8 B each
    weights = 2 * (math_.total_params(small) - 80)
    assert math_.decode_step_bytes(small, 0, 0) == weights
    assert math_.position_bytes(small) == 2 * 1 * 4 * 2 == 16
    assert math_.decode_step_bytes(small, 7, 30, 0.5) == (
        weights - 0.5 * (2 * 2 * 192 * 2) + 7 * 2 * 16 + 30 * 1 * 16)
    assert math_.window_attention_bytes(small, 7) == 7 * 2 * 16
    assert math_.full_attention_bytes(small, 7) == 7 * 1 * 16
    assert math_.served_flops(small, 10, 5) == 15 * 2 * a_token + 5 * 160


def _parents_benchmark():
    """``BENCHMARK.json`` as the commit before this cell had it, or None
    where git cannot say (a checkout without history)."""
    import subprocess

    for rev in ("HEAD", "HEAD~1"):
        got = subprocess.run(["git", "show", f"{rev}:BENCHMARK.json"],
                             cwd=ROOT, capture_output=True, text=True)
        if got.returncode != 0:
            return None
        bench = json.loads(got.stdout)
        if CELL not in [w["name"] for w in bench["workloads"]]:
            return bench
    return None


def test_benchmark_json_lists_the_cell_and_names_its_files():
    """The cell and its configuration are the LAST entries of their lists,
    the harness finds every file the cell names, and everything the
    benchmark had before is as it was, in its place."""
    bench = spec.benchmark()
    cell = spec.Cell(CELL)
    assert cell.kind == "serve_open_window" and cell.chips == 1
    assert cell.entry == bench["workloads"][-1]        # appended, not put in
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "kinds", cell.kind + ".py"))
    entry = bench["configs"][-1]
    assert entry["name"] == cell.entry["config"] == "laguna-s-2.1-ep16"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert entry["source"] == cell.config["source"]
    assert set(entry["reduced"]) == set(cell.config["reduced"]) == set(
        cell.config["published"])
    mine = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) <= mine
    for name in mine:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "layer_metrics", name + ".py")), name
    assert {"decode_step_ms", "moe_expert_live_pct", "kv_pool_used_pct",
            "compiles_in_window", "stream_gap_max_ms"} <= mine
    # another family's arithmetic, and the reader that matches every call
    assert not mine & {"paged_attn_share_pct", "mla_decode_roofline_pct",
                       "kda_decode_roofline_pct", "kimi_serve_mfu_pct",
                       "decode_hbm_roofline_pct", "prefill_mxu_pct"}
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    for m in bench["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert {"ttft_mean_ms", "tpot_mean_ms", "setup_s"} < e2e
    assert len(e2e & {"tpot_p85_ms", "tpot_p90_ms"}) == 1 and len(e2e) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])
    assert len(cell.entry["why"]) <= 200 and len(entry["why"]) <= 200
    # the traffic ISSUE 51 gives, no rung of its ladder taken
    t = cell.traffic
    assert (t["prompt_len"]["median"], t["prompt_len"]["sigma"],
            t["prompt_len"]["min"], t["prompt_len"]["max"]) == (
                1536, 1.1, 128, 16384)
    assert (t["output_len"]["median"], t["output_len"]["sigma"],
            t["output_len"]["min"], t["output_len"]["max"]) == (
                320, 0.6, 64, 1024)
    assert (t["ramp_s"], t["drain_s"], t["arrivals"]["process"]) == (
        30, 45, "poisson")
    accepted = _parents_benchmark()
    if accepted is None:  # a checkout without history: nothing to hold to
        return
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(accepted[section], bench[section]):
            assert dict(now, workloads=None) == dict(was, workloads=None)
            lists = was.get("workloads", [])
            assert now.get("workloads", [])[:len(lists)] == lists
            assert now.get("workloads", [])[len(lists):] in ([], [CELL])
    for key in ("command", "paths", "run_seconds"):
        assert bench[key] == accepted[key]


def test_the_configuration_file_keeps_every_published_width(cfg):
    """Against the catalog's row where this machine has it: every published
    number unchanged but the two in ``reduced``; the per-layer lists cut to
    their first 17 entries."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog on this machine")
    with open(catalog) as f:
        row = next(json.loads(line) for line in f
                   if '"Laguna-S-2.1"' in line)
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value != cfg[key]
        elif isinstance(value, list) and len(value) == 48:
            assert cfg[key] == value[:17], key
        else:
            assert cfg[key] == value, key
    eng = cfg["engine"]
    assert eng["max_seq_len"] == 16384 + 1024 and eng["max_batch_size"] == 64
    assert cfg["router_outputs"] == 256 and cfg["experts_held"] == [0, 16]
    assert cfg["deployment"].startswith("16 chips share each layer")


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
    # a program that books other counters (the parent's)
    assert read(dict(base, trace=None,
                     ledger_before=_ledger(1.0, steps=1),
                     ledger_after=_ledger(2.0, steps=9))) is None


def _ledger(time, **counters):
    return [{"time": time, "points": [], "engine": {DEP: counters}}]


def test_the_share_of_their_lengths_the_window_layers_read(cfg):
    """40 decoding row-steps over 100,000 positions held, of which a window
    layer read 18,000."""
    evidence = {
        "config": cfg, "deployment": DEP,
        "ledger_before": _ledger(100.0, decode_window_positions=500,
                                 decode_full_positions=900),
        "ledger_after": _ledger(110.0, decode_window_positions=18_500,
                                decode_full_positions=100_900)}
    assert _read("window_span_read_pct")(evidence) == pytest.approx(18.0)


@pytest.mark.parametrize("name, kernel, other, layers, a_row", [
    ("window_attn_decode_roofline_pct", "window_paged_attention.3",
     "paged_attention.7", 12, 500),
    ("full_attn_decode_roofline_pct", "paged_attention.7",
     "window_paged_attention.3", 5, 3_000)])
def test_a_kernels_share_of_its_roofline_by_its_name(
        cfg, monkeypatch, name, kernel, other, layers, a_row):
    """Four decoding rows that each read ``a_row`` positions a layer of the
    kind a token-step, 10 traced dispatches of 2 token-steps, the kind's
    calls 8 ms in all: 20 steps x 4 x ``a_row`` positions x the kind's layers
    x 4 KiB over 8 ms of 819 GB/s.  Each reader's pattern finds its own kernel's name and
    not the other's."""
    import re

    from chipbench import hybrid_rows, trace_reduce

    mod = spec.load_module("layer_metrics", name)
    assert re.search(mod.KERNEL, kernel) and not re.search(mod.KERNEL, other)
    counter = ("decode_window_positions" if "window" in name
               else "decode_full_positions")
    monkeypatch.setattr(hybrid_rows, "rows", lambda evidence: 4.0)
    monkeypatch.setattr(trace_reduce, "module_durations",
                        lambda planes, program: [0.014] * 10)
    monkeypatch.setattr(trace_reduce, "op_self_seconds",
                        lambda planes, pattern, within: 0.008)
    evidence = {
        "config": cfg, "deployment": DEP, "decode_chunk": 2,
        "report": {"device_kind": "TPU v5 lite"}, "trace": {"planes": []},
        "ledger_before": _ledger(100.0, decode_live_rows=0, **{counter: 0}),
        "ledger_after": _ledger(110.0, decode_live_rows=5_000,
                                **{counter: 5_000 * a_row})}
    want = 100 * 20 * 4 * a_row * layers * 4096 / (0.008 * 819e9)
    assert mod.read(evidence) == pytest.approx(want)
    assert want < 100


def test_the_served_steps_share_of_the_peak_on_hand_made_evidence(cfg):
    """30,000 prompt tokens and 5,000 emitted in 10 s: 35,000 tokens through
    the layers and 5,000 through the head, over 10 s of 197 TFLOP/s."""
    evidence = {
        "config": cfg, "deployment": DEP,
        "report": {"device_kind": "TPU v5 lite"},
        "ledger_before": _ledger(100.0, prefill_tokens=50, tokens_emitted=7),
        "ledger_after": _ledger(110.0, prefill_tokens=30_050,
                                tokens_emitted=5_007)}
    a_token = 2 * math_.token_matmul_params(cfg)
    want = (35_000 * a_token + 5_000 * 2 * 100352 * 3072) / (
        10 * 197e12) * 100
    assert _read("laguna_serve_mfu_pct")(evidence) == pytest.approx(want)
    assert 0 < want < 10
    assert _read("laguna_serve_mfu_pct")(dict(
        evidence, ledger_before=_ledger(100.0, steps=1))) is None


def test_the_decode_steps_share_of_the_hbm_peak_given_a_step_time(cfg):
    """32 rows that read 14,000 ring positions and 90,000 pool positions, 70%
    of the held experts hit, a token-step of 20 ms: the bytes by hand over 20
    ms of 819 GB/s."""
    share = spec.load_module(
        "layer_metrics", "laguna_decode_hbm_roofline_pct").share_pct
    weights = 2 * (4_287_873_024 + 35 * 3072 - 100352 * 3072) - 0.3 * (
        16 * 16 * 3 * 3072 * 1024 * 2)
    moved = weights + 14_000 * 12 * 4096 + 90_000 * 5 * 4096
    want = 100 * moved / (0.020 * 819e9)
    assert share(cfg, 0.020, 14_000, 90_000, 0.7, 819e9) == pytest.approx(
        want)
    assert 50 < want < 70
    # what no row asked for is no work: nothing left to skip reads over 100
    assert share(cfg, 0.020, 14_000, 90_000, 1.0, 819e9) > want
    # a cache that kept every position for the window layers too would move
    # 90,000 x 12 layers more
    assert math_.decode_step_bytes(cfg, 90_000, 90_000) - (
        math_.decode_step_bytes(cfg, 14_000, 90_000)) == 76_000 * 12 * 4096


def test_the_benchmarks_copy_of_the_reference_is_the_programs(cfg):
    tiny = lg.LagunaConfig.tiny()
    params = lg.init_params(tiny, jax.random.PRNGKey(5))
    toks = np.random.default_rng(0).integers(1, tiny.vocab_size, 23).tolist()
    conf = toy_config(cfg, tiny)
    np.testing.assert_array_equal(
        bench_ref.reference_logits(conf, params, toks, first_row=3),
        ref.reference_logits(tiny, params, toks, first_row=3))
    ours, theirs = (f(c, params, toks) for f, c in (
        (bench_ref.reference_window, conf), (ref.reference_window, tiny)))
    for leaf in ("wk", "wv"):
        np.testing.assert_array_equal(ours[leaf], theirs[leaf])
    # the control moves the logits, and only through the layers' matrices
    low = bench_ref.reference_logits(conf, params, toks,
                                     lowp_weights=bench_ref.to_float8)
    assert 1e-4 < float(np.abs(
        low - ref.reference_logits(tiny, params, toks)).max()) < 0.5


def test_the_cells_engine_and_probes_fit_the_configuration(cfg):
    """The configuration's ``engine`` block goes to ``LLMConfig`` whole, and
    the kind's probes cover what the issue asks: one under the window, one of
    4,096 positions or more, each decoded 16 tokens or more."""
    from chipbench.kinds import serve_open_kda as kda
    from chipbench.kinds import serve_open_window as kind

    eng = cfg["engine"]
    llm = kind.llm_config(dict(cfg, engine=dict(eng, num_blocks=64)),
                          rehearse=False)
    assert llm.prefill_token_budget == eng["prefill_token_budget"]
    assert llm.prefill_chunk == eng["prefill_chunk"]
    assert llm.model_config == dataclasses.replace(
        lg.LagunaConfig(), layer_types=lg.LagunaConfig().layer_types[:17])
    assert any(p < 512 for p, _ in kind.PROBES)
    assert any(p >= 4096 for p, _ in kind.PROBES)
    assert all(n >= 16 for _, n in kind.PROBES)
    assert any(p < 512 < p + n for p, n in kind.PROBES)   # wraps in decode
    assert sum(kind.STATE_PROBE) > 512
    assert kda.BACKGROUND + kda.AT_ONCE <= eng["max_batch_size"]
    assert max(p + n for p, n in kind.PROBES) <= eng["max_seq_len"]
    # the traffic's longest request fits a sequence
    t = spec.load_json(os.path.join(ROOT, "chipbench", "traffic",
                                    "mixed_queue.json"))
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= eng[
        "max_seq_len"]
    with pytest.raises(spec.BenchError, match="model_type"):
        kind.model_config(dict(cfg, model_type="llama"), 128, False)
