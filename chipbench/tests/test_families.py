"""The benchmark with more than one model family: each configuration against
its own published keys, the new readers on recorded evidence, the benchmark's
copy of the latent-attention reference against the program's, and
``model_math_mla_moe`` against counts worked by hand."""

import json
import os
import re

import pytest

from chipbench import model_math_mla_moe as mm
from chipbench import spec

CELL = "pangu-ep16.docqa_warm"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# openPangu-Ultra-MoE-718B
PANGU_PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
MISTRAL_WIDTHS = dict(hidden_size=4096, intermediate_size=14336, head_dim=128,
                      num_attention_heads=32, num_key_value_heads=8,
                      vocab_size=32768)
WIDTH = re.compile(r"(_dim|_rank)$|^(hidden|intermediate|moe_intermediate)_size$"
                   r"|^num_experts_per_tok$|^head_dim$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_every_configuration_states_its_cut_and_cuts_no_width(bench):
    for c in bench["configs"]:
        f = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert f["source"] == c["source"]
        for key in ("reduced", "assumed", "deployment", "chips", "published"):
            assert key in f, (c["name"], key)
        assert set(c["reduced"]) == set(f["reduced"]) == set(f["published"])
        assert not any(WIDTH.search(k) for k in c["reduced"]), c["reduced"]
        if f["model_type"] == "mistral":
            assert {k: f[k] for k in MISTRAL_WIDTHS} == MISTRAL_WIDTHS


def test_pangu_configuration_against_the_catalog_row(bench):
    f = spec.Cell(CELL).config
    for key, value in PANGU_PUBLISHED.items():
        if key in f["reduced"]:
            assert f["published"][key] == value, key
            assert f[key] != value, key
        else:
            assert f[key] == value, key
    assert set(f["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    # the floors of a model_config PR: a period and four layers after the
    # dense ones, eight routed experts, an eighth of the vocabulary
    assert f["num_hidden_layers"] - f["first_k_dense_replace"] >= 4
    assert f["n_routed_experts"] >= 8
    assert f["vocab_size"] * 8 >= f["published"]["vocab_size"]
    assert f["router_outputs"] == f["published"]["n_routed_experts"]
    assert f["experts_held"] == [0, f["n_routed_experts"]]
    assert f["vocab_slice"] == [0, f["vocab_size"]]


def test_the_cell_is_in_the_lists_of_the_readers_that_hold_for_it(bench):
    cell = spec.Cell(CELL)
    assert cell.kind == "serve_open_family"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "ttft_mean_ms", "tpot_mean_ms", "tpot_p90_ms", "setup_s"}
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"paged_attn_share_pct", "mla_decode_roofline_pct",
            "moe_ffn_share_pct", "moe_expert_live_pct",
            "decode_hbm_roofline_pct", "prefix_hit_pct", "kv_pool_used_pct",
            "kv_pool_resident_pct", "decode_step_ms",
            "latent_prefill_mxu_pct"} <= names
    # Llama's operation count is not this cell's; two prefill readers' lists
    # are pinned to the Mistral cell by test_prefill_span_readers.py
    assert not {"prefill_mxu_pct", "prefill_device_share_pct",
                "prefill_span_live_pct"} & names
    for m in cell.metrics("per_layer"):
        assert os.path.exists(os.path.join(
            spec.BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    # the Mistral cell's lists only gained a name
    for m in bench["per_layer"]:
        if "m7b-d16.chat_steady" in m.get("workloads", ()):
            assert m["workloads"][0] == "m7b-d16.chat_steady"


def test_model_math_against_counts_worked_by_hand():
    cfg = spec.Cell(CELL).config
    attn = (7680 * 1536 + 1536 * 128 * 192 + 7680 * 576
            + 512 * 128 * 256 + 128 * 128 * 7680)
    assert mm.attention_params(cfg) == attn == 196_575_232
    assert mm.expert_params(cfg) == 3 * 7680 * 2048 == 47_185_920
    norms = 4 * 7680 + 1536 + 512
    dense = attn + norms + 3 * 7680 * 18432
    expert = attn + norms + 7680 * 256 + 17 * 47_185_920
    assert mm.dense_layer_params(cfg) == dense
    assert mm.expert_layer_params(cfg) == expert
    total = dense + 4 * expert + 2 * 19200 * 7680 + 7680
    assert mm.params_held(cfg) == total
    assert round(total / 1e9, 2) == 4.92
    # a token-step reads every weight but the embedding: 9.54 GB
    assert mm.decode_weight_bytes(cfg) == 2 * (total - 19200 * 7680)
    assert round(mm.decode_weight_bytes(cfg) / 1e9, 2) == 9.54
    assert mm.latent_bytes(cfg) == 1152
    # 16 rows of 8,500 positions: 0.78 GB of latent and 190 GFLOP a step
    live = 16 * 8500
    assert mm.mla_kernel_bytes(cfg, live) == live * 5 * 1152
    assert mm.mla_kernel_flops(cfg, live) == live * 5 * 128 * (576 + 512) * 2
    assert round(mm.mla_kernel_flops(cfg, live) / 1e9) == 189
    assert mm.decode_step_bytes(cfg, live) == (
        mm.decode_weight_bytes(cfg) + live * 5 * 1152)
    # a prompt token: attention in 5 layers, the dense feed-forward, and in
    # 4 layers the router, the shared expert and half a held expert
    per_token = 5 * attn + 3 * 7680 * 18432 + 4 * (
        7680 * 256 + 1.5 * 47_185_920)
    assert mm.prefill_matmul_params(cfg) == per_token
    # 256 tokens behind a document of 8,192: 2.2 M (query, key) pairs a
    # layer at 128 x 320 x 2 operations, 8,448 positions expanded a layer at
    # 512 x 128 x 256 x 2: 0.46 TFLOP of attention a layer (ISSUE 31: 0.45
    # to 0.6), and the head's one row
    pairs = 256 * 8192 + 256 * 257 / 2
    attention = 5 * (pairs * 128 * 320 * 2 + 8448 * 512 * 128 * 256 * 2)
    assert mm.prefill_flops(cfg, 256, 8192, 1024) == pytest.approx(
        2 * per_token * 256 + 2 * 7680 * 19200 + attention)
    assert round(attention / 5 / 1e12, 2) == 0.46
    # two chunks expand the prefix twice
    assert (mm.prefill_flops(cfg, 2048, 0, 1024)
            - mm.prefill_flops(cfg, 2048, 0, 2048)) == pytest.approx(
        5 * 1024 * 512 * 128 * 256 * 2)


def test_the_program_counts_the_same_parameters():
    from chipbench.kinds import serve_open_family as kind

    cfg = spec.Cell(CELL).config
    mcfg = kind.llm_config(cfg, rehearse=False).model_config
    assert mcfg.num_params == mm.params_held(cfg)
    assert (mcfg.cache_width, mcfg.latent_width) == (640, 576)


# -- the new readers on recorded evidence --------------------------------------


def _planes(ops):
    """A one-device trace: the decode program twice, ``ops`` inside each."""
    mods, events, t = [], [], 0.0
    for _ in range(2):
        start = t
        for name, dur, text in ops:
            events.append((name, t, dur, text))
            t += dur
        mods.append(("jit__decode_chunk_impl(123)", start, t - start, ""))
        t += 0.001
    # and one prefill chunk of 4 ms
    mods.append(("jit__prefill_chunk_impl(456)", t, 0.004, ""))
    events.append(("fusion.9 bf16[1,1024,7680]", t, 0.004, "op=fusion"))
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": events}]}]


def _ledger(counters, t):
    return [{"time": t, "engine": {"chipbench": counters}, "points": []}]


@pytest.fixture
def evidence():
    cfg = spec.Cell(CELL).config
    ops = [("fusion.1 bf16[64,1,7680]", 0.002, "op=fusion"),
           ("mla_paged_attention.20 f32[64,128,512]", 0.001,
            "op=custom-call tpu_custom_call"),
           ("add_divide_fusion.2 f32[64,256]", 0.0005, "op=fusion"),
           ("fusion.429 bf16[64,32768]", 0.002, "op=fusion"),
           ("fusion.431 f32[64,7680]", 0.0015, "op=fusion"),
           ("fusion.434 bf16[64,1,7680]", 0.003, "op=fusion")]
    before = {"decode_live_pages": 0, "decode_dispatches": 0,
              "moe_experts_hit": 0, "moe_experts_held": 0,
              "prefix_hit_tokens": 0, "prefill_tokens": 0}
    after = {"decode_live_pages": 8500 // 16 * 16 * 100,
             "decode_dispatches": 100, "moe_experts_hit": 2600,
             "moe_experts_held": 6400, "prefix_hit_tokens": 8192 * 10,
             "prefill_tokens": 300 * 10}
    rows = [{"prompt_len": 8192 + 300, "first": 1.0 + i} for i in range(10)]
    rows += [{"prompt_len": 8192 + 300, "first": None},   # failed
             {"prompt_len": 8192 + 300, "first": -3.0}]   # the ramp's
    return {"config": cfg, "decode_chunk": 2, "deployment": "chipbench",
            "traffic": spec.Cell(CELL).traffic, "seconds": 50.0, "rows": rows,
            "report": {"device_kind": "TPU v5 lite"},
            "report_after": {"utilization": {"kv_blocks": {
                "total": 34000, "used": 100, "free": 33900,
                "cached": 24700}}},
            "trace": {"planes": _planes(ops)},
            "ledger_before": _ledger(before, 10.0),
            "ledger_after": _ledger(after, 70.0)}


def _read(name, evidence):
    return spec.load_module("layer_metrics", name).read(evidence)


def test_new_readers_on_recorded_evidence(evidence):
    busy = 2 * 0.010 + 0.004
    assert _read("paged_attn_share_pct", evidence) == pytest.approx(
        100 * 0.002 / busy)
    # ten prompts of 300 tokens behind a cached document in 50 s, 4 ms of
    # prefill programs in the traced 26 ms
    assert _read("latent_prefill_mxu_pct", evidence) == pytest.approx(
        100 * 10 * mm.prefill_flops(evidence["config"], 300, 8192, 1024)
        / 50.0 / (0.004 / 0.026 * 197e12))
    # router, gate products and the float32 down-projection: 4 ms of 10
    assert _read("moe_ffn_share_pct", evidence) == pytest.approx(
        100 * 2 * 0.004 / busy)
    assert _read("moe_expert_live_pct", evidence) == pytest.approx(
        100 * 2600 / 6400)
    assert _read("prefix_hit_pct", evidence) == pytest.approx(
        100 * 8192 / 8492)
    assert _read("kv_pool_resident_pct", evidence) == pytest.approx(
        100 * 24800 / 34000)
    # 16 rows of 8,496 positions a token-step; 4 token-steps traced; the
    # kernel ran 2 ms: its least time is bytes or operations, whichever more
    live = 16 * 8496
    cfg = evidence["config"]
    least = max(mm.mla_kernel_flops(cfg, live) / 197e12,
                mm.mla_kernel_bytes(cfg, live) / 819e9)
    assert _read("mla_decode_roofline_pct", evidence) == pytest.approx(
        100 * least * 4 / 0.002)
    # the held experts count by the share a token-step's rows hit (2,600 of
    # 6,400): 4 expert layers x 16 held x 3 x 7680 x 2048 weights, bf16
    got = _read("decode_hbm_roofline_pct", evidence)
    assert got == pytest.approx(
        100 * mm.decode_step_bytes(cfg, live, 2600 / 6400)
        / (0.005 * 819e9))
    assert mm.routed_expert_bytes(cfg) == 4 * 16 * 3 * 7680 * 2048 * 2
    assert mm.decode_step_bytes(cfg, live) - mm.decode_step_bytes(
        cfg, live, 2600 / 6400) == pytest.approx(
            (1 - 2600 / 6400) * mm.routed_expert_bytes(cfg))
    assert got < 100 * mm.decode_step_bytes(cfg, live) / (0.005 * 819e9)


def test_new_readers_find_nothing_on_a_program_without_their_sources(evidence):
    """The parent of PR 31 books no expert counters and no cached count; a
    run without a trace has none: every reader then returns None."""
    bare = dict(evidence, trace=None, report_after={"utilization": {
        "kv_blocks": {"total": 6000, "used": 10, "free": 5990}}},
        ledger_before=_ledger({"steps": 1}, 1.0),
        ledger_after=_ledger({"steps": 9}, 9.0))
    for name in ("latent_prefill_mxu_pct", "mla_decode_roofline_pct",
                 "moe_ffn_share_pct", "moe_expert_live_pct",
                 "decode_hbm_roofline_pct", "prefix_hit_pct",
                 "kv_pool_resident_pct"):
        assert _read(name, bare) is None, name


def test_the_kinds_refusal_is_a_bench_error_not_a_traceback(monkeypatch):
    """On a program without the family seam the kind gives NO RESULT."""
    import builtins

    from chipbench.kinds import serve_open_family as kind

    real = builtins.__import__

    def no_seam(name, *a, **k):
        if name == "ray_tpu.models.family":
            raise ImportError("No module named 'ray_tpu.models.family'")
        return real(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_seam)
    with pytest.raises(spec.BenchError, match="no model-family seam"):
        kind.llm_config(spec.Cell(CELL).config, rehearse=False)


def test_benchmark_copy_of_the_reference_equals_the_programs():
    import jax
    import numpy as np

    from chipbench import reference_pangu_moe as bench_ref
    from ray_tpu.models import pangu_moe, pangu_moe_reference

    mcfg = pangu_moe.PanguMoEConfig.tiny(experts_held=(4, 8))
    params = pangu_moe.init_params(mcfg, jax.random.PRNGKey(1))
    cfg = {"num_hidden_layers": mcfg.n_layers,
           "first_k_dense_replace": mcfg.first_k_dense,
           "num_attention_heads": mcfg.n_heads,
           "kv_lora_rank": mcfg.kv_lora_rank,
           "qk_nope_head_dim": mcfg.qk_nope_head_dim,
           "qk_rope_head_dim": mcfg.qk_rope_head_dim,
           "v_head_dim": mcfg.v_head_dim,
           "intermediate_size": mcfg.ffn_dim,
           "moe_intermediate_size": mcfg.moe_ffn_dim,
           "n_shared_experts": mcfg.n_shared_experts,
           "num_experts_per_tok": mcfg.n_experts_per_tok,
           "routed_scaling_factor": mcfg.routed_scaling_factor,
           "experts_held": list(mcfg.experts_held),
           "rope_theta": mcfg.rope_theta, "rms_norm_eps": mcfg.rms_norm_eps}
    tokens = list(range(1, 40))
    want = pangu_moe_reference.reference_logits(mcfg, params, tokens,
                                                first_row=30)
    got = bench_ref.reference_logits(cfg, params, tokens, first_row=30)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # in 8 bits it is another function
    low = bench_ref.reference_logits(cfg, params, tokens, first_row=30,
                                     lowp=bench_ref.to_float8)
    assert float(np.abs(np.asarray(low) - np.asarray(want)).max()) > 1e-3


def test_the_reference_verdict_holds_each_limit():
    """``judge``: the mean over all served tokens, the mean over the tokens
    served behind a document, and the largest gap each refuse alone."""
    from chipbench.kinds import serve_open_family as kind

    assert sum(p[1] for p in kind.PROBES) == 464
    assert sum(p[1] for p in kind.PROBES if p[2]) == 240
    assert kind.PROBES[:3] == ((48, 16, 0, 0), (320, 16, 0, 0),
                               (8192 + 256, 16, 8192, 0))
    # no reference forward longer than the issue's long probe's
    assert max(p[0] + p[1] for p in kind.PROBES) == 8192 + 256 + 16

    def rows(short, long):
        return [{"document": 0, "logit_gaps": short},
                {"document": 8192, "logit_gaps": long}]

    quiet = kind.judge(rows([0.0] * 224, [0.0] * 239 + [0.5]))
    assert quiet["ok"] and quiet["why"] is None and quiet["disagree"] == 1
    assert quiet["long_mean_logit_gap"] == pytest.approx(0.5 / 240)
    assert (quiet["short_tokens"], quiet["long_tokens"]) == (224, 240)
    # a fault behind the documents alone
    long_only = kind.judge(rows([0.0] * 224, [0.016] * 240))
    assert not long_only["ok"] and "behind a document" in long_only["why"]
    assert "short probes" not in long_only["why"]
    # the float8 control's readings: 0.0196 short, 0.0062 behind a document
    control = kind.judge(rows([0.0196] * 224, [0.0062] * 240))
    assert not control["ok"] and "short probes 0.0196" in control["why"]
    assert "behind a document" not in control["why"]
    one_wrong_token = kind.judge(rows([0.0] * 223 + [2.5], [0.0] * 240))
    assert not one_wrong_token["ok"]
    assert "largest gap" in one_wrong_token["why"]
