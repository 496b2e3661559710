"""The hybrid state-space cell's own files (PR 36): its arithmetic, its five
readers on recorded evidence, its copy of the reference, its traffic's
schedule and its kind's verdict and refusal."""

import jax
import numpy as np
import pytest

from chipbench import loadgen, spec
from chipbench import model_math_granite_hybrid as math_

CELL = "granite-h-micro.chat_bursty"
NEW = ("ssm_decode_roofline_pct", "ssm_decode_share_pct",
       "hybrid_decode_hbm_roofline_pct", "slot_state_live_pct",
       "hybrid_prefill_mxu_pct")


@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


# -- the arithmetic -----------------------------------------------------------------


def test_model_math_against_counts_worked_by_hand(cfg):
    assert math_.mamba_mixer_params(cfg) == 25_847_232
    assert math_.attention_mixer_params(cfg) == 10_485_760
    assert math_.ffn_params(cfg) == 50_331_648
    assert math_.total_params(cfg) == 3_191_396_096
    assert math_.state_values(cfg) == 64 * 64 * 128
    assert math_.slot_state_bytes(cfg) == 36 * (2_097_152 + 3 * 4352 * 2)
    assert math_.kv_bytes_per_position(cfg) == 8192
    # 20 decoding rows: each row's 2 MB state and its window (3 taps of
    # 4,352 channels, bf16) in and out, 36 layers: the kernel is the whole
    # layer-step since PR 44
    assert math_.window_bytes(cfg) == 3 * 4352 * 2
    assert math_.ssm_kernel_bytes(cfg, 20) == 20 * 36 * 2 * (
        2_097_152 + 26_112)
    assert math_.decode_step_bytes(cfg, 0, 0) == 2 * 3_191_396_096
    one = math_.prefill_flops(cfg, 1)
    assert one == pytest.approx(
        2 * (math_.layer_matmul_params(cfg) + 100352 * 2048)
        + 4 * 36 * 524288 + 4 * 4 * 32 * 64, rel=1e-12)
    # attention grows with the square, the rest with the length
    assert (math_.prefill_flops(cfg, 2048) - 2048 * (one - 2 * 100352 * 2048)
            ) > 0


def test_the_program_counts_the_same_parameters(cfg):
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    mcfg = GraniteHybridConfig.from_published(cfg, max_seq_len=4096)
    assert mcfg.num_params == math_.total_params(cfg)
    assert mcfg.count("mamba") == 36 and mcfg.count("attention") == 4


def test_the_cell_is_in_the_lists_of_the_readers_that_hold_for_it():
    bench = spec.benchmark()
    mine = {m["name"] for m in spec.Cell(CELL).metrics("per_layer")}
    assert set(NEW) <= mine
    assert "paged_attn_share_pct" not in mine  # two kernels in the program
    assert "prefill_mxu_pct" not in mine       # Llama's count
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["unit"] == "%"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == [] and entry["source"] == spec.Cell(
        CELL).config["source"]
    e2e = {m["name"] for m in spec.Cell(CELL).metrics("end_to_end")}
    assert e2e == {"ttft_mean_ms", "tpot_mean_ms", "tpot_p90_ms", "setup_s"}


# -- the readers on recorded evidence --------------------------------------------------


def _planes(kernel_s, step_ops_s, prefill_s, window_s):
    """A one-device trace: the decode program twice (two token-steps each:
    ``kernel_s`` in 72 kernel calls, 36 a token-step, and the rest of the
    steps), then one prefill chunk; profiler marks ``window_s`` apart."""
    mods, events, t = [], [], 0.0
    for _ in range(2):
        start = t
        for i in range(72):
            events.append((f"ssm_state_update.{i}", t, kernel_s / 72,
                           "op=custom-call tpu_custom_call"))
            t += kernel_s / 72
        events.append(("paged_attention.3", t, 0.0002,
                       "op=custom-call tpu_custom_call"))
        t += 0.0002
        events.append(("fusion.7 bf16[64,16384]", t, step_ops_s, "op=fusion"))
        t += step_ops_s
        mods.append(("jit__decode_chunk_impl(123)", start, t - start, ""))
        t += 0.001
    mods.append(("jit__prefill_chunk_impl(456)", t, prefill_s, ""))
    events.append(("fusion.9 bf16[256,16384]", t, prefill_s, "op=fusion"))
    host = [("$profiler.py:1 start_trace", -0.01, 0.01, ""),
            ("$profiler.py:2 stop_trace", window_s, 0.01, "")]
    return [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": events}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]


def _ledger(counters, t):
    return [{"time": t, "engine": {"chipbench": counters}, "points": []}]


KERNEL_S, REST_S, PREFILL_S, WINDOW_S = 0.016, 0.016, 0.020, 0.2


@pytest.fixture
def evidence(cfg):
    # over the window 30 rows decode a token-step; in the TRACED seconds the
    # two dispatches held 20 rows of 30 blocks each, and one whole prompt of
    # 256 was chunked in
    before = dict.fromkeys(("decode_live_rows", "decode_rows"), 0)
    after = {"decode_live_rows": 30 * 200, "decode_rows": 64 * 200}
    regions = {"decode": [{"slots": 20, "w": 32, "chunk": 2,
                           "pages": 20 * 30} for _ in range(2)],
               "prefill": [{"tokens": 256, "bucket": 256, "is_last": 1,
                            "p0": 0}]}
    return {"config": cfg, "decode_chunk": 2, "deployment": "chipbench",
            "traffic": spec.Cell(CELL).traffic, "seconds": 50.0,
            "report": {"device_kind": "TPU v5 lite"},
            "trace": {"planes": _planes(KERNEL_S, REST_S, PREFILL_S,
                                        WINDOW_S), "regions": regions},
            "ledger_before": _ledger(before, 10.0),
            "ledger_after": _ledger(after, 70.0)}


def _read(name, evidence):
    return spec.load_module("layer_metrics", name).read(evidence)


def test_new_readers_on_recorded_evidence(evidence, cfg):
    hbm, peak = 819e9, 197e12
    # 4 token-steps traced, 20 rows x 36 layers x (4 MiB of state + 51 KB of
    # window) each, in 32 ms of kernel: 12.23 GB at 382 GB/s
    want = 100 * 4 * 20 * 36 * 2 * (2_097_152 + 26_112) / (2 * KERNEL_S * hbm)
    got = _read("ssm_decode_roofline_pct", evidence)
    assert got == pytest.approx(want) and 0 < got < 100
    busy = 2 * (KERNEL_S + 0.0002 + REST_S) + PREFILL_S
    assert _read("ssm_decode_share_pct", evidence) == pytest.approx(
        100 * 2 * KERNEL_S / busy)
    step_s = (KERNEL_S + 0.0002 + REST_S) / 2
    whole = (2 * 3_191_396_096 + 20 * 2 * math_.slot_state_bytes(cfg)
             + 20 * 30 * 16 * 8192)
    got = _read("hybrid_decode_hbm_roofline_pct", evidence)
    assert got == pytest.approx(100 * whole / (step_s * hbm)) and got < 100
    # the window's counters, not the trace's
    assert _read("slot_state_live_pct", evidence) == pytest.approx(
        100 * 30 / 64)
    # one chunk traced, a whole prompt of 256, 20 ms long
    got = _read("hybrid_prefill_mxu_pct", evidence)
    assert got == pytest.approx(100 * math_.prefill_flops(cfg, 256) / (
        PREFILL_S * peak)) and 0 < got < 100


def test_the_rows_are_counted_over_the_traced_dispatches(evidence, cfg):
    """Bursty arrivals: the window's mean (30 rows) is not the trace's.  A
    dispatch counts by its token-steps; a prompt's chunks add up to the
    prompt."""
    from chipbench import hybrid_rows

    assert hybrid_rows.rows(evidence) == 20
    assert hybrid_rows.positions(evidence) == 20 * 30 * 16
    evidence["trace"]["regions"]["decode"] = [
        {"slots": 8, "chunk": 2, "pages": 100},
        {"slots": 14, "chunk": 1, "pages": 200}]
    assert hybrid_rows.rows(evidence) == 10
    assert hybrid_rows.positions(evidence) == 150 * 16
    want = 100 * 4 * 10 * 36 * 2 * (2_097_152 + 26_112) / (
        2 * KERNEL_S * 819e9)
    assert _read("ssm_decode_roofline_pct", evidence) == pytest.approx(want)
    chunks = [(0, 256, False), (256, 256, False), (512, 100, True)]
    assert sum(math_.chunk_flops(cfg, *c) for c in chunks) == pytest.approx(
        math_.prefill_flops(cfg, 612), rel=1e-12)
    evidence["trace"]["regions"]["prefill"] = [
        {"tokens": t, "bucket": 256, "is_last": int(last), "p0": p0}
        for p0, t, last in chunks]
    assert hybrid_rows.prefill_chunks(evidence) == chunks
    # three chunks marked, one run on the device: a chunk's mean, once
    assert _read("hybrid_prefill_mxu_pct", evidence) == pytest.approx(
        100 * math_.prefill_flops(cfg, 612) / 3 / (PREFILL_S * 197e12))


def test_the_regions_stats_are_read_from_a_trace_file(tmp_path):
    """What ``tracing.region`` books as attributes is in the file as the
    event's stats (a capture of this process, no device needed)."""
    import glob

    from chipbench import hybrid_rows
    from ray_tpu.util import tracing

    jax.profiler.start_trace(str(tmp_path))
    for slots in (3, 5):
        with tracing.region("engine.decode_dispatch", slots=slots, w=4,
                            chunk=2, pages=7 * slots):
            pass
    with tracing.region("engine.prefill_chunk", tokens=40, bucket=64,
                        is_last=1, p0=128):
        pass
    with tracing.region("engine.collect", slots=5):
        pass
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    got = hybrid_rows.regions(path)
    assert got == {
        "decode": [{"slots": 3, "w": 4, "chunk": 2, "pages": 21},
                   {"slots": 5, "w": 4, "chunk": 2, "pages": 35}],
        "prefill": [{"tokens": 40, "bucket": 64, "is_last": 1, "p0": 128}]}
    evidence = {"trace": {"regions": got},
                "config": {"engine": {"block_size": 16}}}
    assert hybrid_rows.rows(evidence) == 4
    assert hybrid_rows.positions(evidence) == 28 * 16
    assert hybrid_rows.prefill_chunks(evidence) == [(128, 40, True)]


def test_the_kernel_is_found_by_its_name_alone(evidence):
    """The other Pallas kernel of the decode program is not counted."""
    planes = evidence["trace"]["planes"]
    for ln in planes[0]["lines"]:
        if ln["name"] == "XLA Ops":
            ln["events"] = [(n.replace("ssm_state_update", "closed_call"), *r)
                            for n, *r in ln["events"]]
    assert _read("ssm_decode_share_pct", evidence) is None
    assert _read("ssm_decode_roofline_pct", evidence) is None


def test_new_readers_find_nothing_on_a_program_without_their_sources(
        evidence):
    """A program whose regions carry fewer stats, a trace read without the
    kind's regions, a program that books no ``decode_live_rows``, a run
    without a trace: None, and nothing raised."""
    regions = evidence["trace"]["regions"]
    for r in regions["decode"]:
        del r["pages"]
    for r in regions["prefill"]:
        del r["p0"]
    assert _read("hybrid_decode_hbm_roofline_pct", evidence) is None
    assert _read("hybrid_prefill_mxu_pct", evidence) is None
    assert _read("ssm_decode_roofline_pct", evidence) is not None
    del evidence["trace"]["regions"]
    assert _read("ssm_decode_roofline_pct", evidence) is None
    for rows in ("ledger_before", "ledger_after"):
        for row in evidence[rows]:
            for c in row["engine"].values():
                c.pop("decode_live_rows"), c.pop("decode_rows")
    assert _read("slot_state_live_pct", evidence) is None
    evidence["trace"] = None
    for name in NEW:
        assert _read(name, evidence) is None


# -- the reference, the traffic, the kind --------------------------------------------------


def test_benchmark_copy_of_the_reference_equals_the_programs():
    from chipbench import reference_granite_hybrid as bench_ref
    from ray_tpu.models import granite_hybrid as gh
    from ray_tpu.models.granite_hybrid_reference import reference_logits

    mcfg = gh.GraniteHybridConfig.tiny(layer_types=gh.PUBLISHED_PERIOD * 2,
                                       embedding_multiplier=1.0)
    cfg = {"layer_types": list(mcfg.layer_types), "hidden_size": mcfg.dim,
           "num_attention_heads": mcfg.n_heads,
           "num_key_value_heads": mcfg.n_kv_heads,
           "shared_intermediate_size": mcfg.ffn_dim,
           "mamba_expand": mcfg.mamba_expand,
           "mamba_n_heads": mcfg.mamba_n_heads,
           "mamba_d_head": mcfg.mamba_d_head,
           "mamba_d_state": mcfg.mamba_d_state,
           "mamba_d_conv": mcfg.mamba_d_conv,
           "embedding_multiplier": mcfg.embedding_multiplier,
           "residual_multiplier": mcfg.residual_multiplier,
           "attention_multiplier": mcfg.attention_multiplier,
           "logits_scaling": mcfg.logits_scaling,
           "rms_norm_eps": mcfg.rms_norm_eps}
    params = gh.init_params(mcfg, jax.random.PRNGKey(5))
    tokens = np.random.default_rng(1).integers(1, 256, 50).tolist()
    want = np.asarray(reference_logits(mcfg, params, tokens, first_row=30))
    got = np.asarray(bench_ref.reference_logits(cfg, params, tokens,
                                                first_row=30))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    low = np.asarray(bench_ref.reference_logits(
        cfg, params, tokens, first_row=30, lowp_weights=bench_ref.to_float8))
    assert 0 < np.abs(low - want).max() < want.std()
    # the state after the last token: the program's, and the control's
    from ray_tpu.models.granite_hybrid_reference import reference_state

    want = np.asarray(reference_state(mcfg, params, tokens))
    assert want.shape == (18, mcfg.mamba_n_heads, mcfg.mamba_d_head,
                          mcfg.mamba_d_state)
    np.testing.assert_allclose(
        np.asarray(bench_ref.reference_state(cfg, params, tokens)), want,
        rtol=1e-5, atol=1e-7)
    low = np.asarray(bench_ref.reference_state(
        cfg, params, tokens, lowp_weights=bench_ref.to_float8))
    assert 0.01 < np.linalg.norm(low - want) / np.linalg.norm(want) < 1


@pytest.mark.parametrize("seeds", [(0, 1), (7, 3000000011)])
def test_chat_bursty_schedule_is_the_same_for_two_seeds(seeds):
    traffic = spec.Cell(CELL).traffic
    assert traffic["arrivals"]["process"] == "gamma"
    assert traffic["arrivals"]["cv"] in (2.0, 1.5)  # ISSUE 36's ladder
    assert traffic["arrivals"]["set_seed"] == 36 and traffic["order_seed"] == 23
    steady = spec.Cell("m7b-d16.chat_steady").traffic
    assert traffic["prompt_len"] == steady["prompt_len"]
    assert traffic["output_len"] == steady["output_len"]
    a, b = (loadgen.open_schedule(traffic, s, 50.0) for s in seeds)
    assert a == b and len(a) == round(
        traffic["arrivals"]["rate_per_s"] * 50)
    assert (loadgen.open_schedule(traffic, seeds[0], 15.0, "ramp")
            == loadgen.open_schedule(traffic, seeds[1], 15.0, "ramp"))
    ids = [loadgen.request_ids(traffic, s, a[0], 100352) for s in seeds]
    assert ids[0] != ids[1]  # --seed draws the ids
    # bursty: the gaps' coefficient of variation is near the file's
    gaps = np.diff([r["due"] for r in a])
    assert gaps.std() / gaps.mean() > 1.2


def test_the_reference_verdict_holds_each_limit():
    kind = spec.load_module("kinds", "serve_open_hybrid")
    assert [p for p in kind.PROBES if p[1] >= kind.LONG_DECODE] == [
        (64, 448)] * 2
    assert max(p[0] + p[1] for p in kind.PROBES) <= 2048

    def rows(prompt_gap, decode_gap):
        return ([{"tokens": 16, "logit_gaps": [prompt_gap] * 16}] * 14
                + [{"tokens": 448, "logit_gaps": [decode_gap] * 448}] * 2)

    def state(rel_err, finite=True):
        return {"positions": 511, "ssm": {"rel_err": rel_err,
                                          "finite": finite}}

    ok = kind.judge(rows(0.0, 0.0), state(0.04))
    assert ok["ok"] and ok["prompt_tokens"] == 224
    assert ok["decode_tokens"] == 896 and ok["state_rel_err"] == 0.04
    over = kind.judge(rows(kind.REF_PROMPT_MEAN_TOL * 1.01, 0.0), state(0.04))
    assert not over["ok"] and "behind a prompt" in over["why"]
    over = kind.judge(rows(0.0, kind.REF_DECODE_MEAN_TOL * 1.01), state(0.04))
    assert not over["ok"] and "long-decode" in over["why"]
    one = rows(0.0, 0.0)
    one[0] = {"tokens": 16, "logit_gaps": [kind.REF_MAX_TOL * 1.5] + [0] * 15}
    over = kind.judge(one, state(0.04))
    assert not over["ok"] and "largest gap" in over["why"]
    for bad in (state(kind.REF_STATE_TOL * 1.01), state(0.0, finite=False)):
        over = kind.judge(rows(0.0, 0.0), bad)
        assert not over["ok"] and "recurrent state after 511" in over["why"]


def test_the_kinds_refusal_is_a_bench_error_not_a_traceback(monkeypatch):
    """On the parent commit the family is missing: NO RESULT, at once, before
    any cluster starts."""
    import sys

    kind = spec.load_module("kinds", "serve_open_hybrid")
    monkeypatch.setitem(sys.modules, "ray_tpu.models.granite_hybrid", None)
    with pytest.raises(spec.BenchError, match="does not have this family"):
        kind.llm_config(spec.Cell(CELL).config, False)


def test_the_sweep_of_processes_names_what_is_left(monkeypatch):
    import subprocess
    import sys

    kind = spec.load_module("kinds", "serve_open_hybrid")
    assert kind.left_running() == {}
    child = subprocess.Popen([sys.executable, "-c",
                              "import time; time.sleep(60)"])
    try:
        found = kind.left_running()
        assert list(found) == [child.pid] and "sleep(60)" in found[child.pid]
        logged = []
        monkeypatch.setattr(kind, "log", logged.append)
        assert kind.sweep_processes(grace_s=0.0) == 1
        assert logged[0].startswith(f"LEFT RUNNING: {child.pid} ")
        assert child.wait(timeout=10) == -9
    finally:
        child.kill()
    assert kind.left_running() == {}
