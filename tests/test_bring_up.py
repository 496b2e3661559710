"""What the chip bring-up (ISSUE 21) fixed or added, held on the CPU:
one process for each chip through the lease, no guessed chips or peaks, the
compile cache placed from outside, the float32 reference, what a replica
reports, and the two scripts that must fail without a chip."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- one process for each chip ----------------------------------------------


def test_one_chip_replica_leases_its_chip_where_chips_exist(monkeypatch):
    from ray_tpu.llm import LLMConfig

    monkeypatch.delenv("RAY_TPU_NUM_CHIPS", raising=False)
    # a CPU node: nothing to bind, and the replica must still schedule
    assert LLMConfig().resources_per_replica() == {"CPU": 1.0}
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "4")
    # a chip host: the lease is what binds TPU_VISIBLE_CHIPS to ONE chip
    assert LLMConfig().resources_per_replica() == {"CPU": 1.0, "TPU": 1.0}
    assert LLMConfig(tensor_parallel_size=4).resources_per_replica()[
        "TPU"] == 4.0
    assert "TPU" not in LLMConfig(chips_per_replica=0).resources_per_replica()


def test_use_tpu_without_a_chip_raises_instead_of_asking_for_four(monkeypatch):
    from ray_tpu.train import ScalingConfig

    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "0")
    with pytest.raises(ValueError, match="no TPU chip"):
        ScalingConfig(num_workers=1, use_tpu=True).worker_resources()
    # said explicitly, it is honoured; detected, it is what was detected
    assert ScalingConfig(use_tpu=True, chips_per_worker=4).worker_resources()[
        "TPU"] == 4.0
    monkeypatch.setenv("RAY_TPU_NUM_CHIPS", "1")
    assert ScalingConfig(use_tpu=True).worker_resources()["TPU"] == 1.0


def test_lease_binds_visible_chips(monkeypatch):
    from ray_tpu._private.accelerators import bind_visible_accelerators

    for var in ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
                "TPU_HOST_BOUNDS"):
        monkeypatch.delenv(var, raising=False)
    bind_visible_accelerators({"CPU": []})
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    bind_visible_accelerators({"TPU": [2]})
    assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"


# -- no guessed peak ------------------------------------------------------------


def test_peak_table_knows_its_devices_and_raises_for_the_rest():
    from ray_tpu._private import device_telemetry as dt

    @dataclasses.dataclass
    class Dev:
        device_kind: str

    assert dt.peak_flops(Dev("TPU v5 lite")) == 197e12
    assert "cpu" not in {k.lower() for k in dt.PEAK_FLOPS}
    for kind in ("cpu", "TPU v9", "v5 lite"):
        with pytest.raises(ValueError, match="no published peak"):
            dt.peak_flops(Dev(kind))
    with pytest.raises(ValueError):
        dt.peak_flops()  # this process's device is a CPU
    with pytest.raises(ValueError):
        dt.note_train_step("r", model_flops=1e9, wall_s=1.0)


# -- compile cache placed from outside ------------------------------------------


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    from ray_tpu._private import compile_cache

    before = jax.config.jax_compilation_cache_dir
    keep = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, keep)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.configure() == want
        # children inherit it, and a jax imported earlier is told
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update(keep, kept)
        os.environ.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)


@pytest.mark.parametrize("outside", [None, "2.5"])
def test_compile_cache_keeps_every_program(monkeypatch, tmp_path, outside):
    """JAX's default keeps only programs that took a second to compile; the
    replica's warm-up programs are around that, so a warm start compiled
    some again.  ``configure`` keeps all, unless JAX's variable says else."""
    from ray_tpu._private import compile_cache

    name = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
    flag = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, flag)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        if outside is None:
            monkeypatch.delenv(name, raising=False)
        else:
            monkeypatch.setenv(name, outside)
        compile_cache.configure()
        assert os.environ[name] == (outside or "0")  # children inherit it
        assert getattr(jax.config, flag) == float(outside or 0)
    finally:
        jax.config.update(flag, before)
        monkeypatch.delenv(name, raising=False)


def test_nothing_else_in_the_tree_sets_a_cache_directory():
    import re

    sets = re.compile(r'update\(\s*"jax_compilation_cache_dir"|set_cache_dir\('
                      r'|initialize_cache\(|COMPILATION_CACHE_DIR"\]\s*=')
    hits = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_scratch"))
                   and d not in ("chiprun_out", "__pycache__", "tests")]
        for f in files:
            if f.endswith(".py") and sets.search(
                    open(os.path.join(root, f)).read()):
                hits.append(os.path.relpath(os.path.join(root, f), _REPO))
    assert hits == ["ray_tpu/_private/compile_cache.py"], hits


# -- the float32 reference and what a replica reports ---------------------------


def _tiny():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny(vocab_size=300)


def test_reference_logits_agree_with_the_model_forward():
    from ray_tpu.models import llama
    from ray_tpu.models.llama_reference import reference_logits

    cfg = _tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    tokens = np.arange(40) * 7 % cfg.vocab_size
    want = llama.forward(cfg, params, jnp.asarray(tokens)[None])[0]
    got = reference_logits(cfg, params, tokens)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_replica_reports_and_checks_itself_against_the_reference():
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serve import LLMServer
    from ray_tpu.models.llama_reference import reference_logits

    server = LLMServer(LLMConfig(model_config=_tiny(), max_batch_size=2,
                                 num_blocks=32))
    try:
        rep = server.device_report()
        assert rep["pid"] == os.getpid() and rep["platform"] == "cpu"
        assert rep["device_count"] == len(jax.devices())
        assert rep["paged_attention"] == "gather"  # no kernel off-TPU
        assert rep["warmup"] is None               # warmup() is TPU-only
        assert rep["utilization"]["kv_blocks"]["total"] == 31
        prompt = [5, 9, 200, 17, 3, 88]
        served = server.generate(prompt, max_new_tokens=6)
        ref = server.reference_check(prompt, served)
        assert ref["finite"] and ref["first_divergent"] is None
        assert ref["max_logit_gap"] == 0.0
        # a token the model would not have chosen shows as a logit gap
        wrong = list(served)
        wrong[2] = (wrong[2] + 1) % 300
        bad = server.reference_check(prompt, wrong)
        assert bad["first_divergent"] == 2 and bad["max_logit_gap"] > 0
        # the engine's own programs, held against the reference
        eng = server._engine
        free = eng.blocks.num_free()
        got = server.first_decode_logits(prompt)
        want = np.asarray(reference_logits(eng.cfg, eng.params, prompt))[-1]
        np.testing.assert_allclose(got, want, atol=2e-4)
        assert eng.blocks.num_free() == free  # borrowed blocks came back
    finally:
        server.shutdown()


def test_warmup_says_what_it_compiled():
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.engine import make_engine

    eng = make_engine(LLMConfig(model_config=_tiny(), max_batch_size=2,
                                max_seq_len=64, num_blocks=16,
                                prefill_chunk=32))
    assert eng.warmup_report is None
    eng.warmup()
    rep = eng.warmup_report
    assert rep["decode_table_widths"] == [1, 2, 4]
    assert rep["prefill_chunks"] == [16, 32] and rep["seconds"] > 0


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_server_freezes_the_heap_behind_warmup_on_a_tpu_alone(
        monkeypatch, backend):
    """PR 51: a full collection walked what ``warmup()`` left (a quarter of
    a second with every stream waiting); behind it the heap is frozen, on a
    TPU backend, and nowhere else (no warm-up there, nothing frozen)."""
    import gc

    from ray_tpu.llm import serve as llm_serve

    calls = []

    class Engine:
        def warmup(self):
            calls.append(gc.get_freeze_count())

    monkeypatch.setattr(llm_serve, "_jax_backend", lambda: backend)
    before = gc.get_freeze_count()
    try:
        llm_serve._warm_up(Engine())
        if backend == "tpu":
            assert calls == [before]  # warmed first, frozen behind it
            assert gc.get_freeze_count() > before
        else:
            assert calls == [] and gc.get_freeze_count() == before
    finally:
        gc.unfreeze()


@pytest.mark.parametrize("tp", [1, 4])
def test_serving_after_warmup_compiles_nothing(tp):
    """On the chip, TP=4 requests took 20 times one device's: warmup()
    fed its programs freshly uploaded arrays while serving feeds them each
    other's outputs, which under a mesh keyed different executables, so
    every warmed program was compiled again inside the request path."""
    import logging

    from ray_tpu.llm import GenerationConfig, LLMConfig
    from ray_tpu.llm.engine import make_engine

    eng = make_engine(LLMConfig(
        model_config=dataclasses.replace(_tiny(), n_heads=8, n_kv_heads=4),
        max_batch_size=4, max_seq_len=128, num_blocks=64,
        tensor_parallel_size=tp))
    eng.warmup()
    seen = []
    handler = logging.Handler()
    handler.emit = lambda r: seen.append(r.getMessage())
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    pxla.addHandler(handler)
    jax.config.update("jax_log_compiles", True)
    try:
        out = eng.generate([[1, 2, 3] * 14, [4, 5, 6]],
                           GenerationConfig(max_new_tokens=24))
    finally:
        jax.config.update("jax_log_compiles", False)
        pxla.removeHandler(handler)
    assert [len(o) for o in out] == [24, 24]
    engine_programs = [m[:80] for m in seen if "_chunk_impl" in m]
    assert engine_programs == []


def test_native_status_names_what_loaded():
    from ray_tpu import _native

    _native.load("sched_policy")
    assert _native.status()["sched_policy"] in ("native", "fallback")
    _native._cache["not-there"] = None
    try:
        assert _native.status()["not-there"] == "fallback"
    finally:
        del _native._cache["not-there"]


def test_serve_config_hash_takes_a_class_from_the_deploying_script():
    """The controller hashed init args with plain pickle: a tokenizer class
    defined in the deploying script's __main__ failed there for ever while
    serve.run() waited in silence."""
    from ray_tpu.serve._private.controller import _cfg_hash

    Tok = type("Tok", (), {"__module__": "__main__",
                           "encode": lambda self, t: [1]})
    cfg = {"serialized_callable": b"x", "init_args": (Tok(),),
           "init_kwargs": {}, "user_config": None}
    assert _cfg_hash(cfg) == _cfg_hash(dict(cfg))


def test_mesh_attention_is_the_plain_call_off_tpu():
    from ray_tpu.ops.attention import mesh_attention, multi_head_attention
    from ray_tpu.parallel import MeshSpec

    q = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 4, 8))
    kv = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 2, 8))
    mesh = MeshSpec(fsdp=2, tensor=2).build(jax.devices()[:4])
    got = mesh_attention(q, kv, kv, mesh=mesh, batch_axes=("data", "fsdp"))
    np.testing.assert_array_equal(got, multi_head_attention(q, kv, kv))


# -- the script that must fail without a chip -------------------------------------


def _run(args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_WORKER_QUIET="1")
    env.pop("RAY_TPU_NUM_CHIPS", None)
    return subprocess.run([sys.executable, *args], cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_fails_where_there_is_no_chip():
    proc = _run(["chip_smoke.py"], 120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "0 TPU chip(s)" in proc.stdout


@pytest.mark.timeout(420)
def test_chip_smoke_rehearsal_runs_every_phase_and_prints_no_result():
    """The whole control flow at toy size, replica actor and train worker
    in worker processes with pretended chips: every phase passes, and the
    run still can neither exit 0 nor print the result line."""
    proc = _run(["chip_smoke.py", "--rehearse"], 400)
    out = proc.stdout
    assert proc.returncode == 3, out[-1500:] + proc.stderr[-1500:]
    assert "every phase passed" in out and "rehearsal complete" in out
    assert "TPU_VISIBLE_CHIPS=0" in out  # the lease bound the replica's chip
    assert '"ok"' not in out
