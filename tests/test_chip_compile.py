"""The main path's Pallas kernels, compiled for a described TPU v5e at the
widths ``chip_smoke.py`` runs them at.

No chip is attached: the TPU compiler that is installed here compiles for a
topology that is described (``v5e:2x2``), and refuses what the chip's would
refuse — a misaligned slice, too much fast memory, a kernel that cannot be
partitioned.  Nothing runs, so nothing here says anything about results or
speed.  Each compile takes a second or two.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may hold the TPU library, so nothing at import,
``skipif`` or ``parametrize`` time may touch it, and every compile happens
in this test's own process.  All such tests live in this one file.
"""

import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip, say why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tensor_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("tensor",))


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# -- paged decode attention: Llama-3-8B heads (32 q / 8 kv, head_dim 128) ----

_B, _NH, _KV, _HD, _L, _NB = 8, 32, 8, 128, 8, 2048


def _paged_args(bs, w, sh_q, sh_pool, sh_rep, b=_B):
    """q, k pool, v pool, layer id, table, lengths, active."""
    pool = _spec((_L, _NB, bs, _KV * _HD), BF16, sh_pool)
    return (_spec((b, _NH, _HD), BF16, sh_q), pool, pool,
            _spec((), jnp.int32, sh_rep), _spec((b, w), jnp.int32, sh_rep),
            _spec((b,), jnp.int32, sh_rep), _spec((b,), jnp.int32, sh_rep))


def _paged_under_4_shard_map(mesh):
    """The tensor-parallel engine's wrap (models/llama.py decode_step_paged):
    kv heads over "tensor", per-shard kv*hd = 256; table, lengths and active
    replicated."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    t = P(None, None, None, "tensor")
    kern = jax.shard_map(
        paged_decode_attention, mesh=mesh,
        in_specs=(P(None, "tensor", None), t, t, P(), P(), P(), P()),
        out_specs=P(None, "tensor"), check_vma=False)
    ns = functools.partial(NamedSharding, mesh)
    return kern, (ns(P(None, "tensor", None)), ns(t), ns(P()))


@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("w", [8, 64])
def test_paged_decode_attention_compiles(one_chip, bs, w):
    from ray_tpu.ops.paged_attention import paged_decode_attention

    _assert_kernel(paged_decode_attention,
                   *_paged_args(bs, w, one_chip, one_chip, one_chip))


@pytest.mark.parametrize("bs", [16, 32])
@pytest.mark.parametrize("w", [8, 64])
def test_paged_decode_attention_compiles_under_4_shard_map(tensor_mesh, bs, w):
    kern, shardings = _paged_under_4_shard_map(tensor_mesh)
    _assert_kernel(kern, *_paged_args(bs, w, *shardings))


# the benchmark's serving cell (m7b-d16.chat_steady): batch 64, 16-token
# pages, the two table widths its decode steps run at.  The chunk loop's trip
# count and the page DMAs come from the row's operands, so a Mosaic lowering
# or VMEM failure of the dynamic loop shows here.


@pytest.mark.parametrize("w", [128, 256])
def test_paged_decode_attention_compiles_at_cell_shapes(one_chip, w):
    from ray_tpu.ops.paged_attention import paged_decode_attention

    _assert_kernel(paged_decode_attention,
                   *_paged_args(16, w, one_chip, one_chip, one_chip, b=64))


def test_paged_decode_attention_compiles_at_cell_shapes_under_4_shard_map(
        tensor_mesh):
    kern, shardings = _paged_under_4_shard_map(tensor_mesh)
    _assert_kernel(kern, *_paged_args(16, 128, *shardings, b=64))


# -- the paged prefill chunk at the serving cell's shapes ---------------------
# Mistral-7B widths at 16 layers, 6,000 blocks of 16 tokens, the engine's
# fixed table of 264 blocks: the loop over KV tiles has a trip count the
# device reads (p0 is an operand) and gathers pages of a pool that rides the
# layer scan's carry.  A pool the compiler would rather copy than alias shows
# as 6 GB of temporaries, which the chip has no room for.


def _cell_model(params_sh, pool_sh):
    """``(cfg, params, pool)`` of the serving cell as shapes: ``params_sh`` is
    one sharding for every weight, or ``f(cfg, shapes)`` that places them."""
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, max_seq_len=4096, rope_theta=1e6,
        param_dtype=BF16)
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    if callable(params_sh):
        params = params_sh(cfg, shapes)
    else:
        params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, params_sh),
                              shapes)
    pool = {n: _spec((16, 6000, 16, 8 * 128), BF16, pool_sh)
            for n in ("k", "v")}
    return cfg, params, pool


def _prefill_chunk_program(c, params_sh, pool_sh, rep_sh, tp_plan=None):
    from ray_tpu.models import llama

    cfg, params, pool = _cell_model(params_sh, pool_sh)
    fn = jax.jit(
        lambda p, t, pl, tb, p0: llama.prefill_chunk_paged(
            cfg, p, t, pl, tb, p0, tp_plan=tp_plan), donate_argnums=2)
    return fn.lower(params, _spec((1, c), jnp.int32, rep_sh), pool,
                    _spec((1, 264), jnp.int32, rep_sh),
                    _spec((), jnp.int32, rep_sh)).compile()


@pytest.mark.parametrize("c", [64, 256])
def test_paged_prefill_chunk_compiles_at_cell_shapes(one_chip, c):
    compiled = _prefill_chunk_program(c, one_chip, one_chip, one_chip)
    assert "while" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("overlap", [True, False])
def test_paged_prefill_chunk_compiles_for_4_shards(tensor_mesh, overlap):
    """The tensor-parallel engine's layout: KV heads of the pool and the
    projections over "tensor", the planned collectives explicit."""
    from ray_tpu.models import llama

    ns = functools.partial(NamedSharding, tensor_mesh)

    def sharded(cfg, shapes):
        return jax.tree.map(lambda x, s: _spec(x.shape, x.dtype, ns(s)),
                            shapes, llama.inference_param_specs(cfg))

    plan = llama.TPPlan(mesh=tensor_mesh, axis="tensor", algorithm="flat",
                        overlap=overlap)
    compiled = _prefill_chunk_program(
        256, sharded, ns(P(None, None, None, "tensor")), ns(P()),
        tp_plan=plan)
    assert "while" in compiled.as_text()
    # a shard holds a quarter of the pool and copies none of it
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# -- the engine's decode program at the serving cell's shapes -------------------


@pytest.mark.parametrize("quantum", ["default", 8])
def test_decode_chunk_program_transposes_no_weights(one_chip, quantum):
    """``PagedJaxLLMEngine._decode_chunk_impl`` (the engine's own function,
    its ``self`` a stand-in: nothing can be placed on a described device)
    over batch 64 and the 32-block table: the kernel is in the program, and
    the program keeps no copy of a stacked weight.  Before PR 30 it
    transposed all of ``wq`` and ``wk`` (0.63 GB of temporaries) at the start
    of every dispatch, which is what a short decode quantum paid for."""
    from ray_tpu.llm import LLMConfig

    cfg, params, pool = _cell_model(one_chip, one_chip)
    steps = LLMConfig().decode_chunk if quantum == "default" else quantum
    compiled = _engine_programs(cfg, params, pool, {}, one_chip, 64, 32,
                                steps, 256, 264)[0].compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _engine_programs(cfg, params, pool, state, sh, b, w, steps, c, prefill_w):
    """The engine's own two programs, lowered: ``(decode chunk of ``steps``
    over batch ``b`` and a ``w``-block table, prefill chunk of ``c`` tokens
    over a ``prefill_w``-block table)``.  ``state``: the family's slot state
    (``{}``: it has none).  ``self`` is a stand-in: nothing can be placed on
    a described device."""
    import types

    from ray_tpu.llm.engine import _MAX_STOP_IDS
    from ray_tpu.llm.paged import PagedJaxLLMEngine
    from ray_tpu.models.family import family_of

    eng = types.SimpleNamespace(
        cfg=cfg, family=family_of(cfg), max_seq=cfg.max_seq_len, mesh=None,
        _rope=None, _use_kernel=True, _kernel_interpret=False, _tp_plan=None,
        _tp_prefill_plan=None)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    key = _spec(key.shape, key.dtype, sh)

    def i32(*shape):
        return _spec(shape, jnp.int32, sh)

    decode = jax.jit(
        functools.partial(PagedJaxLLMEngine._decode_chunk_impl, eng),
        donate_argnums=(2, 12), static_argnums=11).lower(
            params, i32(b), pool, i32(b, w), i32(b), i32(b), i32(b),
            i32(b, _MAX_STOP_IDS), key, _spec((b,), jnp.float32, sh), i32(b),
            steps, state)
    prefill = jax.jit(
        functools.partial(PagedJaxLLMEngine._prefill_chunk_impl, eng),
        donate_argnums=(2, 9)).lower(
            params, i32(1, c), pool, i32(1, prefill_w), i32(), i32(), key,
            _spec((1,), jnp.float32, sh), i32(1), state,
            (i32(), i32()) if state else (None, None))
    return decode, prefill


def test_join_program_compiles_at_cell_shapes(one_chip):
    """``PagedJaxLLMEngine._join_impl`` over the serving cells' 64 rows: a
    handful of scatters into the decode mirrors, no kernel, no temporary
    worth the name (it runs between a prompt chunk and a decode chunk, a
    request at a time)."""
    import types

    from ray_tpu.llm.engine import _MAX_STOP_IDS
    from ray_tpu.llm.paged import _JOIN_ROW, PagedJaxLLMEngine

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    def f32(*shape):
        return _spec(shape, jnp.float32, one_chip)

    b = 64
    compiled = jax.jit(functools.partial(
        PagedJaxLLMEngine._join_impl, types.SimpleNamespace(max_seq=4096))
    ).lower((i32(b), i32(b), i32(b), i32(b), i32(b, _MAX_STOP_IDS), f32(b),
             i32(b)), i32(1), i32(_JOIN_ROW), f32(1)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# what the engine's two programs lower to for each family, at the shapes of
# the family's cell: sha256 of the StableHLO text less the line of a Pallas
# kernel's call (its payload carries the source lines of its callers, which
# any edit above them moves; the kernels have tests of their own).  Llama's
# two are from the commit before the family seam (PR 31), which moved
# models/llama.py behind a table of functions; the other six are from the
# commit before PR 50 gave the seam's functions one signature (an empty slot
# state and absent counters are empty pytrees: no parameter, no result);
# Laguna's two are as PR 51 brought the family.
# Neither may change anything the device runs.  A PR that changes what a
# family computes replaces its two (print ``got`` below); PR 48 did, for the
# sampler's conditional (llm/engine.py), which every program ends in.
_PROGRAM_HLO = {
    ("llama", "decode"):
        "de9acd447f92dd14111112d90d20458dd3532760599dd9f1fbbf72232655bc7d",
    ("llama", "prefill"):
        "8960792292dc1c1357a282ec0640bfeaa015d77463266408165d83732c91d2f1",
    ("pangu_moe", "decode"):
        "6191fbf70e2e97344535d9f0ad50a4ef6b17d8d94e844b4ebf0d308fa930b7c6",
    ("pangu_moe", "prefill"):
        "ae2f8b4925fb4f7e79b9b375f79a57446b52470b7d6e91406c1aa5cd9d410ef9",
    ("granite_hybrid", "decode"):
        "a6f39b63cf8c7af3ed627a912165c577b279b1e779c983a2cf06ed48c88c49ec",
    ("granite_hybrid", "prefill"):
        "37f39b95e186e892bc277d876efb77810283e3fd363e8d5ddecf698b06b895a6",
    ("kimi_linear", "decode"):
        "38e933947dfd2a19f6449661a89d36854ed0e73ed6fbb23029cc662ac3af848b",
    ("kimi_linear", "prefill"):
        "828bbe5abe38ba2d403c5dd1b7db1371ddb660ae4c47f0d76952ffefeec6ee1a",
    ("laguna", "decode"):
        "0de0cd764de18378afdcbc460c8b4108bff4f8d96c836e83cdcea8d90d1bfeb3",
    ("laguna", "prefill"):
        "d365de9187b903372f195972f9f3668f832bce2f07a62549c12a99350caa4efd",
}


def _family_programs(family, sh):
    """``{"decode", "prefill"}`` of ``family``'s cell, lowered, at the shapes
    its program test compiles: 64 rows, two token-steps."""
    if family == "llama":
        return _engine_programs(*_cell_model(sh, sh), {}, sh, 64, 32, 2, 256,
                                264)
    if family == "pangu_moe":
        return _engine_programs(*_pangu_cell(sh), sh, 64, 1024, 2, 1024, 641)
    if family == "granite_hybrid":
        return _engine_programs(*_granite_cell(sh), sh, 64, 32, 2, 256, 264)
    if family == "laguna":
        return _engine_programs(*_laguna_cell(sh), sh, 64, 256, 2, 256, 1096)
    return _engine_programs(*_kimi_cell(sh), sh, 64, 32, 2, 256, 384)


@pytest.mark.parametrize("family,program", sorted(_PROGRAM_HLO))
def test_family_programs_lower_to_the_pinned_hlo(one_chip, monkeypatch,
                                                 family, program):
    import hashlib

    if family in ("pangu_moe", "kimi_linear", "laguna"):
        # the expert layers ask the backend, which is the CPU here
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lowered = dict(zip(("decode", "prefill"),
                       _family_programs(family, one_chip)))[program]
    got = hashlib.sha256("\n".join(
        line for line in lowered.as_text().split("\n")
        if "tpu_custom_call" not in line).encode()).hexdigest()
    assert got == _PROGRAM_HLO[family, program], (family, program, got)


def _outside_conditionals(text):
    """The instructions of a compiled program's text that run whenever the
    program does: those of the computations reached from ``ENTRY`` by every
    edge but a conditional's branches."""
    import re

    bodies, name, entry = {}, None, None
    for line in text.split("\n"):
        m = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if m:
            name = m.group(2)
            bodies[name] = []
            if m.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            bodies[name].append(line)
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in bodies[name]:
            line = re.sub(r"(branch_computations=\{[^}]*\}"
                          r"|(true|false)_computation=%[\w.\-]+)", "", line)
            for m in re.finditer(
                    r"(?:calls|to_apply|body|condition)=%([\w.\-]+)"
                    r"|called_computations=\{([^}]*)\}", line):
                todo += [m.group(1)] if m.group(1) else \
                    [c.strip().lstrip("%") for c in m.group(2).split(",")]
    return [line for name in seen for line in bodies[name]]


def test_decode_program_keeps_the_samplers_top_k_inside_a_conditional(
        one_chip):
    """The Mistral cell's decode program as the chip's compiler leaves it:
    the top-64 over ``[64, 32768]`` is in the program, and only in a
    conditional's branch (``engine._sampler_gates``: a token-step whose
    rows are all greedy runs none of it), and so is the draw's generator."""
    from ray_tpu.llm import LLMConfig

    cfg, params, pool = _cell_model(one_chip, one_chip)
    text = _engine_programs(cfg, params, pool, {}, one_chip, 64, 32,
                            LLMConfig().decode_chunk, 256,
                            264)[0].compile().as_text()
    always = "\n".join(_outside_conditionals(text))
    assert " while(" in always and "tpu_custom_call" in always
    for mark in ('custom_call_target="TopK"', "/top_k", "jit(_gumbel)"):
        assert mark not in always, f"{mark} runs in every token-step"
        assert mark in text, f"{mark} is not in the program at all"
    assert " conditional(" in always


# -- the latent-attention expert family at its cell's shapes ---------------------
# openPangu-Ultra-MoE widths, 1 dense + 4 expert layers of 16 held experts,
# 33,000 blocks of 16 positions of a 640-wide latent row.


def _pangu_cell(sh):
    from ray_tpu.models import pangu_moe

    cfg = pangu_moe.PanguMoEConfig()
    shapes = jax.eval_shape(
        lambda: pangu_moe.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda x: _spec(x.shape, x.dtype, sh), shapes)
    pool = {"ckv": _spec((cfg.n_layers, 33000, 16, cfg.cache_width), BF16,
                         sh)}
    return cfg, params, pool, {}


@pytest.mark.parametrize("w", [64, 1024])
def test_latent_decode_kernel_compiles_at_cell_shapes(one_chip, w):
    from ray_tpu.ops.mla_paged_attention import mla_paged_decode_attention

    _assert_kernel(
        functools.partial(mla_paged_decode_attention, value_width=512,
                          scale=192 ** -0.5),
        _spec((64, 128, 640), BF16, one_chip),
        _spec((5, 33000, 16, 640), BF16, one_chip),
        _spec((), jnp.int32, one_chip), _spec((64, w), jnp.int32, one_chip),
        _spec((64,), jnp.int32, one_chip), _spec((64,), jnp.int32, one_chip))


@pytest.mark.parametrize("c", [64, 512, 1024])
def test_latent_prefill_kernel_compiles_at_cell_shapes(one_chip, c):
    """A chunk of ``c`` queries, 128 heads ``[q_nope 128 | q_rope 64 | 0]``,
    over the 641-block table's latent rows in whole tiles of 1,024."""
    from ray_tpu.ops.mla_prefill_attention import mla_prefill_attention

    _assert_kernel(
        functools.partial(mla_prefill_attention, scale=192 ** -0.5),
        _spec((c, 128 * 256), BF16, one_chip),
        _spec((11 * 1024, 640), BF16, one_chip),
        _spec((128, 128, 512), BF16, one_chip),
        _spec((128, 512, 128), BF16, one_chip),
        _spec((), jnp.int32, one_chip))


@pytest.mark.parametrize("rows", [64, 256, 512, 1024])
def test_grouped_expert_kernel_compiles_at_cell_shapes(one_chip, rows):
    """The grouped product of a chunk's (token, expert) pairs, ``rows`` of
    them at most (64: a decode token-step's, one row tile), against the 16
    held experts as they lie in the four expert
    layers' stacks: gate and up ``[4, 7680, 16 x 2048]`` (k 7680, n 2048, an
    expert its columns), down ``[4, 16 x 2048, 7680]`` (k 2048, n 7680, an
    expert its rows)."""
    from ray_tpu.models.pangu_moe import _row_tile
    from ray_tpu.ops.moe_grouped_ffn import moe_grouped_ffn

    names = _kernel_instructions(
        functools.partial(moe_grouped_ffn, tm=_row_tile(rows)),
        _spec((rows, 7680), BF16, one_chip),
        _spec((4, 7680, 16 * 2048), BF16, one_chip),
        _spec((4, 7680, 16 * 2048), BF16, one_chip),
        _spec((4, 16 * 2048, 7680), BF16, one_chip),
        _spec((), jnp.int32, one_chip), _spec((16,), jnp.int32, one_chip),
        _spec((rows,), jnp.float32, one_chip))
    assert any("moe_grouped_ffn_up" in n for n in names), names
    assert any("moe_grouped_ffn_down" in n for n in names), names


def test_latent_family_programs_compile_at_cell_shapes(one_chip, monkeypatch):
    """Decode over the 9,216-position table with the kernel in it, named; a
    1,024-token prefill chunk with its attention kernel in it, named, and no
    score tile among its temporaries.  The expert layers' grouped product
    (named) is in both (the model asks the backend, which is the CPU here,
    so the test answers for it), and the decode program, which holds the
    dense product too for a token-step whose pairs overflow the buffer,
    keeps no copy of a layer's held experts (1.5 GB) for either."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode, prefill = _family_programs("pangu_moe", one_chip)
    compiled = decode.compile()
    names = _custom_call_names(compiled.as_text())
    assert "mla_paged_attention" in names
    assert "moe_grouped_ffn_up" in names and "moe_grouped_ffn_down" in names
    assert "conditional" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    compiled = prefill.compile()
    names = _custom_call_names(compiled.as_text())
    assert "mla_prefill_attention" in names
    assert "moe_grouped_ffn_up" in names and "moe_grouped_ffn_down" in names
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 28


# -- the hybrid state-space family at its cell's shapes ---------------------------
# granite-4.0-h-micro whole: 36 Mamba-2 layers whose state is 64 slots of
# [32, 128, 128] float32 a layer, 4 attention layers with heads of 64 over
# 16,384 blocks of 16 positions.


def _granite_cell(sh):
    from ray_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig()

    def specs(fn):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, sh),
                            jax.eval_shape(fn))

    return (cfg,
            specs(lambda: gh.init_params(cfg, jax.random.PRNGKey(0))),
            specs(lambda: gh.init_paged_cache(cfg, 16384, 16)),
            specs(lambda: gh.init_slot_state(cfg, 64)))


def _ssm_layer_step_args(slots, sh):
    """``(eps, ssm_layer_step's operands as shapes)`` at the cell's widths
    and ``slots`` slots: the two leaves, a layer's index, the in-projection's
    rows and ``dt``, the stacked small parameters, ``active``."""
    from ray_tpu.models import granite_hybrid as gh
    from ray_tpu.ops.ssm_state_update import prepare_layer_params

    cfg = gh.GraniteHybridConfig()

    def operands():
        mp = gh.init_params(cfg, jax.random.PRNGKey(0))["mamba"]
        state = gh.init_slot_state(cfg, slots)
        return (state["ssm"], state["conv"], jnp.int32(0),
                jnp.zeros((slots, cfg.d_inner + cfg.conv_width),
                          cfg.compute_dtype),
                jnp.zeros((slots, cfg.mamba_n_heads), cfg.compute_dtype),
                prepare_layer_params(
                    mp["conv_w"], mp["conv_b"], mp["dt_bias"], mp["a_log"],
                    mp["d"], mp["norm"], cfg.mamba_d_head),
                jnp.zeros(slots, jnp.int32))

    return cfg.rms_norm_eps, jax.tree.map(
        lambda x: _spec(x.shape, x.dtype, sh), jax.eval_shape(operands))


@pytest.mark.parametrize("slots", [64, 16])
def test_ssm_layer_step_kernel_compiles_at_cell_shapes(one_chip, slots):
    """The cell's 64 slots, and a quarter of them (the leaves are taken whole
    and the loop runs over the live rows: nothing is sized by the slots)."""
    from ray_tpu.ops.ssm_state_update import ssm_layer_step

    eps, args = _ssm_layer_step_args(slots, one_chip)
    names = _kernel_instructions(functools.partial(ssm_layer_step, eps=eps),
                                 *args)
    assert any("ssm_state_update" in n for n in names), names


def test_paged_decode_attention_compiles_at_heads_of_64(one_chip):
    from ray_tpu.ops.paged_attention import paged_decode_attention

    _assert_kernel(
        functools.partial(paged_decode_attention, scale=0.015625),
        _spec((64, 32, 64), BF16, one_chip),
        _spec((4, 16384, 16, 512), BF16, one_chip),
        _spec((4, 16384, 16, 512), BF16, one_chip),
        _spec((), jnp.int32, one_chip), _spec((64, 256), jnp.int32, one_chip),
        _spec((64,), jnp.int32, one_chip), _spec((64,), jnp.int32, one_chip))


def test_hybrid_family_programs_compile_at_cell_shapes(one_chip):
    """The engine's two programs with the slot state beside the pool: both
    kernels in the decode program, by name; neither program keeps a copy of
    a stacked weight or of the 4.8 GB state (before the layers indexed their
    weights out of the whole stacks and the in-projection was split at a lane
    tile, the temporaries were 2.7 and 7.7 GB)."""
    decode, prefill = (lo.compile() for lo in
                       _family_programs("granite_hybrid", one_chip))
    names = _custom_call_names(decode.as_text())
    assert "ssm_state_update" in names and "paged_attention" in names
    assert decode.memory_analysis().temp_size_in_bytes < 256 << 20
    assert prefill.memory_analysis().temp_size_in_bytes < 512 << 20


def _loop_bodies_with(text, kernel):
    """``[(fusions, kernel calls)]`` of the compiled program's computations
    (a scan's loop body is one) that call the Pallas kernel named ``kernel``
    themselves."""
    import re

    found = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> )", text):
        calls = re.findall(
            rf"%{kernel}[\w.\-]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
            comp)
        if calls and not comp.startswith("%fused"):
            found.append((len(re.findall(r" fusion\(", comp)), len(calls)))
    return found


def test_a_mamba_layers_decode_step_is_its_products_and_one_call(one_chip):
    """The decode step with the kernels, at the cell's shapes: the loop body
    of a run of Mamba layers holds ONE ``ssm_state_update`` call and at most
    ten fusions (the two norms' three each, the in-projection, the ``dt``
    product, the out-projection, the feed-forward's two products).  The
    row-wise work between the projections (convolution, ``silu``, delta,
    decay, the window's write-back, the gated norm: sixteen fusions more, a
    copy and a slice, before they moved into the call) shows here if an edit
    spills it back out, and not first in a trace of the chip."""
    from ray_tpu.models import granite_hybrid as gh

    cfg, params, pool, state = _granite_cell(one_chip)

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    def step(params, tokens, pool, table, lengths, active, state):
        return gh.decode_step_paged(
            cfg, params, tokens, pool, table, lengths, use_kernel=True,
            active=active, slot_state=state)

    b, w = 64, 32
    text = jax.jit(step, donate_argnums=(2, 6)).lower(
        params, i32(b), pool, i32(b, w), i32(b), i32(b), state
    ).compile().as_text()
    bodies = _loop_bodies_with(text, "ssm_state_update")
    assert len(bodies) == 2, bodies      # a period's two runs of Mamba layers
    for fusions, calls in bodies:
        assert calls == 1 and fusions <= 10, bodies


# -- the Kimi-Linear family at its cell's shapes -------------------------------------
# Kimi-Linear-48B-A3B as one chip of EP16: 20 KDA layers whose state is 64
# slots of [32, 128, 128] float32 a layer, 7 MLA layers of 32 heads over 7,000
# blocks of 16 positions of a 640-wide latent row, 26 expert layers of 16
# held experts at d 2304 and f 1024.


def _kimi_cell(sh):
    from ray_tpu.models import kimi_linear as kl

    cfg = kl.KimiLinearConfig()

    def specs(fn):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, sh),
                            jax.eval_shape(fn))

    return (cfg,
            specs(lambda: kl.init_params(cfg, jax.random.PRNGKey(0))),
            specs(lambda: kl.init_paged_cache(cfg, 7000, 16)),
            specs(lambda: kl.init_slot_state(cfg, 64)))


@pytest.mark.parametrize("slots", [64, 16])
def test_kda_state_update_kernel_compiles_at_cell_shapes(one_chip, slots):
    from ray_tpu.ops.kda_state_update import kda_state_update

    f32 = jnp.float32
    vec = (slots, 32, 128)
    names = _kernel_instructions(
        kda_state_update, _spec((20, slots, 32, 128, 128), f32, one_chip),
        _spec((), jnp.int32, one_chip), _spec(vec, BF16, one_chip),
        _spec(vec, BF16, one_chip), _spec(vec, BF16, one_chip),
        _spec(vec, f32, one_chip), _spec((slots, 32), f32, one_chip),
        _spec((slots,), jnp.int32, one_chip))
    assert any("kda_state_update" in n for n in names), names


def test_latent_and_grouped_kernels_compile_at_the_kimi_widths(one_chip):
    """The three kernels the family shares with pangu, at 32 heads, ``d``
    2304 and ``f`` 1024: the latent decode kernel over the 384-block table,
    a 256-token chunk's attention, the grouped product of a chunk's pairs
    against the 26 expert layers' stacks."""
    from ray_tpu.ops.mla_paged_attention import mla_paged_decode_attention
    from ray_tpu.ops.mla_prefill_attention import mla_prefill_attention
    from ray_tpu.ops.moe_grouped_ffn import moe_grouped_ffn

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    _assert_kernel(
        functools.partial(mla_paged_decode_attention, value_width=512,
                          scale=192 ** -0.5),
        _spec((64, 32, 640), BF16, one_chip),
        _spec((7, 7000, 16, 640), BF16, one_chip), i32(), i32(64, 384),
        i32(64), i32(64))
    _assert_kernel(
        functools.partial(mla_prefill_attention, scale=192 ** -0.5),
        _spec((256, 32 * 256), BF16, one_chip),
        _spec((6 * 1024, 640), BF16, one_chip),
        _spec((32, 128, 512), BF16, one_chip),
        _spec((32, 512, 128), BF16, one_chip), i32())
    names = _kernel_instructions(
        moe_grouped_ffn, _spec((256, 2304), BF16, one_chip),
        _spec((26, 2304, 16 * 1024), BF16, one_chip),
        _spec((26, 2304, 16 * 1024), BF16, one_chip),
        _spec((26, 16 * 1024, 2304), BF16, one_chip), i32(), i32(16),
        _spec((256,), jnp.float32, one_chip))
    assert any("moe_grouped_ffn_up" in n for n in names), names
    assert any("moe_grouped_ffn_down" in n for n in names), names


def test_kimi_family_programs_compile_at_cell_shapes(one_chip, monkeypatch):
    """The engine's two programs with the slot state beside the latent pool
    and the counters: both kernels in the decode program, by name, and the
    experts' grouped product over its 64 rows' live pairs; a 256-token chunk
    with its attention kernel and the experts' grouped product; neither
    program keeps a copy of a stacked weight, of the 2.7 GB state or of the
    pool.  (The model asks the backend, which is the CPU here, so the test
    answers for it.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode, prefill = (lo.compile() for lo in
                       _family_programs("kimi_linear", one_chip))
    names = _custom_call_names(decode.as_text())
    assert "kda_state_update" in names and "mla_paged_attention" in names
    assert "moe_grouped_ffn_up" in names and "moe_grouped_ffn_down" in names
    assert decode.memory_analysis().temp_size_in_bytes < 256 << 20
    names = _custom_call_names(prefill.as_text())
    assert "mla_prefill_attention" in names
    assert "moe_grouped_ffn_up" in names and "moe_grouped_ffn_down" in names
    assert prefill.memory_analysis().temp_size_in_bytes < 512 << 20


# Laguna-S-2.1 as one chip of EP16 at 17 of 48 layers: 12 window layers of 72
# heads whose last 512 positions are a ring a slot ([12, 64, 512, 1024] twice),
# 5 full layers of 48 heads over 10,000 blocks of 16 positions, both over 8 KV
# heads of 128; 16 expert layers of 16 held experts at d 3072 and f 1024.


def _laguna_cell(sh):
    import dataclasses

    from ray_tpu.models import laguna as lg

    cfg = dataclasses.replace(
        lg.LagunaConfig(), layer_types=lg.LagunaConfig().layer_types[:17])

    def specs(fn):
        return jax.tree.map(lambda x: _spec(x.shape, x.dtype, sh),
                            jax.eval_shape(fn))

    return (cfg,
            specs(lambda: lg.init_params(cfg, jax.random.PRNGKey(0))),
            specs(lambda: lg.init_paged_cache(cfg, 10000, 16)),
            specs(lambda: lg.init_slot_state(cfg, 64)))


@pytest.mark.parametrize("heads,layers,blocks,page,w", [
    (72, 12, 64 * 4, 128, 4),      # a window layer: the ring as 4 pages a slot
    (48, 5, 10000, 16, 64),        # a full layer, a short table
    (48, 5, 10000, 16, 2048),      # ... the widest: 17,408 positions' bucket
])
def test_paged_decode_attention_compiles_at_groups_of_9_and_6(
        one_chip, heads, layers, blocks, page, w):
    """72 and 48 query heads over 8 KV heads: groups of 9 and of 6 rows of a
    query block, no multiple of a sublane tile, under the name the caller
    gives."""
    from ray_tpu.ops.paged_attention import paged_decode_attention

    def i32(*shape):
        return _spec(shape, jnp.int32, one_chip)

    pool = _spec((layers, blocks, page, 8 * 128), BF16, one_chip)
    names = _kernel_instructions(
        functools.partial(paged_decode_attention,
                          name="window_paged_attention"),
        _spec((64, heads, 128), BF16, one_chip), pool, pool, i32(),
        i32(64, w), i32(64), i32(64))
    assert names and all("window_paged_attention" in n for n in names), names


def test_laguna_family_programs_compile_at_cell_shapes(one_chip, monkeypatch):
    """The engine's two programs with the ring beside the pool and the
    counters: the decode kernel under both names in the decode program (the
    window layers' calls and the full layers' are told apart in a device
    trace) and the experts' grouped product over its 64 rows' live pairs; a
    256-token chunk with the experts' grouped product and no attention kernel;
    neither program keeps a copy of a stacked weight, of a 0.8 GB ring leaf
    or of the pool.  (The model asks the backend, which is the CPU here, so
    the test answers for it.)"""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decode, prefill = (lo.compile() for lo in
                       _family_programs("laguna", one_chip))
    names = _custom_call_names(decode.as_text()).split()
    assert any(n.startswith("window_paged_attention") for n in names), names
    assert any(n.startswith("paged_attention") for n in names), names
    assert any("moe_grouped_ffn_up" in n for n in names), names
    assert any("moe_grouped_ffn_down" in n for n in names), names
    assert decode.memory_analysis().temp_size_in_bytes < 128 << 20
    names = _custom_call_names(prefill.as_text())
    assert "paged_attention" not in names
    assert "moe_grouped_ffn_up" in names and "moe_grouped_ffn_down" in names
    assert prefill.memory_analysis().temp_size_in_bytes < 256 << 20


# -- flash attention: the 1.14 B train shape and the 8 B widths ----------------

_FLASH = {"train_1b": (8, 2048, 16, 8), "llama3_8b": (1, 2048, 32, 8)}


def _flash_args(name, sharding):
    b, s, hq, hkv = _FLASH[name]
    return (_spec((b, s, hq, _HD), BF16, sharding),
            _spec((b, s, hkv, _HD), BF16, sharding),
            _spec((b, s, hkv, _HD), BF16, sharding))


@pytest.mark.parametrize("name", sorted(_FLASH))
def test_flash_attention_forward_compiles(one_chip, name):
    from ray_tpu.ops.flash_attention import flash_attention

    _assert_kernel(functools.partial(flash_attention, causal=True),
                   *_flash_args(name, one_chip))


@pytest.mark.parametrize("name", sorted(_FLASH))
def test_flash_attention_forward_backward_compiles(one_chip, name):
    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    _assert_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                   *_flash_args(name, one_chip))


# -- the kernels' names, as a profiler trace of the chip will show them --------


def _custom_call_names(text):
    """Names of a compiled program's ``tpu_custom_call`` instructions, as
    one string."""
    import re

    return " ".join(m.group(1) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))


def _kernel_instructions(fn, *args):
    """Names of the compiled program's ``tpu_custom_call`` instructions."""
    return _custom_call_names(
        jax.jit(fn).lower(*args).compile().as_text()).split()


@pytest.mark.parametrize("kernel", ["paged_attention", "flash_attention_fwd",
                                    "flash_attention_bwd",
                                    "ssm_state_update"])
def test_kernel_name_reaches_the_compiled_instruction(one_chip, kernel):
    """``pl.pallas_call(name=)`` names the HLO instruction, and an ``XLA
    Ops`` event of a trace is named by its instruction: the benchmark's
    ``breakdown.device_ops`` then shows ``paged_attention.N`` where it had
    ``closed_call.N``.  Inside the model's layer scan and name scopes too;
    under ``jax.grad`` the name comes wrapped (``jvp_flash_attention_fwd_``,
    ``transpose_jvp_flash_attention_bwd__``)."""
    if kernel == "paged_attention":
        from ray_tpu.ops.paged_attention import paged_decode_attention

        def fn(q, pk, pv, li, table, lengths, active):
            def body(c, _):
                with jax.named_scope("attention"):
                    return c + paged_decode_attention(
                        q, pk, pv, li, table, lengths, active), None

            return jax.lax.scan(
                body, jnp.zeros((_B, _NH * _HD), jnp.float32), None,
                length=2)[0]

        args = _paged_args(16, 8, one_chip, one_chip, one_chip)
    elif kernel == "ssm_state_update":
        from ray_tpu.ops.ssm_state_update import ssm_layer_step

        eps, args = _ssm_layer_step_args(8, one_chip)

        def fn(state, window, _, proj, dt, prep, active):
            def body(leaves, li):
                with jax.named_scope("ssm"):
                    y, *leaves = ssm_layer_step(*leaves, li, proj, dt, prep,
                                                active, eps=eps)
                return tuple(leaves), y

            return jax.lax.scan(body, (state, window), jnp.arange(2))
    else:
        from ray_tpu.ops.flash_attention import flash_attention

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).astype(
                jnp.float32).sum()

        fn = jax.grad(loss, argnums=(0, 1, 2))
        args = _flash_args("train_1b", one_chip)
    names = _kernel_instructions(fn, *args)
    assert names and all("closed_call" not in n for n in names), names
    assert any(kernel in n for n in names), names


# -- grouped matmul (megablox) at the expert model's tiling -------------------


def test_gmm_compiles_at_moe_tiling(one_chip):
    from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

    _assert_kernel(
        functools.partial(gmm, preferred_element_type=BF16,
                          tiling=(512, 512, 2048)),
        _spec((65536, 2048), BF16, one_chip),
        _spec((8, 2048, 4096), BF16, one_chip),
        _spec((8,), jnp.int32, one_chip))
