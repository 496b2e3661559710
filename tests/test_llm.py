"""LLM engine (KV cache, continuous batching), serving, batch processor.

reference test models: ray.llm batch/serve tests; the KV-cache parity test
mirrors how incremental decoding is validated against full forward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import (
    GenerationConfig,
    LLMConfig,
    ProcessorConfig,
    build_llm_processor,
    make_engine,
)
from ray_tpu.models.llama import LlamaConfig, init_params

pytestmark = pytest.mark.slow  # module lane: see pytest.ini


@pytest.fixture(scope="module")
def tiny_cfg():
    return LlamaConfig.tiny()


@pytest.fixture(scope="module")
def engine(tiny_cfg):
    return _engine(tiny_cfg, max_batch_size=4, max_seq_len=128)


def _engine(cfg, params=None, **kw):
    return make_engine(LLMConfig(model_config=cfg, block_size=8,
                                 prefill_chunk=16, **kw), params)


def test_decode_matches_full_forward(tiny_cfg):
    """Greedy incremental decode must equal argmax over the full forward."""
    from ray_tpu.models import llama

    params = llama.init_params(tiny_cfg, jax.random.PRNGKey(0))
    prompt = list(np.random.RandomState(0).randint(1, 255, size=7))
    n_new = 8

    # reference: full forward re-run each step, unpadded and unjitted
    seq = list(prompt)
    for _ in range(n_new):
        logits = llama.forward(tiny_cfg, params, jnp.asarray([seq]))
        seq.append(int(jnp.argmax(logits[0, -1])))
    expected = seq[len(prompt):]

    eng = _engine(tiny_cfg, params, max_batch_size=2, max_seq_len=64)
    out = eng.generate([prompt], GenerationConfig(max_new_tokens=n_new))[0]
    assert out == expected


def test_engine_batch_generate(engine):
    prompts = [[1, 2, 3], [7, 8, 9, 10], [42]]
    outs = engine.generate(prompts, GenerationConfig(max_new_tokens=5))
    assert len(outs) == 3
    assert all(len(o) == 5 for o in outs)


def test_engine_continuous_batching_join(tiny_cfg):
    """A request added mid-generation joins the running batch.

    decode_chunk=1: this test paces generation token-by-token to land a
    second request mid-flight; the default chunked stepping would finish
    the first request within one step()."""
    engine = _engine(tiny_cfg, max_batch_size=4, max_seq_len=128,
                     decode_chunk=1)
    done = {}

    def pump(n):
        for _ in range(n):
            for rid, toks in engine.step().items():
                done.setdefault(rid, []).extend(toks)
            if not engine.has_work():
                break

    r1 = engine.add_request([1, 2, 3], GenerationConfig(max_new_tokens=10))
    pump(3)
    assert 0 < len(done.get(r1, [])) < 10  # mid-generation
    r2 = engine.add_request([5, 6], GenerationConfig(max_new_tokens=4))
    pump(40)
    assert len(done[r1]) == 10
    assert len(done[r2]) == 4


def test_engine_more_requests_than_slots(tiny_cfg):
    eng = _engine(tiny_cfg, max_batch_size=2, max_seq_len=64)
    outs = eng.generate([[i + 1] for i in range(5)],
                        GenerationConfig(max_new_tokens=3))
    assert len(outs) == 5
    assert all(len(o) == 3 for o in outs)


def test_engine_stop_tokens_and_validation(engine):
    with pytest.raises(ValueError):
        engine.add_request([])
    with pytest.raises(ValueError):
        engine.add_request([1], GenerationConfig(max_new_tokens=10_000))
    with pytest.raises(ValueError):
        engine.add_request(
            [1], GenerationConfig(stop_token_ids=tuple(range(99))))


def test_engine_stop_token_truncates_mid_chunk(tiny_cfg):
    """In-program stop handling: the device scan must deactivate a slot the
    moment it emits a stop id, suppressing the rest of the chunk."""
    params = init_params(tiny_cfg, jax.random.PRNGKey(3))
    eng = _engine(tiny_cfg, params, max_batch_size=2, max_seq_len=128,
                  decode_chunk=8)
    prompt = [5, 6, 7]
    free = eng.generate([prompt], GenerationConfig(max_new_tokens=24))[0]
    assert len(free) == 24
    # pick a token the unconstrained run actually emits mid-stream (not the
    # first token, so the stop fires inside a decode chunk, not at prefill)
    stop = next(t for t in free[1:] if t != free[0])
    cut = eng.generate([prompt], GenerationConfig(
        max_new_tokens=24, stop_token_ids=(stop,)))[0]
    assert cut == free[:free.index(stop, 1) + 1], (free, cut)
    # a fresh slot after a stop-terminated one must generate cleanly
    again = eng.generate([prompt], GenerationConfig(max_new_tokens=24))[0]
    assert again == free


def test_llm_serve_deployment(ray_start_regular, tiny_cfg):
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_deployment

    app = build_llm_deployment(
        LLMConfig(model_config=tiny_cfg, max_batch_size=4, max_seq_len=64,
                  chips_per_replica=0))
    handle = serve.run(app, name="llm-app")
    try:
        resp = handle.remote({"prompt": [1, 2, 3], "max_new_tokens": 4}).result(
            timeout_s=240)
        assert len(resp["tokens"]) == 4
        # concurrent callers share the decode batch
        futs = [handle.remote({"prompt": [i + 1], "max_new_tokens": 3})
                for i in range(4)]
        outs = [f.result(timeout_s=240) for f in futs]
        assert all(len(o["tokens"]) == 3 for o in outs)
    finally:
        serve.delete("llm-app")


def test_llm_batch_processor(ray_start_regular, tiny_cfg):
    import ray_tpu.data as rdata

    ds = rdata.from_items([{"prompt_tokens": [1 + i, 2 + i]} for i in range(6)])
    processor = build_llm_processor(
        ProcessorConfig(
            llm_config=LLMConfig(model_config=tiny_cfg, max_batch_size=4,
                                 max_seq_len=64, chips_per_replica=0),
            batch_size=3, concurrency=1, max_new_tokens=4),
        postprocess=lambda row: {"n": len(row["generated_tokens"]), **row},
    )
    rows = processor(ds).take_all()
    assert len(rows) == 6
    assert all(r["n"] == 4 for r in rows)


def test_engine_mixed_sampling_single_batch(tiny_cfg):
    """Greedy and temperature callers share one decode batch/program."""
    from ray_tpu.models import llama

    params = llama.init_params(tiny_cfg, jax.random.PRNGKey(0))
    eng = _engine(tiny_cfg, params, max_batch_size=4, max_seq_len=64)
    r_greedy = eng.add_request([1, 2, 3], GenerationConfig(max_new_tokens=6))
    r_hot = eng.add_request([1, 2, 3],
                            GenerationConfig(max_new_tokens=6, temperature=1.5,
                                             top_k=50))
    done = {}
    for _ in range(30):
        for rid, toks in eng.step().items():
            done.setdefault(rid, []).extend(toks)
        if not eng.has_work():
            break
    assert len(done[r_greedy]) == 6 and len(done[r_hot]) == 6

    # greedy slot must match a solo greedy run exactly
    solo = _engine(tiny_cfg, params, max_batch_size=1, max_seq_len=64)
    expected = solo.generate([[1, 2, 3]], GenerationConfig(max_new_tokens=6))[0]
    assert done[r_greedy] == expected
