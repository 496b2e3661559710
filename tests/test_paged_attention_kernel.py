"""The paged-attention decode kernel in Pallas interpret mode against the
gather path (``models/llama._paged_attend`` over the gathered span).

One parametrised test: every case builds a small pool, a block table and a
batch of rows, runs both, and compares the rows that decode.  What each case
pins is in its id: ragged rows, rows that end on a page or a chunk edge,
table widths down to one chunk, idle rows, NaN beyond the live pages, the
hand-over of the double buffer from one row to the next.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.paged_attention import paged_decode_attention

NH, KV, HD, BS, LAYERS, NB = 4, 2, 128, 16, 2, 160
CFG = llama.LlamaConfig(vocab_size=64, dim=NH * HD, n_layers=LAYERS,
                        n_heads=NH, n_kv_heads=KV, ffn_dim=64,
                        max_seq_len=1024, param_dtype=jnp.bfloat16)

# id -> (table width, tokens visible to each row (nvalid), active flags,
#        what the pool holds beyond each row's live pages)
CASES = {
    "ragged_w32": (32, [1, 40, 300, 512, 129, 17], None, "data"),
    "page_edge_16": (32, [16, 15, 17], None, "data"),
    "chunk_edge_256": (32, [256, 255, 240], None, "data"),
    "chunk_edge_257": (32, [257, 241, 272], None, "data"),
    "w2": (2, [1, 16, 17, 32], None, "data"),
    "w8": (8, [128, 64, 65, 3], None, "data"),
    "idle_rows": (32, [300, 77, 512, 1, 90], [1, 0, 1, 0, 0], "data"),
    "idle_rows_wild_lengths": (8, [100, 4000, 128, -5], [1, 0, 1, 0], "data"),
    "nan_beyond_live_pages_w32": (32, [300, 16, 257, 1], None, "nan"),
    "nan_beyond_live_pages_w8": (8, [100, 17], None, "nan"),
    "nan_and_idle": (32, [260, 33, 400], [1, 0, 1], "nan"),
    # rows of 3, 1, 4 and 3 chunks around an idle one: a row's first chunk is
    # fetched during the last chunk of the decoding row before it, into
    # whichever buffer that row left free
    # a length past the table reads the whole table and nothing beyond it
    "length_past_table_w8": (8, [500, 128, 20], None, "data"),
    "odd_and_even_chunks_w64": (64, [600, 10, 1024, 257, 700],
                                [1, 1, 1, 0, 1], "nan"),
}


def _build(w, nvalid, active, beyond, seed):
    rng = np.random.default_rng(seed)
    b = len(nvalid)
    active = np.ones(b, np.int32) if active is None else np.asarray(
        active, np.int32)
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, NH, HD), jnp.bfloat16)
    pk = np.array(jax.random.normal(kk, (LAYERS, NB, BS, KV * HD),
                                    jnp.float32))
    pv = np.array(jax.random.normal(kv_, (LAYERS, NB, BS, KV * HD),
                                    jnp.float32))
    table = np.zeros((b, w), np.int32)
    live_blocks = set()
    for r in range(b):
        if active[r]:
            n = min(-(-nvalid[r] // BS), w)
            table[r, :n] = rng.choice(np.arange(1, NB // 2), n, replace=False)
            live_blocks.update(table[r, :n].tolist())
            # beyond the live pages the row's table points at other blocks
            table[r, n:] = rng.integers(NB // 2, NB, w - n)
        else:  # an idle row's table and lengths are not the kernel's business
            table[r] = rng.integers(0, NB, w)
    if beyond == "nan":
        dead = [i for i in range(NB) if i not in live_blocks]
        pk[:, dead] = np.nan
        pv[:, dead] = np.nan
    lengths = np.asarray(nvalid, np.int32) - 1
    return (q, jnp.asarray(pk, jnp.bfloat16), jnp.asarray(pv, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(lengths), active)


def _gather(q, pk, pv, li, table, lengths):
    b, w = table.shape
    ck = pk[li, table].reshape(b, w * BS, KV, HD)
    cv = pv[li, table].reshape(b, w * BS, KV, HD)
    visible = jnp.arange(w * BS)[None, None, :] <= lengths[:, None, None]
    # the reference sees a finite pool: what is masked must not matter
    ck, cv = (jnp.where(visible[:, 0, :, None, None], x, 0) for x in (ck, cv))
    return llama._paged_attend(CFG, q[:, None], ck, cv, visible)[:, 0]


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gather(case):
    w, nvalid, active, beyond = CASES[case]
    q, pk, pv, table, lengths, act = _build(w, nvalid, active, beyond,
                                            seed=sorted(CASES).index(case))
    li = 1
    got = np.asarray(paged_decode_attention(
        q, pk, pv, li, table, lengths, jnp.asarray(act), interpret=True))
    want = np.asarray(_gather(q, pk, pv, li, table, lengths))
    on = act > 0
    assert np.isfinite(got).all()
    # bf16 operands, float32 accumulation on both sides; the kernel rounds
    # probabilities to bf16 per chunk, the gather path once
    np.testing.assert_allclose(got[on], want[on], atol=2e-2, rtol=2e-2)
    assert (got[~on] == 0).all()


def test_idle_rows_do_not_touch_the_others():
    """The decoding rows' output is bit-identical whatever the idle rows'
    lengths and table hold, and with ``active`` left out every row runs."""
    w, nvalid, active, _ = CASES["idle_rows"]
    q, pk, pv, table, lengths, act = _build(w, nvalid, active, "data", 7)
    run = lambda t, ln, a: np.asarray(paged_decode_attention(  # noqa: E731
        q, pk, pv, 0, t, ln, a, interpret=True))
    base = run(table, lengths, jnp.asarray(act))
    idle = np.flatnonzero(act == 0)
    table2 = np.asarray(table).copy()
    table2[idle] = 0
    lengths2 = np.asarray(lengths).copy()
    lengths2[idle] = [w * BS * 3, 0, -1][:len(idle)]
    other = run(jnp.asarray(table2), jnp.asarray(lengths2), jnp.asarray(act))
    np.testing.assert_array_equal(base, other)
    every = run(table, lengths, None)
    np.testing.assert_array_equal(every[act > 0], base[act > 0])
    assert np.abs(every[idle]).max() > 0
