"""The control reading of the cell ``pangu-ep16.docqa_warm``'s reference
check: what the benchmark's own float32 reference gives when its experts and
its cached latent are kept in 8 bits (``chipbench/reference_pangu_moe.py``
``lowp=to_float8``), put through the kind's own probes and verdict
(``chipbench/kinds/serve_open_family.py`` ``PROBES``, ``judge``).  It has to
come out NOT correct.

At the cell's configuration and the engine's own weights (``PRNGKey(0)``), for
each ``--seeds`` value: every probe's prompt as the kind builds it (the long
ones behind the traffic's documents), continued by seeded tokens; the float32
rows of the served positions, the 8-bit rows of the same positions
(teacher-forced on the same tokens), and for each position the float32 logit
the 8-bit argmax gives up against the float32 argmax: the statistic
``LLMServer.reference_check`` reports for served tokens.  Prints every probe,
and a seed's verdict as ``judge`` gives it.

    python benchmarks/pangu_lowp_reading.py [--seeds 11,12] [--short-only]

Two forwards of plain ``jax.numpy`` a probe: it runs on the chip (a minute a
long probe) or, being arithmetic and no measurement of the device, on a CPU
with 30 GB free (a quarter of an hour a long probe).  ``--short-only`` leaves
the probes behind a document out.  ``--rehearse`` walks it at toy size and
exits 3.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="11,12")
    ap.add_argument("--short-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    from chipbench import loadgen, spec
    from chipbench import reference_pangu_moe as ref
    from chipbench.kinds import serve_open_family as kind

    cfg = spec.Cell("pangu-ep16.docqa_warm").config
    mcfg = kind.llm_config(cfg, args.rehearse).model_config
    if args.rehearse:
        cfg = dict(cfg, num_hidden_layers=mcfg.n_layers,
                   first_k_dense_replace=mcfg.first_k_dense,
                   num_attention_heads=mcfg.n_heads,
                   kv_lora_rank=mcfg.kv_lora_rank,
                   qk_nope_head_dim=mcfg.qk_nope_head_dim,
                   qk_rope_head_dim=mcfg.qk_rope_head_dim,
                   v_head_dim=mcfg.v_head_dim, intermediate_size=mcfg.ffn_dim,
                   moe_intermediate_size=mcfg.moe_ffn_dim,
                   num_experts_per_tok=mcfg.n_experts_per_tok,
                   experts_held=list(mcfg.experts_held))
    from ray_tpu.models import pangu_moe

    params = pangu_moe.init_params(mcfg, jax.random.PRNGKey(0))
    vocab = mcfg.vocab_size
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = []
        for i, (plen, n, shared, group) in enumerate(kind.PROBES):
            if args.short_only and shared:
                continue
            if args.rehearse:
                plen, n, shared = min(plen, 40), min(n, 8), min(shared, 16)
            prompt = loadgen.prompt_ids(seed, 9_000_000 + i, plen - shared,
                                        vocab)
            if shared:
                prompt = loadgen.prompt_ids(seed, 1_000_000 + group, shared,
                                            vocab) + prompt
            rng = random.Random(seed * 1000 + i)
            seq = prompt + rng.choices(range(1, vocab), k=n)
            want = np.asarray(ref.reference_logits(cfg, params, seq[:-1],
                                                   first_row=plen - 1))
            low = np.asarray(ref.reference_logits(
                cfg, params, seq[:-1], first_row=plen - 1,
                lowp=ref.to_float8))
            gaps = want.max(-1) - want[np.arange(n), low.argmax(-1)]
            rows.append({"prompt": plen, "tokens": n, "document": shared,
                         "logit_gaps": [round(float(g), 4) for g in gaps]})
            print("LOWP " + json.dumps(dict(
                rows[-1], seed=seed,
                mean_logit_gap=round(float(gaps.mean()), 4),
                logit_rms_err=round(float(np.sqrt(((low - want) ** 2).mean())),
                                    4),
                logit_std=round(float(want.std()), 3))), flush=True)
            if args.rehearse:
                break
        verdict = kind.judge(rows)
        print("LOWP_VERDICT " + json.dumps(dict(
            verdict, seed=seed, platform=jax.devices()[0].platform)),
            flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
