"""The control reading of the cell ``granite-h-micro.chat_bursty``'s
reference check: what the benchmark's own float32 reference gives when every layer's
matrices are kept in 8 bits (``chipbench/reference_granite_hybrid.py``
``lowp_weights=to_float8``), put through the kind's own probes and verdict
(``chipbench/kinds/serve_open_hybrid.py`` ``PROBES``, ``STATE_PROBE``,
``judge``).  It has to come out NOT correct.

At the cell's configuration and the engine's own weights (``PRNGKey(0)``),
for each ``--seeds`` value: every probe's prompt as the kind builds it,
continued by seeded tokens; the float32 rows of the served positions, the
control's rows of the same positions (teacher-forced on the same tokens),
and for each position the float32 logit the control's argmax gives up against
the float32 argmax: the statistic ``LLMServer.reference_check`` reports for
served tokens.  Then the state probe: every Mamba layer's recurrent state
after its positions, the control's against float32's, as
``LLMServer.reference_state_check`` reports a slot's.  Prints every probe,
and a seed's verdict as ``judge`` gives it.

    python benchmarks/granite_lowp_reading.py [--seeds 11,12]

Two forwards of plain ``jax.numpy`` a probe: it runs on the chip (seconds a
probe) or, being arithmetic and no measurement of the device, on a CPU with
20 GB free.  ``--rehearse`` walks it at toy size and exits 3.
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
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    from chipbench import loadgen, spec
    from chipbench import reference_granite_hybrid as ref
    from chipbench.kinds import serve_open_hybrid as kind
    from ray_tpu.models import granite_hybrid

    cfg = spec.Cell("granite-h-micro.chat_bursty").config
    mcfg = kind.llm_config(cfg, args.rehearse).model_config
    if args.rehearse:
        cfg = dict(cfg, layer_types=list(mcfg.layer_types),
                   hidden_size=mcfg.dim, num_attention_heads=mcfg.n_heads,
                   num_key_value_heads=mcfg.n_kv_heads,
                   shared_intermediate_size=mcfg.ffn_dim,
                   mamba_n_heads=mcfg.mamba_n_heads,
                   mamba_d_head=mcfg.mamba_d_head,
                   mamba_d_state=mcfg.mamba_d_state,
                   attention_multiplier=mcfg.attention_multiplier)
    params = granite_hybrid.init_params(mcfg, jax.random.PRNGKey(0))
    low_kw = {"lowp_weights": ref.to_float8}
    vocab = mcfg.vocab_size
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = []
        for i, (plen, n) in enumerate(kind.PROBES):
            if args.rehearse:
                plen, n = min(plen, 40), min(n, 8)
            prompt = loadgen.prompt_ids(seed, 9_000_000 + i, plen, vocab)
            rng = random.Random(seed * 1000 + i)
            seq = prompt + rng.choices(range(1, vocab), k=n)
            want = np.asarray(ref.reference_logits(cfg, params, seq[:-1],
                                                   first_row=plen - 1))
            low = np.asarray(ref.reference_logits(
                cfg, params, seq[:-1], first_row=plen - 1, **low_kw))
            gaps = want.max(-1) - want[np.arange(n), low.argmax(-1)]
            rows.append({"prompt": plen, "tokens": n,
                         "logit_gaps": [round(float(g), 5) for g in gaps]})
            print("LOWP " + json.dumps({
                "control": "float8 weights", "seed": seed, "prompt": plen,
                "tokens": n,
                "mean_logit_gap": round(float(gaps.mean()), 5),
                "max_logit_gap": round(float(gaps.max()), 5),
                "disagree": int((gaps > 0).sum()),
                "logit_rms_err": round(float(np.sqrt(
                    ((low - want) ** 2).mean())), 7),
                "logit_std": round(float(want.std()), 4)}), flush=True)
            if args.rehearse and i >= 1:
                break
        plen, n = (40, 8) if args.rehearse else kind.STATE_PROBE
        seq = (loadgen.prompt_ids(seed, 9_100_000, plen, vocab)
               + random.Random(seed * 1000 + 99).choices(range(1, vocab),
                                                         k=n - 1))
        want = np.asarray(ref.reference_state(cfg, params, seq))
        low = np.asarray(ref.reference_state(cfg, params, seq, **low_kw))
        state = {"positions": len(seq), "ssm": {
            "finite": bool(np.isfinite(low).all()),
            "rel_err": float(np.linalg.norm(low - want)
                             / np.linalg.norm(want))}}
        print("LOWP_STATE " + json.dumps(dict(state, seed=seed)), flush=True)
        verdict = kind.judge(rows, state)
        print("LOWP_VERDICT " + json.dumps(dict(
            verdict, control="float8 weights", seed=seed,
            platform=jax.devices()[0].platform)),
            flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
