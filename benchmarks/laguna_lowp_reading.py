"""The control reading of the cell ``laguna-s-ep16.mixed_queue``'s reference
check: what the benchmark's own float32 reference gives when every layer's
matrices are kept in 8 bits (``chipbench/reference_laguna.py``
``lowp_weights=to_float8``), put through the kind's own probes and verdict
(``chipbench/kinds/serve_open_window.py`` ``PROBES``, ``STATE_PROBE``,
``judge``).  It has to come out NOT correct.

At the cell's configuration and the engine's own weights (``PRNGKey(0)``),
for each ``--seeds`` value: every probe's prompt as the kind builds it,
continued by seeded tokens; the float32 rows of the served positions, the
control's rows of the same positions (teacher-forced on the same tokens),
and for each position the float32 logit the control's argmax gives up against
the float32 argmax: the statistic ``LLMServer.reference_check`` reports for
served tokens.  Then the state probe: every window layer's keys and values at
its last 512 positions, the control's against float32's, as
``LLMServer.reference_state_check`` reports a slot's ring.  Prints every
probe, and a seed's verdict as ``judge`` gives it.

    python benchmarks/laguna_lowp_reading.py [--seeds 11,12]

Two forwards of plain ``jax.numpy`` a probe at 4.29 B parameters: it runs on
the chip (seconds a probe).  ``--rehearse`` walks it at toy size and exits 3.

(ISSUE 51 named the float32 reference under the device's default matmul
precision, one pass of bf16 products, as the control.  That is the precision
the program serves at, less its bf16 activations: it reads BELOW the served
program by construction and no limit can lie between them, so the control here
is the next precision down, as the other three ``*_lowp_reading.py`` take it.)
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

CELL = "laguna-s-ep16.mixed_queue"
CONTROL = "float8 weights"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="11,12")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import numpy as np

    from chipbench import loadgen, spec
    from chipbench import reference_laguna as ref
    from chipbench.kinds import serve_open_window as kind
    from ray_tpu.models import laguna

    cfg = spec.Cell(CELL).config
    mcfg = kind.llm_config(cfg, args.rehearse).model_config
    if args.rehearse:
        cfg = toy_config(cfg, mcfg)
    params = laguna.init_params(mcfg, jax.random.PRNGKey(0))
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
                "control": CONTROL, "seed": seed, "prompt": plen,
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
        want = ref.reference_window(cfg, params, seq)
        low = ref.reference_window(cfg, params, seq, **low_kw)
        state = {"positions": len(seq)}
        for leaf in ("wk", "wv"):
            have, held = (np.asarray(x[leaf]).reshape(len(x[leaf]), -1)
                          for x in (low, want))

            def off(axis=None):
                return (np.sqrt(((have - held) ** 2).sum(axis))
                        / np.sqrt((held ** 2).sum(axis)))

            state[leaf] = {"finite": bool(np.isfinite(have).all()),
                           "rel_err": float(off()),
                           "layer_rel_err": [round(float(v), 5)
                                             for v in off(1)]}
        print("LOWP_STATE " + json.dumps(dict(state, control=CONTROL,
                                              seed=seed)), flush=True)
        verdict = kind.judge(rows, state)
        print("LOWP_VERDICT " + json.dumps(dict(
            verdict, control=CONTROL, seed=seed,
            platform=jax.devices()[0].platform)), flush=True)
    return 3 if args.rehearse else 0


def toy_config(cfg: dict, mcfg) -> dict:
    """The configuration file's dict at a toy model config (a rehearsal's,
    a test's): the published keys that ``chipbench/reference_laguna.py`` and
    ``chipbench/model_math_laguna.py`` read, at ``mcfg``'s sizes."""
    names = {"window": "sliding_attention", "full": "full_attention"}
    yarn = dict(mcfg.rope_full)
    return dict(
        cfg, hidden_size=mcfg.dim, num_hidden_layers=mcfg.n_layers,
        vocab_size=mcfg.vocab_size, head_dim=mcfg.head_dim,
        num_key_value_heads=mcfg.n_kv_heads,
        num_attention_heads=mcfg.n_heads_full,
        layer_types=[names[k] for k in mcfg.layer_types],
        num_attention_heads_per_layer=[
            mcfg.heads(k).n_heads for k in mcfg.layer_types],
        mlp_only_layers=list(range(mcfg.first_k_dense)),
        sliding_window=mcfg.window, intermediate_size=mcfg.ffn_dim,
        moe_intermediate_size=mcfg.moe_ffn_dim,
        shared_expert_intermediate_size=(mcfg.n_shared_experts
                                         * mcfg.moe_ffn_dim),
        num_experts=mcfg.n_held, router_outputs=mcfg.n_routed_experts,
        experts_held=list(mcfg.experts_held),
        num_experts_per_tok=mcfg.n_experts_per_tok,
        rope_parameters={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": mcfg.rope_theta_window,
                                  "partial_rotary_factor": 1},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": yarn["theta"],
                "factor": yarn["factor"],
                "original_max_position_embeddings": yarn[
                    "original_max_position"],
                "beta_fast": yarn["beta_fast"],
                "beta_slow": yarn["beta_slow"],
                "attention_factor": yarn["attention_factor"],
                "partial_rotary_factor": (mcfg.rotary_dim_full
                                          / mcfg.head_dim)}})


if __name__ == "__main__":
    sys.exit(main())
