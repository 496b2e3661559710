"""The control reading of the cell ``kimi-linear-ep16.reason_steady``'s
reference check: what the benchmark's own float32 reference gives when every
layer's matrices are kept in 8 bits (``chipbench/reference_kimi_linear.py``
``lowp_weights=to_float8``), put through the kind's own probes and verdict
(``chipbench/kinds/serve_open_kda.py`` ``PROBES``, ``STATE_PROBE``,
``judge``).  It has to come out NOT correct, by every limit.

At the cell's configuration and the engine's own weights (``PRNGKey(0)``),
for each ``--seeds`` value: every probe's prompt as the kind builds it,
continued by seeded tokens; the float32 rows of the served positions, the
control's rows of the same positions (teacher-forced on the same tokens),
and for each position the float32 logit the control's argmax gives up against
the float32 argmax: the statistic ``LLMServer.reference_check`` reports for
served tokens.  Then the state probe: every KDA layer's state after its
positions, the control's against float32's, as
``LLMServer.reference_state_check`` reports a slot's; and beside it
(``bf16_state_at_rest``) the float32 state rounded to bf16 once, the least a
state kept in bf16 at rest would be off.  Prints every probe, and a seed's
verdict as ``judge`` gives it.

    python benchmarks/kimi_lowp_reading.py [--seeds 11,12]

Two forwards of plain ``jax.numpy`` a probe at 4.96 B parameters: it runs on
the chip (seconds a probe).  ``--rehearse`` walks it at toy size and exits 3.

``--control bf16_state`` is the second control: float32 weights, the KDA
state rounded to bf16 after EVERY position (``state_carry``), which is what a
state kept in bf16 at rest would do to the same probes; ``LOWP_STATE`` then
also gives each layer's own error.  ``--layers 4 --vocab 8192`` cuts the
stack to the published pattern's first layers (a dense first layer, ``K K K
M``) and the vocabulary, at the published widths: 0.65 B parameters, which a
CPU takes; a state's error is a layer's own arithmetic, its logit gaps are
not the full model's, so a cut stack gets the state probe alone.
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
    ap.add_argument("--control", default="float8",
                    choices=("float8", "bf16_state"))
    ap.add_argument("--layers", type=int, default=0,
                    help="the first so many layers only (0: all)")
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import loadgen, spec
    from chipbench import reference_kimi_linear as ref
    from chipbench.kinds import serve_open_kda as kind
    from ray_tpu.models import kimi_linear

    cfg = spec.Cell("kimi-linear-ep16.reason_steady").config
    if args.layers or args.vocab:
        cfg = cut_config(cfg, args.layers or cfg["num_hidden_layers"],
                         args.vocab or cfg["vocab_size"])
    mcfg = kind.llm_config(cfg, args.rehearse).model_config
    if args.rehearse:
        cfg = toy_config(cfg, mcfg)
    params = kimi_linear.init_params(mcfg, jax.random.PRNGKey(0))
    control, low_kw = {
        "float8": ("float8 weights", {"lowp_weights": ref.to_float8}),
        "bf16_state": ("bf16 state carried", {"state_carry": jnp.bfloat16}),
    }[args.control]
    whole = not (args.layers or args.vocab)
    vocab = mcfg.vocab_size
    for seed in (int(s) for s in args.seeds.split(",")):
        rows = []
        # a cut stack's logits are not the model's: its state probe only
        for i, (plen, n) in enumerate(kind.PROBES if whole else ()):
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
                "control": control, "seed": seed, "prompt": plen,
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

        def off(x, axis=None):
            flat = (x - want).reshape(len(want), -1), want.reshape(
                len(want), -1)
            return (np.sqrt((flat[0] ** 2).sum(axis))
                    / np.sqrt((flat[1] ** 2).sum(axis)))

        rest = np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32)
        state = {"positions": len(seq), "kda": {
            "finite": bool(np.isfinite(low).all()),
            "rel_err": float(off(low))}}
        print("LOWP_STATE " + json.dumps(dict(
            state, control=control, seed=seed, layers=len(want),
            layer_rel_err=[round(float(v), 5) for v in off(low, 1)],
            bf16_state_at_rest=float(off(rest)))), flush=True)
        if not whole:
            continue
        verdict = kind.judge(rows, state)
        print("LOWP_VERDICT " + json.dumps(dict(
            verdict, control=control, seed=seed, whole_model=whole,
            platform=jax.devices()[0].platform)),
            flush=True)
    return 3 if args.rehearse else 0


def cut_config(cfg: dict, layers: int, vocab: int) -> dict:
    """The configuration file's dict with the first ``layers`` layers of the
    published pattern and ``vocab`` rows, every width as published."""
    la = cfg["linear_attn_config"]
    return dict(cfg, num_hidden_layers=layers, vocab_size=vocab,
                linear_attn_config=dict(
                    la, kda_layers=[i for i in la["kda_layers"] if i <= layers],
                    full_attn_layers=[i for i in la["full_attn_layers"]
                                      if i <= layers]))


def toy_config(cfg: dict, mcfg) -> dict:
    """The configuration file's dict at a rehearsal's toy model config."""
    kda = [i + 1 for i, k in enumerate(mcfg.layer_types) if k == "kda"]
    return dict(
        cfg, hidden_size=mcfg.dim, num_hidden_layers=mcfg.n_layers,
        vocab_size=mcfg.vocab_size, num_attention_heads=mcfg.n_heads,
        kv_lora_rank=mcfg.kv_lora_rank,
        qk_nope_head_dim=mcfg.qk_nope_head_dim,
        qk_rope_head_dim=mcfg.qk_rope_head_dim, v_head_dim=mcfg.v_head_dim,
        intermediate_size=mcfg.ffn_dim,
        moe_intermediate_size=mcfg.moe_ffn_dim,
        num_experts=mcfg.n_held, router_outputs=mcfg.n_routed_experts,
        experts_held=list(mcfg.experts_held),
        num_experts_per_token=mcfg.n_experts_per_tok,
        linear_attn_config={
            "kda_layers": kda,
            "full_attn_layers": [i + 1 for i in range(mcfg.n_layers)
                                 if i + 1 not in kda],
            "head_dim": mcfg.kda_head_dim, "num_heads": mcfg.kda_n_heads,
            "short_conv_kernel_size": mcfg.kda_conv})


if __name__ == "__main__":
    sys.exit(main())
