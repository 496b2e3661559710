#!/usr/bin/env python3
"""Kind ``serve_open_kda``: an open loop against one deployed configuration
of the Kimi-Linear family (``model_type`` ``kimi_linear``: KDA layers whose
matrix state lives in the engine's slots, NoPE latent attention over a paged
latent pool, routed experts of which this chip holds a range).

``serve_open_family`` builds its model config from a table in that file, which
only a ``benchmark`` PR may edit; this kind builds this one ``model_type``
(the program's ``KimiLinearConfig.from_published`` reads the file's published
keys) as ``serve_open_hybrid`` builds its own, and is otherwise ``serving``'s:
``start_cluster``, ``_measure``, ``stop_cluster``, ``serving_correct``.  The
seam is checked before any cluster starts: on a program without the family
the run prints ``NO RESULT`` within seconds.  What ``serve_open_hybrid`` has
that does not depend on the family is taken from it: the sweep of ``/proc``
that ends every run (``sweep_processes``, ``left_running``) and the dispatch
regions' stats read from the trace's file after the drain
(``parse_with_regions``).

**The reference check** (``correct``, besides ``serving.serving_correct``'s
platform, ``paged_attention == "kernel"`` and no failed request): served
greedy tokens held against the family's float32 reference inside the replica,
at the published widths and full depth, teacher-forced (a token "gives up"
the reference logit between the reference's own argmax and it).  Probes
(``PROBES``), each with ids of its own: (48, 16) and (320, 16) twice each
(one padded chunk; two chunks), (1536, 16) twice (six prompt chunks and the
state they carry), and (64, 448) twice (the state after 448 decode
token-steps through the kernel).  They are served ``AT_ONCE`` (6) at a time,
the longest first, from a pool of threads that is joined before the check
goes on: the long-decode probes hold their rows while the others chunk their
prompts in between their token-steps, leave, and hand their slots on.  Then
one more probe (``STATE_PROBE``: 64 tokens, taken out of the engine once it
has emitted 448) for the slot's state ITSELF
(``LLMServer.reference_state_check``: the 20 KDA layers' state against the
recurrence).  **Both phases run under load**: ``BACKGROUND`` (24) other
streams of ``BACKGROUND_SIZE`` are started ``BACKGROUND_LEAD_S`` ahead and
decode all through, so the probes' rows are among some 30 live ones, as a
window's are, and the kernel's loop over the live-row list (a row's fetch and
store under its neighbours') is held to the reference at the load it is timed
at; a background stream that fails fails the check, and the log says when
the background's last tokens came beside when the probes were back.  A reference forward of 4.96 B
parameters in eager float32 takes the replica 10 to 30 s, so the probes are
few.  The logits of these random
weights have a standard deviation of 0.96 (the head is N(0, 0.02) over 2,304
normed values) and the largest of 163,840 stands about 0.2 over the second:
a bf16 program's served token is often not the reference's own and gives up
little.  Limits (``judge``), each between two readings taken on the chip,
near their geometric mean (PERF.md section 6, PR 47): bf16 as served, and the
control of ``benchmarks/kimi_lowp_reading.py``, the reference with every
layer's matrices in 8 bits, which fails every one:

- ``prompt_mean_logit_gap <= REF_PROMPT_MEAN_TOL`` (0.07) over the tokens
  served behind a prompt (the 16-token probes): bf16 0.021, 8 bits 0.24 (no
  probe of the control under 0.16);
- ``decode_mean_logit_gap <= REF_DECODE_MEAN_TOL`` (0.07) over the 896
  tokens of the long-decode probes: a fault that grows with the decode steps
  (a window shifted wrongly, a state updated for a row that did not decode)
  is held to this and is not diluted by the short probes: bf16 0.018, 8 bits
  0.27;
- ``max_logit_gap <= REF_MAX_TOL`` (0.75, three quarters of a standard
  deviation): bf16 0.37, 8 bits 1.44 (1.38 and 1.44 in the two long-decode
  probes); a token that is simply wrong gives up several standard
  deviations;
- ``state_rel_err <= REF_STATE_TOL`` (0.15): the norm of (the 20 layers' KDA
  state the slot holds after some 510 positions less the float32
  recurrence's over the same tokens) over the latter's norm: bf16 as served
  0.075 (the bf16 program's inputs to the update), 8 bits 0.32.

Whether a KDA state kept in bf16 at rest would be told is NOT settled by
these limits.  Rounding the final float32 state once moves it by 0.0017 of its
norm, but that is a lower bound and not what such a state does: carried in
bf16 through every position (``benchmarks/kimi_lowp_reading.py --control
bf16_state``: float32 weights and activations, the state rounded after each
of 511 positions) a layer's state is off by 0.010, 0.019, 0.036, ... 0.075 in
the first nine KDA layers (0.052 over them; the published widths, the stack
cut to 12 layers, arithmetic on a CPU), where the bf16 program as served
reads 0.004, 0.012, 0.019, ... 0.065 in the same layers and 0.080 over all
twenty.  So the rounding of the carry alone costs as much as everything else
the bf16 program rounds, a program with both would read somewhere from 0.11
to 0.18, and 0.15 lies inside that range.  The full-depth control was not
run on the chip; a PR that makes the leaf bf16 brings that reading and moves
``REF_STATE_TOL`` under it (PERF.md section 6, PR 47).

    python3 chipbench/kinds/serve_open_kda.py --workload <cell> --rates 2,3,4

is ``sweep.py`` for a cell of this kind (one set-up, ascending rates, 50 s a
rate, the traffic's own arrival process).
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import serving  # noqa: E402
from chipbench.kinds import serve_open_hybrid as hybrid  # noqa: E402
from chipbench.spec import BenchError, log  # noqa: E402

# readings and reasons: PERF.md section 6 (PR 47)
REF_PROMPT_MEAN_TOL = 0.07
REF_DECODE_MEAN_TOL = 0.07
REF_MAX_TOL = 0.75
REF_STATE_TOL = 0.15
# (prompt tokens, greedy tokens)
PROBES = (((48, 16), (320, 16)) * 2 + ((1536, 16),) * 2 + ((64, 448),) * 2)
LONG_DECODE = 128  # a probe that serves at least this many is a decode probe
AT_ONCE = 6        # probes in flight together
STATE_PROBE = (64, 448)  # the slot's state is read after this many tokens
BACKGROUND = 24    # other streams decoding beside the probes
BACKGROUND_SIZE = (64, 768)  # theirs: outlasts a long-decode probe
BACKGROUND_LEAD_S = 3.0      # 24 one-chunk prompts are in by then


def judge(rows: list, state: dict) -> dict:
    """The reference check's verdict on ``rows``, one a probe: ``tokens``
    (served) and ``logit_gaps`` (a served token each); and on ``state``,
    ``LLMServer.reference_state_check``'s reply for the state probe."""
    gaps = [g for r in rows for g in r["logit_gaps"]]
    decode = [g for r in rows if r["tokens"] >= LONG_DECODE
              for g in r["logit_gaps"]]
    prompt = [g for r in rows if r["tokens"] < LONG_DECODE
              for g in r["logit_gaps"]]

    def mean(v):
        return sum(v) / len(v) if v else 0.0

    worst = max(gaps)
    kda = state["kda"]
    checks = (("prompt_mean_logit_gap", "mean gap behind a prompt",
               mean(prompt), len(prompt), REF_PROMPT_MEAN_TOL),
              ("decode_mean_logit_gap", "mean gap of the long-decode probes",
               mean(decode), len(decode), REF_DECODE_MEAN_TOL),
              ("max_logit_gap", "largest gap", worst, len(gaps), REF_MAX_TOL))
    why = [f"{name} {got:.5f} over {n} tokens (limit {limit})"
           for _, name, got, n, limit in checks if got > limit]
    why = ("served tokens give up reference logit: " + "; ".join(why)
           if why else "")
    if not (kda["finite"] and kda["rel_err"] <= REF_STATE_TOL):
        why += (f"{'; ' if why else ''}the slot's KDA state after "
                f"{state['positions']} positions is {kda['rel_err']:.4f} "
                f"of its norm off the reference's (limit {REF_STATE_TOL})")
    return {"ok": not why, "max_logit_gap": worst,
            "compared": [[key, got, limit] for key, _, got, _, limit in checks]
            + [["state_rel_err", kda["rel_err"], REF_STATE_TOL]],
            "prompt_mean_logit_gap": mean(prompt),
            "decode_mean_logit_gap": mean(decode),
            "prompt_tokens": len(prompt), "decode_tokens": len(decode),
            "disagree": sum(1 for g in gaps if g > 0),
            "state_rel_err": kda["rel_err"], "why": why or None}


def model_config(config: dict, max_seq_len: int, rehearse: bool):
    """The configuration file -> the program's model config.  No result
    where the program lacks the family."""
    try:
        from ray_tpu.models.kimi_linear import KimiLinearConfig
    except ImportError as e:
        raise BenchError(f"the program does not have this family: {e}") from e
    if config.get("model_type") != "kimi_linear":
        raise BenchError(f"kind serve_open_kda builds model_type "
                         f"'kimi_linear', not {config.get('model_type')!r}")
    if rehearse:
        return KimiLinearConfig.tiny(vocab_size=512, max_seq_len=max_seq_len)
    try:
        return KimiLinearConfig.from_published(config,
                                               max_seq_len=max_seq_len)
    except ValueError as e:
        raise BenchError(f"the program's family does not compute this "
                         f"configuration: {e}") from e


def llm_config(config: dict, rehearse: bool):
    try:
        from ray_tpu.llm import LLMConfig
        from ray_tpu.models.family import family_of
    except ImportError as e:
        raise BenchError(f"the program has no model-family seam: {e}") from e
    eng = dict(config["engine"])
    if rehearse:
        eng.update(num_blocks=2048, prefill_chunk=64,
                   max_batch_size=min(8, eng["max_batch_size"]))
    mcfg = model_config(config, eng["max_seq_len"], rehearse)
    fam = family_of(mcfg)
    if getattr(fam, "init_slot_state", None) is None:
        raise BenchError("the program's family keeps no slot state")
    return LLMConfig(model_config=mcfg, **eng)


class KdaReplica(serving.Replica):
    """``serving.Replica`` with the Kimi-Linear model config; the deploy
    steps and their checks are the parent's, restated because its
    constructor builds a Llama config before anything else."""

    def __init__(self, cell, rehearse: bool):
        from ray_tpu import serve
        from ray_tpu.llm import build_openai_app

        self.cell, self.rehearse = cell, rehearse
        self.cfg = llm_config(cell.config, rehearse)
        m = self.cfg.model_config
        self.vocab = m.vocab_size
        log(f"deploying {cell.config_entry['name']}: {type(m).__name__} "
            f"dim={m.dim} layers={m.n_layers} ({m.count('kda')} KDA, "
            f"{m.count('mla')} MLA) experts {m.experts_held} of "
            f"{m.n_routed_experts} vocab={m.vocab_size} "
            f"blocks={self.cfg.num_blocks}x{self.cfg.block_size} "
            f"batch={self.cfg.max_batch_size} "
            f"prefill_chunk={self.cfg.prefill_chunk} "
            f"resources={self.cfg.resources_per_replica()}")
        t0 = time.monotonic()
        app = build_openai_app(self.cfg, params=None,
                               tokenizer=serving.IdTokenizer(),
                               model_id=serving.DEPLOYMENT,
                               name=serving.DEPLOYMENT)
        self.handle = serve.run(app, name=serving.DEPLOYMENT,
                                route_prefix="/v1")
        self.base = serve.start_http_proxy(port=0)
        self.report = self.handle.device_report.remote().result(timeout_s=600)
        self.up_s = time.monotonic() - t0
        rep = self.report
        log(f"replica pid {rep['pid']} up in {self.up_s:.1f}s on "
            f"{rep['device_count']} x {rep['device_kind']} "
            f"({rep['platform']}), attention={rep['paged_attention']}, "
            f"warmup={rep['warmup']}, memory={rep['memory']}, slot state "
            f"{rep['utilization'].get('slot_state')}")
        if rep["pid"] == os.getpid():
            raise BenchError("the replica runs in the harness process")
        if rehearse:
            return
        if rep["platform"] != "tpu":
            raise BenchError(f"replica's platform is {rep['platform']!r}")
        if rep["device_count"] != cell.chips:
            raise BenchError(f"replica sees {rep['device_count']} devices, "
                             f"the cell asks {cell.chips}")
        if rep["warmup"] is None:
            raise BenchError("warmup() did not run")
        holders = serving.chip_holders()
        if set(holders) != {rep["pid"]}:
            raise BenchError(f"chip device files are held by {holders}, not "
                             f"only by the replica {rep['pid']}")

    def _under_load(self, what: str, seed: int, phase: int, body):
        """``body()`` while ``BACKGROUND`` other streams decode: its value,
        when it ended, and why the load was not one (None: it was)."""
        from concurrent.futures import ThreadPoolExecutor

        from chipbench import loadgen

        plen, n = (24, 48) if self.rehearse else BACKGROUND_SIZE
        rows = min(BACKGROUND, self.cfg.max_batch_size - AT_ONCE)
        ids = [loadgen.prompt_ids(seed, 9_200_000 + 1000 * phase + i, plen,
                                  self.vocab) for i in range(rows)]
        t0 = time.monotonic()
        with ThreadPoolExecutor(rows) as pool:  # joined on the way out
            sent = [pool.submit(loadgen.send, tuple(self.base),
                                serving.DEPLOYMENT, p, n, self.vocab, 300.0)
                    for p in ids]
            time.sleep(0.5 if self.rehearse else BACKGROUND_LEAD_S)
            got = body()
            ended = time.monotonic()
            background = [f.result() for f in sent]
        bad = [r["error"] for r in background if not r["ok"]]
        ends = sorted(r["last"] - t0 for r in background if r["ok"])
        log(f"{what} under load: {rows} background streams of {n} tokens, "
            f"{len(bad)} failed, their last tokens "
            f"{ends[0]:.1f} to {ends[-1]:.1f}s after the start; {what} "
            f"back at {ended - t0:.1f}s" if ends else
            f"{what} under load: all {rows} background streams failed")
        return got, ended, (f"a background stream beside the {what} failed: "
                            f"{bad[0]}" if bad else None)

    def check_reference(self, seed: int) -> dict:
        from concurrent.futures import ThreadPoolExecutor

        from chipbench import loadgen

        probes = []
        for i, (plen, n) in enumerate(PROBES):
            if self.rehearse:
                plen, n = min(plen, 40), min(n, 16)
            probes.append((plen, n, loadgen.prompt_ids(
                seed, 9_000_000 + i, plen, self.vocab)))
        t0 = time.monotonic()
        # AT_ONCE at a time, longest first: the long-decode probes hold
        # their rows while the others chunk their prompts in and leave
        order = sorted(range(len(probes)),
                       key=lambda i: -(probes[i][0] + probes[i][1]))

        def serve_probes():
            with ThreadPoolExecutor(AT_ONCE) as pool:  # joined on the way out
                return dict(zip(order, pool.map(
                    lambda i: loadgen.send(
                        tuple(self.base), serving.DEPLOYMENT, probes[i][2],
                        probes[i][1], self.vocab, 300.0), order)))

        served, ended, why = self._under_load("probes", seed, 0,
                                              serve_probes)
        log(f"{len(probes)} probes served {AT_ONCE} at a time in "
            f"{ended - t0:.0f}s")
        if why:
            return {"ok": False, "why": why}
        rows = []
        for i, (plen, n, ids) in enumerate(probes):
            got = served[i]
            if not got["ok"]:
                return {"ok": False, "why": f"probe {i}: {got['error']}"}
            ref = self.handle.reference_check.remote(ids, got["ids"]).result(
                timeout_s=1800)
            rows.append({"prompt": plen, "tokens": n,
                         "max_logit_gap": ref["max_logit_gap"],
                         "logit_gaps": [round(g, 5)
                                        for g in ref["logit_gaps"]],
                         "first_divergent": ref["first_divergent"],
                         "logit_std": ref["logit_std"]})
            if not ref["finite"]:
                return {"ok": False, "why": f"probe {i}: reference not finite"}
        plen, n = (40, 8) if self.rehearse else STATE_PROBE
        # back only after its reference forward: the probe itself ends some
        # 12 s in, well before the background's streams do
        state, _, why = self._under_load(
            "state probe", seed, 1,
            lambda: self.handle.reference_state_check.remote(
                loadgen.prompt_ids(seed, 9_100_000, plen, self.vocab),
                n).result(timeout_s=1800))
        if why:
            return {"ok": False, "why": why}
        verdict = judge(rows, state)
        # every gap, so that a log can be judged again under other limits
        log("reference gaps [prompt, tokens, gaps]: " + json.dumps(
            [[r["prompt"], r["tokens"], r["logit_gaps"]] for r in rows]))
        log(f"float32 reference ({time.monotonic() - t0:.0f}s): "
            f"{[dict(r, logit_gaps=len(r['logit_gaps'])) for r in rows]}; "
            f"worst gap {verdict['max_logit_gap']:.5f} (limit {REF_MAX_TOL}),"
            f" mean gap behind a prompt "
            f"{verdict['prompt_mean_logit_gap']:.5f} over "
            f"{verdict['prompt_tokens']} tokens (limit "
            f"{REF_PROMPT_MEAN_TOL}), of the long-decode probes "
            f"{verdict['decode_mean_logit_gap']:.5f} over "
            f"{verdict['decode_tokens']} (limit {REF_DECODE_MEAN_TOL}), "
            f"{verdict['disagree']} tokens not the reference's own; the "
            f"slot's state after {state['positions']} positions: {state}")
        return dict(verdict, probes=rows, state=state)


def _deployed(cell, rehearse: bool, body):
    """``body(replica)`` between ``serving``'s set-up and teardown, and the
    process sweep after it whatever happened."""
    try:
        llm_config(cell.config, rehearse)  # no result without the family
        serving.start_cluster(cell.chips, rehearse)
        try:
            replica = KdaReplica(cell, rehearse)
            try:
                return body(replica)
            finally:
                replica.down()
        finally:
            serving.stop_cluster()
    finally:
        hybrid.sweep_processes()


def run(cell, args) -> dict:
    if cell.traffic["loop"] != "open":
        raise BenchError("kind serve_open_kda needs a traffic file with "
                         "loop 'open'")
    traffic = cell.traffic
    if args.rehearse:
        traffic = serving.toy_traffic(traffic)
    # ``_measure`` looks the parse up in its module when the time comes
    parse = serving.parse_trace
    serving.parse_trace = hybrid.parse_with_regions
    try:
        return _deployed(
            cell, args.rehearse, lambda replica: serving._measure(
                cell, args, replica, traffic, float(args.seconds)))
    finally:
        serving.parse_trace = parse


correct = serving.serving_correct
compared = serving.compared
device = serving.device_block


def main() -> int:
    """``sweep.py``'s loop over rates, for a cell of this kind."""
    import argparse

    from chipbench import spec, sweep

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ["PYTHONPATH"] = ROOT
    cell = spec.Cell(args.workload)
    traffic = (serving.toy_traffic(cell.traffic) if args.rehearse
               else cell.traffic)

    def body(replica):
        for rate in (float(r) for r in args.rates.split(",")):
            got = sweep.one_rate(replica, traffic, rate, args.seed,
                                 args.seconds)
            if got["failed"] > 0.2 * got["requests"]:
                log("over a fifth of the requests failed: stopping")
                break
        after = replica.handle.device_report.remote().result(timeout_s=120)
        log(f"memory after the sweep: {after['memory']}")

    try:
        _deployed(cell, args.rehearse, body)
    except BenchError as e:
        print(f"[chipbench] NO RESULT: {e}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
