#!/usr/bin/env python3
"""Kind ``serve_open_window``: an open loop against one deployed configuration
of the Laguna family (``model_type`` ``laguna``: window and full attention
layers in one stack, the window layers' last positions a ring in the engine's
slots, the full layers' in the paged pool, routed experts of which this chip
holds a range).

This kind builds this one ``model_type`` (the program's
``LagunaConfig.from_published`` reads the file's published keys) as
``serve_open_kda`` builds its own, and is otherwise ``serving``'s
(``start_cluster``, ``_measure``, ``stop_cluster``, ``serving_correct``),
``serve_open_hybrid``'s (the sweep of ``/proc`` that ends every run,
``sweep_processes`` and ``left_running``; the dispatch regions' stats read
from the trace's file after the drain, ``parse_with_regions``) and
``serve_open_kda``'s (the background load beside the probes,
``KdaReplica._under_load`` and its constants).  The family's module is
imported before any process starts: on a program without it the run prints
``NO RESULT`` within seconds and leaves nothing running.

**The reference check** (``correct``, besides ``serving.serving_correct``'s
platform, ``paged_attention == "kernel"`` and no failed request): served
greedy tokens held against the family's float32 reference inside the replica,
at the published widths, teacher-forced (a token "gives up" the reference
logit between the reference's own argmax and it).  Probes (``PROBES``), each
with ids of its own: (96, 16) and (700, 16) twice each (under the window of
512, where the ring never wraps; just past it: three prompt chunks and one
wrap), (4200, 24) once (seventeen chunks, eight wraps of the ring, over half
of YaRN's trained context; its float32 forward is half of the check's time)
and (300, 320) twice (a row that crosses the window's edge while it
DECODES).  They are served ``AT_ONCE`` (6) at a time,
the longest first, from a pool of threads that is joined before the check
goes on.  Then one more probe (``STATE_PROBE``: 400 tokens, taken out of the
engine once it has emitted 240, so past one wrap) for the ring ITSELF
(``LLMServer.reference_state_check``: the 12 window layers' keys and values
at the slot's last 512 positions against the reference's, in one layout).
**Both phases run under load**: ``serve_open_kda``'s 24 background streams
decode all through, so the probes' rows are among some 30 live ones, as a
window's are.  The logits of these random weights have a standard deviation of
1.1 (the head is N(0, 0.02) over 3,072 normed values).  Limits (``judge``),
each between two readings taken on the chip (PERF.md section 6, PR 51): bf16
as served over seven seeds, and the control of
``benchmarks/laguna_lowp_reading.py``, the reference with every layer's
matrices in 8 bits, READ AT ONE SEED (11), where it fails every one:

- ``prompt_mean_logit_gap <= REF_PROMPT_MEAN_TOL`` (0.02) over the tokens
  served behind a prompt (the short-decode probes): bf16 0.0011 to 0.0057
  over seven seeds, 8 bits 0.081 (no probe of the control under 0.05);
- ``decode_mean_logit_gap <= REF_DECODE_MEAN_TOL`` (0.02) over the 640 tokens
  of the long-decode probes: a fault that grows with the decode steps (a ring
  row written for a row that did not decode, a window one position off) is
  held to this and is not diluted by the short probes: bf16 0.0027 to
  0.0039, 8 bits 0.094;
- ``max_logit_gap <= REF_MAX_TOL`` (0.55, half a standard deviation): bf16
  0.108 to 0.280 over seven seeds, 8 bits 1.04 (0.79 and 1.04 in the two
  long-decode probes); the statistic with the longest tail, so the limit is
  twice the largest of the seven, near the two readings' geometric mean;
- ``ring_rel_err <= REF_RING_TOL`` (0.06): the norm of (the ring the slot
  holds less the reference's keys and values at the same positions) over the
  latter's norm, the worse of the two leaves: bf16 as served 0.022 to 0.026
  after some 700 positions (the bf16 program's inputs to the two projections; it grows with
  the layer, not with the position), 8 bits 0.154.

    python3 chipbench/kinds/serve_open_window.py --workload <cell> --rates 1,2,3

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
from chipbench.kinds import serve_open_kda as kda  # noqa: E402
from chipbench.spec import BenchError, log  # noqa: E402

# readings and reasons: the docstring above and PERF.md section 6 (PR 51)
REF_PROMPT_MEAN_TOL = 0.02
REF_DECODE_MEAN_TOL = 0.02
REF_MAX_TOL = 0.55
REF_RING_TOL = 0.06
# (prompt tokens, greedy tokens)
PROBES = (((96, 16), (700, 16)) * 2 + ((4200, 24),) + ((300, 320),) * 2)
LONG_DECODE = 128  # a probe that serves at least this many is a decode probe
STATE_PROBE = (400, 240)  # the ring is read after this many tokens


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
    ring = max(state[leaf]["rel_err"] for leaf in ("wk", "wv"))
    finite = all(state[leaf]["finite"] for leaf in ("wk", "wv"))
    checks = (("prompt_mean_logit_gap", "mean gap behind a prompt",
               mean(prompt), len(prompt), REF_PROMPT_MEAN_TOL),
              ("decode_mean_logit_gap", "mean gap of the long-decode probes",
               mean(decode), len(decode), REF_DECODE_MEAN_TOL),
              ("max_logit_gap", "largest gap", worst, len(gaps), REF_MAX_TOL))
    why = [f"{name} {got:.5f} over {n} tokens (limit {limit})"
           for _, name, got, n, limit in checks if got > limit]
    why = ("served tokens give up reference logit: " + "; ".join(why)
           if why else "")
    if not (finite and ring <= REF_RING_TOL):
        why += (f"{'; ' if why else ''}the slot's ring after "
                f"{state['positions']} positions is {ring:.4f} of its norm "
                f"off the reference's (limit {REF_RING_TOL})")
    return {"ok": not why, "max_logit_gap": worst,
            "compared": [[key, got, limit] for key, _, got, _, limit in checks]
            + [["ring_rel_err", ring, REF_RING_TOL]],
            "prompt_mean_logit_gap": mean(prompt),
            "decode_mean_logit_gap": mean(decode),
            "prompt_tokens": len(prompt), "decode_tokens": len(decode),
            "disagree": sum(1 for g in gaps if g > 0),
            "ring_rel_err": ring, "why": why or None}


def model_config(config: dict, max_seq_len: int, rehearse: bool):
    """The configuration file -> the program's model config.  No result
    where the program lacks the family."""
    try:
        from ray_tpu.models.laguna import LagunaConfig
    except ImportError as e:
        raise BenchError(f"the program does not have this family: {e}") from e
    if config.get("model_type") != "laguna":
        raise BenchError(f"kind serve_open_window builds model_type "
                         f"'laguna', not {config.get('model_type')!r}")
    if rehearse:
        return LagunaConfig.tiny(vocab_size=512, max_seq_len=max_seq_len)
    try:
        return LagunaConfig.from_published(config, max_seq_len=max_seq_len)
    except (KeyError, ValueError) as e:
        raise BenchError(f"the program's family does not compute this "
                         f"configuration: {e!r}") from e


def llm_config(config: dict, rehearse: bool):
    try:
        from ray_tpu.llm import LLMConfig
        from ray_tpu.models.family import family_of
    except ImportError as e:
        raise BenchError(f"the program has no model-family seam: {e}") from e
    eng = dict(config["engine"])
    if rehearse:
        eng.update(num_blocks=2048, prefill_chunk=64, max_seq_len=512,
                   max_batch_size=min(8, eng["max_batch_size"]))
    mcfg = model_config(config, eng["max_seq_len"], rehearse)
    if getattr(family_of(mcfg), "init_slot_state", None) is None:
        raise BenchError("the program's family keeps no slot state")
    return LLMConfig(model_config=mcfg, **eng)


class WindowReplica(kda.KdaReplica):
    """``serving.Replica`` with the Laguna model config and
    ``KdaReplica``'s background load; the deploy steps and their checks are
    the parent's, restated because every constructor above builds its own
    family's config before anything else."""

    def __init__(self, cell, rehearse: bool):
        from ray_tpu import serve
        from ray_tpu.llm import build_openai_app

        self.cell, self.rehearse = cell, rehearse
        self.cfg = llm_config(cell.config, rehearse)
        m = self.cfg.model_config
        self.vocab = m.vocab_size
        log(f"deploying {cell.config_entry['name']}: {type(m).__name__} "
            f"dim={m.dim} layers={m.n_layers} ({m.count('window')} window of "
            f"{m.window}, {m.count('full')} full) experts {m.experts_held} "
            f"of {m.n_routed_experts} vocab={m.vocab_size} "
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
        # AT_ONCE at a time, longest first: the long probes hold their rows
        # while the others chunk their prompts in and leave
        order = sorted(range(len(probes)),
                       key=lambda i: -(probes[i][0] + probes[i][1]))

        def serve_probes():
            with ThreadPoolExecutor(kda.AT_ONCE) as pool:  # joined on leaving
                return dict(zip(order, pool.map(
                    lambda i: loadgen.send(
                        tuple(self.base), serving.DEPLOYMENT, probes[i][2],
                        probes[i][1], self.vocab, 300.0), order)))

        served, ended, why = self._under_load("probes", seed, 0,
                                              serve_probes)
        log(f"{len(probes)} probes served {kda.AT_ONCE} at a time in "
            f"{ended - t0:.0f}s")
        if why:
            return {"ok": False, "why": why}
        rows = []
        for i, (plen, n, ids) in enumerate(probes):
            got = served[i]
            if not got["ok"]:
                return {"ok": False, "why": f"probe {i}: {got['error']}"}
            t1 = time.monotonic()
            ref = self.handle.reference_check.remote(ids, got["ids"]).result(
                timeout_s=1800)
            log(f"probe {i} ({plen}, {n}): float32 forward "
                f"{time.monotonic() - t1:.0f}s")
            rows.append({"prompt": plen, "tokens": n,
                         "max_logit_gap": ref["max_logit_gap"],
                         "logit_gaps": [round(g, 5)
                                        for g in ref["logit_gaps"]],
                         "first_divergent": ref["first_divergent"],
                         "logit_std": ref["logit_std"]})
            if not ref["finite"]:
                return {"ok": False, "why": f"probe {i}: reference not finite"}
        plen, n = (40, 8) if self.rehearse else STATE_PROBE
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
            f"slot's ring after {state['positions']} positions: {state}")
        return dict(verdict, probes=rows, state=state)


def _deployed(cell, rehearse: bool, body):
    """``body(replica)`` between ``serving``'s set-up and teardown, and the
    process sweep after it whatever happened."""
    try:
        llm_config(cell.config, rehearse)  # no result without the family
        serving.start_cluster(cell.chips, rehearse)
        try:
            replica = WindowReplica(cell, rehearse)
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
        raise BenchError("kind serve_open_window needs a traffic file with "
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
