#!/usr/bin/env python3
"""Kind ``serve_open_hybrid``: an open loop against one deployed configuration
of the hybrid state-space family (``model_type`` ``granitemoehybrid`` with no
routed experts: Mamba-2 layers whose state lives in the engine's slots beside
a paged KV cache for the attention layers).

``serve_open_family`` builds its model config from a table in that file, which
only a ``benchmark`` PR may edit; this kind builds this one ``model_type``
(the program's ``GraniteHybridConfig.from_published`` reads the file's
published keys) and is otherwise ``serving``'s: ``start_cluster``,
``_measure``, ``stop_cluster``, ``serving_correct``.  The seam is checked
before any cluster starts: on a program without the family the run prints
``NO RESULT`` within seconds.

**The reference check** (``correct``, besides ``serving.serving_correct``'s
platform, ``paged_attention == "kernel"`` and no failed request): served
greedy tokens held against the family's float32 reference inside the replica,
teacher-forced (a token "gives up" the reference logit between the
reference's own argmax and it).  Probes (``PROBES``), each with ids of its
own: (48, 16) and (320, 16) six times each (one padded chunk; two chunks),
(1536, 16) twice (six prompt chunks and the state they carry), and (64, 448)
twice (the state after 448 decode token-steps through the kernel).  They are
served ``AT_ONCE`` (8) at a time, the longest first, from a pool of threads
that is joined before the check goes on: the long-decode probes hold their
rows while the others chunk their prompts in between their token-steps, leave,
and hand their slots on, so ``correct`` runs the state update with several
live rows, decode dispatches between a sequence's prompt chunks and re-used
slots beside live neighbours, on the chip.  Then one more probe
(``STATE_PROBE``: 64 tokens, taken out of the engine once it has emitted 448)
for the slot's state ITSELF (``LLMServer.reference_state_check``).  The
logits of these random weights have a standard deviation of 0.0094 (the
embedding is N(0, 0.02 / 12) and the head is tied and divided by 8).  Limits
(``judge``), each between two readings (PERF.md section 6, PR 36): bf16 as
served on the chip over 29 runs, and the control of
``benchmarks/granite_lowp_reading.py``, the reference with every layer's
matrices in 8 bits, which fails all four:

- ``prompt_mean_logit_gap <= REF_PROMPT_MEAN_TOL`` (0.0004) over the 224
  tokens served behind a prompt (the 16-token probes): bf16 0.00002 to
  0.00006, 8 bits 0.0031 and 0.0034;
- ``decode_mean_logit_gap <= REF_DECODE_MEAN_TOL`` (0.0004) over the 896
  tokens of the long-decode probes: a fault that grows with the decode steps
  (a window shifted wrongly, a state updated for a row that did not decode)
  is held to this and is not diluted by the short probes: bf16 0.00003 to
  0.00004, 8 bits 0.0030 and 0.0031;
- ``max_logit_gap <= REF_MAX_TOL`` (0.005, half a standard deviation): bf16
  0.0012 to 0.0017, 8 bits 0.0155 and 0.0189; a token that is simply wrong
  gives up several standard deviations;
- ``state_rel_err <= REF_STATE_TOL`` (0.1): the norm of (the 36 layers'
  recurrent state the slot holds after some 520 positions less the float32
  recurrence's over the same tokens) over the latter's norm: bf16 as served
  0.032 to 0.037 (the bf16 program's inputs to the update: 0.004 in the first
  layer, 0.06 in the last), 8 bits 0.32 and 0.33.

A recurrent state kept in bf16 at rest is NOT told from float32 by any of
them: served tokens do not move (20 of 1,120, mean gaps 0.000004 and
0.000001), and the state itself is 0.011 off after 512 positions, a third of
what the bf16 program's own state is off (PERF.md section 6).

**Nothing survives a run.**  ``run`` ends, on every path (result, ``NO
RESULT``, an exception in the reference check, a load generator that did not
end), by listing from ``/proc`` the descendants of the harness process and
the holders of the chip's device files, killing what is left and logging
``LEFT RUNNING: <pid> <cmdline>``.

    python3 chipbench/kinds/serve_open_hybrid.py --workload <cell> --rates 4,6,8

is ``sweep.py`` for a cell of this kind (one set-up, ascending rates, 50 s a
rate, the traffic's own arrival process).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import hybrid_rows, serving  # noqa: E402
from chipbench.spec import BenchError, log  # noqa: E402

# readings and reasons: PERF.md section 6 (PR 36)
REF_PROMPT_MEAN_TOL = 0.0004
REF_DECODE_MEAN_TOL = 0.0004
REF_MAX_TOL = 0.005
REF_STATE_TOL = 0.1
# (prompt tokens, greedy tokens)
PROBES = (((48, 16), (320, 16)) * 6 + ((1536, 16),) * 2 + ((64, 448),) * 2)
LONG_DECODE = 128  # a probe that serves at least this many is a decode probe
AT_ONCE = 8        # probes in flight together
STATE_PROBE = (64, 448)  # the slot's state is read after this many tokens


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
    ssm = state["ssm"]
    checks = (("prompt_mean_logit_gap", "mean gap behind a prompt",
               mean(prompt), len(prompt), REF_PROMPT_MEAN_TOL),
              ("decode_mean_logit_gap", "mean gap of the long-decode probes",
               mean(decode), len(decode), REF_DECODE_MEAN_TOL),
              ("max_logit_gap", "largest gap", worst, len(gaps), REF_MAX_TOL))
    why = [f"{name} {got:.5f} over {n} tokens (limit {limit})"
           for _, name, got, n, limit in checks if got > limit]
    why = ("served tokens give up reference logit: " + "; ".join(why)
           if why else "")
    if not (ssm["finite"] and ssm["rel_err"] <= REF_STATE_TOL):
        why += (f"{'; ' if why else ''}the slot's recurrent state after "
                f"{state['positions']} positions is {ssm['rel_err']:.4f} "
                f"of its norm off the reference's (limit {REF_STATE_TOL})")
    return {"ok": not why, "max_logit_gap": worst,
            "compared": [[key, got, limit] for key, _, got, _, limit in checks]
            + [["state_rel_err", ssm["rel_err"], REF_STATE_TOL]],
            "prompt_mean_logit_gap": mean(prompt),
            "decode_mean_logit_gap": mean(decode),
            "prompt_tokens": len(prompt), "decode_tokens": len(decode),
            "disagree": sum(1 for g in gaps if g > 0),
            "state_rel_err": ssm["rel_err"], "why": why or None}


def model_config(config: dict, max_seq_len: int, rehearse: bool):
    """The configuration file -> the program's model config.  No result
    where the program lacks the family."""
    try:
        from ray_tpu.models.granite_hybrid import GraniteHybridConfig
    except ImportError as e:
        raise BenchError(f"the program does not have this family: {e}") from e
    if config.get("model_type") != "granitemoehybrid":
        raise BenchError(f"kind serve_open_hybrid builds model_type "
                         f"'granitemoehybrid', not "
                         f"{config.get('model_type')!r}")
    if rehearse:
        return GraniteHybridConfig.tiny(vocab_size=512,
                                        max_seq_len=max_seq_len)
    try:
        return GraniteHybridConfig.from_published(config,
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
                   max_batch_size=min(16, eng["max_batch_size"]))
    mcfg = model_config(config, eng["max_seq_len"], rehearse)
    fam = family_of(mcfg)
    if getattr(fam, "init_slot_state", None) is None:
        raise BenchError("the program's family keeps no slot state")
    return LLMConfig(model_config=mcfg, **eng)


class HybridReplica(serving.Replica):
    """``serving.Replica`` with the hybrid model config; the deploy steps
    and their checks are the parent's, restated because its constructor
    builds a Llama config before anything else."""

    def __init__(self, cell, rehearse: bool):
        from ray_tpu import serve
        from ray_tpu.llm import build_openai_app

        self.cell, self.rehearse = cell, rehearse
        self.cfg = llm_config(cell.config, rehearse)
        m = self.cfg.model_config
        self.vocab = m.vocab_size
        log(f"deploying {cell.config_entry['name']}: {type(m).__name__} "
            f"dim={m.dim} layers={m.n_layers} ({m.count('mamba')} mamba, "
            f"{m.count('attention')} attention) heads={m.n_heads}/"
            f"{m.n_kv_heads} of {m.head_dim} vocab={m.vocab_size} "
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
        # AT_ONCE at a time, longest first: the long-decode probes hold
        # their rows while the others chunk their prompts in and leave
        order = sorted(range(len(probes)),
                       key=lambda i: -(probes[i][0] + probes[i][1]))
        with ThreadPoolExecutor(AT_ONCE) as pool:  # joined before the return
            served = dict(zip(order, pool.map(
                lambda i: loadgen.send(tuple(self.base), serving.DEPLOYMENT,
                                       probes[i][2], probes[i][1],
                                       self.vocab, 300.0), order)))
        log(f"{len(probes)} probes served {AT_ONCE} at a time in "
            f"{time.monotonic() - t0:.0f}s")
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
        state = self.handle.reference_state_check.remote(
            loadgen.prompt_ids(seed, 9_100_000, plen, self.vocab), n).result(
                timeout_s=1800)
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


# -- nothing survives a run ------------------------------------------------------


def _proc_table() -> dict:
    """pid -> (parent pid, state, command line) of every process."""
    table = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                rest = f.read().rsplit(")", 1)[1].split()
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
        except (OSError, IndexError):
            continue  # it ended between the listing and the read
        table[int(name)] = (int(rest[1]), rest[0], cmd)
    return table


def left_running() -> dict:
    """pid -> command line of what a finished run must not leave: live
    descendants of this process, and holders of the chip's device files."""
    table = _proc_table()
    me = os.getpid()
    children: dict = {}
    for pid, (ppid, _, _) in table.items():
        children.setdefault(ppid, []).append(pid)
    found, todo = {}, list(children.get(me, ()))
    while todo:
        pid = todo.pop()
        todo += children.get(pid, ())
        if table[pid][1] != "Z":  # a zombie holds nothing and runs nothing
            found[pid] = table[pid][2]
    for pid in serving.chip_holders():
        if pid != me:
            found.setdefault(pid, table.get(pid, (0, "?", "?"))[2])
    return found


def sweep_processes(grace_s: float = 3.0) -> int:
    """Kill and name whatever :func:`left_running` finds; returns how many.
    ``grace_s``: what a process that is on its way out gets first."""
    deadline = time.monotonic() + grace_s
    found = left_running()
    while found and time.monotonic() < deadline:
        time.sleep(0.2)
        found = left_running()
    for pid, cmd in found.items():
        log(f"LEFT RUNNING: {pid} {cmd[:200]}")
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    if found:
        try:
            serving.wait_gone(list(found), "left running", timeout=10.0)
        except BenchError as e:
            log(str(e))
    return len(found)


# -- one run ---------------------------------------------------------------------------


def _deployed(cell, rehearse: bool, body):
    """``body(replica)`` between ``serving``'s set-up and teardown, and the
    process sweep after it whatever happened."""
    try:
        llm_config(cell.config, rehearse)  # no result without the family
        serving.start_cluster(cell.chips, rehearse)
        try:
            replica = HybridReplica(cell, rehearse)
            try:
                return body(replica)
            finally:
                replica.down()
        finally:
            serving.stop_cluster()
    finally:
        sweep_processes()


_parse_trace = serving.parse_trace


def parse_with_regions(got: dict) -> dict:
    """``serving.parse_trace``, and from the trace's file, while it is
    there, what ``trace_reduce.load`` does not keep: the stats of the
    engine's dispatch regions (``hybrid_rows``).  Both after the drain."""
    got = _parse_trace(got)
    got["regions"] = hybrid_rows.regions(got["path"])
    log("trace regions: " + ", ".join(
        f"{len(v)} {k}" for k, v in got["regions"].items()))
    return got


def run(cell, args) -> dict:
    if cell.traffic["loop"] != "open":
        raise BenchError("kind serve_open_hybrid needs a traffic file with "
                         "loop 'open'")
    traffic = cell.traffic
    if args.rehearse:
        traffic = serving.toy_traffic(traffic)
    # ``_measure`` looks the parse up in its module when the time comes
    serving.parse_trace = parse_with_regions
    try:
        return _deployed(
            cell, args.rehearse, lambda replica: serving._measure(
                cell, args, replica, traffic, float(args.seconds)))
    finally:
        serving.parse_trace = _parse_trace


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
