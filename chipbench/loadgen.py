#!/usr/bin/env python3
"""The one general traffic generator and HTTP load client.

A traffic mix is a data file (``chipbench/traffic/<mix>.json``) of parameters;
this module turns it and a seed into requests, and, run as a program, sends
them to ``/v1/completions`` and records what came back.  It runs as a child
process of the harness so that the client's Python shares no interpreter
lock with the proxy under test; it imports nothing but the standard library
and never touches JAX.

**The seed changes the order, not the work.**  For a window of ``seconds``
an open loop sends ``round(rate * seconds)`` requests.  Their prompt lengths,
output lengths and inter-arrival gaps are the stratified quantiles
``(i + 0.5) / n`` of the stated distributions (clipped as stated), so every
seed gets the same multiset of sizes and gaps; ``--seed`` shuffles each of
the three lists independently and draws the token ids.  Where even the order
changes the work too much (a server near its knee: which long prompts meet
decides the tails), the traffic file fixes the order with ``order_seed`` and
``--seed`` draws the token ids alone.  (A ``gamma`` arrival
process has no closed-form quantile here: its gaps are drawn once from the
traffic file's ``set_seed`` and then shuffled the same way.)  A closed loop
draws a pool of ``request_pool`` requests the same way and its clients take
them in order, wrapping round.

Times are ``time.monotonic()`` (CLOCK_MONOTONIC, shared by the processes of
one machine), stored relative to the window's start.  An open-loop request
is timed from when it was DUE, and ``sent - due`` is the generator's
lateness.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import random
import socket
import statistics
import sys
import threading
import time

_NORMAL = statistics.NormalDist()


# -- distributions -----------------------------------------------------------


def _quantile(spec: dict, q: float) -> float:
    dist = spec["dist"]
    if dist == "lognormal":
        return spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(q))
    if dist == "uniform":
        return spec["min"] + q * (spec["max"] - spec["min"])
    if dist == "fixed":
        return spec["value"]
    raise ValueError(f"unknown length distribution {dist!r}")


def lengths(spec: dict, n: int) -> list:
    """``n`` whole lengths: stratified quantiles of ``spec``, clipped to its
    ``min``/``max``.  The same for every seed."""
    out = []
    for i in range(n):
        v = int(round(_quantile(spec, (i + 0.5) / n)))
        out.append(max(int(spec.get("min", 1)), min(int(spec.get("max", v)), v)))
    return out


def gaps(arrivals: dict, n: int, seconds: float) -> list:
    """``n`` inter-arrival gaps that sum to ``seconds``."""
    process = arrivals["process"]
    if process == "poisson":
        raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    elif process == "gamma":
        cv = float(arrivals["cv"])
        rng = random.Random(int(arrivals.get("set_seed", 0)))
        shape = 1.0 / (cv * cv)
        raw = [rng.gammavariate(shape, 1.0 / shape) for _ in range(n)]
    elif process == "uniform":
        raw = [1.0] * n
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    scale = seconds / sum(raw)
    return [g * scale for g in raw]


def prompt_ids(seed: int, key: int, n: int, vocab: int) -> list:
    """``n`` token ids in [1, vocab) drawn from (seed, key)."""
    rng = random.Random((seed << 20) ^ (key * 2654435761 & 0xFFFFFFFF))
    return rng.choices(range(1, vocab), k=n)


def order_seed(traffic: dict, seed: int) -> int:
    """What orders the sizes and gaps: the traffic file's ``order_seed``
    where it has one (every run then has the same schedule and ``--seed``
    draws only the token ids), else ``--seed``."""
    fixed = traffic.get("order_seed")
    return seed if fixed is None else int(fixed)


def _sized(traffic: dict, n: int, seed: int, salt: int) -> list:
    """``n`` requests' sizes: the fixed multiset, in the order seed's
    order."""
    plens = lengths(traffic["prompt_len"], n)
    olens = lengths(traffic["output_len"], n)
    seed = order_seed(traffic, seed)
    random.Random(seed * 3 + salt).shuffle(plens)
    random.Random(seed * 3 + salt + 1).shuffle(olens)
    return [{"prompt_len": p, "max_tokens": o} for p, o in zip(plens, olens)]


def open_schedule(traffic: dict, seed: int, seconds: float,
                  phase: str = "window") -> list:
    """The open loop's requests for one phase, each with its ``due`` time
    relative to the phase's start.  The ramp (phase ``"ramp"``) is drawn
    the same way over ``ramp_s`` with keys of its own."""
    rate = float(traffic["arrivals"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    salt = 0 if phase == "window" else 1000
    reqs = _sized(traffic, n, seed, salt)
    gs = gaps(traffic["arrivals"], n, seconds)
    random.Random(order_seed(traffic, seed) * 3 + salt + 2).shuffle(gs)
    t = 0.0
    for i, (r, g) in enumerate(zip(reqs, gs)):
        # request i is due at the START of gap i: the first at the phase's
        # start, the last one gap before its end
        r.update(due=t, key=salt + i, phase=phase)
        t += g
    return reqs


def closed_pool(traffic: dict, seed: int) -> list:
    n = int(traffic["request_pool"])
    reqs = _sized(traffic, n, seed, 0)
    for i, r in enumerate(reqs):
        r.update(key=i)
    return reqs


def request_ids(traffic: dict, seed: int, req: dict, vocab: int) -> list:
    """The prompt of one request.  ``shared_prefix`` (optional): the first
    ``len`` tokens are those of the request's group, so prompts of one group
    share them."""
    n = req["prompt_len"]
    share = traffic.get("shared_prefix")
    if not share:
        return prompt_ids(seed, req["key"], n, vocab)
    k = min(int(share["len"]), n)
    group = req["key"] % int(share["groups"])
    return (prompt_ids(seed, 1_000_000 + group, k, vocab)
            + prompt_ids(seed, req["key"], n - k, vocab))


# -- one request over HTTP -----------------------------------------------------


def send(base: tuple, model: str, ids: list, max_tokens: int, vocab: int,
         timeout: float, live: set = None) -> dict:
    """POST one streamed completion; returns times (absolute monotonic),
    frames ``[t, tokens]`` and the served ids.  ``live``: a set that holds
    this request's connection while it is open, for a caller that cuts
    requests short by shutting their sockets."""
    now = time.monotonic
    row = {"sent": now(), "first": None, "last": None, "got": 0,
           "frames": [], "ids": [], "ok": False, "error": None}
    body = json.dumps({"model": model, "prompt": " ".join(map(str, ids)),
                       "max_tokens": max_tokens, "temperature": 0.0,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection(base[0], base[1], timeout=timeout)
    if live is not None:
        live.add(conn)
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            row["error"] = f"HTTP {resp.status}: {resp.read(200)!r}"
            return row
        done = False
        while True:
            raw = resp.readline()
            if not raw:
                break
            line = raw.strip()
            if not line.startswith(b"data: "):
                continue
            if line == b"data: [DONE]":
                done = True
                break
            frame = json.loads(line[6:])
            if "error" in frame:
                row["error"] = str(frame["error"])[:300]
                return row
            text = frame["choices"][0].get("text") or ""
            if not text:
                continue
            toks = [int(t) for t in text.split()]
            t = now()
            if row["first"] is None:
                row["first"] = t
            row["last"] = t
            row["frames"].append([t, len(toks)])
            row["ids"].extend(toks)
        row["got"] = len(row["ids"])
        if not done:
            row["error"] = "stream ended without [DONE]"
        elif row["got"] != max_tokens:
            row["error"] = f"asked {max_tokens} tokens, got {row['got']}"
        elif not all(0 <= t < vocab for t in row["ids"]):
            row["error"] = "served id outside the vocabulary"
        else:
            row["ok"] = True
        return row
    except Exception as e:  # noqa: BLE001 - a failed request is a result row
        row["error"] = f"{type(e).__name__}: {e}"[:300]
        return row
    finally:
        if live is not None:
            live.discard(conn)
        conn.close()


# -- the loops -------------------------------------------------------------------


def _finish(row: dict, req: dict, t0: float) -> dict:
    """Times relative to the window's start; the served ids (checked in
    ``send``) are dropped."""
    for k in ("sent", "first", "last"):
        if row[k] is not None:
            row[k] -= t0
    row["frames"] = [[t - t0, n] for t, n in row["frames"]]
    row["ids"] = []
    row.update({k: req[k] for k in ("key", "prompt_len", "max_tokens")},
               phase=req.get("phase", "window"), due=req.get("due"))
    return row


def run_open(plan: dict) -> list:
    traffic, seed, vocab = plan["traffic"], plan["seed"], plan["vocab"]
    t0 = plan["window_t0"]
    ramp_s = float(traffic.get("ramp_s", 0))
    reqs = []
    if ramp_s > 0:
        for r in open_schedule(traffic, seed, ramp_s, "ramp"):
            r["due"] -= ramp_s
            reqs.append(r)
    reqs += open_schedule(traffic, seed, plan["seconds"], "window")
    # ids are made before the first due time, not on the schedule's clock
    for r in reqs:
        r["ids"] = request_ids(traffic, seed, r, vocab)
    rows, lock, threads = [], threading.Lock(), []
    base = tuple(plan["base"])
    timeout = float(traffic.get("request_timeout_s", 120))

    def one(r):
        row = send(base, plan["model"], r["ids"], r["max_tokens"], vocab,
                   timeout)
        with lock:
            rows.append(_finish(row, r, t0))

    for r in reqs:
        wait = t0 + r["due"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        th = threading.Thread(target=one, args=(r,), daemon=True)
        th.start()
        threads.append(th)
    deadline = t0 + plan["seconds"] + float(traffic.get("drain_s", 60))
    for th in threads:
        th.join(max(0.0, deadline - time.monotonic()))
    with lock:
        done = {r["key"] for r in rows}
        out = list(rows)
    for r in reqs:  # not back when the drain limit ended: failed
        if r["key"] not in done:
            out.append(_finish(
                {"sent": None, "first": None, "last": None, "got": 0,
                 "frames": [], "ids": [], "ok": False,
                 "error": "not finished when the drain limit ended"},
                r, t0))
    return out


def run_closed(plan: dict) -> list:
    """``clients`` callers, each sending its next request when the last one
    was answered, from the ramp's start to the window's end.  Requests still
    in flight when the window ends are cut (``cut``: neither completed nor
    failed); tokens count when they arrive."""
    traffic, seed, vocab = plan["traffic"], plan["seed"], plan["vocab"]
    t0 = plan["window_t0"]
    end = t0 + plan["seconds"]
    pool = closed_pool(traffic, seed)
    for r in pool:
        r["ids"] = request_ids(traffic, seed, r, vocab)
    rows, lock = [], threading.Lock()
    counter = iter(range(10 ** 9))
    base = tuple(plan["base"])
    timeout = float(traffic.get("request_timeout_s", 120))
    start = t0 - float(traffic.get("ramp_s", 0))

    live: set = set()

    def client():
        while time.monotonic() < end:
            with lock:
                n = next(counter)
            r = pool[n % len(pool)]
            row = send(base, plan["model"], r["ids"], r["max_tokens"], vocab,
                       timeout, live)
            row["cut"] = not row["ok"] and time.monotonic() >= end
            row["seq"] = n
            with lock:
                rows.append(_finish(row, r, t0))

    wait = start - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(int(traffic["clients"]))]
    for th in threads:
        th.start()
        time.sleep(0.002)  # callers do not all knock in the same millisecond
    time.sleep(max(0.0, end - time.monotonic()))
    for conn in list(live):  # cut what is in flight: the readers wake at once
        try:
            conn.sock.shutdown(socket.SHUT_RDWR)
        except (OSError, AttributeError):
            pass
    for th in threads:
        th.join(max(0.0, end + 30 - time.monotonic()))
    with lock:
        return list(rows)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(args.plan) as f:
        plan = json.load(f)
    loop = plan["traffic"]["loop"]
    rows = run_open(plan) if loop == "open" else run_closed(plan)
    with open(args.out, "w") as f:
        json.dump({"rows": rows, "ended": time.monotonic()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
