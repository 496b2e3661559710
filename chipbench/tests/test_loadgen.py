"""The traffic generator and the load client."""

import json
import statistics
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from chipbench import loadgen, spec

CHAT = spec.load_json(spec.BENCH + "/traffic/chat_steady.json")
FREE = {k: v for k, v in CHAT.items() if k != "order_seed"}  # --seed orders
LONGDOC = spec.load_json(spec.BENCH + "/traffic/longdoc_closed.json")


def test_same_seed_same_requests_other_seed_same_work():
    a = loadgen.open_schedule(FREE, 7, 45.0)
    b = loadgen.open_schedule(FREE, 7, 45.0)
    c = loadgen.open_schedule(FREE, 2**31 + 5, 45.0)
    assert a == b
    assert a != c
    # another seed: another order of the SAME sizes and gaps
    for key in ("prompt_len", "max_tokens"):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in c)
    full = sorted(loadgen.gaps(CHAT["arrivals"], len(a), 45.0))
    for rs in (a, c):
        diffs = [y["due"] - x["due"] for x, y in zip(rs, rs[1:])]
        # request i is due at the start of gap i, so the last gap is the
        # one between the last arrival and the window's end
        assert sorted(diffs + [45.0 - rs[-1]["due"]]) == pytest.approx(full)
    assert loadgen.request_ids(CHAT, 7, a[0], 32768) == \
        loadgen.request_ids(CHAT, 7, b[0], 32768)
    assert loadgen.request_ids(CHAT, 7, a[0], 32768) != \
        loadgen.request_ids(CHAT, 8, a[0], 32768)


def test_stated_clips_and_medians_hold():
    n = round(CHAT["arrivals"]["rate_per_s"] * 45)
    reqs = loadgen.open_schedule(CHAT, 1, 45.0)
    assert len(reqs) == n
    p = [r["prompt_len"] for r in reqs]
    o = [r["max_tokens"] for r in reqs]
    assert min(p) >= 32 and max(p) <= 2048 and min(o) >= 16 and max(o) <= 512
    assert abs(statistics.median(p) - 256) <= 8
    assert abs(statistics.median(o) - 192) <= 6
    assert all(0 <= r["due"] < 45.0 for r in reqs)
    assert [r["due"] for r in reqs] == sorted(r["due"] for r in reqs)
    pool = loadgen.closed_pool(LONGDOC, 3)
    assert len(pool) == 256
    lp = [r["prompt_len"] for r in pool]
    assert min(lp) >= 1024 and max(lp) <= 3072
    assert abs(statistics.median(lp) - 1536) <= 16
    assert all(32 <= r["max_tokens"] <= 96 for r in pool)
    ids = loadgen.prompt_ids(5, 1, 500, 32768)
    assert len(ids) == 500 and all(1 <= t < 32768 for t in ids)


def test_shared_prefix_is_data_only():
    t = dict(CHAT, shared_prefix={"groups": 2, "len": 16})
    reqs = loadgen.open_schedule(t, 1, 10.0)
    a, b = [r for r in reqs if r["key"] % 2 == 0][:2]
    ia, ib = (loadgen.request_ids(t, 1, r, 1000) for r in (a, b))
    assert ia[:16] == ib[:16] and ia[16:] != ib[16:]


class _SSE(BaseHTTPRequestHandler):
    """Answers like the proxy: SSE frames of ids, after a fixed service time
    during which the (single) server is busy, so a queue forms."""

    busy = threading.Lock()

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        with self.busy:
            time.sleep(0.15)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Connection", "close")
        self.end_headers()
        n = body["max_tokens"]
        for i in range(0, n, 4):
            text = "".join(f"{7 + j} " for j in range(min(4, n - i)))
            frame = {"choices": [{"text": text}]}
            self.wfile.write(b"data: " + json.dumps(frame).encode() + b"\n\n")
            self.wfile.flush()
        self.wfile.write(b"data: [DONE]\n\n")


@pytest.fixture()
def server():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _SSE)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield srv.server_address
    srv.shutdown()


def test_open_loop_times_from_the_due_time(server):
    traffic = {"loop": "open", "ramp_s": 0, "drain_s": 20,
               "arrivals": {"process": "uniform", "rate_per_s": 10.0},
               "prompt_len": {"dist": "fixed", "value": 8},
               "output_len": {"dist": "fixed", "value": 8}}
    plan = {"base": list(server), "model": "m", "seed": 1, "seconds": 1.0,
            "vocab": 100, "traffic": traffic,
            "window_t0": time.monotonic() + 0.3}
    rows = sorted(loadgen.run_open(plan), key=lambda r: r["due"])
    assert len(rows) == 10 and all(r["ok"] and r["got"] == 8 for r in rows)
    # sent on schedule whatever the server does (10 a second against a
    # server that takes 0.15 s each): the generator was not late ...
    assert max(r["sent"] - r["due"] for r in rows) < 0.05
    # ... and the wait a stall imposes on later requests counts: the last
    # request waited for the nine before it, measured from when it was DUE
    waits = [r["first"] - r["due"] for r in rows]
    assert waits[0] < 0.3 and waits[-1] > 0.5
    assert waits == sorted(waits) or waits[-1] > waits[0] + 0.3


def test_closed_loop_cuts_at_the_window_and_counts_arrivals(server):
    traffic = {"loop": "closed", "clients": 2, "request_pool": 4, "ramp_s": 0.3,
               "prompt_len": {"dist": "fixed", "value": 8},
               "output_len": {"dist": "fixed", "value": 8}}
    t0 = time.monotonic() + 0.5
    plan = {"base": list(server), "model": "m", "seed": 1, "seconds": 1.0,
            "vocab": 100, "traffic": traffic, "window_t0": t0}
    rows = loadgen.run_closed(plan)
    assert time.monotonic() < t0 + 1.0 + 1.5      # ended with the window
    done = [r for r in rows if r["ok"]]
    assert 4 <= len(done) <= 12                    # one server, 0.15 s each
    assert all(r["cut"] for r in rows if not r["ok"])
    assert any(r["first"] < 0 for r in rows)       # the ramp ran before t0


def test_a_failed_request_is_a_row_not_a_crash():
    row = loadgen.send(("127.0.0.1", 9), "m", [1, 2], 4, 100, 2.0)
    assert not row["ok"] and row["error"]


def test_order_seed_fixes_the_schedule_and_leaves_the_ids_to_the_seed():
    assert CHAT.get("order_seed") is not None
    a = loadgen.open_schedule(CHAT, 1, 50.0)
    b = loadgen.open_schedule(CHAT, 2**31 + 9, 50.0)
    assert a == b                                   # sizes, gaps and their order
    assert loadgen.request_ids(CHAT, 1, a[3], 32768) != \
        loadgen.request_ids(CHAT, 2**31 + 9, b[3], 32768)
    free = {k: v for k, v in CHAT.items() if k != "order_seed"}
    assert loadgen.open_schedule(free, 1, 50.0) != loadgen.open_schedule(free, 2, 50.0)
