"""Zygote (pre-fork) worker spawner.

Worker spawn via ``Popen([sys.executable, -m, workers_main])`` pays full
interpreter + import startup per worker (seconds, on a one-core box).
The zygote is one warm process that performs those imports ONCE and then
``fork()``s a child per spawn request: child startup is ~50 ms, and an
actor/worker fan-out of hundreds becomes seconds instead of tens of
minutes.  (Same design as Android's app zygote and the reference's
prestarted-worker pool, worker_pool.cc — taken further because process
creation itself is the bottleneck here.)

Fork safety: the zygote stays SINGLE-THREADED for its whole life (one
accept loop, no executors), so no lock can be held at fork time.  The
zygote never imports jax (``import ray_tpu`` does not): a child imports it
after its environment is rebuilt, so JAX_PLATFORMS, TPU_VISIBLE_CHIPS and
JAX_COMPILATION_CACHE_DIR are read from the CHILD's environment, and only
a child that touches jax ever opens a chip.  Children get a fresh session (setsid), their
own log file on fd 1/2, a rebuilt ``os.environ``, and run the normal
``workers_main.main()`` — registration with the raylet is unchanged.

Zombie reaping: children are the zygote's children, so the zygote reaps
them with a SIGCHLD handler; the raylet's liveness checks
(``_PidHandle.poll`` → ``kill(pid, 0)``) then see death promptly.

Protocol (unix socket, one JSON line per connection):
  request:  {"env": {...}, "log_file": "/path", "deadline": unix_ts}
            |  {"shutdown": true}
  reply:    {"pid": 1234}  |  {"error": "..."}
``deadline`` (optional) is the wall-clock instant the CLIENT stops
waiting; the zygote drops requests already past it instead of forking a
worker nobody tracks (the client has Popen-fallen-back by then).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import sys
import time


def _recv_line(conn: socket.socket) -> bytes:
    buf = b""
    while not buf.endswith(b"\n"):
        try:
            chunk = conn.recv(65536)
        except OSError:
            break
        if not chunk:
            break
        buf += chunk
    return buf


def _reply(conn: socket.socket, obj) -> bool:
    """Best-effort reply: a client that already hung up (spawn timeout)
    must never take the zygote loop down with BrokenPipeError.  Returns
    whether the reply was delivered — the fork path kills the child when it
    wasn't, since an unannounced pid would become an untracked duplicate of
    the client's Popen fallback."""
    try:
        conn.sendall(json.dumps(obj).encode() + b"\n")
        return True
    except OSError:
        return False
    finally:
        try:
            conn.close()
        except OSError:
            pass


def serve(sock_path: str) -> None:
    """Zygote main loop (runs as ``python -m ray_tpu._private.zygote``)."""
    # Pre-warm: everything a worker imports before it can serve a task.
    # These are the expensive imports the fork amortizes.
    import ray_tpu  # noqa: F401
    import ray_tpu._private.worker  # noqa: F401
    from ray_tpu._private import workers_main

    try:
        # compile the native stack-dump component once here: children then
        # dlopen the cached .so instead of each paying a g++ build
        from ray_tpu import _native

        _native.load("stack_dump")
    except Exception:  # noqa: BLE001 — warm-cache build is an optimization only
        pass

    def _reap(_sig, _frm):
        while True:
            try:
                pid, _ = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                return
            if pid == 0:
                return

    signal.signal(signal.SIGCHLD, _reap)

    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    srv.bind(sock_path)
    srv.listen(64)
    # readiness handshake: the raylet waits for this line
    sys.stdout.write("ZYGOTE_READY\n")
    sys.stdout.flush()

    while True:
        try:
            conn, _ = srv.accept()
        except InterruptedError:
            continue
        try:
            req = json.loads(_recv_line(conn) or b"null")
        except ValueError:
            req = None
        if not isinstance(req, dict) or (
                not req.get("shutdown") and "log_file" not in req):
            # client died mid-send (timeout/close): never fork on junk
            conn.close()
            continue
        if req.get("shutdown"):
            conn.close()
            break
        # stale-request guard: the client stops waiting at its (short)
        # socket deadline and Popen-falls-back; forking anyway would add an
        # untracked duplicate worker.  Same-host wall clock, so the
        # comparison is skew-free.
        deadline = req.get("deadline")
        if deadline is not None and time.time() > deadline:
            conn.close()
            continue
        try:
            pid = os.fork()
        except OSError as e:
            _reply(conn, {"error": str(e)})
            continue
        if pid == 0:
            # ---- child: becomes a normal worker process ----
            try:
                srv.close()
                conn.close()
                os.setsid()
                lf = os.open(req["log_file"],
                             os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
                os.dup2(lf, 1)
                os.dup2(lf, 2)
                os.close(lf)
                os.environ.clear()
                os.environ.update(req["env"])
                signal.signal(signal.SIGCHLD, signal.SIG_DFL)
                workers_main.main()
                os._exit(0)
            except BaseException:  # noqa: BLE001 — never unwind into the loop
                import traceback

                traceback.print_exc()
                os._exit(1)
        if not _reply(conn, {"pid": pid}):
            # the raylet gave up on this request (short spawn timeout) and
            # already took the Popen path: reap the orphan before it can
            # register as an untracked extra worker
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    srv.close()
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass


class ZygoteClient:
    """Raylet-side handle: starts the zygote in the BACKGROUND, requests
    forks once it is ready.

    ``spawn`` never blocks on zygote startup — while the zygote warms (or
    after it dies, while a restart is in flight) it returns ``None`` and
    the caller uses the Popen fallback, so the zygote is a pure fast path
    and a wedged zygote can never stall the raylet's dispatch loop (which
    calls spawn under its lock)."""

    def __init__(self, state_dir: str, worker_env: dict, log_sink):
        from ray_tpu._private.analysis.lock_witness import make_lock

        self._sock_path = os.path.join(
            state_dir, f"zygote-{os.getpid()}.sock")
        self._env = worker_env
        self._log_sink = log_sink  # file path for the zygote's own output
        self._proc = None
        self._lock = make_lock("ZygoteClient._lock")
        self._starting = False
        self._stopped = False
        self.start_async()

    def start_async(self):
        """Kick off (re)start in a daemon thread; returns immediately."""
        import threading

        if sys.platform != "linux":
            return
        with self._lock:
            if self._stopped or self._starting:
                return
            if self._proc is not None and self._proc.poll() is None:
                return
            self._starting = True
        threading.Thread(target=self._start, daemon=True,
                         name="zygote-start").start()

    def _start(self):
        import subprocess
        import time

        try:
            try:
                os.unlink(self._sock_path)
            except FileNotFoundError:
                pass
            lf = open(self._log_sink, "ab")
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", "ray_tpu._private.zygote",
                 self._sock_path],
                env=self._env, stdout=lf, stderr=subprocess.STDOUT)
            lf.close()
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                if os.path.exists(self._sock_path):
                    break
                if proc.poll() is not None:
                    return
                time.sleep(0.01)
            with self._lock:
                if self._stopped:
                    proc.terminate()
                else:
                    self._proc = proc
        except Exception:  # noqa: BLE001 — boot failure falls back to Popen spawns (counted there)
            pass
        finally:
            with self._lock:
                self._starting = False

    def spawn(self, env: dict, log_file: str):
        """Fork one worker; returns its pid, or None to use the fallback
        (zygote still warming, dead, or wedged).

        The socket budget is SHORT (zygote_spawn_timeout_s, default 2 s):
        this runs under the raylet's dispatch lock, so a wedged-but-alive
        zygote must cost at most one short timeout before the Popen path
        takes over — never the 15 s a generous timeout allowed.  Fallbacks
        are counted (ray_tpu_raylet_zygote_fallback_total) so a sick zygote
        is visible instead of silently degrading every spawn to ~2.3 s."""
        with self._lock:
            proc = self._proc
        if proc is None or proc.poll() is not None:
            self.start_async()  # warm it for next time
            return None
        conn = None
        try:
            from ray_tpu._private.config import global_config

            budget = max(global_config().zygote_spawn_timeout_s, 0.1)
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.settimeout(budget)
            conn.connect(self._sock_path)
            # deadline rides the request: once we stop waiting, the zygote
            # must NOT fork a duplicate of the Popen fallback (and a fork
            # whose reply can't be delivered is killed zygote-side)
            conn.sendall(json.dumps(
                {"env": env, "log_file": log_file,
                 "deadline": time.time() + budget}).encode() + b"\n")
            reply = json.loads(_recv_line(conn) or b"{}")
            pid = reply.get("pid")
            if pid is None:
                self._note_fallback()
            return pid
        except Exception:  # noqa: BLE001
            self._note_fallback()
            return None
        finally:
            # deterministic close: the zygote detects an abandoned request
            # by its reply send failing, so the socket must die NOW, not at
            # a later GC
            if conn is not None:
                try:
                    conn.close()
                except OSError:
                    pass

    @staticmethod
    def _note_fallback():
        try:
            from ray_tpu._private import runtime_metrics

            runtime_metrics.inc_zygote_fallback()
        except Exception:  # noqa: BLE001 — fallback counter is telemetry; never block a spawn
            pass

    def shutdown(self):
        with self._lock:
            self._stopped = True  # an in-flight _start will self-terminate
            proc, self._proc = self._proc, None
        if proc is None:
            return
        try:
            conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            conn.settimeout(2.0)
            conn.connect(self._sock_path)
            conn.sendall(b'{"shutdown": true}\n')
            conn.close()
        except Exception:  # noqa: BLE001 — zygote already dead: terminate below still runs
            pass
        try:
            proc.terminate()
        except Exception:  # noqa: BLE001 — already-exited zygote is the desired state
            pass


if __name__ == "__main__":
    serve(sys.argv[1])
