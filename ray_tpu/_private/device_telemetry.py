"""Chip-level telemetry: the device-side observability pillar (ISSUE 16).

Every earlier observability layer measured the *host* — metrics (PR 1),
tracing (PR 2), flight recorder + goodput (PR 6), SLO sketches (PR 9).
This module observes the *chip* and the programs running on it:

  1. **HBM accounting** — per-device live-bytes gauges.  TPU backends
     report allocator stats via ``Device.memory_stats()``; CPU hosts
     (every hermetic test lane) report ``None``, so the fallback sums
     ``jax.live_arrays()`` bytes.  The paged engine additionally splits
     its footprint into weights vs KV pool vs transient activations.
  2. **Engine utilization & headroom** — :class:`EngineTelemetry`, the
     per-engine recorder the paged/static engines drive from ``step()``:
     decode slot occupancy, KV block occupancy, chunked-prefill budget
     spend, and the step duty cycle: the engine LOOP's occupancy of host
     wall time (seconds inside ``step()`` over seconds since the last
     step ended), which the pool autoscaler reads as "is this replica's
     loop ever idle".  It is not a device figure: a step blocks on device
     reads and does host booking alike.  For the device, read the
     engine's ``counters`` (``device_wait_s`` against ``host_s``) or a
     ``state.jax_profile`` trace.  Values are captured under the engine
     lock into locals and booked AFTER release.  Per-replica rows fold
     into ``state.utilization()`` / ``/api/utilization`` — the
     SLO-feedback autoscaler's input surface (ROADMAP item 1).
  3. **Compile watch** — a process-wide jit-compile observer.
     ``jax.monitoring`` duration events count backend compiles and their
     seconds; instrumented call sites name their program via
     :func:`note_trace` (fires only on a retrace, i.e. exactly when a new
     compile is coming), and a thread-local attributes the following
     backend-compile event to that program.  A compile-storm detector
     (N traces/compiles of the same program inside M seconds) folds into
     ``state.diagnose()`` with the re-compiling program's callers.
  4. **MFU/roofline accounting** — model FLOPs from
     ``jax.jit(...).lower().cost_analysis()`` cached per program key,
     divided by step wall into ``ray_tpu_train_mfu_ratio{run}`` and
     serving tok/s-per-chip.
  5. **Heartbeat** — a daemon thread started with the compile observer
     re-pushes this process's metrics every few seconds.  Without it, a
     replica blocked in one long jit compile stops pushing (every normal
     push site rides request/step completions) and the GCS's 30 s
     silent-reporter sweep expires its gauges: the replica *vanishes*
     from ``state.node_metrics()`` mid-compile.  With it, the reporter's
     receive stamp stays fresh and the gauges read stale-but-present.

Disabled path (``device_telemetry_enabled = false``): engines never
attach a recorder, so the per-step cost is one attribute read + ``None``
check and the layer books nothing — metric output is byte-identical
(benchmarks/device_telemetry_bench.py gates <1 µs disabled, <10 µs
enabled, <50 ms for a 16-replica utilization fold).
"""

from __future__ import annotations

import collections
import threading
import time
import traceback
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private.analysis.lock_witness import make_lock

# GCS KV prefix for per-replica utilization rows (state.utilization()
# folds every row under this prefix; serve/_private/replica.py publishes)
UTIL_KV_PREFIX = "util:"

# Published peak bf16 FLOP/s of ONE chip, keyed by ``device.device_kind``
# exactly as JAX reports it.  The one table the MFU gauges read.
# Source: Google Cloud TPU documentation, system
# architecture pages "TPU v4", "TPU v5e", "TPU v5p", "TPU v6e".  A device
# that is not here is an error, never a default: a utilization over a
# guessed peak is not a measurement.
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e
}


def enabled() -> bool:
    from ray_tpu._private.config import global_config

    return bool(global_config().device_telemetry_enabled)


def peak_flops(device=None) -> float:
    """Published peak bf16 FLOP/s of ``device`` (default: this process's
    first device).  Raises for a device the table does not know."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(device.device_kind)
    try:
        return PEAK_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no published peak FLOP/s for device_kind {kind!r}: add it to "
            "device_telemetry.PEAK_FLOPS with its source, or pass the peak "
            "explicitly") from None


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------


def hbm_snapshot() -> List[dict]:
    """Per-device live-bytes rows.

    ``memory_stats()`` where the backend reports allocator stats (TPU);
    otherwise one summed ``jax.live_arrays()`` row per device (CPU hosts
    — the hermetic lanes), marked by ``source`` so a dashboard never
    mistakes the fallback for allocator truth.
    """
    try:
        import jax

        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no backend at all
        return []
    rows: List[dict] = []
    fallback: List[Any] = []
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without allocator stats
            stats = None
        if stats:
            rows.append({
                "device": str(d),
                "kind": str(getattr(d, "device_kind", "?")),
                "used_bytes": int(stats.get("bytes_in_use", 0)),
                "limit_bytes": int(stats.get("bytes_limit", 0)),
                "peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
                "source": "memory_stats",
            })
        else:
            fallback.append(d)
    if fallback:
        per_dev: Dict[str, int] = {str(d): 0 for d in fallback}
        try:
            import jax

            for a in jax.live_arrays():
                for shard_dev in getattr(a, "devices", lambda: ())():
                    key = str(shard_dev)
                    if key in per_dev:
                        # sharded arrays: attribute an even split
                        per_dev[key] += a.nbytes // max(
                            1, len(a.devices()))
        except Exception:  # noqa: BLE001 — live_arrays is best-effort
            pass
        for d in fallback:
            rows.append({
                "device": str(d),
                "kind": str(getattr(d, "device_kind", "?")),
                "used_bytes": int(per_dev.get(str(d), 0)),
                "limit_bytes": 0,
                "peak_bytes": 0,
                "source": "live_arrays",
            })
    return rows


def record_hbm() -> List[dict]:
    """Record the per-device gauges and return the snapshot rows."""
    rows = hbm_snapshot()
    if not enabled():
        return rows
    from ray_tpu._private import runtime_metrics

    for r in rows:
        runtime_metrics.set_device_hbm(r["device"], r["used_bytes"],
                                       r["limit_bytes"])
    return rows


def device_used_bytes() -> int:
    """Total live bytes across local devices (for the transient split)."""
    return sum(r["used_bytes"] for r in hbm_snapshot())


def tree_nbytes(tree) -> int:
    """Summed leaf bytes of a pytree of arrays (metadata only — no host
    transfer; non-array leaves count zero)."""
    try:
        import jax

        return int(sum(getattr(leaf, "nbytes", 0) or 0
                       for leaf in jax.tree_util.tree_leaves(tree)))
    except Exception:  # noqa: BLE001
        return 0


def tree_nbytes_per_device(tree) -> int:
    """Per-DEVICE byte footprint of a pytree of (possibly sharded)
    arrays: each leaf contributes its largest single-device shard, so a
    tensor-sharded leaf counts size/N while a replicated leaf counts full
    size.  This is what an engine must feed hbm_split() — tree_nbytes of
    a mesh-sharded pool is the GLOBAL size and over-reports every
    device's engine-owned HBM by the sharding degree.  Metadata only (no
    host transfer); unsharded arrays fall back to ``nbytes``."""
    try:
        import jax

        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                total += max(int(getattr(s.data, "nbytes", 0) or 0)
                             for s in shards)
            else:
                total += int(getattr(leaf, "nbytes", 0) or 0)
        return total
    except Exception:  # noqa: BLE001
        return 0


# ---------------------------------------------------------------------------
# Compile watch
# ---------------------------------------------------------------------------

_UNATTRIBUTED = "_jax"
_MAX_PROGRAMS = 256   # tag-cardinality backstop for the metric families
_MAX_EVENTS = 512


class _CompileWatch:
    """Process-wide jit-compile observer.

    Two feeds: ``note_trace(program)`` from instrumented call sites — it
    executes inside the traced Python function, i.e. only on a cache
    miss, so each call marks an imminent compile and names it — and the
    ``jax.monitoring`` backend-compile duration events, attributed to the
    calling thread's most recent traced program.  Trace counts back the
    ``compile_count()`` APIs (rllib/env_runner.py); backend events back
    the ``ray_tpu_jit_compiles_total`` / ``_seconds_total`` families.
    """

    def __init__(self):
        self._lock = make_lock("device_telemetry._CompileWatch._lock")
        self._trace_counts: Dict[str, int] = {}
        self._compile_counts: Dict[str, int] = {}
        self._compile_seconds: Dict[str, float] = {}
        self._shape_keys: Dict[str, set] = {}
        self._callers: Dict[str, str] = {}
        # (monotonic, program) ring for the storm detector
        self._events: collections.deque = collections.deque(
            maxlen=_MAX_EVENTS)
        self._tls = threading.local()

    # -- feeds ---------------------------------------------------------------

    def note_trace(self, program: str, shape_key: Any = None) -> None:
        now = time.monotonic()
        self._tls.program = program
        # caller summary: nearest non-jax, non-telemetry frames — who is
        # retracing this program (the storm report names them)
        callers = _caller_summary()
        with self._lock:
            self._trace_counts[program] = \
                self._trace_counts.get(program, 0) + 1
            if shape_key is not None:
                keys = self._shape_keys.setdefault(program, set())
                if len(keys) < 64:
                    keys.add(repr(shape_key))
            if callers:
                self._callers[program] = callers
            self._events.append((now, program))
        _heartbeat_stamp()

    def note_compile(self, program: Optional[str], seconds: float) -> None:
        program = program or _UNATTRIBUTED
        with self._lock:
            if (program not in self._compile_counts
                    and len(self._compile_counts) >= _MAX_PROGRAMS):
                program = _UNATTRIBUTED
            self._compile_counts[program] = \
                self._compile_counts.get(program, 0) + 1
            self._compile_seconds[program] = \
                self._compile_seconds.get(program, 0.0) + seconds
        if enabled():
            from ray_tpu._private import runtime_metrics

            runtime_metrics.inc_jit_compile(program, seconds)
        _heartbeat_stamp()

    def current_program(self) -> Optional[str]:
        return getattr(self._tls, "program", None)

    # -- reads ---------------------------------------------------------------

    def trace_count(self, program: str) -> int:
        with self._lock:
            return self._trace_counts.get(program, 0)

    def storm_report(self, threshold: Optional[int] = None,
                     window_s: Optional[float] = None) -> List[dict]:
        """Programs re-tracing/re-compiling fast enough to be a storm:
        >= threshold events inside the trailing window, newest-first."""
        from ray_tpu._private.config import global_config

        cfg = global_config()
        threshold = threshold or cfg.compile_storm_threshold
        window_s = window_s or cfg.compile_storm_window_s
        cutoff = time.monotonic() - window_s
        with self._lock:
            recent: Dict[str, int] = {}
            for t, program in self._events:
                if t >= cutoff:
                    recent[program] = recent.get(program, 0) + 1
            out = []
            for program, n in recent.items():
                if n >= threshold:
                    out.append({
                        "program": program,
                        "compiles": n,
                        "window_s": window_s,
                        "shape_keys": sorted(
                            self._shape_keys.get(program, ()))[:16],
                        "callers": self._callers.get(program, ""),
                        "total_traces": self._trace_counts.get(program, 0),
                        "total_compile_seconds": round(
                            self._compile_seconds.get(program, 0.0), 3),
                    })
        out.sort(key=lambda r: -r["compiles"])
        return out

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "traces": dict(self._trace_counts),
                "compiles": dict(self._compile_counts),
                "compile_seconds": {k: round(v, 4) for k, v in
                                    self._compile_seconds.items()},
            }

    def _reset_for_tests(self) -> None:
        with self._lock:
            self._trace_counts.clear()
            self._compile_counts.clear()
            self._compile_seconds.clear()
            self._shape_keys.clear()
            self._callers.clear()
            self._events.clear()


_watch = _CompileWatch()


def _caller_summary(limit: int = 3) -> str:
    """Nearest application frames (file:line:function), skipping jax and
    this module — the names a storm report blames."""
    out = []
    try:
        for f in reversed(traceback.extract_stack(limit=24)):
            fn = f.filename
            base = fn.rsplit("/", 1)[-1]
            if ("/jax/" in fn or "/jax_" in fn or "jax/_src" in fn
                    or base == "device_telemetry.py"):
                continue
            out.append(f"{base}:{f.lineno}:{f.name}")
            if len(out) >= limit:
                break
    except Exception:  # noqa: BLE001 — forensics must never raise
        pass
    return " <- ".join(out)


def note_trace(program: str, shape_key: Any = None) -> None:
    """Mark a retrace of ``program`` (call INSIDE the jitted Python
    function: the body only runs on a cache miss, so each call is an
    imminent compile).  Always books into the watch — ``compile_count()``
    APIs must work even with the metric layer disabled — and installs the
    jax.monitoring listener on first use."""
    install()
    _watch.note_trace(program, shape_key)


def trace_count(program: str) -> int:
    return _watch.trace_count(program)


def storm_report(threshold: Optional[int] = None,
                 window_s: Optional[float] = None) -> List[dict]:
    return _watch.storm_report(threshold, window_s)


def compile_totals() -> Tuple[int, float]:
    """``(backend compiles, their seconds)`` this process has seen since
    the first call registered the listener; an engine subtracts what it
    read when ``warmup()`` returned."""
    _install_listener()
    with _watch._lock:
        return (sum(_watch._compile_counts.values()),
                sum(_watch._compile_seconds.values()))


def compile_snapshot() -> dict:
    return _watch.snapshot()


# -- jax.monitoring listener -------------------------------------------------

_installed = False
_install_lock = make_lock("device_telemetry._install_lock")


def _on_jax_event(key: str, seconds: float, **_kw) -> None:
    # one endswith per event: the listener runs for every monitored jax
    # duration event in the process, most of which are not compiles
    if key.endswith("backend_compile_duration"):
        _watch.note_compile(_watch.current_program(), seconds)
    elif key.endswith("jaxpr_to_mlir_module_duration"):
        # pre-backend-compile stamp: the heartbeat gets one fresh push in
        # right before a potentially long backend compile
        _heartbeat_stamp()


def _install_listener() -> None:
    """Register the jax.monitoring compile listener, once per process."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_event)
        except Exception:  # noqa: BLE001 — jax absent/too old: trace-only
            pass
        _installed = True


def install() -> None:
    """Register the jax.monitoring compile listener and start the
    telemetry heartbeat (both once per process, both best-effort)."""
    _install_listener()
    if enabled():
        _start_heartbeat()


# ---------------------------------------------------------------------------
# Heartbeat (satellite: gauge expiry during long compiles)
# ---------------------------------------------------------------------------

_hb_thread: Optional[threading.Thread] = None
_hb_lock = make_lock("device_telemetry._hb_lock")
_hb_last_stamp = 0.0


def _default_heartbeat_push() -> None:
    from ray_tpu._private import runtime_metrics

    runtime_metrics.maybe_push()


# rebindable for tests (injected push recorder)
_heartbeat_push: Callable[[], None] = _default_heartbeat_push


def _heartbeat_stamp() -> None:
    """Cheap liveness stamp from compile-observer feeds; the loop uses it
    only for introspection — the push itself rides the daemon thread."""
    global _hb_last_stamp
    _hb_last_stamp = time.monotonic()


def _start_heartbeat(interval_s: Optional[float] = None) -> None:
    """Start the telemetry heartbeat daemon (idempotent).

    The thread re-pushes this process's metrics every
    ``device_telemetry_heartbeat_s`` so the GCS's silent-reporter gauge
    sweep (gcs.py ``_GAUGE_STALE_S``) sees a fresh receive stamp even
    while every request/step thread is blocked inside one long jit
    compile — the replica's utilization gauges read stale-but-present
    instead of vanishing from ``state.node_metrics()``."""
    global _hb_thread
    with _hb_lock:
        if _hb_thread is not None and _hb_thread.is_alive():
            return

        def loop():
            from ray_tpu._private.config import global_config

            while True:
                period = interval_s or \
                    global_config().device_telemetry_heartbeat_s
                time.sleep(max(0.05, period))
                try:
                    _heartbeat_push()
                except Exception:  # noqa: BLE001 — no GCS yet / teardown
                    pass

        _hb_thread = threading.Thread(
            target=loop, daemon=True, name="device-telemetry-heartbeat")
        _hb_thread.start()


# ---------------------------------------------------------------------------
# Engine utilization & headroom
# ---------------------------------------------------------------------------


class EngineTelemetry:
    """Per-engine utilization recorder.

    Single writer — the engine step loop.  ``note_step()`` stores plain
    slots every step (the <10 µs budget) and flushes bound gauges at most
    every ``device_telemetry_flush_interval_s``; the HBM split flushes on
    a 10x slower cadence (it may walk ``jax.live_arrays()`` on CPU
    hosts).  All values arrive as locals captured under the engine lock —
    nothing here takes it."""

    __slots__ = ("deployment", "clock", "active_slots", "max_slots",
                 "free_blocks", "total_blocks", "pending",
                 "prefill_spent", "prefill_budget", "duty_cycle",
                 "steps", "weights_bytes", "kv_pool_bytes",
                 "_last_step_end", "_flush_interval", "_last_flush",
                 "_last_hbm_flush", "_last_hbm")

    def __init__(self, deployment: str, *, weights_bytes: int = 0,
                 kv_pool_bytes: int = 0,
                 clock: Callable[[], float] = time.monotonic,
                 flush_interval_s: Optional[float] = None):
        from ray_tpu._private.config import global_config

        self.deployment = deployment
        self.clock = clock
        self.weights_bytes = weights_bytes
        self.kv_pool_bytes = kv_pool_bytes
        self.active_slots = 0
        self.max_slots = 0
        self.free_blocks = 0
        self.total_blocks = 0
        self.pending = 0
        self.prefill_spent = 0
        self.prefill_budget = 0
        self.duty_cycle = 0.0
        self.steps = 0
        self._last_step_end = clock()
        self._flush_interval = (
            flush_interval_s if flush_interval_s is not None
            else global_config().device_telemetry_flush_interval_s)
        self._last_flush = float("-inf")
        self._last_hbm_flush = float("-inf")
        self._last_hbm: Dict[str, int] = {}

    def note_step(self, *, active_slots: int, max_slots: int,
                  free_blocks: int, total_blocks: int, pending: int,
                  prefill_spent: int, prefill_budget: int,
                  busy_s: float, now: float) -> None:
        """Book one engine step.  ``busy_s`` is the wall time of the step
        body (host booking, dispatch and blocking device reads together:
        the engine's ``host_s + device_wait_s`` of this step); wall is
        measured here as the time since the previous step ended, so the
        duty cycle is the loop's occupancy of host wall time: idle gaps
        between steps depress it, a slow device does not."""
        wall = now - self._last_step_end
        self._last_step_end = now
        self.active_slots = active_slots
        self.max_slots = max_slots
        self.free_blocks = free_blocks
        self.total_blocks = total_blocks
        self.pending = pending
        self.prefill_spent = prefill_spent
        self.prefill_budget = prefill_budget
        if wall > 0:
            d = busy_s / wall
            self.duty_cycle = d if d < 1.0 else 1.0
        self.steps += 1
        if now - self._last_flush >= self._flush_interval:
            self._last_flush = now
            self._flush(now)

    def _flush(self, now: float) -> None:
        from ray_tpu._private import runtime_metrics

        runtime_metrics.record_engine_utilization(
            self.deployment,
            self.active_slots / self.max_slots if self.max_slots else 0.0,
            ((self.total_blocks - self.free_blocks) / self.total_blocks
             if self.total_blocks else 0.0),
            (self.prefill_spent / self.prefill_budget
             if self.prefill_budget else 0.0),
            self.duty_cycle)
        if now - self._last_hbm_flush >= 10 * self._flush_interval:
            self._last_hbm_flush = now
            hbm = self.hbm_split()
            runtime_metrics.record_engine_hbm(
                self.deployment, hbm["weights_bytes"],
                hbm["kv_pool_bytes"], hbm["transient_bytes"])
            for r in record_hbm():
                self._last_hbm[r["device"]] = r["used_bytes"]

    def hbm_split(self) -> dict:
        """Weights / KV-pool / transient split.  Transient = device live
        bytes minus the two accounted segments, clamped at zero (other
        processes' allocations on a shared chip can make it negative)."""
        used = device_used_bytes()
        transient = used - self.weights_bytes - self.kv_pool_bytes
        return {
            "weights_bytes": self.weights_bytes,
            "kv_pool_bytes": self.kv_pool_bytes,
            "transient_bytes": max(0, transient),
            "device_used_bytes": used,
        }

    def rates(self) -> dict:
        """Step-derived rates for utilization rows (the exact occupancy
        numbers come from the engine's own bookkeeping, not from here)."""
        return {
            "duty_cycle": round(self.duty_cycle, 4),
            "prefill_budget_tokens": self.prefill_budget,
            "prefill_spent_tokens": self.prefill_spent,
            "prefill_spend_ratio": round(
                self.prefill_spent / self.prefill_budget, 4)
            if self.prefill_budget else 0.0,
            "steps": self.steps,
        }


def engine_telemetry_for(deployment: Optional[str], *, weights_bytes: int = 0,
                         kv_pool_bytes: int = 0) -> Optional[EngineTelemetry]:
    """Attach point for engines: an :class:`EngineTelemetry` when the
    layer is enabled and the engine serves a named deployment, else
    ``None`` (the books-nothing disabled path — one attribute read +
    None check per step)."""
    if deployment is None or not enabled():
        return None
    install()
    return EngineTelemetry(deployment, weights_bytes=weights_bytes,
                           kv_pool_bytes=kv_pool_bytes)


# ---------------------------------------------------------------------------
# Utilization registry + fold (state.utilization / bench / local mode)
# ---------------------------------------------------------------------------

# name -> weakref-ish provider callable returning a utilization row dict;
# serve replicas publish rows to the GCS KV, but local-testing-mode apps
# (no GCS, in-process replicas) and engine-direct use register here so
# state.utilization() still has a surface to fold
_providers: Dict[str, Callable[[], Optional[dict]]] = {}
_providers_lock = make_lock("device_telemetry._providers_lock")


def register_utilization_provider(name: str,
                                  fn: Callable[[], Optional[dict]]) -> None:
    with _providers_lock:
        _providers[name] = fn


def unregister_utilization_provider(name: str) -> None:
    with _providers_lock:
        _providers.pop(name, None)


def register_utilization_object(name: str, obj: Any) -> None:
    """Register ``obj.utilization`` behind a weakref — a GC'd engine or
    server drops out of the fold instead of being pinned alive."""
    ref = weakref.ref(obj)

    def provider() -> Optional[dict]:
        target = ref()
        if target is None:
            return None
        try:
            return target.utilization()
        except Exception:  # noqa: BLE001 — a dying engine books nothing
            return None

    register_utilization_provider(name, provider)


def local_utilization_rows() -> List[dict]:
    rows = []
    with _providers_lock:
        items = list(_providers.items())
    dead = []
    for name, fn in items:
        row = fn()
        if row is None:
            dead.append(name)
            continue
        row = dict(row)
        row.setdefault("replica", name)
        row["source"] = "local"
        rows.append(row)
    for name in dead:
        unregister_utilization_provider(name)
    return rows


def fold_utilization_rows(rows: List[dict]) -> dict:
    """Cluster utilization snapshot: per-deployment replica rows plus
    summed headroom — free decode slots and free KV blocks per deployment
    are THE autoscaler inputs, so the fold names them explicitly."""
    deployments: Dict[str, dict] = {}
    for row in rows:
        dep = str(row.get("deployment") or "?")
        d = deployments.setdefault(dep, {
            "replicas": [], "free_slots": 0, "total_slots": 0,
            "active_slots": 0, "free_kv_blocks": 0, "total_kv_blocks": 0,
            "duty_cycles": []})
        d["replicas"].append(row)
        slots = row.get("slots") or {}
        blocks = row.get("kv_blocks") or {}
        d["active_slots"] += int(slots.get("active", 0))
        d["total_slots"] += int(slots.get("max", 0))
        d["free_slots"] += int(slots.get("free", 0))
        d["free_kv_blocks"] += int(blocks.get("free", 0))
        d["total_kv_blocks"] += int(blocks.get("total", 0))
        if row.get("duty_cycle") is not None:
            d["duty_cycles"].append(float(row["duty_cycle"]))
    for d in deployments.values():
        duties = d.pop("duty_cycles")
        d["mean_duty_cycle"] = round(sum(duties) / len(duties), 4) \
            if duties else 0.0
        d["slot_occupancy"] = round(
            d["active_slots"] / d["total_slots"], 4) \
            if d["total_slots"] else 0.0
        d["kv_occupancy"] = round(
            (d["total_kv_blocks"] - d["free_kv_blocks"])
            / d["total_kv_blocks"], 4) if d["total_kv_blocks"] else 0.0
    return {
        "time": time.time(),
        "deployments": deployments,
        "replicas": sum(len(d["replicas"]) for d in deployments.values()),
    }


def local_utilization() -> dict:
    """Fold of this process's registered providers (local-testing-mode
    serve apps, engine-direct benches)."""
    return fold_utilization_rows(local_utilization_rows())


def util_kv_key(app: str, deployment: str, replica: str) -> str:
    return f"{UTIL_KV_PREFIX}{app}/{deployment}/{replica}"


# ---------------------------------------------------------------------------
# MFU / roofline accounting
# ---------------------------------------------------------------------------

_flops_cache: Dict[Any, float] = {}
_flops_lock = make_lock("device_telemetry._flops_lock")


def jit_flops(fn, *args, key: Any = None, **kwargs) -> Optional[float]:
    """FLOPs of one execution of jitted ``fn`` at these args, from
    ``lower().cost_analysis()``, cached per ``key`` (default: the
    function identity + arg shapes).  ``None`` when the backend does not
    report a flops figure — callers fall back to analytic counts."""
    if key is None:
        try:
            import jax

            shapes = tuple(
                str(getattr(a, "shape", None)) for a in
                jax.tree_util.tree_leaves((args, kwargs)))
        except Exception:  # noqa: BLE001
            shapes = ()
        key = (id(fn), shapes)
    with _flops_lock:
        if key in _flops_cache:
            return _flops_cache[key]
    flops = lowered_flops(_lower(fn, *args, **kwargs))
    if flops is not None:
        with _flops_lock:
            if len(_flops_cache) < 256:
                _flops_cache[key] = flops
    return flops


def _lower(fn, *args, **kwargs):
    try:
        lower = getattr(fn, "lower", None)
        if lower is None:
            import jax

            lower = jax.jit(fn).lower
        return lower(*args, **kwargs)
    except Exception:  # noqa: BLE001 — unlowerable: no figure
        return None


def lowered_flops(lowered) -> Optional[float]:
    """Pull a flops figure out of ``cost_analysis()`` across the jax
    return-shape variants (dict, per-device list of dicts, None)."""
    if lowered is None:
        return None
    try:
        ca = lowered.cost_analysis()
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


def note_train_step(run: str, *, model_flops: float, wall_s: float,
                    peak: Optional[float] = None) -> float:
    """Record ``ray_tpu_train_mfu_ratio{run}``: model FLOPs of one step
    over (step wall * peak FLOPs/s).  Returns the ratio."""
    if wall_s <= 0 or model_flops <= 0:
        return 0.0
    peak = peak or peak_flops()
    mfu = model_flops / wall_s / peak
    if enabled():
        from ray_tpu._private import runtime_metrics

        runtime_metrics.set_train_mfu(run, mfu)
    return mfu


def note_serving_rate(deployment: str, tok_per_s: float,
                      n_chips: int = 1) -> float:
    """Record serving tok/s-per-chip for a deployment; returns the
    normalized figure."""
    per_chip = tok_per_s / max(1, n_chips)
    if enabled():
        from ray_tpu._private import runtime_metrics

        runtime_metrics.set_serve_tokens_per_chip(deployment, per_chip)
    return per_chip


# ---------------------------------------------------------------------------
# Test hooks
# ---------------------------------------------------------------------------


def _reset_for_tests() -> None:
    """Clear watch state and the provider registry (the jax.monitoring
    listener and heartbeat thread, once installed, stay — they are
    process-lifetime singletons)."""
    _watch._reset_for_tests()
    with _providers_lock:
        _providers.clear()
    with _flops_lock:
        _flops_cache.clear()
