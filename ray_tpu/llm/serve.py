"""LLM serving: deployment wrapping the JAX engine with continuous batching.

reference: python/ray/llm/_internal/serve/deployments/llm/ — LLMServer
deployments on vLLM with per-replica placement groups sized from the
engine's TP/PP degrees (vllm_models.py:177-186, :241-259).  Here the
replica owns a PagedJaxLLMEngine; concurrent requests enqueue into the engine
and a background thread drives ``engine.step()``, so all in-flight
requests share one decode batch (continuous batching across callers).
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Any, Dict, List, Optional, Sequence  # noqa: F401

from ray_tpu._private.utils import name_os_thread
from ray_tpu.llm.config import GenerationConfig, LLMConfig
from ray_tpu.util import tracing


def _jax_backend() -> str:
    import jax

    return jax.default_backend()


def _warm_up(engine) -> None:
    """On a TPU backend: compile every decode (B, W) bucket before serving
    traffic — a bucket transition otherwise costs a multi-second XLA
    compile inside the latency path (vLLM warms shapes at startup the same
    way) — then move the heap as it stands out of the cyclic collector's
    reach (``gc.freeze``, as vLLM does after start-up): tracing and
    compiling leave some hundred thousand long-lived containers (a family
    whose layers differ in shape keeps a jaxpr a layer a program), and a
    full collection that walks them holds the GIL, and so the engine loop
    and every stream, for a quarter of a second (235 ms measured for the
    17 programs of a 17-layer hybrid stack, PERF.md section 6, PR 51).
    What is allocated from here on is collected as before."""
    if _jax_backend() != "tpu":
        return
    engine.warmup()
    gc.collect()
    gc.freeze()


class LLMServer:
    """Deployment callable; bind with serve: see ``build_llm_deployment``.

    Multi-LoRA (reference: ray.llm's vLLM LoRA serving): ``lora_adapters``
    maps model ids to adapter pytrees (llm/lora.py). Each adapter gets its
    own engine over MERGED weights, created lazily on first request and all
    driven by the one loop — batched decode stays a single jitted program
    per engine, the right TPU trade (no per-slot adapter gathers)."""

    def __init__(self, llm_config: LLMConfig, params=None,
                 lora_adapters: Optional[Dict[str, Any]] = None,
                 draft_params=None):
        from ray_tpu.llm.engine import make_engine

        self._config = llm_config
        self._engine = make_engine(llm_config, params,
                                   draft_params=draft_params)
        # the MATERIALIZED draft weights (the engine random-initializes
        # when draft_params is None): per-adapter draft merges apply to
        # what actually runs, not the constructor argument
        self._draft_params = self._engine._draft_params
        _warm_up(self._engine)
        self._engines: Dict[Optional[str], Any] = {None: self._engine}
        self._engine_gen: Dict[Optional[str], int] = {None: 0}
        self._engine_order: list = []  # adapter LRU (base never evicted)
        self._adapters: Dict[str, Any] = dict(lora_adapters or {})
        self._engines_lock = threading.Lock()
        # held by the _run loop across each step + token-apply pair, and
        # by export/cancel across their engine drain + waiter reconcile:
        # a drain landing between a step's gather and its apply would
        # otherwise double-deliver the step's delta (see _reap_drained)
        self._step_lock = threading.Lock()
        self._cv = threading.Condition()
        self._done: Dict[Any, List[int]] = {}
        self._waiters: Dict[Any, List[int]] = {}
        # wkeys some caller is still consuming — eviction cleanup must not
        # delete their results out from under them (guarded by _cv's lock)
        self._active_waiters: set = set()
        # wkeys aborted mid-stream (client disconnect): one trailing
        # emission batch may still surface after the engine cancel — it
        # must not recreate the popped waiter entry as a leaked _done row
        # (guarded by _cv's lock; bounded by the clear-cap below)
        self._aborted: set = set()
        # wkeys mid-migration (serve/_private/kv_migration.py): their
        # engine request is being (or has been) exported away, so the
        # _run loop must neither re-apply their history nor declare them
        # done when the rid leaves the engine — the splice relay owns
        # their buffer lifecycle (guarded by _cv's lock)
        self._migrating: set = set()
        # mig_id -> import result memo (idempotent migration retries;
        # guarded by _cv's lock, bounded)
        self._mig_imports: Dict[str, Any] = {}
        self._stop = False
        self._error: Optional[BaseException] = None
        self._loop = threading.Thread(target=self._run, daemon=True,
                                      name="llm-engine-loop")
        self._loop.start()

    _slo_label: Optional[str] = None

    def lora_model_ids(self) -> List[str]:
        return sorted(self._adapters)

    def set_slo_label(self, name: str) -> None:
        """Serving SLO layer threading (serve/_private/replica.py): label
        this server's engines with the hosting deployment's name so
        engine-side lifecycle stages (queue_wait, prefill, decode) book
        under it.  Unlabeled servers (direct library use) book nothing."""
        self._slo_label = name
        for eng in list(self._engines.values()):
            eng.slo_label = name

    @contextlib.contextmanager
    def _hold_step_lock(self, who: str):
        """``_step_lock``, with the wait for it marked on the profiler's
        timeline (``who``: ``loop``, ``cancel``, ``export``)."""
        with tracing.region("serve.step_lock_wait", who=who):
            self._step_lock.acquire()
        try:
            yield
        finally:
            self._step_lock.release()

    def _engine_of(self, wkey):
        """The engine that runs ``wkey``'s request (None: evicted or
        rebuilt since)."""
        model, gen_id, _rid = wkey
        if model is None:
            return self._engine
        with self._engines_lock:
            return (self._engines.get(model)
                    if self._engine_gen.get(model, 0) == gen_id else None)

    def _note_first_yield(self, wkey) -> Optional[float]:
        """Book the ``stream_out`` stage (first token emitted by the
        engine -> its chunk handed to the replica's stream) and return
        it; None where the engine tracks no stamps (unlabeled server)."""
        eng = self._engine_of(wkey)
        req = eng.tracked_request(wkey[2]) if eng is not None else None
        if req is None or not req.t_first_emit:
            return None
        from ray_tpu.serve._private import slo

        dt = time.monotonic() - req.t_first_emit
        slo.record_stage(self._slo_label, "stream_out", dt)
        return dt

    def _note_request_row(self, wkey, stream_out_s: Optional[float]) -> None:
        """A finished request's stage times and token counts into this
        process's ledger ring (``state.recent_requests()``)."""
        eng = self._engine_of(wkey)
        row = eng.pop_request_row(wkey[2]) if eng is not None else None
        if row is None:
            return
        from ray_tpu.serve._private import slo

        if stream_out_s is not None:
            row["stream_out_s"] = round(stream_out_s, 6)
        slo.record_engine_request(self._slo_label, row)

    def utilization(self) -> Dict[str, Any]:
        """Device-telemetry utilization row for the hosting replica's
        publish loop and the local-mode fold (state.utilization()): the
        base engine's exact bookkeeping, plus any live adapter engines'
        rows under ``adapters``."""
        row = self._engine.utilization()
        with self._engines_lock:
            extras = [(m, e) for m, e in self._engines.items()
                      if m is not None]
        adapters = {model: eng.utilization() for model, eng in extras}
        if adapters:
            row["adapters"] = adapters
        if self._slo_label is not None:
            row["deployment"] = self._slo_label
        return row

    def device_report(self) -> Dict[str, Any]:
        """What this replica's process holds and chose, for whoever has to
        show it from outside (``chip_smoke.py``): the devices JAX gave it,
        the chips its lease bound, which attention path the engine took,
        whether ``warmup()`` ran, allocator peaks per device, and which
        native components this process loaded."""
        import os

        import jax

        from ray_tpu import _native

        eng = self._engine
        devices = jax.devices()
        memory = []
        for d in devices:
            stats = d.memory_stats() or {}
            memory.append({k: int(stats[k]) for k in
                           ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                           if k in stats})
        if not eng._use_kernel:
            attention = "gather"
        elif eng._kernel_interpret:
            attention = "kernel-interpret"
        else:
            attention = "kernel"
        return {
            "pid": os.getpid(),
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "device_ids": [d.id for d in devices],
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "engine": type(eng).__name__,
            "n_layers": eng.cfg.n_layers,
            "num_blocks": eng.num_blocks,
            "paged_attention": attention,
            "warmup": eng.warmup_report,
            "memory": memory,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "native": _native.status(),
            "utilization": self.utilization(),
            # what this process's last state.jax_profile capture cost
            "last_capture": tracing.last_capture(),
        }

    def reference_check(self, prompt: Sequence[int],
                        served: Sequence[int]) -> Dict[str, Any]:
        """Hold greedy tokens this replica served for ``prompt`` against
        the plain float32 forward of the same weights (the model family's
        ``reference_logits``, models/family.py), teacher-forced over prompt +
        served: for each served token, the reference logit it gives up
        against the reference's own argmax at that position (0 where they
        agree).  The caller states the tolerance."""
        import numpy as np

        eng = self._engine
        seq = list(prompt) + list(served)
        rows = np.asarray(eng.family.reference_logits(
            eng.cfg, eng.params, seq[:-1], first_row=len(prompt) - 1))
        served = np.asarray(served)
        gaps = rows.max(-1) - rows[np.arange(len(served)), served]
        argmax = rows.argmax(-1)
        diverged = np.nonzero(argmax != served)[0]
        return {
            "finite": bool(np.isfinite(rows).all()),
            "reference_argmax": argmax.tolist(),
            "logit_gaps": gaps.tolist(),
            "max_logit_gap": float(gaps.max()),
            "first_divergent": int(diverged[0]) if len(diverged) else None,
            "logit_std": float(rows.std()),
        }

    def reference_state_check(self, prompt: Sequence[int],
                              tokens: int) -> Dict[str, Any]:
        """Hold the SLOT STATE this replica keeps for a sequence against the
        plain float32 recurrence over the same positions (the family's
        ``reference_slot_state``, models/family.py).  ``prompt`` is served
        greedily like any request; once it has emitted ``tokens`` tokens it
        is taken out of the engine mid-decode (``export_stream``: the handoff
        carries the slot's leaves and the token history) and its stream is
        ended there.  For each leaf the reference defines: the relative error
        (the norm of the difference over the reference's norm) of the whole
        leaf and of each layer.  The caller states the tolerance."""
        import numpy as np

        eng = self._engine
        if eng.family.reference_slot_state is None:
            raise ValueError(f"family {eng.family.name!r} keeps no slot "
                             "state with a reference")
        # the export waits for the step lock, which the loop takes again at
        # once: the request decodes on meanwhile (5 to over 64 tokens, on
        # the chip), so its budget leaves that room and the reply says where
        # it was taken
        gen = GenerationConfig(temperature=0.0, max_new_tokens=min(
            tokens + 1024, self._config.max_seq_len - len(prompt)))
        wkey = self._submit(None, list(prompt), gen)
        handoff, got = None, 0
        for chunk in self._iter_tokens(wkey):
            got += len(chunk)
            if handoff is None and got >= tokens:
                handoff = self.export_stream(wkey[2])
                self._finish_migrated(wkey[2])
        emitted = handoff["emitted"]
        # the state has taken in every token but the last emitted, which the
        # next token-step would have fed
        taken = list(prompt) + emitted[:-1]
        out = {"positions": len(taken), "emitted": len(emitted)}
        for name, (have, want) in eng.family.reference_slot_state(
                eng.cfg, eng.params, taken, handoff["slot_state"]).items():
            have, want = (np.asarray(a, np.float32).reshape(len(a), -1)
                          for a in (have, want))

            def rel(axis):
                return (np.sqrt(((have - want) ** 2).sum(axis))
                        / np.maximum(np.sqrt((want ** 2).sum(axis)), 1e-30))

            out[name] = {"finite": bool(np.isfinite(have).all()),
                         "rel_err": float(rel(None)),
                         "layer_rel_err": [float(v) for v in rel(1)]}
        return out

    def first_decode_logits(self, prompt: Sequence[int]):
        """The base engine's ``first_decode_logits``."""
        return self._engine.first_decode_logits(prompt)

    def prefix_digest(self) -> Dict[str, Any]:
        """Cache-aware routing surface (serve/handle.py): the base engine's
        prefix-chain digest plus the adapter ids this replica has loaded
        (LoRA affinity) and the live request depth.  Published to the GCS
        KV by the hosting replica (throttled, versioned)."""
        digest = self._engine.prefix_digest()
        with self._engines_lock:
            engines = list(self._engines.values())
            models = [m for m in self._engine_order]
        qlen = 0
        for eng in engines:
            with eng._lock:
                qlen += len(eng._requests)
        digest["models"] = models
        digest["qlen"] = qlen
        return digest

    def _wait_done(self, wkey) -> List[int]:
        """Block until ``wkey``'s request finishes; return all its tokens."""
        try:
            with self._cv:
                while wkey not in self._done:
                    if self._error is not None:
                        raise RuntimeError(
                            "LLM engine loop failed") from self._error
                    if self._stop:
                        raise RuntimeError("LLM server shut down")
                    self._cv.wait(timeout=0.1)
                buf = self._done.pop(wkey)
            self._note_specdec(wkey)
            self._note_request_row(wkey, None)
            return buf
        finally:
            with self._cv:
                self._active_waiters.discard(wkey)

    def _note_specdec(self, wkey) -> None:
        """Attach a finished request's speculative acceptance (engine-side
        per-request stats) to the active SLO tracker's recent-row.  A
        no-op for non-speculative engines, unknown ids, or callers with
        no tracker context — never raises into the serving path.

        Tracker context is thread-local and ingress-side (see
        slo.note_specdec_request): the row field lands for local-mode
        streaming and handle-level callers under ``slo.activate``; a
        cluster-mode replica process has no tracker and relies on the
        ledger fold + metric families for the acceptance signal."""
        try:
            eng = self._engine_of(wkey)
            stats = (eng.specdec_request_stats(wkey[2])
                     if eng is not None else None)
        except Exception:  # noqa: BLE001
            stats = None
        if stats:
            from ray_tpu.serve._private import slo

            slo.note_specdec_request(stats[0], stats[1])

    def _buffer_locked(self, wkey):
        """``(done, tokens so far)`` of ``wkey``'s stream, under ``_cv``."""
        done = wkey in self._done
        return done, (self._done[wkey] if done
                      else self._waiters.get(wkey, []))

    def _iter_tokens(self, wkey):
        """Yield ``wkey``'s token chunks as they decode (generate_stream's
        engine-side loop, shared with the disaggregated decode stage).

        Closing the generator BEFORE exhaustion (the caller's client
        disconnected — the proxy closes the stream chain) aborts the
        engine-side request: its slot and KV blocks return to the pool
        immediately instead of decoding to max_new_tokens for nobody."""
        sent = 0
        completed = False
        stream_out_s = None
        try:
            while True:
                with self._cv:
                    while True:
                        if self._error is not None:
                            raise RuntimeError(
                                "LLM engine loop failed") from self._error
                        if self._stop:
                            raise RuntimeError("LLM server shut down")
                        done, buf = self._buffer_locked(wkey)
                        if len(buf) > sent or done:
                            break
                        self._cv.wait(timeout=0.1)
                # the pass that hands tokens out, without the wait above:
                # this thread now holds the interpreter beside the loop's
                with tracing.region("serve.iter_tokens",
                                    rid=wkey[2]) as span:
                    with self._cv:  # read again: an export swaps buffers
                        done, buf = self._buffer_locked(wkey)
                        chunk = list(buf[sent:])
                        sent += len(chunk)
                        if done:
                            self._done.pop(wkey, None)
                    if span is not None:
                        span.set_metadata(tokens=len(chunk))
                    if chunk and sent == len(chunk):  # the first chunk
                        stream_out_s = self._note_first_yield(wkey)
                if chunk:
                    yield chunk
                if done:
                    completed = True
                    self._note_specdec(wkey)
                    self._note_request_row(wkey, stream_out_s)
                    return
        finally:
            if not completed:
                self._abort_wkey(wkey)
            with self._cv:
                self._active_waiters.discard(wkey)

    def _abort_wkey(self, wkey) -> None:
        """Cancel ``wkey``'s engine request and drop its buffers (stream
        abandoned mid-decode).  Best-effort: a request that finished in
        the race just cleans its unclaimed buffers."""
        model, gen_id, rid = wkey
        if model is None:
            # the cancel's drain resolves the in-flight chunk for EVERY
            # slot — run it atomically vs the loop's step+apply and
            # reconcile bystander buffers after (see _reap_drained)
            with self._hold_step_lock("cancel"):
                try:
                    self._engine.cancel_request(rid)
                except Exception:  # noqa: BLE001 — abort must never mask the close
                    pass
                with self._cv:
                    self._waiters.pop(wkey, None)
                    self._done.pop(wkey, None)
                    self._aborted.add(wkey)
                    if len(self._aborted) > 4096:  # backstop
                        self._aborted.clear()
                self._reap_drained()
            return
        try:
            with self._engines_lock:
                eng = (self._engines.get(model)
                       if self._engine_gen.get(model, 0) == gen_id
                       else None)
            if eng is not None:
                eng.cancel_request(rid)
        except Exception:  # noqa: BLE001 — abort must never mask the close
            pass
        with self._cv:
            self._waiters.pop(wkey, None)
            self._done.pop(wkey, None)
            self._aborted.add(wkey)
            if len(self._aborted) > 4096:  # never-seen-again backstop
                self._aborted.clear()

    _MAX_ADAPTER_ENGINES = 4

    def _submit(self, model: Optional[str], prompt, gen):
        """``_enqueue``, entry to return, as one region on the profiler's
        timeline; ``rid`` is the engine's request id, which the engine
        loop's ``engine.admit`` / ``.prefill_chunk`` / ``.join`` and the
        stream's ``serve.iter_tokens`` carry too."""
        with tracing.region("serve.add_request") as span:
            wkey = self._enqueue(model, prompt, gen)
            if span is not None:
                span.set_metadata(rid=wkey[2])
            return wkey

    def _enqueue(self, model: Optional[str], prompt, gen):
        """Resolve the engine for ``model`` and enqueue the request under
        ONE _engines_lock critical section, returning the waiter key.

        Invariants this protects (each was a bug once):
          - the merge + XLA compile happens OUTSIDE the lock (the _run loop
            takes it every iteration; compiling under it would freeze every
            in-flight stream);
          - add_request runs while holding the lock, so the eviction scan
            (which only removes engines with has_work() false, also under
            the lock) can never orphan a just-submitted request;
          - waiter keys carry the engine's BUILD GENERATION: a rebuilt
            engine restarts its request-id counter, and without the gen a
            new request could collide with an abandoned one's buffers."""
        if not model or model not in self._adapters:
            # base engine is never evicted, so its waiters need no
            # registry.  A step holds the engine's lock for its whole
            # body: the request waits here for the step in progress
            with tracing.region("serve.step_lock_wait", who="add_request"):
                return (None, 0, self._engine.add_request(prompt, gen))
        built = None
        while True:
            with self._engines_lock:
                eng = self._engines.get(model)
                if eng is None and built is not None:
                    self._engine_gen[model] = self._engine_gen.get(model, 0) + 1
                    if self._slo_label is not None:
                        built.slo_label = self._slo_label
                    self._engines[model] = eng = built
                if eng is not None:
                    rid = eng.add_request(prompt, gen)
                    wkey = (model, self._engine_gen[model], rid)
                    with self._cv:
                        self._active_waiters.add(wkey)
                    if model in self._engine_order:
                        self._engine_order.remove(model)
                    self._engine_order.append(model)
                    self._evict_idle_locked(keep=model)
                    return wkey
            # build outside the lock: merged weights are owned solely by the
            # engine map (single LRU bounds HBM)
            import dataclasses

            from ray_tpu.llm.engine import make_engine
            from ray_tpu.llm.lora import adapter_speculation, merge_lora

            # per-adapter draft choice (the multi-LoRA extension of
            # speculative decoding): an adapter may opt out, override k,
            # or carry its own draft-model LoRA so the draft tracks the
            # tuned target
            spec_cfg, draft_adapter = adapter_speculation(
                self._config.speculative_config, model)
            cfg = self._config
            if spec_cfg is not self._config.speculative_config:
                cfg = dataclasses.replace(cfg, speculative_config=spec_cfg)
            # tensor-parallel replicas: the merged-weight adapter engine
            # must shard over the SAME mesh as the base engine — a fresh
            # mesh built from tensor_parallel_size over "first visible
            # devices" could pick different chips than a placement-group
            # pinned base, double-committing HBM on one slice while the
            # reserved one idles
            base_mesh = self._engine.mesh
            if base_mesh is not None and cfg.mesh is None:
                cfg = dataclasses.replace(cfg, mesh=base_mesh)
            dparams = self._draft_params
            if spec_cfg is not None and draft_adapter is not None:
                dparams = merge_lora(self._draft_params, draft_adapter)
            built = make_engine(
                cfg, merge_lora(self._engine.params,
                                self._adapters[model]),
                draft_params=dparams)

    def _evict_idle_locked(self, keep):
        extra = len(self._engine_order) - self._MAX_ADAPTER_ENGINES
        for name in list(self._engine_order):
            if extra <= 0:
                break
            if name != keep and not self._engines[name].has_work():
                del self._engines[name]
                self._engine_order.remove(name)
                extra -= 1
                # drop the evicted engine's ABANDONED result buffers only:
                # a finished-but-unclaimed result may still have a live
                # caller between cv polls — never delete under a waiter
                with self._cv:
                    for wkey in [k for k in self._done
                                 if k[0] == name and k not in self._active_waiters]:
                        del self._done[wkey]
                    for wkey in [k for k in self._waiters
                                 if k[0] == name and k not in self._active_waiters]:
                        del self._waiters[wkey]

    def _run(self):
        name_os_thread()
        while not self._stop:
            with self._engines_lock:
                engines = list(self._engines.items())
            worked = False
            for key, engine in engines:
                if not engine.has_work():
                    continue
                worked = True
                gen_id = self._engine_gen.get(key, 0)
                # step + apply are one atomic unit vs export/cancel
                # drains: a drain between the step's snapshot-delta
                # gather and this apply would reconcile the buffer to
                # full history and then have the stale delta re-appended
                with self._hold_step_lock("loop"):
                    try:
                        emitted = engine.step()
                    except BaseException as e:  # noqa: BLE001 — fail waiters, not hang
                        with self._cv:
                            self._error = e
                            self._cv.notify_all()
                        return
                    if emitted:
                        with tracing.region("serve.apply",
                                            requests=len(emitted)), self._cv:
                            for rid, toks in emitted.items():
                                wk = (key, gen_id, rid)
                                if wk in self._migrating:
                                    # an export is reconciling this
                                    # stream's history into its buffer —
                                    # these tokens are already part of
                                    # the handoff
                                    continue
                                if wk in self._aborted:
                                    self._aborted.discard(wk)
                                    continue
                                self._waiters.setdefault(wk, []).extend(
                                    toks)
                            with engine._lock:
                                live = set(engine._requests)
                            for wkey in list(self._waiters):
                                if (wkey[0] == key and wkey[1] == gen_id
                                        and wkey[2] not in live
                                        and wkey not in self._migrating):
                                    buf = self._waiters.pop(wkey)
                                    if wkey in self._aborted:
                                        self._aborted.discard(wkey)
                                    else:
                                        self._done[wkey] = buf
                            self._cv.notify_all()
            if not worked:
                t0 = time.monotonic()
                with tracing.region("serve.loop_idle"):
                    time.sleep(0.002)
                self._engine.note_loop_idle(time.monotonic() - t0)

    # -- live KV migration (serve/_private/kv_migration.py) -------------
    #
    # A live stream moves between decode replicas in phases: the SOURCE
    # exports the engine request (export_stream — the slot and KV blocks
    # free immediately), the handoff travels to the DESTINATION
    # (import_migration — scatter + draft re-seed, or recompute), and the
    # source installs a relay (_splice) that keeps feeding the client's
    # ORIGINAL waiter buffer from the destination's continuation stream
    # (resume_stream).  The client's _iter_tokens never observes the
    # switch; the source lingers only as a thin byte relay until its
    # spliced streams finish — its engine is empty.

    def migratable_streams(self) -> List[int]:
        """Base-engine request ids currently in the exportable state
        (prefill complete, >= 1 token emitted).  Adapter streams are not
        listed — they carry no base-pool KV and resume on a destination
        by recompute through the planner's recompute path."""
        eng = self._engine
        out: List[int] = []
        with eng._lock:
            for rid, req in eng._requests.items():
                if (not req.done and req.slot >= 0
                        and req.prefill_pos >= len(req.prompt)
                        and req.out_tokens):
                    out.append(rid)
        return out

    def export_stream(self, rid: int) -> Dict[str, Any]:
        """Source-side migration export: drain + export ``rid`` from the
        base engine and reconcile the waiter buffer with the handoff's
        authoritative token history (the export's drain may resolve
        tokens the _run loop never gathered; marking the wkey migrating
        first makes the reconcile race-free against the loop).  On ANY
        failure the stream is healed back to normal operation — tokens
        re-synced from the engine, migration mark dropped — and the
        error re-raised for the planner's retry ladder."""
        wkey = (None, 0, rid)
        with self._cv:
            self._migrating.add(wkey)
        with self._hold_step_lock("export"):
            try:
                h = self._engine.export_request(rid)
            except BaseException:
                # export refused/died: the request may still be live in
                # the engine.  Re-sync the waiter buffer from engine
                # truth (the loop skipped emissions while the wkey was
                # marked) and hand the stream back to the normal path.
                with self._cv:
                    self._migrating.discard(wkey)
                    if wkey not in self._aborted:
                        with self._engine._lock:
                            req = self._engine._requests.get(rid)
                            hist = (list(req.out_tokens)
                                    if req is not None and not req.done
                                    else None)
                        if hist is not None:
                            buf = self._waiters.setdefault(wkey, [])
                            if len(hist) > len(buf):
                                buf.extend(hist[len(buf):])
                        self._cv.notify_all()
                self._reap_drained()  # rid, if the drain completed it
                raise
            h["model"] = None
            with self._cv:
                if wkey in self._aborted:
                    # client vanished during the export — nothing to
                    # splice
                    self._migrating.discard(wkey)
                else:
                    buf = self._waiters.setdefault(wkey, [])
                    if len(h["emitted"]) > len(buf):
                        buf.extend(h["emitted"][len(buf):])
                        self._cv.notify_all()
            # OTHER streams: the drain resolved their in-flight chunk
            # (and may have completed some) — reconcile before the loop
            # resumes stepping
            self._reap_drained()
        return h

    def _reap_drained(self) -> None:
        """Reconcile waiter buffers after an export/cancel drain.  The
        drain resolves the in-flight decode chunk for EVERY slot, and
        ``step()`` reports tokens as a snapshot delta taken at step
        entry — tokens a drain appended to ``out_tokens`` are invisible
        to all future deltas, so without this sync bystander streams
        silently lose one chunk.  A waiter buffer is always a prefix of
        its request's ``out_tokens`` (both are append-only, the loop
        extends from snapshot diffs), so topping up is bit-exact.
        Requests the drain COMPLETED are also moved to done here: once
        every slot is free ``has_work`` goes false and the loop would
        never gather them, hanging their consumers.  Mid-migration wkeys
        are skipped (their splice relay owns the buffer); aborted wkeys
        just clear their mark."""
        with self._cv:
            with self._engine._lock:
                dead, live = [], []
                for rid, req in list(self._engine._requests.items()):
                    wk = (None, 0, rid)
                    if wk in self._migrating:
                        continue
                    if req.done:
                        dead.append((wk, list(req.out_tokens)))
                        del self._engine._requests[rid]
                    elif req.out_tokens:
                        live.append((wk, list(req.out_tokens)))
            for wk, hist in live:
                if wk in self._aborted:
                    continue
                buf = self._waiters.setdefault(wk, [])
                if len(hist) > len(buf):
                    buf.extend(hist[len(buf):])
            for wk, hist in dead:
                if wk in self._aborted:
                    self._aborted.discard(wk)
                    self._waiters.pop(wk, None)
                    continue
                buf = self._waiters.setdefault(wk, [])
                if len(hist) > len(buf):
                    buf.extend(hist[len(buf):])
                self._done[wk] = self._waiters.pop(wk)
            if dead or live:
                self._cv.notify_all()

    @staticmethod
    def _handoff_gen(handoff: Dict[str, Any],
                     max_new_tokens: Optional[int] = None):
        g = handoff["gen"]
        return GenerationConfig(
            max_new_tokens=(g["max_new_tokens"] if max_new_tokens is None
                            else max_new_tokens),
            temperature=g["temperature"], top_k=g["top_k"],
            seed=g.get("seed", 0),
            stop_token_ids=tuple(g["stop_token_ids"]))

    def import_migration(self, handoff: Dict[str, Any],
                         allow_recompute: bool = False):
        """Destination-side migration import.  Tries the exact-resume KV
        import first (zero recompute); ``allow_recompute`` falls back to
        re-prefilling prompt + history as a fresh request with the
        remaining token budget (bit-equal for greedy decode — emitted
        history is never re-emitted either way).  Returns
        {wkey, done, mode} or None when this replica can't take the
        stream right now (no slot / no blocks) — the planner tries the
        next candidate.

        Idempotent under retry: the handoff's ``mig_id`` keys a bounded
        result memo, so a planner retrying after a lost reply gets the
        FIRST import's stream back instead of forking a duplicate."""
        mig_id = handoff.get("mig_id")
        if mig_id is not None:
            with self._cv:
                prev = self._mig_imports.get(mig_id)
            if prev is not None:
                return prev
        model = handoff.get("model")
        emitted = [int(t) for t in handoff["emitted"]]
        res = None
        leaves = {n: handoff.get(n) for n in self._engine.cache_leaves}
        if not model and all(x is not None for x in leaves.values()):
            try:
                res = self._engine.import_request(
                    handoff["prompt"], handoff["first_token"], leaves,
                    gen=self._handoff_gen(handoff), emitted=emitted,
                    slot_state=handoff.get("slot_state"))
            except ValueError:
                # geometry mismatch (block size / max_seq) — recompute
                # is the only road
                res = None
        if res is not None:
            wkey = (None, 0, res["request_id"])
            out = {"wkey": list(wkey), "done": bool(res["done"]),
                   "mode": "import"}
            with self._cv:
                self._active_waiters.add(wkey)
                if res["done"]:
                    # budget/stop boundary hit exactly at the handoff:
                    # the continuation stream is empty but must exist
                    self._done[wkey] = []
                    self._cv.notify_all()
                self._memo_import_locked(mig_id, out)
            return out
        if not allow_recompute:
            return None
        out = self._recompute_resume(model, handoff)
        if out is not None:
            with self._cv:
                self._memo_import_locked(mig_id, out)
        return out

    def _memo_import_locked(self, mig_id, result) -> None:
        if mig_id is None:
            return
        self._mig_imports[mig_id] = result
        while len(self._mig_imports) > 1024:  # bounded retry memo
            self._mig_imports.pop(next(iter(self._mig_imports)))

    def _recompute_resume(self, model: Optional[str],
                          handoff: Dict[str, Any]):
        """Resume a migrated stream WITHOUT its KV: re-prefill
        prompt + emitted history as a fresh request whose budget is the
        remaining tokens (PR 7's degraded-handoff path; the prefix cache
        usually absorbs most of the re-prefill).  History is the new
        prompt's tail, so nothing is ever re-emitted."""
        hist = [int(t) for t in handoff["emitted"]]
        g = handoff["gen"]
        remaining = int(g["max_new_tokens"]) - len(hist)
        if remaining <= 0 or (hist and hist[-1] in g["stop_token_ids"]):
            return {"wkey": None, "done": True, "mode": "recompute"}
        gen = self._handoff_gen(handoff, max_new_tokens=remaining)
        wkey = self._submit(model, list(handoff["prompt"]) + hist, gen)
        with self._cv:
            self._active_waiters.add(wkey)
        return {"wkey": list(wkey), "done": False, "mode": "recompute"}

    def resume_stream(self, wkey):
        """Destination-side continuation stream for a migrated-in
        request: yields only tokens decoded AFTER the handoff point
        (the source already streamed the history)."""
        yield from self._iter_tokens(tuple(wkey))

    def cancel_stream(self, wkey) -> None:
        """Abort a migrated-in stream (the source's client vanished, or
        a splice fallback abandoned this destination)."""
        self._abort_wkey(tuple(wkey))

    def _splice(self, rid: int, pull, cancel_remote,
                handoff: Dict[str, Any]) -> threading.Thread:
        """Install the waiter-splice for an exported stream: a relay
        thread feeds the client's ORIGINAL waiter buffer (old wkey) from
        ``pull`` — an iterator of continuation chunks from the migration
        destination (or a local restore).  If the destination dies
        mid-relay, the stream degrades once to local recompute from
        prompt + delivered history (the survivor in that case is this
        replica): zero client-visible drops, at re-prefill cost."""
        wkey = (None, 0, rid)
        hist = [int(t) for t in handoff["emitted"]]
        g = dict(handoff["gen"])

        def run():
            from ray_tpu._private import runtime_metrics

            it = pull
            fell_back = False
            while True:
                try:
                    for chunk in it:
                        toks = [int(t) for t in chunk]
                        with self._cv:
                            if wkey in self._aborted:
                                for fn in (getattr(it, "close", None),
                                           cancel_remote):
                                    try:
                                        if fn is not None:
                                            fn()
                                    except Exception:  # noqa: BLE001 — abort cleanup is best-effort
                                        pass
                                self._migrating.discard(wkey)
                                return
                            hist.extend(toks)
                            self._waiters.setdefault(wkey, []).extend(toks)
                            self._cv.notify_all()
                    break  # destination stream completed cleanly
                except Exception:  # noqa: BLE001 — dest died mid-relay: degrade, don't drop
                    if fell_back:
                        break  # local fallback failed too: terminate below
                    fell_back = True
                    runtime_metrics.record_kv_migration(
                        handoff.get("reason", "manual"), "fallback")
                    remaining = int(g["max_new_tokens"]) - len(hist)
                    if remaining <= 0:
                        break
                    try:
                        new_wkey = self._submit(
                            None, list(handoff["prompt"]) + hist,
                            self._handoff_gen(handoff,
                                              max_new_tokens=remaining))
                    except Exception:  # noqa: BLE001 — even local admission failed
                        break
                    it = self._iter_tokens(new_wkey)
            with self._cv:
                self._migrating.discard(wkey)
                if wkey not in self._aborted:
                    self._done[wkey] = self._waiters.pop(wkey, [])
                    self._cv.notify_all()

        t = threading.Thread(target=run, daemon=True,
                             name="kv-migration-splice")
        t.start()
        return t

    def _finish_migrated(self, rid: int) -> None:
        """Terminate an exported stream whose continuation is EMPTY (the
        budget/stop boundary landed exactly on the handoff): the waiter
        buffer already holds the full history, so just finish it."""
        wkey = (None, 0, rid)
        with self._cv:
            self._migrating.discard(wkey)
            if wkey not in self._aborted:
                self._done[wkey] = self._waiters.pop(wkey, [])
                self._cv.notify_all()

    def evacuate_streams(self, dests=None, reason: str = "drain",
                         max_streams: Optional[int] = None,
                         dest_servers=None) -> Dict[str, int]:
        """Migrate this server's live base-engine streams to ``dests``
        (replica actor-id hexes; ``dest_servers`` takes in-process
        LLMServer objects for local mode and tests).  The planner's
        entry point for drain evacuation and rebalancing; every stream
        survives — worst case it stays here via local restore."""
        from ray_tpu.serve._private import kv_migration

        return kv_migration.evacuate(self, dests or [], reason=reason,
                                     max_streams=max_streams,
                                     dest_servers=dest_servers)

    def shutdown(self):
        self._stop = True

    def generate(self, prompt: Sequence[int],
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 top_k: int = 0, stop_token_ids: Sequence[int] = (),
                 model: Optional[str] = None) -> List[int]:
        """Generate completion token ids for one prompt (sync; batching with
        concurrent callers happens inside the engine). ``model`` selects a
        registered LoRA adapter (None/base id -> base weights)."""
        gen = GenerationConfig(max_new_tokens=max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               stop_token_ids=tuple(stop_token_ids))
        wkey = self._submit(model, list(prompt), gen)
        return self._wait_done(wkey)

    def generate_stream(self, prompt: Sequence[int],
                        max_new_tokens: int = 64, temperature: float = 0.0,
                        top_k: int = 0, stop_token_ids: Sequence[int] = (),
                        model: Optional[str] = None):
        """Yield token chunks AS DECODED — pair with
        ``.options(num_returns="streaming")`` on the actor method so callers
        iterate an ObjectRefGenerator while decoding continues (reference:
        vLLM streaming generate + serve streaming responses)."""
        gen = GenerationConfig(max_new_tokens=max_new_tokens,
                               temperature=temperature, top_k=top_k,
                               stop_token_ids=tuple(stop_token_ids))
        wkey = self._submit(model, list(prompt), gen)
        yield from self._iter_tokens(wkey)

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """HTTP-style entry: {"prompt": [ids], "max_new_tokens": n, ...}."""
        toks = self.generate(
            request["prompt"],
            max_new_tokens=request.get("max_new_tokens", 64),
            temperature=request.get("temperature", 0.0),
            top_k=request.get("top_k", 0),
            stop_token_ids=request.get("stop_token_ids", ()),
            model=request.get("model"),
        )
        return {"tokens": toks}

    def check_health(self) -> bool:
        return self._loop.is_alive()


def build_llm_deployment(llm_config: LLMConfig, params=None, *,
                         name: str = "llm",
                         lora_adapters: Optional[Dict[str, Any]] = None,
                         draft_params=None):
    """An Application serving ``llm_config`` (reference:
    llm/_internal/serve build_openai_app / LLMServer deployment).

    Replica resources follow the engine's parallelism degrees the way the
    reference sizes placement groups from vLLM engine_kwargs.
    ``draft_params``: weights for ``llm_config.speculative_config``'s
    draft model (ignored without a speculative config).
    """
    from ray_tpu import serve

    deployment = serve.deployment(
        LLMServer,
        name=name,
        num_replicas=llm_config.num_replicas,
        # concurrent callers share the engine's decode batch
        max_ongoing_requests=max(8, llm_config.max_batch_size),
        ray_actor_options={"resources": llm_config.resources_per_replica()},
    )
    return deployment.bind(llm_config, params, lora_adapters, draft_params)
