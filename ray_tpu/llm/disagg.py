"""Disaggregated prefill/decode LLM serving with KV-block handoff.

The monolithic ``LLMServer`` runs prefill and decode in one engine: a burst
of long prompts competes with the running decode batch for the same chips,
and burst TTFT collapses (measured r05: p50 2.4-2.8 s at 32 SSE clients).
This module splits the two phases into separately autoscaled serve
deployments — the topology the Gemma-on-TPU serving comparison argues for
(PAPERS.md, arxiv 2605.25645):

  - ``PrefillServer``: paged engines that ONLY prefill.  A finished
    prompt's KV blocks are exported (``PagedJaxLLMEngine.export_request``)
    and handed to a decode replica; the prompt's chain stays registered in
    the prefill replica's tiered prefix cache, so repeated prefixes keep
    hitting HBM/host tiers there.
  - ``DecodeServer``: an ``LLMServer`` whose requests arrive ALREADY
    prefilled — ``import_request`` scatters the handed-off blocks into its
    pool and the request joins the continuous decode batch with zero
    prompt compute.  If the import cannot be admitted right now (no
    slot/blocks), it falls back to ordinary ``add_request`` recompute —
    the prefix cache absorbs most of the cost, and no request is dropped.
  - ``DisaggLLMServer``: the lightweight ingress coordinating the two;
    its prefill handle routes cache-aware (serve/handle.py reads the
    per-replica prefix digests), so a warm prefix lands on the replica
    already holding the chain.

KV handoff rides either the plain actor-call payload path (``transport=
"object"`` — plasma/inline, works everywhere) or the device-tensor channel
plane (``transport="channel"`` — XlaTensorChannel ICI p2p on TPU, the
store communicator off-TPU; arrays never transit the GCS), optionally
int8-quantized with the PR 3 codec (``handoff_compression="int8"``,
lossy opt-in).  Both legs are metered as ``ray_tpu_kv_handoff_*``; the
100k-GPU collectives paper (arxiv 2510.20171) is the argument for keeping
this traffic on the transfer plane instead of the control plane.
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu.llm.config import GenerationConfig, LLMConfig
from ray_tpu.llm.serve import LLMServer, _warm_up

_HANDOFF_TIMEOUT_S = 600.0  # covers first-request jit compiles


class PrefillServer:
    """Prefill-only deployment: drives ``step(decode=False)`` and exports
    finished prompts' KV blocks.  Concurrent requests interleave their
    prefill chunks through the engine's own admission/budget machinery
    (one step advances every mid-prefill slot under the prefill token
    budget), exactly as in the monolithic engine — there is just no decode
    batch competing for the dispatch queue."""

    def __init__(self, llm_config: LLMConfig, params=None):
        import dataclasses

        from ray_tpu.llm.engine import make_engine

        if llm_config.speculative_config is not None:
            # prefill never decodes: a draft pool here would burn HBM and
            # every prompt would pay a pointless draft prefill.  The
            # DECODE stage is the speculative consumer — import_request
            # seeds its draft KV by recompute at draft size.
            llm_config = dataclasses.replace(llm_config,
                                             speculative_config=None)
        self._config = llm_config
        self._engine = make_engine(llm_config, params)
        _warm_up(self._engine)
        self._inflight = 0
        self._lock = threading.Lock()

    def set_slo_label(self, name: str) -> None:
        """Serving SLO threading (serve/_private/replica.py): engine-side
        lifecycle stages book under the prefill deployment's name."""
        self._engine.slo_label = name

    def prefix_digest(self) -> Dict[str, Any]:
        digest = self._engine.prefix_digest()
        digest["models"] = []
        digest["qlen"] = self._inflight
        return digest

    def utilization(self) -> Dict[str, Any]:
        """Device-telemetry row (replica publish / state.utilization())."""
        return self._engine.utilization()

    def queue_depth(self) -> int:
        return self._inflight

    def _track(self, delta: int):
        from ray_tpu._private import runtime_metrics

        with self._lock:
            self._inflight += delta
            n = self._inflight
        runtime_metrics.set_disagg_queue_depth("prefill", n)

    def prefill(self, prompt: Sequence[int], max_new_tokens: int = 64,
                temperature: float = 0.0, top_k: int = 0,
                stop_token_ids: Sequence[int] = (),
                handoff_channel=None) -> Dict[str, Any]:
        """Prefill one prompt and export its KV + first sampled token.

        Returns the handoff descriptor; with ``handoff_channel`` the k/v
        arrays are written to the channel (off-thread — the descriptor
        returns immediately so the decode side can start reading) and the
        descriptor carries only shapes."""
        from ray_tpu._private import runtime_metrics

        eng = self._engine
        # the real token budget is enforced by the decode stage; prefill
        # only needs the request alive past its first emit (>= 2), while
        # still respecting the pool's max_seq admission check
        gen = GenerationConfig(
            max_new_tokens=max(2, min(int(max_new_tokens),
                                      eng.max_seq - len(prompt))),
            temperature=temperature, top_k=top_k,
            stop_token_ids=tuple(stop_token_ids))
        # the handoff latency metric covers export gather + transfer
        # enqueue only — NOT the prefill compute (nor a first-request jit
        # compile), which would swamp it by orders of magnitude
        t0 = time.perf_counter()
        self._track(1)
        try:
            rid = eng.add_request(list(prompt), gen)
            deadline = time.monotonic() + _HANDOFF_TIMEOUT_S
            while True:
                eng.step(decode=False)
                with eng._lock:
                    req = eng._requests.get(rid)
                    ready = (req is not None and req.slot >= 0
                             and req.prefill_pos >= len(req.prompt)
                             and req.out_tokens)
                    gone = req is None
                if ready:
                    break
                if gone:
                    raise RuntimeError(
                        "prefill request finished before export (1-token "
                        "budget near max_seq) — decode will recompute")
                if time.monotonic() > deadline:
                    raise TimeoutError("prefill timed out")
                if not eng.has_work():
                    time.sleep(0.001)
            t0 = time.perf_counter()
            handoff = eng.export_request(rid)
        except (RuntimeError, ValueError):
            # graceful degradation: hand off the prompt with no KV — the
            # decode stage recomputes (its prefix cache usually helps; a
            # genuinely invalid request raises the same error there)
            handoff = {"prompt": list(prompt), "first_token": None,
                       "k": None, "v": None,
                       "block_size": self._config.block_size}
        finally:
            self._track(-1)
        # sender legs book latency only (nbytes=0) under a distinct
        # "<transport>_export" tag: the receiver is the one place that
        # knows the true moved size for every transport (wire codes+scales
        # when quantized), so the plain transport tag counts each handoff
        # exactly once — bytes, handoff count and effective bandwidth all
        # read off the receiver leg even when both stages share a process
        if handoff_channel is not None and handoff.get("k") is not None:
            k, v = handoff.pop("k"), handoff.pop("v")
            spec = getattr(handoff_channel, "_compression", None)
            transport = "channel_int8" if spec is not None else "channel"

            def _write():
                try:
                    handoff_channel.write((k, v),
                                          timeout=_HANDOFF_TIMEOUT_S)
                except Exception:  # noqa: BLE001 — reader gone: drop
                    pass

            # off-thread: channel writes rendezvous with the reader, and
            # the reader only starts once this call returns the descriptor
            threading.Thread(target=_write, daemon=True,
                             name="kv-handoff-write").start()
            handoff["via_channel"] = True
            runtime_metrics.record_kv_handoff(
                transport + "_export", 0, time.perf_counter() - t0)
        elif handoff.get("k") is not None:
            runtime_metrics.record_kv_handoff(
                "object_export", 0, time.perf_counter() - t0)
        return handoff

    def check_health(self) -> bool:
        return True


class DecodeServer(LLMServer):
    """Decode stage: an ``LLMServer`` (engine loop, waiters, LoRA LRU)
    whose requests normally arrive as KV handoffs instead of prompts."""

    def _import_handoff(self, handoff: Dict[str, Any],
                        gen: GenerationConfig):
        """Admit a handoff into the base engine; returns the waiter key.
        Falls back to plain add_request (recompute) when the handoff has
        no KV or cannot be admitted right now."""
        from ray_tpu._private import runtime_metrics

        eng = self._engine
        t0 = time.perf_counter()
        k, v = handoff.get("k"), handoff.get("v")
        chan = handoff.get("channel")
        transport = "object"
        if chan is not None and handoff.get("via_channel"):
            spec = getattr(chan, "_compression", None)
            transport = "channel_int8" if spec is not None else "channel"
            try:
                chan.register_reader(0)
            except Exception:  # noqa: BLE001 — reader already registered
                pass           # by a prior handoff on this channel
            try:
                k, v = chan.read(timeout=_HANDOFF_TIMEOUT_S)
            except Exception:  # noqa: BLE001 — lost channel: recompute
                k = v = None
        res = None
        if k is not None and handoff.get("first_token") is not None:
            try:
                res = eng.import_request(handoff["prompt"],
                                         handoff["first_token"], k, v, gen)
            except ValueError:
                # shape mismatch (per-stage config overrides: different
                # block_size / smaller decode max_seq): the handoff KV is
                # unusable here — recompute; a request that is genuinely
                # invalid for THIS engine raises the same error from
                # add_request below
                res = None
        if res is None:
            # recompute path: zero drops even when the pool is full or the
            # handoff was degraded — continuous batching absorbs it
            rid = eng.add_request(list(handoff["prompt"]), gen)
            self._set_decode_depth()
            return (None, 0, rid)
        # channel legs meter the WIRE bytes (int8 codes + scales when
        # quantized), not the logical array size
        nbytes = (chan.last_read_nbytes
                  if (chan is not None and transport.startswith("channel"))
                  else (k.nbytes + v.nbytes))
        handoff_s = time.perf_counter() - t0
        runtime_metrics.record_kv_handoff(transport, nbytes, handoff_s)
        # lifecycle stage under the decode deployment's label (the receiver
        # leg is the authoritative per-handoff observation, matching the
        # kv_handoff metric convention)
        from ray_tpu.serve._private import slo

        slo.record_stage(self._slo_label, "handoff", handoff_s)
        wkey = (None, 0, res["request_id"])
        # seed the waiter with the prefill-sampled first token: the engine
        # emitted it before the loop's next snapshot, so the loop alone
        # would never deliver it.  PREPENDED, not appended — between
        # import_request releasing the engine lock and this block, the
        # _run loop may already have stepped the engine and buffered token
        # #2 (or finished the request and moved its buffer to _done);
        # appending would deliver tokens out of order / strand the first
        # token in a leaked _waiters entry
        with self._cv:
            self._active_waiters.add(wkey)
            if res["done"] or wkey in self._done:
                self._done.setdefault(wkey, [])[:0] = res["emitted"]
            else:
                self._waiters.setdefault(wkey, [])[:0] = res["emitted"]
            self._cv.notify_all()
        self._set_decode_depth()
        return wkey

    def _set_decode_depth(self):
        from ray_tpu._private import runtime_metrics

        try:
            with self._engine._lock:
                n = len(self._engine._requests)
            runtime_metrics.set_disagg_queue_depth("decode", n)
        except Exception:  # noqa: BLE001 — depth gauge is telemetry; engine may be mid-swap
            pass

    @staticmethod
    def _gen_of(max_new_tokens, temperature, top_k, stop_token_ids):
        return GenerationConfig(max_new_tokens=max_new_tokens,
                                temperature=temperature, top_k=top_k,
                                stop_token_ids=tuple(stop_token_ids))

    def decode_from_handoff(self, handoff: Dict[str, Any],
                            max_new_tokens: int = 64,
                            temperature: float = 0.0, top_k: int = 0,
                            stop_token_ids: Sequence[int] = ()) -> List[int]:
        wkey = self._import_handoff(
            handoff, self._gen_of(max_new_tokens, temperature, top_k,
                                  stop_token_ids))
        return self._wait_done(wkey)

    def decode_stream_from_handoff(self, handoff: Dict[str, Any],
                                   max_new_tokens: int = 64,
                                   temperature: float = 0.0, top_k: int = 0,
                                   stop_token_ids: Sequence[int] = ()):
        wkey = self._import_handoff(
            handoff, self._gen_of(max_new_tokens, temperature, top_k,
                                  stop_token_ids))
        yield from self._iter_tokens(wkey)


class DisaggLLMServer:
    """Ingress of the disaggregated topology: prefill handle (cache-aware
    routed) -> KV handoff -> decode handle.  LoRA requests (``model=``)
    bypass disaggregation and run monolithically on the decode stage —
    adapter engines live there."""

    def __init__(self, llm_config: LLMConfig, prefill_handle, decode_handle,
                 transport: str = "object", handoff_compression=None):
        if transport not in ("object", "channel"):
            raise ValueError(f"transport must be 'object' or 'channel' "
                             f"(got {transport!r})")
        self._config = llm_config
        self._prefill = prefill_handle
        self._decode = decode_handle
        self._transport = transport
        self._compression = handoff_compression
        self._slo_label: Optional[str] = None

    def set_slo_label(self, name: str) -> None:
        self._slo_label = name

    def _make_channel(self):
        from ray_tpu.experimental.channel.xla_tensor_channel import (
            XlaTensorChannel,
        )

        return XlaTensorChannel(f"kvh-{uuid.uuid4().hex[:12]}",
                                compression=self._compression)

    def _run_prefill(self, prompt, gen_kwargs):
        chan = self._make_channel() if self._transport == "channel" else None
        resp = self._prefill.prefill.remote(
            prompt=list(prompt), handoff_channel=chan, **gen_kwargs)
        handoff = resp.result(timeout_s=_HANDOFF_TIMEOUT_S)
        if chan is not None:
            handoff["channel"] = chan
        return handoff

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 64,
                 temperature: float = 0.0, top_k: int = 0,
                 stop_token_ids: Sequence[int] = (),
                 model: Optional[str] = None) -> List[int]:
        gen_kwargs = dict(max_new_tokens=max_new_tokens,
                          temperature=temperature, top_k=top_k,
                          stop_token_ids=tuple(stop_token_ids))
        if model:
            return self._decode.generate.remote(
                prompt=list(prompt), model=model,
                **gen_kwargs).result(timeout_s=_HANDOFF_TIMEOUT_S)
        handoff = self._run_prefill(prompt, gen_kwargs)
        return self._decode.decode_from_handoff.remote(
            handoff, **gen_kwargs).result(timeout_s=_HANDOFF_TIMEOUT_S)

    def generate_stream(self, prompt: Sequence[int],
                        max_new_tokens: int = 64, temperature: float = 0.0,
                        top_k: int = 0, stop_token_ids: Sequence[int] = (),
                        model: Optional[str] = None):
        gen_kwargs = dict(max_new_tokens=max_new_tokens,
                          temperature=temperature, top_k=top_k,
                          stop_token_ids=tuple(stop_token_ids))
        if model:
            gen = self._decode.options(stream=True).generate_stream.remote(
                prompt=list(prompt), model=model, **gen_kwargs)
        else:
            handoff = self._run_prefill(prompt, gen_kwargs)
            gen = self._decode.options(
                stream=True).decode_stream_from_handoff.remote(
                    handoff, **gen_kwargs)
        for chunk in gen:
            yield chunk

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """Same dict API as ``LLMServer.__call__``."""
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
        return True


def build_disagg_llm_deployment(
        llm_config: LLMConfig, params=None, *, name: str = "llm",
        prefill_replicas: int = 1, decode_replicas: int = 1,
        transport: str = "object", handoff_compression=None,
        prefill_config: Optional[LLMConfig] = None,
        decode_config: Optional[LLMConfig] = None,
        prefill_autoscaling: Optional[dict] = None,
        decode_autoscaling: Optional[dict] = None,
        lora_adapters: Optional[Dict[str, Any]] = None,
        draft_params=None):
    """An Application serving ``llm_config`` as separately autoscaled
    prefill and decode deployments behind one ingress (the disaggregated
    analog of ``build_llm_deployment``).  ``prefill_config`` /
    ``decode_config`` override the per-stage engine shapes (a prefill pool
    mostly needs prompt-sized residency; decode wants the full pool);
    ``*_autoscaling`` are the standard serve autoscaling_config dicts, so
    the controller scales each stage on its own queue depth.

    With ``llm_config.speculative_config`` set, the DECODE stage is the
    speculative consumer (``draft_params`` feeds its draft model; every
    imported handoff seeds the draft KV by recompute at draft size); the
    prefill stage strips speculation — it never decodes."""
    from ray_tpu import serve

    pre_cfg = prefill_config or llm_config
    dec_cfg = decode_config or llm_config
    prefill_app = serve.deployment(
        PrefillServer, name=f"{name}-prefill",
        num_replicas=prefill_replicas,
        max_ongoing_requests=max(8, pre_cfg.max_batch_size),
        autoscaling_config=prefill_autoscaling,
        ray_actor_options={"resources": pre_cfg.resources_per_replica()},
    ).bind(pre_cfg, params)
    decode_app = serve.deployment(
        DecodeServer, name=f"{name}-decode",
        num_replicas=decode_replicas,
        max_ongoing_requests=max(8, dec_cfg.max_batch_size),
        autoscaling_config=decode_autoscaling,
        ray_actor_options={"resources": dec_cfg.resources_per_replica()},
    ).bind(dec_cfg, params, lora_adapters, draft_params)
    ingress = serve.deployment(
        DisaggLLMServer, name=name, num_replicas=1,
        max_ongoing_requests=4 * max(8, dec_cfg.max_batch_size),
        ray_actor_options={"resources": {"CPU": 0.1}},
    ).bind(llm_config, prefill_app, decode_app,
           transport=transport, handoff_compression=handoff_compression)
    return ingress
