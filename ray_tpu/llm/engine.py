"""JAX LLM inference engine: KV-cache decode with continuous batching.

The reference delegates serving to vLLM and reserves matching placement
groups (reference: llm/_internal/serve/deployments/llm/vllm/vllm_models.py
:177-186, :241-259).  Here the engine itself is framework-native and
TPU-first:

  - static-shape KV cache with `max_batch` sequence slots; one jitted
    decode program advances EVERY active slot (continuous batching — new
    requests join the running batch at any step by prefilling into a free
    slot, no generation restart)
  - multi-step scheduling: each step() runs `decode_chunk` tokens as ONE
    device program (stop tokens / budgets / cache bounds handled
    in-program; slots self-deactivate mid-chunk), amortizing per-dispatch
    host latency (the gain on a directly attached chip: not measured)
  - the decode-loop state (next tokens, lengths, active mask, budgets,
    stop ids, PRNG key) lives on DEVICE between steps; the host uploads
    mirrors only on slot transitions and reads back one [chunk, B] token
    block per step
  - prefill jitted per bucketed prompt length (powers of two) so arrival
    order doesn't cause recompiles
  - sampling (greedy / temperature / top-k) inside the jitted program;
    only sampled token ids cross the host boundary each step
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.analysis.lock_witness import make_lock
from ray_tpu._private import device_telemetry
from ray_tpu.llm.config import GenerationConfig, LLMConfig
from ray_tpu.models import llama
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.util import tracing


# stop-token ids travel to the device as a fixed-width padded row per slot
_MAX_STOP_IDS = 8
# top-k sampling cap: the kth threshold comes from lax.top_k(logits, 64)
# instead of a full [B, V] sort — the sort was milliseconds per decode step
# at V=32k on TPU, the top-64 is microseconds
_MAX_TOP_K = 64


@dataclasses.dataclass
class _Request:
    request_id: int
    prompt: List[int]
    gen: GenerationConfig
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    slot: int = -1
    error: Optional[str] = None


def _masked_scaled(logits, temps, top_ks):
    """Temperature-scaled, top-k-masked logits [B, V] — the categorical
    branch's pre-softmax shape, shared by sampling and the speculative
    verifier (target/draft distributions MUST match what non-speculative
    sampling would draw from).  temps <= 0 rows divide by 1.0 (a benign
    placeholder — those rows are greedy and never read the scaled value;
    the old ``max(temps, 1e-6)`` scaled logits by 1e6, a needless
    overflow hazard on the never-used branch)."""
    t = jnp.where(temps > 0.0, temps, 1.0)[:, None]
    scaled = logits / t
    # kth-largest via a capped top-k (not a full [B, V] sort — V=32k sorts
    # cost milliseconds per step on TPU; see _MAX_TOP_K)
    kmax = min(_MAX_TOP_K, logits.shape[-1])
    topv, _ = jax.lax.top_k(scaled, kmax)
    idx = jnp.clip(top_ks - 1, 0, kmax - 1)
    kth = jnp.take_along_axis(topv, idx[:, None], axis=-1)
    return jnp.where((top_ks[:, None] > 0) & (scaled < kth), -1e30, scaled)


@jax.named_scope("sample")
def _sample(logits, key, temps, top_ks):
    """Sample [B] token ids from [B, V] logits with *per-slot* traced
    sampling params — one compiled program serves any mix of greedy /
    temperature / top-k callers sharing the decode batch.

    temps [B] float32 (<= 0 -> greedy); top_ks [B] int32 (<= 0 -> off).

    temperature <= 0 is EXACT argmax of the raw logits: no temperature
    scaling, no top-k perturbation, and no dependence on ``key`` (the
    categorical draw happens on the other branch of the select; greedy
    rows ignore it entirely) — the enabling precondition for speculative
    decoding's greedy bit-parity pin (tests/test_specdec.py).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    masked = _masked_scaled(logits, temps, top_ks)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def _sample_dist(logits, temps, top_ks):
    """The probability distribution [B, V] that ``_sample`` draws from:
    post temperature/top-k softmax for temps > 0 rows, an exact one-hot
    at the argmax for greedy rows.  The one-hot form makes speculative
    rejection sampling COLLAPSE to exact greedy verification — accept iff
    the draft token is the target argmax, corrections/bonus tokens are
    the argmax — with no separate greedy branch in the verifier."""
    probs = jax.nn.softmax(_masked_scaled(logits, temps, top_ks), axis=-1)
    one_hot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                             dtype=probs.dtype)
    return jnp.where(temps[:, None] <= 0.0, one_hot, probs)


def build_tp_mesh(cfg, tp: int):
    return build_engine_mesh(cfg, tp, 1)


def build_engine_mesh(cfg, tp: int, pp: int, mesh=None):
    """Validate the TP × PP degrees and build a `pipeline`×`tensor` mesh.

    TP=PP=1 stays mesh-free (single-device fast path).  PP shards the
    STACKED layer dim of params and KV cache over `pipeline`
    (vllm_models.py:181-191 folds the degree into placement; here it is a
    real mesh axis): each stage holds L/pp layers' weights + cache — the
    way to serve a model whose layers don't fit one chip/slice.  The
    layer scan crosses stage boundaries with XLA-inserted transfers of the
    [B, D] activation (tiny for decode); stages run sequentially within
    one step — PP here buys MEMORY reach, microbatch overlap is the
    training path's job (parallel/pipeline.py).

    ``mesh`` (LLMConfig.mesh): a caller-built mesh pinning WHICH devices
    the replica shards over — validated against the degrees (axis sizes
    must match) and the model's divisibility, then used as-is."""
    if tp <= 1 and pp <= 1 and mesh is None:
        return None
    if mesh is not None:
        shape = dict(mesh.shape)
        if shape.get("tensor", 1) != max(tp, 1):
            raise ValueError(
                f"config.mesh tensor axis is {shape.get('tensor', 1)} but "
                f"tensor_parallel_size={tp} — the degrees must agree")
        if shape.get("pipeline", 1) != max(pp, 1):
            raise ValueError(
                f"config.mesh pipeline axis is {shape.get('pipeline', 1)} "
                f"but pipeline_parallel_size={pp}")
    devices = jax.devices()
    if mesh is None and len(devices) < tp * pp:
        raise ValueError(
            f"tensor_parallel_size={tp} x pipeline_parallel_size={pp} needs "
            f"{tp * pp} devices but only {len(devices)} visible device(s) — "
            f"an engine must never silently compute on fewer chips than it "
            f"reserves")
    if cfg.n_layers % max(pp, 1):
        raise ValueError(
            f"pipeline_parallel_size={pp} does not divide n_layers={cfg.n_layers}")
    if tp > 1:
        for name, dim in (("n_heads", cfg.n_heads),
                          ("n_kv_heads", cfg.n_kv_heads),
                          ("ffn_dim", cfg.ffn_dim),
                          ("vocab_size", cfg.vocab_size)):
            if dim % tp:
                raise ValueError(
                    f"tensor_parallel_size={tp} does not divide model "
                    f"{name}={dim}")
    if mesh is not None:
        return mesh
    from ray_tpu.parallel.mesh import MeshSpec

    return MeshSpec(pipeline=pp, tensor=tp).build(devices[:tp * pp])


def pp_param_specs(specs: dict, pp: int) -> dict:
    """Shard the stacked-layer dim of inference params over `pipeline`."""
    if pp <= 1:
        return specs
    from jax.sharding import PartitionSpec as P

    specs = dict(specs)
    specs["layers"] = jax.tree.map(
        lambda s: P(*(("pipeline",) + tuple(s)[1:])), specs["layers"],
        is_leaf=lambda x: isinstance(x, P))
    return specs


def pp_cache_spec(spec: dict, pp: int) -> dict:
    """KV caches/pools are [L, ...]: shard dim 0 over `pipeline` too."""
    if pp <= 1:
        return spec
    from jax.sharding import PartitionSpec as P

    return {k: P(*(("pipeline",) + tuple(s)[1:])) for k, s in spec.items()}


def make_engine(config: "LLMConfig", params=None, *, key=None,
                draft_params=None):
    """Engine factory: ``config.kv_cache`` picks paged (default) or static.

    ``draft_params``: params for ``config.speculative_config``'s draft
    model (paged engine only; None with speculation configured random-
    initializes the draft — fine for tests, acceptance-rate ~0 in prod).
    """
    if config.kv_cache == "paged":
        from ray_tpu.llm.paged import PagedJaxLLMEngine

        return PagedJaxLLMEngine(config, params, key=key,
                                 draft_params=draft_params)
    if config.kv_cache == "static":
        if config.speculative_config is not None:
            raise ValueError(
                "speculative_config requires kv_cache='paged' (the static "
                "engine has no block pool for the draft KV)")
        return JaxLLMEngine(config, params, key=key)
    raise ValueError(
        f"kv_cache must be 'paged' or 'static' (got {config.kv_cache!r})")


class JaxLLMEngine:
    """Single-process engine owning params + cache on device.

    API: ``add_request() -> id``, ``step() -> {id: [new tokens]}``,
    ``generate()`` (sync convenience driving step() to completion).
    """

    def __init__(self, config: LLMConfig, params=None, *, key=None):
        self.config = config
        cfg = config.model_config
        if cfg is None:
            raise ValueError("LLMConfig.model_config is required")
        self.cfg = cfg
        self.max_batch = config.max_batch_size
        self.max_seq = config.max_seq_len or cfg.max_seq_len
        if config.decode_chunk < 1:
            # 0 would scan zero steps: step() emits nothing while
            # has_work() stays true — generate()/serve drivers spin forever
            raise ValueError(
                f"decode_chunk must be >= 1 (got {config.decode_chunk})")
        if params is None:
            params = llama.init_params(cfg, key or jax.random.PRNGKey(0))
        self.params = params
        cos, sin = rope_frequencies(cfg.head_dim, self.max_seq, cfg.rope_theta)
        self._rope = (jnp.asarray(cos), jnp.asarray(sin))

        # --- tensor parallelism: a real mesh, not just a chip reservation ---
        # (reference: vllm_models.py:177-186 wires TP from engine_kwargs into
        # the engine; here TP is a jax mesh axis and GSPMD partitions the
        # prefill/decode programs from the param + cache shardings alone)
        pp = config.pipeline_parallel_size
        self.mesh = build_engine_mesh(cfg, config.tensor_parallel_size, pp,
                                      mesh=getattr(config, "mesh", None))
        self.cache = llama.init_kv_cache(cfg, self.max_batch, self.max_seq)
        if self.mesh is not None:
            from ray_tpu.parallel.mesh import shard_pytree

            self.params = shard_pytree(
                self.params,
                pp_param_specs(llama.inference_param_specs(cfg), pp),
                self.mesh)
            self.cache = shard_pytree(
                self.cache, pp_cache_spec(llama.kv_cache_spec(), pp),
                self.mesh)
        # host-side slot state
        self._slot_req: List[Optional[_Request]] = [None] * self.max_batch
        self._lengths = np.zeros(self.max_batch, np.int32)
        self._next_tok = np.zeros(self.max_batch, np.int32)
        self._slot_temp = np.zeros(self.max_batch, np.float32)
        self._slot_topk = np.zeros(self.max_batch, np.int32)
        # device mirrors of the decode-loop state: the steady-state loop
        # must not upload ANYTHING per token, and the PRNG key lives on
        # device too (a host-side random.split is a dispatch and a
        # readback per token); mirrors refresh only on slot transitions
        self._dirty = True
        self._d_next = self._d_lengths = self._d_active = None
        self._d_temp = self._d_topk = None
        self._d_remaining = self._d_stops = None
        self._d_key = jax.random.PRNGKey(config.model_config.vocab_size + 1)
        self._pending: List[_Request] = []
        self._requests: Dict[int, _Request] = {}
        self._req_counter = 0
        self._lock = make_lock("JaxLLMEngine._lock")
        # one decode chunk may stay in flight (collected next step): its
        # readback overlaps the next chunk's compute, like the paged
        # engine.  (em_dev, active_slots).
        self._inflight = None
        # serving deployment name (set via the replica's set_slo_label
        # threading); assigning one attaches device telemetry.  None
        # (direct engine use) keeps the disabled path: one attribute
        # read + None check per step.
        self._slo_label: Optional[str] = None
        self._telemetry: Optional[device_telemetry.EngineTelemetry] = None

        # params are an ARGUMENT of the jitted programs, never a closure:
        # captured closures lower as inline constants, and a real model's
        # weights (GBs) baked into the module stall compilation and double
        # HBM (observed: 2.3GB of captured constants on the 1B config)
        self._decode = jax.jit(self._decode_chunk_impl, donate_argnums=2,
                               static_argnums=10)
        # jax.jit caches per input shape, so bucketed prompt lengths reuse
        # compilations automatically
        self._prefill = jax.jit(self._prefill_impl)
        self._write_slot = jax.jit(llama.write_cache_slot, donate_argnums=0)

    def _build_tp_mesh(self, tp: int):
        return build_tp_mesh(self.cfg, tp)

    # -- device telemetry ----------------------------------------------

    @property
    def slo_label(self) -> Optional[str]:
        return self._slo_label

    @slo_label.setter
    def slo_label(self, name: Optional[str]) -> None:
        self._slo_label = name
        if name is None:
            self._telemetry = None
            return
        self._telemetry = device_telemetry.engine_telemetry_for(
            name,
            weights_bytes=device_telemetry.tree_nbytes(self.params),
            kv_pool_bytes=device_telemetry.tree_nbytes(self.cache))
        if self._telemetry is not None:
            device_telemetry.register_utilization_object(
                f"{name}:{id(self):x}", self)

    def utilization(self) -> dict:
        """Exact engine bookkeeping for ``state.utilization()``.  The
        static cache has no block pool — KV occupancy is slot occupancy
        (a slot owns its full max_seq stripe for its lifetime)."""
        with self._lock:
            active = sum(1 for r in self._slot_req if r is not None)
            pending = len(self._pending)
        row = {
            "engine": "static",
            "deployment": self._slo_label,
            "slots": {"active": active, "max": self.max_batch,
                      "free": self.max_batch - active},
            "kv_blocks": {"total": self.max_batch, "free":
                          self.max_batch - active, "used": active},
            "pending": pending,
        }
        tel = self._telemetry
        if tel is not None:
            rates = tel.rates()
            row["duty_cycle"] = rates["duty_cycle"]
            row["rates"] = rates
            row["hbm"] = tel.hbm_split()
        return row

    # -- jitted programs ------------------------------------------------

    def _decode_chunk_impl(self, params, tokens, cache, lengths, active,
                           remaining, stops, key, temps, top_ks, n_steps):
        """Advance every slot up to ``n_steps`` tokens in ONE program.

        Multi-step scheduling: stop-token / token-budget / cache-full
        handling runs in-program (slots self-deactivate mid-chunk), so the
        host syncs once per chunk instead of once per token: per-dispatch
        latency is not small beside a 1-token step's compute.
        Returns (emitted [n_steps, B] with -1 for inactive slots, new state).
        """

        def one(carry, _):
            tokens, cache, lengths, active, remaining, key = carry
            logits, cache = llama.decode_step(
                self.cfg, params, tokens, cache, lengths,
                rope_cache=self._rope)
            key, sub = jax.random.split(key)
            ids = _sample(logits, sub, temps, top_ks)
            emitted = jnp.where(active > 0, ids, -1)
            lengths = lengths + active
            remaining = remaining - active
            hit_stop = (stops == ids[:, None]).any(-1)
            done = (active > 0) & (hit_stop | (remaining <= 0)
                                   | (lengths + 1 >= self.max_seq))
            active = active * (1 - done.astype(active.dtype))
            tokens = jnp.where(active > 0, ids, tokens)
            return (tokens, cache, lengths, active, remaining, key), emitted

        carry = (tokens, cache, lengths, active, remaining, key)
        carry, emitted = jax.lax.scan(one, carry, None, length=n_steps)
        tokens, cache, lengths, active, remaining, key = carry
        return emitted, tokens, cache, lengths, active, remaining, key

    def _prefill_impl(self, params, tokens, length, key, temps, top_ks):
        logits, kv = llama.prefill(
            self.cfg, params, tokens, rope_cache=self._rope)
        last = logits[jnp.arange(tokens.shape[0]), length - 1]
        key, sub = jax.random.split(key)
        ids = _sample(last, sub, temps, top_ks)
        return ids, kv, key

    # -- request lifecycle ---------------------------------------------

    def add_request(self, prompt: Sequence[int],
                    gen: Optional[GenerationConfig] = None) -> int:
        gen = gen or GenerationConfig()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(gen.stop_token_ids) > _MAX_STOP_IDS:
            raise ValueError(
                f"at most {_MAX_STOP_IDS} stop_token_ids supported "
                f"(got {len(gen.stop_token_ids)})")
        if gen.top_k > _MAX_TOP_K:
            raise ValueError(
                f"top_k is capped at {_MAX_TOP_K} (got {gen.top_k}) — the "
                "kth threshold comes from a fixed-width lax.top_k")
        if len(prompt) + gen.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({gen.max_new_tokens})"
                f" exceeds max_seq_len {self.max_seq}")
        with self._lock:
            self._req_counter += 1
            req = _Request(self._req_counter, list(prompt), gen)
            self._requests[req.request_id] = req
            self._pending.append(req)
            return req.request_id

    def has_work(self) -> bool:
        with self._lock:
            return (bool(self._pending) or self._inflight is not None
                    or any(r is not None for r in self._slot_req))

    def _admit_locked(self):
        """Prefill pending requests into free slots (continuous batching)."""
        for slot in range(self.max_batch):
            if not self._pending or self._slot_req[slot] is not None:
                continue
            req = self._pending.pop(0)
            plen = len(req.prompt)
            bucket = 1 << max(3, math.ceil(math.log2(plen)))
            bucket = min(bucket, self.max_seq)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :plen] = req.prompt
            ids, kv, self._d_key = self._prefill(
                self.params, jnp.asarray(tokens), jnp.asarray([plen]),
                self._d_key,
                jnp.asarray([req.gen.temperature], jnp.float32),
                jnp.asarray([req.gen.top_k], jnp.int32))
            self.cache = self._write_slot(self.cache, kv, slot)
            first = int(ids[0])
            req.slot = slot
            self._slot_req[slot] = req
            self._lengths[slot] = plen
            self._next_tok[slot] = first
            self._slot_temp[slot] = req.gen.temperature
            self._slot_topk[slot] = req.gen.top_k
            self._dirty = True  # device mirrors stale: new slot joined
            self._emit_locked(req, first)

    def _emit_locked(self, req: _Request, token: int):
        req.out_tokens.append(token)
        if (token in req.gen.stop_token_ids
                or len(req.out_tokens) >= req.gen.max_new_tokens
                or self._lengths[req.slot] + 1 >= self.max_seq):
            req.done = True
            self._slot_req[req.slot] = None
            self._lengths[req.slot] = 0
            req.slot = -1
            self._dirty = True  # device mirrors stale: slot freed

    def step(self, decode: bool = True) -> Dict[int, List[int]]:
        """Admit pending, then advance every active slot by up to
        ``config.decode_chunk`` tokens in one device program (multi-step
        scheduling; slots hitting a stop/budget mid-chunk deactivate
        in-program). decode_chunk=1 recovers per-token stepping.
        ``decode=False`` runs admission/prefill only (ramp control).

        Returns {request_id: [tokens emitted this step]}.
        """
        now = time.monotonic()
        # device telemetry: one attribute read + None check when disabled
        tel = self._telemetry
        with tracing.region(
                "engine.step", pending=len(self._pending),
                active=self.max_batch - self._slot_req.count(None),
                inflight=int(self._inflight is not None)), self._lock:
            before = {id(r): len(r.out_tokens)
                      for r in self._requests.values()}
            if self._pending:
                # admission prefills synchronously; its cache writes chain
                # after any in-flight chunk on the cache dataflow, and the
                # new slot was inactive in that chunk (garbage rows are
                # overwritten by the decode step that first uses them)
                with tracing.region("engine.admit",
                                    pending=len(self._pending)):
                    self._admit_locked()
            active = [s for s in range(self.max_batch)
                      if self._slot_req[s] is not None]
            if active and decode:
                if self._dirty:
                    self._collect_inflight_locked()
                    active = [s for s in range(self.max_batch)
                              if self._slot_req[s] is not None]
                if self._dirty and active:
                    # slot transition since last chunk: refresh the device
                    # mirrors from host truth — the ONLY uploads in the loop
                    self._d_next = jnp.asarray(self._next_tok)
                    self._d_lengths = jnp.asarray(self._lengths)
                    self._d_active = jnp.asarray(np.array(
                        [0 if r is None else 1 for r in self._slot_req],
                        np.int32))
                    self._d_temp = jnp.asarray(self._slot_temp)
                    self._d_topk = jnp.asarray(self._slot_topk)
                    remaining = np.zeros(self.max_batch, np.int32)
                    stops = np.full((self.max_batch, _MAX_STOP_IDS), -1,
                                    np.int32)
                    for s, r in enumerate(self._slot_req):
                        if r is not None:
                            remaining[s] = (r.gen.max_new_tokens
                                            - len(r.out_tokens))
                            for j, sid in enumerate(r.gen.stop_token_ids):
                                stops[s, j] = sid
                    self._d_remaining = jnp.asarray(remaining)
                    self._d_stops = jnp.asarray(stops)
                    self._dirty = False
            if active and decode:
                # one chunked decode program for the whole batch; sampling
                # params are traced per-slot arrays, so mixed greedy /
                # temperature / top-k callers share a single forward.
                # PIPELINED: the chunk dispatched here is collected next
                # step, its readback riding under this dispatch's compute.
                with tracing.region("engine.decode_dispatch",
                                    slots=len(active),
                                    chunk=self.config.decode_chunk):
                    (em_dev, self._d_next, self.cache, self._d_lengths,
                     self._d_active, self._d_remaining, self._d_key) = \
                        self._decode(
                            self.params, self._d_next, self.cache,
                            self._d_lengths, self._d_active,
                            self._d_remaining, self._d_stops, self._d_key,
                            self._d_temp, self._d_topk,
                            self.config.decode_chunk)
                prev, self._inflight = self._inflight, (em_dev, active)
                if prev is not None:
                    self._book_chunk_locked(*prev)
            else:
                self._collect_inflight_locked()
            emitted = self._gather_emitted_locked(before)
            tel_active = self.max_batch - self._slot_req.count(None)
            tel_pending = len(self._pending)
        if tel is not None:
            # booked after release, from the locals captured under the lock
            t_end = time.monotonic()
            tel.note_step(
                active_slots=tel_active, max_slots=self.max_batch,
                free_blocks=self.max_batch - tel_active,
                total_blocks=self.max_batch, pending=tel_pending,
                prefill_spent=0, prefill_budget=0,
                busy_s=t_end - now, now=t_end)
        return emitted

    def _book_chunk_locked(self, em_dev, active):
        with tracing.region("engine.collect", slots=len(active)):
            em = np.asarray(em_dev)  # [chunk, B] — the single sync
        for t in range(em.shape[0]):
            for s in active:
                req = self._slot_req[s]
                if req is None:
                    continue  # finished earlier in this chunk
                tok = int(em[t, s])
                if tok < 0:
                    continue
                self._lengths[s] += 1
                self._next_tok[s] = tok
                self._emit_locked(req, tok)

    def _collect_inflight_locked(self):
        if self._inflight is not None:
            em_dev, active = self._inflight
            self._inflight = None
            self._book_chunk_locked(em_dev, active)

    def _gather_emitted_locked(self, before):
        emitted: Dict[int, List[int]] = {}
        for req in list(self._requests.values()):
            n0 = before.get(id(req), 0)
            if len(req.out_tokens) > n0:
                emitted[req.request_id] = req.out_tokens[n0:]
            if req.done:
                del self._requests[req.request_id]
        return emitted

    def flush(self) -> Dict[int, List[int]]:
        """Collect any in-flight decode chunk and return its tokens."""
        with self._lock:
            before = {id(r): len(r.out_tokens)
                      for r in self._requests.values()}
            self._collect_inflight_locked()
            return self._gather_emitted_locked(before)

    def prefix_digest(self, max_hashes: Optional[int] = None) -> Dict:
        """Uniform engine surface for the cache-aware serve router: the
        static cache has no sharable prefix blocks, so its digest is empty
        (the router then treats every prompt as cold and uses pow-2)."""
        return {"block_size": 0, "hashes": []}

    # -- sync convenience ----------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]],
                 gen: Optional[GenerationConfig] = None) -> List[List[int]]:
        """Generate for a batch of prompts, driving step() to completion."""
        ids = [self.add_request(p, gen) for p in prompts]
        results: Dict[int, List[int]] = {i: [] for i in ids}
        waiting = set(ids)
        while waiting and self.has_work():
            emitted = self.step()
            for rid, toks in emitted.items():
                if rid in results:
                    results[rid].extend(toks)
            with self._lock:
                waiting = {rid for rid in waiting if rid in self._requests}
        # the last booking step may have dispatched one more (all-inactive)
        # chunk: collect it so has_work() is False on a drained engine
        self.flush()
        return [results[i] for i in ids]
