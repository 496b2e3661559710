"""Paged-KV LLM engine: block-table cache, chunked prefill, prefix caching.

The reference gets paged attention / chunked prefill / prefix caching by
delegating serving to vLLM (reference: llm/_internal/serve/deployments/llm/
vllm/vllm_models.py:177-186 passes engine_kwargs straight through); a
TPU-native rebuild provides the equivalent itself:

  - the KV cache is a POOL of fixed-size HBM blocks shared by every request
    (the model family's ``init_paged_cache``, `models/family.py`: a dict of
    arrays the engine handles leaf by leaf); a request's HBM cost is
    proportional to its ACTUAL length, not max_seq — admission is
    memory-based (free blocks), not slot-count
  - the device sees a padded block TABLE [B, W] per decode chunk, W bucketed
    to the max blocks any active slot uses: short batches read a SMALLER
    attention span than max_seq
  - long prompts prefill in `prefill_chunk`-token pieces interleaved with
    decode chunks, so one long prompt never stalls the running batch
    (the family's ``prefill_chunk`` reads earlier chunks back from the
    pool, a KV tile at a time and only the tiles that hold the live prefix
    — no growing inter-chunk state, no cost for the table's width)
  - full prompt blocks are chain-hashed and shared across requests
    (refcounted; matches capped at plen-1 so sampling always has a logit)
  - pool exhaustion preempts the youngest running request by RECOMPUTE:
    its blocks are freed and it requeues with prompt+generated as the new
    prompt (emitted tokens are never re-emitted)

All device programs are static-shape (jit cache keyed on the (B, W, C)
buckets); block gathers/scatters are XLA gather/scatter on the block axis.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private.analysis.lock_witness import make_lock
from ray_tpu._private import device_telemetry
from ray_tpu.llm.config import GenerationConfig, LLMConfig
from ray_tpu.llm.engine import (
    _MAX_STOP_IDS,
    _MAX_TOP_K,
    _Request,
    _sample,
    _sample_dist,
    build_engine_mesh,
    pp_cache_spec,
    pp_param_specs,
)
from ray_tpu._private.prefix_hash import chain_hash, prefix_chain_hashes
from ray_tpu.models import llama
from ray_tpu.models.family import family_of
from ray_tpu.util import tracing


class BlockManager:
    """Host-side allocator + prefix cache over the device block pool.

    ``on_evict(block, chain_hash)`` fires when allocation pressure
    repurposes a hash-registered (cached) block, BEFORE its registration is
    dropped — the tier ladder's demotion hook: the engine copies the
    block's KV to the host-RAM tier while the pool still holds it."""

    def __init__(self, num_blocks: int, block_size: int,
                 prefix_caching: bool = True, on_evict=None):
        self.num_blocks = num_blocks
        self.bs = block_size
        self.prefix_caching = prefix_caching
        self.on_evict = on_evict
        # block 0 is the SINK: inactive decode slots' zero-padded table rows
        # make the device scatter land there, so it is never allocated —
        # a live request's data can never be corrupted by an idle slot.
        # TWO insertion-ordered free sets: plain (not hash-registered) and
        # cached (freed but revivable by match_prefix).  alloc drains plain
        # first, so prefix-cache entries are evicted only under real
        # pressure, oldest first — LRU-preserving allocation (the vLLM
        # free-list policy; without the split, pipelining's margin allocs
        # churned cached blocks while plain ones sat free).
        self.free_plain: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict((i, None) for i in range(1, num_blocks)))
        self.free_cached: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict())
        self.ref = [0] * num_blocks
        self.hash_of: Dict[int, int] = {}   # block -> chain hash
        self.by_hash: Dict[int, int] = {}   # chain hash -> block

    def num_free(self) -> int:
        return len(self.free_plain) + len(self.free_cached)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > self.num_free():
            return None
        if n > len(self.free_plain):
            # the plain free list cannot cover it: prefix-cache entries
            # are evicted (and demoted, one device read-back each)
            with tracing.region("kv.alloc", blocks=n):
                return self._alloc(n)
        return self._alloc(n)

    def _alloc(self, n: int) -> List[int]:
        out = []
        for _ in range(n):
            if self.free_plain:
                b, _ = self.free_plain.popitem(last=False)
            else:
                b, _ = self.free_cached.popitem(last=False)
            h = self.hash_of.pop(b, None)  # repurposed: stale cache entry out
            if h is not None and self.by_hash.get(h) == b:
                if self.on_evict is not None:
                    try:
                        self.on_evict(b, h)  # demote before the data is lost
                    except Exception:  # noqa: BLE001 — tiering is best-effort
                        pass
                del self.by_hash[h]
            self.ref[b] = 1
            out.append(b)
        return out

    def release(self, blocks: Sequence[int]):
        for b in blocks:
            self.ref[b] -= 1
            assert self.ref[b] >= 0, f"double free of block {b}"
            if self.ref[b] == 0:
                # still hash-registered blocks stay revivable by
                # match_prefix until allocation pressure evicts them
                if b in self.hash_of:
                    self.free_cached[b] = None
                else:
                    self.free_plain[b] = None

    def match_prefix(self, prompt: Sequence[int]) -> Tuple[List[int], int]:
        """Longest run of cached full blocks covering < len(prompt) tokens
        (the last token is always recomputed so sampling has a logit).
        Matched blocks are ref'd for the caller."""
        if not self.prefix_caching:
            return [], 0
        ids: List[int] = []
        h: Optional[int] = None
        limit = (len(prompt) - 1) // self.bs
        for i in range(limit):
            h = chain_hash(h, prompt[i * self.bs:(i + 1) * self.bs])
            b = self.by_hash.get(h)
            if b is None:
                break
            ids.append(b)
        for b in ids:
            if self.ref[b] == 0:
                self.free_cached.pop(b, None)  # revive a cached-free block
                self.free_plain.pop(b, None)
            self.ref[b] += 1
        return ids, len(ids) * self.bs

    def register(self, prompt: Sequence[int], blocks: Sequence[int]):
        """Register this sequence's full PROMPT blocks for future sharing."""
        if not self.prefix_caching:
            return
        h: Optional[int] = None
        for i in range(len(prompt) // self.bs):
            h = chain_hash(h, prompt[i * self.bs:(i + 1) * self.bs])
            b = blocks[i]
            if h not in self.by_hash and b not in self.hash_of:
                self.by_hash[h] = b
                self.hash_of[b] = h

    def adopt(self, block: int, h: int):
        """Register a chain hash for an already-allocated block (a tier
        revival: the caller just uploaded the cached KV into ``block``)."""
        if not self.prefix_caching:
            return
        if h not in self.by_hash and block not in self.hash_of:
            self.by_hash[h] = block
            self.hash_of[block] = h


# the reference/vLLM name for this role; the serve layer and ISSUE docs use
# it — one object, two names
BlockAllocator = BlockManager


class HostBlockCache:
    """Tiers 2+3 of the prefix-cache ladder: host-RAM LRU of full KV
    blocks keyed by chain hash, spilling to the plasma object store.

    HBM (tier 1) evictions demote here; ``get`` revives through host RAM
    first, then plasma (promoting the block back up).  Byte-capped LRU;
    plasma entries are ObjectRefs whose payloads live in the store (freed
    when the ref is dropped).  Thread-safe: the engine calls under its own
    lock, but the serve digest publisher reads concurrently."""

    def __init__(self, capacity_bytes: int, plasma_blocks: int = 0):
        self._cap = max(0, capacity_bytes)
        self._plasma_cap = max(0, plasma_blocks)
        self._entries: "collections.OrderedDict[int, Tuple]" = (
            collections.OrderedDict())  # hash -> the block's cache leaves
        self._bytes = 0
        self._plasma: "collections.OrderedDict[int, object]" = (
            collections.OrderedDict())  # hash -> ObjectRef
        self._lock = make_lock("HostBlockCache._lock")

    def __len__(self):
        with self._lock:
            return len(self._entries) + len(self._plasma)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def hashes(self) -> List[int]:
        with self._lock:
            return list(self._plasma) + list(self._entries)

    def put(self, h: int, *leaves):
        """Demote one block's cache leaves (a Llama block: k and v) into
        the host tier (LRU-evicting over the byte cap into plasma, or
        dropping when plasma is off/full)."""
        if self._cap <= 0:
            return
        from ray_tpu._private import runtime_metrics

        spill = []
        with self._lock:
            if h in self._entries:
                self._entries.move_to_end(h)
                return
            self._plasma.pop(h, None)  # promoted copy supersedes the spill
            self._entries[h] = leaves
            self._bytes += sum(x.nbytes for x in leaves)
            while self._bytes > self._cap and len(self._entries) > 1:
                eh, gone = self._entries.popitem(last=False)
                self._bytes -= sum(x.nbytes for x in gone)
                spill.append((eh, gone))
        for eh, gone in spill:
            runtime_metrics.add_prefix_cache_evictions("host")
            self._spill_to_plasma(eh, gone)

    def _spill_to_plasma(self, h: int, leaves):
        from ray_tpu._private import runtime_metrics

        if self._plasma_cap <= 0:
            return
        try:
            import ray_tpu

            if not ray_tpu.is_initialized():
                return
            ref = ray_tpu.put(tuple(leaves))
        except Exception:  # noqa: BLE001 — tiering is best-effort
            return
        with self._lock:
            self._plasma[h] = ref
            while len(self._plasma) > self._plasma_cap:
                self._plasma.popitem(last=False)
                runtime_metrics.add_prefix_cache_evictions("plasma")

    def get(self, h: int):
        """(*leaves, tier) for a cached block (a Llama block: k, v, tier),
        or None.  A plasma hit is promoted back into the host tier (it is
        about to be hot)."""
        with self._lock:
            got = self._entries.get(h)
            if got is not None:
                self._entries.move_to_end(h)
                return (*got, "host")
            ref = self._plasma.get(h)
        if ref is None:
            return None
        try:
            import ray_tpu

            leaves = ray_tpu.get(ref, timeout=5)
        except Exception:  # noqa: BLE001 — lost spill: treat as a miss
            with self._lock:
                self._plasma.pop(h, None)
            return None
        self.put(h, *leaves)
        return (*leaves, "plasma")


@dataclasses.dataclass
class _PagedReq(_Request):
    blocks: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0      # prompt tokens already in the pool
    admitted_order: int = 0   # preemption picks the youngest
    # request-lifecycle stamps (serving SLO layer; only read when the
    # engine carries an slo_label — direct engine use books nothing)
    t_submit: float = 0.0     # add_request entered (before the lock)
    t_enqueue: float = 0.0
    t_admit: float = 0.0      # FIRST admission (a preemption keeps it)
    t_prefill_end: float = 0.0  # final prompt chunk dispatched (first time)
    t_first_emit: float = 0.0
    t_done: float = 0.0
    prompt_tokens: int = 0    # as submitted (preemption grows .prompt)
    prefix_hit_tokens: int = 0
    prefill_chunks: int = 0
    preempted: int = 0
    # --- speculative decoding (engine._spec is not None) ---
    # draft-pool blocks mirroring this request's KV in the draft model's
    # pool; draft_prefill_pos tracks the draft's own chunked prefill
    # (a target prefix-cache hit does not help the draft — it recomputes
    # the matched region, cheap at draft size)
    draft_blocks: List[int] = dataclasses.field(default_factory=list)
    draft_prefill_pos: int = 0
    # False = this request decodes non-speculatively (draft-pool
    # exhaustion degrade, or a per-adapter opt-out) — zero drops
    spec_enabled: bool = False
    # acceptance bookkeeping (per-request speedup/acceptance metering)
    spec_proposed: int = 0
    spec_accepted: int = 0


# integer engine counters (cumulative; see PagedJaxLLMEngine.counters)
_COUNTERS = ("steps", "prefill_chunks", "prefill_kernel_chunks",
             "prefill_grouped_chunks", "prefill_tokens",
             "prefill_padded_tokens", "prefix_hit_tokens",
             "prefill_live_pages", "prefill_visited_pages",
             "decode_dispatches", "decode_dispatches_pipelined",
             "decode_token_steps", "decode_sampled_token_steps",
             "decode_topk_token_steps", "decode_table_pages",
             "decode_live_pages",
             "decode_rows", "decode_live_rows", "decode_joins",
             "tokens_emitted", "preemptions", "kv_demotions")
_TRACKED_MAX = 4096
# what the host hands ``_join_impl`` of one row, as one int32 vector:
# [slot, length, remaining, top_k, spec, stop ids...]
_JOIN_ROW = 5 + _MAX_STOP_IDS


def _bucket_pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _spec_accept(pdist, qdist, drafted, key):
    """Rejection-sampling core of speculative verification (traced).

    pdist [B, k+1, V]: target distributions at each window position;
    qdist [B, k, V]: the draft distributions that generated ``drafted``
    [B, k] (zeroed rows disable speculation for that slot — acceptance
    is forced off and the correction residual degenerates to the target
    distribution itself).  Returns ``(a [B], corr [B])``: the count of
    leading accepted proposals and the correction token sampled from
    ``normalize(max(p_a - q_a, 0))`` — which, with ``q`` zero-padded at
    index k, IS the bonus-token draw from ``p_k`` on full acceptance.

    The standard speculative-sampling guarantee holds position-wise: the
    emitted token at each position is distributed exactly as the target
    distribution (pinned empirically in tests/test_specdec.py).  Greedy
    rows (one-hot dists from engine._sample_dist) collapse to exact
    longest-agreeing-prefix verification with argmax corrections."""
    b, k = drafted.shape
    key, ku, kr = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (b, k))
    p_d = jnp.take_along_axis(pdist[:, :k], drafted[..., None], -1)[..., 0]
    q_d = jnp.take_along_axis(qdist, drafted[..., None], -1)[..., 0]
    # q_d > 0: a token the draft could not have drawn is never accepted
    # (a genuinely drafted token always has q_d > 0 — the categorical
    # cannot pick a zero-probability id — so this changes nothing on the
    # real path; it is what makes a ZEROED q row force a = 0, pinning
    # degraded slots' corrections to the position-0 target distribution)
    accept = (u * q_d < p_d) & (q_d > 0)
    a = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(1)  # [B] 0..k
    q_pad = jnp.concatenate(
        [qdist, jnp.zeros((b, 1, qdist.shape[-1]), qdist.dtype)], axis=1)
    p_a = jnp.take_along_axis(
        pdist, a[:, None, None].repeat(pdist.shape[-1], -1), 1)[:, 0]
    q_a = jnp.take_along_axis(
        q_pad, a[:, None, None].repeat(q_pad.shape[-1], -1), 1)[:, 0]
    resid = jnp.maximum(p_a - q_a, 0.0)
    resid = jnp.where(resid.sum(-1, keepdims=True) > 0, resid, p_a)
    # exact-zero residual entries get -inf weight (NOT log(x+eps): the
    # greedy one-hot path must have literally zero probability of
    # drawing a non-argmax token — the bit-parity pin)
    corr = jax.random.categorical(
        kr, jnp.where(resid > 0, jnp.log(resid), -jnp.inf),
        axis=-1).astype(jnp.int32)
    return a, corr


def _prefill_plan(plen: int, matched: int, chunk: int, bs: int):
    """Simulate the chunked-prefill loop: chunk widths are POW2-BUCKETED
    multiples of block_size (so the jit cache holds log2(chunk/bs) prefill
    programs per table bucket, not one per prefix-cache offset — an
    arbitrary-width chunk measured a 7.2 s XLA compile inside the serving
    window).  Returns the max block index any chunk's table must cover."""
    pos, cover = matched, matched // bs
    while pos < plen:
        rem = plen - pos
        c = min(chunk, _bucket_pow2(_pad_to(rem, bs), lo=bs))
        cover = max(cover, math.ceil((pos + c) / bs))
        pos += min(c, rem)
    return cover


def _prefill_cover_worst(plen: int, chunk: int, bs: int) -> int:
    """Max block index any prefill chunk of a ``plen``-token prompt can
    touch, over every possible prefix-cache offset.  Intermediate chunks
    never reach past plen; only the FINAL chunk's pow2 bucket overshoots,
    and a prefix hit merely shifts its start to another block boundary —
    so scanning block-aligned final-chunk starts bounds it exactly."""
    worst = 0
    lo = max(0, plen - chunk)
    start = ((lo + bs - 1) // bs) * bs
    for pos in range(start, plen, bs):
        c = min(chunk, _bucket_pow2(_pad_to(plen - pos, bs), lo=bs))
        worst = max(worst, math.ceil((pos + c) / bs))
    return worst


def _prefill_table_width(max_seq: int, chunk: int, bs: int) -> int:
    """True worst-case prefill table width: 1 (decode spare, reserved at
    admission) + the max block index any chunk dispatch can touch.

    ``max_blocks_per_seq + 2`` was NOT an upper bound: the final chunk's
    pow2 bucket can overshoot the prompt by up to ~chunk/2 tokens (e.g.
    max_seq=992, bs=16, chunk=256, plen=897 → the pos=768 chunk buckets
    to 256 wide and covers 1024 tokens = 65 blocks, past
    bucket_pow2(62+2)=64 — a broadcast ValueError mid-serve).  Only the
    last ~2*chunk prompt lengths can attain the max (any shorter plen
    covers ≤ plen + chunk, below the plen=max_seq floor), keeping the
    scan O(chunk²/bs) at engine init."""
    return 1 + max(
        _prefill_cover_worst(plen, chunk, bs)
        for plen in range(max(1, max_seq - 2 * chunk), max_seq + 1))


class PagedJaxLLMEngine:
    """The serving engine: continuous batching over a paged KV pool.

    API: ``add_request() -> id``, ``step() -> {id: [new tokens]}``,
    ``generate()`` (sync convenience driving step() to completion).

    With ``config.speculative_config`` set, decode runs draft-model
    speculative: a small draft proposes k tokens per slot per step and
    the target verifies all k in ONE forward window (rejection sampling
    at temperature > 0; exact longest-agreeing-prefix at temperature 0 —
    greedy output is bit-identical to non-speculative decode).  The
    draft's KV lives in its own block pool under the same BlockManager
    machinery; draft-pool exhaustion degrades the affected request to
    plain decode (zero drops).
    """

    def __init__(self, config: LLMConfig, params=None, *, key=None,
                 draft_params=None):
        self.config = config
        cfg = config.model_config
        if cfg is None:
            raise ValueError("LLMConfig.model_config is required")
        self.cfg = cfg
        # everything architecture-specific comes through the family seam
        fam = self.family = family_of(cfg)
        if fam.param_specs is None and (
                config.tensor_parallel_size > 1
                or config.pipeline_parallel_size > 1):
            raise ValueError(
                f"the {fam.name} family supplies no tensor- or "
                "pipeline-parallel layout: tensor_parallel_size and "
                "pipeline_parallel_size must be 1")
        if fam.decode_window is None and config.speculative_config is not None:
            raise ValueError(
                f"the {fam.name} family supplies no decode window: "
                "speculative_config is not supported")
        self.max_batch = config.max_batch_size
        self.max_seq = config.max_seq_len or cfg.max_seq_len
        self.bs = config.block_size
        if config.decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1 (got {config.decode_chunk})")
        if config.prefill_chunk % self.bs:
            raise ValueError(
                f"prefill_chunk ({config.prefill_chunk}) must be a multiple "
                f"of block_size ({self.bs})")
        nb = config.num_blocks
        if nb is None:
            # default pool: max_batch x max_seq / 2 positions (half of
            # every slot at full length); override via config.num_blocks
            nb = max(4, (self.max_batch * self.max_seq) // (2 * self.bs))
        self.num_blocks = nb
        self.max_blocks_per_seq = math.ceil(self.max_seq / self.bs)
        # FIXED prefill table width: per-request widths would key a jit
        # program per (chunk, width) combo, and prefix-cache hits reach
        # widths no warmup predicted — measured as multi-second XLA
        # compiles inside the serving window.  One width = at most
        # log2(prefill_chunk/bs) prefill programs, all warmed at init.
        # The width costs nothing past the live prefix: the chunk's
        # attention loops over the table's first cdiv(p0 + C, tile) KV
        # tiles (the family's prefill_chunk) and never reads the rest.
        # Width = the simulated worst case over every prompt length and
        # chunk start (see _prefill_table_width) — pow2 chunk bucketing
        # can cover past max_blocks_per_seq + 2.
        self._prefill_w = _prefill_table_width(
            self.max_seq, config.prefill_chunk, self.bs)
        # tier ladder under the HBM chain-hash pool: HBM evictions demote
        # full prompt blocks to host RAM (and optionally plasma); a later
        # prefix match revives them by pool upload instead of recompute
        self._host_cache: Optional[HostBlockCache] = None
        # a family with a slot state (models/family.py) gets no prefix hit,
        # whatever the option says: a block's keys and values can be shared,
        # but the recurrent state after that block was never kept
        prefix_matching = (config.enable_prefix_caching
                           and fam.init_slot_state is None)
        if prefix_matching and config.host_kv_cache_bytes > 0:
            self._host_cache = HostBlockCache(
                config.host_kv_cache_bytes, config.plasma_kv_cache_blocks)
        self.blocks = BlockManager(
            nb, self.bs, prefix_matching,
            on_evict=(self._demote_block if self._host_cache is not None
                      else None))

        self._rope = fam.rope_cache(cfg, self.max_seq)

        pp = config.pipeline_parallel_size
        self.mesh = build_engine_mesh(cfg, config.tensor_parallel_size, pp,
                                      mesh=config.mesh)
        pkey = key if key is not None else jax.random.PRNGKey(0)
        self._rep = None  # replicated sharding over the mesh, if any
        decode_out = prefill_out = None  # out_shardings: jit's default
        # the state a SLOT holds, {leaf: [layers, max_batch, ...]} ({}: the
        # family's only state is the pool; under a mesh too, where such a
        # family has no param_specs, as the refusal above has it)
        self.slot_state: Dict[str, jnp.ndarray] = (
            fam.init_slot_state(cfg, self.max_batch)
            if fam.init_slot_state else {})
        if self.mesh is None:
            self.params = (params if params is not None
                           else fam.init_params(cfg, pkey))
            self.pool = fam.init_paged_cache(cfg, nb, self.bs)
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            from ray_tpu.parallel.mesh import shard_pytree

            pspecs = pp_param_specs(fam.param_specs(cfg), pp)
            if params is not None:
                self.params = shard_pytree(params, pspecs, self.mesh)
            else:
                # random weights are BORN sharded: a model that needs the
                # mesh to fit (8 B at full depth on four 16 GB chips) can
                # never be materialized on one device first.  Same values
                # as the unsharded init (partitionable threefry).
                self.params = jax.jit(
                    lambda k: fam.init_params(cfg, k),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(self.mesh, s), pspecs))(pkey)
            # the paged pool shards on the folded kv-head dim, matching
            # wk/wv's column sharding: each rank's cache scatter/gather
            # touches only its own head group — no resharding anywhere in
            # the decode dataflow.  The block table, BlockManager,
            # admission, prefix cache, and scheduling all stay host-side
            # and replicated: one logical engine over N devices.  Born
            # sharded like the weights: a pool sized to fill N chips does
            # not fit the first one.
            pool_sh = {k: NamedSharding(self.mesh, s) for k, s in
                       pp_cache_spec(fam.paged_cache_spec(), pp).items()}
            self.pool = jax.jit(
                lambda: fam.init_paged_cache(cfg, nb, self.bs),
                out_shardings=pool_sh)()
            # Every small array a program takes or returns is COMMITTED,
            # replicated over the mesh: what the host uploads (_put) and
            # what a program hands to the next one (out_shardings) then
            # key the SAME executable.  Left to default placement, an
            # uploaded array and a fed-back one keyed different ones, so
            # warmup() compiled programs serving never ran and serving
            # compiled its own inside the request path.
            self._rep = NamedSharding(self.mesh, PartitionSpec())
            rep = self._rep
            decode_out = (rep, rep, pool_sh, rep, rep, rep, rep, {})
            prefill_out = (rep, pool_sh, rep, {})
        # --- planner-routed TP collectives (tentpole, ISSUE 20) ---------
        # decode's per-layer allreduces are KiB-scale and latency-bound —
        # the α-β planner's flat/tree regime.  Plan once per program kind
        # at init (message sizes are static: B and chunk geometry are
        # compile-time), route the chosen algorithm into the jitted
        # programs as explicit shard_map collectives, and meter the
        # decision.  PP keeps GSPMD's implicit path (the layer scan spans
        # stages; an explicit island per stage boundary buys nothing).
        self._tp_plan = None          # llama.TPPlan for decode chunks
        self._tp_verify_plan = None   # ... for the spec-verify window
        self._tp_prefill_plan = None  # ... for prefill chunks
        self._tp_collectives = None   # {kind: plan_explain row} (bench)
        if (self.mesh is not None and config.tensor_parallel_size > 1
                and pp <= 1 and config.tp_planned_collectives):
            self._init_tp_planning()

        # host slot state
        self._slot_req: List[Optional[_PagedReq]] = [None] * self.max_batch
        self._lengths = np.zeros(self.max_batch, np.int32)
        self._next_tok = np.zeros(self.max_batch, np.int32)
        self._slot_temp = np.zeros(self.max_batch, np.float32)
        self._slot_topk = np.zeros(self.max_batch, np.int32)
        # the decode program's row state lives on the device (the mirrors
        # below, uploaded whole at the end of __init__).  A row enters
        # them, and a finished one is cleared, by a program queued in order
        # (_join_locked); only the rare events that change a row behind
        # the device's back (preempt, cancel, import, export) mark them
        # dirty, which drains the chunk in flight and uploads them anew
        self._dirty = False
        self._d_next = self._d_lengths = self._d_active = None
        self._d_temp = self._d_topk = None
        self._d_remaining = self._d_stops = None
        self._d_key = self._put(jax.random.PRNGKey(cfg.vocab_size + 1))
        self._pending: "collections.deque[_PagedReq]" = collections.deque()
        self._requests: Dict[int, _PagedReq] = {}
        self._req_counter = 0
        self._admit_counter = 0
        self._lock = make_lock("PagedJaxLLMEngine._lock")
        # serving SLO layer: the hosting deployment's name, set via the
        # replica's set_slo_label threading (serve/_private/replica.py).
        # None (direct engine use) books no lifecycle stages at all.
        # Assigning a name also attaches device telemetry (slo_label is a
        # property) — the disabled path is self._telemetry staying None.
        self._slo_label: Optional[str] = None
        self._telemetry: Optional[device_telemetry.EngineTelemetry] = None
        # chunked-prefill budget spend, tracked per step for telemetry
        self._tel_prefill_budget = (config.prefill_token_budget
                                    or config.prefill_chunk)
        self._tel_prefill_spent = 0
        # one decode chunk may stay IN FLIGHT while the host books the
        # previous chunk's tokens: the readback of chunk N overlaps chunk
        # N+1's device compute, hiding the dispatch+fence round trip
        # (its size on a directly attached chip: not measured).
        # (em_dev, active_slots, spec_slots): collected lazily by
        # _drain_locked(); spec_slots is () on the non-speculative path.
        self._inflight: Optional[Tuple] = None
        # cumulative engine counters (utilization()["counters"], and the
        # replica's ledger row under "engine"): plain numbers, written
        # under self._lock (loop_idle_s: by the server's loop thread
        # alone), read without it — a reader subtracts two reads
        self._c: Dict[str, float] = dict.fromkeys(
            _COUNTERS + fam.decode_counters, 0)
        self._c.update(host_s=0.0, device_wait_s=0.0, loop_idle_s=0.0,
                       device_empty_s=0.0)
        # device_empty_s: when the host learned that nothing is queued on
        # the device (None: something is, or may be), and how many decode
        # chunks had been dispatched when the last prompt chunk was (a
        # drain proves that chunk done only by reading a later one)
        self._empty_since: Optional[float] = time.monotonic()
        self._chunk_mark = -1
        self._drains: Dict[str, int] = {}
        # why the device mirrors went stale (first cause since the last
        # refresh): names the drain that the refresh forces
        self._dirty_cause: Optional[str] = None
        # seconds this step spent blocked in device reads (collect /
        # first-token reads); reset at step entry
        self._step_wait = 0.0
        # backend compiles the process had seen when warmup() returned
        # (or at init, where it never runs): counters report the excess
        self._compile_base = device_telemetry.compile_totals()
        # rid -> request, for the serving layer's per-request stage row
        # (LLMServer reads t_first_emit at the first yield and pops the
        # row at the end); only filled under an slo_label, bounded
        self._tracked: Dict[int, _PagedReq] = {}
        # a final prompt chunk's sampled first token stays a DEVICE future
        # until the end of the step that dispatched the chunk: the join
        # hands it to the decode mirrors on the device, the step's decode
        # chunk is queued behind it, and only then does the host read it
        # (_resolve_first_tokens_locked).  What a synchronous int(ids[0])
        # at the dispatch would cost: not measured.
        # (slot, req, ids_future) tuples.
        self._first_pending: List[Tuple[int, _PagedReq, jnp.ndarray]] = []

        # fused pallas paged-attention kernel (ray_tpu/ops/paged_attention):
        # DMAs only the decoding slots' live pages — no gather
        # materialization, no work for idle slots or table padding (the
        # decode chunk hands it the scan carry's `active`).  Default ON
        # where supported (its record: the kernel module's docstring).
        # Composes with TP via shard_map (kv heads over "tensor"); PP still
        # uses the gather path (the layer scan spans all stages, so a
        # pipeline-sharded pool cannot feed per-shard page DMAs).
        self._kernel_interpret = False
        supported = (fam.kernel_supported(cfg)
                     and config.pipeline_parallel_size <= 1)
        want = config.paged_attention_kernel
        if want is None:
            self._use_kernel = supported
        elif want == "interpret":
            # explicit test hook: run the kernel in pallas interpret mode
            # off-TPU (exercises the TP shard_map plumbing on the virtual
            # CPU mesh).  Never chosen implicitly — interpret speed would
            # be a silent production footgun.
            if config.pipeline_parallel_size > 1:
                raise ValueError(
                    "paged_attention_kernel needs pipeline_parallel_size == 1")
            self._use_kernel = True
            self._kernel_interpret = jax.default_backend() != "tpu"
        elif want and not supported:
            raise ValueError(
                f"paged_attention_kernel=True: the {fam.name} family's "
                "decode kernel does not apply here (its kernel_supported "
                "wants a TPU backend and the family's own shape rules) or "
                "pipeline_parallel_size > 1")
        else:
            self._use_kernel = bool(want)
        # whether a prefill chunk's attention runs in a kernel of the
        # family's too (counter prefill_kernel_chunks)
        self._prefill_kernel = bool(
            self._use_kernel and fam.prefill_kernel_fits is not None
            and fam.prefill_kernel_fits(cfg))
        # the chunk width from which on the family's expert layers run as a
        # grouped product (counter prefill_grouped_chunks); None: never
        self._prefill_grouped_from = (
            fam.prefill_grouped_from(cfg, self._kernel_interpret)
            if fam.prefill_grouped_from else None)
        # the pool and the slot state are donated and recaptured
        self._decode = jax.jit(self._decode_chunk_impl,
                               donate_argnums=(2, 12), static_argnums=11,
                               out_shardings=decode_out)
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                      donate_argnums=(2, 9),
                                      out_shardings=prefill_out)
        # one row into (or out of) the decode mirrors; every output placed
        # as the decode program's are, so each feeds the other's executable
        self._join = jax.jit(self._join_impl, out_shardings=self._rep)
        # what a row that leaves is given for a first token
        self._no_ids = self._put(np.zeros(1, np.int32))
        # the pool's leaves, in the order the host tier and the handoff
        # carry them (a Llama pool: k, v)
        self.cache_leaves: Tuple[str, ...] = tuple(sorted(self.pool))

        def scatter_blocks(pool, idx, blocks):
            return {n: pool[n].at[:, idx].set(blocks[n]) for n in pool}

        # a handed-off sequence's slot state into its new slot (one index
        # along the leaves' second axis, as a block is one along the pool's)
        self._import_slot = jax.jit(scatter_blocks, donate_argnums=0)

        # tier revival: scatter one host-cached block back into the pool
        # (fixed shapes -> exactly one compile)
        self._upload_block = jax.jit(scatter_blocks, donate_argnums=0)
        # disaggregated handoff import: scatter a request's blocks (padded
        # to a pow2 count; pad rows land in sink block 0) into the pool
        self._import_blocks = jax.jit(scatter_blocks, donate_argnums=0)

        # --- draft-model speculative decoding ---------------------------
        # The disabled path (speculative_config=None) stops HERE: no draft
        # pool, no extra programs, and step() pays one `is None` test.
        self.warmup_report: Optional[dict] = None  # set by warmup()
        self._spec = config.speculative_config
        self._spec_k = 0
        self._draft_params = None
        if self._spec is not None:
            dcfg = self._spec.draft_model_config
            if dcfg is None:
                raise ValueError(
                    "speculative_config.draft_model_config is required")
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {dcfg.vocab_size} != target "
                    f"{cfg.vocab_size} — verification compares token ids")
            k = int(self._spec.num_speculative_tokens)
            if k < 1:
                raise ValueError(
                    f"num_speculative_tokens must be >= 1 (got {k})")
            self._spec_k = k
            self._draft_cfg = dcfg
            dfam = self._draft_family = family_of(dcfg)
            if draft_params is None:
                draft_params = dfam.init_params(
                    dcfg, key or jax.random.PRNGKey(1))
            self._draft_params = draft_params
            self._draft_rope = dfam.rope_cache(dcfg, self.max_seq)
            dnb = self._spec.draft_num_blocks or nb
            self._draft_num_blocks = dnb
            # no prefix caching in the draft pool: draft KV is never
            # shared across requests (recompute at draft size is cheap,
            # and chain bookkeeping would double the admission work)
            self.draft_blocks = BlockManager(dnb, self.bs,
                                             prefix_caching=False)
            self._draft_pool = dfam.init_paged_cache(dcfg, dnb, self.bs)
            if self.mesh is not None:
                from jax.sharding import PartitionSpec as P

                from ray_tpu.parallel.mesh import shard_pytree

                # the draft stays single-chip: REPLICATE its params and
                # pool over the mesh (each device runs the tiny draft
                # redundantly).  Draft messages are so small that
                # allreduce α would dominate any sharding win — zero
                # collectives in every draft program, while the target's
                # verify window runs fully sharded.
                rep = jax.tree_util.tree_map(
                    lambda _: P(), dfam.param_specs(dcfg),
                    is_leaf=lambda x: isinstance(x, P))
                self._draft_params = shard_pytree(
                    self._draft_params, rep, self.mesh)
                self._draft_pool = shard_pytree(
                    self._draft_pool,
                    {n: P() for n in self._draft_pool}, self.mesh)
            self._d_spec = None  # device mirror of per-slot spec enable
            # draft chunked prefill: same chunk/table geometry as the
            # target (block_size is shared, so the fixed width carries)
            self._draft_prefill = jax.jit(
                lambda p, tok, pool, tab, p0: dfam.prefill_chunk(
                    self._draft_cfg, p, tok, pool, tab, p0,
                    rope_cache=self._draft_rope)[1],
                donate_argnums=2)
            self._draft_propose = jax.jit(self._draft_propose_impl,
                                          donate_argnums=2)
            self._spec_verify = jax.jit(self._spec_verify_impl,
                                        donate_argnums=4)
            # engine-lifetime acceptance totals (bench / specdec_stats)
            self._spec_proposed_total = 0
            self._spec_accepted_total = 0
            # finished requests' (proposed, accepted) for the serving
            # layer's per-request acceptance rows (bounded)
            self._spec_finished: "collections.OrderedDict[int, Tuple[int, int]]" = (
                collections.OrderedDict())
        self._upload_mirrors_locked()  # all rows empty: there is no other thread yet

    def _put(self, x, dtype=None):
        """Host value -> device array for a program argument; under a mesh
        committed and replicated (see ``_rep``)."""
        x = np.asarray(x, dtype)
        return jnp.asarray(x) if self._rep is None else jax.device_put(
            x, self._rep)

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
        # per-DEVICE bytes, not logical: a tp=N pool puts 1/N of its bytes
        # on each chip (the draft pool is replicated — full size per
        # device).  tree_nbytes of a sharded array counts GLOBAL bytes;
        # feeding that into hbm_split over-reported each device's
        # engine-owned HBM by N× on sharded replicas, making chip
        # telemetry and the disagg router's free-HBM digests lie.
        kv_bytes = device_telemetry.tree_nbytes_per_device(self.pool)
        # engine-owned state, like the pool
        kv_bytes += device_telemetry.tree_nbytes_per_device(self.slot_state)
        if self._spec is not None:
            kv_bytes += device_telemetry.tree_nbytes_per_device(
                self._draft_pool)
        self._telemetry = device_telemetry.engine_telemetry_for(
            name,
            weights_bytes=device_telemetry.tree_nbytes_per_device(
                self.params),
            kv_pool_bytes=kv_bytes)
        if self._telemetry is not None:
            # local-mode / engine-direct utilization surface; serve
            # replicas additionally publish rows to the GCS KV
            device_telemetry.register_utilization_object(
                f"{name}:{id(self):x}", self)
        # cumulative counters ride the replica's ledger row ("engine")
        from ray_tpu.serve._private import slo

        slo.register_engine(name, self)

    def utilization(self) -> dict:
        """Exact engine bookkeeping for ``state.utilization()``: slot and
        KV-block occupancy read from the live structures under the lock,
        plus the step-derived rates and HBM split when telemetry is
        attached, and the cumulative ``counters``.  Block 0 is the sink
        (never allocated), so capacity is ``num_blocks - 1``.
        ``duty_cycle`` is the engine LOOP's occupancy of host wall time
        (time inside ``step()`` over time since the last step ended),
        not a device figure: for the device read ``counters``'
        ``device_wait_s`` against ``host_s``, or a ``state.jax_profile``
        trace."""
        # a step holds the lock for its whole body: a publisher or a
        # dashboard waits here, on a thread of its own
        with tracing.region("serve.utilization"), self._lock:
            active = sum(1 for r in self._slot_req if r is not None)
            free = self.blocks.num_free()
            cached = len(self.blocks.free_cached)
            pending = len(self._pending)
        total = self.num_blocks - 1
        row = {
            "engine": "paged",
            "deployment": self._slo_label,
            "slots": {"active": active, "max": self.max_batch,
                      "free": self.max_batch - active},
            # cached: free blocks that still hold a registered prefix (a
            # later match revives them; allocation pressure evicts them)
            "kv_blocks": {"total": total, "free": free,
                          "used": total - free, "cached": cached},
            "pending": pending,
            "counters": self.counters(),
        }
        if self.slot_state:
            # what does not page: one fixed-size state a slot, and no prefix
            # hit for its family whatever enable_prefix_caching says
            row["slot_state"] = {
                "slots": self.max_batch,
                "bytes": device_telemetry.tree_nbytes_per_device(
                    self.slot_state),
                "prefix_matching": self.blocks.prefix_caching}
        tel = self._telemetry
        if tel is not None:
            rates = tel.rates()
            row["duty_cycle"] = rates["duty_cycle"]
            row["rates"] = rates
            row["hbm"] = tel.hbm_split()
        if self.mesh is not None:
            # mesh-aware view: KV/weights bytes PER DEVICE (the pool
            # shards its kv-head dim over "tensor"), plus the planned
            # collective decisions — what the disagg digests read
            row["tp"] = {
                "degree": self.config.tensor_parallel_size,
                "pipeline": self.config.pipeline_parallel_size,
                "mesh_devices": int(np.asarray(self.mesh.devices).size),
                "mesh_shape": {k: int(v)
                               for k, v in dict(self.mesh.shape).items()
                               if int(v) > 1},
                "kv_bytes_per_device":
                    device_telemetry.tree_nbytes_per_device(self.pool),
                "weights_bytes_per_device":
                    device_telemetry.tree_nbytes_per_device(self.params),
                "planned_collectives": self._tp_collectives,
            }
        return row

    def counters(self) -> dict:
        """Cumulative counters since the engine was built; nothing ever
        decreases, so two reads subtract to a window.  Takes no lock (the
        ledger's publisher calls it): a read beside a running step may
        be one step behind in some keys.

        ``steps``; ``prefill_chunks``, ``prefill_kernel_chunks`` (those of
        them whose attention ran in the family's prefill kernel),
        ``prefill_grouped_chunks`` (those wide enough that the family's
        expert layers multiplied a token by the experts it chose alone; 0
        for a family without expert layers),
        ``prefill_tokens`` (prompt tokens
        run through the model, recompute after a preemption included),
        ``prefill_padded_tokens`` (bucket padding run but not asked
        for), ``prefix_hit_tokens`` (prompt tokens admission found in the
        cache), ``prefill_live_pages`` / ``prefill_visited_pages`` (per
        chunk dispatch: the blocks that hold the prompt through this chunk,
        and the blocks of the whole KV tiles the chunk's attention loop
        visits: what the tile's rounding and the chunk's padding add);
        ``decode_dispatches``, ``decode_dispatches_pipelined``
        (the previous chunk was still in flight at the dispatch, so the
        device never waited for the host), ``decode_token_steps``,
        ``decode_sampled_token_steps`` / ``decode_topk_token_steps`` (the
        token-steps dispatched while some decoding row's request has a
        temperature above 0, and those where such a row asks for top-k
        too: over ``decode_token_steps``, how often the sampler's draw
        and its top-k ran at all, ``engine._sampler_gates``);
        ``decode_table_pages`` / ``decode_live_pages`` (per dispatch: the
        padded block table handed to the decode program, ``max_batch`` x
        its bucketed width, and the blocks the decoding slots really hold:
        the share of that table a kernel that follows the live pages
        touches); ``decode_rows`` / ``decode_live_rows`` (per dispatch, times
        its token-steps: ``max_batch`` rows the decode program runs over, and
        the rows that decode: the share a kernel that follows the decoding
        rows, as the state-space update does, moves bytes for);
        ``decode_joins`` (rows that entered the decode batch through the
        device join, ``_join_locked``: no drain, no upload);
        ``tokens_emitted``; ``drains`` by cause (an in-flight chunk
        collected before the next dispatch could be queued behind it:
        ``preempt``, ``cancel``, ``import``, ``export``, ``flush``,
        ``idle``; a final prompt chunk and a finish cause none);
        ``preemptions``, ``kv_demotions``; ``host_s`` / ``device_wait_s``
        (each step's wall time: blocked in the device reads of
        ``engine.collect`` / ``engine.drain``, and everything else);
        ``loop_idle_s`` (the serving loop found no work);
        ``device_empty_s`` (the idle share by the engine's own account, with
        no capture: seconds from the moment the host LEARNED that nothing is
        queued on the device, a drain whose blocking reads left no chunk in
        flight and no prompt chunk behind them, to the next dispatch of a
        program, a decode chunk that finds none in flight or a prompt chunk,
        counted only while a request is live; a steady pipelined step books
        nothing, and a device that idles behind a chunk the host has not
        read yet is not seen: a trace sees that); ``compiles`` /
        ``compile_s`` (backend compiles in this process since
        ``warmup()`` returned: anything above zero ran inside serving);
        and the family's ``decode_counters``, booked by the decode program
        itself a token-step (the expert family: ``moe_experts_held``,
        ``moe_experts_hit``, ``moe_pairs_here``, ``moe_grouped_calls``, see
        models/pangu_moe.py).
        """
        out = dict(self._c)
        out["drains"] = dict(self._drains)
        n, sec = device_telemetry.compile_totals()
        out["compiles"] = n - self._compile_base[0]
        out["compile_s"] = round(sec - self._compile_base[1], 6)
        return out

    def note_loop_idle(self, seconds: float) -> None:
        """The serving loop (its only caller and this key's only writer)
        waited ``seconds`` because no engine had work."""
        self._c["loop_idle_s"] += seconds

    def tracked_request(self, request_id: int) -> Optional["_PagedReq"]:
        """The request's stamps for the serving layer (None: unlabeled
        engine, unknown id, or already popped).  No lock: the caller
        reads fields the engine has finished writing (``t_first_emit``
        once a token of the request is visible)."""
        return self._tracked.get(request_id)

    def pop_request_row(self, request_id: int) -> Optional[dict]:
        """Stage times and counts of one FINISHED request, engine side;
        the entry is dropped (None if it was never tracked, or is not
        finished: an aborted stream leaves no row)."""
        req = self._tracked.pop(request_id, None)
        if req is None or not req.t_done or not req.t_first_emit:
            return None
        return {
            "engine_rid": req.request_id,
            "prompt_tokens": req.prompt_tokens,
            "prefix_hit_tokens": req.prefix_hit_tokens,
            "prefill_chunks": req.prefill_chunks,
            "enqueue_wait_s": round(req.t_enqueue - req.t_submit, 6),
            "queue_wait_s": round(req.t_admit - req.t_enqueue, 6),
            "prefill_s": round(req.t_prefill_end - req.t_admit, 6),
            "first_emit_s": round(req.t_first_emit - req.t_prefill_end, 6),
            "decode_s": round(req.t_done - req.t_first_emit, 6),
            "decode_tokens": len(req.out_tokens),
            "preempted": req.preempted,
        }

    # -- planner-routed TP collectives ---------------------------------

    def _init_tp_planning(self):
        """Plan the per-layer decode/verify/prefill allreduces through the
        PR 10 α-β planner and stash per-kind :class:`llama.TPPlan` routing
        for the jitted programs.

        Message sizes are compile-time constants (every dispatch pads to
        ``max_batch`` and the chunk geometry is fixed), so one decision
        per kind covers steady state: zero plan lookups in the hot loop.
        Each decision is metered into ``ray_tpu_collective_plan_total``
        (algorithm + reason — flat/tree's "latency_bound" is decode's
        regime) and the full ``plan_explain`` row is kept for
        ``utilization()``."""
        from ray_tpu.util.collective import planner as _planner
        from ray_tpu.util.collective.compression import CompressionSpec

        config, cfg = self.config, self.cfg
        axes = list(self.mesh.axis_names)
        dev_arr = np.asarray(self.mesh.devices)
        index = [0] * dev_arr.ndim
        index[axes.index("tensor")] = slice(None)
        tdevs = dev_arr[tuple(index)].ravel().tolist()
        topo = _planner.topology_for_devices(tdevs)
        # scheme "none" + hierarchical None = algorithm-only planning (no
        # quantization codec); min_bytes 0 because decode messages are
        # KiB-scale — the 64 KiB training default would force everything
        # stock before the cost model ever ran
        spec = CompressionSpec(scheme="none", min_bytes=0)
        allowed = ("flat", "ring", "tree")
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        k = (int(config.speculative_config.num_speculative_tokens)
             if config.speculative_config is not None else 0)
        # the reduced payload is the [*, dim] partial-sum output of the
        # attn/FFN projections, in compute dtype
        kinds = {"decode": self.max_batch * cfg.dim * itemsize,
                 "prefill": config.prefill_chunk * cfg.dim * itemsize}
        if k:
            kinds["verify"] = self.max_batch * (k + 1) * cfg.dim * itemsize
        forced = config.tp_collective_algorithm
        rows = {}
        plans = {}
        for kind, nbytes in kinds.items():
            row = _planner.plan_explain(nbytes, topo, spec, allowed=allowed)
            if forced is not None:
                row = dict(row, chosen=forced, reason="forced")
            _planner.record_plan(row["chosen"], row["reason"])
            rows[kind] = row
            plans[kind] = llama.TPPlan(
                mesh=self.mesh, algorithm=row["chosen"],
                overlap=config.tp_overlap_collectives)
        self._tp_collectives = rows
        self._tp_plan = plans["decode"]
        self._tp_prefill_plan = plans["prefill"]
        self._tp_verify_plan = plans.get("verify")

    def _book_tp_collectives(self, kind: str, programs: int = 1,
                             nbytes_each: Optional[int] = None):
        """Meter one dispatch's planned TP collectives: 2 allreduces per
        layer per program (attn-out + FFN-down), bytes exact from the
        message size (``nbytes_each`` overrides the planned size for
        short prefill chunks), seconds from the α-β model (a modeled
        attribution — per-collective device timing isn't observable from
        the host without fencing the async dispatch pipeline).  The
        unsharded / planning-disabled path books NOTHING."""
        rows = self._tp_collectives
        row = rows.get(kind) if rows is not None else None
        if row is None:
            return
        from ray_tpu._private import runtime_metrics

        n = 2 * self.cfg.n_layers * programs
        cost = row["modeled_cost_s"].get(row["chosen"]) or 0.0
        runtime_metrics.observe_tp_collective(
            self.slo_label or "engine", row["chosen"], seconds=n * cost,
            nbytes=n * (nbytes_each if nbytes_each is not None
                        else row["nbytes"]))

    # -- jitted programs ------------------------------------------------

    def _decode_chunk_impl(self, params, tokens, pool, table, lengths, active,
                           remaining, stops, key, temps, top_ks, n_steps,
                           state):
        """Multi-step paged decode (the host guarantees every active slot's
        table covers lengths + n_steps tokens of appends).  ``state``: the
        family's slot state ({}: it has none), carried through the
        token-steps beside the pool and returned last.  First of what it
        returns: the emitted tokens ``[n_steps, B]``, or where the family
        books ``decode_counters`` the pair of them and the counters of every
        token-step ``[n_steps, len(decode_counters)]`` (``_collect_locked``
        tells them apart: the programs' result names are pinned)."""

        def one(carry, _):
            tokens, pool, lengths, active, remaining, key, state = carry
            logits, pool, state, booked = self.family.decode_step(
                self.cfg, params, tokens, pool, table, lengths,
                rope_cache=self._rope, use_kernel=self._use_kernel,
                mesh=self.mesh, kernel_interpret=self._kernel_interpret,
                tp_plan=self._tp_plan, active=active, slot_state=state)
            key, sub = jax.random.split(key)
            # a row that ended, in this chunk or before it, asks nothing of
            # the sampler whatever its mirrors still hold
            ids = _sample(logits, sub, temps, top_ks, active)
            emitted = jnp.where(active > 0, ids, -1)
            lengths = lengths + active
            remaining = remaining - active
            hit_stop = (stops == ids[:, None]).any(-1)
            done = (active > 0) & (hit_stop | (remaining <= 0)
                                   | (lengths + 1 >= self.max_seq))
            active = active * (1 - done.astype(active.dtype))
            tokens = jnp.where(active > 0, ids, tokens)
            carry = (tokens, pool, lengths, active, remaining, key, state)
            return carry, (emitted, booked)

        carry = (tokens, pool, lengths, active, remaining, key, state)
        carry, (emitted, booked) = jax.lax.scan(one, carry, None,
                                                length=n_steps)
        tokens, pool, lengths, active, remaining, key, state = carry
        return (emitted if booked is None else (emitted, booked), tokens,
                pool, lengths, active, remaining, key, state)

    def _prefill_chunk_impl(self, params, tokens, pool, table, p0,
                            sample_idx, key, temp, top_k, state, where):
        """One chunk; also samples the token at chunk-local position
        ``sample_idx`` (the caller uses it only on the final chunk).
        ``state`` / ``where``: the family's slot state and the chunk's
        ``(slot, real tokens)`` (``_slot_args``; {} and Nones: the family
        has none); the state is returned last."""
        slot, take = where
        logits, pool, state = self.family.prefill_chunk(
            self.cfg, params, tokens, pool, table, p0, rope_cache=self._rope,
            tp_plan=self._tp_prefill_plan, use_kernel=self._use_kernel,
            kernel_interpret=self._kernel_interpret, slot_state=state,
            slot=slot, take=take)
        key, sub = jax.random.split(key)
        ids = _sample(logits[:, sample_idx], sub, temp, top_k)
        return ids, pool, key, state

    def _join_impl(self, mirrors, ids, row, temp):
        """One row of the decode mirrors, set on the device: ``mirrors`` as
        ``_mirrors()`` lists them, ``ids`` [1] the final prompt chunk's
        sampled token (still a future on the host), ``row`` the host's
        int32 ``_JOIN_ROW`` vector, ``temp`` [1].  The row decodes unless
        it ends at this first token, by the predicate ``_emit_locked``
        applies to the same token on the host.  A row that LEAVES is the
        same scatter with length and remaining 0: inactive, and its length
        no index into a table row that is zeros from now on."""
        nxt, lengths, active, remaining, stops, temps, top_ks, *spec = mirrors
        slot, length, rem, top_k, spec_on = row[:5]
        first, stop_ids = ids[0], row[5:]
        ends = ((stop_ids == first).any() | (rem <= 0)
                | (length + 1 >= self.max_seq))
        return (nxt.at[slot].set(first), lengths.at[slot].set(length),
                active.at[slot].set((~ends).astype(active.dtype)),
                remaining.at[slot].set(rem), stops.at[slot].set(stop_ids),
                temps.at[slot].set(temp[0]), top_ks.at[slot].set(top_k),
                *(m.at[slot].set(spec_on) for m in spec))

    def _draft_propose_impl(self, params, tokens, pool, table, lengths,
                            key, temps, top_ks, active):
        """k+1 autoregressive draft steps per slot: step j feeds the
        running token at position lengths+j and samples the next proposal.
        Steps 0..k-1 yield the k proposals; step k exists only to WRITE
        the last proposal's draft KV (on full acceptance the next cycle
        starts at lengths+k+1, and the draft's attention span must cover
        position lengths+k — without the extra step the draft pool would
        silently fall one token behind after every full accept).

        Returns (drafted [k, B], qdist [k, B, V] — the exact per-step
        sampling distributions, for rejection sampling — updated pool,
        key).  Positions clamp at max_seq-1: a slot that close to the
        end finishes this cycle, and the clamped writes only ever clobber
        draft KV of a sequence about to free its slot."""
        k = self._spec_k

        def one(carry, j):
            tok, pool, key = carry
            cur = jnp.minimum(lengths + j, self.max_seq - 1)
            logits, pool, _, _ = self._draft_family.decode_step(
                self._draft_cfg, params, tok, pool, table, cur,
                rope_cache=self._draft_rope)
            key, sub = jax.random.split(key)
            ids = _sample(logits, sub, temps, top_ks, active)
            q = _sample_dist(logits, temps, top_ks, active)
            return (ids, pool, key), (ids, q)

        (_, pool, key), (drafted, qdist) = jax.lax.scan(
            one, (tokens, pool, key), jnp.arange(k + 1))
        return drafted[:k], qdist[:k], pool, key

    def _spec_verify_impl(self, params, tokens, drafted, qdist, pool, table,
                          lengths, active, remaining, stops, key, temps,
                          top_ks, spec):
        """Verify k drafted tokens per slot in ONE target forward.

        The window [t0, d_1..d_k] runs through ``decode_window_paged``
        (KV written at positions lengths..lengths+k; rejected positions'
        KV goes stale and is overwritten by later steps — attention masks
        by length, so stale KV is never read).  Acceptance is standard
        rejection sampling — accept d_j iff u*q(d_j) < p(d_j), correction
        from normalize(max(p-q, 0)), bonus from p_k on full acceptance —
        where greedy rows' distributions are exact argmax one-hots
        (engine._sample_dist), which COLLAPSES the same arithmetic to
        exact longest-agreeing-prefix verification: greedy output is
        bit-identical to non-speculative decode.  Slots with spec=0
        (degraded / draft disabled) force zero acceptances and a zeroed
        draft distribution, making their single emission an exact plain
        decode step.  Stop-token / budget / max_seq handling mirrors the
        non-speculative scan ORDER-EXACTLY over the emission sequence.

        Returns (emitted [k+1, B] (-1 padded), accepted [B] — the TRUE
        per-slot acceptance count, BEFORE stop/budget/max_seq truncation
        of the emission window, so metered acceptance measures draft
        quality rather than conflating it with a request's final-cycle
        truncation — next tokens, pool, lengths, active, remaining,
        key); the emitted matrix matches the chunked decode program's
        contract, so collection reuses the pipeline."""
        k = self._spec_k
        b = tokens.shape[0]
        window = jnp.concatenate([tokens[:, None], drafted.T], axis=1)
        logits, pool = self.family.decode_window(
            self.cfg, params, window, pool, table, lengths,
            rope_cache=self._rope, pos_limit=self.max_seq,
            tp_plan=self._tp_verify_plan)
        # per-position target distributions under each slot's sampling
        # params — exactly what non-speculative _sample would draw from
        pdist = jax.vmap(lambda lg: _sample_dist(lg, temps, top_ks, active),
                         in_axes=1, out_axes=1)(logits)  # [B, k+1, V]
        d = drafted.T  # [B, k]
        # zero the draft distribution for non-spec slots: acceptance is
        # forced off (u*0 < p never accepts a q-impossible token... and
        # the explicit mask below makes it unconditional) AND the
        # correction residual max(p - 0, 0) becomes p itself — their one
        # emission is an exact plain decode sample
        q = qdist.transpose(1, 0, 2) * (spec[:, None, None] > 0)
        key, ka = jax.random.split(key)
        a, corr = _spec_accept(pdist, q, d, ka)
        idx = jnp.arange(k + 1)[None, :]
        # candidate emission j: accepted draft for j < a, correction at a
        e = jnp.where(idx < a[:, None],
                      jnp.pad(d, ((0, 0), (0, 1))), corr[:, None])
        # sequential stop/budget/max_seq semantics, mirroring the
        # non-speculative scan: emission j implies lengths+j+1 written
        # tokens and remaining-(j+1) budget; the first done truncates
        base = (idx <= a[:, None]) & (active[:, None] > 0)
        hit_stop = (stops[:, None, :] == e[..., None]).any(-1)
        done_at = (hit_stop
                   | (remaining[:, None] - (idx + 1) <= 0)
                   | (lengths[:, None] + idx + 2 >= self.max_seq))
        stopped_before = jnp.cumsum(
            jnp.pad((base & done_at).astype(jnp.int32),
                    ((0, 0), (1, 0)))[:, :-1], axis=1) > 0
        valid = base & ~stopped_before
        emitted = jnp.where(valid, e, -1).astype(jnp.int32).T  # [k+1, B]
        n_emit = valid.sum(1)
        new_len = lengths + n_emit
        new_rem = remaining - n_emit
        done = (valid & done_at).any(1)
        new_active = active * (1 - done.astype(active.dtype))
        last = jnp.take_along_axis(
            e, jnp.maximum(n_emit - 1, 0)[:, None], 1)[:, 0]
        new_tok = jnp.where(new_active > 0, last, tokens).astype(jnp.int32)
        return (emitted, a.astype(jnp.int32), new_tok, pool, new_len,
                new_active, new_rem, key)

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
        worst = math.ceil((len(prompt) + gen.max_new_tokens + 1) / self.bs)
        # admission reserves cover+1 blocks (chunk-bucket overhang included,
        # any prefix offset) — an infeasible reserve must fail HERE, not
        # retry forever in _admit_locked
        worst = max(worst, 1 + _prefill_cover_worst(
            len(prompt), self.config.prefill_chunk, self.bs))
        if worst > self.num_blocks - 1:  # block 0 is the sink
            raise ValueError(
                f"request needs up to {worst} KV blocks but the pool has "
                f"{self.num_blocks} — raise num_blocks or lower max_new_tokens")
        # step() holds the lock for its whole body: a caller can wait
        # most of a step here before its request is even queued
        t_submit = time.monotonic() if self.slo_label is not None else 0.0
        with self._lock:
            self._note_arrival_locked()
            self._req_counter += 1
            req = _PagedReq(self._req_counter, list(prompt), gen)
            req.spec_enabled = self._spec is not None
            req.prompt_tokens = len(req.prompt)
            if t_submit:
                req.t_submit = t_submit
                req.t_enqueue = time.monotonic()
                self._tracked[req.request_id] = req
                if len(self._tracked) > _TRACKED_MAX:  # nobody popped them
                    del self._tracked[next(iter(self._tracked))]
            self._requests[req.request_id] = req
            self._pending.append(req)
        if t_submit:
            from ray_tpu.serve._private import slo

            slo.record_stage(self.slo_label, "enqueue_wait",
                             req.t_enqueue - t_submit)
        return req.request_id

    def _note_arrival_locked(self):
        """A request is about to become live.  ``device_empty_s`` counts
        only while one is: where the device is known to be empty and no
        request is live, its clock starts at this arrival, not at the drain
        that emptied the device."""
        if (self._empty_since is not None and not self._pending
                and self._slot_req.count(None) == self.max_batch):
            self._empty_since = time.monotonic()

    def _book_empty_locked(self):
        """A program has just been queued on a device the host knew to be
        empty (the caller tested ``_empty_since``): its host-side build and
        dispatch are part of the empty time."""
        self._c["device_empty_s"] += time.monotonic() - self._empty_since
        self._empty_since = None

    def has_work(self) -> bool:
        with self._lock:
            return (bool(self._pending) or self._inflight is not None
                    or any(r is not None for r in self._slot_req))

    # -- tiered prefix cache --------------------------------------------

    def _demote_block(self, block: int, h: int):
        """BlockManager eviction hook: copy the repurposed cached block's
        KV to the host tier before the pool overwrites it.  One small
        device->host readback per eviction — off the steady decode path
        (it only fires under real allocation pressure); free blocks are
        never written by in-flight programs, so the read is consistent."""
        from ray_tpu._private import runtime_metrics

        with tracing.region("kv.demote", blocks=1):
            self._host_cache.put(h, *(
                np.asarray(self.pool[n][:, block])
                for n in self.cache_leaves))
        self._c["kv_demotions"] += 1
        runtime_metrics.add_prefix_cache_evictions("hbm")

    def _match_prefix_tiered(self, prompt: Sequence[int]):
        """HBM chain match, then extend the chain through the host/plasma
        tiers: each tier hit allocates a pool block, uploads the cached KV
        and re-registers the link, so the revived prefix is an ordinary
        HBM match for every later request.

        Returns ``(shared, matched, (hbm_hits, misses, revived_tiers))``:
        NO metrics are booked here — the caller books them only on a
        SUCCESSFUL admission.  A pool-full head-of-line request re-matches
        every step, so booking per attempt would fabricate phantom counts;
        and a block revived on a failed attempt re-matches as an ordinary
        HBM hit on the retry (adopt registered it), so hits + misses must
        always sum to the prompt's block count per admission."""
        shared, matched = self.blocks.match_prefix(prompt)
        if not self.blocks.prefix_caching:
            return shared, matched, (0, 0, ())
        limit = (len(prompt) - 1) // self.bs
        hbm_hits = len(shared)
        revived = []
        if self._host_cache is not None and len(shared) < limit:
            chain = prefix_chain_hashes(prompt, self.bs, limit=limit)
            i = len(shared)
            while i < limit:
                got = self._host_cache.get(chain[i])
                if got is None:
                    break
                fresh = self.blocks.alloc(1)
                if fresh is None:
                    break  # pool full: revival loses to live requests
                *leaves, tier = got
                b = fresh[0]
                self.pool = self._upload_block(
                    self.pool, jnp.int32(b),
                    {n: jnp.asarray(np.asarray(x, dtype=self.pool[n].dtype))
                     for n, x in zip(self.cache_leaves, leaves)})
                self.blocks.adopt(b, chain[i])
                shared.append(b)
                revived.append(tier)
                i += 1
        return (shared, len(shared) * self.bs,
                (hbm_hits, limit - len(shared), tuple(revived)))

    def prefix_digest(self, max_hashes: Optional[int] = None) -> Dict:
        """Compact summary of the prefix chains this engine can serve
        without recompute (HBM registrations + host/plasma tiers), newest
        last.  The serve router compares request chains against it
        (cache-aware routing); hashes are stable across processes
        (_private/prefix_hash.py)."""
        if not self.config.enable_prefix_caching:
            return {"block_size": self.bs, "hashes": []}
        if max_hashes is None:
            from ray_tpu._private.config import global_config

            max_hashes = global_config().serve_prefix_digest_max_hashes
        with self._lock:
            hashes = list(self.blocks.by_hash)
        if self._host_cache is not None:
            seen = set(hashes)
            hashes = [h for h in self._host_cache.hashes()
                      if h not in seen] + hashes
        if len(hashes) > max_hashes:
            hashes = hashes[-max_hashes:]
        return {"block_size": self.bs, "hashes": hashes}

    # -- speculative decoding surfaces ----------------------------------

    def specdec_stats(self) -> Optional[Dict[str, float]]:
        """Engine-lifetime acceptance totals, or None with speculation
        off (the same books-nothing shape as the metric families)."""
        if self._spec is None:
            return None
        with self._lock:
            p, a = self._spec_proposed_total, self._spec_accepted_total
        return {"k": self._spec_k, "proposed": p, "accepted": a,
                "acceptance_rate": (a / p) if p else 0.0}

    def specdec_request_stats(self, request_id: int):
        """(proposed, accepted) for a FINISHED request, or None (unknown
        id, speculation off, or the request never speculated) — the
        serving layer attaches this to the request's SLO recent-row."""
        if self._spec is None:
            return None
        with self._lock:
            return self._spec_finished.get(request_id)

    # -- admission / prefill -------------------------------------------

    def _admit_locked(self):
        """Memory-based admission: a pending request enters when the pool
        has blocks for its full (chunk-padded) prompt plus one decode block
        — proportional to ACTUAL prompt length, never max_seq.  Reserving
        the prompt up front (instead of chunk-by-chunk) makes the system
        livelock-free: a mid-prefill request can never stall on allocation,
        so every admitted request reaches the preemptible decode state.
        One ``engine.admit`` region a request tried (the hash and match of
        ITS prompt's prefix lie inside it)."""
        for slot in range(self.max_batch):
            if not self._pending:
                return
            if self._slot_req[slot] is not None:
                continue
            req = self._pending[0]
            with tracing.region("engine.admit", rid=req.request_id) as span:
                matched = self._admit_one_locked(req, slot)
                if span is not None:
                    span.set_metadata(admitted=int(matched is not None),
                                      prefix_hit_tokens=matched or 0)
            if matched is None:
                return  # pool full: keep FIFO order, retry next step

    def _admit_one_locked(self, req: _PagedReq, slot: int) -> Optional[int]:
        """Admit the queue's head into ``slot``: its prefix-hit tokens, or
        None where the pool has no blocks for it."""
        shared, matched, hit_miss = self._match_prefix_tiered(req.prompt)
        # reserve every block any (pow2-bucketed) prefill chunk's table
        # must cover — chunk padding may reach past the prompt's own
        # blocks (trimmed at prefill end); +1 is the first decode
        # write's spare
        cover = _prefill_plan(len(req.prompt), matched,
                              self.config.prefill_chunk, self.bs)
        need = cover - len(shared) + 1
        fresh = self.blocks.alloc(need)
        if fresh is None:
            self.blocks.release(shared)
            return None
        if req.spec_enabled:
            # the draft prefills the WHOLE prompt (no prefix cache in
            # the draft pool), so it needs the full chunk-padded cover
            dcover = _prefill_plan(len(req.prompt), 0,
                                   self.config.prefill_chunk, self.bs)
            dfresh = self.draft_blocks.alloc(dcover + 1)
            if dfresh is None:
                # draft-pool exhaustion degrades THIS request to
                # plain decode — never blocks admission (zero drops)
                req.spec_enabled = False
            else:
                req.draft_blocks = dfresh
                req.draft_prefill_pos = 0
        if self.blocks.prefix_caching:
            from ray_tpu._private import runtime_metrics

            hbm_hits, misses, revived = hit_miss
            runtime_metrics.add_prefix_cache_hits("hbm", hbm_hits)
            for tier in revived:
                runtime_metrics.add_prefix_cache_hits(tier)
            runtime_metrics.add_prefix_cache_misses(misses)
        self._pending.popleft()
        req.slot = slot
        req.blocks = shared + fresh
        req.prefill_pos = matched
        self._admit_counter += 1
        req.admitted_order = self._admit_counter
        self._slot_req[slot] = req
        req.prefix_hit_tokens += matched
        self._c["prefix_hit_tokens"] += matched
        if (self.slo_label is not None and req.t_enqueue
                and not req.t_admit):
            # first admission only: a preempted request re-queues
            # with t_admit set, and its stages were booked once (its
            # recompute shows in first_emit or decode, where the
            # client waits for it)
            from ray_tpu.serve._private import slo

            req.t_admit = time.monotonic()
            slo.record_stage(self.slo_label, "queue_wait",
                             req.t_admit - req.t_enqueue)
        return matched

    def _decode_ready(self, req: _PagedReq) -> bool:
        """A slot joins the decode batch only when its target prefill —
        and, when speculating, its draft prefill — covers the prompt."""
        plen = len(req.prompt)
        if req.prefill_pos < plen:
            return False
        return not req.spec_enabled or req.draft_prefill_pos >= plen

    def _draft_prefill_chunk_locked(self, req: _PagedReq,
                                    seq: Optional[Sequence[int]] = None):
        """Dispatch one draft prefill chunk (same pow2 chunk geometry and
        fixed table width as the target — block_size is shared).  ``seq``
        overrides the sequence being prefilled (default: the prompt): a
        mid-decode migration import re-seeds the draft over
        prompt + generated history so the draft can propose from the
        resume position."""
        seq = req.prompt if seq is None else seq
        plen = len(seq)
        remaining = plen - req.draft_prefill_pos
        c = min(self.config.prefill_chunk,
                _bucket_pow2(_pad_to(remaining, self.bs), lo=self.bs))
        p0 = req.draft_prefill_pos
        need = math.ceil((p0 + c) / self.bs)
        assert need <= len(req.draft_blocks), (
            f"draft prefill chunk not covered: need {need} blocks, "
            f"have {len(req.draft_blocks)} (draft admission reserve bug)")
        take = min(c, remaining)
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :take] = seq[p0:p0 + take]
        table = np.zeros((1, self._prefill_w), np.int32)
        table[0, :len(req.draft_blocks)] = req.draft_blocks
        self._draft_pool = self._draft_prefill(
            self._draft_params, self._put(tokens), self._draft_pool,
            self._put(table), self._put(p0, np.int32))
        req.draft_prefill_pos = p0 + take
        if req.draft_prefill_pos >= plen:
            # trim chunk-padding draft blocks down to the prompt cover
            keep = math.ceil(plen / self.bs)
            if len(req.draft_blocks) > keep:
                self.draft_blocks.release(req.draft_blocks[keep:])
                del req.draft_blocks[keep:]

    def _prefill_step_locked(self):
        """Advance mid-prefill slots, one chunk per slot, until the step's
        token budget (config.prefill_token_budget, default one chunk) is
        spent — chunked-prefill scheduling: prefill interleaves with
        decode at a bounded per-step cost (the vLLM
        max_num_batched_tokens analog) so a long prompt can never starve
        decode ITL, while a burst of arrivals still ramps many slots per
        step.  Prefill dispatches are pipelined: a FINAL chunk's
        sampled token joins the decode mirrors on the device and is read
        by the host at the end of the step.  Blocks were reserved at admission
        — no allocation can fail here.

        With speculation, the draft model prefills the same prompt into
        its own pool: after each target chunk the draft catches up to the
        target position (draft chunks ride outside the token budget —
        the budget bounds TARGET compute, and draft chunks are a small
        fraction of it; a target prefix-cache hit makes the draft replay
        the matched region, still cheap at draft size)."""
        budget = (self.config.prefill_token_budget
                  or self.config.prefill_chunk)
        self._tel_prefill_budget = budget
        progress = True
        while budget > 0 and progress:
            # round-robin over mid-prefill slots, one chunk each, until
            # the budget is spent: a burst of arrivals ramps many slots
            # per step AND a lone long prompt can use the whole budget
            # (multiple chunks per step) instead of silently pacing at
            # one chunk regardless of the knob
            progress = False
            for slot in range(self.max_batch):
                if budget <= 0:
                    return
                req = self._slot_req[slot]
                if req is None or self._decode_ready(req):
                    continue
                plen = len(req.prompt)
                if req.prefill_pos >= plen:
                    # target done, draft lagging: catch up (defensive —
                    # the frontier loop below keeps them in lockstep)
                    while req.draft_prefill_pos < plen:
                        self._draft_prefill_chunk_locked(req)
                    self._mark_dirty("draft_prefill")  # no join ran for it
                    continue
                remaining = plen - req.prefill_pos
                c = min(self.config.prefill_chunk,
                        _bucket_pow2(_pad_to(remaining, self.bs),
                                     lo=self.bs))
                need = math.ceil((req.prefill_pos + c) / self.bs)
                assert need <= len(req.blocks), (
                    f"prefill chunk not covered: need {need} blocks, "
                    f"have {len(req.blocks)} (admission reserve bug)")
                p0 = req.prefill_pos
                take = min(c, remaining)
                tokens = np.zeros((1, c), np.int32)
                tokens[0, :take] = req.prompt[p0:p0 + take]
                table = np.zeros((1, self._prefill_w), np.int32)
                table[0, :len(req.blocks)] = req.blocks
                is_last = p0 + take >= plen
                sample_idx = (plen - 1 - p0) if is_last else 0
                grouped = int(self._prefill_grouped_from is not None
                              and c >= self._prefill_grouped_from)
                with tracing.region("engine.prefill_chunk", tokens=take,
                                    bucket=c, is_last=int(is_last), p0=p0,
                                    grouped=grouped, rid=req.request_id):
                    ids, self.pool, self._d_key, self.slot_state = (
                        self._prefill_chunk(
                            self.params, self._put(tokens), self.pool,
                            self._put(table), self._put(p0, np.int32),
                            self._put(sample_idx, np.int32), self._d_key,
                            self._put([req.gen.temperature], np.float32),
                            self._put([req.gen.top_k], np.int32),
                            self.slot_state, self._slot_args(slot, take)))
                if self._empty_since is not None:
                    self._book_empty_locked()
                self._chunk_mark = self._c["decode_dispatches"]
                req.prefill_chunks += 1
                self._c["prefill_chunks"] += 1
                self._c["prefill_kernel_chunks"] += self._prefill_kernel
                self._c["prefill_grouped_chunks"] += grouped
                self._c["prefill_tokens"] += take
                self._c["prefill_padded_tokens"] += c - take
                self._c["prefill_live_pages"] += math.ceil(
                    (p0 + take) / self.bs)
                self._c["prefill_visited_pages"] += (
                    self.family.prefill_visited_pages(p0, c, self.bs))
                if self._tp_collectives is not None:
                    self._book_tp_collectives(
                        "prefill",
                        nbytes_each=c * self.cfg.dim
                        * jnp.dtype(self.cfg.compute_dtype).itemsize)
                req.prefill_pos = p0 + take
                # the draft tracks the target's prefill frontier
                while (req.spec_enabled
                       and req.draft_prefill_pos < min(req.prefill_pos,
                                                       plen)):
                    self._draft_prefill_chunk_locked(req)
                progress = True
                if is_last:
                    if (self.slo_label is not None and req.t_admit
                            and not req.t_prefill_end):
                        from ray_tpu.serve._private import slo

                        req.t_prefill_end = time.monotonic()
                        slo.record_stage(self.slo_label, "prefill",
                                         req.t_prefill_end - req.t_admit)
                    # trim chunk-padding blocks; decode's ensure pass
                    # re-allocates
                    keep = math.ceil(plen / self.bs)
                    if len(req.blocks) > keep:
                        self.blocks.release(req.blocks[keep:])
                        del req.blocks[keep:]
                    self.blocks.register(req.prompt, req.blocks)
                    self._lengths[slot] = plen
                    self._slot_temp[slot] = req.gen.temperature
                    self._slot_topk[slot] = req.gen.top_k
                    self._first_pending.append((slot, req, ids))
                    self._join_locked(slot, req, ids)
                budget -= take
                self._tel_prefill_spent += take

    def _slot_args(self, slot: int, take: int) -> tuple:
        """A prompt chunk's ``(slot, real tokens)`` for the program; an empty
        slot state passes none (two Nones: no parameter of the program)."""
        if not self.slot_state:
            return (None, None)
        return (self._put(slot, np.int32), self._put(take, np.int32))

    def _mark_dirty(self, cause: str):
        """The device mirrors are stale; ``cause`` (the first since the
        last refresh) names the drain that refreshing them forces."""
        self._dirty = True
        if self._dirty_cause is None:
            self._dirty_cause = cause

    def _mirrors(self) -> tuple:
        return (self._d_next, self._d_lengths, self._d_active,
                self._d_remaining, self._d_stops, self._d_temp, self._d_topk,
                *(() if self._spec is None else (self._d_spec,)))

    def _join_locked(self, slot: int, req: Optional[_PagedReq] = None,
                     ids=None):
        """Queue ``_join_impl`` for ``slot``: ``req`` enters the decode
        batch with the first token its final prompt chunk sampled
        (``ids``), or, ``req`` None, the row a finished request held is
        cleared.  Either way the device is not waited for: the program
        runs after the prompt chunk and before the next decode chunk, in
        the order dispatched."""
        if self._dirty:
            return  # an upload of every row is due before the next dispatch
        row = np.full(_JOIN_ROW, -1, np.int32)
        row[:5] = slot, 0, 0, 0, 0
        temp = 0.0
        if req is not None:
            g = req.gen
            row[1:5] = (len(req.prompt),
                        g.max_new_tokens - len(req.out_tokens) - 1, g.top_k,
                        req.spec_enabled)
            row[5:5 + len(g.stop_token_ids)] = g.stop_token_ids
            temp = g.temperature
            self._c["decode_joins"] += 1
        with tracing.region("engine.join", rows=int(req is not None),
                            slot=slot,
                            rid=-1 if req is None else req.request_id):
            mirrors = self._join(
                self._mirrors(), self._no_ids if ids is None else ids,
                self._put(row), self._put([temp], np.float32))
        (self._d_next, self._d_lengths, self._d_active, self._d_remaining,
         self._d_stops, self._d_temp, self._d_topk, *spec) = mirrors
        if spec:
            self._d_spec = spec[0]

    def _emit_locked(self, req: _PagedReq, token: int):
        req.out_tokens.append(token)
        self._c["tokens_emitted"] += 1
        if self.slo_label is not None and not req.t_first_emit:
            from ray_tpu.serve._private import slo

            req.t_first_emit = time.monotonic()
            if req.t_prefill_end:
                slo.record_stage(self.slo_label, "first_emit",
                                 req.t_first_emit - req.t_prefill_end)
        if (token in req.gen.stop_token_ids
                or len(req.out_tokens) >= req.gen.max_new_tokens
                or self._lengths[req.slot] + 1 >= self.max_seq):
            req.done = True
            if self.slo_label is not None and req.t_first_emit:
                from ray_tpu.serve._private import slo

                req.t_done = time.monotonic()
                slo.record_stage(self.slo_label, "decode",
                                 req.t_done - req.t_first_emit)
            if self._spec is not None and req.spec_proposed:
                # retain per-request acceptance for the serving layer's
                # recent-request rows (bounded ring; read via
                # specdec_request_stats after the request is gone)
                self._spec_finished[req.request_id] = (
                    req.spec_proposed, req.spec_accepted)
                while len(self._spec_finished) > 1024:
                    self._spec_finished.popitem(last=False)
            self._free_slot_locked(req)

    def _free_slot_locked(self, req: _PagedReq):
        self.blocks.release(req.blocks)
        req.blocks = []
        if req.draft_blocks:
            self.draft_blocks.release(req.draft_blocks)
            req.draft_blocks = []
        self._slot_req[req.slot] = None
        self._lengths[req.slot] = 0
        # a finish dirties nothing: the decode program cleared the row's
        # `active` itself, and this zeroes its length (a stale one would
        # index past a narrower table on the gather path).  The callers
        # that free a row the device still decodes mark the mirrors dirty
        # first, and an upload follows.
        self._join_locked(req.slot)
        req.slot = -1

    def _preempt_locked(self, exclude_slot: int = -1) -> bool:
        """Evict the youngest decode-active request by recompute: free its
        blocks, requeue with prompt+generated as the new prompt.  The OLDEST
        active request is never evicted — it always wins block contention,
        so it completes and the system makes progress (no preemption
        livelock)."""
        candidates = [r for r in self._slot_req
                      if r is not None and r.slot != exclude_slot
                      and r.prefill_pos >= len(r.prompt)]
        if len(candidates) < 2:
            return False  # never evict the sole (oldest) runner
        oldest = min(c.admitted_order for c in candidates)
        victim = max((c for c in candidates if c.admitted_order > oldest),
                     key=lambda c: c.admitted_order, default=None)
        if victim is None:
            return False
        with tracing.region("engine.preempt",
                            tokens_discarded=int(self._lengths[victim.slot])):
            self._c["preemptions"] += 1
            victim.preempted += 1
            victim.prompt = victim.prompt + victim.out_tokens
            victim.prefill_pos = 0
            victim.draft_prefill_pos = 0
            self._mark_dirty("preempt")
            self._free_slot_locked(victim)
            # recompute re-prefills the draft pool too, so a request
            # degraded by earlier draft-pool pressure gets a fresh chance
            # to speculate
            victim.spec_enabled = self._spec is not None
            victim.done = False
            self._pending.appendleft(victim)
        return True

    # -- decode ---------------------------------------------------------

    def _ensure_decode_blocks_locked(self, margin: int) -> List[int]:
        """Every decode-active slot's table must cover lengths + margin
        appends before dispatch (allocation is host-side; the device program
        is static). Returns the decode-active slot list."""
        with tracing.region("engine.ensure_blocks", margin=margin):
            return self._cover_decode_blocks_locked(margin)

    def _cover_decode_blocks_locked(self, margin: int) -> List[int]:
        restart = True
        while restart:
            restart = False
            active = []
            for s in range(self.max_batch):
                req = self._slot_req[s]
                if req is None or not self._decode_ready(req):
                    continue
                while True:
                    need = math.ceil(
                        (int(self._lengths[s]) + margin) / self.bs)
                    need = min(need, self.max_blocks_per_seq)
                    deficit = need - len(req.blocks)
                    if deficit <= 0:
                        self._ensure_draft_blocks_locked(req, need)
                        active.append(s)
                        break
                    fresh = self.blocks.alloc(deficit)
                    if fresh is not None:
                        req.blocks.extend(fresh)
                        active.append(s)
                        break
                    if self._inflight is not None:
                        # the in-flight chunk may still WRITE blocks a
                        # victim owns — never free them under it.  The
                        # drain advances lengths AND trims margin blocks
                        # off slots already validated this pass, so every
                        # coverage decision so far is stale: restart the
                        # whole pass (the drain can happen at most once).
                        self._drain_locked("preempt")
                        restart = True
                        break
                    if not self._preempt_locked():
                        # can't evict anyone else; run without this slot
                        # rather than deadlock (it keeps its blocks and
                        # retries)
                        break
                    if self._slot_req[s] is None:
                        break  # we were the youngest and got evicted
                if restart:
                    break
        return [s for s in active if self._slot_req[s] is not None]

    def _ensure_draft_blocks_locked(self, req: _PagedReq, need: int):
        """Draft-pool coverage for a decode-ready speculating slot.
        Exhaustion NEVER preempts or stalls anyone: the request simply
        degrades to plain decode (spec_enabled=False, its draft blocks
        returned to the pool) — the documented zero-drop behavior.  A
        degraded request stays degraded for this residency (its draft KV
        is gone; recompute after preemption re-enables speculation)."""
        if not req.spec_enabled:
            return
        deficit = need - len(req.draft_blocks)
        if deficit <= 0:
            return
        fresh = self.draft_blocks.alloc(deficit)
        if fresh is not None:
            req.draft_blocks.extend(fresh)
            return
        self.draft_blocks.release(req.draft_blocks)
        req.draft_blocks = []
        req.spec_enabled = False
        self._dirty = True  # the device spec mask must refresh

    def _trim_locked(self, margin: int = 0):
        """Return over-allocated chunk blocks (sequence stopped early).
        ``margin``: appends the device may still make (an in-flight chunk)
        beyond the host's view of lengths — those blocks must be kept."""
        for s in range(self.max_batch):
            req = self._slot_req[s]
            if req is None or req.prefill_pos < len(req.prompt):
                continue
            keep = max(1, math.ceil(
                (int(self._lengths[s]) + margin + 1) / self.bs))
            if len(req.blocks) > keep:
                self.blocks.release(req.blocks[keep:])
                del req.blocks[keep:]
            if req.draft_blocks and len(req.draft_blocks) > keep:
                self.draft_blocks.release(req.draft_blocks[keep:])
                del req.draft_blocks[keep:]

    def _collect_locked(self, em_dev, active: List[int], margin: int,
                        spec_slots: Sequence[int] = (), acc_dev=None):
        """Book one finished decode chunk's tokens into host state
        (lengths, next token, done transitions, block trims).  ``margin``:
        appends another still-in-flight chunk may make beyond this one.
        ``spec_slots``: slots that ran this chunk WITH speculation —
        their acceptance is metered from ``acc_dev`` (the verifier's TRUE
        per-slot accepted counts; deriving accepted from the emission
        matrix would conflate draft rejection with stop/budget/max_seq
        truncation of a request's final cycle and bias acceptance low
        exactly for short generations) BEFORE the emit loop, so a
        request finishing mid-collect reports final stats at its
        terminal booking.  Dead slots (zero emissions) book nothing."""
        booked = None
        if isinstance(em_dev, tuple):  # the family's decode_counters ride
            em_dev, booked = em_dev    # beside the emitted tokens
        with tracing.region("engine.collect", slots=len(active)):
            t0 = time.monotonic()
            em = np.asarray(em_dev)  # fences this chunk (a later may run on)
            self._step_wait += time.monotonic() - t0
        if booked is not None:
            for name, n in zip(self.family.decode_counters,
                               np.asarray(booked).sum(0)):
                self._c[name] += int(n)
        if spec_slots:
            acc = np.asarray(acc_dev)
            proposed = accepted = 0
            k = self._spec_k
            for s in spec_slots:
                req = self._slot_req[s]
                if int((em[:, s] >= 0).sum()) <= 0:
                    continue
                got = min(int(acc[s]), k)
                proposed += k
                accepted += got
                if req is not None:
                    req.spec_proposed += k
                    req.spec_accepted += got
            if proposed:
                self._spec_proposed_total += proposed
                self._spec_accepted_total += accepted
                self._book_specdec(proposed, accepted)
        for t in range(em.shape[0]):
            for s in active:
                req = self._slot_req[s]
                if req is None:
                    continue
                tok = int(em[t, s])
                if tok < 0:
                    continue
                self._lengths[s] += 1
                self._next_tok[s] = tok
                self._emit_locked(req, tok)
        self._trim_locked(margin=margin)

    def _book_specdec(self, proposed: int, accepted: int):
        """Meter drafted/accepted token counts into the runtime-metrics
        families and the serving SLO ledger.  Only ever called with
        speculation configured — the disabled path books NOTHING (the
        same invariant as the PR 9 lifecycle layer)."""
        from ray_tpu._private import runtime_metrics

        dep = self.slo_label or "engine"
        runtime_metrics.add_specdec_tokens(dep, proposed, accepted)
        if self.slo_label is not None:
            from ray_tpu.serve._private import slo

            # ledger-side fold (state.serving_slo()); records under the
            # process ledger's lock only — never an RPC under step()'s
            # engine lock
            slo.note_specdec(self.slo_label, proposed, accepted)

    def _resolve_first_tokens_locked(self):
        """Book pending first-token futures: each read waits for its
        prompt chunk alone, never for a decode chunk queued after it, and
        a request's first token is booked before any token such a chunk
        decodes for it (``step()`` calls this before it returns; a drain
        and an upload call it too)."""
        pending, self._first_pending = self._first_pending, []
        for slot, req, ids in pending:
            if self._slot_req[slot] is not req:
                continue  # preempted before its first token surfaced:
                # recompute will re-sample it (it was never emitted)
            t0 = time.monotonic()
            first = int(np.asarray(ids)[0])
            self._step_wait += time.monotonic() - t0
            self._next_tok[slot] = first
            self._emit_locked(req, first)

    def _drain_locked(self, cause: str):
        """Collect the in-flight decode chunk, if any, and any pending
        first tokens.  A drain that finds a chunk in flight counts under
        ``cause``: the next dispatch cannot queue behind it."""
        if self._inflight is None and not self._first_pending:
            return
        with tracing.region("engine.drain", cause=cause,
                            inflight=int(self._inflight is not None)):
            if self._inflight is not None:
                self._drains[cause] = self._drains.get(cause, 0) + 1
                em_dev, active, spec_slots, acc_dev = self._inflight
                self._inflight = None
                self._collect_locked(em_dev, active, margin=0,
                                     spec_slots=spec_slots, acc_dev=acc_dev)
            self._resolve_first_tokens_locked()
            if self._c["decode_dispatches"] > self._chunk_mark:
                # every decode chunk is read, and the last prompt chunk
                # ran before one of them: nothing is queued (a join's
                # microseconds aside) until the next dispatch books this
                self._empty_since = time.monotonic()

    def step(self, decode: bool = True) -> Dict[int, List[int]]:
        """One engine step: admit, prompt chunks up to the budget, one
        decode chunk.

        Decode PIPELINES: the chunk dispatched here is collected on the
        NEXT step, so its device compute overlaps this step's host
        bookkeeping and readback latency.  Admission, prompt chunks, a row
        that joins the batch after its final chunk and a finished request
        all keep the pipeline: the row state the decode program reads is
        updated on the device, in the order dispatched (``_join_locked``).
        A first token is returned by the step that dispatched its final
        prompt chunk.  What drains the chunk in flight first: preemption
        pressure, a cancel, an import or export, and a step with no row to
        decode.  ``decode=False`` runs admission/prefill only (ramp
        control)."""
        now = time.monotonic()
        # device telemetry: one attribute read + None check when disabled
        tel = self._telemetry
        with tracing.region(
                "engine.step", pending=len(self._pending),
                active=self.max_batch - self._slot_req.count(None),
                inflight=int(self._inflight is not None)), self._lock:
            self._tel_prefill_spent = 0
            self._step_wait = 0.0
            before = self._emit_snapshot_locked()
            if self._pending or any(
                    r is not None and not self._decode_ready(r)
                    for r in self._slot_req):
                # admission + prefill run WITHOUT draining the in-flight
                # decode chunk: a new slot's fresh blocks are disjoint from
                # every in-flight table row (its own row was zeros → sink),
                # and prefill dispatches chain after the decode on the pool
                # dataflow.  A final chunk's row joins the mirrors on the
                # device and decodes in this step's dispatch, below.
                self._admit_locked()
                self._prefill_step_locked()
            chunk = self.config.decode_chunk
            # device appends per dispatch: a speculative cycle writes up
            # to k+1 positions (k drafted + the bonus slot), a plain
            # chunk writes `chunk`
            app = (self._spec_k + 1) if self._spec is not None else chunk
            if decode:
                # margin covers this dispatch plus one still in flight
                margin = app + 1 + (app if self._inflight else 0)
                active = self._ensure_decode_blocks_locked(margin)
            else:
                active = []
            if active:
                if self._dirty:
                    self._drain_locked(self._dirty_cause or "other")
                    self._refresh_mirrors_locked()
                    # the drain invalidated the ensure pass above: it
                    # advances lengths AND _trim_locked(margin=0) releases
                    # the margin blocks just reserved, so dispatching with
                    # the old `active` would scatter KV into sink block 0
                    # on any append crossing a block boundary (ADVICE r5
                    # high).  Re-run coverage from scratch — _inflight is
                    # now None, so one in-flight chunk's margin suffices.
                    active = self._ensure_decode_blocks_locked(app + 1)
                    if self._dirty:
                        # the re-run preempted someone: mirrors are stale
                        # again (no drain needed — nothing is in flight)
                        self._refresh_mirrors_locked()
                        active = [s for s in active
                                  if self._slot_req[s] is not None]
            if active:
                prev = self._dispatch_decode_locked(active, chunk)
                if prev is not None:
                    # collect chunk N while chunk N+1 computes: the fence
                    # latency rides under the new dispatch.  The device is
                    # up to `app` appends ahead of the collected view.
                    self._collect_locked(prev[0], prev[1], margin=app,
                                         spec_slots=prev[2],
                                         acc_dev=prev[3])
                # this step's final prompt chunks: the device already runs
                # the decode chunk queued behind them
                self._resolve_first_tokens_locked()
            else:
                self._drain_locked("idle")
            emitted = self._gather_emitted_locked(before)
            tel_active = self.max_batch - self._slot_req.count(None)
            tel_free = self.blocks.num_free()
            tel_pending = len(self._pending)
            # the step's wall time, split: blocked in device reads, and
            # the rest (waiting for this lock included)
            t_end = time.monotonic()
            c = self._c
            c["steps"] += 1
            c["device_wait_s"] += self._step_wait
            c["host_s"] += t_end - now - self._step_wait
        if tel is not None:
            # booked after release, from the locals captured under the lock
            tel.note_step(
                active_slots=tel_active, max_slots=self.max_batch,
                free_blocks=tel_free, total_blocks=self.num_blocks - 1,
                pending=tel_pending,
                prefill_spent=self._tel_prefill_spent,
                prefill_budget=self._tel_prefill_budget,
                busy_s=t_end - now, now=t_end)
        return emitted

    def _dispatch_decode_locked(self, active: List[int], chunk: int):
        """Build the block table and dispatch one decode chunk (or one
        speculative cycle) for ``active``; it becomes the chunk in flight.
        Returns the chunk that was in flight before, still to collect."""
        w = _bucket_pow2(max(len(self._slot_req[s].blocks) for s in active))
        pages = sum(len(self._slot_req[s].blocks) for s in active)
        with tracing.region("engine.decode_dispatch", slots=len(active),
                            w=w, chunk=chunk, pages=pages):
            table = np.zeros((self.max_batch, w), np.int32)
            for s in active:
                blks = self._slot_req[s].blocks
                table[s, :len(blks)] = blks
            if self._spec is not None:
                em_dev, acc_dev, spec_slots = self._spec_step_locked(
                    table, active)
                steps = self._spec_k + 1
            else:
                em_dev = self._decode_locked(table, chunk)
                acc_dev, spec_slots, steps = None, (), chunk
        prev, self._inflight = (self._inflight,
                                (em_dev, active, spec_slots, acc_dev))
        c = self._c
        c["decode_dispatches"] += 1
        c["decode_token_steps"] += steps
        # how often the sampler's two conditionals engage (engine.py
        # _sampler_gates), by the host's view of the rows it dispatched
        draws = [g for g in (self._slot_req[s].gen for s in active)
                 if g.temperature > 0]
        if draws:
            c["decode_sampled_token_steps"] += steps
            if any(g.top_k > 0 for g in draws):
                c["decode_topk_token_steps"] += steps
        c["decode_table_pages"] += self.max_batch * w
        c["decode_live_pages"] += pages
        c["decode_rows"] += self.max_batch * steps
        c["decode_live_rows"] += len(active) * steps
        if prev is not None:
            c["decode_dispatches_pipelined"] += 1
        elif self._empty_since is not None:
            self._book_empty_locked()
        return prev

    def _decode_locked(self, table, steps: int):
        """Dispatch the decode program over ``table`` for ``steps``
        token-steps and recapture what it carries (mirrors, pool, slot
        state).  Returns the emitted tokens, still on the device, as
        ``_collect_locked`` takes them."""
        (em_dev, self._d_next, self.pool, self._d_lengths,
         self._d_active, self._d_remaining, self._d_key,
         self.slot_state) = self._decode(
            self.params, self._d_next, self.pool, self._put(table),
            self._d_lengths, self._d_active, self._d_remaining,
            self._d_stops, self._d_key, self._d_temp, self._d_topk, steps,
            self.slot_state)
        self._book_tp_collectives("decode", steps)
        return em_dev

    def _spec_step_locked(self, table, active: List[int]):
        """One speculative decode cycle: draft proposes k tokens per
        slot (k+1 small autoregressive steps), the target verifies all
        of them in ONE window forward.  Two dispatches, zero host syncs
        — the emission matrix is collected on the NEXT step exactly like
        a plain pipelined chunk.  Returns (em_dev [k+1, B], acc_dev [B]
        true acceptance counts, spec_slots).

        Slots whose requests are degraded (draft-pool exhaustion /
        per-adapter opt-out) ride the same verify program with a zeroed
        spec mask: zero acceptances, and their single emission is an
        exact plain decode sample — mixed batches need no second
        program.  A FULLY degraded batch instead falls back to the
        ordinary chunked decode program at k+1 steps (the same appends
        bound the ensure margin reserved): paying the (k+1)-wide verify
        window for one token per slot would make 'degraded' far slower
        than plain decode, the opposite of what degradation promises."""
        k = self._spec_k
        b = self.max_batch
        spec_slots = tuple(
            s for s in active
            if self._slot_req[s] is not None
            and self._slot_req[s].spec_enabled)
        if not spec_slots:
            return self._decode_locked(table, k + 1), None, ()
        # the draft table reuses the TARGET table's bucketed width:
        # block counts track each other (same ensure/trim formulas),
        # and one shared width means one propose compile per verify
        # bucket — warmup() covers both with a single shape grid
        dtable = np.zeros((b, table.shape[1]), np.int32)
        for s in spec_slots:
            blks = self._slot_req[s].draft_blocks
            dtable[s, :len(blks)] = blks
        (drafted, qdist, self._draft_pool, self._d_key) = \
            self._draft_propose(
                self._draft_params, self._d_next, self._draft_pool,
                self._put(dtable), self._d_lengths, self._d_key,
                self._d_temp, self._d_topk, self._d_active)
        (em_dev, acc_dev, self._d_next, self.pool, self._d_lengths,
         self._d_active, self._d_remaining, self._d_key) = \
            self._spec_verify(
                self.params, self._d_next, drafted, qdist, self.pool,
                self._put(table), self._d_lengths, self._d_active,
                self._d_remaining, self._d_stops, self._d_key,
                self._d_temp, self._d_topk, self._d_spec)
        self._book_tp_collectives("verify")
        return em_dev, acc_dev, spec_slots

    def flush(self) -> Dict[int, List[int]]:
        """Collect any in-flight decode chunk and return its tokens."""
        with self._lock:
            before = self._emit_snapshot_locked()
            self._drain_locked("flush")
            return self._gather_emitted_locked(before)

    def cancel_request(self, request_id: int) -> bool:
        """Abort a live request and return its slot + blocks to the pool
        NOW (a disconnected streaming client must not keep decoding to
        max_new_tokens for nobody).  Safe at any lifecycle point: queued,
        mid-prefill, or decode-active.  Returns False if the request
        already finished (or never existed)."""
        from ray_tpu._private import flight_recorder

        with self._lock:
            self._tracked.pop(request_id, None)  # an aborted stream: no row
            req = self._requests.get(request_id)
            if req is None:
                return False
            del self._requests[request_id]
            if req in self._pending:
                try:
                    self._pending.remove(req)
                except ValueError:
                    pass
            elif req.slot >= 0:
                # the in-flight decode chunk may still WRITE blocks this
                # request owns — never free them under it (the same
                # argument as preemption's drain)
                if self._inflight is not None:
                    self._drain_locked("cancel")
                if req.slot >= 0 and self._slot_req[req.slot] is req:
                    self._mark_dirty("cancel")
                    self._free_slot_locked(req)
            req.done = True
            flight_recorder.record("request", self.slo_label or "paged",
                                   (request_id, "cancel"))
            return True

    # -- disaggregated prefill/decode handoff ---------------------------

    def export_request(self, request_id: int) -> Dict:
        """Export a request's live KV blocks + emitted-token history and
        release its slot.  Two callers: the prefill stage of a
        disaggregated deployment (export right after prefill, history is
        the single first token) and live KV migration (export mid-decode:
        the in-flight chunk is drained first — the same argument as
        ``cancel_request`` — and the handoff carries everything the
        destination needs to resume at the exact position).  The
        request's registered prompt blocks stay revivable in this
        engine's prefix cache, so the source keeps serving chain hits
        for the prompt it just handed off.

        Returns {prompt, first_token, <the cache's leaves>, block_size,
        emitted, gen}: each leaf of the family's paged cache under its own
        name (a Llama handoff: k and v), host arrays [L, nblocks,
        block_size, width] covering
        exactly the live positions (prompt + generated-so-far), emitted
        is the full output-token history, and gen carries the sampling /
        stop / budget state.  Raises if the request isn't in the
        exportable state (prefill incomplete, or already finished — a
        1-token budget completes on the first emit and frees its partial
        block).

        Tensor-parallel engines export the same payload: the gather
        below reads the kv-head-sharded pool and ``np.asarray`` on the
        result assembles the FULL logical blocks on host (an all-gather
        over the mesh, paid once per handoff, not per step).  The
        handoff is therefore geometry-invariant — k/v carry no trace of
        the source's TP degree, so single↔sharded and 2-way↔4-way
        migrations all interoperate; the importer re-shards on entry."""
        with self._lock:
            self._drain_locked("export")  # the in-flight chunk's tokens
            req = self._requests.get(request_id)
            if req is None or req.done or req.slot < 0:
                raise KeyError(
                    f"request {request_id} is not exportable (finished or "
                    "unknown — use max_new_tokens >= 2 for prefill-stage "
                    "requests)")
            if req.prefill_pos < len(req.prompt):
                raise RuntimeError(
                    f"request {request_id} prefill incomplete "
                    f"({req.prefill_pos}/{len(req.prompt)})")
            if not req.out_tokens:
                raise RuntimeError(
                    f"request {request_id} first token unresolved")
            # the KV pool covers positions 0..lengths-1; mid-decode the
            # block list may run ahead of that (decode_block_margin), so
            # export only the live cover — the destination re-validates
            # against the same formula
            live = int(self._lengths[req.slot])
            nb_live = max(1, math.ceil(live / self.bs))
            blocks = list(req.blocks)[:nb_live]
            barr = jnp.asarray(np.asarray(blocks, np.int32))
            # one gather program + readback a leaf; [L, nb, bs, D]
            g = req.gen
            out = {"prompt": list(req.prompt),
                   "first_token": int(req.out_tokens[0]),
                   **{n: np.asarray(self.pool[n][:, barr])
                      for n in self.cache_leaves},
                   "block_size": self.bs,
                   # what the sequence holds that does not page: the slot's
                   # own index of every leaf, [layers, ...]
                   **({"slot_state": {
                       n: np.asarray(x[:, req.slot])
                       for n, x in self.slot_state.items()}}
                      if self.slot_state else {}),
                   "emitted": [int(t) for t in req.out_tokens],
                   "gen": {"max_new_tokens": g.max_new_tokens,
                           "temperature": g.temperature,
                           "top_k": g.top_k, "seed": g.seed,
                           "stop_token_ids": list(g.stop_token_ids)}}
            req.done = True
            self._mark_dirty("export")
            self._free_slot_locked(req)
            del self._requests[request_id]
            return out

    def import_request(self, prompt: Sequence[int], first_token: int,
                       k, v=None, gen: Optional[GenerationConfig] = None,
                       emitted: Optional[Sequence[int]] = None,
                       slot_state: Optional[Dict] = None):
        """Admit a request directly into the decode state from handed-off
        KV (``k`` and ``v`` as ``export_request`` gave them; a family whose
        cache has other leaves hands the dict of them as ``k`` and leaves
        ``v`` out): allocates pool blocks, scatters the KV in, registers the
        prompt's chain for prefix sharing, and resumes decode.  Two
        callers: the decode stage of a disaggregated deployment
        (``emitted`` omitted — ``first_token`` is emitted as the
        request's first output token) and live KV migration (``emitted``
        is the source's full output history — decode resumes at position
        prompt+len(emitted)-1 and the history is NOT re-emitted, the
        source already streamed it).  ``slot_state``: the handoff's leaves
        of the same name, for a family whose sequences hold a state that does
        not page; a handoff without them is refused (ValueError: the caller
        recomputes, as for any handoff this engine cannot use).

        Returns {request_id, emitted, done} or None when no slot/blocks
        are free right now — the caller falls back to a plain
        ``add_request`` (recompute; the prefix cache usually absorbs most
        of it).  Never queues: a queued import would pin host copies of
        KV that recompute could regenerate.

        On a tensor-parallel engine the scatter program writes into the
        kv-head-sharded pool, so the full-logical host blocks from
        ``export_request`` are re-sharded on entry — each device keeps
        only its kv-head slice.  Because the exported payload is
        geometry-invariant, a mixed fleet (single-device prefill tier,
        sharded decode tier, or rebalancing between TP degrees) hands
        off without a resharding step in between; when this engine has
        no free slot/blocks the usual None → ``add_request`` recompute
        fallback applies unchanged, so mixed handoff never drops a
        request."""
        gen = gen or GenerationConfig()
        plen = len(prompt)
        if plen == 0:
            raise ValueError("empty prompt")
        if emitted is not None and not emitted:
            raise ValueError("emitted history must hold >= 1 token")
        resume = emitted is not None
        hist = [int(t) for t in emitted] if resume else [int(first_token)]
        # live positions covered by the handoff KV: prompt plus every
        # emitted token except the last (whose KV is written by the NEXT
        # decode step, exactly as in the monolithic flow)
        live = plen + len(hist) - 1
        if plen + gen.max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({plen}) + max_new_tokens ({gen.max_new_tokens})"
                f" exceeds max_seq_len {self.max_seq}")
        leaves = k if isinstance(k, dict) else {"k": k, "v": v}
        if set(leaves) != set(self.cache_leaves):
            raise ValueError(
                f"handoff carries cache leaves {sorted(leaves)}, the "
                f"{self.family.name} family's pool has "
                f"{list(self.cache_leaves)}")
        if self.slot_state and (
                slot_state is None
                or set(slot_state) != set(self.slot_state)
                or any(tuple(np.shape(slot_state[n]))
                       != x.shape[:1] + x.shape[2:]
                       for n, x in self.slot_state.items())):
            raise ValueError(
                f"the {self.family.name} family resumes a sequence from its "
                f"slot state {sorted(self.slot_state)}, which this handoff "
                "does not carry at this engine's shapes")
        nb = int(leaves[self.cache_leaves[0]].shape[1])
        if nb != max(1, math.ceil(live / self.bs)):
            raise ValueError(
                f"handoff covers {nb} blocks but {live} live tokens "
                f"need {max(1, math.ceil(live / self.bs))} at block_size "
                f"{self.bs}")
        with self._lock:
            slot = next((s for s in range(self.max_batch)
                         if self._slot_req[s] is None), None)
            if slot is None:
                return None
            blocks = self.blocks.alloc(nb)
            if blocks is None:
                return None
            self._note_arrival_locked()
            pad = _bucket_pow2(nb)
            idx = np.zeros(pad, np.int32)
            idx[:nb] = blocks  # pad rows scatter into sink block 0
            padded = {}
            for n, x in leaves.items():
                dt = self.pool[n].dtype
                xp = np.zeros((x.shape[0], pad) + tuple(x.shape[2:]), dtype=dt)
                xp[:, :nb] = np.asarray(x, dtype=dt)
                padded[n] = jnp.asarray(xp)
            self.pool = self._import_blocks(
                self.pool, jnp.asarray(idx), padded)
            if self._empty_since is not None:
                self._book_empty_locked()  # the scatter is a dispatch
            if self.slot_state:
                self.slot_state = self._import_slot(
                    self.slot_state, jnp.int32(slot),
                    {n: jnp.asarray(np.asarray(slot_state[n], dtype=x.dtype))
                     for n, x in self.slot_state.items()})
            self._req_counter += 1
            req = _PagedReq(self._req_counter, list(prompt), gen)
            req.slot = slot
            req.blocks = list(blocks)
            req.prefill_pos = plen
            self._admit_counter += 1
            req.admitted_order = self._admit_counter
            self._requests[req.request_id] = req
            self._slot_req[slot] = req
            self.blocks.register(req.prompt, req.blocks)
            self._lengths[slot] = live
            # seed the DRAFT model's KV for the handed-off prefix by
            # recomputing it at draft size (the handoff carries only the
            # target's KV — draft layers/dims differ, so there is nothing
            # to scatter).  Without this, every disagg handoff would
            # decode at acceptance-rate ~0: the draft's attention span
            # over the prompt would be garbage.  Chunked like ordinary
            # draft prefill; draft-pool exhaustion degrades to plain
            # decode exactly as elsewhere.  A mid-decode migration
            # re-seeds over prompt + history so the draft covers every
            # live position, not just the prompt.
            if self._spec is not None:
                req.spec_enabled = True
                dseq = list(prompt) + hist[:-1]
                dcover = _prefill_plan(len(dseq), 0,
                                       self.config.prefill_chunk, self.bs)
                dfresh = self.draft_blocks.alloc(dcover + 1)
                if dfresh is None:
                    req.spec_enabled = False
                else:
                    req.draft_blocks = dfresh
                    while req.draft_prefill_pos < len(dseq):
                        self._draft_prefill_chunk_locked(req, seq=dseq)
            self._next_tok[slot] = hist[-1]
            self._slot_temp[slot] = gen.temperature
            self._slot_topk[slot] = gen.top_k
            self._mark_dirty("import")
            # the source sampled these tokens; they count toward the
            # output budget exactly as in the monolithic flow.  The
            # history prefix is pre-seeded WITHOUT emission (a resumed
            # stream's client already has it); only the last token runs
            # the emit/done transition.
            req.out_tokens = hist[:-1]
            self._emit_locked(req, hist[-1])
            return {"request_id": req.request_id,
                    "emitted": [] if resume else [int(first_token)],
                    "done": req.done}

    def _emit_snapshot_locked(self) -> Dict[int, int]:
        return {id(r): len(r.out_tokens) for r in self._requests.values()}

    def _gather_emitted_locked(self, before: Dict[int, int]):
        emitted: Dict[int, List[int]] = {}
        for req in list(self._requests.values()):
            n0 = before.get(id(req), 0)
            if len(req.out_tokens) > n0:
                emitted[req.request_id] = req.out_tokens[n0:]
            if req.done:
                del self._requests[req.request_id]
        return emitted

    def _refresh_mirrors_locked(self):
        with tracing.region("engine.refresh"):
            self._upload_mirrors_locked()

    def _upload_mirrors_locked(self):
        self._resolve_first_tokens_locked()  # _next_tok must be current
        decode_ready = [
            0 if (r is None or not self._decode_ready(r)) else 1
            for r in self._slot_req]
        if self._spec is not None:
            self._d_spec = self._put(np.array(
                [1 if (decode_ready[s] and r is not None and r.spec_enabled)
                 else 0
                 for s, r in enumerate(self._slot_req)], np.int32))
        self._d_next = self._put(self._next_tok)
        self._d_lengths = self._put(self._lengths)
        self._d_active = self._put(np.array(decode_ready, np.int32))
        self._d_temp = self._put(self._slot_temp)
        self._d_topk = self._put(self._slot_topk)
        remaining = np.zeros(self.max_batch, np.int32)
        stops = np.full((self.max_batch, _MAX_STOP_IDS), -1, np.int32)
        for s, r in enumerate(self._slot_req):
            if r is not None and decode_ready[s]:
                remaining[s] = r.gen.max_new_tokens - len(r.out_tokens)
                for j, sid in enumerate(r.gen.stop_token_ids):
                    stops[s, j] = sid
        self._d_remaining = self._put(remaining)
        self._d_stops = self._put(stops)
        self._dirty = False
        self._dirty_cause = None

    # -- warmup ---------------------------------------------------------

    def warmup(self, max_len: Optional[int] = None):
        """Compile the decode program for every (B, W) table bucket, the
        prefill program for every chunk width, and the join.

        W buckets are powers of two up to the per-sequence block cap (or
        the blocks covering ``max_len`` + pipelining margin, if given); a
        bucket transition mid-stream (a sequence crossing a pow2 block
        count) otherwise triggers an XLA compile inside the serving hot
        path, landing in every steady-state window (vLLM warms its shape
        buckets at startup for the same reason).  Uses throwaway dummy state; engine
        state is untouched."""
        b = self.max_batch
        chunk = self.config.decode_chunk
        t0 = time.monotonic()
        decode_widths: List[int] = []
        prefill_chunks: List[int] = []
        w_cap = _bucket_pow2(self.max_blocks_per_seq)
        if max_len is not None:
            need = math.ceil((max_len + 2 * chunk + 1) / self.bs)
            w_cap = min(w_cap,
                        _bucket_pow2(min(need, self.max_blocks_per_seq)))
        key = self._put(jax.random.PRNGKey(0))

        def zi(*shape):
            return self._put(np.zeros(shape, np.int32))

        def zf(*shape):
            return self._put(np.zeros(shape, np.float32))

        stops = self._put(np.full((b, _MAX_STOP_IDS), -1, np.int32))
        with self._lock:
            self._drain_locked("flush")
            w = 1
            while True:
                # donate the REAL pool and recapture it: a second full-size
                # pool would double peak HBM exactly when num_blocks is
                # sized to fill it.  All-zero tables + active=0 mean every
                # warmup write lands in sink block 0 (garbage by design).
                if self._spec is not None:
                    # speculative serving dispatches verify (per target-
                    # table bucket) + propose (per draft-table bucket),
                    # never the chunked decode program — warm what runs
                    k, v = self._spec_k, self.cfg.vocab_size
                    out = self._spec_verify(
                        self.params, zi(b), zi(k, b), zf(k, b, v), self.pool,
                        zi(b, w), zi(b), zi(b), zi(b), stops, key, zf(b),
                        zi(b), zi(b))
                    self.pool = out[3]  # (emitted, accepted, tokens, pool..)
                    np.asarray(out[0])
                    pout = self._draft_propose(
                        self._draft_params, zi(b), self._draft_pool,
                        zi(b, w), zi(b), key, zf(b), zi(b), zi(b))
                    self._draft_pool = pout[2]
                    np.asarray(pout[0])
                    # fully-degraded fallback: chunked decode at k+1
                    # steps — a mid-serve degrade must not compile
                    steps = k + 1
                else:
                    steps = chunk
                # active=0: a slot state comes back as it was
                em, _, self.pool, _, _, _, _, self.slot_state = (
                    self._decode(
                        self.params, zi(b), self.pool, zi(b, w), zi(b),
                        zi(b), zi(b), stops, key, zf(b), zi(b), steps,
                        self.slot_state))
                jax.block_until_ready(em)  # compile + run to completion
                decode_widths.append(w)
                if w >= w_cap:
                    break
                w *= 2
            # prefill programs: one per pow2 chunk width (table width is
            # fixed), so this covers EVERY prefill shape serving can hit.
            # Serving caps chunks at the bucketed max prompt width AND the
            # fixed table's coverage — warm only reachable widths.
            c_cap = min(self.config.prefill_chunk,
                        self._prefill_w * self.bs,
                        _bucket_pow2(_pad_to(self.max_seq, self.bs),
                                     lo=self.bs))
            c = self.bs
            while True:
                c = min(c, c_cap)
                # no real token: a slot state stays as it was
                ids, self.pool, _, self.slot_state = self._prefill_chunk(
                    self.params, zi(1, c), self.pool, zi(1, self._prefill_w),
                    zi(), zi(), key, zf(1), zi(1), self.slot_state,
                    self._slot_args(0, 0))
                np.asarray(ids)
                if self._spec is not None:
                    self._draft_pool = self._draft_prefill(
                        self._draft_params, zi(1, c), self._draft_pool,
                        zi(1, self._prefill_w), zi())
                prefill_chunks.append(c)
                if c >= c_cap:
                    break
                c *= 2
            # the join: one shape, over mirrors of its own
            jax.block_until_ready(self._join(
                (zi(b), zi(b), zi(b), zi(b), stops, zf(b), zi(b),
                 *(() if self._spec is None else (zi(b),))),
                zi(1), zi(_JOIN_ROW), zf(1)))
        # what ran, for whoever has to show that it did (device_report)
        # compiles from here on ran inside serving
        self._compile_base = device_telemetry.compile_totals()
        self.warmup_report = {
            "seconds": time.monotonic() - t0, "batch": b,
            "decode_table_widths": decode_widths,
            "prefill_chunks": prefill_chunks}

    # -- diagnostics ----------------------------------------------------

    def first_decode_logits(self, prompt: Sequence[int]) -> np.ndarray:
        """float32 logits [V] of the decode step that follows ``prompt``,
        through this engine's own model programs: one paged prefill chunk
        of ``prompt[:-1]``, then ``decode_step_paged`` of the last token
        with the engine's kernel choice, mesh and collective plan, on
        blocks borrowed from the pool and returned.  What two engines
        over the same weights (one device against a tensor-parallel mesh)
        are compared by; one program of its own, off the serving path."""
        n = len(prompt) - 1
        if n < 1:
            raise ValueError("prompt needs at least 2 tokens")
        c = _pad_to(n, self.bs)
        toks = np.zeros((1, c), np.int32)
        toks[0, :n] = prompt[:-1]

        def run(params, pool, toks, table, last, length):
            # a family with a slot state runs on one slot of its own here
            init = self.family.init_slot_state
            _, pool, state = self.family.prefill_chunk(
                self.cfg, params, toks, pool, table, jnp.int32(0),
                rope_cache=self._rope, tp_plan=self._tp_prefill_plan,
                use_kernel=self._use_kernel,
                kernel_interpret=self._kernel_interpret,
                slot_state=init(self.cfg, 1) if init else {},
                slot=jnp.int32(0), take=jnp.int32(n))
            logits, pool, _, _ = self.family.decode_step(
                self.cfg, params, last, pool, table, length,
                rope_cache=self._rope, use_kernel=self._use_kernel,
                mesh=self.mesh, kernel_interpret=self._kernel_interpret,
                tp_plan=self._tp_plan, slot_state=state)
            return logits[0], pool

        with self._lock:
            self._drain_locked("flush")
            blocks = self.blocks.alloc(c // self.bs + 1)
            if blocks is None:
                raise RuntimeError("no free KV blocks for the diagnostic")
            try:
                logits, self.pool = jax.jit(run, donate_argnums=1)(
                    self.params, self.pool, self._put(toks),
                    self._put([blocks], np.int32),
                    self._put(prompt[-1:], np.int32),
                    self._put([n], np.int32))
                return np.asarray(logits)
            finally:
                self.blocks.release(blocks)

    # -- sync convenience ----------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]],
                 gen: Optional[GenerationConfig] = None) -> List[List[int]]:
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


def _pad_to(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m
