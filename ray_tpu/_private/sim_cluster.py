"""Simulated mega-cluster harness: hundreds–thousands of skeleton raylets
against ONE real GCS, in one process, with no sockets and no threads per
node.

The scaling questions this answers ("is sync traffic proportional to churn
or to cluster size?", "how many publishes does one control event cost the
GCS?") are protocol properties, not kernel properties — so the harness
keeps the real ``GcsServer`` (real handlers, real versioned changelog,
real ``Pubsub`` tree logic) and replaces only what cannot exist 1000x in
one process:

- **SkeletonRaylet** — the report loop + view application of a raylet and
  nothing else (no worker pool, no object store, no threads; the chaos-
  injection style of ``tests/test_preemption.py``).  View application goes
  through the SAME ``cluster_view.apply_sync_reply`` protocol code the
  production raylet runs, over a plain-dict store.
- **SimNet** — an in-process ClientPool lookalike routing the pubsub
  plane's ``call_async``/``call_async_frame`` to skeleton handlers
  synchronously, raising ``ConnectionLost`` for killed nodes exactly like
  a refused connect.  Ticks are driven explicitly by the caller
  (injectable-clock style: convergence is measured in tick rounds, never
  wall time), so the harness is deterministic and leaves no threads behind
  beyond the one real GCS's own loops.

Metering rides the production metric families
(``ray_tpu_gcs_sync_bytes_total{kind}``,
``ray_tpu_pubsub_relay_publishes_total{role}``,
``ray_tpu_gcs_sync_version``) — the same counters the perf-smoke gate
reads.
"""

from __future__ import annotations

import pickle
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

from ray_tpu._private import runtime_metrics
from ray_tpu._private.cluster_view import (
    DictViewStore,
    apply_sync_reply,
    tree_partition,
)
from ray_tpu._private.config import RayTpuConfig
from ray_tpu._private.gcs import GcsServer
from ray_tpu._private.ids import NodeID
from ray_tpu._private.rpc import ConnectionLost, decode_body

Addr = Tuple[str, int]


class _SimClient:
    """One fake-address endpoint of a SimNet (RpcClient lookalike)."""

    def __init__(self, net: "SimNet", address: Addr):
        self._net = net
        self.address = address

    def call_async(self, method: str, payload=None) -> Future:
        target = self._net.registry.get(self.address)
        if target is None:
            # same surface as a refused connect on a real RpcClient
            raise ConnectionLost(f"cannot connect to {self.address}")
        self._net.sends[method] = self._net.sends.get(method, 0) + 1
        fut: Future = Future()
        if self._net.drop_relay_publishes and method == "RelayPublish":
            fut.set_result(True)  # counted, not delivered (bulk build-up)
            return fut
        try:
            fut.set_result(getattr(target, f"Handle{method}")(payload))
        except ConnectionLost:
            raise
        except Exception as e:  # noqa: BLE001 — handler error, peer alive
            fut.set_exception(e)
        return fut

    def call_async_frame(self, parts) -> Future:
        body = bytearray(b"".join(bytes(p) for p in parts))
        method, payload = decode_body(body)
        return self.call_async(method, payload)

    def call(self, method: str, payload=None, timeout=None, **_kw):
        return self.call_async(method, payload).result()

    def notify(self, method: str, payload=None):
        try:
            self.call_async(method, payload)
        except ConnectionLost:
            pass


class SimNet:
    """In-process 'network': fake addresses -> handler objects."""

    def __init__(self):
        self.registry: Dict[Addr, object] = {}
        self.sends: Dict[str, int] = {}      # method -> total sends
        self.drop_relay_publishes = False
        self._clients: Dict[Addr, _SimClient] = {}

    def get(self, address) -> _SimClient:
        address = tuple(address)
        cli = self._clients.get(address)
        if cli is None:
            cli = self._clients[address] = _SimClient(self, address)
        return cli

    def invalidate(self, address):
        self._clients.pop(tuple(address), None)

    def close_all(self):
        self._clients.clear()


class SkeletonRaylet:
    """Report loop + view application only — no worker pool, no object
    store, no threads.  ``tick()`` is one resource-report round trip; view
    application is the shared ``cluster_view`` protocol over a dict."""

    def __init__(self, gcs: GcsServer, net: SimNet, index: int,
                 resources: Optional[Dict[str, float]] = None):
        self.gcs = gcs
        self.net = net
        self.node_id = NodeID.random()
        self.address: Addr = ("sim-raylet", index)
        self.resources = dict(resources or {"CPU": 1.0})
        self.available = dict(self.resources)
        self.view: Dict[NodeID, dict] = {}
        self._store = DictViewStore(self.view)
        self.view_version = -1
        self.alive = True
        self.restarts = 0
        self.events_seen: List[dict] = []
        self.relay_sends = 0
        net.registry[self.address] = self

    # -- sync plane -------------------------------------------------------

    def register(self):
        reply = self.gcs.HandleRegisterNode({
            "node_id": self.node_id, "address": self.address,
            "resources": dict(self.resources), "labels": {},
            "is_head": False,
        })
        self._apply(reply)
        return reply

    def tick(self, force_full: bool = False, apply_reply: bool = True):
        """One report tick.  ``force_full`` asks for a whole snapshot every
        time (known_version=-1) — the pre-delta behavior, kept as the A/B
        baseline.  ``apply_reply=False`` simulates a dropped reply: the
        GCS saw the report but this raylet learned nothing."""
        known = -1 if force_full else self.view_version
        reply = self.gcs.HandleReportResources({
            "node_id": self.node_id, "available": dict(self.available),
            "known_version": known,
        })
        if reply.get("restart"):
            self.restarts += 1
            self.register()
            return reply
        if apply_reply:
            self._apply(reply)
        return reply

    def _apply(self, reply):
        self.view_version = apply_sync_reply(
            reply, self._store, self.node_id, self.view_version)

    # -- relay plane (mirrors Raylet.HandleRelayPublish) ------------------

    def HandleRelayPublish(self, req):
        frame = req.get("frame")
        if not isinstance(frame, (bytes, bytearray)):
            frame = bytes(frame)
        subtree = [tuple(a) for a in (req.get("subtree") or ())]
        if subtree:
            self._relay_forward(frame, subtree)
        self.events_seen.append(pickle.loads(frame))
        return True

    def _relay_forward(self, frame: bytes, subtree: List[Addr]):
        # same tree shape as Raylet._relay_forward (via the shared
        # tree_partition), but synchronous: SimNet surfaces dead peers as
        # an immediate ConnectionLost, so the production forwarder's
        # async done-callback fallback leg has no sim equivalent — the
        # real-socket leg is covered by
        # tests/test_control_plane.py::test_real_raylets_delta_sync_and_relay_plane
        fanout = self.gcs.config.pubsub_tree_fanout
        for group in tree_partition(subtree, fanout):
            head, rest = group[0], group[1:]
            try:
                self.net.get(head).call_async(
                    "RelayPublish", {"frame": frame, "subtree": rest})
            except ConnectionLost:
                # dead child: deliver its subtree directly (same fallback
                # the production relay applies; like production, only
                # sends that went out are counted)
                for t in rest:
                    try:
                        self.net.get(t).call_async(
                            "RelayPublish", {"frame": frame, "subtree": []})
                    except ConnectionLost:
                        continue
                    runtime_metrics.inc_relay_publish("fallback")
                continue
            self.relay_sends += 1
            runtime_metrics.inc_relay_publish("relay")


class MegaClusterHarness:
    """One real GCS + N skeleton raylets, ticked explicitly.

    Typical session::

        h = MegaClusterHarness(num_nodes=1000)
        h.build()                       # register everyone
        h.tick_all()                    # settle to the current version
        stats = h.tick_all(rounds=5)    # steady state: empty deltas
        h.drain_node(h.skeletons[3]); h.kill_node(h.skeletons[7])
        lag = h.converge()              # tick rounds until views match
        h.close()
    """

    def __init__(self, num_nodes: int,
                 fanout: Optional[int] = None,
                 changelog_len: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None):
        cfg = RayTpuConfig()
        # ticks are driven manually — the wall-clock health sweep must
        # never declare a paused simulation dead
        cfg.health_check_failure_threshold = 1_000_000_000
        cfg.heartbeat_interval_s = 3600.0
        if fanout is not None:
            cfg.pubsub_tree_fanout = fanout
        if changelog_len is not None:
            cfg.cluster_view_changelog_len = changelog_len
        self.net = SimNet()
        self.gcs = GcsServer(config=cfg)
        # route the pubsub plane through the in-process network (relay
        # targets carry sim addresses only this net can reach)
        self.gcs.pubsub._pool = self.net
        self._probe_seq = 0
        self.skeletons: List[SkeletonRaylet] = [
            SkeletonRaylet(self.gcs, self.net, i, resources)
            for i in range(num_nodes)
        ]

    # -- lifecycle --------------------------------------------------------

    def build(self):
        """Register every skeleton.  Relay deliveries are suppressed (but
        still counted) during the storm — 1000 registrations each fanning
        a NODE-alive event to every earlier node is O(N^2) deliveries the
        scaling measurements don't need."""
        self.net.drop_relay_publishes = True
        try:
            for s in self.skeletons:
                s.register()
        finally:
            self.net.drop_relay_publishes = False

    def close(self):
        self.gcs.shutdown()
        self.net.registry.clear()
        self.net.close_all()

    # -- ticking + metering ----------------------------------------------

    def alive_skeletons(self) -> List[SkeletonRaylet]:
        return [s for s in self.skeletons if s.alive]

    def tick_all(self, rounds: int = 1, force_full: bool = False) -> dict:
        """Drive ``rounds`` full report rounds; returns the metered cost:
        sync bytes by kind (off the production counters) and GCS handler
        wall time, totalled over every tick."""
        before = runtime_metrics.sync_snapshot()
        handler_s = 0.0
        ticks = 0
        for _ in range(rounds):
            for s in self.alive_skeletons():
                t0 = time.perf_counter()
                s.tick(force_full=force_full)
                handler_s += time.perf_counter() - t0
                ticks += 1
        after = runtime_metrics.sync_snapshot()
        return {
            "ticks": ticks,
            "gcs_handler_s": handler_s,
            "delta_bytes": after["delta_bytes"] - before["delta_bytes"],
            "full_bytes": after["full_bytes"] - before["full_bytes"],
        }

    # -- churn ------------------------------------------------------------

    def add_nodes(self, n: int) -> List[SkeletonRaylet]:
        added = []
        for i in range(n):
            s = SkeletonRaylet(self.gcs, self.net,
                               len(self.skeletons) + i, None)
            s.register()
            added.append(s)
        self.skeletons.extend(added)
        return added

    def drain_node(self, s: SkeletonRaylet, reason: str = "sim drain"):
        self.gcs.HandleDrainNode({"node_id": s.node_id, "reason": reason})

    def kill_node(self, s: SkeletonRaylet, reason: str = "sim kill",
                  notify_gcs: bool = True):
        """Crash a node: unreachable immediately; the GCS hears about it
        only when ``notify_gcs`` (else it keeps publishing through/to the
        corpse — the dead-relay fallback scenario)."""
        s.alive = False
        self.net.registry.pop(s.address, None)
        if notify_gcs:
            self.gcs.HandleNodeDead({"node_id": s.node_id, "reason": reason})

    # -- convergence ------------------------------------------------------

    def gcs_states(self) -> Dict[NodeID, str]:
        with self.gcs._lock:
            return {nid: snap["state"]
                    for nid, snap in self.gcs._node_snaps.items()}

    def diverged(self) -> List[tuple]:
        """(skeleton_index, why) for every live skeleton whose applied view
        disagrees with the GCS's — empty means converged."""
        expect = self.gcs_states()
        bad = []
        for i, s in enumerate(self.skeletons):
            if not s.alive:
                continue
            want = {nid: st for nid, st in expect.items()
                    if nid != s.node_id}
            if set(s.view) != set(want):
                bad.append((i, "node-set mismatch"))
                continue
            for nid, st in want.items():
                if s.view[nid]["state"] != st:
                    bad.append((i, f"state mismatch on {nid}"))
                    break
        return bad

    def converge(self, max_rounds: int = 10) -> int:
        """Tick until every live skeleton's view matches the GCS view;
        returns the number of rounds taken (the convergence lag)."""
        for rounds in range(1, max_rounds + 1):
            self.tick_all()
            if not self.diverged():
                return rounds
        raise AssertionError(
            f"views did not converge within {max_rounds} rounds: "
            f"{self.diverged()[:5]}")

    # -- pubsub A/B -------------------------------------------------------

    def publish_probe(self) -> dict:
        """Publish one control event through the NODE channel and return
        {root_sends, relay_sends, fallback_sends, delivered}: the GCS-side
        fan-out cost (root) vs what the relay tree carried, plus how many
        live skeletons actually received it."""
        self._probe_seq += 1
        seq = self._probe_seq
        before = runtime_metrics.sync_snapshot()["relay_publishes"]
        self.gcs.pubsub.publish(
            "NODE", {"event": "sim-probe", "node_id": None, "seq": seq})
        after = runtime_metrics.sync_snapshot()["relay_publishes"]
        delivered = sum(
            1 for s in self.skeletons if s.alive
            and any(e.get("message", {}).get("seq") == seq
                    for e in s.events_seen))
        return {
            "root_sends": after.get("root", 0) - before.get("root", 0),
            "relay_sends": after.get("relay", 0) - before.get("relay", 0),
            "fallback_sends": (after.get("fallback", 0)
                               - before.get("fallback", 0)),
            "delivered": delivered,
        }
