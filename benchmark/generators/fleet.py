"""The node fleet: thousands of in-process node agents on shared RPC conns.

The benchmark's own copy of what it needs from nomad_tpu/simcluster/
simnode.py (PERF.md lists the original for a later PR to delete): a
fingerprint-shaped registration in batched tranches, heap-paced TTL
heartbeat renewals, and seeded re-registrations. Only the server's
client-facing surface is used: ``Node.BatchRegister`` and
``Node.BatchHeartbeat`` over the RPC tier.
"""

from __future__ import annotations

import heapq
import threading
import time
from typing import Dict, List, Sequence

from nomad_tpu import structs
from nomad_tpu.api.codec import to_dict
from nomad_tpu.rpc import ConnPool, RPCError
from nomad_tpu.structs import Node, Resources

BATCH = 500


def node_id(i: int) -> str:
    return f"sim-{i:05d}"


def node_spec(shape: Dict, i: int) -> Dict:
    """Node ``i`` of a configuration's fleet as plain data: what the
    server is told and what the plain reference judges eligibility and
    capacity by. ``shape`` is the configuration file's ``nodes`` group."""
    dcs = shape["datacenters"]
    attrs = dict(shape["attributes"])
    for variant in shape.get("variants", ()):
        if i % int(variant["every"]) == int(variant["every"]) - 1:
            attrs.update(variant["attributes"])
    return {"id": node_id(i), "datacenter": dcs[i % len(dcs)],
            "attributes": attrs, "cpu": int(shape["cpu"]),
            "memory_mb": int(shape["memory_mb"]), "ready": True}


def build_node(shape: Dict, spec: Dict) -> Node:
    return Node(
        id=spec["id"], datacenter=spec["datacenter"], name=spec["id"],
        attributes=dict(spec["attributes"]),
        resources=Resources(
            cpu=spec["cpu"], memory_mb=spec["memory_mb"],
            disk_mb=int(shape.get("disk_mb", 100 * 1024)),
            iops=int(shape.get("iops", 150))),
        status=structs.NODE_STATUS_READY,
    )


class Fleet:
    """A fleet of simulated nodes against one server RPC address."""

    def __init__(self, addr: str, n_conns: int = 2,
                 beat_fraction: float = 0.8, tick: float = 0.25,
                 rpc_timeout: float = 60.0):
        self.addr = addr
        self.beat_fraction = beat_fraction
        self.tick = tick
        self.rpc_timeout = rpc_timeout
        self._pools = [ConnPool(timeout=rpc_timeout)
                       for _ in range(max(1, n_conns))]
        self._rr = 0
        self._lock = threading.Lock()
        self.granted: Dict[str, float] = {}
        self._due: List[tuple] = []
        self._stop = threading.Event()
        self._beater = None
        self.beat_errors = 0

    def call(self, method: str, args: dict):
        with self._lock:
            self._rr += 1
            pool = self._pools[self._rr % len(self._pools)]
        return pool.call(self.addr, method, args, timeout=self.rpc_timeout)

    def register(self, nodes: Sequence[Node]) -> None:
        """Register ``nodes`` in batched tranches; granted TTLs arm the
        beat schedule (a re-registration re-arms it)."""
        for lo in range(0, len(nodes), BATCH):
            out = self.call("Node.BatchRegister", {
                "nodes": [to_dict(n) for n in nodes[lo:lo + BATCH]]})
            now = time.monotonic()
            with self._lock:
                for nid, ttl in out.get("heartbeat_ttls", {}).items():
                    ttl = float(ttl)
                    if ttl <= 0:
                        continue
                    known = nid in self.granted
                    self.granted[nid] = ttl
                    if not known:
                        heapq.heappush(
                            self._due, (now + self.beat_fraction * ttl, nid))

    def start_heartbeats(self) -> None:
        if self._beater is None:
            self._beater = threading.Thread(
                target=self._beat_loop, daemon=True, name="bench-beats")
            self._beater.start()

    def _beat_loop(self) -> None:
        while not self._stop.wait(self.tick):
            now = time.monotonic()
            due: List[str] = []
            with self._lock:
                while self._due and self._due[0][0] <= now:
                    due.append(heapq.heappop(self._due)[1])
            for lo in range(0, len(due), BATCH):
                tranche = due[lo:lo + BATCH]
                try:
                    out = self.call("Node.BatchHeartbeat",
                                    {"node_ids": tranche})
                except RPCError:
                    # A real client keeps beating at its stale cadence
                    # through transient failures.
                    self.beat_errors += 1
                    with self._lock:
                        for nid in tranche:
                            heapq.heappush(
                                self._due, (now + self.tick * 2, nid))
                    continue
                ttls = out.get("heartbeat_ttls", {})
                with self._lock:
                    for nid in tranche:
                        ttl = float(ttls.get(nid, 0.0) or 0.0)
                        if ttl > 0:
                            self.granted[nid] = ttl
                        else:
                            ttl = self.granted.get(nid, 0.0)
                            if ttl <= 0:
                                continue
                        heapq.heappush(
                            self._due,
                            (time.monotonic() + self.beat_fraction * ttl,
                             nid))

    def stop(self) -> None:
        self._stop.set()
        if self._beater is not None:
            self._beater.join(timeout=2.0)
        for pool in self._pools:
            pool.shutdown()
