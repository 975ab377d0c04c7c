"""The node fleet: thousands of in-process node agents on shared RPC conns.

The benchmark's own copy of what it needs from nomad_tpu/simcluster/
simnode.py (PERF.md lists the original for a later PR to delete): a
fingerprint-shaped registration in batched tranches, heap-paced TTL
heartbeat renewals (after one renewal of the whole fleet in set-up:
``Fleet.renew_all``), and seeded re-registrations. Only the server's
client-facing surface is used: ``Node.BatchRegister`` and
``Node.BatchHeartbeat`` over the RPC tier.
"""

from __future__ import annotations

import functools
import heapq
import threading
import time
from typing import Dict, List, Sequence, Tuple

from nomad_tpu import structs
from nomad_tpu.api.codec import to_dict
from nomad_tpu.rpc import ConnPool, RPCError
from nomad_tpu.structs import Node, Resources

BATCH = 500


def node_id(i: int) -> str:
    return f"sim-{i:05d}"


def node_shapes(shape: Dict) -> List[Dict]:
    """The machine table of a configuration's ``nodes`` group: its
    ``shapes`` (each ``{count, cpu, memory_mb, attributes}``), or, where
    ``count``, ``cpu`` and ``memory_mb`` stand on the group itself, that
    one shape."""
    return shape.get("shapes") or [shape]


def node_count(shape: Dict) -> int:
    """The fleet's size: the sum of its shapes' counts."""
    return sum(int(s["count"]) for s in node_shapes(shape))


@functools.lru_cache(maxsize=8)
def deal_shapes(counts: Tuple[int, ...]) -> Tuple[int, ...]:
    """Which shape each node index gets, by largest remainders and no
    seed: index ``i`` goes to the shape that is furthest behind its share
    of the first ``i + 1`` indices (``count x (i + 1) / n`` less what it
    has been dealt; the earlier in the table on a tie). Every shape so
    holds its share of every prefix to within a node or two, and any run
    of consecutive indices holds the shapes in their proportions to
    within three: first fit does not meet all the large machines first."""
    n = sum(counts)
    dealt = [0] * len(counts)
    out = []
    for k in range(1, n + 1):
        s = max(range(len(counts)),
                key=lambda j: (counts[j] * k - dealt[j] * n, -j))
        dealt[s] += 1
        out.append(s)
    return tuple(out)


def node_spec(shape: Dict, i: int) -> Dict:
    """Node ``i`` of a configuration's fleet as plain data: what the
    server is told and what the plain reference judges eligibility and
    capacity by. ``shape`` is the configuration file's ``nodes`` group;
    the node's own shape is the one ``deal_shapes`` gives index ``i``.
    Its attributes are the group's, then its shape's, then those of each
    variant that takes it."""
    dcs = shape["datacenters"]
    shapes = node_shapes(shape)
    own = shapes[deal_shapes(tuple(int(s["count"]) for s in shapes))[i]]
    attrs = dict(shape.get("attributes", {}))
    attrs.update(own.get("attributes", {}))
    for variant in shape.get("variants", ()):
        if i % int(variant["every"]) == int(variant["every"]) - 1:
            attrs.update(variant["attributes"])
    return {"id": node_id(i), "datacenter": dcs[i % len(dcs)],
            "attributes": attrs, "cpu": int(own["cpu"]),
            "memory_mb": int(own["memory_mb"]), "ready": True}


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
        self._beating = threading.Lock()  # held over a pass of renewals
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

    def renew_all(self) -> None:
        """One renewal of every node now, in set-up, and the beat
        schedule armed anew from what it grants. The server scales a TTL
        by the timers it holds when it arms one, so the first nodes of a
        fleet are granted its shortest (10-20 s where the 10,000th gets
        200-400 s), and renewed at ``beat_fraction`` of that they have 2
        s in hand: a host that stands still that long in a run's first
        seconds lets them lapse, the server marks them down and places
        their tasks again, and the run reads more placements than were
        asked. Renewed once with the whole fleet armed, every node holds
        the TTL of a fleet in steady state, and none falls due again
        inside a run."""
        with self._beating:   # no tranche is in flight
            with self._lock:
                ids = sorted(self.granted)
                self._due = []
            self._beat(ids)

    def ttl_range(self) -> Tuple[float, float]:
        """The shortest and the longest TTL now granted."""
        with self._lock:
            ttls = list(self.granted.values()) or [0.0]
        return min(ttls), max(ttls)

    def _beat_loop(self) -> None:
        while not self._stop.wait(self.tick):
            with self._beating:
                now = time.monotonic()
                due: List[str] = []
                with self._lock:
                    while self._due and self._due[0][0] <= now:
                        due.append(heapq.heappop(self._due)[1])
                self._beat(due)

    def _beat(self, due: List[str]) -> None:
        """Renew these nodes in tranches, and arm each one's next beat
        at ``beat_fraction`` of the TTL it was granted."""
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
                            self._due,
                            (time.monotonic() + self.tick * 2, nid))
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
