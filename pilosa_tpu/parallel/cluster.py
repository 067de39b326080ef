"""Cluster topology: nodes, partitions, replica placement.

Parity with /root/reference/cluster.go: the column space is sharded into
2^20-wide slices; (index, slice) hashes to one of PartitionN partitions
via fnv64a, and a partition maps to ReplicaN consecutive nodes on the
ring chosen by jump consistent hash (cluster.go:198-277).

The same math places slices onto TPU devices in the mesh plane
(parallel.mesh): a device mesh is just a cluster whose "nodes" are
devices, so placement stays consistent between the host fan-out path and
the device-sharded path.
"""

from __future__ import annotations

import random
import threading
from typing import Dict, List, Optional, Set, Tuple

DEFAULT_PARTITION_N = 16
DEFAULT_REPLICA_N = 1

# Bounds on PartitionTable: slices kept per table (2^20 slices = 2^40
# columns; a slice beyond that hashes on every read), and tables kept
# per cluster (index names reach the executor from the wire).
PARTITION_TABLE_SLICES = 1 << 20
PARTITION_TABLES = 1024

# Membership lifecycle: JOINING -> ACTIVE -> LEAVING -> DOWN. ACTIVE
# serializes as "UP" — the reference's wire literal, which every status
# consumer already speaks. JOINING nodes are in the TARGET ring (they
# will own data once migration cuts over) but not the serving ring;
# LEAVING nodes are the mirror image: they keep serving until their
# fragments are handed off, then drop out.
NODE_STATE_UP = "UP"
NODE_STATE_ACTIVE = NODE_STATE_UP
NODE_STATE_DOWN = "DOWN"
NODE_STATE_JOINING = "JOINING"
NODE_STATE_LEAVING = "LEAVING"

# States that may serve queries (the rebalancer keeps LEAVING nodes on
# the hook until cutover).
SERVING_STATES = (NODE_STATE_UP, NODE_STATE_LEAVING)

# Legal lifecycle edges. Liveness collapses (anything -> DOWN) ride the
# mark_unreachable fast path; everything else must be a listed edge so
# a buggy admin sequence fails loudly instead of corrupting placement.
_TRANSITIONS = {
    NODE_STATE_JOINING: {NODE_STATE_UP, NODE_STATE_DOWN},
    NODE_STATE_UP: {NODE_STATE_LEAVING, NODE_STATE_DOWN},
    NODE_STATE_LEAVING: {NODE_STATE_UP, NODE_STATE_DOWN},
    NODE_STATE_DOWN: {NODE_STATE_JOINING, NODE_STATE_UP},
}

_FNV64_OFFSET = 0xCBF29CE484222325
_FNV64_PRIME = 0x100000001B3
_MASK64 = 0xFFFFFFFFFFFFFFFF


def fnv64a(data: bytes) -> int:
    h = _FNV64_OFFSET
    for b in data:
        h ^= b
        h = (h * _FNV64_PRIME) & _MASK64
    return h


def partition_of(index: str, slice_: int, partition_n: int) -> int:
    """(index, slice) -> partition id via fnv64a over index bytes +
    big-endian slice (reference cluster.go:198-207)."""
    data = index.encode() + int(slice_).to_bytes(8, "big")
    return fnv64a(data) % partition_n


class PartitionTable(dict):
    """slice -> partition of one index under one `partition_n`: a miss
    hashes once and keeps the answer. `partition_of` is pure, so nothing
    ever invalidates an entry; a read takes no lock, and two threads
    that miss the same slice store the same value (one dict store each,
    atomic under the GIL)."""

    __slots__ = ("index", "partition_n")

    def __init__(self, index: str, partition_n: int):
        super().__init__()
        self.index, self.partition_n = index, partition_n

    def __missing__(self, slice_: int) -> int:
        p = partition_of(self.index, slice_, self.partition_n)
        if len(self) < PARTITION_TABLE_SLICES:
            self[slice_] = p
        return p


class Node:
    """One cluster member (reference cluster.go:39-57)."""

    def __init__(self, host: str, internal_host: str = "",
                 state: str = NODE_STATE_UP):
        self.host = host
        self.internal_host = internal_host
        self.state = state

    def set_state(self, state: str):
        """Raw setter — liveness feeds (status poll, tests) that only
        speak UP/DOWN. Lifecycle changes go through transition()."""
        self.state = state

    def transition(self, state: str):
        """Validated lifecycle edge; raises ValueError on an illegal
        transition (e.g. JOINING -> LEAVING)."""
        if state == self.state:
            return
        if state not in _TRANSITIONS.get(self.state, ()):
            raise ValueError(
                f"illegal node transition {self.state} -> {state} "
                f"for {self.host}")
        self.state = state

    def mark_live(self):
        """Liveness signal: a reachable node that was DOWN comes back
        UP. JOINING/LEAVING are lifecycle states the rebalancer owns —
        a liveness ping must not promote a node mid-migration."""
        if self.state == NODE_STATE_DOWN:
            self.state = NODE_STATE_UP

    def mark_unreachable(self):
        """Lost liveness collapses any state to DOWN (a JOINING node
        that dies mid-migration is dropped from the join; the operator
        re-issues once it's back)."""
        self.state = NODE_STATE_DOWN

    def to_dict(self) -> dict:
        return {"host": self.host, "internalHost": self.internal_host}

    def __repr__(self):
        return f"Node({self.host!r})"


class JmpHasher:
    """Jump consistent hash (Lamping & Veach), the reference's default
    placement hash (cluster.go:266-277)."""

    def hash(self, key: int, n: int) -> int:
        key &= _MASK64
        b, j = -1, 0
        while j < n:
            b = j
            key = (key * 2862933555777941757 + 1) & _MASK64
            j = int(float(b + 1) * (float(1 << 31) / float((key >> 33) + 1)))
        return b


class ModHasher:
    """key % n — deterministic fake for tests (reference cluster_test.go)."""

    def hash(self, key: int, n: int) -> int:
        return key % n


class ConstHasher:
    """Always the same bucket — test fake (reference cluster_test.go)."""

    def __init__(self, i: int = 0):
        self.i = i

    def hash(self, key: int, n: int) -> int:
        return self.i


class Cluster:
    """Node list + placement math (reference cluster.go:121-254)."""

    def __init__(self, nodes: Optional[List[Node]] = None,
                 hasher=None,
                 partition_n: int = DEFAULT_PARTITION_N,
                 replica_n: int = DEFAULT_REPLICA_N):
        self.nodes: List[Node] = nodes or []
        self.hasher = hasher or JmpHasher()
        self.partition_n = partition_n
        self.replica_n = replica_n
        # Live membership, fed by the gossip/nodeset layer; None means
        # "no liveness source, treat everyone as up".
        self.node_set_hosts: Optional[List[str]] = None
        # Cutover ledger: (index, slice) pairs whose migrated copy the
        # new owner has acknowledged (checksum-verified) — those route
        # on the TARGET ring; everything else routes on the serving
        # ring until then, so queries keep answering mid-migration.
        self._handoff: Set[Tuple[str, int]] = set()
        self._handoff_mu = threading.Lock()
        self._partition_tables: Dict[Tuple[str, int], PartitionTable] = {}

    # -- membership ----------------------------------------------------------

    def hosts(self) -> List[str]:
        return [n.host for n in self.nodes]

    def node_by_host(self, host: str) -> Optional[Node]:
        for n in self.nodes:
            if n.host == host:
                return n
        return None

    def mark_unreachable(self, host: str) -> bool:
        """Liveness collapse by host — the failure-detector feeds
        (status poll, gossip, an OPENING circuit breaker) all converge
        here so the write path stops paying per-write timeouts to a
        node everyone already knows is down. Returns True on an actual
        state change (was not already DOWN)."""
        n = self.node_by_host(host)
        if n is None or n.state == NODE_STATE_DOWN:
            return False
        n.mark_unreachable()
        return True

    def mark_live(self, host: str) -> bool:
        """Liveness recovery by host (DOWN -> UP only; lifecycle
        states belong to the rebalancer). Returns True when the node
        actually came back — callers use that edge to wake hint
        drainers immediately instead of on their timer."""
        n = self.node_by_host(host)
        if n is None or n.state != NODE_STATE_DOWN:
            return False
        n.mark_live()
        return True

    def node_states(self) -> Dict[str, str]:
        """host -> lifecycle state, degraded to DOWN when the liveness
        feed no longer sees the host (reference cluster.go:156-169)."""
        live = set(self.node_set_hosts if self.node_set_hosts is not None
                   else self.hosts())
        return {
            n.host: n.state if n.host in live else NODE_STATE_DOWN
            for n in self.nodes
        }

    # -- resize lifecycle ----------------------------------------------------

    def resizing(self) -> bool:
        """True while any node is mid-lifecycle (JOINING/LEAVING) —
        i.e. while the serving ring and the target ring differ."""
        return any(n.state in (NODE_STATE_JOINING, NODE_STATE_LEAVING)
                   for n in self.nodes)

    def begin_join(self, host: str) -> Node:
        """Admit `host` as JOINING: it enters the target ring and will
        own data after migration, but serves nothing yet."""
        n = self.node_by_host(host)
        if n is None:
            n = Node(host, state=NODE_STATE_JOINING)
            self.nodes.append(n)
        elif n.state == NODE_STATE_DOWN:
            n.transition(NODE_STATE_JOINING)
        return n

    def begin_leave(self, host: str) -> Node:
        """Mark `host` LEAVING: it keeps serving its slices until each
        is handed off to the new owners, then drops out."""
        n = self.node_by_host(host)
        if n is None:
            raise ValueError(f"unknown node: {host}")
        n.transition(NODE_STATE_LEAVING)
        return n

    def complete_resize(self):
        """Cutover epilogue: JOINING nodes become ACTIVE, LEAVING
        nodes drop out of the ring entirely, and the per-slice handoff
        ledger resets (both rings are equal again)."""
        kept = []
        for n in self.nodes:
            if n.state == NODE_STATE_JOINING:
                n.transition(NODE_STATE_UP)
            if n.state == NODE_STATE_LEAVING:
                continue
            kept.append(n)
        self.nodes = kept
        with self._handoff_mu:
            self._handoff.clear()

    def mark_handed_off(self, index: str, slice_: int):
        with self._handoff_mu:
            self._handoff.add((index, int(slice_)))

    def handed_off(self, index: str, slice_: int) -> bool:
        with self._handoff_mu:
            return (index, int(slice_)) in self._handoff

    def handoff_count(self) -> int:
        with self._handoff_mu:
            return len(self._handoff)

    def serving_ring(self) -> List[Node]:
        """Nodes queries may route to today: everyone but JOINING
        (LEAVING still serves until its slices hand off)."""
        ring = [n for n in self.nodes if n.state != NODE_STATE_JOINING]
        return ring or self.nodes

    def target_ring(self) -> List[Node]:
        """Post-rebalance ownership: JOINING in, LEAVING out."""
        ring = [n for n in self.nodes if n.state != NODE_STATE_LEAVING]
        return ring or self.nodes

    # -- placement -----------------------------------------------------------

    def partition(self, index: str, slice_: int) -> int:
        return partition_of(index, slice_, self.partition_n)

    def partition_table(self, index: str) -> PartitionTable:
        """`partition(index, ·)` as a table, for a caller that asks it
        for many slices on every query. Keyed by (index, partition_n),
        so a table is never read under another `partition_n`."""
        key = (index, self.partition_n)
        table = self._partition_tables.get(key)
        if table is None:
            if len(self._partition_tables) >= PARTITION_TABLES:
                self._partition_tables = {}
            table = self._partition_tables.setdefault(
                key, PartitionTable(*key))
        return table

    def placement_rings(self, index: str
                        ) -> Tuple[List[Node], List[Node], frozenset]:
        """What `_placement_ring` answers slice by slice, read once:
        the ring of a slice not handed off, the ring of a handed-off
        one, and the slices of `index` the ledger holds (one lock).
        Outside a resize both rings are the node list and the set is
        empty."""
        if not self.resizing():
            return self.nodes, self.nodes, frozenset()
        with self._handoff_mu:
            handed = frozenset(s for i, s in self._handoff if i == index)
        return self.serving_ring(), self.target_ring(), handed

    def _owners_over(self, ring: List[Node],
                     partition_id: int) -> List[Node]:
        if not ring:
            return []
        replica_n = min(max(self.replica_n, 1), len(ring))
        primary = self.hasher.hash(partition_id, len(ring))
        return [ring[(primary + i) % len(ring)] for i in range(replica_n)]

    def partition_nodes(self, partition_id: int,
                        ring: Optional[List[Node]] = None) -> List[Node]:
        """Replica owners: jump-hash primary + consecutive ring nodes
        (reference cluster.go:220-240). `ring` overrides the node list
        (the rebalancer diffs serving vs target ownership)."""
        return self._owners_over(
            self.nodes if ring is None else ring, partition_id)

    def _placement_ring(self, index: str, slice_: int) -> List[Node]:
        """The ring THIS fragment routes on: during a resize, handed-off
        slices use the target ring (new owners have a verified copy),
        everything else stays on the serving ring — so queries keep
        answering throughout a join/leave."""
        if not self.resizing():
            return self.nodes
        if self.handed_off(index, slice_):
            return self.target_ring()
        return self.serving_ring()

    def fragment_nodes(self, index: str, slice_: int) -> List[Node]:
        return self._owners_over(self._placement_ring(index, slice_),
                                 self.partition(index, slice_))

    def fragment_nodes_over(self, ring: List[Node], index: str,
                            slice_: int) -> List[Node]:
        """Ownership over an explicit ring (rebalancer plan math)."""
        return self._owners_over(ring, self.partition(index, slice_))

    def owns_fragment(self, host: str, index: str, slice_: int) -> bool:
        return any(n.host == host for n in self.fragment_nodes(index, slice_))

    def owns_slices(self, index: str, max_slice: int, host: str) -> List[int]:
        """Slices whose PRIMARY owner is host (reference cluster.go:243-254
        — primary only, not replicas)."""
        out = []
        for s in range(max_slice + 1):
            ring = self._placement_ring(index, s)
            p = self.partition(index, s)
            primary = self.hasher.hash(p, len(ring))
            if ring[primary].host == host:
                out.append(s)
        return out

    def status(self) -> dict:
        return {"nodes": [{"host": n.host, "state": n.state}
                          for n in self.nodes]}


def owner_tier(host: str, local_host: str,
               ici_hosts=None) -> str:
    """Locality tier of serving a slice owned by `host` from the node
    at `local_host`: `local` (same chip / same process), `ici` (a
    same-pod peer — its shard is one psum over the interconnect away),
    or `http` (cross-node RPC is the only road). The executor's
    placement (`_slices_by_node`) and `?explain=true` both classify
    through this one function so the route metric's `tier` label and
    the explain output can never disagree."""
    if host == local_host:
        return "local"
    if ici_hosts and host in ici_hosts:
        return "ici"
    return "http"


def preferred_owner(owners: List[Node], breaker_state=None,
                    prefer: Optional[str] = None,
                    ici_hosts=None) -> Node:
    """Routing preference among a slice's replica owners: ACTIVE nodes
    whose circuit breaker is closed, then any ACTIVE node, then LEAVING
    nodes (still serving until cutover), then anyone — liveness,
    lifecycle state, and breaker state are all advisory, so a slice
    whose owners all look bad still tries one (the executor's reactive
    re-split is the authority). `breaker_state(host) -> str` comes from
    the cluster client; None means no breaker info. Within the winning
    tier, `prefer` (the coordinating node's own host) breaks the tie —
    a locally-held replica serves locally instead of paying an HTTP
    hop, which is what keeps query QPS flat across a resize when the
    replica sets overlap. `ici_hosts` is the second rung of the same
    ladder: when no locally-held replica wins, a same-pod-ICI owner
    beats a cross-pod one (the executor folds its slices into the
    local mesh dispatch instead of an HTTP leg)."""

    def pick(cands: List[Node]) -> Node:
        if prefer is not None:
            for o in cands:
                if o.host == prefer:
                    return o
        if ici_hosts:
            for o in cands:
                if o.host in ici_hosts:
                    return o
        return cands[0]

    up = [o for o in owners if o.state == NODE_STATE_UP]
    if breaker_state is not None:
        healthy = [o for o in up if breaker_state(o.host) == "closed"]
        if healthy:
            return pick(healthy)
    if up:
        return pick(up)
    leaving = [o for o in owners if o.state == NODE_STATE_LEAVING]
    return pick(leaving or owners)


def pick_read_replica(owners: List[Node], breaker_state=None,
                      staleness_ok=None, queue_depth=None,
                      prefer: Optional[str] = None,
                      ici_hosts=None, rnd=None,
                      node_ok=None) -> Optional[Node]:
    """Bounded-staleness read placement (ISSUE 18): spread an eligible
    read over EVERY in-sync replica instead of pinning it to
    `preferred_owner`'s deterministic pick. Eligibility is strict —
    ACTIVE, breaker closed, and `staleness_ok(host) -> bool` (the
    EpochTracker's writes-behind check) — because this path trades
    freshness for throughput only within the client's stated bound;
    anything weaker falls back to the owner ladder, never sideways to
    a staler replica.

    Among eligible replicas: a locally-held replica always wins (free
    is better than balanced), then power-of-two-choices by gossiped
    `queue_depth(host) -> int`, with ICI locality as the tie-break —
    p2c gives near-best-of-N load spreading from two samples without
    herding every coordinator onto the same momentarily-idle replica
    the way full-min selection would.

    Returns None when no replica is eligible; the caller falls back to
    `preferred_owner` (strict semantics) and counts the fallback."""
    up = [o for o in owners if o.state == NODE_STATE_UP]
    cands = up
    if breaker_state is not None:
        cands = [o for o in cands if breaker_state(o.host) == "closed"]
    if staleness_ok is not None:
        cands = [o for o in cands
                 if o.host == prefer or staleness_ok(o.host)]
    if node_ok is not None:
        # Liveness-plane filter (ISSUE 20): `node_ok(host) -> bool` is
        # the gossiped per-node health verdict (HEALTH.peer_ready) —
        # a peer advertising a stalled critical subsystem is wedged,
        # not down, so membership still shows it UP and the breaker
        # may not have opened yet. Advisory: unknown/stale peers pass.
        cands = [o for o in cands
                 if o.host == prefer or node_ok(o.host)]
    if not cands:
        return None
    if prefer is not None:
        for o in cands:
            if o.host == prefer:
                return o
    if len(cands) == 1:
        return cands[0]
    if rnd is None:
        rnd = random
    a, b = rnd.sample(cands, 2)
    qd = queue_depth or (lambda _h: 0)
    da, db = qd(a.host), qd(b.host)
    if da != db:
        return a if da < db else b
    if ici_hosts:
        if a.host in ici_hosts and b.host not in ici_hosts:
            return a
        if b.host in ici_hosts and a.host not in ici_hosts:
            return b
    return a


def new_test_cluster(n: int) -> Cluster:
    """n fake nodes host0..host{n-1} with ModHasher — the reference's
    deterministic test cluster (cluster_test.go:146-177)."""
    return Cluster(
        nodes=[Node(f"host{i}") for i in range(n)],
        hasher=ModHasher(),
        partition_n=n,
        replica_n=1,
    )
