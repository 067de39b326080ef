"""Executor: recursive PQL evaluation fanned out per-slice.

Parity with /root/reference/executor.go: bitmap calls (Bitmap, Union,
Intersect, Difference, Range) map per-slice and merge; Count sums
per-slice counts; TopN is two-phase (approximate pass, then exact
re-count of the merged candidate ids); SetBit/ClearBit route to every
replica owner of the bit's slice; SetRowAttrs/SetColumnAttrs apply
locally and broadcast to all other nodes. A failed node's slices are
re-split across remaining replicas (executor.go:1140-1151).

The TPU twist: Count over a pure bitmap-op tree takes a fused device
path — the whole expression tree compiles to one XLA computation per
slice batch (pilosa_tpu.parallel.plan), popcounting the combined blocks
without materializing intermediate rows (closing the reference's
materialize-then-count gap, SURVEY.md §3.2 note).
"""

from __future__ import annotations

import os
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from datetime import datetime
from typing import Callable, List, Optional, Sequence

from .core import views_by_time_range
from .core.cache import add_to_pairs
from .core.fragment import TopOptions
from .core.frame import DEFAULT_ROW_LABEL
from .core.index import DEFAULT_COLUMN_LABEL
from .core.row import Row
from .core.view import VIEW_INVERSE, VIEW_STANDARD
from .errors import (
    BroadcastError,
    DeadlineExceededError,
    FrameNotFoundError,
    IndexNotFoundError,
    IndexRequiredError,
    QueryError,
    SliceUnavailableError,
    WriteConsistencyError,
)
from .parallel.cluster import (
    NODE_STATE_DOWN,
    NODE_STATE_UP,
    SERVING_STATES,
    pick_read_replica,
    preferred_owner,
)
from .parallel.epochs import EpochTracker, ResultCache, fragment_key
from .pql import Call, Query
from . import SLICE_WIDTH
from . import fault
from . import obs

# Frame used when a query doesn't specify one (executor.go:35).
DEFAULT_FRAME = "general"

# Lowest count a TopN pass will consider (executor.go:37-39).
MIN_THRESHOLD = 1

# PQL timestamp format (reference TimeFormat "2006-01-02T15:04").
TIME_FORMAT = "%Y-%m-%dT%H:%M"

_WRITE_CALLS = ("ClearBit", "SetBit", "SetValue", "SetRowAttrs",
                "SetColumnAttrs")

# BSI aggregates over integer fields (bsi.<field> views).
_BSI_AGGREGATES = ("Sum", "Min", "Max")

# Shadow-verification counters, keyed "checks:<backend>" /
# "mismatch:<backend>" — exported as pilosa_shadow_checks_total /
# pilosa_shadow_mismatch_total{backend} Prometheus families. A
# mismatch means the device returned a DIFFERENT answer than the host
# roaring fold for the same tree: miscompiled plan, bad staging, or
# silent device fault — the one failure class checksums can't see.
SHADOW_STATS = obs.StatMap()

# Write-consistency outcome counters, keyed "<level>:<outcome>" —
# exported as pilosa_write_consistency_total{level,outcome}. Outcomes:
# ok (all replicas acked), hinted (consistency reached, misses
# journaled as hints), below_consistency (dispatched but too few acks
# — 503 after hints enqueued), rejected_unavailable (too few owners
# reachable, rejected BEFORE local apply).
CONSISTENCY_STATS = obs.StatMap()


def _call_shape(c) -> str:
    """Structural fingerprint of a Call tree — names + frame args,
    row/column ids elided: `Count(Intersect(Bitmap[f],Bitmap[f]))`.
    The flight recorder's shape key (human-readable, bounded
    cardinality — one entry per query SHAPE, not per query)."""
    frame = c.args.get("frame")
    label = f"{c.name}[{frame}]" if isinstance(frame, str) else c.name
    if c.children:
        return (label + "("
                + ",".join(_call_shape(k) for k in c.children) + ")")
    return label


def required_acks(level: str, owners: int) -> int:
    """Replica acks (local apply included) a write needs before it is
    acked to the client."""
    if level == "one":
        return 1
    if level == "all":
        return owners
    return owners // 2 + 1  # quorum


class ExecOptions:
    """Per-Execute context (executor.go:1253-1256).

    `deadline` — absolute time.monotonic() instant by which the whole
    query must finish; every remote hop is given only the REMAINING
    budget and expiry raises DeadlineExceededError instead of riding
    out the flat per-hop client timeout. None = no deadline.
    `partial` — opt-in graceful degradation: a slice with no reachable
    owner is skipped and collected in `missing_slices` instead of
    failing the query with SliceUnavailableError."""

    def __init__(self, remote: bool = False,
                 deadline: Optional[float] = None, partial: bool = False,
                 staleness: float = 0.0):
        self.remote = remote
        self.deadline = deadline
        self.partial = partial
        # Bounded-staleness read budget in seconds (X-Pilosa-Staleness
        # / [cluster] default-read-staleness): > 0 lets the placement
        # layer spread eligible slices over in-sync replicas and the
        # coordinator serve from the epoch-keyed result cache. 0 (the
        # default) is a STRICT read — owner-only placement, no result
        # cache — bit-for-bit the pre-ISSUE-18 path.
        self.staleness = max(0.0, float(staleness))
        # Breaker states snapshotted ONCE per query (satellite of
        # ISSUE 18): every placement decision in this execution — the
        # initial split and any failure re-split — sees the same
        # breaker world, so a breaker flapping half-open mid-query
        # can't flip the pick between legs. None until execute() fills
        # it (or the client has no registry).
        self.breaker_snapshot: Optional[dict] = None
        # Slices this query could not serve (partial mode only); the
        # handler surfaces them as {partial: true, missing_slices}.
        self.missing_slices: List[int] = []
        # Locality-tier footprints, set while the query executes:
        # used_http when any slice group was actually submitted over
        # the HTTP ring (_mapper's remote leg), used_ici when slices
        # owned by a same-pod ICI peer were folded into the local mesh
        # dispatch (_slices_by_node). _record_route derives the
        # query's `tier` label (http > ici > local) from these.
        self.used_http = False
        self.used_ici = False

    def deadline_left(self) -> Optional[float]:
        """Remaining budget in seconds (negative when expired), or
        None when no deadline is set."""
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def check_deadline(self, what: str = "query") -> None:
        left = self.deadline_left()
        if left is not None and left <= 0:
            raise DeadlineExceededError(
                f"{what}: deadline exceeded by {-left * 1e6:.0f}us")


def parse_time(s: str) -> datetime:
    return datetime.strptime(s, TIME_FORMAT)


def _device_top_pairs(frag, min_threshold: int, n: int):
    """Exact top-n (rowID, count) pairs, ordered (count desc, row asc),
    from a fragment's device pool image — or None when any part of the
    device attempt fails (caller serves the host path instead)."""
    import numpy as np

    from .ops.pool import pool_row_counts

    try:
        pool, row_ids = frag.pool
        if len(row_ids) == 0:
            return []
        # num_rows is a static jit arg: pad to the next power of two so
        # growing fragments recompile on doubling, not on every new row
        # (matching the pool's own capacity padding, ops/pool.py).
        padded = 1 << (len(row_ids) - 1).bit_length()
        counts = np.asarray(pool_row_counts(pool, padded))[:len(row_ids)]
    except Exception:  # noqa: BLE001 — device attempt failed: host path
        obs.get_logger("executor").warning(
            "per-fragment device TopN failed; the host path answers",
            exc_info=True)
        return None
    keep = np.nonzero(counts >= min_threshold)[0]
    order = np.lexsort((row_ids[keep], -counts[keep]))
    if n:
        order = order[:n]
    keep = keep[order]
    return [(int(row_ids[i]), int(counts[i])) for i in keep]


def needs_slices(calls: Sequence[Call]) -> bool:
    """True when any call requires per-slice fan-out (executor.go:1281)."""
    return any(c.name not in _WRITE_CALLS for c in calls)


class Executor:
    """Evaluates PQL against a Holder, fanning out across the cluster.

    `client` is the remote-execution seam (reference Executor.HTTPClient
    + exec, executor.go:1000-1083): any object with
    execute_query(node, index, query: str, slices, remote=True) -> list.
    Tests inject fakes here; the HTTP layer injects the real client.
    """

    def __init__(self, holder, host: str = "", cluster=None, client=None,
                 use_device: Optional[bool] = None, max_workers: int = 8,
                 device_min_work: Optional[int] = None,
                 prefer_local_reads: bool = False,
                 mesh_config: Optional[dict] = None,
                 ici_hosts: Optional[Sequence[str]] = None):
        self.holder = holder
        # [mesh] knobs (config.Config.mesh_config()) handed to the
        # MeshManager on construction: HBM budget, headroom, plan
        # quarantine policy. Empty dict = env/auto resolution.
        self.mesh_config = dict(mesh_config or {})
        self.host = host
        self.cluster = cluster
        self.client = client
        # Locality tie-break for slice placement: when on, a healthy
        # locally-held replica serves locally instead of paying the
        # HTTP hop to the ring-order primary. Off by default — the
        # reference routes each slice to ring order, spreading load
        # across replicas, which is right when clients hit every node.
        self.prefer_local_reads = prefer_local_reads
        # Same-pod ICI peers ([cluster] ici-hosts): hosts whose chips
        # share this node's interconnect AND whose data dirs are
        # replicated here (the SPMD deployment shape). Slices the ring
        # assigns to an ICI peer are served from the LOCAL mesh — the
        # collective already spans the pod's devices — so the query
        # pays one psum over the fabric instead of an HTTP leg
        # (_slices_by_node). The local host being listed is harmless.
        self.ici_hosts = frozenset(ici_hosts or ())
        # Write-path replication (ISSUE 13): replica acks required
        # before a mutation acks ("one" | "quorum" | "all"), and the
        # hinted-handoff manager that journals missed replica ops.
        # Both server-wired; a bare executor (unit tests) keeps the
        # legacy fail-on-remote-error behavior while `hints` is None.
        self.write_consistency: str = "quorum"
        self.hints = None
        # Liveness-plane read steering (ISSUE 20): server-wired to
        # HEALTH.peer_ready so follower reads route around a peer
        # whose gossiped health digest says a critical subsystem is
        # stalled. None = no filtering (bare executors, unit tests).
        self.peer_health_ok = None
        # None = auto (device path when available); False = host roaring only.
        self.use_device = use_device
        # Cost-routing threshold (see _route_to_host); None = resolve
        # from PILOSA_TPU_DEVICE_MIN_WORK / the use_device mode.
        self.device_min_work = device_min_work
        self._pool = ThreadPoolExecutor(max_workers=max_workers)
        # Separate pool for per-slice fan-out: _mapper submits node-level
        # tasks to _pool that block on slice-level results, so sharing
        # one bounded pool could deadlock with every worker waiting.
        self._slice_pool = ThreadPoolExecutor(max_workers=max_workers)
        # Mesh serving layer (parallel/serve.py): created on first use
        # when the device backend is on. Count/TopN slice batches route
        # through it as ONE shard_map'd collective; the per-slice paths
        # below remain the fallback.
        self._mesh_mgr = None
        self._mesh_mgr_failed = False
        # SPMD descriptor plane (parallel/spmd.py), set by server wiring
        # when [cluster] type = "spmd": device collectives must be
        # driven through the multi-host descriptor stream, never by
        # this process alone (a unilateral psum over a global mesh
        # hangs every rank).
        self._spmd = None
        # Guards lazy construction: two concurrent first queries must
        # not each build a manager and stage duplicate device images.
        import threading

        self._mesh_mgr_lock = threading.Lock()
        # Generation-validated caches for the cost-routed host count
        # path (plan.HostQueryCache): repeated small queries serve at
        # memo speed instead of re-extracting + re-folding.
        from .parallel.plan import HostQueryCache

        self._host_cache = HostQueryCache()
        # _route_to_host threshold, resolved once (the env lookup is
        # per-query overhead on the small-query path otherwise).
        self._min_work_resolved: Optional[int] = None
        # Backend-aware routing verdict (cpu backend + live native
        # kernels => large folds go to the host C++ path), resolved
        # once — jax.default_backend() and the ctypes load don't change
        # within a process.
        self._cpu_route_native: Optional[bool] = None
        # Route-level Count telemetry: which engine served (memo /
        # host-fold / mesh / roaring) and the end-to-end latency per
        # engine — the backend-labeled latency histogram at /metrics.
        self.route_stats = obs.StatMap()
        # Locality-tier split of the same routes, keyed "route|tier"
        # (tier ∈ local|ici|http): which interconnect the query's
        # slice fan-out actually crossed. Separate map so count_*
        # consumers keep exact keys.
        self.tier_stats = obs.StatMap()
        self._route_hists: dict = {}
        # Query-shape flight recorder (/debug/queryshapes): per
        # plan-signature route/tier/latency aggregation in a bounded
        # ring. The server resizes it from [obs] queryshape-ring.
        self.flight = obs.flight.FlightRecorder()
        # [integrity] shadow-sample-1-in: every Nth device Count/TopN
        # result is recomputed through the host roaring fold and
        # compared (0 = off). itertools.count() next() is atomic under
        # the GIL, so the sampler needs no lock.
        import itertools

        self.shadow_sample = 0
        self._shadow_counter = itertools.count()
        # Read-path resilience plane (ISSUE 18): the replication-epoch
        # tracker (what this coordinator knows about every replica's
        # write progress) and the epoch-keyed whole-query result cache
        # serving bounded-staleness repeats. Both live even on bare
        # executors — they are cheap dicts — and the server wires
        # their knobs ([cluster] result-cache-size, [integrity]
        # result-cache-verify-1-in).
        self.epochs = EpochTracker()
        self.result_cache = ResultCache()
        # Every Nth result-cache hit is recomputed and compared (the
        # PR-10 shadow-verify discipline): a mismatch means an entry
        # survived an epoch bump it should not have. 0 = off.
        self.result_cache_verify_1_in = 16
        self._rc_verify_counter = itertools.count(1)
        # Read-replica pick counters, keyed "pick|staleness_class"
        # (pick ∈ owner|follower|fallback_owner, class ∈
        # strict|bounded) -> pilosa_read_replica_total{replica,
        # staleness} at /metrics.
        self.read_stats = obs.StatMap()
        # "owner_decisions": owner-ladder climbs _slices_by_node made
        # (one per partition and ring of a strict read, one per slice
        # of a bounded spread) -> pilosa_route_owner_decisions_total.
        self.placement_stats = obs.StatMap()

    def set_spmd(self, spmd):
        """Wire the SPMD descriptor plane (rank 0 of a multi-host
        deployment): Count/TopN collectives and bit writes route
        through `spmd`, and the executor shares its MeshManager so
        staging/stats have one home."""
        self._spmd = spmd
        self._mesh_mgr = spmd.manager

    # Set True on SPMD worker ranks (server wiring): a mutation applied
    # here alone would silently diverge this rank's replica from the
    # descriptor-ordered stream — reject so the client retargets rank 0.
    spmd_reject_writes = False

    def _check_writable(self, what: str, opt: "ExecOptions"):
        if self.spmd_reject_writes and not opt.remote:
            raise QueryError(
                f"{what} must be sent to SPMD rank 0 (this is a worker "
                "rank; writes ride the descriptor stream)")

    # -- top level -----------------------------------------------------------

    def execute(self, index: str, q: Query, slices: Optional[Sequence[int]] = None,
                opt: Optional[ExecOptions] = None) -> list:
        """Execute each call serially, returning one result per call
        (executor.go:62-145)."""
        if not index:
            raise IndexRequiredError()
        # Slice-cover derivation is planning work: the max_slice scan
        # is the measurable part of query setup at headline slice
        # counts, so the plan phase brackets it (union-interval merges
        # with the per-call plan bracket in _execute_count).
        with obs.profile.phase("plan"):
            opt = opt or ExecOptions()

            # Snapshot breaker states once per query: placement (the
            # initial split AND any failure re-split) must not re-read
            # a registry a half-open probe is flapping mid-execution.
            if opt.breaker_snapshot is None:
                state = getattr(self.client, "breaker_state", None)
                if callable(state) and self.cluster is not None:
                    opt.breaker_snapshot = {
                        n.host: state(n.host)
                        for n in self.cluster.nodes}

            need = needs_slices(q.calls)
            # Built lazily on the first inverse call: most queries
            # touch no inverse view, and at headline slice counts (960)
            # the eager list was a measurable per-query tax on the
            # routed fast path.
            inverse_slices: Optional[List[int]] = None
            column_label = DEFAULT_COLUMN_LABEL

            idx = self.holder.index(index)
            defaulted = False
            if slices:
                slices = list(slices)
            else:
                slices = []
                if need:
                    if idx is None:
                        raise IndexNotFoundError()
                    defaulted = True
                    slices = list(range(idx.max_slice() + 1))
                    column_label = idx.column_label

        # Bulk attribute insertion fast path (executor.go:857-941).
        if q.calls and all(c.name == "SetRowAttrs" for c in q.calls):
            return self._execute_bulk_set_row_attrs(index, q.calls, opt)

        results = []
        for call in q.calls:
            opt.check_deadline(call.name)
            call_slices = slices
            if call.supports_inverse() and need:
                frame = call.args.get("frame") or DEFAULT_FRAME
                f = self.holder.frame(index, frame)
                if f is None:
                    raise FrameNotFoundError()
                if call.is_inverse(f.row_label, column_label):
                    if inverse_slices is None:
                        # Explicit caller slices keep their original
                        # behavior (inverse calls got the empty list);
                        # only the defaulted path derives the cover.
                        inverse_slices = list(
                            range(idx.max_inverse_slice() + 1)) \
                            if defaulted else []
                    call_slices = inverse_slices
            results.append(self._execute_call(index, call, call_slices, opt))
        return results

    def _execute_call(self, index: str, c: Call, slices: Sequence[int],
                      opt: ExecOptions):
        if c.name == "ClearBit":
            return self._execute_clear_bit(index, c, opt)
        if c.name == "Count":
            return self._execute_count(index, c, slices, opt)
        if c.name == "SetBit":
            return self._execute_set_bit(index, c, opt)
        if c.name == "SetValue":
            return self._execute_set_value(index, c, opt)
        if c.name in _BSI_AGGREGATES:
            return self._execute_bsi_aggregate(index, c, slices, opt)
        if c.name == "SetRowAttrs":
            return self._execute_set_row_attrs(index, c, opt)
        if c.name == "SetColumnAttrs":
            return self._execute_set_column_attrs(index, c, opt)
        if c.name == "TopN":
            return self._execute_top_n(index, c, slices, opt)
        return self._execute_bitmap_call(index, c, slices, opt)

    # -- bitmap calls --------------------------------------------------------

    def _execute_bitmap_call(self, index: str, c: Call, slices: Sequence[int],
                             opt: ExecOptions) -> Row:
        # Fused materialization (VERDICT r4 #5): a lowerable multi-leaf
        # tree folds dense word blocks once per slice and lifts the
        # RESULT into roaring — no per-operand container
        # materialization, no pairwise merges. Single-leaf Bitmap()
        # stays on the fragment row cache (a plain cache hit beats any
        # fold); non-lowerable trees keep the general roaring path.
        mat_plan = None
        if c.name in ("Intersect", "Union", "Difference", "Range"):
            from .parallel.plan import HostMaterializePlan, _lower_tree

            leaves: list = []
            shape = _lower_tree(self.holder, index, c, leaves)
            if shape is not None and len(leaves) > 1:
                mat_plan = HostMaterializePlan(
                    self.holder, index, shape, leaves,
                    cache=self._host_cache)

        if mat_plan is not None:
            def batch_fn(batch_slices):
                return mat_plan.materialize_row(batch_slices)

            def map_fn(slice_):
                seg = mat_plan.materialize_slice(slice_)
                r = Row()
                if seg is not None:
                    r.segments[slice_] = seg
                return r

            def reduce_fn(prev, v):
                # batch_fn/map_fn results are freshly built (never the
                # fragment row cache's shared Rows) — the first one can
                # be adopted without a defensive merge-clone.
                if prev is None:
                    return v
                prev.merge(v)
                return prev

            row = self._map_reduce(index, slices, c, opt, map_fn,
                                   reduce_fn, batch_fn=batch_fn)
        else:
            def map_fn(slice_):
                return self.execute_bitmap_call_slice(index, c, slice_)

            def reduce_fn(prev, v):
                if prev is None:
                    prev = Row()
                prev.merge(v)
                return prev

            row = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn)
        if row is None:
            row = Row()

        # Attach attrs for root Bitmap() calls (executor.go:218-247).
        if c.name == "Bitmap":
            idx = self.holder.index(index)
            if idx is not None:
                col_id, col_ok = c.uint_arg(idx.column_label)
                if col_ok:
                    row.attrs = idx.column_attr_store.attrs(col_id)
                else:
                    f = idx.frame(c.args.get("frame") or DEFAULT_FRAME)
                    if f is not None:
                        row_id, _ = c.uint_arg(f.row_label)
                        row.attrs = f.row_attr_store.attrs(row_id)
        return row

    def execute_bitmap_call_slice(self, index: str, c: Call, slice_: int) -> Row:
        """One slice of a bitmap call (executor.go:253-268)."""
        if c.name == "Bitmap":
            return self._execute_bitmap_slice(index, c, slice_)
        if c.name == "Difference":
            return self._execute_binop_slice(index, c, slice_, "difference")
        if c.name == "Intersect":
            return self._execute_binop_slice(index, c, slice_, "intersect")
        if c.name == "Range":
            return self._execute_range_slice(index, c, slice_)
        if c.name == "Union":
            return self._execute_binop_slice(index, c, slice_, "union")
        raise QueryError(f"unknown call: {c.name}")

    def _execute_bitmap_slice(self, index: str, c: Call, slice_: int) -> Row:
        """Bitmap(rowID=..) / Bitmap(columnID=..) for one slice
        (executor.go:420-465)."""
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        column_label = idx.column_label

        frame = c.args.get("frame") or DEFAULT_FRAME
        f = idx.frame(frame)
        if f is None:
            raise FrameNotFoundError()
        row_label = f.row_label

        row_id, row_ok = c.uint_arg(row_label)
        col_id, col_ok = c.uint_arg(column_label)
        if row_ok and col_ok:
            raise QueryError(
                f"Bitmap() cannot specify both {row_label} and {column_label} values")
        if not row_ok and not col_ok:
            raise QueryError(
                f"Bitmap() must specify either {row_label} or {column_label} values")

        view, id_ = VIEW_STANDARD, row_id
        if col_ok:
            if not f.inverse_enabled:
                raise QueryError(
                    "Bitmap() cannot retrieve columns unless inverse storage enabled")
            view, id_ = VIEW_INVERSE, col_id

        frag = self.holder.fragment(index, frame, view, slice_)
        if frag is None:
            return Row()
        return frag.row(id_)

    def _execute_binop_slice(self, index: str, c: Call, slice_: int, op: str) -> Row:
        if not c.children:
            if op == "union":
                return Row()
            raise QueryError(f"empty {c.name} query is currently not supported")
        other = None
        for child in c.children:
            row = self.execute_bitmap_call_slice(index, child, slice_)
            other = row if other is None else getattr(other, op)(row)
        return other

    def _execute_range_slice(self, index: str, c: Call, slice_: int) -> Row:
        """Range(frame=.., <row>=.., start=.., end=..) over time-quantum
        views (executor.go:490-546)."""
        frame = c.args.get("frame") or DEFAULT_FRAME
        f = self.holder.frame(index, frame)
        if f is None:
            raise FrameNotFoundError()

        # Value comparison over an integer field: Range(frame=f, v >= 3)
        # — one O'Neil plane ladder over the field's bsi view. This is
        # the per-slice host form; lowerable trees never get here (the
        # fused materialize/count paths lower the same ladder).
        fname_cond = self._bsi_cond(c)
        if fname_cond is not None:
            from .bsi import host as bsi_host

            fname, cond = fname_cond
            schema = f.bsi_field(fname)
            if schema is None:
                from .bsi import FieldNotFoundError

                raise FieldNotFoundError(frame, fname)
            frag = self.holder.fragment(index, frame, schema.view, slice_)
            return bsi_host.range_row(frag, schema, cond.op, cond.value)

        row_id, _ = c.uint_arg(f.row_label)

        start = c.args.get("start")
        if not isinstance(start, str):
            raise QueryError("Range() start time required")
        end = c.args.get("end")
        if not isinstance(end, str):
            raise QueryError("Range() end time required")
        try:
            start_t = parse_time(start)
            end_t = parse_time(end)
        except ValueError:
            raise QueryError("cannot parse Range() time")

        q = f.time_quantum
        if not str(q):
            return Row()

        out = Row()
        for vname in views_by_time_range(VIEW_STANDARD, start_t, end_t, q):
            frag = self.holder.fragment(index, frame, vname, slice_)
            if frag is None:
                continue
            out = out.union(frag.row(row_id))
        return out

    # -- count ---------------------------------------------------------------

    def _execute_count(self, index: str, c: Call, slices: Sequence[int],
                       opt: ExecOptions) -> int:
        if len(c.children) == 0:
            raise QueryError("Count() requires an input bitmap")
        if len(c.children) > 1:
            raise QueryError("Count() only accepts a single bitmap input")
        child = c.children[0]
        t0 = time.monotonic()
        h2d0 = self._h2d_bytes()

        # Whole-query memo (the Range/nary routed-path answer to the
        # reference's rank cache): a repeated read-only Count on an
        # unmutated holder is one dict probe validated by the
        # process-wide MUTATION_EPOCH — skipping re-lowering and plan
        # construction, which together dwarf the actual fold on small
        # routed queries.
        # Single-node only: with cluster fan-out, remote writes don't
        # bump the LOCAL epoch, so a hit could serve another node's
        # stale slices. (SPMD replicates writes to every rank's holder
        # via the descriptor stream, so its rank-0 executor — which
        # has no cluster nodes — still qualifies; so does the default
        # server's one-node static cluster, where every write IS local.)
        psp = obs.span("plan", call="Count", slices=len(slices))
        pph = obs.profile.phase("plan").start()
        qkey = qepoch = qsepoch = None
        nodes = self.cluster.nodes if self.cluster is not None else []
        if (not nodes
                or (len(nodes) == 1 and nodes[0].host == self.host)):
            ck = c.cache_key()
            if ck is not None:
                from .core.fragment import MUTATION_EPOCH

                qkey = (index, ck, tuple(slices))
                qepoch = MUTATION_EPOCH.n
                qsepoch = MUTATION_EPOCH.s
                hit = self._host_cache.query_get(qkey, qepoch, qsepoch)
                if hit is not None:
                    psp.tag(route="memo").finish()
                    pph.stop()
                    # A memo hit never leaves this process: tier from
                    # the options anyway (a remote leg's hit still
                    # belongs to the tier the query paid), never the
                    # bare legacy default.
                    self._record_route("memo", t0,
                                       tier=self._query_tier(opt, False),
                                       call=c)
                    return hit

        # Epoch-keyed result cache (ISSUE 18): the clustered
        # counterpart of the memo above. Serves BOUNDED reads only
        # (X-Pilosa-Staleness > 0) on a multi-node cluster — strict
        # reads bypass (counted), keeping their byte-identical
        # owner-only path — keyed by (plan signature, slices, max
        # fragment epoch over the touched slices), so any write this
        # coordinator has observed to a touched slice produces a
        # different key and the stale entry invalidates instead of
        # serving. Every Nth hit is recomputed and compared (shadow
        # verify) to prove epoch-freshness end to end.
        rc = self.result_cache
        rc_key = rc_epoch = rc_verify = None
        if (rc is not None and not opt.remote and nodes
                and len(nodes) > 1):
            rck = c.cache_key()
            if opt.staleness <= 0 or rck is None:
                rc.bypass()
            else:
                rc_key = (index, rck, tuple(slices))
                # Epoch read BEFORE the probe/compute (the memo's
                # discipline): a write racing the fold bumps the max,
                # so the entry stored below can never validate for a
                # post-write read.
                rc_epoch = self.epochs.max_epoch_slices(index, slices)
                cached = rc.get(rc_key, rc_epoch)
                if cached is not None:
                    v1 = self.result_cache_verify_1_in
                    if v1 and next(self._rc_verify_counter) % v1 == 0:
                        rc_verify = cached  # recompute + compare below
                    else:
                        psp.tag(route="result-cache").finish()
                        pph.stop()
                        self._record_route(
                            "result-cache", t0,
                            tier=self._query_tier(opt, False),
                            call=c, cache="hit")
                        return cached

        # Lower the tree ONCE; every count engine shares it. The
        # per-slice CountPlan is only built if the mesh batch declines
        # (it compiles per-slice jits the batch path never uses).
        # Cost routing (_route_to_host) may decline the device entirely:
        # the query then runs the fused HOST fold (HostCountPlan — C++
        # popcount over dense word blocks, no roaring materialization),
        # which beats the materializing Row path ~5x on small trees.
        lowered = None
        host_lowered = None
        qtoken = None
        backend_on = self._device_backend_on()
        if backend_on or qkey is not None:
            # Lowering is pure host work; with the backend off it still
            # runs when a memo entry will be stored, because the leaves
            # name exactly the views the revalidation token must cover
            # (a tokenless entry dies on every epoch bump).
            from .parallel.plan import _lower_tree, _tree_signature

            leaves: list = []
            shape = _lower_tree(self.holder, index, child, leaves)
            route_reason = None
            if shape is not None and leaves:
                if backend_on:
                    import json as _json

                    sig = _json.dumps(_tree_signature(shape))
                    route_reason = self._route_to_host(
                        len(slices), len(leaves), index=index,
                        leaves=leaves, sig=sig)
                    if route_reason:
                        host_lowered = (shape, leaves)
                    else:
                        lowered = (shape, leaves)
                if qkey is not None:
                    qtoken = self._query_token(index, leaves)

        # Routing decision, recorded for trace attribution: which
        # engine serves, and which kill-switches steered it there.
        route = ("host-fold" if host_lowered is not None
                 else "mesh" if lowered is not None else "roaring")
        psp.tag(route=route, backend_on=backend_on,
                leaves=len(leaves) if backend_on or qkey is not None
                else 0)
        if host_lowered is not None and route_reason:
            psp.tag(route_reason=route_reason)
        switches = self._kill_switches()
        if switches:
            psp.tag(kill_switches=switches)
        psp.finish()
        pph.stop()

        plan_cell: list = []

        def slice_plan():
            if not plan_cell:
                from .parallel.plan import CountPlan, HostCountPlan

                if lowered is not None:
                    plan_cell.append(CountPlan(self.holder, index, *lowered))
                elif host_lowered is not None:
                    plan_cell.append(
                        HostCountPlan(self.holder, index, *host_lowered,
                                      cache=self._host_cache))
                else:
                    plan_cell.append(None)
            return plan_cell[0]

        def map_fn(slice_):
            plan = slice_plan()
            if plan is not None:
                n = plan.count_slice(slice_)
                if n is not None:
                    return n
            return self.execute_bitmap_call_slice(index, child, slice_).count()

        def reduce_fn(prev, v):
            return (prev or 0) + v

        if host_lowered is not None:
            # Cost-routed host queries serve whole slice batches inline
            # (plan.count_slices): the per-slice thread fan-out costs
            # more than the memo-backed folds it would parallelize.
            def host_batch_fn(batch_slices):
                plan = slice_plan()
                return plan.count_slices(batch_slices) if plan else None

            batch_fn = host_batch_fn
        else:
            batch_fn = self._mesh_count_batch(index, lowered)

        # Host routes (roaring fold or the fused host popcount) do all
        # their gather work on host threads: the whole map-reduce is
        # host_fold time. The mesh route instead accrues device_exec /
        # stage_h2d / compile inside the serving layer (union-interval
        # accounting absorbs HostCountPlan's own nested bracket).
        gph = (obs.profile.phase("host_fold") if lowered is None
               else obs.profile.NOOP_PHASE)
        with gph:
            result = self._map_reduce(
                index, slices, c, opt, map_fn, reduce_fn, batch_fn=batch_fn)
        # The books of a computed Count: memo and result-cache puts,
        # route metrics, SLO and cost taps.
        with obs.profile.phase("account"):
            n = int(result or 0)
            if qkey is not None:
                # Stored against the PRE-compute epoch (and PRE-compute
                # view write counters): a write racing the fold bumped
                # them, so the entry can never validate — stale results
                # invalidate, they don't serve.
                self._host_cache.query_put(qkey, qepoch, n, qsepoch, qtoken)
            cache_tag = None
            if rc_verify is not None:
                # Shadow verify: the hit we withheld vs the fresh
                # compute. A mismatch is an epoch-freshness bug — count
                # it where the PR-10 machinery already alerts
                # (pilosa_shadow_mismatch_total) and quarantine the
                # entry.
                cache_tag = "verify"
                SHADOW_STATS.inc("checks:result-cache")
                if int(rc_verify) != n:
                    SHADOW_STATS.inc("mismatch:result-cache")
                    rc.invalidate(rc_key)
            elif rc_key is not None:
                cache_tag = "miss"
                rc.put(rc_key, rc_epoch, n)
            self._record_route(route, t0,
                               tier=self._query_tier(opt, route == "mesh"),
                               call=c,
                               staged_bytes=max(0, self._h2d_bytes() - h2d0),
                               cache=cache_tag)
        return n

    def _query_token(self, index: str, leaves) -> tuple:
        """((WriteCounter, value), ...), one entry per unique leaf VIEW
        of the lowered tree — the revalidation token for
        HostQueryCache.query_get, O(views) whatever the fan-out. Read
        BEFORE the fold on purpose (see query_put). An absent view is
        simply skipped: it has no fragment, and one appearing later
        bumps the structural epoch (View._open_fragment), which
        already invalidates the token.

        Never weaker than a token of every touched fragment's
        generation: whatever moves a generation moves its view's
        counter (fragment._log_append/_log_reset). Coarser in one
        case: a Count over a SUBSET of a view's slices refolds after a
        write to another slice of that view."""
        views = (self.holder.view(index, f, v) for f, v in
                 dict.fromkeys((f, v) for f, v, _r, _q in leaves))
        return tuple((v.writes, v.writes.n) for v in views if v is not None)

    # -- BSI aggregates ------------------------------------------------------

    @staticmethod
    def _bsi_cond(c: Call):
        """The call's single field comparison as (field, Cond), None
        when it has none; raises QueryError on more than one."""
        from .pql.ast import Cond

        found = [(k, v) for k, v in c.args.items() if isinstance(v, Cond)]
        if not found:
            return None
        if len(found) > 1:
            raise QueryError(
                f"{c.name}() accepts one field comparison, got "
                f"{len(found)}")
        return found[0]

    def _bsi_call_schema(self, index: str, c: Call):
        """Resolve (frame name, Frame, FieldSchema) for a BSI aggregate
        call; raises the NotFound errors the handler maps to 404."""
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        frame = c.args.get("frame") or DEFAULT_FRAME
        f = idx.frame(frame)
        if f is None:
            raise FrameNotFoundError()
        field = c.args.get("field")
        if not isinstance(field, str) or not field:
            raise QueryError(f"{c.name}() field required")
        schema = f.bsi_field(field)
        if schema is None:
            from .bsi import FieldNotFoundError

            raise FieldNotFoundError(frame, field)
        return frame, f, schema

    @staticmethod
    def _valcount_pair(v):
        """Normalize a per-leg aggregate result — local (value, count)
        tuple or a remote leg's decoded {"value", "count"} dict — to a
        tuple; None stays None (an empty Min/Max leg)."""
        if v is None:
            return None
        if isinstance(v, dict):
            return int(v.get("value", 0)), int(v.get("count", 0))
        return v

    def _execute_bsi_aggregate(self, index: str, c: Call,
                               slices: Sequence[int], opt: ExecOptions):
        """Sum / Min / Max over an integer field, with an optional
        bitmap filter child.

        Device path (single-host mesh): Sum is one fused per-row-count
        collective over the whole bsi view — every magnitude plane, the
        existence row, and the sign row counted in a single masked
        popcount + segment-sum — plus a second sign-side pass that is
        SKIPPED when no negative values exist (the sign count is
        visible in the first pass); the 2^k weighting folds host-side
        in unbounded Python ints. Min/Max binary-search the magnitude
        planes MSB-down, each probe one fused tree-count collective.
        Both shadow-verify sampled batches against the host roaring
        fold and serve the HOST value on mismatch.

        SPMD deployments route the same collectives through the BSISUM
        / COUNT descriptors (parallel/spmd.py) so every rank enters
        them together — the pod-scale form of the same plan.

        Host path (fallback, cost-routed small queries, remote legs'
        per-slice work): exact roaring folds in bsi.host."""
        frame, _f, schema = self._bsi_call_schema(index, c)
        if len(c.children) > 1:
            raise QueryError(
                f"{c.name}() only accepts a single bitmap input")
        child = c.children[0] if c.children else None
        t0 = time.monotonic()
        h2d0 = self._h2d_bytes()

        # Lower the filter child once; a non-lowerable filter pins the
        # whole aggregate to the host path (its per-slice evaluation
        # needs host state anyway).
        filter_lowered = None
        device_ok = self._device_backend_on()
        if device_ok and child is not None:
            from .parallel.plan import _lower_tree

            fleaves: list = []
            fshape = _lower_tree(self.holder, index, child, fleaves)
            if fshape is None or not fleaves:
                device_ok = False
            else:
                filter_lowered = (fshape, fleaves)
        if device_ok and self._route_to_host(
                len(slices), schema.row_count, index=index):
            device_ok = False

        view = schema.view
        from .bsi import host as bsi_host

        def map_fn(slice_):
            frag = self.holder.fragment(index, frame, view, slice_)
            filter_row = (self.execute_bitmap_call_slice(index, child,
                                                         slice_)
                          if child is not None else None)
            if c.name == "Sum":
                return bsi_host.sum_slice(frag, schema, filter_row)
            if c.name == "Max":
                return bsi_host.max_slice(frag, schema, filter_row)
            return bsi_host.min_slice(frag, schema, filter_row)

        if c.name == "Sum":
            def reduce_fn(prev, v):
                v = self._valcount_pair(v)
                if v is None:
                    return prev
                if prev is None:
                    return v
                return prev[0] + v[0], prev[1] + v[1]
        else:
            maximize = c.name == "Max"

            def reduce_fn(prev, v):
                return bsi_host.reduce_extremes(
                    [prev, self._valcount_pair(v)], maximize)

        batch_fn = None
        shadow_out: list = []  # per-check mismatch flags (flight rec)
        if device_ok:
            inner = (self._bsi_sum_batch(index, frame, schema,
                                         filter_lowered)
                     if c.name == "Sum" else
                     self._bsi_extremum_batch(index, frame, schema,
                                              filter_lowered,
                                              c.name == "Max"))
            if inner is not None:
                def batch_fn(batch_slices):
                    v = inner(batch_slices)
                    if v is not None and self._shadow_sampled():
                        v = self._shadow_check_bsi(
                            c.name, index, batch_slices, v, map_fn,
                            reduce_fn, outcome=shadow_out)
                    return v
            else:
                device_ok = False

        out = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn,
                               batch_fn=batch_fn)
        self._record_route("bsi-mesh" if device_ok else "bsi-host", t0,
                           tier=self._query_tier(opt, device_ok),
                           call=c,
                           staged_bytes=max(0, self._h2d_bytes() - h2d0),
                           shadow_checked=bool(shadow_out),
                           shadow_mismatch=any(shadow_out))
        if c.name == "Sum":
            s, n = out if out is not None else (0, 0)
            return {"value": int(s), "count": int(n)}
        if out is None:
            return None
        return {"value": int(out[0]), "count": int(out[1])}

    def _bsi_sum_batch(self, index: str, frame: str, schema,
                       filter_lowered):
        """batch_fn computing (sum, count) for a slice batch from the
        fused per-row-count collectives, or None when no manager. With
        the SPMD plane wired, the collectives ride BSISUM descriptors
        (every rank must enter the psum together); the host-side 2^k
        weighting below is identical either way."""
        mgr = self.mesh_manager()
        if mgr is None:
            return None
        from .bsi.field import ROW_SIGN
        from .ops.bsi import sum_from_plane_dicts

        view = schema.view

        def plane_counts(batch_slices, num, src):
            if self._spmd is not None:
                return self._spmd.bsi_sum(index, frame, view,
                                          batch_slices, num, src=src)
            return mgr.bsi_plane_counts(index, frame, view,
                                        batch_slices, num, src=src)

        def batch_fn(batch_slices):
            num = self._batch_num_slices(index, batch_slices)
            try:
                counts = plane_counts(batch_slices, num, filter_lowered)
                if counts is None:
                    return None
                neg: dict = {}
                if counts.get(ROW_SIGN, 0):
                    # Negative values present: second pass restricted
                    # to the sign row (AND the filter, when given).
                    sshape: list = ["leaf"]
                    sleaves = [(frame, view, ROW_SIGN, False)]
                    if filter_lowered is not None:
                        fshape, fleaves = filter_lowered
                        sshape = ["and", fshape, ["leaf"]]
                        sleaves = list(fleaves) + sleaves
                    neg = plane_counts(batch_slices, num,
                                       (sshape, sleaves))
                    if neg is None:
                        return None
            except Exception:  # noqa: BLE001 — device failure → host
                self._device_failed("BSI sum")
                return None
            return sum_from_plane_dicts(counts, neg, schema.bit_depth)

        return batch_fn

    def _bsi_extremum_batch(self, index: str, frame: str, schema,
                            filter_lowered, maximize: bool):
        """batch_fn binary-searching the magnitude planes MSB-down for
        a slice batch — ~bit_depth fused tree-count collectives over
        growing candidate trees. Returns (value, count) or None (empty
        batch falls through to the host fold, which agrees)."""
        mgr = self.mesh_manager()
        if mgr is None:
            return None
        from .bsi import lower as L
        from .bsi.field import ROW_PLANE0

        view = schema.view

        def batch_fn(batch_slices):
            num = self._batch_num_slices(index, batch_slices)

            def count_tree(tree):
                leaves: list = []
                shape = L.to_shape(tree, frame, view, leaves)
                if filter_lowered is not None:
                    fshape, fleaves = filter_lowered
                    shape = ["and", shape, fshape]
                    leaves = leaves + list(fleaves)
                try:
                    # SPMD: each probe is one COUNT descriptor so all
                    # ranks enter the collective together.
                    n = (self._spmd.count(index, shape, leaves,
                                          batch_slices, num)
                         if self._spmd is not None else
                         mgr.count(index, shape, leaves, batch_slices,
                                   num))
                except Exception:  # noqa: BLE001 — device → host
                    self._device_failed("BSI extremum count")
                    return None
                return None if n is None else int(n)

            def search(cand, big_mag: bool):
                mag = 0
                for k in range(schema.bit_depth - 1, -1, -1):
                    p = L.leaf(ROW_PLANE0 + k)
                    inter = L.t_and(cand, p)
                    if big_mag:
                        n = count_tree(inter)
                        if n is None:
                            return None
                        if n:
                            cand, mag = inter, mag | (1 << k)
                    else:
                        rest = L.t_andnot(cand, p)
                        n = count_tree(rest)
                        if n is None:
                            return None
                        if n:
                            cand = rest
                        else:
                            cand, mag = inter, mag | (1 << k)
                n = count_tree(cand)
                if n is None:
                    return None
                return mag, n

            n_pos = count_tree(L.POS)
            if n_pos is None:
                return None
            n_neg = count_tree(L.NEG)
            if n_neg is None:
                return None
            first, second = ((n_pos, L.POS, 1), (n_neg, L.NEG, -1))
            if not maximize:
                first, second = second, first
            for n_side, base, sign in (first, second):
                if not n_side:
                    continue
                # max: positives hold the LARGEST magnitude, negatives
                # the smallest; min mirrors.
                big = (sign > 0) == maximize
                out = search(base, big_mag=big)
                if out is None:
                    return None
                return sign * out[0], out[1]
            return None  # no values in batch; host fold agrees

        return batch_fn

    def _shadow_check_bsi(self, name: str, index: str, batch_slices,
                          device_v, map_fn, reduce_fn, outcome=None):
        """Recompute a sampled device aggregate through the host
        roaring fold and compare. On mismatch: count it, log, and
        serve the HOST value — BSI collectives are keyed per staged
        view rather than one plan signature, so the counter and log
        line are the alarm (as with TopN)."""
        SHADOW_STATS.inc("checks:bsi")
        host_v = None
        for s in batch_slices:
            host_v = reduce_fn(host_v, map_fn(s))
        if name == "Sum" and host_v is None:
            host_v = (0, 0)
        if host_v == self._valcount_pair(device_v):
            if outcome is not None:
                outcome.append(False)
            return device_v
        SHADOW_STATS.inc("mismatch:bsi")
        if outcome is not None:
            outcome.append(True)
        cur = obs.current_span()
        trace = getattr(getattr(cur, "trace", None), "trace_id", "-")
        obs.get_logger("executor").error(
            "shadow verification MISMATCH (bsi %s): device=%s host=%s "
            "index=%s slices=%d trace=%s — serving host fold",
            name, device_v, host_v, index, len(batch_slices), trace)
        return host_v

    def mesh_manager(self):
        """The mesh serving layer, or None when the device backend is
        off or its construction failed (no devices, import error)."""
        if self._mesh_mgr is not None:
            return self._mesh_mgr
        if self._mesh_mgr_failed or not self._device_backend_on():
            return None
        with self._mesh_mgr_lock:
            if self._mesh_mgr is not None or self._mesh_mgr_failed:
                return self._mesh_mgr
            try:
                from .parallel.serve import MeshManager

                self._mesh_mgr = MeshManager(self.holder,
                                             config=self.mesh_config)
            except Exception:  # noqa: BLE001 — device layer unavailable
                self._mesh_mgr_failed = True
                obs.get_logger("executor").warning(
                    "mesh manager construction failed; serving from the "
                    "host for the life of this process", exc_info=True)
                return None
        return self._mesh_mgr

    def _device_failed(self, what: str) -> None:
        """A device attempt raised and the host path is about to answer
        in its place. Production keeps serving; the failure is counted
        (pilosa_device_fallback_total{reason="error"}) and logged with
        its traceback, so a device path that never works cannot pass
        for one that does. Call from the except block."""
        mgr = self._mesh_mgr
        if mgr is not None:
            mgr.stats.inc("fallback_error")
        obs.get_logger("executor").warning(
            "device %s failed; the host path answers", what, exc_info=True)

    def invalidate_device_index(self, index: Optional[str] = None):
        """Drop staged device images for an index (or all). Called by
        the API layer on index/frame deletion — the object-identity
        check in refresh() also catches this, but dropping eagerly
        frees device HBM immediately."""
        if self._mesh_mgr is not None:
            self._mesh_mgr.invalidate(index)

    @property
    def device_stats(self):
        """Mesh serving-layer counters for /debug/vars, or None when no
        manager has been built (never forces construction)."""
        return self._mesh_mgr.stats if self._mesh_mgr is not None else None

    @property
    def host_cache_stats(self):
        """Routed-host-path cache counters for /debug/vars."""
        return self._host_cache.stats

    def _query_tier(self, opt: Optional["ExecOptions"],
                    collective: bool) -> str:
        """Locality tier a served query actually paid, worst-first:
        `http` when any slice group went over the HTTP ring, `ici`
        when a multi-device collective ran (slices reduced over the
        interconnect — including ICI-peer slices folded into the local
        dispatch), else `local` (one chip, or pure host fold)."""
        if opt is not None and opt.used_http:
            return "http"
        if opt is not None and opt.used_ici:
            return "ici"
        if collective and self._multi_device():
            return "ici"
        return "local"

    def _multi_device(self) -> bool:
        """True when the serving mesh spans more than one device (its
        reductions cross the interconnect)."""
        if self._spmd is not None:
            return True
        mgr = self._mesh_mgr
        try:
            return bool(mgr is not None
                        and mgr.mesh.devices.size > 1)
        except Exception:  # noqa: BLE001 — no mesh constructed
            return False

    @staticmethod
    def _shape_sig(c) -> str:
        """Structural plan signature for the flight recorder: call
        names plus frame arguments, with row/column ids elided — two
        queries differing only in ids aggregate as one shape. Memoized
        on the Call (immutable after parse, like cache_key)."""
        sig = c.__dict__.get("_shape_sig")
        if sig is None:
            try:
                sig = _call_shape(c)
            except Exception:  # noqa: BLE001 — telemetry never raises
                sig = c.name
            c.__dict__["_shape_sig"] = sig
        return sig

    def _h2d_bytes(self) -> int:
        """Cumulative mesh H2D staging bytes (0 without a manager) —
        deltas attribute staging cost to the query that triggered it
        (approximate under concurrency; it is an attribution
        instrument, not an invoice)."""
        stats = self.device_stats
        return int(stats.get("h2d_bytes", 0)) if stats is not None else 0

    def _record_route(self, route: str, t0: float,
                      tier: Optional[str] = None, call=None,
                      staged_bytes: int = 0,
                      shadow_checked: bool = False,
                      shadow_mismatch: bool = False,
                      cache: Optional[str] = None):
        self.route_stats.inc(f"count_{route}")
        # Tier split rides a parallel StatMap (route|tier) so the
        # legacy count_* keys — bench dumps, tests, dashboards — keep
        # their meaning; /metrics joins both into
        # pilosa_query_route_total{backend, tier}.
        self.tier_stats.inc(f"{route}|{tier or 'local'}")
        h = self._route_hists.get(route)
        if h is None:
            # setdefault: two first-observers race benignly to one.
            h = self._route_hists.setdefault(route, obs.Histogram())
        lat_us = (time.monotonic() - t0) * 1e6
        # Exemplar: with a trace active, its id rides into the latency
        # bucket this observation lands in, so /metrics?exemplars=true
        # links a burning p99 straight to /debug/traces/<id>. No trace
        # = None = zero extra work in the histogram.
        cur = obs.current_span()
        trace = getattr(cur, "trace", None)
        h.observe(lat_us, exemplar=getattr(trace, "trace_id", None))
        if call is not None:
            sig = self._shape_sig(call)
            self.flight.record(sig, route,
                               tier or "local", lat_us,
                               staged_bytes=staged_bytes,
                               shadow_checked=shadow_checked,
                               shadow_mismatch=shadow_mismatch,
                               cache=cache,
                               example=lambda: str(call))
            # Cost observatory tap: stamps the shape on the ambient
            # attribution context (the handler bound the tenant),
            # meters staged bytes + op count into the (tenant, shape)
            # account, and feeds the baseline watch. One attribute
            # read when the ledger is off.
            obs.costs.observe_route(sig, route, tier or "local",
                                    lat_us, staged_bytes=staged_bytes,
                                    cache=cache)

    @property
    def route_latency_hists(self) -> dict:
        """route name -> Histogram of Count latencies (µs), for the
        /metrics backend-labeled histogram."""
        return dict(self._route_hists)

    def estimate_service_us(self):
        """Admission-control service-time estimate (sched/): p95 of
        the busiest measured route's Count latency, in µs. None until
        enough queries have been measured — the scheduler blends this
        with its own observed latencies and a configured floor, so an
        honest 'don't know yet' beats a guess here."""
        best = None
        best_n = 0
        for h in list(self._route_hists.values()):
            n = h.total
            if n > best_n:
                best, best_n = h, n
        if best is None or best_n < 4:
            return None
        return best.percentile(0.95)

    def burst_hint(self, n: int):
        """Scheduler cohort-release hint: n coalesced queries are about
        to arrive together, so the mesh batch loop should hold its
        drain window open for the whole group (serve.expect_burst).
        No-op before the manager exists — a hint must never force
        device construction."""
        mgr = self._mesh_mgr
        if mgr is not None and n > 1:
            mgr.expect_burst(n)

    @staticmethod
    def _kill_switches() -> list:
        """The routing kill-switch env vars currently set, for trace
        attribution and EXPLAIN output."""
        switches = []
        for env, name in (("PILOSA_TPU_USE_DEVICE", "use_device"),
                          ("PILOSA_TPU_DEVICE_MIN_WORK", "device_min_work"),
                          ("PILOSA_TPU_CPU_ROUTE_NATIVE",
                           "cpu_route_native")):
            if os.environ.get(env, ""):
                switches.append(f"{name}={os.environ[env]}")
        return switches

    # -- explain -------------------------------------------------------------

    def explain(self, index: str, q: Query,
                slices: Optional[Sequence[int]] = None,
                opt: Optional[ExecOptions] = None) -> dict:
        """The PLANNED execution of `q` as a JSON-able dict: per-call
        routing decision with its cost-model inputs, slice→owner
        placement (breaker-aware, exactly the picks _slices_by_node
        would make), cache peeks, and estimated staging bytes — WITHOUT
        dispatching device work or mutating executor state. Every probe
        is a peek: no LRU reorder, no stats bumps, no staging, no
        compiles, no manager construction. Serves `?explain=true` on
        POST /index/{index}/query."""
        if not index:
            raise IndexRequiredError()
        idx = self.holder.index(index)
        if slices:
            slices = list(slices)
        else:
            slices = []
            if needs_slices(q.calls):
                if idx is None:
                    raise IndexNotFoundError()
                slices = list(range(idx.max_slice() + 1))
        return {
            "index": index,
            "slices": len(slices),
            "calls": [self._explain_call(index, c, slices, opt)
                      for c in q.calls],
        }

    def _explain_call(self, index: str, c: Call, slices: Sequence[int],
                      opt: Optional[ExecOptions] = None) -> dict:
        import json as _json

        info: dict = {"call": c.name}
        if c.name in _WRITE_CALLS:
            info["route"] = "write"
            info["placement"] = self._explain_placement(index, slices,
                                                        opt)
            owners = (self.cluster.replica_n
                      if self.cluster is not None and self.cluster.nodes
                      else 1)
            info["consistency"] = {
                "level": self.write_consistency,
                "replicas": owners,
                "required_acks": required_acks(
                    self.write_consistency, owners),
                "hinted_handoff": self.hints is not None,
            }
            return info
        if c.name in _BSI_AGGREGATES:
            return self._explain_bsi_aggregate(index, c, slices, info,
                                               opt)
        if c.name != "Count" or len(c.children) != 1:
            # Non-Count reads run the per-slice roaring map-reduce.
            info["route"] = "roaring"
            cond = self._find_cond(c)
            if cond is not None:
                # Range(field <op> N): report the plane ladder the
                # comparison compiles to, and what it would stage.
                from .parallel.plan import _lower_tree

                leaves: list = []
                shape = _lower_tree(self.holder, index, c, leaves)
                if shape is not None and leaves:
                    info["bsi"] = {"field": cond[0],
                                   "cond": str(cond[1]),
                                   "planes": len(leaves)}
                    info["staging"] = self._explain_staging(
                        index, leaves, slices)
            info["placement"] = self._explain_placement(index, slices,
                                                        opt)
            return info

        child = c.children[0]
        backend_on = self._device_backend_on()
        from .parallel.plan import _lower_tree, _tree_signature

        leaves: list = []
        shape = _lower_tree(self.holder, index, child, leaves)
        lowerable = shape is not None and bool(leaves)
        cond = self._find_cond(child)
        if cond is not None and lowerable:
            info["bsi"] = {"field": cond[0], "cond": str(cond[1]),
                           "planes": len(leaves)}

        # Memo peek mirrors _execute_count's single-node gate.
        memo_hit = False
        nodes = self.cluster.nodes if self.cluster is not None else []
        single = (not nodes
                  or (len(nodes) == 1 and nodes[0].host == self.host))
        ck = c.cache_key()
        if single and ck is not None:
            from .core.fragment import MUTATION_EPOCH

            memo_hit = self._host_cache.query_peek(
                (index, ck, tuple(slices)), MUTATION_EPOCH.n)

        route_reason = None
        if memo_hit:
            route = "memo"
        elif lowerable and backend_on:
            sig = _json.dumps(_tree_signature(shape))
            route_reason = self._would_route_to_host(
                len(slices), len(leaves), index=index, leaves=leaves,
                sig=sig)
            route = "host-fold" if route_reason else "mesh"
        else:
            route = "roaring"
        info["route"] = route
        if route_reason:
            info["route_reason"] = route_reason
        info["cost_model"] = {
            "backend_on": backend_on,
            "lowerable": lowerable,
            "leaves": len(leaves),
            "work_units": len(slices) * max(1, len(leaves)),
            "min_work": self._min_work(),
            "cpu_native_routes": self._cpu_native_routes(),
        }
        info["kill_switches"] = self._kill_switches()
        info["memo_hit"] = memo_hit

        mgr = self._mesh_mgr  # peek only: never force construction
        plan_hit = quarantined = False
        if lowerable and mgr is not None:
            sig = _json.dumps(_tree_signature(shape))
            plan_hit = mgr._fused_plans.contains_sig(sig)
            quarantined = mgr.plan_quarantined(sig)
        info["plan_cache"] = {"checked": mgr is not None,
                              "hit": plan_hit,
                              "quarantined": quarantined}
        if lowerable and mgr is not None:
            info["device_format"] = self._explain_format(
                index, leaves, shape, mgr)
        if lowerable:
            info["staging"] = self._explain_staging(index, leaves, slices)
        info["placement"] = self._explain_placement(index, slices, opt)
        return info

    @classmethod
    def _find_cond(cls, c: Call):
        """First (field, Cond) pair anywhere in a call tree — the
        explain() marker that a query compiles plane ladders."""
        from .pql.ast import Cond

        for k, v in c.args.items():
            if isinstance(v, Cond):
                return k, v
        for child in c.children:
            found = cls._find_cond(child)
            if found is not None:
                return found
        return None

    def _explain_bsi_aggregate(self, index: str, c: Call,
                               slices: Sequence[int], info: dict,
                               opt: Optional[ExecOptions] = None) -> dict:
        """Planned execution of Sum/Min/Max: which engine serves it,
        the plane count behind the field, and what a device dispatch
        would stage (every row of the bsi view)."""
        from .bsi import FieldNotFoundError

        try:
            frame, _f, schema = self._bsi_call_schema(index, c)
        except (IndexNotFoundError, FrameNotFoundError,
                FieldNotFoundError, QueryError) as err:
            # explain() never dispatches: a bad call reports its error
            # instead of raising, so the rest of the plan still renders.
            info["route"] = "error"
            info["error"] = str(err) or type(err).__name__
            return info
        backend_on = self._device_backend_on()
        route_reason = None
        if backend_on:
            route_reason = self._would_route_to_host(
                len(slices), schema.row_count, index=index)
            route = "bsi-host" if route_reason else "bsi-mesh"
        else:
            route = "bsi-host"
        info["route"] = route
        if route_reason:
            info["route_reason"] = route_reason
        info["bsi"] = {"field": c.args.get("field"),
                       "planes": schema.bit_depth,
                       "rows": schema.row_count}
        info["cost_model"] = {
            "backend_on": backend_on,
            "leaves": schema.row_count,
            "work_units": len(slices) * schema.row_count,
            "min_work": self._min_work(),
            "cpu_native_routes": self._cpu_native_routes(),
        }
        leaves = [(frame, schema.view, r, False)
                  for r in range(schema.row_count)]
        info["staging"] = self._explain_staging(index, leaves, slices)
        info["placement"] = self._explain_placement(index, slices, opt)
        return info

    @staticmethod
    def _resident_format(sv) -> str:
        """A StagedView's container format as the EXPLAIN label:
        dense / sparse / mixed (per-slice split)."""
        fmts = getattr(sv, "slice_formats", None)
        if sv.sparse is None or fmts is None or not fmts.any():
            return "dense"
        if fmts.all() or not sv.keys_host.shape[1]:
            return "sparse"
        return "mixed"

    def _explain_format(self, index: str, leaves, shape, mgr) -> dict:
        """Which container format would serve this Count on-device:
        per-leaf resident format plus whether the tree shape fits the
        sparse slice-group dispatch (and which sparse kernel backend
        is calibrated, if any). Peek only — unstaged leaves report
        "unstaged"; the stager decides their format at dispatch."""
        from .parallel.plan import _tree_signature

        fmts = []
        for frame, view, _r, _q in leaves:
            sv = mgr._views.get((index, frame, view))
            fmts.append("unstaged" if sv is None
                        else self._resident_format(sv))
        out: dict = {"leaves": fmts}
        if any(f in ("sparse", "mixed") for f in fmts):
            kind = mgr._sparse_shape_kind(_tree_signature(shape))
            out["sparse_shape"] = kind or "unsupported"
            # Peek the cached calibration pick; never trigger one.
            out["sparse_backend"] = (mgr._sparse_backend_cached
                                     or "unresolved")
        return out

    def _sparse_threshold_peek(self) -> float:
        """The sparse-density threshold the stager would use, without
        forcing manager construction: live manager if one exists, else
        the same env-over-config resolution it would apply."""
        mgr = self._mesh_mgr
        if mgr is not None:
            return mgr._sparse_threshold()
        cfg = self.mesh_config.get("sparse_density_threshold")
        base = float(cfg) if cfg is not None else 0.05
        try:
            return float(os.environ.get(
                "PILOSA_TPU_SPARSE_DENSITY_THRESHOLD", base))
        except ValueError:
            return base

    def _explain_staging(self, index: str, leaves,
                         slices: Sequence[int]) -> dict:
        """Which of the Count's (frame, view) images are already
        resident on-device — and in which container format — plus a
        host-side byte estimate for the ones a dispatch would have to
        stage, priced at the format pick_slice_formats would make
        today (dense slices at packed-word cost, sparse slices at
        sorted-array cost). Loaded fragments estimate from live
        container stats (exactly what the dual-pool builders upload);
        lazily-opened ones fall back to storage file size and stay
        format-unknown — EXPLAIN never forces a parse."""
        import numpy as np

        from .ops.pool import CONTAINER_WORDS
        from .parallel.mesh import pick_slice_formats

        mgr = self._mesh_mgr
        threshold = self._sparse_threshold_peek()
        uniq = list(dict.fromkeys((f, v) for f, v, _r, _q in leaves))
        staged = unstaged = est = 0
        views: list = []
        for frame, view in uniq:
            sv = (mgr._views.get((index, frame, view))
                  if mgr is not None else None)
            if sv is not None:
                staged += 1
                views.append({"frame": frame, "view": view,
                              "resident": True,
                              "format": self._resident_format(sv)})
                continue
            unstaged += 1
            stats = np.zeros((len(slices), 3), dtype=np.int64)
            opaque = 0
            for j, s in enumerate(slices):
                frag = self.holder.fragment(index, frame, view, s)
                if frag is None:
                    continue
                with frag._mu:
                    if frag._pending_load:
                        try:
                            opaque += os.path.getsize(frag.path)
                        except OSError:
                            pass
                        continue
                    nc = len(frag.storage.keys)
                    if nc:
                        ns = [c.n for c in frag.storage.containers]
                        stats[j] = (nc, sum(ns), max(ns))
            sp = pick_slice_formats(stats, threshold).astype(bool)
            n_sparse = int(sp.sum())
            n_live = int((stats[:, 0] > 0).sum())
            # Dense slices upload packed words; sparse ones upload the
            # value arrays plus their key/cardinality table entries.
            vb = (int(stats[~sp, 0].sum()) * (CONTAINER_WORDS * 4 + 4)
                  + int(stats[sp, 1].sum()) * 2
                  + int(stats[sp, 0].sum()) * 8 + opaque)
            est += vb
            views.append({
                "frame": frame, "view": view, "resident": False,
                "format": ("mixed" if 0 < n_sparse < n_live
                           else "sparse" if n_sparse else "dense"),
                "sparse_slices": n_sparse,
                "estimated_h2d_bytes": vb,
            })
        return {"staged_views": staged, "unstaged_views": unstaged,
                "estimated_h2d_bytes": est,
                "sparse_density_threshold": threshold,
                "views": views}

    def _explain_placement(self, index: str, slices: Sequence[int],
                           opt: Optional[ExecOptions] = None) -> dict:
        """slice→owner picks as _slices_by_node would make them —
        breaker/liveness-aware, and follower-spread when the request
        carries a staleness bound — plus each host's current breaker
        state, the locality tier of each pick (same-chip → same-pod-
        ICI → cross-node-HTTP), and the per-device group sizes one
        local mesh dispatch would shard the local+ici slices into.
        Slice lists are sampled (first 16) so a 960-slice explain
        stays readable. The follower p2c sample is seeded per explain
        so the rendered picks are stable within one response."""
        import random as _random

        from .parallel.cluster import owner_tier

        if self.cluster is None or not self.cluster.nodes:
            out = {"mode": "local", "slices": len(slices),
                   "tier": "ici" if self._multi_device() else "local"}
            self._explain_device_groups(out, slices, len(slices))
            return out
        state = self._breaker_callable(opt)
        read_bound = (opt.staleness
                      if opt is not None and not opt.remote else 0.0)
        rnd = _random.Random(0)
        nodes = list(self.cluster.nodes)
        per_host: dict = {}
        unowned: list = []
        tiers = {"local": 0, "ici": 0, "http": 0}
        read = {"staleness_s": read_bound, "followers": 0,
                "fallback_owner": 0} if read_bound > 0 else None
        for slice_ in slices:
            owners = [o for o in self.cluster.fragment_nodes(index, slice_)
                      if o in nodes]
            if not owners:
                unowned.append(slice_)
                continue
            pick = None
            role = "owner"
            if read_bound > 0 and len(owners) > 1:
                pick = pick_read_replica(
                    owners, state,
                    staleness_ok=lambda h, s=slice_:
                        self.epochs.staleness_ok_slice(
                            h, index, s, read_bound),
                    queue_depth=self.epochs.queue_depth,
                    prefer=self.host,
                    ici_hosts=self.ici_hosts or None, rnd=rnd,
                    node_ok=self.peer_health_ok)
                if pick is not None and pick.host != owners[0].host:
                    role = "follower"
                    read["followers"] += 1
                elif pick is None:
                    role = "fallback_owner"
                    read["fallback_owner"] += 1
            if pick is None:
                pick = preferred_owner(
                    owners, state,
                    prefer=self.host if self.prefer_local_reads else None,
                    ici_hosts=self.ici_hosts or None)
            tier = owner_tier(pick.host, self.host, self.ici_hosts)
            tiers[tier] += 1
            ent = per_host.setdefault(pick.host,
                                      {"slices": 0, "sample": [],
                                       "tier": tier})
            ent["slices"] += 1
            if read is not None:
                ent.setdefault("roles", {})
                ent["roles"][role] = ent["roles"].get(role, 0) + 1
            if len(ent["sample"]) < 16:
                ent["sample"].append(slice_)
        out = {"mode": "cluster", "nodes": per_host, "tiers": tiers,
               "tier": ("http" if tiers["http"]
                        else "ici" if tiers["ici"] or (
                            tiers["local"] and self._multi_device())
                        else "local")}
        if read is not None:
            out["read"] = read
        self._explain_device_groups(out, slices,
                                    tiers["local"] + tiers["ici"])
        if unowned:
            out["unowned_count"] = len(unowned)
            out["unowned_sample"] = unowned[:16]
        breakers = getattr(self.client, "breakers", None)
        snap = getattr(breakers, "snapshot", None)
        if callable(snap):
            out["breakers"] = snap()
        return out

    def _explain_device_groups(self, out: dict, slices, eligible) -> None:
        """Attach the per-device slice-group sizes one local mesh
        dispatch would shard the locally-served (local + ici tier)
        slices into. Peek only: the resident manager's mesh when one
        exists, else the process device count — never forces manager
        construction."""
        if not eligible or not slices or not self._device_backend_on():
            return
        try:
            if self._mesh_mgr is not None:
                n_dev = int(self._mesh_mgr.mesh.devices.size)
            else:
                import jax

                n_dev = len(jax.devices())
            from .parallel.plan import device_slice_groups

            out["device_groups"] = device_slice_groups(
                slices, max(slices) + 1, n_dev)
            out["devices"] = n_dev
        except Exception:  # noqa: BLE001 — explain never raises for this
            pass

    def _batch_num_slices(self, index: str, batch_slices) -> int:
        idx = self.holder.index(index)
        top = max(batch_slices) if batch_slices else 0
        if idx is not None:
            top = max(top, idx.max_slice())
        return top + 1

    def _shadow_sampled(self) -> bool:
        """True on every Nth call when [integrity] shadow-sample-1-in
        is set (N > 0)."""
        n = self.shadow_sample
        return n > 0 and next(self._shadow_counter) % n == 0

    def _shadow_check_count(self, index: str, shape, leaves, batch_slices,
                            device_n: int, backend: str) -> int:
        """Recompute a sampled device Count through the host roaring
        fold and compare. On mismatch: count it, log the divergence,
        quarantine the plan signature (identical queries host-fold
        until the TTL expires — a miscompiled plan must not keep
        serving wrong answers), and return the HOST value, which is
        what the caller serves. The host fold is ground truth: it reads
        the same roaring containers the checksums protect."""
        from .parallel.plan import HostCountPlan, _tree_signature

        SHADOW_STATS.inc(f"checks:{backend}")
        host_n = HostCountPlan(self.holder, index, shape, leaves,
                               cache=self._host_cache
                               ).count_slices(batch_slices)
        if host_n is None or int(host_n) == int(device_n):
            return device_n
        import json as _json

        sig = _json.dumps(_tree_signature(shape))
        SHADOW_STATS.inc(f"mismatch:{backend}")
        cur = obs.current_span()
        trace = getattr(getattr(cur, "trace", None), "trace_id", "-")
        obs.get_logger("executor").error(
            "shadow verification MISMATCH (%s): device=%d host=%d "
            "index=%s slices=%d trace=%s — quarantining plan sig",
            backend, int(device_n), int(host_n), index,
            len(batch_slices), trace)
        mgr = self._mesh_mgr
        if mgr is not None:
            mgr.quarantine_plan(sig)
        return int(host_n)

    def _mesh_count_batch(self, index: str, lowered):
        """A batch_fn serving a whole slice set as one mesh collective,
        or None when the tree/backend doesn't qualify. `lowered` is the
        (shape, leaves) pair from plan._lower_tree."""
        if lowered is None:
            return None
        mgr = self.mesh_manager()
        if mgr is None:
            return None
        shape, leaves = lowered

        if self._spmd is not None:
            # Multi-host: the collective must be driven through the
            # descriptor stream so every rank enters it together.
            def batch_fn(batch_slices):
                try:
                    n = self._spmd.count(
                        index, shape, leaves, batch_slices,
                        self._batch_num_slices(index, batch_slices))
                except Exception:  # noqa: BLE001 — device failure → host
                    self._device_failed("SPMD count")
                    return None
                if n is not None and self._shadow_sampled():
                    n = self._shadow_check_count(
                        index, shape, leaves, batch_slices, n, "spmd")
                return n

            return batch_fn

        def batch_fn(batch_slices):
            try:
                with obs.profile.phase("mesh_prepare"):
                    num = self._batch_num_slices(index, batch_slices)
                n = mgr.count(index, shape, leaves, batch_slices, num)
            except Exception:  # noqa: BLE001 — any device failure → host path
                self._device_failed("count")
                return None
            if n is not None and self._shadow_sampled():
                # residual: the check's host fold has its own phase.
                with obs.profile.residual("account"):
                    n = self._shadow_check_count(
                        index, shape, leaves, batch_slices, n, "mesh")
            return n

        return batch_fn

    # Default cost-routing threshold, in work units (slices × tree
    # leaves). Measured on the r2 rig: the device pays a ~2 ms dispatch
    # floor per query while the host C++ kernels cost ~10 µs per
    # slice-leaf unit (960 slices × 2 leaves ≈ 18 ms host, 2.8 ms
    # device) — crossover ≈ 200 units. The reference has no such split:
    # its per-query cost is flat regardless of size
    # (executor.go:567-597); here small queries must not pay the floor
    # (r2 measured nary_* at 26-270× SLOWER than host without routing).
    _DEFAULT_MIN_WORK = 192

    def _route_to_host(self, num_slices: int, num_leaves: int,
                       index: Optional[str] = None, leaves=None,
                       sig: Optional[str] = None) -> Optional[str]:
        """Truthy (the routing reason) when a lowerable Count tree
        should serve from the host C++ kernels anyway — falsy (None)
        when the device path should run. Cost reasons ("min_work",
        "cpu_native"): estimated device benefit below threshold.
        Threshold resolution: explicit device_min_work arg >
        PILOSA_TPU_DEVICE_MIN_WORK env > _DEFAULT_MIN_WORK. The cost
        model applies in EVERY device mode — use_device picks which
        backends are available, not which engine a given query should
        pay for; 0 disables cost routing (every lowerable tree → mesh).
        Routed queries count in /debug/vars mesh stats (routed_host).

        RESILIENCE reasons apply even with cost routing disabled, when
        `index`/`leaves`/`sig` context is supplied: "quarantined" (the
        plan signature is serving a quarantine TTL after repeated
        device failures) and "hbm_infeasible" (a leaf's view alone
        overflows [mesh] hbm-budget-bytes — staging is known-doomed,
        skip straight to the host fold). These also bump the matching
        pilosa_device_fallback_total reason counter.

        The router is BACKEND-AWARE above the threshold: on a `cpu`
        JAX backend, large folds route to the host C++ kernels too —
        JAX-on-CPU loses ~2x to the repo's own popcnt fold at every
        size (BENCH r03-r05 cpu-fallback headlines; the Roaring papers'
        host popcnt path is the CPU winner, arXiv:1611.07612), so with
        no accelerator behind the mesh the dispatch floor buys nothing.
        PILOSA_TPU_CPU_ROUTE_NATIVE=off pins large folds to the mesh
        (measurement / regression escape hatch); thr <= 0 still
        disables all COST routing."""
        reason = self._would_route_to_host(num_slices, num_leaves,
                                           index=index, leaves=leaves,
                                           sig=sig)
        if not reason:
            return None
        mgr = self.mesh_manager()
        if mgr is not None:
            mgr.stats.inc("routed_host")
            if reason in ("quarantined", "hbm_infeasible"):
                mgr.stats.inc(f"fallback_{reason}")
        return reason

    def _min_work(self) -> int:
        """The resolved cost-routing threshold (see _route_to_host)."""
        thr = self.device_min_work
        if thr is None:
            thr = self._min_work_resolved
        if thr is None:
            import os

            env = os.environ.get("PILOSA_TPU_DEVICE_MIN_WORK", "")
            if env:
                try:
                    thr = int(env)
                except ValueError:
                    thr = None
            if thr is None:
                thr = self._DEFAULT_MIN_WORK
            self._min_work_resolved = thr
        return thr

    def _would_route_to_host(self, num_slices: int, num_leaves: int,
                             index: Optional[str] = None, leaves=None,
                             sig: Optional[str] = None) -> Optional[str]:
        """The pure routing decision (reason string or None) — no
        stats, no manager construction — shared by _route_to_host and
        explain(). Resilience gates consult the EXISTING mesh manager
        only: with no manager yet there is nothing staged, no
        quarantine history, and no resolved budget to gate on."""
        mgr = self._mesh_mgr
        if mgr is not None:
            if sig and mgr.plan_quarantined(sig):
                return "quarantined"
            if index is not None and leaves:
                try:
                    if mgr.stage_infeasible(index, leaves, num_slices):
                        return "hbm_infeasible"
                except Exception:  # noqa: BLE001 — peek must not kill
                    pass           # the query; _stage_once re-checks
        thr = self._min_work()
        if thr <= 0:
            return None
        if num_slices * max(1, num_leaves) < thr:
            return "min_work"
        if self._cpu_native_routes():
            return "cpu_native"
        return None

    def _cpu_native_routes(self) -> bool:
        """True when large folds should route to the host despite
        clearing the work threshold: cpu JAX backend + native C++
        kernels live + not opted out (see _route_to_host)."""
        verdict = self._cpu_route_native
        if verdict is None:
            import os

            import jax

            from .ops import native

            verdict = (
                os.environ.get("PILOSA_TPU_CPU_ROUTE_NATIVE", "on").lower()
                not in ("off", "0")
                and jax.default_backend() == "cpu"
                and native.has_native())
            self._cpu_route_native = verdict
        return verdict

    def _device_backend_on(self) -> bool:
        """use_device: True forces the device path, False forces host
        roaring, None = auto — the PILOSA_TPU_USE_DEVICE env var if set
        (on/off/auto etc., config.parse_use_device), else device when a
        TPU backend is live. An unparseable env value warns once and
        falls back to auto rather than failing every query."""
        if self.use_device is False:
            return False
        if self.use_device is None:
            import os

            from .config import parse_use_device

            try:
                forced = parse_use_device(
                    os.environ.get("PILOSA_TPU_USE_DEVICE", ""))
            except ValueError as e:
                if not getattr(self, "_warned_env", False):
                    self._warned_env = True
                    obs.get_logger("executor").warning(
                        "ignoring PILOSA_TPU_USE_DEVICE: %s", e)
                forced = None
            if forced is not None:
                return forced
            import jax

            return jax.default_backend() == "tpu"
        return True

    # -- TopN ----------------------------------------------------------------

    def _execute_top_n(self, index: str, c: Call, slices: Sequence[int],
                       opt: ExecOptions) -> List[tuple]:
        """Two-phase TopN (executor.go:273-310)."""
        row_ids, _ = c.uint_slice_arg("ids")
        n, _ = c.uint_arg("n")

        exact = [False]
        pairs = self._execute_top_n_slices(index, c, slices, opt, exact)
        if not pairs or row_ids or opt.remote:
            return pairs
        if exact[0]:
            # Phase 1 was served by the mesh path with exact global
            # counts — the reference needs phase 2 only because its
            # phase 1 is rank-cache-approximate; a recount would run
            # the identical collective again.
            return pairs

        # Phase 2: exact re-count of candidate ids, only at the coordinator.
        other = c.clone()
        other.args["ids"] = sorted(p[0] for p in pairs)
        trimmed = self._execute_top_n_slices(index, other, slices, opt)
        if n and n < len(trimmed):
            trimmed = trimmed[:n]
        return trimmed

    def _execute_top_n_slices(self, index: str, c: Call, slices: Sequence[int],
                              opt: ExecOptions,
                              exact: Optional[list] = None) -> List[tuple]:
        def map_fn(slice_):
            return self.execute_top_n_slice(index, c, slice_)

        def reduce_fn(prev, v):
            return add_to_pairs(prev or [], v)

        batch_fn = self._mesh_top_n_batch(index, c)
        single_node = self.cluster is None or not self.cluster.nodes
        if batch_fn is not None and exact is not None and single_node:
            inner = batch_fn

            def batch_fn(batch_slices):
                v = inner(batch_slices)
                if v is not None:
                    # Device counts cover every requested slice of the
                    # only node — already exact.
                    exact[0] = True
                return v

        pairs = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn,
                                 batch_fn=batch_fn) or []
        pairs.sort(key=lambda p: (-p[1], p[0]))
        return pairs

    def _mesh_top_n_batch(self, index: str, c: Call):
        """A batch_fn serving TopN (and its exact ids phase 2) as
        masked row-count collectives — including a src bitmap child
        (evaluated on device, serve.row_counts_src), attr filters
        (exact device counts + a bounded host attr walk), and tanimoto
        (band math over three exact device vectors); None only for a
        non-lowerable src tree or malformed args (host path owns the
        error reporting)."""
        if not self._device_backend_on():
            # Must be checked BEFORE consulting the manager: an SPMD
            # worker rank has a manager injected for stats visibility
            # but use_device=False — letting it drive mgr.top_n would
            # enter a global-mesh psum unilaterally and hang every
            # rank.
            return None
        mgr = self.mesh_manager()
        if mgr is None:
            return None
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            return None  # host path owns the error
        attr_predicate = None
        filters = c.args.get("filters")
        field = c.args.get("field") or ""
        if filters and field:
            f_obj = self.holder.frame(index,
                                      c.args.get("frame") or DEFAULT_FRAME)
            if f_obj is None or f_obj.row_attr_store is None:
                return None
            store, allowed = f_obj.row_attr_store, set(filters)

            def attr_predicate(row_id):
                attr = store.attrs(row_id)
                return bool(attr) and attr.get(field) in allowed
        elif filters:
            return None  # filters without a field: host path owns errors
        src = None
        if tanimoto and not c.children:
            return None  # tanimoto requires a src bitmap
        if c.children:
            if len(c.children) > 1:
                return None
            from .parallel.plan import _lower_tree

            src_leaves: list = []
            src_shape = _lower_tree(self.holder, index, c.children[0],
                                    src_leaves)
            if src_shape is None or not src_leaves:
                return None
            src = (src_shape, src_leaves)
        frame = c.args.get("frame") or DEFAULT_FRAME
        n, _ = c.uint_arg("n")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")

        # Shadow verification applies only to the exact-ids form: its
        # host recount (f.top over storage) is ground truth, where the
        # ranked form's host pass is cache-approximate and would
        # false-positive against exact device counts.
        shadow_ok = bool(row_ids) and src is None and \
            attr_predicate is None and not tanimoto

        def shadow(batch_slices, pairs, backend):
            if pairs is None or not shadow_ok or not self._shadow_sampled():
                return pairs
            return self._shadow_check_top_n(index, c, batch_slices,
                                            pairs, backend)

        if self._spmd is not None:
            def batch_fn(batch_slices):
                try:
                    pairs = self._spmd.top_n(
                        index, frame, VIEW_STANDARD, batch_slices,
                        self._batch_num_slices(index, batch_slices),
                        0 if row_ids else n, row_ids,
                        min_threshold or MIN_THRESHOLD, src=src,
                        attr_predicate=attr_predicate,
                        tanimoto_threshold=tanimoto)
                except Exception:  # noqa: BLE001 — device failure → host
                    self._device_failed("SPMD TopN")
                    return None
                return shadow(batch_slices, pairs, "spmd")

            return batch_fn

        def batch_fn(batch_slices):
            try:
                pairs = mgr.top_n(
                    index, frame, VIEW_STANDARD, batch_slices,
                    self._batch_num_slices(index, batch_slices),
                    0 if row_ids else n, row_ids,
                    min_threshold or MIN_THRESHOLD, src=src,
                    attr_predicate=attr_predicate,
                    tanimoto_threshold=tanimoto)
            except Exception:  # noqa: BLE001 — any device failure → host path
                self._device_failed("TopN")
                return None
            return shadow(batch_slices, pairs, "mesh")

        return batch_fn

    def _shadow_check_top_n(self, index: str, c: Call, batch_slices,
                            pairs, backend: str):
        """Recompute a sampled exact-ids TopN through the host storage
        recount and compare. On mismatch the batch_fn returns None, so
        the map/reduce host path serves the query — TopN device
        programs are keyed per fragment pool rather than per query
        tree, so there is no plan signature to quarantine; the mismatch
        counter and log line are the alarm."""
        SHADOW_STATS.inc(f"checks:{backend}")
        host: List[tuple] = []
        for s in batch_slices:
            host = add_to_pairs(host, self.execute_top_n_slice(index, c, s))
        if dict(host) == dict(pairs):
            return pairs
        SHADOW_STATS.inc(f"mismatch:{backend}")
        cur = obs.current_span()
        trace = getattr(getattr(cur, "trace", None), "trace_id", "-")
        obs.get_logger("executor").error(
            "shadow verification MISMATCH (%s TopN): device=%s host=%s "
            "index=%s slices=%d trace=%s — serving host recount",
            backend, dict(pairs), dict(host), index, len(batch_slices),
            trace)
        return None

    def execute_top_n_slice(self, index: str, c: Call, slice_: int) -> List[tuple]:
        """One slice of TopN (executor.go:333-396)."""
        frame = c.args.get("frame") or DEFAULT_FRAME
        n, _ = c.uint_arg("n")
        field = c.args.get("field") or ""
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        filters = c.args.get("filters") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")

        src = None
        if len(c.children) == 1:
            src = self.execute_bitmap_call_slice(index, c.children[0], slice_)
        elif len(c.children) > 1:
            raise QueryError("TopN() can only have one input bitmap")

        f = self.holder.fragment(index, frame, VIEW_STANDARD, slice_)
        if f is None:
            return []
        if min_threshold <= 0:
            min_threshold = MIN_THRESHOLD
        if tanimoto > 100:
            raise QueryError("Tanimoto Threshold is from 1 to 100 only")

        # Plain TopN (no src/ids/filters/tanimoto) evaluates on device:
        # one fused popcount + segment-sum over the fragment's HBM pool
        # (ops/pool.pool_row_counts). EXACT counts over every row — a
        # strict improvement on the reference's rank-cache approximation
        # pass (fragment.go:493-625); the args that need host state
        # (attr filters, src intersection) keep the host path.
        if (src is None and not row_ids and not filters and tanimoto == 0
                and self._device_backend_on()):
            pairs = _device_top_pairs(f, min_threshold, n)
            if pairs is not None:
                return pairs

        return f.top(TopOptions(
            n=n,
            src=src,
            row_ids=row_ids,
            min_threshold=min_threshold,
            filter_field=field,
            filter_values=filters,
            tanimoto_threshold=tanimoto,
        ))

    # -- writes --------------------------------------------------------------

    def _read_bit_args(self, index: str, c: Call):
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        frame = c.args.get("frame")
        if not isinstance(frame, str):
            raise QueryError(f"{c.name}() frame required")
        f = idx.frame(frame)
        if f is None:
            raise FrameNotFoundError()

        row_id, ok = c.uint_arg(f.row_label)
        if not ok:
            raise QueryError(f"{c.name}() row field '{f.row_label}' required")
        col_id, ok = c.uint_arg(idx.column_label)
        if not ok:
            raise QueryError(f"{c.name}() column field '{idx.column_label}' required")
        return f, row_id, col_id

    def _execute_set_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        self._check_writable("SetBit()", opt)
        f, row_id, col_id = self._read_bit_args(index, c)

        timestamp = None
        ts = c.args.get("timestamp")
        if isinstance(ts, str):
            try:
                timestamp = parse_time(ts)
            except ValueError:
                raise QueryError(f"invalid date: {ts}")

        if self._spmd is not None and not opt.remote:
            # Multi-host SPMD: the write broadcast on the descriptor
            # stream IS the replication (every rank applies it to its
            # holder, totally ordered with queries) — the per-replica
            # HTTP fan-out below is the single-host-cluster path.
            return self._spmd.write(index, f.name, row_id, col_id,
                                    ts if isinstance(ts, str) else None,
                                    clear=False)

        return self._execute_mutate_view(
            index, c, opt, col_id,
            lambda: f.set_bit(row_id, col_id, timestamp,
                              deadline=opt.deadline))

    def _execute_set_value(self, index: str, c: Call,
                           opt: ExecOptions) -> bool:
        """SetValue(frame=f, col=N, field=V): overwrite a column's
        integer field value. The encode covers EVERY row of the bsi
        view (set + clear lists), so overwrite needs no
        read-modify-write; replication rides the same quorum fan-out
        as SetBit — the call re-parses verbatim on replicas and hints."""
        self._check_writable("SetValue()", opt)
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        frame = c.args.get("frame")
        if not isinstance(frame, str):
            raise QueryError("SetValue() frame required")
        f = idx.frame(frame)
        if f is None:
            raise FrameNotFoundError()
        col_id, ok = c.uint_arg(idx.column_label)
        if not ok:
            raise QueryError(
                f"SetValue() column field '{idx.column_label}' required")

        fields = [(k, v) for k, v in c.args.items()
                  if k not in ("frame", idx.column_label)]
        if len(fields) != 1:
            raise QueryError(
                "SetValue() requires exactly one field=value pair")
        fname, value = fields[0]
        if isinstance(value, bool) or not isinstance(value, int):
            raise QueryError(f"SetValue() field '{fname}' must be an int")
        schema = f.bsi_field(fname)
        if schema is None:
            from .bsi import FieldNotFoundError

            raise FieldNotFoundError(frame, fname)
        # Validate BEFORE any replica sees the write: an out-of-range
        # value is a clean 422 with no state mutated anywhere.
        schema.validate(value)

        if self._spmd is not None and not opt.remote:
            # The SPMD write descriptor encodes (row, col, clear) bit
            # flips only; multi-valued field writes don't fit it yet.
            raise QueryError(
                "SetValue() is not supported under SPMD serving")

        return self._execute_mutate_view(
            index, c, opt, col_id,
            lambda: f.set_value(fname, col_id, value,
                                deadline=opt.deadline))

    def _execute_clear_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        self._check_writable("ClearBit()", opt)
        f, row_id, col_id = self._read_bit_args(index, c)
        if self._spmd is not None and not opt.remote:
            return self._spmd.write(index, f.name, row_id, col_id, None,
                                    clear=True)
        return self._execute_mutate_view(
            index, c, opt, col_id,
            lambda: f.clear_bit(row_id, col_id, deadline=opt.deadline))

    def _execute_mutate_view(self, index: str, c: Call, opt: ExecOptions,
                             col_id: int, local_fn: Callable[[], bool]) -> bool:
        """Route a bit mutation to every replica owner of its slice
        (executor.go:767-797), with quorum semantics instead of the
        reference's serial first-error-fails fan-out.

        Owners are dispatched in PARALLEL and every future is awaited
        (the _broadcast_query discipline). The write acks once
        `write-consistency` replicas — local apply included — succeed;
        misses are journaled as hints for the drainer to replay, so an
        acked write converges without waiting for anti-entropy. Two
        orderings are load-bearing: owners the failure detector already
        knows are down (node state DOWN, breaker open) are counted
        BEFORE local apply — a write that cannot possibly reach
        consistency is rejected with no state mutated anywhere, so
        there is no acked-but-ambiguous outcome and the write path
        never pays a timeout to a known-dead node; and hints are
        enqueued even on the below-consistency path, because any
        replica that DID apply must still converge with the rest."""
        slice_ = col_id // SLICE_WIDTH
        owners = self._fragment_nodes(index, slice_)
        locals_ = [n for n in owners if n is None or n.host == self.host]
        remotes = [n for n in owners if n is not None and n.host != self.host]

        if opt.remote or not remotes:
            # Remote leg (the coordinator counts this node's ack) or a
            # single-owner slice: plain local apply.
            ret = False
            for _ in locals_:
                if local_fn():
                    ret = True
            if locals_:
                self._observe_write_epochs(index, c, slice_)
            return ret

        level = self.write_consistency
        required = required_acks(level, len(owners))
        hints = self.hints

        down: list = []
        live = list(remotes)
        if hints is not None:
            breaker = self._breaker_callable()
            down = [n for n in remotes
                    if n.state == NODE_STATE_DOWN
                    or (breaker is not None
                        and breaker(n.host) == "open")]
            live = [n for n in remotes if n not in down]
            if len(locals_) + len(live) < required:
                CONSISTENCY_STATS.inc(f"{level}:rejected_unavailable")
                raise WriteConsistencyError(
                    f"write-consistency={level} needs {required} of "
                    f"{len(owners)} replicas, only "
                    f"{len(locals_) + len(live)} reachable",
                    level=level, required=required, acked=0)

        ret = False
        acked = 0
        for _ in locals_:
            if local_fn():
                ret = True
            acked += 1
        wrote_epochs: dict = {}
        if locals_:
            wrote_epochs = self._observe_write_epochs(index, c, slice_)

        q = Query(calls=[c])
        futures = [
            (node, self._pool.submit(obs.wrap_ctx(self._exec_remote),
                                     node, index, q, None, opt))
            for node in live
        ]
        failures = []
        for node, fut in futures:
            try:
                res = fut.result()
                if res and res[0]:
                    ret = True
                acked += 1
            except Exception as err:  # noqa: BLE001 — collected below
                failures.append((node.host, err))

        if hints is None:
            # Legacy contract for bare executors: no handoff plane
            # means no repair path, so a remote failure must surface.
            if failures:
                raise failures[0][1]
            return ret

        pql = str(q)
        missed = [n.host for n in down] + [h for h, _ in failures]
        for host in missed:
            hints.enqueue_query(host, index, pql, epochs=wrote_epochs)

        if acked >= required:
            CONSISTENCY_STATS.inc(
                f"{level}:hinted" if missed else f"{level}:ok")
            return ret
        CONSISTENCY_STATS.inc(f"{level}:below_consistency")
        raise WriteConsistencyError(
            f"write-consistency={level}: {acked} of {required} required "
            f"replica acks ({len(failures)} failed mid-write; misses "
            f"journaled as hints)",
            level=level, required=required, acked=acked)

    def _observe_write_epochs(self, index: str, c: Call,
                              slice_: int) -> dict:
        """Feed the epoch tracker the post-apply epochs of every
        fragment a local mutation touched (the write fans out to one
        frame, but a SetBit may land in standard + inverse + time
        views): the coordinator's freshness bar advances at WRITE
        time, not at the next digest poll, so a follower missing this
        write ages from now. Returns the observed (key -> epoch) map —
        the write path carries it on hints so replay can floor-raise
        the recovered replica to the origin's numbering."""
        out: dict = {}
        tracker = self.epochs
        if tracker is None:
            return out
        frame = c.args.get("frame")
        f = self.holder.frame(index, frame if isinstance(frame, str)
                              and frame else DEFAULT_FRAME)
        if f is None:
            return out
        for vname, view in list(f.views.items()):
            frag = view.fragments.get(slice_)
            if frag is not None and not frag._pending_load:
                key = fragment_key(index, f.name, vname, slice_)
                tracker.observe_local(key, frag.epoch)
                out[key] = frag.epoch
        return out

    def _fragment_nodes(self, index: str, slice_: int):
        if self.cluster is None or not self.cluster.nodes:
            return [None]  # single-node: always local
        return self.cluster.fragment_nodes(index, slice_)

    def _other_nodes(self):
        if self.cluster is None:
            return []
        return [n for n in self.cluster.nodes if n.host != self.host]

    def _execute_set_row_attrs(self, index: str, c: Call, opt: ExecOptions):
        """SetRowAttrs (executor.go:799-855)."""
        self._check_writable("SetRowAttrs()", opt)
        if self._spmd is not None and not opt.remote:
            # Replicate through the descriptor stream (PQL re-serialized,
            # the reference's own remote-exec encoding, pql/ast.go
            # String()): every rank applies the attrs to its own store,
            # totally ordered with writes and queries.
            return self._spmd.execute_pql(index, str(c))
        frame_name = c.args.get("frame")
        if not isinstance(frame_name, str):
            raise QueryError("SetRowAttrs() frame required")
        f = self.holder.frame(index, frame_name)
        if f is None:
            raise FrameNotFoundError()
        row_id, ok = c.uint_arg(f.row_label)
        if not ok:
            raise QueryError(f"SetRowAttrs() row field '{f.row_label}' required")

        attrs = dict(c.args)
        attrs.pop("frame", None)
        attrs.pop(f.row_label, None)
        f.row_attr_store.set_attrs(row_id, attrs)

        if not opt.remote:
            self._broadcast_with_hints(index, Query(calls=[c]), opt)
        return None

    def _execute_bulk_set_row_attrs(self, index: str, calls: Sequence[Call],
                                    opt: ExecOptions) -> list:
        """Grouped bulk insertion (executor.go:857-941)."""
        self._check_writable("SetRowAttrs()", opt)
        if self._spmd is not None and not opt.remote:
            self._spmd.execute_pql(index, " ".join(str(c) for c in calls))
            return [None] * len(calls)
        by_frame = {}
        for c in calls:
            frame_name = c.args.get("frame")
            if not isinstance(frame_name, str):
                raise QueryError("SetRowAttrs() frame required")
            f = self.holder.frame(index, frame_name)
            if f is None:
                raise FrameNotFoundError()
            row_id, ok = c.uint_arg(f.row_label)
            if not ok:
                raise QueryError(f"SetRowAttrs() row field '{f.row_label}' required")
            attrs = dict(c.args)
            attrs.pop("frame", None)
            attrs.pop(f.row_label, None)
            by_frame.setdefault(frame_name, {}).setdefault(row_id, {}).update(attrs)

        for frame_name, items in by_frame.items():
            self.holder.frame(index, frame_name).row_attr_store.set_bulk_attrs(items)

        if not opt.remote:
            self._broadcast_with_hints(index, Query(calls=list(calls)), opt)
        return [None] * len(calls)

    def _execute_set_column_attrs(self, index: str, c: Call, opt: ExecOptions):
        """SetColumnAttrs (executor.go:943-998)."""
        self._check_writable("SetColumnAttrs()", opt)
        if self._spmd is not None and not opt.remote:
            return self._spmd.execute_pql(index, str(c))
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()

        id_, ok = c.uint_arg("id")
        col_name = "id"
        if not ok:
            id_, ok = c.uint_arg(idx.column_label)
            if not ok:
                raise QueryError("SetColumnAttrs() id required")
            col_name = idx.column_label

        attrs = dict(c.args)
        attrs.pop(col_name, None)
        idx.column_attr_store.set_attrs(id_, attrs)

        if not opt.remote:
            self._broadcast_with_hints(index, Query(calls=[c]), opt)
        return None

    def _broadcast_query(self, index: str, q: Query, opt: ExecOptions):
        """Forward a write to every other node in parallel. EVERY
        future is awaited before any error is raised (the reference's
        first-error-wins, executor.go:833-855, leaks unawaited futures
        behind one slow replica), and the error lists every failed
        host. The client layer owns per-node retry; nodes that still
        fail are reported together via BroadcastError."""
        nodes = self._other_nodes()
        if not nodes:
            return
        futures = [
            (node, self._pool.submit(obs.wrap_ctx(self._exec_remote),
                                     node, index, q, None, opt))
            for node in nodes
        ]
        failures = []
        for node, fut in futures:
            try:
                fut.result()
            except Exception as err:  # noqa: BLE001 — collected below
                failures.append((node.host, err))
        if failures:
            raise BroadcastError(failures, len(nodes))

    def _broadcast_with_hints(self, index: str, q: Query,
                              opt: ExecOptions) -> None:
        """Attr broadcasts mutate the local store BEFORE fanning out,
        so a failed peer used to leave local state mutated with no
        repair path behind the error. With a hint manager wired, the
        failed hosts' calls are journaled and replayed — attrs
        converge the same way bits do and the write acks; without one
        (bare executors), the BroadcastError surfaces as before."""
        try:
            self._broadcast_query(index, q, opt)
        except BroadcastError as err:
            if self.hints is None:
                raise
            pql = str(q)
            for host, _e in err.failures:
                self.hints.enqueue_query(host, index, pql)

    # -- distributed fan-out -------------------------------------------------

    def _exec_remote(self, node, index: str, q: Query,
                     slices: Optional[Sequence[int]], opt: ExecOptions) -> list:
        """Remote execution via the injected client (executor.go:1000-1083).
        The query travels as its canonical PQL serialization, plus the
        REMAINING deadline budget when one is set (the client forwards
        it as X-Pilosa-Deadline-Us so downstream hops inherit it)."""
        if self.client is None:
            raise SliceUnavailableError()
        sp = obs.span("fanout", node=node.host,
                      slices=len(slices) if slices else 0)
        try:
            with sp, obs.profile.phase("fanout_remote"):
                fault.point("executor.fanout", node=node.host)
                opt.check_deadline(f"fanout to {node.host}")
                kw = {}
                if opt.deadline is not None:
                    # Only pass the kwarg when set: test fakes implement
                    # the positional execute_query seam without it.
                    kw["deadline"] = opt.deadline
                return self.client.execute_query(
                    node, index, str(q), slices or [], remote=True, **kw)
        finally:
            left = opt.deadline_left()
            if left is not None:
                # Tagged on exit so an expired hop shows a NEGATIVE
                # remaining budget in /debug/queries.
                sp.tag(deadline_left_us=int(left * 1e6))

    def _breaker_callable(self, opt: Optional[ExecOptions] = None):
        """The per-query breaker snapshot when `opt` carries one
        (execute() filled it — stable across re-splits), else the
        injected client's live breaker_state(host) callable, or None
        when it has no breaker registry (test fakes, single client)."""
        if opt is not None and opt.breaker_snapshot is not None:
            snap = opt.breaker_snapshot
            return lambda host: snap.get(host, "closed")
        state = getattr(self.client, "breaker_state", None)
        return state if callable(state) else None

    def _slices_by_node(self, nodes, index: str, slices: Sequence[int],
                        opt: Optional[ExecOptions] = None):
        """node -> slices owned, restricted to `nodes`
        (executor.go:1087-1101).

        Locality hierarchy (same-chip → same-pod-ICI → cross-node
        HTTP): a slice whose picked owner is a configured ICI peer
        (`[cluster] ici-hosts`) is folded into the LOCAL node's group —
        its shard is already addressable through this node's mesh, and
        the collective reduces over the interconnect — so only slices
        owned by hosts OUTSIDE the pod pay the HTTP ring.

        The owner ladder is climbed once per (partition, placement
        ring), lazily, over state read once here, and not once per
        slice: a cluster has `partition_n` partitions and a headline
        query 960 slices. Per slice stays what differs per slice: the
        ring of a slice handed off mid-resize, and the replica spread
        of a bounded-staleness read (its p2c sample and epoch check).
        `read_stats` is bumped once per label, as the split ends."""
        cluster = self.cluster
        local_node = (cluster.node_by_host(self.host)
                      if self.ici_hosts else None)
        if local_node is not None and local_node not in nodes:
            # e.g. a re-split that excluded this node: don't route an
            # ICI peer's slices back into the excluded local group.
            local_node = None
        breaker = self._breaker_callable(opt)
        # Bounded-staleness reads (X-Pilosa-Staleness > 0) spread over
        # every in-sync replica; strict reads (the default) and remote
        # legs keep the owner-only pick bit-for-bit.
        read_bound = (opt.staleness
                      if opt is not None and not opt.remote else 0.0)
        sclass = "bounded" if read_bound > 0 else "strict"
        partial = opt is not None and opt.partial
        prefer = self.host if self.prefer_local_reads else None
        ici_hosts = self.ici_hosts or None
        serving_ring, target_ring, handed = cluster.placement_rings(index)
        partitions = cluster.partition_table(index)
        m = {}
        # partition -> where its slices go, one dict per ring: m's
        # list of the picked node, None (no serving owner, partial
        # mode), or the owners as a tuple (spread per slice).
        on_serving, on_target = {}, {}
        spread = {}  # label -> slices placed by the per-slice spread

        def group(pick):
            if (local_node is not None and pick.host != self.host
                    and pick.host in self.ici_hosts):
                # ICI-tier slice: serve it from the local mesh dispatch
                # (one psum over the pod fabric beats an HTTP leg).
                if opt is not None:
                    opt.used_ici = True
                pick = local_node
            return m.setdefault(pick, [])

        def decide(partition, ring):
            owners = [o for o in cluster.partition_nodes(partition, ring)
                      if o in nodes]
            if partial:
                # Membership-aware degradation: a JOINING node hasn't
                # received its slices yet and a DOWN node can't answer,
                # so in partial mode route only to serving replicas
                # (ACTIVE/LEAVING) and report the slice missing when
                # none remain — never hang on a non-serving owner.
                owners = [o for o in owners if o.state in SERVING_STATES]
                if not owners:
                    return None
            elif not owners:
                raise SliceUnavailableError()
            if read_bound > 0 and len(owners) > 1:
                return tuple(owners)
            # Prefer replicas the status-poll daemon currently sees UP
            # AND whose circuit breaker is closed; a slice whose owners
            # are all marked DOWN/open still tries one (liveness is
            # advisory — the reactive re-split below is the authority,
            # executor.go:1140-1151).
            return group(preferred_owner(owners, breaker, prefer=prefer,
                                         ici_hosts=ici_hosts))

        def spread_pick(owners, slice_):
            # Bounded reads first try the follower-spread ladder:
            # pick_read_replica over in-sync replicas (breaker-closed,
            # epoch staleness within the client's bound, p2c by
            # gossiped queue depth). An empty candidate set falls DOWN
            # the ladder to the strict owner pick — never sideways to
            # a staler replica — and the fallback is counted.
            pick = pick_read_replica(
                owners, breaker,
                staleness_ok=lambda h: self.epochs.staleness_ok_slice(
                    h, index, slice_, read_bound),
                queue_depth=self.epochs.queue_depth,
                prefer=self.host, ici_hosts=ici_hosts,
                node_ok=self.peer_health_ok)
            if pick is not None:
                # "follower" = spread away from the ring primary
                # (owners[0] is ring order) — the label that proves
                # replicas actually absorb read load.
                label = ("follower|" if pick.host != owners[0].host
                         else "owner|") + sclass
            else:
                label = "fallback_owner|" + sclass
                pick = preferred_owner(owners, breaker, prefer=prefer,
                                       ici_hosts=ici_hosts)
            spread[label] = spread.get(label, 0) + 1
            group(pick).append(slice_)

        try:
            for slice_ in slices:
                partition = partitions[slice_]
                if handed and slice_ in handed:
                    decided, ring = on_target, target_ring
                else:
                    decided, ring = on_serving, serving_ring
                dest = decided.get(partition, decided)
                if dest is decided:  # not decided yet (None is a decision)
                    dest = decided[partition] = decide(partition, ring)
                if dest.__class__ is list:
                    dest.append(slice_)
                elif dest is None:
                    opt.missing_slices.append(slice_)
                else:
                    spread_pick(dest, slice_)
        finally:
            # Every slice the spread did not place went to the strict
            # ring pick: one label. Counted also when an unowned slice
            # raises, for the slices placed before it.
            spread_n = sum(spread.values())
            owner_n = sum(len(v) for v in m.values()) - spread_n
            if owner_n:
                label = "owner|" + sclass
                spread[label] = spread.get(label, 0) + owner_n
            for label, n in spread.items():
                self.read_stats.inc(label, n)
            climbs = spread_n + sum(
                dest.__class__ is not tuple for decided in
                (on_serving, on_target) for dest in decided.values())
            if climbs:
                self.placement_stats.inc("owner_decisions", climbs)
        return m

    def _map_reduce(self, index: str, slices: Sequence[int], c: Call,
                    opt: ExecOptions, map_fn, reduce_fn, batch_fn=None):
        """Cluster-wide map + reduce with node-failure re-split
        (executor.go:1103-1163).

        batch_fn, when given, serves a whole LOCAL slice batch in one
        device collective (the mesh serving path); a None return falls
        back to the per-slice map_fn fan-out. Remote nodes always go
        through the RPC path — each runs its own batch_fn on arrival."""
        if self.cluster is None or not self.cluster.nodes:
            return self._mapper_local(slices, map_fn, reduce_fn, batch_fn,
                                      opt.deadline)

        if opt.remote:
            # Already forwarded: restrict to the local node.
            nodes = [self.cluster.node_by_host(self.host)]
        else:
            nodes = list(self.cluster.nodes)

        return self._mapper(nodes, index, slices, c, opt, map_fn, reduce_fn,
                            batch_fn)

    @staticmethod
    def _transient_error(err: BaseException) -> bool:
        """Should this node failure trigger a replica re-split?
        Duck-typed on the `transient` attribute so the executor never
        imports the HTTP client (api -> handler -> executor cycle) and
        never parses messages: structured ClientErrors say so
        themselves, DeadlineExceededError says False, and anything
        unannotated (socket errors from fakes, pool crashes) defaults
        to transient — matching the reference's retry-anything
        behavior (executor.go:1140-1151). Non-transient remote errors
        (bad PQL, missing frame) would fail identically on every
        replica, so they propagate immediately."""
        transient = getattr(err, "transient", None)
        if transient is not None:
            return bool(transient)
        return not isinstance(err, QueryError)

    def _mapper(self, nodes, index: str, slices: Sequence[int], c: Call,
                opt: ExecOptions, map_fn, reduce_fn, batch_fn=None):
        with obs.profile.phase("route_slices"):
            m = self._slices_by_node(nodes, index, slices, opt)

        futures = {}
        local_fut = hand = None
        for node, node_slices in m.items():
            # wrap_ctx: pool workers inherit the active trace span (a
            # fresh contextvars copy per submit), so the gather/fan-out
            # spans attach under this query, not nowhere.
            if node.host == self.host:
                # pool_handoff: the two thread switches of the local
                # leg. Entered here and left by the worker as it takes
                # the leg up; entered again by the worker as it returns
                # and left below, where wait() wakes.
                hand = [obs.profile.phase("pool_handoff").start()]
                fut = local_fut = self._pool.submit(
                    obs.wrap_ctx(self._local_leg), hand, node_slices,
                    map_fn, reduce_fn, batch_fn, opt.deadline)
            elif not opt.remote:
                # This group actually pays a cross-node HTTP leg — the
                # query's tier is `http` no matter what else served.
                opt.used_http = True
                fut = self._pool.submit(
                    obs.wrap_ctx(self._exec_remote_one), node, index, c,
                    node_slices, opt)
            else:
                continue
            futures[fut] = (node, node_slices)

        result = None
        pending = set(futures)
        while pending:
            left = opt.deadline_left()
            if left is not None and left <= 0:
                for fut in pending:
                    fut.cancel()
                raise DeadlineExceededError(
                    f"fan-out wait: deadline exceeded by "
                    f"{-left * 1e6:.0f}us")
            done, pending = wait(pending, timeout=left,
                                 return_when=FIRST_COMPLETED)
            for fut in done:
                node, node_slices = futures[fut]
                if fut is local_fut:
                    hand[0].stop()
                try:
                    v = fut.result()
                except Exception as err:
                    if not self._transient_error(err):
                        for f in pending:
                            f.cancel()
                        raise
                    # Re-split this node's slices across remaining
                    # replicas (executor.go:1140-1151). The resplit
                    # span (resplit=1) makes the double failure visible
                    # in traces.
                    remaining = [n for n in nodes if n is not node]
                    try:
                        with obs.span("resplit", node=node.host,
                                      slices=len(node_slices), resplit=1):
                            v = self._mapper(remaining, index, node_slices,
                                             c, opt, map_fn, reduce_fn,
                                             batch_fn)
                    except SliceUnavailableError as resplit_err:
                        if opt.partial:
                            # No replica left for these slices: report
                            # them missing instead of failing.
                            opt.missing_slices.extend(node_slices)
                            continue
                        # Chain the re-split failure so the trace shows
                        # BOTH the root cause and the exhausted re-split.
                        raise err from resplit_err
                    if v is None:
                        # A partial-mode re-split that lost EVERY slice
                        # produced no result; nothing to fold.
                        continue
                result = reduce_fn(result, v)
        return result

    def _exec_remote_one(self, node, index: str, c: Call,
                         slices: Sequence[int], opt: ExecOptions):
        results = self._exec_remote(node, index, Query(calls=[c]), slices, opt)
        return results[0] if results else None

    def _local_leg(self, hand: list, *args):
        """_mapper_local as _mapper's pool task: closes the hand-off
        phase _mapper opened at submit, and opens the one _mapper
        closes when its wait() wakes."""
        hand[0].stop()
        try:
            return self._mapper_local(*args)
        finally:
            hand[0] = obs.profile.phase("pool_handoff").start()

    def _mapper_local(self, slices: Sequence[int], map_fn, reduce_fn,
                      batch_fn=None, deadline: Optional[float] = None):
        """Local per-slice map + reduce (executor.go:1200-1236 runs a
        goroutine per slice; here the map fans out on the dedicated
        _slice_pool — NOT self._pool, see __init__ — and the reduce
        folds results in slice order, so the output is deterministic
        regardless of completion order). reduce_fn must handle prev=None
        by allocating a fresh accumulator — results never alias fragment
        row caches.

        When batch_fn serves the whole batch (mesh path), its result
        feeds reduce_fn directly — one device collective replaces the
        per-slice fan-out. `deadline` bounds each slice-result wait
        with the remaining budget (absolute monotonic instant)."""
        slices = list(slices)
        with obs.span("gather", slices=len(slices)) as gsp:
            if batch_fn is not None and slices:
                v = batch_fn(slices)
                if v is not None:
                    gsp.tag(mode="batch")
                    return reduce_fn(None, v)
            result = None
            if len(slices) <= 1:
                with obs.span("map", slices=len(slices)):
                    for slice_ in slices:
                        result = reduce_fn(result, map_fn(slice_))
                gsp.tag(mode="inline")
                return result
            gsp.tag(mode="fanout")
            futures = [self._slice_pool.submit(obs.wrap_ctx(map_fn), s)
                       for s in slices]
            try:
                with obs.span("reduce", slices=len(slices)):
                    for fut in futures:
                        if deadline is None:
                            result = reduce_fn(result, fut.result())
                            continue
                        left = deadline - time.monotonic()
                        if left <= 0:
                            raise DeadlineExceededError(
                                f"slice wait: deadline exceeded by "
                                f"{-left * 1e6:.0f}us")
                        try:
                            v = fut.result(timeout=left)
                        except TimeoutError:
                            raise DeadlineExceededError(
                                "slice wait: deadline exceeded")
                        result = reduce_fn(result, v)
            except BaseException:
                # Don't leave orphaned slice tasks burning pool workers
                # while the node-failure re-split re-executes these
                # slices.
                for fut in futures:
                    fut.cancel()
                raise
            return result
