"""Mesh serving layer: the bridge from the query Executor to the
device mesh.

This is what makes the shard_map+psum engine the SERVING path rather
than a library demo: a MeshManager owns staged device images of live
holder views and the Executor routes whole slice batches through it —
one jitted collective per query instead of the reference's
goroutine-per-slice fan-out (executor.go:1200-1236) or this codebase's
per-slice thread-pool fallback (parallel/plan.py).

Staging and maintenance:
  - A (index, frame, view) is staged once via build_sharded_index and
    then maintained INCREMENTALLY: each Fragment keeps a mutation log
    (core/fragment.py log_since), and refresh() folds the bits written
    since the staged generation into one device scatter
    (compile_serve_apply_writes), which runs in the staged pool's own
    buffer when no reader holds the pool and from one copy of it when
    one does (_apply_writes). A container the writes CREATED is
    patched into a free slot of its slice first (_refresh_walk,
    compile_serve_patch_containers; the pool's key order afterwards:
    ops.pool.assign_free_slots), and its bits go through the same
    scatter. Only what a slot cannot take forces a restage — a
    container emptied, a slice with no free slot, a row new to the
    view, a sparse or mixed-format view, a fragment that appeared or
    went, a bulk import — matching the reference's cheap mmap mutation
    (fragment.go:371-413) without ever re-uploading the pool.
  - Queries carry a per-slice ownership mask, so one staged index
    serves any slice subset (the cluster's slicesByNode split,
    executor.go:1087-1101) and non-owned slices contribute nothing to
    the psum.

Counts are returned as Python ints combined from (lo, hi) int32 limbs
(mesh.combine_count) — no int32 saturation at 2^31 set bits.
"""

from __future__ import annotations

import contextlib
import json
import queue
import threading
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..core.fragment import MUTATION_EPOCH
from ..obs import StatMap, costs, get_logger, jax_scope, profile, span
from ..obs.health import HEALTH
from ..ops.pool import (
    CONTAINER_WORDS,
    INVALID_KEY,
    ROW_SPAN,
    PatchRefused,
    assign_free_slots,
    fold_log_entries,
    plan_slice_mutations,
    plan_sparse_slice,
)
from .mesh import (
    SLICE_AXIS,
    SparseShardedIndex,
    _VALUE_ALIGN,
    build_sharded_index,
    build_sparse_sharded_index,
    coarse_row_starts,
    combine_count,
    compile_serve_count_sparse_pair,
    compile_serve_topn_gather,
    compile_serve_topn_rank,
    global_row_ids,
    pick_slice_formats,
    slice_format_stats,
    sparse_capacity,
    sparse_pool_bytes,
    sparse_pool_dims,
    split_bitmaps_by_format,
    compile_serve_apply_writes,
    compile_serve_count,
    compile_serve_count_batch_shared,
    compile_serve_count_coarse_pallas,
    compile_serve_count_coarse_pallas_batch,
    compile_serve_count_coarse_pallas_uniform,
    compile_serve_patch_containers,
    compile_serve_row_counts,
    compile_serve_row_counts_src,
    compile_serve_row_counts_src_sparse,
    compile_serve_row_counts_tanimoto,
    compile_serve_sparse_write,
    default_mesh,
    pack_container_patches,
    pack_mutation_batches,
    pack_sparse_writes,
    resolve_row_indices,
    slice_offset_keys,
    sparse_overflow_slots,
)
from .plan import CompiledPlanCache, _tree_signature, format_signature
from .. import fault
from ..errors import DeviceResourceError


_log = get_logger("mesh")


def _is_compile_refusal(e: BaseException) -> bool:
    """The COMPILER refused the program: a Pallas kernel wants more
    scoped VMEM than the chip has, or XLA's compile-time accounting
    (which bills every aliased operand as its own buffer) overflows
    HBM. jit compiles lazily, so this surfaces at the first launch,
    inside _guarded_exec, as the same JaxRuntimeError class and the
    same RESOURCE_EXHAUSTED status a runtime allocation failure
    carries; the wording is what tells them apart
    (tests/test_tpu_compile.py holds this to the installed compiler's
    real messages). Nothing staged is in such a program's way:
    evicting views and trying again only throws the pool away."""
    msg = str(e)
    return ("memory space vmem" in msg or "scoped vmem" in msg
            or "compile permanent error" in msg
            or "Mosaic failed to compile" in msg)


def _is_resource_exhausted(e: BaseException) -> bool:
    """Device OOM classifier. jaxlib surfaces allocation failure as
    JaxRuntimeError with RESOURCE_EXHAUSTED (or "out of memory") in the
    message — there is no exception subclass for it, so the message IS
    the contract — and the fault seams raise
    SimulatedResourceExhausted carrying the same marker. A compiler
    refusal (_is_compile_refusal) carries it too and is NOT one."""
    if isinstance(e, fault.SimulatedResourceExhausted):
        return True
    if _is_compile_refusal(e):
        return False
    msg = str(e)
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def _num_env(name: str, default, cast=int):
    """Env-var number with parse-failure fallback — the one copy of
    the try/cast/except idiom the tunables below share."""
    import os

    try:
        return cast(os.environ.get(name, str(default)))
    except ValueError:
        return default


class DispatchGenMoved(Exception):
    """Raised inside the launch gate when a view's dispatch generation
    moved between resolve and launch — another dispatch (batch thread,
    racing querier, post-eviction restage) launched against the same
    staged image first. Pure control flow: the caller falls back to a
    coalescing path; never a plan failure, never a strike."""


class StagedView:
    """One (index, frame, view)'s staged device image + bookkeeping."""

    __slots__ = ("sharded", "row_ids", "keys_host", "slots_host",
                 "free_slots", "slice_gens", "num_slices", "idx_cache",
                 "host_idx_cache", "last_used",
                 "last_stage_s", "inc_spend_s", "inc_ewma_s", "inc_count",
                 "validated_epoch", "pins", "sparse", "sparse_keys_host",
                 "sparse_cards_host", "slice_formats", "sparse_idx_cache",
                 "sparse_slots_host", "sparse_free_slots", "sparse_kmax",
                 "sparse_k64", "sparse_overflow", "sparse_overflow_dev",
                 "sparse_dev_idx_cache", "dispatch_gen", "writes_seen")

    def __init__(self, sharded, row_ids, keys_host, slice_gens, num_slices,
                 sparse=None, sparse_keys_host=None, sparse_cards_host=None,
                 slice_formats=None):
        self.sharded = sharded            # ShardedIndex (device, padded S)
        self.row_ids = row_ids            # (R,) uint64 dense row table
        self.keys_host = keys_host        # (S_padded, cap) int32, SORTED
        # The device slot of each entry of keys_host, and how many slots
        # each slice has left for containers created after staging
        # (ops.pool.assign_free_slots has the layout): as staged the
        # identity, and capacity less the slice's containers.
        s_pad, cap = keys_host.shape
        self.slots_host = np.tile(np.arange(cap, dtype=np.int32),
                                  (s_pad, 1))
        self.free_slots = cap - (keys_host != INVALID_KEY).sum(axis=1)
        self.slice_gens = slice_gens      # per-slice (fragment, gen);
        #                                   None = staged as absent
        self.num_slices = num_slices      # unpadded staged slice count
        # Sparse (sorted-array) pool of this view, or None when every
        # slice staged dense. row_ids is SHARED between the pools (one
        # global table), so one dense row id resolves against either
        # key layout. slice_formats is the (num_slices,) uint8 format
        # byte (1 = sorted-array) the stager picked — carried across
        # restages as the hysteresis input so a boundary slice doesn't
        # flip layout per refresh.
        self.sparse = sparse
        # The sparse pool's host keys, SORTED, with the device slot of
        # each beside it (sparse_slots_host: the layout of
        # ops.pool.assign_free_slots, as keys_host/slots_host), the
        # cards by device SLOT (the device cards' mirror: what the
        # single-leaf _sparse_count sums), the free slots a slice has
        # left, and a bound on every card of the pool (the sparse
        # ranked program's loop: compile_serve_row_counts_src_sparse).
        # _refresh_sparse keeps all of them exact after a write.
        self.sparse_keys_host = sparse_keys_host    # (S_padded, C) int32
        self.sparse_cards_host = sparse_cards_host  # (S_padded, C) int32
        if sparse_keys_host is not None:
            sp_pad, sp_cap = sparse_keys_host.shape
            self.sparse_slots_host = np.tile(
                np.arange(sp_cap, dtype=np.int32), (sp_pad, 1))
            self.sparse_free_slots = sp_cap - (
                sparse_keys_host != INVALID_KEY).sum(axis=1)
            self.sparse_kmax = int(sparse_cards_host.max(initial=0))
        else:
            self.sparse_slots_host = self.sparse_free_slots = None
            self.sparse_kmax = 0
        # mesh.slice_offset_keys of sparse_keys_host, made at the first
        # resolve and kept with it (a 480-slice Star Trace pool is 4.4 M
        # keys: ~10 ms to make again for every row resolved).
        self.sparse_k64: Optional[np.ndarray] = None
        # mesh.sparse_overflow_slots of sparse_cards_host (the slots of
        # containers of more than one value), kept with the cards, and
        # its device copy (None: made again at the next sparse ranking).
        self.sparse_overflow: Optional[np.ndarray] = None
        self.sparse_overflow_dev = None
        # dense_id -> DEVICE (idx, hit) against the sparse pool, for a
        # sparse src leaf (_sparse_leaf_arrays; as idx_cache).
        self.sparse_dev_idx_cache: "OrderedDict[int, tuple]" = OrderedDict()
        self.slice_formats = (slice_formats if slice_formats is not None
                              else np.zeros(num_slices, dtype=np.uint8))
        # dense_id -> host (idx, hit) resolved against the SPARSE key
        # table (same lifetime argument as host_idx_cache below).
        self.sparse_idx_cache: "OrderedDict[int, tuple]" = OrderedDict()
        # dense_id -> (flat_idx, hit) device arrays (resolve_row_indices
        # output), LRU-ordered (move-to-end on hit — a hot row staged
        # early must not be the first evicted at the 1024 bound). Valid
        # as long as the key layout is — incremental word scatters don't
        # touch it; a container patched into a free slot moves no other
        # container and drops its own row's entry (_refresh_walk); a
        # restage builds a fresh StagedView, so the cache
        # dies with the stale keys. Uploading these per query is
        # several device_puts; cached, a repeat-row query pays
        # nothing.
        self.idx_cache: "OrderedDict[int, tuple]" = OrderedDict()
        # dense_id -> HOST (idx, hit) numpy pair for the fused
        # single-dispatch path, which passes gather metadata as jit
        # arguments instead of device_put-ing it (the resolve itself is
        # ~0.1 ms of searchsorted — cheap, but a hot repeated row should
        # pay zero). Same lifetime argument as idx_cache above.
        self.host_idx_cache: "OrderedDict[int, tuple]" = OrderedDict()
        # Use-epoch stamp (MeshManager._use_epoch at last access): the
        # evictor never evicts a view used by the RESOLUTION in
        # progress, so one query touching more frames than the budget
        # fits degrades to over-budget rather than restage-thrashing.
        self.last_used = 0
        # Wall seconds the last _stage of this view took — one side of
        # refresh()'s measured incremental-vs-restage cost gate — and
        # the incremental seconds spent on this view since that stage
        # (drives the periodic restage probe).
        self.last_stage_s: Optional[float] = None
        self.inc_spend_s = 0.0
        # EWMA (seconds) of THIS view's measured incremental-apply cost
        # — the other side of the gate. Per-view, not manager-global
        # (ADVICE r4): with heterogeneous view sizes a cheap scatter
        # measured on a small view must not drive repeated full
        # restages of a large one. Seeded across a restage of the same
        # key so a gate-chosen restage doesn't amnesia the estimate.
        self.inc_ewma_s: Optional[float] = None
        # Incremental applies since this view was staged — drives the
        # deterministic (count-based) restage policy in SPMD mode.
        self.inc_count = 0
        # In-flight query refcount: taken at plan time (_stage_leaves*
        # under _mu) and released after the fold/fetch. A pinned view
        # is never evicted — neither by the budget scan nor by the OOM
        # emergency evictor — so a query's staged arrays stay resident
        # for its whole unlocked execution window (the use-epoch stamp
        # below only protects the resolution currently holding _mu).
        self.pins = 0
        # Per-view dispatch generation: bumped (under the launch gate)
        # every time a device execution launches against this image.
        # The lone fused path captures the generations of its resolved
        # views and re-validates them at launch: if another dispatch
        # (a racing querier's batch, an eviction-churn restage's first
        # query) moved them in between, the lone launch aborts to the
        # coalescing batch path instead of stacking a second concurrent
        # multi-device execution.
        self.dispatch_gen = 0
        # MUTATION_EPOCH.read() pair captured BEFORE the last staleness
        # walk that found (or made) this view current. refresh()'s O(1)
        # fast path: while the process-wide pair hasn't moved, no
        # fragment generation can have moved either (every generation
        # bump pairs with an epoch bump — fragment.py:334-346), so the
        # per-slice walk is skipped entirely. None = never validated.
        self.validated_epoch: Optional[tuple] = None
        # (the view's WriteCounter, its n) read with that pair, before
        # the walk or snapshot it stamps: every write of the view up to
        # that n is in the image, so the next refresh visits only the
        # slices the counter's record names after it
        # (MeshManager._slices_written). None = no record to read.
        self.writes_seen: Optional[tuple] = None

    @property
    def padded_slices(self) -> int:
        return self.sharded.num_slices


def _view_arrays(sv: StagedView) -> tuple:
    """The device arrays by which the limb memo finds the entries that
    read `sv` (MeshManager._purge_memo): its dense pool's words and, if
    it has one, its sparse pool's values (every memo entry that read a
    sparse pool holds its values among its refs)."""
    if sv.sparse is None:
        return (sv.sharded.words,)
    return (sv.sharded.words, sv.sparse.values)


def _pool_refs(pools) -> tuple:
    """A launch's pools as flat arrays, a memo entry's refs: a dense
    leaf's words, or each array of a sparse leaf's (values, cards)."""
    return tuple(a for p in pools
                 for a in (p if isinstance(p, tuple) else (p,)))


def _pool_ids(pools) -> tuple:
    """The identities of _pool_refs(pools), for a memo or in-flight key."""
    return tuple(id(a) for a in _pool_refs(pools))


def _all_sparse(sv: StagedView) -> bool:
    """Whether every slice of `sv` staged sorted-array: its dense pool
    has no slot (capacity 0). Such a view is read where it lies by the
    src form, as a src leaf or as the ranked pool."""
    return sv.sparse is not None and sv.keys_host.shape[1] == 0


def _touched_containers(storage, entries) -> list:
    """[(container key, sorted uint16 values)] of every container a
    fragment's log entries touched, as `storage` holds it now: what a
    sorted-array pool takes whole (MeshManager._refresh_sparse). Call
    under the fragment's lock, with the log read under the same hold.
    A container no longer there was emptied, which the caller restages
    for before it reads this."""
    out = []
    for fk in sorted({pos >> 16 for _op, pos, _churn in entries}):
        c = storage._container_for(fk)
        if c is not None:
            out.append((fk, c.values().astype(np.uint16)))
    return out


def combine_limbs(limbs: np.ndarray, n: int, start: int = 0) -> np.ndarray:
    """Combine a (2, R) [lo16, hi] int32 limb array's columns
    [start, start+n) into int64 counts — the ONE host-side inverse of
    the device kernels' 16-bit limb split (compile_serve_row_counts and
    friends). Every consumer (single-host TopN paths, the SPMD
    descriptor plane) must use this so a limb-width change lands
    everywhere at once."""
    lo = limbs[0, start:start + n].astype(np.int64)
    hi = limbs[1, start:start + n].astype(np.int64)
    return (hi << 16) + lo


RANK_DEVICE_MIN_ROWS = 1 << 18
"""The padded row count from which TopN ranks the limbs where they lie
(MeshManager._rank_on_device) instead of reading all of them back and
ranking on the host (combine_limbs + rank_pairs). On a TPU v5e
(tools/rank_cut.py, n = 5, medians of 50) a device pass costs about
one launch and one small readback whatever the rows: phase 1 1.53,
1.54, 1.60, 1.55, 1.50 and 2.18 ms at 2^8, 2^12, 2^16, 2^17, 2^18 and
2^20 rows, phase 2 1.30-1.57. The host pass grows with the rows: 1.46,
1.45, 2.18, 2.78, 4.36 and 11.19 ms where the limbs are read back (a
memo miss), 0.03, 0.05, 0.34, 0.68, 1.36 and 6.18 where a memo hit's
host copy is kept. From 2^18 rows the device form is within 0.14 ms of
a memo hit's host form and a third of a miss's; at 2^17 and below a
hit, the commoner pass, is cheaper on the host. Star Trace's `stargazer`
(2^20) ranks on the device; `topn-1b`'s 256 rows and `language`'s 32
on the host."""

RANK_EXACT_SLICES = 1 << 11
"""Phase 1 on the device recombines the limbs in int32, exact while a
row's count, at most 2^20 a slice, stays below 2^31: under 2,048
slices. A view of more ranks on the host, in int64."""


def rank_k(n: int) -> int:
    """The device rank's output width for n rows: n rounded up to a
    power of two, at least 8, so a new n compiles at a doubling."""
    return max(8, 1 << (n - 1).bit_length())


def id_positions(all_rows, row_ids):
    """TopN phase 2's candidates in the ascending `all_rows`: (the ids
    sorted as uint64, their searchsorted positions, which are there)."""
    want = np.asarray(sorted(row_ids), dtype=np.uint64)
    i = np.searchsorted(all_rows, want)
    ok = (i < len(all_rows))
    ok &= all_rows[np.minimum(i, max(len(all_rows) - 1, 0))] == want
    return want, i, ok


def rank_pairs(all_rows, counts, n: int, row_ids, min_threshold: int,
               attr_predicate=None):
    """Host-side TopN semantics over exact per-row totals: candidate
    ids (phase 2), threshold, n, and the bounded attr-filter walk —
    shared by the single-host serving path (MeshManager.top_n) and the
    SPMD descriptor plane so the two cannot drift. See top_n's
    docstring for the deliberate threshold deviation."""
    if len(all_rows) == 0:
        return []
    if row_ids:
        want, i, ok = id_positions(all_rows, row_ids)
        pairs = [(int(r), int(counts[j]))
                 for r, j in zip(want[ok], i[ok])
                 if counts[j] >= max(min_threshold, 1)
                 and (attr_predicate is None or attr_predicate(int(r)))]
        pairs.sort(key=lambda p: (-p[1], p[0]))
        return pairs
    keep = np.nonzero(counts >= max(min_threshold, 1))[0]
    if n and attr_predicate is None and len(keep) > n:
        # Only the first n are asked for: the rows above the n-th
        # largest count, then as many of those AT it as fill n, by row
        # id (all_rows is ascending, so index order is id order) — the
        # sort below then sorts n rows, not every row with a count
        # (over 2^20 rows a lexsort of them all is tens of ms a TopN).
        c = counts[keep]
        kth = np.partition(c, len(c) - n)[len(c) - n]
        above = keep[c > kth]
        keep = np.concatenate([above, keep[c == kth][:n - len(above)]])
    order = np.lexsort((all_rows[keep], -counts[keep]))
    keep = keep[order]
    if attr_predicate is None:
        if n:
            keep = keep[:n]
        return [(int(all_rows[j]), int(counts[j])) for j in keep]
    # Attr filters (reference fragment.go:538-546): counts are already
    # exact, so walk the sorted rows applying the host-side attribute
    # predicate until n match — attr-store lookups stay bounded near n
    # instead of scanning every row.
    out = []
    for j in keep:
        if attr_predicate(int(all_rows[j])):
            out.append((int(all_rows[j]), int(counts[j])))
            if n and len(out) == n:
                break
    return out


def tanimoto_rank(all_rows, full, inter, src_count: int, n: int,
                  tanimoto: int, row_ids, attr_predicate=None
                  ) -> List[Tuple[int, int]]:
    """Host-side tanimoto band math over three exact count vectors
    (reference fragment.go:550-560,580-585: candidacy band on full
    counts, ceil similarity check on intersect counts) — shared by the
    single-host serving path and the SPMD descriptor plane so the two
    cannot drift."""
    if src_count == 0:
        return []
    min_tan = src_count * tanimoto / 100.0
    max_tan = src_count * 100.0 / tanimoto
    wanted = set(int(r) for r in row_ids) if row_ids else None
    pairs: List[Tuple[int, int]] = []
    for j in np.lexsort((all_rows, -inter)):
        if wanted is not None and int(all_rows[j]) not in wanted:
            continue  # exact ids recount phase (executor.go:273-310)
        cnt, count = int(full[j]), int(inter[j])
        if cnt <= min_tan or cnt >= max_tan or count == 0:
            continue
        t = -(-100 * count // (cnt + src_count - count))  # ceil
        if t <= tanimoto:
            continue
        if attr_predicate is not None and not attr_predicate(
                int(all_rows[j])):
            continue
        pairs.append((int(all_rows[j]), count))
        if n and len(pairs) == n:
            break
    return pairs


def _reraise_shared(what: str, err: BaseException):
    """Raise a FRESH exception wrapping a shared one: many threads can
    hold the same failed-group/in-flight error, and re-raising one
    instance concurrently races on its __traceback__."""
    raise RuntimeError(f"{what} failed: {err}") from err


# Who takes MeshManager._mu, one name a site: the lines of its _HoldBook.
MU_HOLDERS = ("refresh", "count_args", "lone_args", "sparse_args",
              "topn_args", "memo", "release", "stage", "invalidate",
              "evict", "infeasible", "measure", "batch", "memory",
              "formats")


class _HoldBook:
    """Wall time a re-entrant lock is held, by holder, in ns on the
    profile's clock. Only the thread that holds the lock touches it, so
    it needs no lock of its own; the depth makes a thread's outermost
    acquisition the one that records. The holders are fixed at
    construction, so a reader may copy `ns` while holds go on."""

    __slots__ = ("ns", "_depth", "_holder", "_t0")

    def __init__(self, holders):
        self.ns = dict.fromkeys(holders, 0)
        self._depth = 0
        self._holder = None
        self._t0 = 0

    def enter(self, holder: str) -> None:
        self._depth += 1
        if self._depth == 1:
            self._holder = holder
            self._t0 = profile.monotonic_ns()

    def exit(self) -> None:
        self._depth -= 1
        if self._depth == 0:
            self.ns[self._holder] += profile.monotonic_ns() - self._t0

    def held_us(self) -> dict:
        """µs held by each holder, and their `total`."""
        out = {k: v / 1e3 for k, v in dict(self.ns).items()}
        out["total"] = sum(out.values())
        return out


class _held:
    """`with _held(lock):` is `with lock:`, with the time the caller is
    blocked in acquire under its profile phase `mesh_lock_wait` (with
    `wait_phase` False, under whatever phase the caller is in: where that
    is not a residual one, a phase inside it would count twice). Given a
    `_HoldBook`, the time it then holds the lock goes to `holder`'s line
    (two clock reads a hold; never a profile phase: holds overlap the
    phases of what runs under them)."""

    __slots__ = ("_lock", "_holder", "_book", "_wait_phase")

    def __init__(self, lock, holder: str = "", book=None,
                 wait_phase: bool = True):
        self._lock = lock
        self._holder = holder
        self._book = book
        self._wait_phase = wait_phase

    def __enter__(self):
        if self._wait_phase:
            with profile.phase("mesh_lock_wait"):
                self._lock.acquire()
        else:
            self._lock.acquire()
        if self._book is not None:
            self._book.enter(self._holder)

    def __exit__(self, *exc):
        if self._book is not None:
            self._book.exit()
        self._lock.release()


class _CountRequest:
    """One pending count in the dynamic batch queue. coarse_t holds a
    per-leaf (starts, valid) device pair when the leaf is
    coarse-eligible (coarse_row_starts), else None for that leaf — the
    batch runner picks the coarse whole-row-gather program only when
    every leaf of every request in a group is eligible."""

    __slots__ = ("args", "coarse_t", "leaf_keys", "done", "result",
                 "error", "views")

    def __init__(self, sig, words_t, idx_t, hit_t, coarse_t, dev_mask):
        self.args = (sig, words_t, idx_t, hit_t, dev_mask)
        self.coarse_t = coarse_t
        # StagedViews this request resolved against — stamped with a
        # dispatch generation when the group launches (see
        # _launch_gate), so lone-path snapshots observe batch launches.
        self.views = ()
        # Logical (frame, view, row_id) per leaf, set by count() — the
        # shared-batch planner canonicalizes on THIS (stable across
        # restages/evictions, unlike array ids).
        self.leaf_keys = None
        self.done = threading.Event()
        self.result = None
        self.error = None

    def group_key(self):
        """Batchable together: same tree shape, same underlying pools
        (object identity — same staging generation), same mask."""
        sig, words_t, _idx, _hit, dev_mask = self.args
        return (sig, tuple(id(w) for w in words_t), id(dev_mask))


class _SparseRanked(NamedTuple):
    """A sparse view's ranked pool as _src_counts_args snapshots it under
    _mu: the pool, its overflow slots on the device and its card bound
    (mesh.compile_serve_row_counts_src_sparse's arguments)."""

    pool: SparseShardedIndex
    overflow: object
    kmax: int


class _Picked(NamedTuple):
    """What MeshManager._pick_count decided for one deduped group."""

    kind: str                       # general | coarse | uniform | shared
    width: int                      # columns the program computes
    program: Callable[[], Callable]  # get-or-compile, run by the launch
    args: tuple
    order: List["_CountRequest"]    # request of each leading column
    counters: Tuple[Tuple[str, int], ...]


class MeshManager:
    """Stages holder views onto the device mesh and serves queries.

    Thread-safe: staging/refresh runs under one lock; the compiled
    query functions operate on immutable jax arrays, so serving needs
    no lock once a StagedView snapshot is taken. All public query
    methods return None on any device-path failure so the caller can
    fall back to the host path.
    """

    def __init__(self, holder, mesh=None, config=None):
        self.holder = holder
        self._mesh = mesh
        # [mesh] knobs threaded from config.Config.mesh_config() (plain
        # dict so tests can hand-build one): hbm_budget_bytes (0 = auto,
        # negative = unlimited), hbm_headroom, quarantine_after,
        # quarantine_ttl. Env vars override per-knob (resolution order
        # in _resolve_budget / the quarantine fields below).
        self._config = dict(config or {})
        self._mu = threading.RLock()
        # Held wall time of _mu by holder (_hold; /debug/vars
        # mesh.mu_held_us, /metrics pilosa_mesh_lock_held_us_total).
        self._mu_book = _HoldBook(MU_HOLDERS)
        # Staged device images, LRU-ordered (move-to-end on access):
        # total HBM held by staged pools is bounded by _hbm_budget_bytes
        # and the least-recently-USED view is evicted to make room — the
        # device analog of the holder's periodic cache flush
        # (holder.go:326-358). An evicted view restages on next use.
        self._views: "OrderedDict[Tuple[str, str, str], StagedView]" = \
            OrderedDict()
        # Bumped under _mu on every structural change to the residency
        # picture (stage insert, any evict, invalidate, incremental
        # image swap): device_memory()'s lock-free snapshot rereads
        # until the counter holds still, so a scrape racing a stage
        # can't report per-device totals from a different generation
        # than its padded total.
        self._views_gen = 0
        # Resolved HBM budget cache (one memory_stats() probe) and the
        # poisoned-plan strike counter feeding CompiledPlanCache's
        # quarantine set. _quar_mu is its own tiny lock: strikes are
        # noted from the batch thread, fetch workers, and serving
        # threads, and must not wait behind a multi-second stage.
        self._budget_resolved: Optional[int] = None
        self._plan_failures: Dict[str, int] = {}
        self._quar_mu = threading.Lock()
        qa = self._config.get("quarantine_after") or 0
        self._quarantine_after = (int(qa) if qa
                                  else _num_env("PILOSA_TPU_QUARANTINE_AFTER",
                                                2))
        qt = self._config.get("quarantine_ttl") or 0.0
        self._quarantine_ttl = (float(qt) if qt
                                else _num_env("PILOSA_TPU_QUARANTINE_TTL_S",
                                              60.0, float))
        # Per-(view, num_slices) infeasibility verdicts for the routing
        # peek (stage_infeasible), validated against MUTATION_EPOCH —
        # the O(slices) container-count walk must not run per query.
        self._infeasible_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # Compiled count programs, keyed on what _pick_count decided:
        # (kind, sig, leaves, width, backend, uniform). See
        # _count_program.
        self._count_programs: Dict[tuple, object] = {}
        # Shared-read batch programs keyed on (sig, leaf_map, U): used
        # when ALREADY compiled; compiled in the background the first
        # time a composition is seen (policy below) so hot repeated
        # workloads upgrade to unique-leaf traffic without a compile
        # stall on the serving path.
        self._shared_fns: "OrderedDict[tuple, object]" = OrderedDict()
        self._shared_pending: set = set()
        # Guards ONLY the _shared_fns/_shared_seen/_shared_pending
        # structural ops (get+move_to_end, insert+trim) — held for dict
        # ops alone, never across a compile, so the dispatch fast path
        # can't stall behind an unrelated multi-second _compile_mu
        # build. Ordering: _compile_mu -> _shared_mu where both are
        # held; never the reverse.
        self._shared_mu = threading.Lock()
        # Composition sightings: a shared program only compiles once a
        # composition REPEATS (timing-dependent batch groupings must
        # not each mint a multi-second background compile).
        self._shared_seen: "OrderedDict[tuple, int]" = OrderedDict()
        self._rowcount_fns: Dict[int, object] = {}
        self._rowcount_src_fns: Dict[tuple, object] = {}
        self._tanimoto_fns: Dict[tuple, object] = {}
        # Sparse-pair programs keyed (op, kind, backend) and the
        # resident-sparse-view counter gating the _sparse_count probe:
        # while zero, count() skips the sparse resolution entirely (the
        # overwhelmingly common all-dense case pays one int check).
        # Recomputed on stage/invalidate; evictions may leave it
        # stale-high, which only costs a redundant probe.
        self._sparse_fns: Dict[tuple, object] = {}
        self._sparse_backend_cached: Optional[str] = None
        self._sparse_views = 0
        # Views pinned to the dense format because the workload asked
        # for a shape only the packed-word programs serve (n-ary fold,
        # TopN row-counts). Sticky until invalidate(): one mixed
        # workload settles into one layout instead of ping-ponging a
        # restage per query. Guarded by _mu.
        self._dense_pins: set = set()
        # Fused single-dispatch count programs (mesh.
        # compile_serve_count, host_meta), LRU-keyed on (tree shape, leaf
        # count, fragment widths, backend) — the compiled-plan cache
        # the lone-query fast path serves from.
        self._fused_plans = CompiledPlanCache()
        # Lone-query gate state: a count takes the fused fast path only
        # when it is the SOLE count in flight — a concurrent herd must
        # keep flowing through the batch loop, where coalescing (not
        # dispatch count) is what pays. PILOSA_TPU_LONE_FUSED=off kills
        # the fast path (bench uses it to measure the chained floor).
        import os as _os

        self.lone_fused = _os.environ.get(
            "PILOSA_TPU_LONE_FUSED", "on").lower() not in ("off", "0")
        self._lone_mu = threading.Lock()
        self._counts_inflight = 0
        # Scheduler cohort hint (sched.QueryScheduler.on_release via
        # executor.burst_hint): >1 means a released cohort is landing
        # together, so (a) the first member must NOT take the lone
        # fused path — it would strand the rest in a narrower batch —
        # and (b) the batch loop holds its drain window open even when
        # the previous drain was lone. Decremented as requests drain.
        self._burst_mu = threading.Lock()
        self._burst_hint = 0
        # compile_serve_apply_writes' two forms, by `donate`
        # (_apply_writes picks).
        self._apply_fns: dict = {}
        self._patch_fn = None  # compile_serve_patch_containers
        # compile_serve_sparse_write's two forms, by `donate`
        # (_refresh_sparse picks), and the sparse ranked src programs.
        self._sparse_write_fns: dict = {}
        self._rowcount_src_sparse_fns: Dict[tuple, object] = {}
        # TopN's rank and gather over limbs on the device, by
        # (program, padded rows, k) (_rank_on_device).
        self._rank_fns: Dict[tuple, object] = {}
        # What those programs read back, by limb array (_readbacks).
        self._ranked: Dict[int, tuple] = {}
        # EWMA (seconds) of measured incremental-apply cost — the other
        # side of refresh()'s cost gate (vs StagedView.last_stage_s) —
        # and the batch/pool shapes already compiled (novel shapes pay
        # a jit compile and are excluded from the EWMA).
        self._inc_ewma_s: Optional[float] = None
        self._apply_shapes: set = set()
        # SPMD descriptor-plane mode (set by SpmdServer): replace the
        # measured incremental-vs-restage gate with a deterministic
        # count-based policy so every rank picks the same path for the
        # same descriptor — per-rank timings must never steer a
        # decision that changes device-pool shapes (ADVICE r4).
        self.deterministic_gate = False
        # One long-lived worker measures device-completion costs (a
        # thread per refresh would churn on write-heavy paths, and
        # blocked threads would each pin a device image while the
        # device stalls). Bounded: a full queue drops the sample, never blocks
        # the serving path.
        self._measure_q: "queue.Queue" = queue.Queue(maxsize=4)
        self._measure_thread: Optional[threading.Thread] = None
        self._mask_cache: "OrderedDict[bytes, object]" = OrderedDict()
        # Replicated uniform-starts vectors, by value (_device_starts).
        self._starts_cache: "OrderedDict[tuple, object]" = OrderedDict()
        self._batch_q: "queue.Queue[_CountRequest]" = queue.Queue()
        # Dispatched-but-unfetched batches (see _fetch_loop); maxsize is
        # the readback pipeline depth — one slot per fetch worker plus
        # a small buffer so the batch loop keeps dispatching while all
        # workers sit inside a completion wait. The pool size is read
        # ONCE here and reused by _ensure_batch_thread, so the queue
        # bound and the worker count cannot disagree if the env changes
        # between construction and first query.
        self._fetch_pool_n = self._fetch_threads()
        self._fetch_q: "queue.Queue" = queue.Queue(
            maxsize=self._fetch_pool_n + 2)
        self._batch_thread: Optional[threading.Thread] = None
        # In-flight row-count executions shared by identical concurrent
        # callers: key -> [done_event, result, error]. Own tiny lock —
        # piggybacking on _mu would make waiter wakeup wait behind an
        # unrelated multi-second stage/refresh.
        self._inflight: Dict[tuple, list] = {}
        self._inflight_mu = threading.Lock()
        # Guards get-or-compile on the _*_fns caches above: the dict ops
        # are GIL-safe, but without the lock two concurrent FIRST
        # queries of one shape each pay the multi-second compile
        # (ADVICE r2). Call sites invoke _get_or_compile OUTSIDE _mu
        # (a multi-second compile must not stall staging), and nothing
        # under _compile_mu ever takes _mu — no ordering cycle.
        self._compile_mu = threading.Lock()
        # Device-launch gate (see _launch_gate): serializes program
        # launches on a >1-device CPU mesh — where XLA executes every
        # per-device program inline on the CALLING threads, so two
        # concurrent multi-device launches can cross-pair their
        # per-device programs into a collective-rendezvous spin — and
        # stamps each launched view's dispatch_gen. Real accelerators
        # queue launches on the device stream, so the lock is skipped
        # there (resolved lazily; None = not yet probed).
        self._dispatch_mu = threading.Lock()
        self._serialize_dispatch: Optional[bool] = None
        # Completed-result memo for TopN-family limb vectors — the
        # device analog of the reference's rank cache
        # (cache.go:126-275): a repeat TopN on an unchanged image re-enters
        # no collective. Keyed on the staged arrays' identities, so an
        # image swap (scatter or restage) naturally misses; entries hold
        # strong refs to those arrays (id() of a dead object can be
        # recycled — a ref-less key could false-hit a fresh array).
        # _purge_memo drops entries when a view's words swap, so stale
        # device images don't linger in HBM behind the memo. The epoch
        # closes the put-after-purge race: a query snapshots the epoch
        # under _mu alongside the arrays, and a store whose epoch is
        # stale (any purge ran since) is dropped — otherwise a result
        # landing after a concurrent refresh would insert an
        # unreachable entry pinning the replaced device image.
        self._topn_memo: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._memo_epoch = 0
        # Bumped at the start of each query resolution (under _mu);
        # views touched since then carry the stamp and are
        # eviction-exempt (see _evict_over_budget).
        self._use_epoch = 0
        # Serving-path stats, surfaced at /debug/vars (SURVEY.md §5
        # observability): counts of staged/incremental refreshes and
        # served device queries, plus cumulative timings and cache
        # hit/miss/size gauges. StatMap because these are bumped from
        # serving threads, the batch thread, the fetch pool, and the
        # cost-measure worker concurrently — bare `+=` on a dict drops
        # increments under that contention.
        self.stats = StatMap({
            "stage": 0, "incremental": 0, "evicted": 0,
            # Residency governor: reason-split eviction counters
            # (evicted stays the total for dashboard continuity), OOM
            # evict-and-retry attempts, the resolved byte budget, and
            # the degraded-mode fallbacks by reason (these feed
            # pilosa_device_fallback_total{reason} at /metrics).
            "evicted_budget": 0, "evicted_oom": 0, "oom_retries": 0,
            "hbm_budget_bytes": 0, "plan_quarantined": 0,
            "fallback_infeasible": 0, "fallback_oom": 0,
            "fallback_quarantined": 0, "fallback_compile": 0,
            "fallback_error": 0,
            "staged_bytes": 0, "count": 0, "topn": 0,
            "batched": 0, "deduped": 0, "inflight_shared": 0, "coarse": 0,
            "coarse_uniform": 0,
            "fallback": 0, "stage_us": 0, "query_us": 0,
            "h2d_bytes": 0, "h2d_dispatch_us": 0,
            "refresh_pick_incremental": 0, "refresh_pick_restage": 0,
            "refresh_probe_restage": 0, "inc_ewma_us": 0,
            # Slices the refreshes past the epoch fast path set out to
            # visit (refresh_walks counts those refreshes), and the
            # refreshes that took every slice because the view's write
            # record could not say which were written, by reason
            # (_slices_written).
            "refresh_walk_fragments": 0, "refresh_full_walks": 0,
            "refresh_full_walks_unrecorded": 0,
            "refresh_full_walks_structural": 0,
            "refresh_full_walks_overrun": 0,
            # Containers that writes created and _refresh_walk patched
            # into free slots of the staged pool; the creations it had
            # to restage for instead, by reason (/metrics:
            # pilosa_container_patch_refused_total{reason}); and the
            # fewest free slots of any slice of any dense staged view,
            # set at staging and after each patch: the distance to the
            # next no_slot.
            "container_patches": 0, "container_patch_refused_no_slot": 0,
            "container_patch_refused_new_row": 0,
            "container_patch_refused_format": 0, "free_slots_min": 0,
            # Refreshes that scattered writes, by what _apply_writes
            # picked (/metrics: pilosa_apply_writes_total{mode}), and
            # the in-place launches that failed and cost the view.
            "apply_in_place": 0, "apply_copied": 0,
            "apply_in_place_failed": 0,
            # Sorted-array (sparse) views served where they lie: src
            # leaves read from a sparse pool and src-form row counts
            # ranked over one (route.sparse_kept_share reads them
            # against the two ways a sparse view leaves its format:
            # sparse_demote, a restage dense, and fallback_sparse_format,
            # the host fold when that restage does not fit); and writes
            # into a sparse pool (_refresh_sparse): containers created
            # into a free slot (sparse_inserts) or rewritten in their
            # own (sparse_patches), the restages it refused, by reason
            # (/metrics pilosa_sparse_patch_refused_total), and the
            # fewest free slots of any slice of a sparse pool, as
            # free_slots_min is of the dense ones.
            "sparse_src_leaves": 0, "sparse_row_counts": 0,
            "sparse_demote": 0, "fallback_sparse_format": 0,
            "sparse_inserts": 0, "sparse_patches": 0,
            "sparse_patch_refused_no_slot": 0,
            "sparse_patch_refused_new_row": 0,
            "sparse_patch_refused_value_cap": 0,
            "sparse_free_slots_min": 0,
            "memo_hit": 0, "memo_store": 0, "memo_size": 0,
            # TopN ranking passes by where the ranking ran: over the
            # limbs on the device (_rank_on_device) or read back whole
            # and ranked on the host (rank_pairs, tanimoto_rank).
            "topn_rank_device": 0, "topn_rank_host": 0,
            "idx_cache_hit": 0, "idx_cache_miss": 0,
            "mask_cache_hit": 0, "mask_cache_miss": 0,
            "routed_host": 0, "shared_batch": 0, "fetch_threads": 0,
            # Device operations issued on the query path: +1 per leaf
            # metadata upload group, per mask/starts upload, per program
            # launch. A distinct cold-metadata 2-leaf query costs 3 on
            # the chained path; the fused lone path costs exactly 1
            # (bench lone_query_dispatch measures the delta).
            "device_dispatches": 0, "lone_fused": 0,
            # Program-compile telemetry: every entry-point compile
            # funnels through _timed_build (serve-side caches AND the
            # fused-plan LRU), so first-shape stalls are attributable
            # from /metrics without a profiler run.
            "compile_count": 0, "compile_us": 0,
            # Staging pipeline shape of the LAST stage: slices per
            # chunk, and how many chunked device_puts actually ran
            # (1 = single-put path, >1 = the pack/transfer pipeline).
            "h2d_chunk_slices": 0, "h2d_chunks": 0,
            # Drains whose window was held open by a scheduler cohort
            # hint (expect_burst) — how often the sched/ layer actually
            # steered coalescing.
            "sched_hinted": 0,
        })
        # Per-entry-point compile counters ({entry}_count/{entry}_us:
        # count, count_batch, coarse, row_counts, row_counts_src,
        # tanimoto, shared, fused) — the label-bearing face of the
        # compile_count/compile_us totals above.
        self.compile_stats = StatMap()

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = default_mesh()
        return self._mesh

    def _hold(self, holder: str, wait_phase: bool = True) -> _held:
        """`with self._hold("memo"):` takes _mu, its wait under
        mesh_lock_wait (see _held for `wait_phase`) and its hold in
        `holder`'s line of the book."""
        return _held(self._mu, holder, self._mu_book, wait_phase)

    def lock_held_us(self) -> dict:
        """µs _mu was held, by holder (MU_HOLDERS) and in `total`."""
        return self._mu_book.held_us()

    def _hbm_budget_bytes(self) -> int:
        """Resolved staged-pool HBM byte budget; <= 0 means unlimited
        (no eviction, no infeasibility gate). Resolution order:
          1. [mesh] hbm-budget-bytes (positive = that many bytes,
             negative = explicitly unlimited, 0 = fall through);
          2. PILOSA_TPU_HBM_BUDGET_BYTES env;
          3. PILOSA_TPU_HBM_BUDGET_MB env (the legacy knob);
          4. auto: the smallest bytes_limit the mesh's devices report
             (memory_stats()), once per device, minus the
             [mesh] hbm-headroom-fraction left for XLA scratch and
             compiled-program buffers;
          5. 8 GiB — half a v5e chip — when the backend reports no
             limit (CPU test meshes report none).
        Config and env are re-read on every call (both are cheap, and
        operators retune the env knob on a live process); only the
        auto-probed device limit is cached — tests reset it by
        clearing _budget_resolved."""
        import os

        b = None
        cfg = int(self._config.get("hbm_budget_bytes", 0) or 0)
        if cfg:
            b = cfg  # negative = unlimited, handled by <= 0 checks
        else:
            for env, shift in (("PILOSA_TPU_HBM_BUDGET_BYTES", 0),
                               ("PILOSA_TPU_HBM_BUDGET_MB", 20)):
                raw = os.environ.get(env, "")
                if raw:
                    try:
                        b = int(raw) << shift
                        break
                    except ValueError:
                        pass
        if b is None:
            b = self._budget_resolved
            if b is None:
                b = self._probe_budget()
                self._budget_resolved = b
        if self.stats["hbm_budget_bytes"] != max(0, b):
            self.stats["hbm_budget_bytes"] = max(0, b)
        return b

    def _probe_budget(self) -> int:
        """Budget for ALL staged views together, from what the mesh's
        devices report: every pool shards evenly over the slice axis,
        so the mesh holds its smallest device's limit once per device.
        (Asking only the first local device gave a four-chip mesh one
        chip's budget.)"""
        headroom = float(self._config.get("hbm_headroom", 0.15))
        try:
            import jax

            devs = list(np.asarray(self.mesh.devices).flat)
            # Only this process's devices answer; a multi-host mesh is
            # the same chips on every host.
            limits = [int((d.memory_stats() or {}).get("bytes_limit", 0))
                      for d in devs
                      if d.process_index == jax.process_index()]
            if limits and min(limits) > 0:
                return int(min(limits) * len(devs) * (1.0 - headroom))
        except Exception:  # noqa: BLE001 — backends without memory_stats
            pass
        return 8192 << 20

    @staticmethod
    def _sharded_bytes(sh) -> int:
        """Padded device bytes of ONE ShardedIndex snapshot. Takes the
        snapshot, not the StagedView: device_memory() must read
        sv.sharded exactly once per view (a concurrent incremental
        swap between a words read and a keys read would mix two
        generations of the image)."""
        return (int(np.prod(sh.words.shape)) * 4
                + int(np.prod(sh.keys.shape)) * 4)

    @staticmethod
    def _sparse_pool_device_bytes(sp) -> int:
        """Padded device bytes of one SparseShardedIndex snapshot:
        u16 values + i32 keys + i32 cards. dtype-aware (the values are
        2-byte), so the governor credits a sparse view's ACTUAL staged
        bytes — the whole point of the format."""
        if sp is None:
            return 0
        return (int(np.prod(sp.values.shape)) * 2
                + int(np.prod(sp.keys.shape)) * 4
                + int(np.prod(sp.cards.shape)) * 4)

    def _view_bytes(self, sv: StagedView) -> int:
        return (self._sharded_bytes(sv.sharded)
                + self._sparse_pool_device_bytes(sv.sparse))

    def _evict_over_budget(self):
        """Evict least-recently-used staged views until under the HBM
        budget. Views stamped with the CURRENT use-epoch (touched by
        the resolution in progress — possibly several frames of one
        query tree) and views PINNED by an in-flight query
        (StagedView.pins) are never evicted: a query spanning more
        frames than the budget fits runs over budget once rather than
        restage-thrashing forever, and a query mid-fold keeps its
        images. Call under _mu. Safe against in-flight queries even
        without the pin: they hold their own references to the
        immutable arrays; eviction only drops the manager's, and the
        memo entries reading those arrays are purged with them."""
        total = sum(self._view_bytes(v) for v in self._views.values())
        budget = self._hbm_budget_bytes()
        if budget > 0:
            for key in [k for k, v in self._views.items()
                        if v.last_used != self._use_epoch
                        and v.pins == 0]:
                if total <= budget:
                    break
                sv = self._views.pop(key)
                self._purge_memo(*_view_arrays(sv))
                self._views_gen += 1
                total -= self._view_bytes(sv)
                self.stats.inc("evicted")
                self.stats.inc("evicted_budget")
                costs.LEDGER.view_evicted(key)
        self.stats["staged_bytes"] = total

    def _evict_for_oom(self) -> int:
        """Emergency eviction after a device RESOURCE_EXHAUSTED: drop
        every staged view not pinned by an in-flight query — including
        current-use-epoch ones; the failing query's own views are
        pinned, and anything else is worth less than recovering the
        request. Returns how many views were dropped (0 means nothing
        left to free — the retry will likely fail too)."""
        # Inside the failed launch's stage_h2d or device_exec.
        with self._hold("evict", wait_phase=False):
            dropped = 0
            for key in [k for k, v in self._views.items()
                        if v.pins == 0]:
                sv = self._views.pop(key)
                self._purge_memo(*_view_arrays(sv))
                self._views_gen += 1
                self.stats.inc("evicted")
                self.stats.inc("evicted_oom")
                costs.LEDGER.view_evicted(key)
                dropped += 1
            self.stats["staged_bytes"] = sum(
                self._view_bytes(v) for v in self._views.values())
        return dropped

    def device_memory(self) -> dict:
        """HBM residency report for /metrics: padded bytes (what the
        pool actually allocates, INVALID_KEY slots included), live
        bytes (valid containers only — padding overhead is the gap),
        and a per-device breakdown from JAX shard placement.

        Lock-free but CONSISTENT: each attempt snapshots the views and
        each view's sharded image ONCE, then checks that _views_gen
        (bumped under _mu by every stage/evict/invalidate/incremental
        swap) held still across the walk — a moved counter retries, so
        a scrape racing a stage can't sum per-device shards from a
        different residency generation than its padded total. After a
        few dirty reads it falls back to computing under _mu (bounded
        staleness beats an unbounded retry loop when staging churns);
        shard reads are metadata-only (no device transfer) either way."""
        for _ in range(3):
            gen = self._views_gen
            snap = [(sv.sharded, sv.keys_host, sv.sparse,
                     sv.sparse_keys_host, sv.sparse_cards_host)
                    for sv in list(self._views.values())]
            if self._views_gen == gen:
                return self._device_memory_from(snap)
        with self._hold("memory"):
            snap = [(sv.sharded, sv.keys_host, sv.sparse,
                     sv.sparse_keys_host, sv.sparse_cards_host)
                    for sv in self._views.values()]
        return self._device_memory_from(snap)

    def _device_memory_from(self, snap) -> dict:
        padded = live = sparse_padded = 0
        per_device: Dict[str, int] = {}
        live_per_device: Dict[str, int] = {}
        n_dev = max(1, int(self.mesh.shape[SLICE_AXIS]))

        def add_live(keys_host, per_slot_live):
            """Aggregate + per-device live bytes from a host key table:
            valid slots * bytes-per-slot, split by the contiguous
            slice→device layout the SLICE_AXIS sharding uses.
            per_slot_live is a scalar (dense: every container bills a
            full word block) or a (S, C) array (sparse: each container
            bills its cardinality)."""
            nonlocal live
            valid = keys_host != INVALID_KEY
            slot = valid * np.asarray(per_slot_live, dtype=np.int64)
            live += int(slot.sum())
            devs = [str(d) for d in np.asarray(self.mesh.devices).flat]
            for di, chunk in enumerate(np.array_split(slot, n_dev)):
                dev = devs[di % len(devs)]
                live_per_device[dev] = (live_per_device.get(dev, 0)
                                        + int(chunk.sum()))

        for sh, keys_host, sp, sp_keys, sp_cards in snap:
            padded += self._sharded_bytes(sh)
            sp_bytes = self._sparse_pool_device_bytes(sp)
            padded += sp_bytes
            sparse_padded += sp_bytes
            if keys_host is not None and keys_host.size:
                add_live(keys_host, CONTAINER_WORDS * 4 + 4)
            if sp_keys is not None and sp_cards is not None:
                # Live sparse bytes: 2 B per stored value + the 8 B of
                # key+card bookkeeping per valid container.
                add_live(sp_keys, sp_cards.astype(np.int64) * 2 + 8)
            placed = False
            arrs = list(sh) + (list(sp) if sp is not None else [])
            try:
                for arr in arrs:
                    # From the sharding, never the shards' buffers: a
                    # pool the next write donated since the snapshot
                    # keeps its shape and placement and has no buffer.
                    n = (int(np.prod(arr.sharding.shard_shape(arr.shape)))
                         * arr.dtype.itemsize)
                    for d in arr.sharding.addressable_devices:
                        dev = str(d)
                        per_device[dev] = per_device.get(dev, 0) + n
                        placed = True
            except (AttributeError, TypeError):
                placed = False
            if not placed:
                devs = [str(d) for d in np.asarray(self.mesh.devices).flat]
                share = (self._sharded_bytes(sh) + sp_bytes) \
                    // max(1, len(devs))
                for dev in devs:
                    per_device[dev] = per_device.get(dev, 0) + share
        # Residency: live bytes per HBM byte actually held. 1.0 when
        # nothing is staged (an empty pool wastes nothing) — the gauge
        # answers "how much of what I'm paying for is data".
        ratio = (live / padded) if padded else 1.0
        residency_per_device = {
            dev: (live_per_device.get(dev, 0) / b if b else 1.0)
            for dev, b in per_device.items()}
        return {"views": len(snap), "padded_bytes": padded,
                "live_bytes": live, "sparse_bytes": sparse_padded,
                "residency_ratio": ratio, "per_device": per_device,
                "live_per_device": live_per_device,
                "residency_per_device": residency_per_device}

    # Bound on memoized per-view infeasibility verdicts: each is a few
    # machine words; the bound exists for never-repeating view names.
    _INFEASIBLE_CACHE_MAX = 256

    def stage_infeasible(self, index: str, leaves,
                         num_slices: int) -> bool:
        """Would ANY of these leaves' views overflow the HBM budget on
        its own? The executor's routing peek: an infeasible view is
        known-doomed before a single byte moves, so the query goes
        straight to the host fold instead of paying a snapshot + raise
        per request. Verdicts memoize per (index, frame, view,
        num_slices) against the global MUTATION_EPOCH — any write
        anywhere invalidates (capacity only grows via writes), keeping
        the steady-state cost of this gate one dict probe per view.
        Never forces a fragment parse (lazily-opened fragments are
        skipped — they under-estimate, and the stage-time check in
        _stage_once remains the authority)."""
        budget = self._hbm_budget_bytes()
        if budget <= 0:
            return False
        ep = MUTATION_EPOCH.read()
        for frame, view in dict.fromkeys((f, v)
                                         for f, v, _r, _q in leaves):
            ck = (index, frame, view, num_slices)
            # The executor's routing peek, inside its `plan` phase.
            with self._hold("infeasible", wait_phase=False):
                hit = self._infeasible_cache.get(ck)
                if hit is not None and hit[0] == ep:
                    self._infeasible_cache.move_to_end(ck)
                    if hit[1]:
                        return True
                    continue
            bad = self._view_would_exceed(index, frame, view,
                                          num_slices, budget)
            with self._hold("infeasible", wait_phase=False):
                self._infeasible_cache[ck] = (ep, bad)
                self._infeasible_cache.move_to_end(ck)
                while (len(self._infeasible_cache)
                       > self._INFEASIBLE_CACHE_MAX):
                    self._infeasible_cache.popitem(last=False)
            if bad:
                return True
        return False

    def _sparse_threshold(self) -> float:
        """Mean-container-fill density below which a slice stages as
        sorted-array containers. Resolution order matches the other
        mesh knobs: env override, [mesh] sparse-density-threshold,
        default 5% (a 5%-full container is ~3.3 K values = 6.5 KB as
        an array vs 8 KB dense — already winning, and comfortably
        under the 4096-value break-even). <= 0 disables the sparse
        format entirely (everything dense)."""
        cfg = self._config.get("sparse_density_threshold")
        base = float(cfg) if cfg is not None else 0.05
        return _num_env("PILOSA_TPU_SPARSE_DENSITY_THRESHOLD", base,
                        float)

    def _demote_to_dense(self, key, num_slices: int):
        """Pin `key` to packed words and restage it dense: the workload
        just asked for a shape only the dense programs serve (an n-ary
        count tree, a TopN row-counts collective) against a
        sparse/mixed view. Demoting keeps the query ON the device —
        the alternative is host-folding every such query forever. The
        pin is sticky until invalidate() so one mixed workload settles
        into one layout. If the dense image can't stage (budget/OOM —
        it IS bigger than the sparse one), the pin is dropped so
        leaf/pair queries keep their sparse serving, and the caller
        degrades to the host fold. Takes _mu (reentrant)."""
        with self._hold("stage"):
            self._dense_pins.add(key)
            self.stats.inc("sparse_demote")
            sv = self._views.pop(key, None)
            if sv is not None:
                self._purge_memo(*_view_arrays(sv))
                self._views_gen += 1
                self.stats["staged_bytes"] = max(
                    0, self.stats["staged_bytes"]
                    - self._view_bytes(sv))
            self._sparse_views = sum(1 for v in self._views.values()
                                     if v.sparse is not None)
            fresh = self.refresh(*key, num_slices)
            if fresh is None:
                self._dense_pins.discard(key)
            return fresh

    def _view_would_exceed(self, index: str, frame: str, view: str,
                           num_slices: int, budget: int) -> bool:
        """Mirror of _estimate_staged_bytes computed from the LIVE
        fragments (no snapshot): per-slice container stats feed the
        same format pick the stager would make (sans hysteresis —
        there is no previous image here, or the view would be
        resident), then the dense and sparse pool byte math."""
        if (index, frame, view) in self._views:
            return False  # resident: it fit when it staged
        n_dev = max(1, int(self.mesh.shape[SLICE_AXIS]))
        s_pad = -(-max(1, num_slices) // n_dev) * n_dev
        stats = np.zeros((num_slices, 3), dtype=np.int64)
        for s in range(num_slices):
            frag = self.holder.fragment(index, frame, view, s)
            if frag is None:
                continue
            with frag._mu:
                if frag._pending_load:
                    continue
                nc = len(frag.storage.keys)
                if not nc:
                    continue
                ns = [c.n for c in frag.storage.containers]
            stats[s] = (nc, sum(ns), max(ns))
        formats = pick_slice_formats(stats, self._sparse_threshold())
        return self._format_pool_bytes(stats, formats, num_slices,
                                       s_pad, n_dev) > budget

    @staticmethod
    def _format_pool_bytes(stats, formats, num_slices: int, s_pad: int,
                           n_dev: int) -> int:
        """Dense + sparse pool bytes from per-slice container stats and
        a format vector — the stats-domain twin of
        _estimate_staged_bytes (which works on bitmap snapshots)."""
        dense_n = [int(stats[s, 0]) for s in range(num_slices)
                   if not formats[s]]
        sparse_rows = [s for s in range(num_slices) if formats[s]]
        if not sparse_rows:
            cap = max(1, max(dense_n, default=1))
            cap = -(-cap // ROW_SPAN) * ROW_SPAN
            return s_pad * cap * (CONTAINER_WORDS * 4 + 4)
        cap = max(dense_n, default=0)
        cap = -(-cap // ROW_SPAN) * ROW_SPAN
        sc = sparse_capacity(max(int(stats[s, 0]) for s in sparse_rows))
        sk = max(1, max(int(stats[s, 2]) for s in sparse_rows))
        sk = -(-sk // _VALUE_ALIGN) * _VALUE_ALIGN
        return (s_pad * cap * (CONTAINER_WORDS * 4 + 4)
                + sparse_pool_bytes(num_slices, n_dev, sc, sk))

    # -- staging -------------------------------------------------------------

    def _snapshot_fragments(self, index: str, frame: str, view: str,
                            num_slices: int):
        """COW-clone each fragment's storage under its lock, with the
        generation captured atomically alongside. slice_gens entries are
        (fragment, generation) — the OBJECT is part of the staleness
        check, because a deleted-and-recreated index yields new Fragment
        objects whose generations are incomparable with the staged
        ones."""
        bitmaps, gens = [], []
        for s in range(num_slices):
            frag = self.holder.fragment(index, frame, view, s)
            if frag is None:
                bitmaps.append(None)
                gens.append(None)
                continue
            with frag._mu:
                frag.ensure_loaded()  # lazily-opened: parse before staging
                bitmaps.append(frag.storage.clone())
                gens.append((frag, frag.generation))
        return bitmaps, gens

    def _estimate_staged_bytes(self, bitmaps, formats=None) -> int:
        """Pre-H2D estimate of the device bytes the stage will allocate
        for these fragment snapshots — EXACT, because it mirrors the
        padding math in mesh.build_sharded_index /
        build_sparse_sharded_index: slices padded to a multiple of the
        mesh's slice-axis extent, capacities padded to ROW_SPAN (and
        value counts to _VALUE_ALIGN) multiples of the fullest slice.
        With a `formats` vector the estimate splits into the dense pool
        over dense slices plus the sparse pool over sparse ones. Lets
        the governor reject or make room for a stage before a single
        byte moves."""
        n_dev = max(1, int(self.mesh.shape[SLICE_AXIS]))
        s = len(bitmaps)
        s_pad = -(-max(1, s) // n_dev) * n_dev
        if formats is not None and formats.any():
            dense_b, sparse_b = split_bitmaps_by_format(bitmaps, formats)
            cap = max((len(b.keys) for b in dense_b if b is not None),
                      default=0)
            cap = -(-cap // ROW_SPAN) * ROW_SPAN
            sc, sk = sparse_pool_dims(sparse_b)
            return (s_pad * cap * (CONTAINER_WORDS * 4 + 4)
                    + sparse_pool_bytes(s, n_dev, sc, sk))
        cap = max(1, max((len(b.keys) for b in bitmaps if b is not None),
                         default=1))
        cap = -(-cap // ROW_SPAN) * ROW_SPAN
        return s_pad * cap * (CONTAINER_WORDS * 4 + 4)

    def _reserve(self, key, est: int, budget: int) -> None:
        """Make room for an incoming stage of `est` bytes: evict cold
        unpinned views (LRU, excluding `key` itself — its old image is
        being replaced anyway) until resident + est fits the budget.
        If pinned/current-epoch views block the way, proceed over
        budget rather than thrash: the overshoot is one stage's worth
        and self-corrects at the next _evict_over_budget. Call under
        _mu."""
        total = sum(self._view_bytes(v) for k, v in self._views.items()
                    if k != key)
        for k in [k for k, v in self._views.items()
                  if k != key and v.pins == 0
                  and v.last_used != self._use_epoch]:
            if total + est <= budget:
                break
            sv = self._views.pop(k)
            self._purge_memo(*_view_arrays(sv))
            self._views_gen += 1
            total -= self._view_bytes(sv)
            self.stats.inc("evicted")
            self.stats.inc("evicted_budget")
            costs.LEDGER.view_evicted(k)
        self.stats["staged_bytes"] = total

    def _stage(self, key, num_slices: int) -> StagedView:
        """Stage with the OOM recovery ladder: a RESOURCE_EXHAUSTED
        from the H2D path triggers an emergency eviction of every
        unpinned view and ONE retry; a second failure surfaces as
        DeviceResourceError(reason="oom") so callers degrade to the
        host-fold path instead of 500ing. Infeasibility (a single view
        bigger than the whole budget) is raised by _stage_once before
        any transfer and passes straight through."""
        try:
            return self._stage_once(key, num_slices)
        except DeviceResourceError:
            raise
        except Exception as e:  # noqa: BLE001 — classify then rethrow
            if not _is_resource_exhausted(e):
                raise
            self.stats.inc("oom_retries")
            self._evict_for_oom()
            try:
                return self._stage_once(key, num_slices)
            except Exception as e2:  # noqa: BLE001
                if _is_resource_exhausted(e2):
                    raise DeviceResourceError(
                        f"stage {key} out of device memory after "
                        f"eviction: {e2}", reason="oom") from e2
                raise

    def _note_placement(self, words) -> None:
        """Gauges of where the pool just staged lies: the size of the
        serving mesh, and the pool bytes on its emptiest and its
        fullest device (a device of the mesh that holds no shard
        counts 0). /debug/vars mesh.*, /metrics pilosa_mesh_*."""
        per_device = dict.fromkeys(self.mesh.devices.flat, 0)
        for shard in words.addressable_shards:
            per_device[shard.device] = (per_device.get(shard.device, 0)
                                        + int(shard.data.nbytes))
        self.stats.set("devices", len(per_device))
        self.stats.set("shard_bytes_min", min(per_device.values()))
        self.stats.set("shard_bytes_max", max(per_device.values()))

    def _stage_once(self, key, num_slices: int) -> StagedView:
        index, frame, view = key
        fault.point("mesh.stage", index=index, frame=frame, view=view,
                    slices=num_slices)
        t0 = time.monotonic()
        sp = span("stage", index=index, frame=frame, view=view,
                  slices=num_slices)
        # Union-interval semantics: build_sharded_index re-enters the
        # same phase inside; only this outermost bracket counts.
        ph = profile.phase("stage_h2d").start()
        old = self._views.get(key)
        if old is not None:
            self._purge_memo(*_view_arrays(old))
        inherit_inc_ewma = old.inc_ewma_s if old is not None else None
        bitmaps, gens = self._snapshot_fragments(index, frame, view,
                                                 num_slices)
        # Format pick BEFORE the budget check: a sparse-eligible view's
        # admission must be judged on the bytes it will actually stage.
        # The previous image's formats feed the hysteresis band so a
        # boundary slice keeps its layout across restages.
        prev_fmt = old.slice_formats if old is not None else None
        thr = (0.0 if key in self._dense_pins
               else self._sparse_threshold())
        formats = pick_slice_formats(slice_format_stats(bitmaps), thr,
                                     prev=prev_fmt)
        budget = self._hbm_budget_bytes()
        if budget > 0:
            est = self._estimate_staged_bytes(bitmaps, formats)
            if est > budget:
                # One view alone overflows the budget: no eviction can
                # help — route this query to the host-fold path.
                raise DeviceResourceError(
                    f"staged view {key} needs {est} bytes, over the "
                    f"{budget}-byte HBM budget", reason="hbm_infeasible")
            self._reserve(key, est, budget)
        stage_io: dict = {}
        sparse = sparse_keys = sparse_cards = None
        with jax_scope("pilosa:h2d_stage"):
            if formats.any():
                dense_b, sparse_b = split_bitmaps_by_format(bitmaps,
                                                            formats)
                rid = global_row_ids(bitmaps)
                n_dense = max((len(b.keys) for b in dense_b
                               if b is not None), default=0)
                # capacity=0 when every populated slice went sparse:
                # the dense pool stays a real (but empty) array, so
                # every sv.sharded consumer keeps working.
                sharded, row_ids, keys_host = build_sharded_index(
                    dense_b, self.mesh, with_host_keys=True,
                    stats_out=stage_io, row_ids=rid,
                    capacity=None if n_dense else 0)
                sparse, _, sparse_keys, sparse_cards = \
                    build_sparse_sharded_index(
                        sparse_b, self.mesh, row_ids=rid,
                        stats_out=stage_io)
            else:
                sharded, row_ids, keys_host = build_sharded_index(
                    bitmaps, self.mesh, with_host_keys=True,
                    stats_out=stage_io)
        self.stats.inc("h2d_bytes", stage_io.get("h2d_bytes", 0)
                       + stage_io.get("sparse_h2d_bytes", 0))
        self.stats.inc("h2d_dispatch_us", int(
            stage_io.get("h2d_dispatch_s", 0.0) * 1e6))
        self.stats.set("h2d_chunk_slices",
                       stage_io.get("h2d_chunk_slices", 0))
        self.stats.set("h2d_chunks", stage_io.get("h2d_chunks", 0))
        self._note_placement(sharded.words)
        if "h2d_fallback" in stage_io:
            # build_sharded_index could not place shards per device and
            # shipped the whole pool through one sharded device_put.
            self.stats.inc("h2d_whole_pool_fallback")
        sp.tag(h2d_bytes=stage_io.get("h2d_bytes", 0),
               h2d_dispatch_us=int(stage_io.get("h2d_dispatch_s", 0.0)
                                   * 1e6))
        sv = StagedView(
            sharded=sharded,
            row_ids=row_ids,
            keys_host=keys_host,
            slice_gens=gens,
            num_slices=num_slices,
            sparse=sparse,
            sparse_keys_host=sparse_keys,
            sparse_cards_host=sparse_cards,
            slice_formats=formats,
        )
        sv.last_used = self._use_epoch
        n_sparse = int(formats.sum())
        if n_sparse:
            self.stats.inc("stage_sparse_slices", n_sparse)
            sp.tag(sparse_slices=n_sparse)
        # Carry the same key's incremental estimate across the restage:
        # a gate-chosen restage must not amnesia the cost evidence (the
        # caller decays it first when the restage was gate-chosen).
        sv.inc_ewma_s = inherit_inc_ewma
        self._views[key] = sv
        self._views_gen += 1
        # Residency meter: bytes × dt accrues to the accounts that
        # touch this view from now until eviction (obs/costs.py).
        costs.LEDGER.view_staged(key, self._view_bytes(sv))
        self._evict_over_budget()
        self._sparse_views = sum(1 for v in self._views.values()
                                 if v.sparse is not None)
        self._note_free_slots()
        self.stats.inc("stage")
        dispatch_s = time.monotonic() - t0
        self.stats.inc("stage_us", int(dispatch_s * 1e6))
        # Cost-gate measurement must include DEVICE completion (the
        # async H2D), not just host dispatch — but blocking here would
        # serialize the cold-start pipeline (transfer overlapping the
        # first compile). The measurement worker records the true cost
        # with a small lag.
        sv.last_stage_s = None

        self._measure_async(
            sv.sharded.words, t0,
            lambda elapsed, ok=True, sv=sv:
                self._record_stage_sample(sv, elapsed, ok))
        sp.finish()
        ph.stop()
        return sv

    def _record_stage_sample(self, sv: StagedView, elapsed: float,
                             ok: bool) -> None:
        """Store a stage-cost measurement on the view. A FAILED fetch
        (ok=False) reports time-to-exception, which for a fast abort is
        near zero — recording it raw would read as "staging is free"
        and steer the gate into a restage storm against an unhealthy
        device. Clamp to no less than the view's incremental estimate
        so the gate degrades to the cheap path (incremental) while the
        probe stays armed; a COLD view (no incremental estimate yet)
        clamps to the fixed pessimistic floor instead — without it the
        raw near-zero sample would arm the probe after microseconds of
        incremental spend and fire a restage at the device that just
        failed."""
        if not ok:
            floor = sv.inc_ewma_s
            elapsed = max(elapsed,
                          floor if floor is not None
                          else self._FAILED_STAGE_FLOOR_S)
        sv.last_stage_s = elapsed

    def _measure_async(self, words, t0: float, on_done) -> None:
        """Enqueue a device-completion cost measurement: the worker
        blocks until `words` is ready and calls on_done(elapsed). A
        full queue drops the sample (bounded lag under a device stall;
        at most maxsize device images are pinned by pending items)."""
        if self._measure_thread is None:
            with self._hold("measure", wait_phase=False):  # in stage_h2d
                if self._measure_thread is None:
                    t = threading.Thread(target=self._measure_loop,
                                         name="mesh-cost-measure",
                                         daemon=True)
                    t.start()
                    self._measure_thread = t
        try:
            self._measure_q.put_nowait((words, t0, on_done))
        except queue.Full:
            # Never leave the sample unrecorded — a view whose
            # last_stage_s stays None would disable its cost gate AND
            # the probe forever. Dispatch-so-far is a lower bound; the
            # next measurement that fits the queue refines it.
            try:
                on_done(time.monotonic() - t0)
            except Exception:  # noqa: BLE001
                pass

    def _measure_loop(self):
        while True:
            words, t0, on_done = self._measure_q.get()
            try:
                ok = True
                try:
                    words.block_until_ready()
                    elapsed = time.monotonic() - t0
                except Exception:  # noqa: BLE001 — surfaces at query
                    # (Or the array is gone: the next write donated it
                    # to its scatter before this worker reached it.)
                    # A failed fetch still records a sample (ADVICE
                    # r4): dropping it would leave last_stage_s=None
                    # forever, disabling the view's cost gate AND the
                    # restage probe — exactly the failure mode the
                    # queue-full fallback below documents as forbidden.
                    # ok=False tells the callback the value is a
                    # time-to-exception, not a cost — a fast abort
                    # must not read as "this path is cheap".
                    elapsed = time.monotonic() - t0
                    ok = False
                finally:
                    del words
                try:
                    on_done(elapsed, ok)
                except Exception:  # noqa: BLE001 — never kill the worker
                    pass
            finally:
                # task_done bookkeeping lets callers wait for SETTLED
                # measurements (unfinished_tasks == 0), not merely an
                # empty queue with the worker still mid-item.
                self._measure_q.task_done()

    def refresh(self, index: str, frame: str, view: str,
                num_slices: int) -> Optional[StagedView]:
        """Return an up-to-date StagedView, restaging or incrementally
        scatter-updating as needed. None when the view can't be staged
        (missing index/frame) — or when the HBM governor refuses it
        (view bigger than the budget, or device OOM that survived the
        evict-and-retry ladder): callers already treat an unstaged view
        as "fold on the host", so degraded mode is the same None."""
        idx = self.holder.index(index)
        if idx is None or idx.frame(frame) is None:
            return None
        key = (index, frame, view)
        try:
            return self._refresh_locked(key, num_slices)
        except DeviceResourceError as e:
            self.stats.inc(f"fallback_{e.reason}")
            return None

    def _refresh_locked(self, key, num_slices: int) -> Optional[StagedView]:
        # Profile phases: the wait for _mu, then `view_refresh` for the
        # epoch check and, after a write, the walk and the patch
        # (residual: a restage below is stage_h2d's).
        with self._hold("refresh"), profile.residual("view_refresh"):
            # Epoch pair read UNDER _mu, before any staleness
            # inspection: a write that lands mid-walk bumps the pair
            # past `ep`, so stamping `ep` after the walk can never mark
            # that write validated. By `_MutationEpoch`'s ordering rule
            # (generation first, the epoch second) any bump included
            # in `ep` has its generation visible to the walk/snapshot
            # below. The read must sit INSIDE the lock: validators
            # serialize on _mu, so an in-lock read is always >= any
            # pair a finished validator stamped — read outside, a
            # reader that stalled before the lock could stamp its
            # stale pair OVER a newer one and silently disable the
            # O(1) fast path until the next write.
            ep = MUTATION_EPOCH.read()
            sv = self._views.get(key)
            if sv is not None:
                self._views.move_to_end(key)  # LRU: most recently used
                sv.last_used = self._use_epoch
                # Charge the residency interval so far, then join the
                # ambient account to the view's touch set.
                costs.LEDGER.view_touched(key)
                if (sv.validated_epoch == ep
                        and sv.num_slices == num_slices):
                    # O(1) fast path: nothing in the process has
                    # mutated since the pair was stamped, so no
                    # fragment generation can have moved — skip the
                    # per-slice walk (960 lock-and-compare iterations
                    # at headline scale, serialized under _mu; measured
                    # as the dominant host cost of a concurrent herd).
                    return sv
            if sv is None or sv.num_slices != num_slices:
                # The view's write count, read after `ep` and before the
                # snapshot: every write up to it is in the staged image.
                v = self.holder.view(*key)
                seen = None if v is None else (v.writes, v.writes.n)
                fresh = self._stage(key, num_slices)
                fresh.validated_epoch, fresh.writes_seen = ep, seen
                return fresh
            # What the first Count after a write pays, and no profiled
            # request of the benchmark crosses: counted.
            t0 = time.monotonic()
            try:
                return self._refresh_walk(key, sv, ep, num_slices)
            finally:
                self.stats.inc("refresh_walks")
                self.stats.inc("refresh_walk_us",
                               int((time.monotonic() - t0) * 1e6))

    def _slices_written(self, sv: StagedView, ep, writes,
                        num_slices: int) -> tuple:
        """(the writes_seen to store once `sv` is brought up to `ep`,
        the slices _refresh_walk visits). With the structural epoch
        where `sv` was validated no fragment appeared or went, so the
        slices `writes` recorded since sv.writes_seen are all that can
        have moved: those below num_slices, each once, none after a
        write to another view. Every slice when the record cannot say,
        counted by reason (refresh_full_walks_<reason>; /metrics
        pilosa_refresh_full_walks_total{reason}): `unrecorded`, no
        record read at the last validation or another counter now (the
        view was made again); `structural`, the structural epoch moved;
        `overrun`, the ring no longer reaches back. Call under _mu."""
        seen = sv.writes_seen
        if writes is None or seen is None or seen[0] is not writes:
            reason = "unrecorded"
        elif sv.validated_epoch[1] != ep[1]:
            reason = "structural"
        else:
            n, written = MUTATION_EPOCH.written_since(writes, seen[1])
            if written is not None:
                return (writes, n), sorted(
                    {s for s in written if s < num_slices})
            reason = "overrun"
        self.stats.inc("refresh_full_walks")
        self.stats.inc("refresh_full_walks_" + reason)
        return (None if writes is None else (writes, writes.n),
                range(num_slices))

    def _refresh_walk(self, key, sv: StagedView, ep,
                      num_slices: int) -> StagedView:
        """The slow side of _refresh_locked (call under _mu): the
        process has mutated since `sv` was validated, so walk the
        slices' generations and bring the staged image up to the epoch
        pair `ep` — nothing to do, an incremental scatter, or a
        restage. A container the writes created takes a free slot of
        its slice (ops.pool.assign_free_slots: the new key on the host
        and, through compile_serve_patch_containers, on the device)
        and its bits go through the same scatter as the writes to
        containers that were there, before this call returns: nothing
        is deferred or gathered over several reads. The log's entries
        are all the patch needs: the container did not exist at the
        staged generation, so its words are the zero words of the free
        slot with the log's surviving sets for its key. What a free
        slot cannot take restages as before: a container EMPTIED (its
        slot would have to be given back), a slice with no free slot,
        a row the view's row table lacks, a write to a dense slice of a
        mixed view, a fragment that appeared or went, a pruned log. A
        sparse view's writes go to _refresh_sparse.

        Which slices it visits: those the view's write record
        (core.fragment.WriteCounter) names since `sv.writes_seen`, so a
        write costs the slices it wrote, not the slices staged; none at
        all after a write to another view. Every slice, as before, when
        the record cannot answer (_slices_written: the structural epoch
        moved, the ring was overrun, or nothing was recorded)."""
        index, frame, view = key
        v = self.holder.view(index, frame, view)
        seen, slices = self._slices_written(
            sv, ep, v.writes if v is not None else None, num_slices)

        def restage(refused: Optional[str] = None):
            if refused is not None:
                self.stats.inc("container_patch_refused_" + refused)
            f = self._stage(key, num_slices)
            f.validated_epoch, f.writes_seen = ep, seen
            return f

        pending: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        # Sparse-format slices: every container the writes touched, as
        # the fragment holds it at `gen` (read under the fragment's lock
        # with the log), for _refresh_sparse.
        touched: Dict[int, list] = {}
        sparse_fmt = (sv.slice_formats if sv.sparse is not None
                      else None)
        created = False
        moved: Dict[int, tuple] = {}  # slice -> (fragment, gen) now
        self.stats.inc("refresh_walk_fragments", len(slices))
        for s in slices:
            frag = self.holder.fragment(index, frame, view, s)
            staged = sv.slice_gens[s]
            if frag is None:
                if staged is None:
                    continue
                return restage()  # fragment deleted
            if staged is None or staged[0] is not frag:
                # New fragment object (appeared, or the index was
                # deleted and recreated): generations from a
                # different object are meaningless — restage.
                return restage()
            staged_gen = staged[1]
            with frag._mu:
                gen = frag.generation
                if gen == staged_gen:
                    continue
                entries = frag.log_since(staged_gen)
                emptied = entries is None or any(
                    op and churn for op, _, churn in entries)
                if not emptied and sparse_fmt is not None \
                        and s < len(sparse_fmt) and sparse_fmt[s]:
                    touched[s] = _touched_containers(frag.storage, entries)
            if emptied:
                return restage()  # log pruned, or a container emptied
            created = created or any(e[2] for e in entries)
            pending[s] = fold_log_entries(entries)
            moved[s] = (frag, gen)

        if not pending:
            sv.validated_epoch, sv.writes_seen = ep, seen
            return sv
        if sv.sparse is not None:
            # A sorted-array pool takes its writes as whole containers
            # (_refresh_sparse). A mixed view with a write to one of
            # its dense slices, and the SPMD plane's deterministic
            # gate, restage as before: re-running the pick (with
            # hysteresis) is also what lets a densifying slice convert
            # back to packed words.
            if self.deterministic_gate or len(touched) != len(pending):
                self.stats.inc("refresh_pick_restage")
                return restage("format" if created else None)
            return self._refresh_sparse(key, sv, (ep, seen), moved,
                                        touched, restage)
        # Cost gate: incremental scatter vs full
        # restage, decided from MEASURED costs on THIS backend —
        # the view's own last stage time vs an EWMA of recent
        # incremental applies. Which side wins depends on the
        # backend and the pool (on a small CPU pool the restage is
        # the cheaper one), so a hard-wired incremental would be
        # the wrong policy somewhere.
        # First incremental runs unmeasured (no EWMA yet) and seeds
        # the estimate; decisions surface in /debug/vars.
        if self.deterministic_gate:
            # SPMD mode (ADVICE r4): every rank executes the same
            # descriptor stream, but measured timings are per-rank —
            # a measured gate could pick restage on one rank and
            # incremental on another, and if a restage shrinks
            # capacity the shapes diverge and the fingerprint gate
            # host-falls-back every collective for this view
            # forever. Decide from replicated state only: restage
            # every fixed number of incremental applies (bounds
            # capacity creep the scatters can't reclaim), otherwise
            # incremental. Same stream -> same counter -> same pick
            # on every rank.
            if sv.inc_count >= self._DET_RESTAGE_EVERY:
                self.stats.inc("refresh_pick_restage")
                return restage()
        else:
            # Per-VIEW incremental estimate (ADVICE r4): comparing a
            # per-view stage time against a manager-global EWMA let
            # cheap scatters measured on a small view drive repeated
            # full restages of a large one — both sides of the gate
            # must cost the same pool.
            inc_est = sv.inc_ewma_s
            # Periodic restage PROBE — the symmetric re-exploration:
            # a stale stage-cost sample (e.g. a slow COLD first
            # stage) would otherwise freeze the gate on incremental
            # forever, since restaging is the only event that
            # re-measures stage cost. Probing when cumulative
            # incremental spend reaches 20x the stage estimate
            # bounds probe overhead at ~5% while re-calibrating
            # quickly when restage is genuinely cheap.
            probe = (sv.last_stage_s is not None
                     and sv.inc_spend_s > 20.0 * sv.last_stage_s)
            if probe or (inc_est is not None
                         and sv.last_stage_s is not None
                         and sv.last_stage_s < inc_est):
                self.stats.inc("refresh_pick_restage")
                if probe:
                    self.stats.inc("refresh_probe_restage")
                elif inc_est is not None:
                    # Decay the incremental estimate on a GATE-chosen
                    # restage: one anomalous slow scatter sample must
                    # not freeze the gate on restage forever — the
                    # decayed EWMA (inherited by the fresh view in
                    # _stage) eventually re-admits an incremental,
                    # which re-measures reality. (A PROBE carries no
                    # evidence against incremental, so it must not
                    # bias the estimate.)
                    sv.inc_ewma_s = inc_est * 0.9
                return restage()
        t_inc = time.monotonic()
        per_slice, patches = {}, {}
        try:
            for s, (pos, val) in pending.items():
                keys_row, slots_row = sv.keys_host[s], sv.slots_host[s]
                patch = assign_free_slots(keys_row, slots_row, sv.row_ids,
                                          pos, val)
                if patch is not None:
                    keys_row, slots_row = patch[:2]
                    patches[s] = patch
                per_slice[s] = plan_slice_mutations(
                    keys_row, sv.row_ids, pos, val, slots_row)
        except PatchRefused as e:
            return restage(e.reason)
        batches = pack_mutation_batches(
            per_slice, sv.padded_slices, sv.keys_host.shape[1])
        patched = self._patch_containers(sv, patches) if patches else None
        sp = span("incremental", index=index, frame=frame, view=view)
        fresh_compile = self._apply_writes(key, sv, batches, patched)
        sp.finish()
        for s, g in moved.items():
            sv.slice_gens[s] = g
        sv.validated_epoch, sv.writes_seen = ep, seen
        sv.inc_count += 1
        self.stats.inc("incremental")
        self.stats.inc("refresh_pick_incremental")
        if not fresh_compile:
            # Like staging, measure to DEVICE completion on the
            # measurement worker — host dispatch alone is a
            # near-constant floor that says nothing about the
            # scatter's real cost.
            def on_inc(dt, ok=True, sv=sv):
                if not ok:
                    # A failed scatter's time-to-exception says
                    # nothing about incremental cost — feeding it
                    # to the EWMA would make incrementals look
                    # artificially cheap. Skip the sample; the
                    # stage side keeps the gate decidable.
                    return
                with self._hold("measure"):
                    sv.inc_ewma_s = (
                        dt if sv.inc_ewma_s is None
                        else 0.5 * (dt + sv.inc_ewma_s))
                    # Manager-global EWMA survives only as an
                    # observability gauge (/debug/vars) — the gate
                    # reads the per-view estimate.
                    self._inc_ewma_s = (
                        dt if self._inc_ewma_s is None
                        else 0.5 * (dt + self._inc_ewma_s))
                    self.stats["inc_ewma_us"] = \
                        int(self._inc_ewma_s * 1e6)
                    sv.inc_spend_s += dt

            self._measure_async(sv.sharded.words, t_inc, on_inc)
        return sv

    def _apply_writes(self, key, sv: StagedView, batches,
                      patched) -> bool:
        """Scatter pack_mutation_batches' `batches` into sv's staged
        words and swap the result in (call under _mu; the keys, patched
        or not, stay as they are). THE place that picks the form of
        compile_serve_apply_writes, from what it observes: with no pin
        on the view the words are donated and the scatter runs in the
        pool's own buffer; with a pin the same program starts from a
        copy and the pinned reader's pool stays whole.

        Why the pin count is the right witness. Donation deletes the
        array for every holder that has not launched on it yet, and a
        reader holds a staged `words` unlaunched only between its plan
        and its launch. Every such reader (_stage_leaves,
        _stage_leaves_host, _sparse_count, _row_counts_args,
        _src_counts_args) takes its pin under _mu in the same critical
        section that snapshots the words, and gives it back only after
        its result is fetched; this refresh runs under _mu, before the
        reader that triggered it snapshots or pins anything. So pins ==
        0, read here, says no reader holds the old words, and pins > 0
        that one may. What else names the array takes no pin and cannot
        launch on it: the limb memo's refs are purged just below, the
        cost measurement only waits on it (a deleted array is a skipped
        sample), the byte accounting reads shapes and shardings.

        A failed donated launch may have consumed the pool, so the view
        is dropped: the host's fragments are the truth, this read folds
        there and the next one restages, writes included. A failed
        undonated launch leaves the old pool in place, as before.
        Returns whether this launch compiled (the caller keeps such a
        sample out of the cost gate's EWMA)."""
        in_place = sv.pins == 0
        fn = self._apply_fns.get(in_place)
        if fn is None:
            fn = self._apply_fns[in_place] = compile_serve_apply_writes(
                self.mesh, donate=in_place)
        words = sv.sharded.words
        # The jitted apply recompiles on any NEW batch/pool shape
        # (mutation_batch_width doubles, a different capacity) and
        # once for each form — a sample carrying a one-off XLA
        # compile must not feed the EWMA or the gate would flip to
        # restage on costs the steady state never pays.
        # Shape-novelty mirrors exactly what jit keys compilation on.
        shapes = (in_place, tuple(words.shape),
                  tuple(tuple(np.shape(b)) for b in batches), patched)
        fresh_compile = shapes not in self._apply_shapes
        self._apply_shapes.add(shapes)
        self._purge_memo(words)
        try:
            with jax_scope("pilosa:apply_writes"):
                words = fn(words, *batches)
        except Exception:
            if in_place:
                self._drop_consumed(key)
            raise
        sv.sharded = sv.sharded._replace(words=words)
        self._views_gen += 1
        self.stats.inc("apply_in_place" if in_place else "apply_copied")
        return fresh_compile

    def _drop_consumed(self, key) -> None:
        """Drop a view whose donated write launch failed: the launch may
        have consumed its pool. The host's fragments are the truth; the
        read that triggered the write folds there and the next one
        restages, writes included. Call under _mu."""
        del self._views[key]
        self._views_gen += 1
        self._sparse_views = sum(1 for v in self._views.values()
                                 if v.sparse is not None)
        self.stats["staged_bytes"] = sum(
            self._view_bytes(v) for v in self._views.values())
        self.stats.inc("apply_in_place_failed")
        costs.LEDGER.view_evicted(key)
        _log.warning("in-place write of %s failed; the staged view is "
                     "dropped and restages at the next read", key,
                     exc_info=True)

    def _patch_containers(self, sv: StagedView, patches: dict) -> tuple:
        """Write the keys of created containers into the free slots
        _refresh_walk assigned them ({slice: assign_free_slots'
        result}), on the device and on the host, and drop what was
        resolved against the old keys: the (idx, hit) of the rows that
        gained a container. Call under _mu; the containers' bits follow
        in the caller's scatter. Returns the shape the program was
        launched at (what jit keys its compilation on)."""
        if self._patch_fn is None:
            self._patch_fn = compile_serve_patch_containers(self.mesh)
        slot, new_key = pack_container_patches(
            {s: p[2:] for s, p in patches.items()}, sv.padded_slices,
            sv.keys_host.shape[1])
        with jax_scope("pilosa:patch_containers"):
            keys = self._patch_fn(sv.sharded.keys, slot, new_key)
        sv.sharded = sv.sharded._replace(keys=keys)
        n = 0
        for s, (keys_row, slots_row, new_keys, _) in patches.items():
            sv.keys_host[s], sv.slots_host[s] = keys_row, slots_row
            sv.free_slots[s] -= len(new_keys)
            n += len(new_keys)
            for dense_id in set((new_keys // ROW_SPAN).tolist()):
                sv.idx_cache.pop(dense_id, None)
                sv.host_idx_cache.pop(dense_id, None)
        self.stats.inc("container_patches", n)
        self._note_free_slots()
        return slot.shape

    def _refresh_sparse(self, key, sv: StagedView, stamp, moved,
                        touched: Dict[int, list], restage) -> StagedView:
        """_refresh_walk's branch for a view whose every written slice
        is sorted-array (call under _mu): each container the writes
        touched goes to the device whole, its sorted values as the
        fragment holds them, into its own slot, or, created, into a
        free slot of its slice (ops.pool.plan_sparse_slice); one launch
        of compile_serve_sparse_write, donated when no reader is pinned
        to the view (the rule and its argument: _apply_writes). The
        host's sorted keys, slots, cards by slot, free slots and card
        bound move with it, so the single-leaf _sparse_count and every
        (idx, hit) resolved after it are exact. What it cannot take
        restages, counted by reason: a row the view lacks (new_row), a
        slice with no free slot (no_slot), a container past the pool's
        K values (value_cap)."""
        sp = sv.sparse
        plans, rows, made = {}, {}, 0
        try:
            for s, containers in touched.items():
                keys_row, slots_row, plan, created = plan_sparse_slice(
                    sv.sparse_keys_host[s], sv.sparse_slots_host[s],
                    sv.row_ids, containers, sp.value_cap)
                plans[s], rows[s] = plan, (keys_row, slots_row, created)
                made += created
        except PatchRefused as e:
            self.stats.inc("sparse_patch_refused_" + e.reason)
            self.stats.inc("refresh_pick_restage")
            return restage()
        slot, pkey, card, vals, n = pack_sparse_writes(
            plans, sv.padded_slices, sp.capacity, sp.value_cap)
        in_place = sv.pins == 0
        fn = self._sparse_write_fns.get(in_place)
        if fn is None:
            fn = self._sparse_write_fns[in_place] = \
                compile_serve_sparse_write(self.mesh, donate=in_place)
        self._purge_memo(*_view_arrays(sv))
        try:
            with jax_scope("pilosa:sparse_write"):
                keys, values, cards = fn(sp.keys, sp.values, sp.cards,
                                         slot, pkey, card, vals, n)
        except Exception:
            if in_place:
                self._drop_consumed(key)
            raise
        sv.sparse = sp._replace(keys=keys, values=values, cards=cards)
        self._views_gen += 1
        for s, (keys_row, slots_row, created) in rows.items():
            sl, k, c, _v = plans[s]
            sv.sparse_keys_host[s], sv.sparse_slots_host[s] = \
                keys_row, slots_row
            if created and sv.sparse_k64 is not None:
                cap = keys_row.shape[0]
                sv.sparse_k64[s * cap:(s + 1) * cap] = \
                    keys_row.astype(np.int64) + (np.int64(s) << 33)
            sv.sparse_cards_host[s, sl] = c
            sv.sparse_free_slots[s] -= created
            if sv.sparse_overflow is not None:
                over = np.flatnonzero(sv.sparse_cards_host[s] > 1)
                if len(over) > sv.sparse_overflow.shape[1]:
                    sv.sparse_overflow = None  # wider: made again
                else:
                    sv.sparse_overflow[s] = sp.capacity
                    sv.sparse_overflow[s, :len(over)] = over
            sv.sparse_kmax = max(sv.sparse_kmax, int(c.max()))
            if created:
                for dense_id in set((k // ROW_SPAN).tolist()):
                    sv.sparse_idx_cache.pop(dense_id, None)
                    sv.sparse_dev_idx_cache.pop(dense_id, None)
        sv.sparse_overflow_dev = None
        rewritten = sum(len(p[0]) for p in plans.values())
        self.stats.inc("sparse_inserts", made)
        self.stats.inc("sparse_patches", rewritten - made)
        self.stats.inc("apply_in_place" if in_place else "apply_copied")
        self.stats.inc("incremental")
        for s, g in moved.items():
            sv.slice_gens[s] = g
        sv.validated_epoch, sv.writes_seen = stamp
        sv.inc_count += 1
        self._note_free_slots()
        return sv

    def _note_free_slots(self) -> None:
        """Gauges free_slots_min, the fewest free slots of any slice of
        any dense staged view, and sparse_free_slots_min, the same of
        the sorted-array pools (each 0 while no such view is staged):
        the distance to a restage for want of a slot. Two gauges, as a
        dense view whose every (row, block) container exists (Star
        Trace's `language`) has none free and needs none. Call under
        _mu."""
        views = [v for v in self._views.values() if v.num_slices]
        self.stats.set("free_slots_min", min(
            (int(v.free_slots[:v.num_slices].min())
             for v in views if v.sparse is None), default=0))
        self.stats.set("sparse_free_slots_min", min(
            (int(v.sparse_free_slots[:v.num_slices].min())
             for v in views if v.sparse is not None), default=0))

    def invalidate(self, index: Optional[str] = None):
        """Drop staged views (all, or one index's)."""
        with self._hold("invalidate"):
            if index is None:
                for key in self._views:
                    costs.LEDGER.view_evicted(key)
                self._views.clear()
                self._views_gen += 1
                self._sparse_views = 0
                self._dense_pins.clear()
                self.stats["staged_bytes"] = 0
                self._topn_memo.clear()
                # The epoch must advance here too: an in-flight query's
                # _memo_put would otherwise pass the staleness check and
                # re-insert an entry pinning a just-dropped device image.
                self._memo_epoch += 1
                self.stats["memo_size"] = 0
            else:
                for key in [k for k in self._views if k[0] == index]:
                    self._purge_memo(*_view_arrays(self._views[key]))
                    del self._views[key]
                    self._views_gen += 1
                    costs.LEDGER.view_evicted(key)
                self._sparse_views = sum(
                    1 for v in self._views.values()
                    if v.sparse is not None)
                self._dense_pins = {k for k in self._dense_pins
                                    if k[0] != index}
                self.stats["staged_bytes"] = sum(
                    self._view_bytes(v) for v in self._views.values())

    # -- completed-result memo (device rank-cache analog) ----------------------

    # Pessimistic stage-cost floor recorded when a COLD view's stage
    # measurement fails (no incremental estimate to clamp to yet):
    # "staging looks very expensive" is the safe lie — the gate stays
    # on incremental and the probe can't fire until real spend
    # justifies re-trying the device that just failed.
    _FAILED_STAGE_FLOOR_S = 60.0

    # Deterministic-gate restage period: in SPMD mode a view restages
    # after this many incremental applies (bounds capacity creep from
    # rows/containers the scatters can't add), otherwise scatters. The
    # value only needs to be identical across ranks; 256 keeps restage
    # amortized to well under 1% of refreshes on write-heavy streams.
    _DET_RESTAGE_EVERY = 256

    # Bound on memoized TopN limb vectors: each is a (2, R_padded) int32
    # device array (~32 KB at 4096 rows) plus refs to live staged
    # arrays, so the memo itself is cheap; the bound exists so entries
    # for masks/srcs that never repeat don't accumulate.
    _TOPN_MEMO_MAX = 128

    def _memo_get(self, key: tuple):
        """Finished limb array for `key`, or None. Takes _mu (reentrant —
        callers already under it just recurse)."""
        with self._hold("memo"):
            hit = self._topn_memo.get(key)
            if hit is None:
                return None
            self._topn_memo.move_to_end(key)
            self.stats.inc("memo_hit")
            return hit[0]

    def _memo_put(self, key: tuple, limbs, refs: tuple, epoch: int):
        """Memoize a finished limb array. `refs` must hold every staged
        device array whose identity appears in `key` — they pin the ids
        (no recycling) and let _purge_memo find entries by image.
        `epoch` is the _memo_epoch snapshotted WITH the arrays: a store
        from before any intervening purge is dropped rather than
        inserted dead (see the __init__ comment).

        A note on failed executions: `limbs` may be an async device
        array whose execution later fails — the failure then surfaces
        on every fetch, memo hits included, and callers fall back to
        the host path per query. That's deliberate: the program runs
        over immutable staged arrays, so re-running it deterministically
        fails too; memoizing the failure loses nothing."""
        with self._hold("memo"):
            if epoch != self._memo_epoch:
                return
            if key in self._topn_memo:
                self._topn_memo.move_to_end(key)
                return
            if len(self._topn_memo) >= self._TOPN_MEMO_MAX:
                self._topn_memo.popitem(last=False)
            self._topn_memo[key] = (limbs, refs)
            self.stats.inc("memo_store")
            self.stats["memo_size"] = len(self._topn_memo)

    def _purge_memo(self, *images):
        """Drop every memo entry that read any of `images` (device
        arrays about to be replaced: a dense pool's words, a sparse
        pool's values; _view_arrays). Call under _mu."""
        self._memo_epoch += 1
        dead = [k for k, (_, refs) in self._topn_memo.items()
                if any(r is w for r in refs for w in images)]
        for k in dead:
            del self._topn_memo[k]
        if dead:
            self.stats["memo_size"] = len(self._topn_memo)

    # -- serving -------------------------------------------------------------

    def _mask_for(self, sv: StagedView, slices: Sequence[int]):
        mask = np.zeros(sv.padded_slices, dtype=np.int32)
        idx = np.asarray(slices, dtype=np.int64)
        if idx.size:
            if int(idx.max()) >= sv.num_slices:
                return None  # staged image doesn't cover the request
            mask[idx] = 1
        return mask

    def _release_pins(self, pins) -> None:
        """Drop the eviction pins a query took at plan time. Each entry
        is a StagedView whose pins count was incremented under _mu;
        decrement under the same lock and clear the list so a double
        release is a no-op.

        Release is also the governor's reconvergence point: a batch
        whose members together staged more than the budget runs over it
        (every view shares one use-epoch, so _evict_over_budget spares
        them all — deliberately, to finish the batch without
        restage-thrashing mid-flight). Without a hook here the
        overshoot would be PERMANENT once the working set is fully
        resident, since eviction otherwise only runs at stage time and
        resident views never stage again. Evicting on release pulls
        residency back under the budget as soon as the batch is done,
        at the cost of honest LRU thrash when the steady working set
        exceeds the budget."""
        if not pins:
            return
        with self._hold("release"):
            for sv in pins:
                if sv.pins > 0:
                    sv.pins -= 1
            pins.clear()
            if (self._hbm_budget_bytes() > 0
                    and self.stats["staged_bytes"]
                    > self._hbm_budget_bytes()):
                self._evict_over_budget()

    def _count_args(self, index: str, shape, leaves, slices: Sequence[int],
                    num_slices: int, pins=None):
        """Resolve a count request to device arrays:
        (sig, words_t, idx_t, hit_t, dev_mask) or None. All staging
        state (refresh, words snapshot, idx/mask caches) is read and
        mutated under _mu: a concurrent refresh() swaps sv.sharded in
        place, and a query that read one leaf's words before the swap
        and another after would mix two generations of the same view.
        Only compiled calls run unlocked. `pins` (a list) collects a
        pin per staged view used, held until the caller's
        _release_pins: the unlocked execution window must not have its
        images evicted-and-restaged under memory pressure mid-fold,
        and a pinned reader's pool is never donated to a write's
        scatter (_apply_writes copies instead), so the generation it
        snapshotted stays whole until it has launched and fetched. A
        caller without `pins` must let no refresh run between this
        call and its launch (the SPMD descriptor plane: one descriptor
        at a time, writes among them)."""
        with self._hold("count_args"):
            self._use_epoch += 1
            out = self._stage_leaves(index, leaves, num_slices, pins=pins)
            if out is None:
                return None
            words_t, idx_t, hit_t, coarse_t, first = out
            mask = self._mask_for(first, slices)
            if mask is None:
                self.stats.inc("fallback")
                return None
            dev_mask = self._device_mask(mask)

        sig = json.dumps(_tree_signature(shape))
        return (sig, words_t, idx_t, hit_t, coarse_t, dev_mask)

    def _stage_leaves(self, index: str, leaves, num_slices: int,
                      pins=None, sparse_ok: bool = False):
        """Stage every leaf's (frame, view) and resolve its row into
        cached device gather arrays. Call under _mu (staging snapshot
        consistency — see _count_args). Returns
        (words_t, idx_t, hit_t, coarse_t, first_staged_view) or None;
        an absent row maps to the past-the-end dense sentinel, which
        the resolver turns into hit=0 everywhere. coarse_t[i] is the
        leaf's (starts, valid) device pair when coarse-eligible, else
        None. Shared by the Count path and the TopN src path so
        absent-row/staging semantics can't diverge. When `pins` is a
        list, each unique view gets one eviction pin (released by the
        caller via _release_pins). With `sparse_ok` (a caller whose
        program expands sparse leaves: the src form), a view staged all
        sorted-array is a SPARSE leaf, where it lies: its pool entry is
        the (values, cards) pair and its (idx, hit) are resolved
        against the sparse keys (_sparse_leaf_arrays); every other
        sparse or mixed view is demoted as for the Count trees."""
        staged: Dict[Tuple[str, str], tuple] = {}
        words_t, idx_t, hit_t, coarse_t = [], [], [], []
        for frame, view, row_id, _req in leaves:
            vkey = (frame, view)
            if vkey not in staged:
                sv = self.refresh(index, frame, view, num_slices)
                if sv is None:
                    self.stats.inc("fallback")
                    return None
                if sparse_ok and _all_sparse(sv):
                    pass
                elif sv.sparse is not None:
                    # This collective reads the dense pool only; a
                    # sparse/mixed view would silently undercount its
                    # sorted-array slices. Pin it dense and restage so
                    # the query stays on the device.
                    sv = self._demote_to_dense((index, frame, view),
                                               num_slices)
                    if sv is None:
                        self.stats.inc("fallback_sparse_format")
                        self.stats.inc("fallback")
                        return None
                if pins is not None:
                    sv.pins += 1
                    pins.append(sv)
                staged[vkey] = (sv, (sv.sparse.values, sv.sparse.cards)
                                if sparse_ok and _all_sparse(sv)
                                else sv.sharded.words)
            sv, words = staged[vkey]
            i = int(np.searchsorted(sv.row_ids, np.uint64(row_id)))
            if i >= len(sv.row_ids) or sv.row_ids[i] != np.uint64(row_id):
                i = len(sv.row_ids)  # absent row: resolver yields hit=0
            if isinstance(words, tuple):
                self.stats.inc("sparse_src_leaves")
                flat_idx, hit = self._sparse_leaf_arrays(sv, i)
                coarse = None
            else:
                flat_idx, hit, coarse = self._leaf_arrays(sv, i)
            words_t.append(words)
            idx_t.append(flat_idx)
            hit_t.append(hit)
            coarse_t.append(coarse)
        first = next(iter(staged.values()))[0]
        return (tuple(words_t), tuple(idx_t), tuple(hit_t),
                tuple(coarse_t), first)

    def _get_or_compile(self, cache: dict, key, build,
                        entry: str = "other"):
        """Get-or-compile under _compile_mu so a given program compiles
        ONCE even when two first queries of the same shape race
        (ADVICE r2: the GIL kept the dicts safe but let both pay the
        multi-second compile). The fast path stays lock-free; _mu is
        never acquired here, so compiles don't block staging. `entry`
        names the program family for the compile telemetry."""
        fn = cache.get(key)
        if fn is not None:
            return fn
        with self._compile_mu:
            fn = cache.get(key)
            if fn is None:
                fn = self._timed_build(entry, build)
                cache[key] = fn
        return fn

    def _timed_build(self, entry: str, build):
        """The one choke point every program compile passes through:
        wall-time + count, both per entry point (compile_stats) and in
        aggregate (stats compile_count/compile_us), so /metrics can
        attribute first-shape serving stalls to the program family
        that paid them."""
        t0 = time.monotonic()
        with profile.phase("compile"):
            fn = build()
        us = int((time.monotonic() - t0) * 1e6)
        self.compile_stats.inc(f"{entry}_count")
        self.compile_stats.inc(f"{entry}_us", us)
        self.stats.inc("compile_count")
        self.stats.inc("compile_us", us)
        return fn

    def _count_program(self, kind: str, sig: str, num_leaves: int,
                       width: int, backend: str = "xla",
                       uniform: bool = False):
        """Get-or-compile one batched count program — the ONE place
        the cache key lives, and where a key becomes a builder call.
        kind "general" (container gather, XLA whatever the backend) or
        "coarse" (whole-row runs). A coarse program under a Pallas
        backend is the one-launch streaming kernel at width 1, the
        identity-map grid kernel above it (both read each leaf row
        HBM->VMEM once with no gathered intermediate), or with
        `uniform` the multi-slice-fetch kernel, whose call contract
        differs (scalar starts + mask, no valid arrays). The key
        carries the exact backend string: "pallas" and
        "pallas_interpret" compile different programs, and an env flip
        between them must not serve the other's."""
        key = (kind, sig, num_leaves, width, backend, uniform)

        def build():
            tree = json.loads(sig)
            if kind == "coarse" and backend != "xla":
                interpret = backend == "pallas_interpret"
                if uniform:
                    return compile_serve_count_coarse_pallas_uniform(
                        self.mesh, tree, num_leaves, width,
                        interpret=interpret)
                if width == 1:
                    return compile_serve_count_coarse_pallas(
                        self.mesh, tree, num_leaves, interpret=interpret)
                return compile_serve_count_coarse_pallas_batch(
                    self.mesh, tree, num_leaves, width,
                    interpret=interpret)
            return compile_serve_count(self.mesh, tree, num_leaves, width,
                                       runs=kind == "coarse")

        return self._get_or_compile(
            self._count_programs, key, build,
            entry=("coarse" if kind == "coarse"
                   else "count" if width == 1 else "count_batch"))

    # "auto" resolution cache: None = unresolved, else "pallas"/"xla".
    # Process-wide (ops/calibrate.py measures once; its verdict holds
    # for every manager in the process — this mirror only saves the
    # cross-module call on the hot dispatch path).
    _AUTO_BACKEND: "Optional[str]" = None

    @classmethod
    def _count_backend(cls) -> str:
        """PILOSA_TPU_COUNT_BACKEND: "auto" (default), "pallas",
        "pallas_interpret" (CPU test path), or "xla". The explicit
        values pin the dispatch; "auto" resolves through the measured
        startup calibration (ops/calibrate.py): trivial-kernel canary
        probe, then a timed Pallas-vs-XLA race on a representative
        uniform coarse-count shape, winner cached per process (and per
        device kind via PILOSA_TPU_CALIBRATION_FILE). The whole
        resolution runs in an abandonable daemon thread under a
        bounded wait, so a Pallas compile that hangs verdicts "xla"
        instead of wedging the server. Non-TPU backends resolve instantly
        to "xla". The record behind the verdict is surfaced at
        /debug/vars under "count_calibration"."""
        import os

        v = os.environ.get("PILOSA_TPU_COUNT_BACKEND", "auto")
        if v == "auto":
            return cls._resolve_auto_backend()
        if v not in ("pallas", "pallas_interpret", "xla"):
            # A typo'd pin degrades to the conservative constant — it
            # must NOT trigger the probe the operator was pinning away
            # from (and must not memoize a verdict into _AUTO_BACKEND).
            return "xla"
        return v

    @classmethod
    def _resolve_auto_backend(cls) -> str:
        # Lock-free fast path: the verdict is written once; reading a
        # stale None merely re-enters the resolution below. Queries
        # arriving DURING the (bounded) calibration serve on xla
        # (wait=False) instead of blocking behind it — the compile
        # keys differ per backend, so the switch mid-stream is safe.
        v = cls._AUTO_BACKEND
        if v is not None:
            return v
        from ..ops.calibrate import calibration_snapshot, resolve_backend

        b = "pallas" if resolve_backend(wait=False) == "pallas" else "xla"
        if calibration_snapshot() is not None:  # resolved, not provisional
            cls._AUTO_BACKEND = b
        return b

    def _uniform_starts(self, coarse_ts, backend: str):
        """(B*L,) int32 scalar starts for the uniform Pallas programs,
        or None when any leaf is non-uniform or the backend isn't
        Pallas. coarse_ts: one coarse_t tuple per request (each leaf's
        (starts, valid, uniform_scalar) from _leaf_arrays)."""
        if backend not in ("pallas", "pallas_interpret"):
            return None
        flat = []
        for ct in coarse_ts:
            for c in ct:
                if c[2] is None:
                    return None
                flat.append(c[2])
        return np.asarray(flat, dtype=np.int32)

    @staticmethod
    def _shared_policy() -> str:
        """PILOSA_TPU_BATCH_SHARED: "auto" (default — use a cached
        shared-read program, compile new compositions in the
        background), "sync" (compile inline; tests/bench), "off"."""
        import os

        v = os.environ.get("PILOSA_TPU_BATCH_SHARED", "auto").lower()
        return v if v in ("auto", "sync", "off") else "auto"

    def _shared_plan(self, group, backend: str):
        """(key, leaf_map, uniques, ordered_group) for a
        coarse-eligible group, or None when sharing saves no reads
        (every leaf distinct). The leaf map indexes each request's
        leaves into the group's unique-(words, start, valid) table.
        The group is CANONICALLY ordered by LOGICAL leaf identity
        ((frame, view, row_id) — stable across restages and HBM
        evictions, unlike array ids) so a repeated workload
        composition maps to ONE compile key regardless of queue
        arrival order or staging generation."""
        if any(r.leaf_keys is None for r in group):
            return None  # direct callers without logical keys
        ordered = sorted(group, key=lambda r: r.leaf_keys)
        uniq: Dict[tuple, int] = {}
        uniques = []
        leaf_map = []
        for r in ordered:
            row = []
            # Logical keys are 1:1 with arrays WITHIN a group (same
            # staged generation, enforced by group_key), so the unique
            # table can key on them while carrying the arrays.
            for k, (wt, ct) in zip(r.leaf_keys,
                                   zip(r.args[1], r.coarse_t)):
                u = uniq.get(k)
                if u is None:
                    u = uniq[k] = len(uniques)
                    uniques.append((wt, ct[0], ct[1], ct[2]))
                row.append(u)
            leaf_map.append(tuple(row))
        total_slots = sum(len(m) for m in leaf_map)
        if len(uniques) >= total_slots:
            return None  # nothing shared: plain batch reads the same
        # AOT compile accounting bills EVERY operand as its own buffer
        # even when all U uniques alias one staged pool ("arguments:
        # U x pool bytes" — observed as a compile-time HBM rejection at
        # 30 GB for 32 aliases of the 1 GB headline pool). Skip the
        # shared upgrade when the aliased-argument bill would crowd a
        # 16 GB chip (PILOSA_TPU_SHARED_ARG_BUDGET_MB, default 11264);
        # the plain batch program (L operands) serves instead. The
        # 28-pair/8-row headline composition bills ~8 GB and passes.
        arg_budget = _num_env("PILOSA_TPU_SHARED_ARG_BUDGET_MB",
                              11264) << 20
        # Arguments shard over the slice axis, so each chip is billed
        # global bytes / mesh size — budget the PER-CHIP bill, not the
        # global one (a 4-chip mesh quarters the per-chip cost).
        n_dev = max(1, self.mesh.shape.get(SLICE_AXIS, 1))
        arg_bytes = sum(int(np.prod(u[0].shape)) * 4
                        for u in uniques) // n_dev
        if arg_bytes > arg_budget:
            return None
        sig = group[0].args[0]
        # Uniform layout (every unique leaf at ONE row-run index across
        # slices — _leaf_arrays detects it) upgrades the shared program
        # to the multi-slice-fetch kernel. In the KEY because a restage
        # can change the layout: a uniform program must never serve a
        # non-uniform staging of the same composition.
        uniform = (backend in ("pallas", "pallas_interpret")
                   and all(u[3] is not None for u in uniques))
        # The backend is part of the compile key: an env flip between
        # xla and pallas must not serve the other's program.
        return ((sig, tuple(leaf_map), len(uniques), backend, uniform),
                tuple(leaf_map), uniques, ordered)

    _SHARED_FNS_MAX = 32
    _SHARED_SEEN_MAX = 256

    def _shared_get(self, key):
        """LRU lookup in the shared-program cache under its own
        short-hold lock (the background builder inserts/popitems the
        same OrderedDict; a bare .get() during structural mutation is
        not a guaranteed-safe pattern — ADVICE r3)."""
        with self._shared_mu:
            fn = self._shared_fns.get(key)
            if fn is not None:
                self._shared_fns.move_to_end(key)
            return fn

    def _shared_put(self, key, fn):
        with self._shared_mu:
            self._shared_fns[key] = fn
            while len(self._shared_fns) > self._SHARED_FNS_MAX:
                self._shared_fns.popitem(last=False)

    def _build_shared(self, tree_sig, leaf_map, num_unique, backend,
                      uniform: bool = False):
        """Construct the shared-read batch program on `backend` — the
        string baked into the caller's cache key by _shared_plan, NOT
        re-read from the env here: a background build must cache the
        program the key names even if the env flips mid-build. With
        `uniform` (also from the key) the program takes (words_t,
        scalar starts (U,), mask) — _pick_count reads the contract off
        the key."""
        if backend in ("pallas", "pallas_interpret"):
            interpret = backend == "pallas_interpret"
            if uniform:
                from .mesh import (
                    compile_serve_count_batch_shared_pallas_uniform)

                return compile_serve_count_batch_shared_pallas_uniform(
                    self.mesh, json.loads(tree_sig), leaf_map,
                    num_unique, interpret=interpret)
            from .mesh import compile_serve_count_batch_shared_pallas

            return compile_serve_count_batch_shared_pallas(
                self.mesh, json.loads(tree_sig), leaf_map, num_unique,
                interpret=interpret)
        return compile_serve_count_batch_shared(
            self.mesh, json.loads(tree_sig), leaf_map, num_unique)

    def _shared_compile_sync(self, key, tree_sig, leaf_map, num_unique):
        """Inline compile for policy="sync" (tests/bench). _compile_mu
        dedupes racing first compiles; _shared_mu alone covers the dict
        ops, so warm lookups elsewhere never wait on the build."""
        with self._compile_mu:
            fn = self._shared_get(key)
            if fn is None:
                fn = self._timed_build(
                    "shared",
                    lambda: self._build_shared(tree_sig, leaf_map,
                                               num_unique, key[-2],
                                               uniform=key[-1]))
                self._shared_put(key, fn)
        return fn

    @staticmethod
    def _shared_seen_min() -> int:
        """Sightings of one composition before the auto policy spends a
        background compile on it (PILOSA_TPU_SHARED_SEEN_MIN, default
        8). Random herd fragmentation mints compositions that almost
        never repeat, and each would cost a compile of seconds; a
        genuinely repeated composition (dashboard refresh, a hot query
        set) reaches 8 sightings in moments and earns the shared
        program, drain-window noise does not. Whether 8 is the right
        number on the attached chip is not measured (ROADMAP D3)."""
        return max(1, _num_env("PILOSA_TPU_SHARED_SEEN_MIN", 8))

    def _shared_compile_async(self, key, tree_sig, leaf_map, num_unique):
        """Kick a background compile of the shared program — only once
        a composition has repeated enough to be worth a pipeline stall
        (_shared_seen_min), and bounded caches throughout."""
        with self._shared_mu:
            if key in self._shared_fns or key in self._shared_pending:
                return
            n = self._shared_seen.get(key, 0) + 1
            self._shared_seen[key] = n
            self._shared_seen.move_to_end(key)
            while len(self._shared_seen) > self._SHARED_SEEN_MAX:
                self._shared_seen.popitem(last=False)
            if n < self._shared_seen_min():
                return
            self._shared_pending.add(key)

        def build():
            try:
                fn = self._timed_build(
                    "shared",
                    lambda: self._build_shared(tree_sig, leaf_map,
                                               num_unique, key[-2],
                                               uniform=key[-1]))
                self._shared_put(key, fn)
            finally:
                with self._shared_mu:
                    self._shared_pending.discard(key)

        threading.Thread(target=build, name="shared-batch-compile",
                         daemon=True).start()

    # -- plan quarantine + guarded device execution ---------------------------

    def _note_plan_failure(self, sig: str) -> None:
        """Count a device-execution strike against a plan signature;
        at [mesh] quarantine-after strikes the signature is quarantined
        in the compiled-plan cache for quarantine-ttl, and identical
        queries skip the device path (host fold) until it expires. A
        success is NOT required to clear strikes early — the TTL is the
        release valve — but strikes reset when the quarantine lands so
        the next TTL window starts clean."""
        if not sig:
            return
        with self._quar_mu:
            n = self._plan_failures.get(sig, 0) + 1
            if n < self._quarantine_after:
                self._plan_failures[sig] = n
                return
            self._plan_failures.pop(sig, None)
        self._fused_plans.quarantine(sig, self._quarantine_ttl)
        self.stats.inc("plan_quarantined")

    def plan_quarantined(self, sig: str) -> bool:
        return self._fused_plans.is_quarantined(sig)

    def quarantine_plan(self, sig: str) -> None:
        """Quarantine a signature IMMEDIATELY, bypassing the strike
        ladder. For failures where a retry cannot help and serving the
        device answer again would be wrong — shadow verification caught
        the plan returning a different count than the host fold."""
        if not sig:
            return
        with self._quar_mu:
            self._plan_failures.pop(sig, None)
        self._fused_plans.quarantine(sig, self._quarantine_ttl)
        self.stats.inc("plan_quarantined")

    def quarantined_plans(self) -> List[str]:
        return self._fused_plans.quarantined_sigs()

    def clear_quarantine(self, sig: Optional[str] = None) -> int:
        """Operator reset (ctl / debug): lift a quarantine (or all) and
        forget accumulated strikes. Returns how many were lifted."""
        with self._quar_mu:
            if sig is None:
                self._plan_failures.clear()
            else:
                self._plan_failures.pop(sig, None)
        return self._fused_plans.clear_quarantine(sig)

    def _dispatch_serialized(self) -> bool:
        """True when device program launches must serialize through
        _dispatch_mu: on a >1-device CPU mesh (forced host platform
        device count — CI, the MULTICHIP dryrun) XLA executes the
        per-device programs of a collective inline on the calling
        threads, and two concurrent multi-device launches can
        interleave their per-device programs into a cross-paired
        collective rendezvous that spins forever. Real accelerators
        queue launches on the device stream, so they skip the lock."""
        v = self._serialize_dispatch
        if v is None:
            try:
                import jax

                v = bool(self.mesh.devices.size > 1
                         and jax.default_backend() == "cpu")
            except Exception:  # noqa: BLE001 — no mesh: nothing launches
                v = False
            self._serialize_dispatch = v
        return v

    @contextlib.contextmanager
    def _launch_gate(self, views=(), expect_gens=None):
        """The per-view dispatch-generation gate every device launch
        passes through. Under the gate (serialized on CPU multi-device
        meshes, see _dispatch_serialized): first re-validate
        `expect_gens` — (view, generation) pairs captured at resolve
        time — raising DispatchGenMoved when any view has been
        launched against since (the caller falls back to a coalescing
        path instead of stacking a second in-flight execution); then
        stamp every participating view's dispatch_gen."""
        lock = self._dispatch_mu if self._dispatch_serialized() else None
        if lock is not None:
            lock.acquire()
        try:
            if expect_gens is not None and any(
                    sv.dispatch_gen != gen for sv, gen in expect_gens):
                raise DispatchGenMoved()
            for sv in views:
                sv.dispatch_gen += 1
            yield
        finally:
            if lock is not None:
                lock.release()

    def _guarded_exec(self, sig: str, launch, kind: str = "count",
                      note: bool = True, views=(), expect_gens=None):
        """Run one device program launch through the recovery ladder:

          quarantined sig  -> DeviceResourceError("quarantined") now,
                              no launch (callers host-fold);
          RESOURCE_EXHAUSTED -> emergency-evict unpinned views, retry
                              ONCE; a second OOM degrades to
                              DeviceResourceError("oom");
          compiler refusal -> counted (fallback_compile), never
                              evicted for and never retried: see
                              _is_compile_refusal;
          other errors     -> propagate unchanged (caller semantics
                              keep working), after noting a strike.

        `note=False` suppresses strike counting AND the fallback_*
        stat bumps for launches whose failure another path will retry
        and re-count (e.g. _lone_count falling through to the chained
        path) — otherwise one transient fault would double-strike
        straight into quarantine and double-count the fallback.

        `views` / `expect_gens` thread through to _launch_gate: views
        get their dispatch generation stamped per launch; expect_gens
        aborts the launch (DispatchGenMoved, propagated without a
        strike — it is not a plan failure) when another dispatch beat
        this one to those views."""

        def attempt():
            fault.point("device.exec", sig=sig, kind=kind)
            with self._launch_gate(views, expect_gens):
                return launch()

        if self.plan_quarantined(sig):
            if note:
                self.stats.inc("fallback_quarantined")
            raise DeviceResourceError(
                f"plan quarantined: {sig[:80]}", reason="quarantined")
        try:
            return attempt()
        except DispatchGenMoved:
            # Control flow, not a plan failure: no strike. Counted so
            # the retry-into-coalescing rate is visible at /metrics.
            self.stats.inc("dispatch_gen_moved")
            raise
        except Exception as e:  # noqa: BLE001 — classify then rethrow
            if not _is_resource_exhausted(e):
                if note:
                    self._note_plan_failure(sig)
                    if _is_compile_refusal(e):
                        self.stats.inc("fallback_compile")
                raise
            self.stats.inc("oom_retries")
            self._evict_for_oom()
            try:
                return attempt()
            except Exception as e2:  # noqa: BLE001
                if note:
                    self._note_plan_failure(sig)
                if _is_resource_exhausted(e2):
                    if note:
                        self.stats.inc("fallback_oom")
                    raise DeviceResourceError(
                        f"device OOM after eviction: {e2}",
                        reason="oom") from e2
                raise

    # -- dynamic batching -----------------------------------------------------

    # Queries coalesced into one device program, max. Compile cost grows
    # with the unroll; 16 queries share one dispatch and one readback.
    _MAX_BATCH = 16

    @staticmethod
    def _fetch_threads() -> int:
        """Readback worker count (PILOSA_TPU_FETCH_THREADS env, default
        8). A result fetch waits for its program to complete; one
        fetch worker serializes every batch behind the one before,
        while concurrent fetches overlap, so a small pool lets
        fragmented herd groups' readbacks ride together. The workers
        only block in the PJRT client (GIL released). What a fetch
        costs on the attached chip, and so whether the pool pays, is
        not measured (ROADMAP D3)."""
        return max(1, _num_env("PILOSA_TPU_FETCH_THREADS", 8))

    def _ensure_batch_thread(self):
        if self._batch_thread is None:
            with self._hold("batch"):
                if self._batch_thread is None:
                    t = threading.Thread(target=self._batch_loop,
                                         name="mesh-count-batch", daemon=True)
                    t.start()
                    self._batch_thread = t
                    for i in range(self._fetch_pool_n):
                        f = threading.Thread(
                            target=self._fetch_loop,
                            name=f"mesh-count-fetch-{i}", daemon=True)
                        f.start()
                    self.stats["fetch_threads"] = self._fetch_pool_n

    def _fetch_loop(self):
        """Materialize dispatched batches' results and wake waiters.
        Decoupled from the batch loop so the per-batch host readback
        overlaps the NEXT batch's dispatch and device
        execution — without it the device idles for a full readback
        between batches. SEVERAL workers run this loop: concurrent
        fetches overlap (see _fetch_threads), so distinct groups'
        readbacks do not queue behind one another. Each finish() is self-contained
        (its own group's results + events), so completion order across
        workers doesn't matter. The fetch queue's bound (maxsize) is
        the pipeline depth: the batch loop blocks once that many
        batches await readback, so a flood of clients can't queue
        unbounded device work."""
        while True:
            finish = self._fetch_q.get()
            try:
                finish()
            except Exception:  # noqa: BLE001 — finisher handles errors
                pass

    def expect_burst(self, n: int):
        """Scheduler cohort hint (sched/ via executor.burst_hint): n
        requests were just released together. Without the hint, the
        first arrival of a fresh herd either takes the lone fused path
        or drains alone (last_group == 1 skips the window), and the
        cohort fragments into two device programs; with it, the whole
        cohort rides one drain into one shared-read batch."""
        with self._burst_mu:
            self._burst_hint += int(n)

    @staticmethod
    def _drain_window_s() -> float:
        """Herd drain window (PILOSA_TPU_BATCH_WINDOW_MS env, default
        3 ms): how long the batch loop waits for stragglers when the
        PREVIOUS group showed concurrency. With the fetch pool
        overlapping readbacks, a merged group saves one program
        dispatch plus the extra group's padded device time. Whether
        3 ms is the right price on the attached chip is not measured
        (ROADMAP D3)."""
        return max(0.0, _num_env("PILOSA_TPU_BATCH_WINDOW_MS", 3.0,
                                 float)) / 1e3

    def _batch_loop(self):
        """Drain-and-group: take everything queued while the device was
        busy, group by compatible shape, execute each group as one
        program. A LONE request runs immediately (no timed window), but
        when the previous drain coalesced multiple requests — a
        concurrent-client herd mid-wake, whose members arrive spread
        over a few GIL-staggered milliseconds — the loop waits a short
        drain window for stragglers. Since the fetch POOL overlaps
        concurrent groups' readbacks (see _fetch_threads), a fragmented
        herd no longer serializes its readbacks; what fragmentation
        still costs is one extra program dispatch plus padded-width
        device time per extra group, which the drain window is priced
        against."""
        # Event-driven (interval=None): blocking in q.get() with an
        # empty queue is idle, not a hang — the watchdog judges this
        # subsystem only through the in-flight record around each
        # group's device execution below.
        hb = HEALTH.register("mesh-count-batch", interval=None,
                             critical=True)
        last_group = 1
        while True:
            hb.idle()
            first = self._batch_q.get()
            hb.beat()
            reqs = [first]
            with self._burst_mu:
                hinted = self._burst_hint > 1
            deadline = (time.monotonic() + self._drain_window_s()
                        if (last_group > 1 or hinted) else 0.0)
            while len(reqs) < self._MAX_BATCH:
                try:
                    reqs.append(self._batch_q.get_nowait())
                except queue.Empty:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        break
                    try:
                        reqs.append(self._batch_q.get(timeout=wait))
                    except queue.Empty:
                        break
            last_group = len(reqs)
            with self._burst_mu:
                if self._burst_hint:
                    self._burst_hint = max(0,
                                           self._burst_hint - len(reqs))
            if hinted:
                self.stats.inc("sched_hinted")
            groups: Dict[tuple, List[_CountRequest]] = {}
            for r in reqs:
                groups.setdefault(r.group_key(), []).append(r)
            for group in groups.values():
                try:
                    # A device launch that never returns (wedged
                    # runtime, lost collective) must trip the watchdog:
                    # every queued count behind this loop is stuck.
                    with HEALTH.inflight("mesh-count-batch", "count-group",
                                         base=30.0):
                        self._run_count_group(group)
                except Exception as e:  # noqa: BLE001 — fail the group only
                    for r in group:
                        r.error = e
                        r.done.set()

    def _pick_count(self, group: List["_CountRequest"],
                    backend: str) -> "_Picked":
        """Which program one deduped group runs: the whole decision,
        nowhere else. `backend` is _count_backend(), read once by the
        caller. b = len(group); the program is compiled (or found) when
        the launch calls `program()`, under the launch guard.

          width   1 when b == 1, else _MAX_BATCH: ONE batch width per
                  shape, padded with repeats of the last request.
                  Sizing the pad to the group meant a 16-client herd
                  that fragmented into 13+3 compiled TWO programs, each
                  a multi-second XLA compile ON THE BATCH THREAD,
                  fragmenting the next herd into yet more odd widths.
                  The padding's device cost is the repeated request's
                  extra gathers; whether it pays on the attached chip
                  is not measured (ROADMAP D3).
          general some leaf of some request is not a whole-row run
                  (coarse_row_starts): the container-gather program,
                  XLA under every backend. Counts `batched` b if b > 1.
          shared  every leaf coarse, b > 1, PILOSA_TPU_BATCH_SHARED not
                  "off", the group shares a leaf within the argument
                  budget (_shared_plan) and the composition's program
                  is compiled: cached, or built inline under "sync".
                  Under "auto" a composition not yet compiled counts a
                  sighting (_shared_compile_async builds it in the
                  background from the _shared_seen_min-th) and the
                  group runs coarse or uniform meanwhile. Exact width
                  b, columns in the plan's canonical order. Counts
                  `shared_batch`, `coarse`, `batched` b.
          uniform every leaf coarse, a Pallas backend, and every leaf
                  at ONE row-run index across all slices
                  (_leaf_arrays): scalar starts. Counts
                  `coarse_uniform`, `coarse` b, `batched` b if b > 1.
          coarse  every leaf coarse, otherwise: (start, valid) runs,
                  XLA or the Pallas twin. Counts `coarse` b, `batched`
                  b if b > 1.
        """
        b = len(group)
        sig, words_t, idx_t, _hit_t, dev_mask = group[0].args
        n = len(idx_t)
        width = 1 if b == 1 else self._MAX_BATCH
        padded = group + [group[-1]] * (width - b)
        counters = (("batched", b),) if b > 1 else ()
        if not all(c is not None for r in group for c in r.coarse_t):
            return _Picked(
                "general", width,
                lambda: self._count_program("general", sig, n, width),
                (words_t, tuple(a for r in padded for a in r.args[2]),
                 tuple(a for r in padded for a in r.args[3]), dev_mask),
                group, counters)
        counters = (("coarse", b),) + counters
        policy = self._shared_policy() if b > 1 else "off"
        plan = (self._shared_plan(group, backend)
                if policy != "off" else None)
        if plan is not None:
            key, leaf_map, uniques, ordered = plan
            shared = self._shared_get(key)
            if shared is None:
                if policy == "sync":
                    shared = self._shared_compile_sync(
                        key, sig, leaf_map, len(uniques))
                else:
                    self._shared_compile_async(
                        key, sig, leaf_map, len(uniques))
            if shared is not None:
                words_u = tuple(u[0] for u in uniques)
                if key[-1]:  # uniform: scalar starts, no valid arrays
                    args = (words_u, self._device_starts(np.asarray(
                        [u[3] for u in uniques], dtype=np.int32)),
                        dev_mask)
                else:
                    args = (words_u, tuple(u[1] for u in uniques),
                            tuple(u[2] for u in uniques), dev_mask)
                return _Picked("shared", b, lambda: shared, args, ordered,
                               (("shared_batch", b),) + counters)
        ustarts = self._uniform_starts([r.coarse_t for r in padded],
                                       backend)
        if ustarts is not None:
            return _Picked(
                "uniform", width,
                lambda: self._count_program("coarse", sig, n, width,
                                            backend, uniform=True),
                (words_t, self._device_starts(ustarts), dev_mask),
                group, (("coarse_uniform", b),) + counters)
        return _Picked(
            "coarse", width,
            lambda: self._count_program("coarse", sig, n, width, backend),
            (words_t, tuple(c[0] for r in padded for c in r.coarse_t),
             tuple(c[1] for r in padded for c in r.coarse_t), dev_mask),
            group, counters)

    def _run_count_group(self, group: List["_CountRequest"]):
        """One group of the batch loop, one device program: dedupe,
        _pick_count, one guarded launch, the D2H copy started, and the
        results handed out by finish() (on a fetch worker when the
        batch thread calls, inline for a direct caller)."""
        # Identical requests (same leaf arrays AND mask — e.g. many
        # clients polling the same Count) collapse to ONE program slot;
        # only distinct queries consume batch width.
        uniq: Dict[tuple, _CountRequest] = {}
        dups: List[Tuple[_CountRequest, tuple]] = []
        for r in group:
            sig, words_t, idx_t, hit_t, dev_mask = r.args
            key = (sig, tuple(id(a) for a in idx_t),
                   tuple(id(a) for a in hit_t), id(dev_mask))
            if key in uniq:
                dups.append((r, key))
            else:
                uniq[key] = r
        group = list(uniq.values())
        self.stats.inc("deduped", len(dups))
        # Union of staged views this group launches against — each
        # launch below stamps their dispatch generations under the
        # launch gate.
        gviews = tuple({id(sv): sv for r in group
                        for sv in r.views}.values())

        def _propagate():
            for r, key in dups:
                src = uniq[key]
                r.result, r.error = src.result, src.error
                r.done.set()

        sig = group[0].args[0]
        pick = self._pick_count(group, self._count_backend())
        # The general batch is the one group launch a device trace
        # names (the lone paths have scopes of their own).
        scoped = pick.kind == "general" and pick.width > 1

        def launch():
            with (jax_scope("pilosa:count_batch") if scoped
                  else contextlib.nullcontext()):
                return pick.program()(*pick.args)

        limbs = self._guarded_exec(sig, launch, views=gviews)
        for name, n in pick.counters:
            self.stats.inc(name, n)
        self.stats.inc("device_dispatches")
        # Output columns follow pick.order (a shared program's are in
        # the plan's canonical order; padding columns are not read).
        group = pick.order

        # Start the D2H copy NOW: by the time the program completes,
        # the bytes are already on their way and the worker's
        # np.asarray is a memcpy, not a second round-trip.
        try:
            limbs.copy_to_host_async()
        except Exception:  # noqa: BLE001 — optional fast path only
            pass

        # Dispatch done (async device handle in `limbs`); the FETCH
        # happens on a fetcher-pool worker so the next batch's dispatch
        # overlaps it and concurrent groups' readbacks overlap each
        # other.
        # (Direct callers — tests, no batch thread running — finish
        # synchronously below.)
        def finish():
            try:
                arr = np.asarray(limbs)
                for j, r in enumerate(group):
                    r.result = (int(arr[1, j]) << 16) + int(arr[0, j])
            except Exception as e:  # noqa: BLE001 — fail the group
                # Async execution errors surface HERE (first fetch),
                # not at dispatch — strike the plan signature so a
                # persistently failing program still quarantines, and
                # degrade device OOM to the transient error count()
                # turns into a host-fold (the dispatched program can't
                # be retried post-hoc; the re-issued query can).
                self._note_plan_failure(sig)
                if _is_compile_refusal(e):
                    self.stats.inc("fallback_compile")
                elif _is_resource_exhausted(e):
                    self.stats.inc("fallback_oom")
                    e = DeviceResourceError(
                        f"device OOM at result fetch: {e}", reason="oom")
                for r in group:
                    r.error = e
            for r in group:
                r.done.set()
            _propagate()

        if threading.current_thread() is self._batch_thread:
            self._fetch_q.put(finish)
        else:
            # Direct callers (tests, bench helpers) must see results
            # set when this returns — and must not depend on a fetch
            # thread that may not exist.
            finish()

    def count(self, index: str, shape, leaves, slices: Sequence[int],
              num_slices: int) -> Optional[int]:
        """Serve Count over a lowered bitmap-op tree: one shard_map'd
        fused eval + psum across the requested slices. `shape`/`leaves`
        come from plan._lower_tree: leaves are (frame, view, row_id,
        required) in depth-first order; each leaf gathers from its own
        staged view (trees may span frames and time-quantum views).

        A LONE count (no other count in flight) takes the fused
        single-dispatch path: gather metadata and mask ride the one
        jitted call as host arguments (compile_serve_count, host_meta), so a
        distinct query pays one dispatch + one fetch instead of the
        chained metadata-upload + program sequence (three dispatches).

        Concurrent same-shape counts COALESCE: the request goes through
        the batch loop, which drains whatever queued while the device
        was busy and runs up to _MAX_BATCH queries as one program.
        Dispatch and readback are a fixed cost per program, so
        batching multiplies concurrent throughput while a lone request
        runs immediately.

        Profile phases: the locks, the view refresh, staging, compile,
        the launch and the readback have their own; `mesh_prepare` is
        the rest of this call (residual: each of those pauses it)."""
        with profile.residual("mesh_prepare"):
            out = self._count(index, shape, leaves, slices, num_slices)
        prof = profile.current()
        if prof is not None and out is not None:
            # What the answer ran on: a ?profile=true reader sees a
            # server that came up on one of four chips.
            prof.tag(devices=int(self.mesh.devices.size))
        return out

    def _count(self, index: str, shape, leaves, slices: Sequence[int],
               num_slices: int) -> Optional[int]:
        t0 = time.monotonic()
        sp = span("dispatch", engine="mesh", leaves=len(leaves),
                  slices=len(slices))
        # Quarantine gate BEFORE any staging or inflight accounting:
        # a signature that keeps killing the device path skips it
        # entirely (the executor folds on the host) until the TTL
        # expires. Cheap — json.dumps of the already-lowered shape.
        sig = json.dumps(_tree_signature(shape))
        if self.plan_quarantined(sig):
            self.stats.inc("fallback_quarantined")
            sp.tag(mode="quarantined")
            sp.finish()
            return None
        # Probe the sparse path when a resident view serves from a
        # sorted-array pool — or when a queried view is COLD (not
        # staged yet): its first staging may pick the sparse format,
        # and the dense-pool paths would immediately demote it back.
        # All-dense steady state keeps the one-int check.
        sparse_probe = bool(self._sparse_views) or any(
            (index, f, v) not in self._views for f, v, _r, _q in leaves)
        if sparse_probe:
            # _SPARSE_NA means none of THIS query's leaves touch a
            # sparse pool — flow on to the dense paths; None means the
            # sparse kernels can't serve the shape (or the device
            # failed) — fold on the host, the dense pools don't hold
            # those slices' containers.
            out = self._sparse_count(index, shape, leaves, slices,
                                     num_slices, sig)
            if out is not self._SPARSE_NA:
                if out is None:
                    sp.tag(mode="fallback", reason="sparse_format")
                    sp.finish()
                    return None
                self.stats.inc("count")
                self.stats.inc("sparse_count")
                self.stats.inc("query_us",
                               int((time.monotonic() - t0) * 1e6))
                sp.tag(mode="sparse", dispatches=1)
                sp.finish()
                return fault.perturb("device.exec", out, sig=sig,
                                     kind="count-result")
        if not self.lone_fused:
            sp.tag(kill_switch="lone_fused=off")
        with _held(self._lone_mu):
            self._counts_inflight += 1
            lone = self._counts_inflight == 1
        if lone:
            # A scheduler-released cohort arrives GIL-staggered: the
            # first member would see itself alone and take the fused
            # path, stranding the rest in a narrower batch. The burst
            # hint says siblings are right behind — batch instead.
            with _held(self._burst_mu):
                if self._burst_hint > 1:
                    lone = False
        pins: list = []
        try:
            if lone and self.lone_fused:
                out = self._lone_count(index, shape, leaves, slices,
                                       num_slices)
                if out is not None:
                    self.stats.inc("count")
                    self.stats.inc("query_us",
                                   int((time.monotonic() - t0) * 1e6))
                    sp.tag(mode="fused", dispatches=1)
                    return fault.perturb("device.exec", out[0], sig=sig,
                                         kind="count-result")
            prepared = self._count_args(index, shape, leaves, slices,
                                        num_slices, pins=pins)
            if prepared is None:
                sp.tag(mode="fallback")
                return None
            req = _CountRequest(*prepared)
            req.leaf_keys = tuple((f, v, int(r)) for f, v, r, _ in leaves)
            req.views = tuple(pins)
            self._ensure_batch_thread()
            self._batch_q.put(req)
            prof = profile.current()
            if prof is None:
                req.done.wait()
            else:
                # Batched dispatch runs on the batch thread; from here
                # the wait is the batch queue (the drain window, and
                # the programs ahead of this request's), then device
                # execution + readback (the fetcher sets done after
                # np.asarray). Attributed as device_exec — the queue
                # and D2H splits would need per-request timestamps on
                # the batch and fetch threads, not worth a hot-path
                # field.
                with prof.phase("device_exec"):
                    req.done.wait()
                prof.add_bytes("bytes_touched_hbm",
                               len(leaves) * len(slices)
                               * ROW_SPAN * CONTAINER_WORDS * 4)
                prof.add_slice(engine="device_batched",
                               leaves=len(leaves), slices=len(slices))
            if req.error is not None:
                if isinstance(req.error, DeviceResourceError):
                    # The recovery ladder already retried and counted
                    # the fallback; answer None so the executor folds
                    # this query on the host instead of 500ing.
                    sp.tag(mode="fallback", reason=req.error.reason)
                    return None
                _reraise_shared("batched device count", req.error)
            self.stats.inc("count")
            self.stats.inc("query_us", int((time.monotonic() - t0) * 1e6))
            sp.tag(mode="batched")
            # Bit-rot seam for shadow verification: a delta rule on
            # device.exec (kind=count-result) perturbs the returned
            # count, modeling a silent device miscomputation.
            return fault.perturb("device.exec", req.result, sig=sig,
                                 kind="count-result")
        finally:
            self._release_pins(pins)
            sp.finish()
            with _held(self._lone_mu):
                self._counts_inflight -= 1

    def _lone_count(self, index: str, shape, leaves,
                    slices: Sequence[int], num_slices: int):
        """The fused single-dispatch count: resolve every leaf's gather
        metadata on the HOST (cached per view), look the program up in
        the compiled-plan LRU, and launch it with the metadata and mask
        as jit arguments — no standalone device_put ever runs. Returns
        a 1-tuple (count,) so a legitimate zero survives the truthiness
        at the call site, or None to fall through to the chained path
        (which re-resolves and reports its own fallback). Device
        launches go through _guarded_exec with note=False: a failure
        here falls through to the chained path, which retries and
        notes its OWN strike — noting both would double-strike one
        transient fault straight into quarantine."""
        pins: list = []
        try:
            with self._hold("lone_args"):
                self._use_epoch += 1
                out = self._stage_leaves_host(index, leaves, num_slices,
                                              pins=pins)
                if out is None:
                    return None
                words_t, idx_all, hit_all, first = out
                mask = self._mask_for(first, slices)
                if mask is None:
                    return None
            # Dispatch-generation snapshot of the resolved views: if
            # any other launch lands on them between here and the
            # launch gate (a racing querier's batch on the batch
            # thread — the PR-13 CPU-mesh rendezvous hazard), the gate
            # raises DispatchGenMoved and this query falls through to
            # the coalescing chained path instead of stacking a second
            # concurrent multi-device execution.
            gens = tuple((sv, sv.dispatch_gen) for sv in pins)
            sig = json.dumps(_tree_signature(shape))
            key = CompiledPlanCache.key(sig, words_t)
            fn = self._fused_plans.get_or_build(
                key, lambda: self._timed_build(
                    "fused", lambda: compile_serve_count(
                        self.mesh, json.loads(sig), len(leaves),
                        host_meta=True)))
            prof = profile.current()
            if prof is None:
                # THE fast path: async dispatch, no completion wait —
                # combine_count's device_get is the only sync point.
                def launch():
                    with jax_scope("pilosa:count_fused"):
                        return fn(words_t, idx_all, hit_all, mask)

                limbs = self._guarded_exec(sig, launch, note=False,
                                           views=pins, expect_gens=gens)
            else:
                # Profiled: bracket the dispatch with block_until_ready
                # so device_exec is the kernel's wall time and
                # readback_d2h is ONLY the D2H fetch. The bracketing
                # serializes dispatch/readback — profiling observes a
                # (slightly) slowed query, never the other way around.
                def launch():
                    with jax_scope("pilosa:count_fused"):
                        out_l = fn(words_t, idx_all, hit_all, mask)
                        out_l.block_until_ready()
                        return out_l

                with prof.phase("device_exec"):
                    limbs = self._guarded_exec(sig, launch, note=False,
                                               views=pins,
                                               expect_gens=gens)
                # Each leaf gathers ROW_SPAN containers per slice.
                prof.add_bytes("bytes_touched_hbm",
                               len(leaves) * len(slices)
                               * ROW_SPAN * CONTAINER_WORDS * 4)
                prof.add_bytes("bytes_read_back",
                               int(getattr(limbs, "nbytes", 0)))
                prof.add_slice(engine="device_fused",
                               leaves=len(leaves), slices=len(slices),
                               devices=self.mesh.devices.size
                               if self.mesh is not None else 1)
            self.stats.inc("device_dispatches")
            self.stats.inc("lone_fused")
            with profile.phase("readback_d2h"):
                return (combine_count(limbs),)
        except (DispatchGenMoved, DeviceResourceError):
            return None  # control flow / a ladder that already counted
        except Exception:  # noqa: BLE001 — fast path only; the chained
            # path re-resolves and surfaces real errors. Said aloud: a
            # fused program that never runs (a compile the chip's
            # compiler refuses) would otherwise show only as a
            # lone_fused counter that stays at zero.
            self.stats.inc("lone_fused_failed")
            _log.warning("fused lone count failed; taking the batch "
                         "path", exc_info=True)
            return None
        finally:
            self._release_pins(pins)

    def _stage_leaves_host(self, index: str, leaves, num_slices: int,
                           pins=None):
        """_stage_leaves for the fused path: identical staging and
        absent-row semantics, but the resolved gather metadata stays on
        the host — (words_t, idx_all (L, S, 16) int32, hit_all
        (L, S, 16) uint32, first_staged_view) or None. Call under _mu
        (same snapshot-consistency contract as _stage_leaves, same
        optional eviction-pin collection)."""
        staged: Dict[Tuple[str, str], tuple] = {}
        words_t, idx_l, hit_l = [], [], []
        for frame, view, row_id, _req in leaves:
            vkey = (frame, view)
            if vkey not in staged:
                sv = self.refresh(index, frame, view, num_slices)
                if sv is None:
                    self.stats.inc("fallback")
                    return None
                if sv.sparse is not None:
                    # See _stage_leaves: dense-pool-only path.
                    sv = self._demote_to_dense((index, frame, view),
                                               num_slices)
                    if sv is None:
                        self.stats.inc("fallback_sparse_format")
                        self.stats.inc("fallback")
                        return None
                if pins is not None:
                    sv.pins += 1
                    pins.append(sv)
                staged[vkey] = (sv, sv.sharded.words)
            sv, words = staged[vkey]
            i = int(np.searchsorted(sv.row_ids, np.uint64(row_id)))
            if i >= len(sv.row_ids) or sv.row_ids[i] != np.uint64(row_id):
                i = len(sv.row_ids)  # absent row: resolver yields hit=0
            idx, hit = self._leaf_host_arrays(sv, i)
            words_t.append(words)
            idx_l.append(idx)
            hit_l.append(hit)
        first = next(iter(staged.values()))[0]
        return (tuple(words_t), np.stack(idx_l), np.stack(hit_l), first)

    def _leaf_host_arrays(self, sv: StagedView, dense_id: int):
        """HOST (idx, hit) numpy pair for one leaf row, cached per view
        with the same LRU bound as the device-side idx_cache. Call
        under _mu (eviction safety, as _leaf_arrays)."""
        cached = sv.host_idx_cache.pop(dense_id, None)
        if cached is not None:
            sv.host_idx_cache[dense_id] = cached  # reinsert at MRU end
            self.stats.inc("idx_cache_hit")
            return cached
        self.stats.inc("idx_cache_miss")
        out = resolve_row_indices(sv.keys_host, dense_id, sv.slots_host)
        if len(sv.host_idx_cache) >= self._IDX_CACHE_MAX:
            sv.host_idx_cache.popitem(last=False)
        sv.host_idx_cache[dense_id] = out
        return out

    # -- sparse (sorted-array) serving ---------------------------------------

    # Sentinel: "no sparse pool involved — serve through the regular
    # dense paths". Distinct from None, which means "fold on the host".
    _SPARSE_NA = object()

    @staticmethod
    def _sparse_shape_kind(shape):
        """"leaf" for a single-leaf tree, the op name for a flat
        two-leaf op in leaf order (the shapes the sparse kernels
        cover), else None (host fold)."""
        sig = _tree_signature(shape)
        if sig == ["leaf", 0]:
            return "leaf"
        if (isinstance(sig, list) and len(sig) == 3
                and sig[0] in ("and", "or", "andnot")
                and sig[1] == ["leaf", 0] and sig[2] == ["leaf", 1]):
            return sig[0]
        return None

    def _sparse_leaf_host_arrays(self, sv: StagedView, dense_id: int):
        """_leaf_host_arrays against the SPARSE key table — same key
        packing, same resolver, its own LRU (the two pools have
        different layouts for the same row). Call under _mu."""
        cached = sv.sparse_idx_cache.pop(dense_id, None)
        if cached is not None:
            sv.sparse_idx_cache[dense_id] = cached  # reinsert at MRU
            self.stats.inc("idx_cache_hit")
            return cached
        self.stats.inc("idx_cache_miss")
        out = self._sparse_resolve(sv, dense_id)
        if len(sv.sparse_idx_cache) >= self._IDX_CACHE_MAX:
            sv.sparse_idx_cache.popitem(last=False)
        sv.sparse_idx_cache[dense_id] = out
        return out

    @staticmethod
    def _sparse_resolve(sv: StagedView, dense_id: int):
        """resolve_row_indices against the sparse pool: its sorted host
        keys through their slots, with the kept slice-offset keys.
        Call under _mu."""
        if sv.sparse_k64 is None:
            sv.sparse_k64 = slice_offset_keys(sv.sparse_keys_host)
        return resolve_row_indices(sv.sparse_keys_host, dense_id,
                                   sv.sparse_slots_host, sv.sparse_k64)

    def _sparse_overflow_dev(self, sv: StagedView):
        """The sparse pool's overflow slots on the device (a copy of the
        host's, which _refresh_sparse writes in place), made again after
        a write. Call under _mu."""
        if sv.sparse_overflow_dev is None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            if sv.sparse_overflow is None:
                sv.sparse_overflow = sparse_overflow_slots(
                    sv.sparse_cards_host)
            self.stats.inc("device_dispatches")
            sv.sparse_overflow_dev = jax.device_put(
                sv.sparse_overflow.copy(),
                NamedSharding(self.mesh, P(SLICE_AXIS)))
        return sv.sparse_overflow_dev

    def _sparse_leaf_arrays(self, sv: StagedView, dense_id: int):
        """DEVICE (idx, hit) of one row against the sparse pool, for a
        sparse src leaf (mesh._sparse_leaf_blocks), cached per view as
        _leaf_arrays' are. Call under _mu."""
        cached = sv.sparse_dev_idx_cache.get(dense_id)
        if cached is not None:
            sv.sparse_dev_idx_cache.move_to_end(dense_id)
            self.stats.inc("idx_cache_hit")
            return cached
        self.stats.inc("idx_cache_miss")
        self.stats.inc("device_dispatches")
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        idx, hit = self._sparse_resolve(sv, dense_id)
        sharding = NamedSharding(self.mesh, P(SLICE_AXIS))
        out = (jax.device_put(idx, sharding), jax.device_put(hit, sharding))
        if len(sv.sparse_dev_idx_cache) >= self._IDX_CACHE_MAX:
            sv.sparse_dev_idx_cache.popitem(last=False)
        sv.sparse_dev_idx_cache[dense_id] = out
        return out

    def _sparse_backend(self) -> str:
        """Which ss-kernel serves array×array groups: the calibrated
        Pallas-vs-XLA race winner (ops.calibrate), resolved once per
        manager. Probe kinds (sd/ds) are XLA-only regardless."""
        b = self._sparse_backend_cached
        if b is None:
            try:
                from ..ops.kernels import use_sparse_pallas

                b = "pallas" if use_sparse_pallas() else "xla"
            except Exception:  # noqa: BLE001 — calibration must never
                b = "xla"      # take serving down
            self._sparse_backend_cached = b
        return b

    def _sparse_pair_fn(self, op: str, kind: str, backend: str):
        return self._get_or_compile(
            self._sparse_fns, (op, kind, backend),
            lambda: compile_serve_count_sparse_pair(
                self.mesh, op, kind, backend=backend),
            entry="sparse")

    def _sparse_count(self, index: str, shape, leaves,
                      slices: Sequence[int], num_slices: int, sig: str):
        """Count when any leaf view holds a sorted-array pool.

        Slices partition by the per-leaf format pair into at most four
        groups — dense×dense (the existing fused program), and the
        ss/sd/ds sparse kernel classes (the device analog of the
        reference's container-type dispatch table, roaring.go:1270) —
        one masked collective per non-empty group, summed host-side.
        A single sparse leaf needs no kernel at all: the count is the
        cardinality table gathered at the row's containers.

        Returns an int count, None ("fold on the host" — unsupported
        shape or a device failure), or _SPARSE_NA ("no sparse pool
        involved": the regular dense paths serve this query).

        A view whose DENSE pool is empty (capacity 0 — every populated
        slice went sparse) routes all its slices through the sparse
        kernels: absent containers resolve hit=0 there, cardinalities
        zero out, and the inclusion–exclusion op identities stay exact.
        """
        pins: list = []
        jobs: list = []
        host_total = 0
        try:
            with self._hold("sparse_args"):
                self._use_epoch += 1
                staged: Dict[Tuple[str, str], StagedView] = {}
                svs = []
                for frame, view, row_id, _req in leaves:
                    vkey = (frame, view)
                    if vkey not in staged:
                        sv = self.refresh(index, frame, view, num_slices)
                        if sv is None:
                            # The regular path re-tries and does its
                            # own fallback accounting.
                            return self._SPARSE_NA
                        sv.pins += 1
                        pins.append(sv)
                        staged[vkey] = sv
                    svs.append(staged[vkey])
                if all(sv.sparse is None for sv in staged.values()):
                    return self._SPARSE_NA
                kind = self._sparse_shape_kind(shape)
                if kind is None or len(leaves) > 2:
                    # n-ary/nested trees only the packed-word fold
                    # serves: pin the sparse views dense and hand the
                    # query to the regular count paths. A demote that
                    # can't stage dense (budget) degrades to the host
                    # fold via the regular path's own accounting.
                    self.stats.inc("fallback_sparse_shape")
                    for vkey, sv in staged.items():
                        if sv.sparse is not None:
                            self._demote_to_dense(
                                (index, vkey[0], vkey[1]), num_slices)
                    return self._SPARSE_NA
                first = svs[0]
                mask = self._mask_for(first, slices)
                if mask is None:
                    self.stats.inc("fallback")
                    return None
                sel = mask.astype(bool)
                metas = []
                for sv, (frame, view, row_id, _req) in zip(svs, leaves):
                    i = int(np.searchsorted(sv.row_ids,
                                            np.uint64(row_id)))
                    if (i >= len(sv.row_ids)
                            or sv.row_ids[i] != np.uint64(row_id)):
                        i = len(sv.row_ids)  # absent row: hit=0
                    d_meta = (self._leaf_host_arrays(sv, i)
                              if sv.keys_host.shape[1] else None)
                    s_meta = (self._sparse_leaf_host_arrays(sv, i)
                              if sv.sparse is not None else None)
                    fmts = np.zeros(first.padded_slices, dtype=bool)
                    fmts[:len(sv.slice_formats)] = \
                        sv.slice_formats.astype(bool)
                    if sv.keys_host.shape[1] == 0:
                        fmts[:] = True  # capacity-0 dense pool: see above
                    metas.append((sv, sv.sharded, sv.sparse, d_meta,
                                  s_meta, fmts))
                if kind == "leaf":
                    sv, sh, _sp, d_meta, s_meta, fmts = metas[0]
                    sp_sel = sel & fmts
                    if s_meta is not None and sp_sel.any():
                        s_idx, s_hit = s_meta
                        per = (np.take_along_axis(sv.sparse_cards_host,
                                                  s_idx, axis=1)
                               .astype(np.int64) * s_hit)
                        host_total += int(per[sp_sel].sum())
                    d_sel = sel & ~fmts
                    if d_meta is not None and d_sel.any():
                        jobs.append(("fused", (sh.words,),
                                     np.stack([d_meta[0]]),
                                     np.stack([d_meta[1]]),
                                     d_sel.astype(np.int32)))
                else:
                    backend = self._sparse_backend()
                    _sva, sh_a, sp_a, da, sa, fa = metas[0]
                    _svb, sh_b, sp_b, db, sb, fb = metas[1]
                    groups = (("dd", sel & ~fa & ~fb),
                              ("sd", sel & fa & ~fb),
                              ("ds", sel & ~fa & fb),
                              ("ss", sel & fa & fb))
                    for gk, gsel in groups:
                        if not gsel.any():
                            continue
                        gmask = gsel.astype(np.int32)
                        if gk == "dd":
                            jobs.append(("fused",
                                         (sh_a.words, sh_b.words),
                                         np.stack([da[0], db[0]]),
                                         np.stack([da[1], db[1]]),
                                         gmask))
                            continue
                        pool_a = ((sp_a.values, sp_a.cards)
                                  if gk in ("ss", "sd")
                                  else (sh_a.words,))
                        pool_b = ((sp_b.values, sp_b.cards)
                                  if gk in ("ss", "ds")
                                  else (sh_b.words,))
                        ia, ha = sa if gk in ("ss", "sd") else da
                        ib, hb = sb if gk in ("ss", "ds") else db
                        bk = backend if gk == "ss" else "xla"
                        jobs.append(("sparse", kind, gk, bk, pool_a,
                                     pool_b, ia, ha, ib, hb, gmask))
            # Launches OUTSIDE _mu: compiles must not stall staging,
            # and the pins keep every image resident meanwhile.
            total = host_total
            for job in jobs:
                if job[0] == "fused":
                    _, words_t, idx_all, hit_all, gmask = job
                    key = CompiledPlanCache.key(sig, words_t)
                    fn = self._fused_plans.get_or_build(
                        key, lambda n=len(words_t): self._timed_build(
                            "fused",
                            lambda: compile_serve_count(
                                self.mesh, json.loads(sig), n,
                                host_meta=True)))
                    tagged = format_signature(sig, "dd")
                    args = (words_t, idx_all, hit_all, gmask)
                else:
                    _, op, gk, bk, pool_a, pool_b, ia, ha, ib, hb, \
                        gmask = job
                    fn = self._sparse_pair_fn(op, gk, bk)
                    tagged = format_signature(sig, gk)
                    args = (pool_a, pool_b, ia, ha, ib, hb, gmask)

                def launch(fn=fn, args=args):
                    with jax_scope("pilosa:count_sparse"):
                        return fn(*args)

                limbs = self._guarded_exec(tagged, launch)
                total += combine_count(limbs)
            self.stats.inc("device_dispatches", max(1, len(jobs)))
            return total
        except DeviceResourceError:
            # _guarded_exec already counted the reason-specific
            # fallback; answer "host fold".
            self.stats.inc("fallback")
            return None
        except Exception:  # noqa: BLE001 — device path must degrade
            self.stats.inc("fallback_sparse_exec")
            self.stats.inc("fallback")
            return None
        finally:
            self._release_pins(pins)

    # Bound on cached (row -> gather indices) entries per staged view:
    # each costs 2 * S * 16 * 4 bytes of HBM (~120 KB at 960 slices).
    _IDX_CACHE_MAX = 1024

    def _leaf_arrays(self, sv: StagedView, dense_id: int):
        """Device (idx, hit, coarse) for one leaf row, cached per view;
        coarse is a (starts, valid) device pair when the row stages as
        contiguous aligned whole-row runs (coarse_row_starts — the
        165-vs-125 GB/s gather-granularity fast path), else None.
        Call under _mu — the eviction below is not otherwise safe."""
        cached = sv.idx_cache.get(dense_id)
        if cached is not None:
            sv.idx_cache.move_to_end(dense_id)  # LRU, not FIFO
            self.stats.inc("idx_cache_hit")
            return cached
        self.stats.inc("idx_cache_miss")
        # One leaf metadata upload GROUP (the device_puts below issue
        # back-to-back as one logical device operation) — a unit of the
        # per-query dispatch accounting the fused path eliminates.
        self.stats.inc("device_dispatches")
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        flat_idx, hit = resolve_row_indices(sv.keys_host, dense_id,
                                            sv.slots_host)
        sharding = NamedSharding(self.mesh, P(SLICE_AXIS))
        coarse = coarse_row_starts(sv.keys_host, dense_id, sv.slots_host)
        if coarse is not None:
            starts_h, valid_h = coarse
            # Uniform layout: the row sits at ONE run index on every
            # slice (or is absent everywhere). Detected here, on host
            # keys, so the Pallas path can run the multi-slice-fetch
            # uniform kernel (coarse_count_uniform) — the scalar rides
            # the cache as a plain int (None = not uniform).
            if valid_h.all() and (starts_h == starts_h[0]).all():
                uniform = int(starts_h[0])
            elif not valid_h.any():
                uniform = -1
            else:
                uniform = None
            coarse = (jax.device_put(starts_h, sharding),
                      jax.device_put(valid_h, sharding),
                      uniform)
        out = (jax.device_put(flat_idx, sharding),
               jax.device_put(hit, sharding),
               coarse)
        if len(sv.idx_cache) >= self._IDX_CACHE_MAX:
            sv.idx_cache.popitem(last=False)
        sv.idx_cache[dense_id] = out
        return out

    def _device_cached(self, cache: "OrderedDict", key, cap: int, make):
        """Value-keyed LRU of device copies — the shared body of
        _device_mask/_device_starts. Callers on the query path hold _mu
        or run on the single batch thread; individual dict ops are
        GIL-atomic, so a rare race costs one duplicate device_put.
        The hit path is pop+reinsert, NOT get+move_to_end: between a
        get and its move_to_end a concurrent eviction (popitem below)
        can remove the key, and move_to_end on a missing key raises —
        pop is one atomic dict op, and reinserting lands the entry at
        the MRU end exactly like move_to_end would."""
        cached = cache.pop(key, None)
        if cached is not None:
            cache[key] = cached  # reinsert at the MRU end
            return cached
        dev = make()
        if len(cache) >= cap:
            cache.popitem(last=False)
        cache[key] = dev
        return dev

    def _device_mask(self, mask: np.ndarray):
        """Slice-ownership masks are few (one per cluster split) and
        reused every query — cache the device copies. Call under _mu."""
        key = mask.tobytes()
        hit = key in self._mask_cache
        self.stats.inc("mask_cache_hit" if hit else "mask_cache_miss")

        def make():
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.stats.inc("device_dispatches")
            return jax.device_put(
                mask, NamedSharding(self.mesh, P(SLICE_AXIS)))

        return self._device_cached(self._mask_cache, key, 64, make)

    def _device_starts(self, starts: np.ndarray):
        """Replicated device copy of a uniform-starts vector, cached by
        value. The uniform programs take starts as a replicated (B*L,)
        int32 arg; passing the host ndarray re-uploads it every call,
        one more transfer on the dispatch path. Herd compositions
        repeat, so a
        small LRU (keyed by the scalar values) makes the steady state
        all device-resident handles. The key carries dtype and the FULL
        shape, not just tobytes(): equal bytes from different dtypes
        (int32 vs int64 scalars) or a reshaped vector must not alias to
        one device array of the wrong type."""
        key = (starts.dtype.str, starts.shape, starts.tobytes())

        def make():
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.stats.inc("device_dispatches")
            return jax.device_put(starts, NamedSharding(self.mesh, P()))

        return self._device_cached(self._starts_cache, key, 256, make)

    def _row_counts_args(self, index: str, frame: str, view: str,
                         slices: Sequence[int], num_slices: int,
                         pins=None):
        """Snapshot the staged arrays for a per-row-counts collective:
        (row_ids, sharded, dev_mask, padded, epoch), ("empty", row_ids)
        for a rowless view, or None on fallback. The resolution half of
        _row_counts_call, shared with the SPMD descriptor plane
        (spmd.SpmdServer) so staging/mask semantics cannot diverge.
        Takes _mu. `pins` collects an eviction pin (see _count_args)."""
        with self._hold("topn_args"):
            self._use_epoch += 1
            sv = self.refresh(index, frame, view, num_slices)
            if sv is None:
                self.stats.inc("fallback")
                return None
            if sv.sparse is not None:
                # Row-counts collectives read the dense pool only:
                # pin the view dense and restage rather than folding
                # every TopN on the host forever.
                sv = self._demote_to_dense((index, frame, view),
                                           num_slices)
                if sv is None:
                    self.stats.inc("fallback_sparse_format")
                    self.stats.inc("fallback")
                    return None
            if pins is not None:
                sv.pins += 1
                pins.append(sv)
            sharded = sv.sharded  # snapshot before releasing _mu
            mask = self._mask_for(sv, slices)
            if mask is None:
                self.stats.inc("fallback")
                return None
            if len(sv.row_ids) == 0:
                return ("empty", sv.row_ids)
            padded = 1 << (len(sv.row_ids) - 1).bit_length()
            dev_mask = self._device_mask(mask)
            epoch = self._memo_epoch
        return sv.row_ids, sharded, dev_mask, padded, epoch

    def _row_counts_call(self, index: str, frame: str, view: str,
                         slices: Sequence[int], num_slices: int,
                         pins=None):
        """(row_ids, zero-arg callable -> (2, padded) DEVICE limb
        array — async; np.asarray it to materialize) or None. Identical concurrent
        calls (same staged image, mask, padding) SHARE one in-flight
        device execution — the common shape of a TopN hotspot is many
        clients asking the same frame."""
        out = self._row_counts_args(index, frame, view, slices,
                                    num_slices, pins=pins)
        if out is None:
            return None
        if len(out) == 2:  # ("empty", row_ids): rowless view
            return out[1], None
        row_ids, sharded, dev_mask, padded, epoch = out
        # Compile OUTSIDE _mu: a multi-second first-shape compile must
        # not block staging/serving of every other query.
        fn = self._get_or_compile(
            self._rowcount_fns, padded,
            lambda: compile_serve_row_counts(self.mesh, padded),
            entry="row_counts")
        key = ("rc", id(sharded.words), id(dev_mask), padded)
        memo = self._memo_get(key)
        if memo is not None:
            return row_ids, (lambda: memo)

        def call():
            # Pseudo-signature per padded width: row_counts has no
            # lowered tree, but the quarantine/recovery ladder still
            # wants a stable identity for the program family.
            # Single-flight wraps the guarded launch, never the
            # reverse: the launch gate can hold the CPU-mesh dispatch
            # lock for the whole execution, and an identical
            # concurrent caller must join the leader at the in-flight
            # table instead of queueing on that lock for a duplicate
            # run.
            def compute():
                return self._guarded_exec(
                    f"__row_counts__:{padded}",
                    lambda: fn(sharded, dev_mask), kind="row_counts")

            out = self._launch_shared(key, compute)
            self._memo_put(key, out, (sharded.words, dev_mask), epoch)
            return out

        return row_ids, call

    def _single_flight(self, key: tuple, compute):
        """Share one in-flight device execution among identical
        concurrent callers. Returns compute()'s DEVICE array — dispatch
        is async (callers block only when they fetch the value, and jax
        caches the fetched host copy on the array), so benchmarks can
        still chain outputs without a per-call sync."""
        with self._inflight_mu:
            pending = self._inflight.get(key)
            if pending is None:
                pending = [threading.Event(), None, None]
                self._inflight[key] = pending
                leader = True
            else:
                leader = False
        if not leader:
            pending[0].wait()
            with self._inflight_mu:
                self.stats.inc("inflight_shared")
            if pending[2] is not None:
                _reraise_shared("shared device query", pending[2])
            return pending[1]
        try:
            out = compute()
            pending[1] = out
            return out
        except Exception as e:
            pending[2] = e
            raise
        finally:
            with self._inflight_mu:
                self._inflight.pop(key, None)
            pending[0].set()

    def _launch_shared(self, key: tuple, compute):
        """_single_flight, and with a profile active the launch and the
        wait until its result is ready as the phase device_exec, as the
        Count leg brackets it; unprofiled, dispatch stays async."""
        prof = profile.current()
        if prof is None:
            return self._single_flight(key, compute)
        with prof.phase("device_exec"):
            out = self._single_flight(key, compute)
            out.block_until_ready()
        return out

    def _row_limbs(self, index: str, frame: str, view: str,
                   slices: Sequence[int], num_slices: int, src, finish):
        """One view's (2, padded) row-count limbs, launched or taken
        from the memo, handed still on the device to finish(row_ids,
        limbs) while the view stays pinned (limbs None for a view with
        no rows). With `src` = (shape, leaves) the counts are |row ∩
        src| (row_counts_src). Returns finish's result, or None on any
        fallback: not staged, or the device out of memory."""
        t0 = time.monotonic()
        pins: list = []
        try:
            if src is None:
                out = self._row_counts_call(index, frame, view, slices,
                                            num_slices, pins=pins)
                if out is None:
                    return None
                row_ids, call = out
                dev = None if call is None else call()
            else:
                out = self._src_counts_limbs(
                    "rcs", self._rowcount_src_fns,
                    compile_serve_row_counts_src, index, frame, view, src,
                    slices, num_slices, pins=pins)
                if out is None:
                    return None
                row_ids, _padded, dev = out
            result = finish(row_ids, dev)
            if dev is None:
                return result  # a view with no rows: nothing ran
        except DeviceResourceError:
            # Ladder exhausted (counted where it failed); degrade to
            # the host fold by answering "not staged".
            return None
        except Exception as e:  # noqa: BLE001 — classify fetch errors
            if _is_resource_exhausted(e):
                self.stats.inc("fallback_oom")
                return None
            raise
        finally:
            self._release_pins(pins)
        self.stats.inc("topn")
        self.stats.inc("query_us", int((time.monotonic() - t0) * 1e6))
        return result

    @staticmethod
    def _host_counts(row_ids, limbs):
        """_row_limbs' finish that reads every row back: (row_ids,
        counts int64), combined on the host."""
        if limbs is None:
            return row_ids, np.zeros(0, dtype=np.int64)
        with profile.phase("readback_d2h"):
            limbs = np.asarray(limbs)
        with profile.phase("rank"):
            return row_ids, combine_limbs(limbs, len(row_ids))

    def row_counts(self, index: str, frame: str, view: str,
                   slices: Sequence[int], num_slices: int):
        """Exact per-row counts over the requested slices: one masked
        popcount + segment-sum + psum. Returns (row_ids, counts int64)
        or None. num_rows pads to a power of two so growing row spaces
        recompile on doubling only."""
        return self._row_limbs(index, frame, view, slices, num_slices,
                               None, self._host_counts)

    def _top_n_tanimoto(self, index: str, frame: str, view: str, src,
                        slices: Sequence[int], num_slices: int, n: int,
                        tanimoto: int, row_ids: Sequence[int] = (),
                        attr_predicate=None
                        ) -> Optional[List[Tuple[int, int]]]:
        """Tanimoto-banded TopN from three exact device vectors — full
        per-row counts, per-row src-intersection counts, and |src| —
        then the reference's band math on the host
        (fragment.go:550-560,580-585: candidacy band on full counts,
        ceil similarity check on the intersect counts).

        All three vectors come from ONE fused collective
        (compile_serve_row_counts_tanimoto): round 2 ran 3-4 separate
        dispatches with a staged-image identity re-check between them,
        which both tripled the dispatch floor and left a window where a
        src-side write could zip vectors from different generations
        (ADVICE r2). A single program reads a single immutable snapshot
        — there is no window to re-check."""
        t0 = time.monotonic()
        pins: list = []
        try:
            out = self._src_counts_limbs(
                "tan", self._tanimoto_fns,
                compile_serve_row_counts_tanimoto,
                index, frame, view, src, slices, num_slices, pins=pins)
            if out is not None and out[2] is not None:
                with profile.phase("readback_d2h"):
                    out = out[:2] + (np.asarray(out[2]),)
        except DeviceResourceError:
            return None
        except Exception as e:  # noqa: BLE001 — classify fetch errors
            if _is_resource_exhausted(e):
                self.stats.inc("fallback_oom")
                return None
            raise
        finally:
            self._release_pins(pins)
        if out is None:
            return None
        all_rows, padded, limbs = out
        if limbs is None:
            return []  # staged view has no rows
        r = len(all_rows)
        with profile.phase("rank"):
            full = combine_limbs(limbs, r)
            inter = combine_limbs(limbs, r, start=padded)
            src_count = int(combine_limbs(limbs, 1, start=2 * padded)[0])
        self.stats.inc("topn")
        self.stats.inc("query_us", int((time.monotonic() - t0) * 1e6))
        self.stats.inc("topn_rank_host")
        with profile.phase("rank"):
            return tanimoto_rank(all_rows, full, inter, src_count, n,
                                 tanimoto, row_ids, attr_predicate)

    def _src_counts_args(self, index: str, frame: str, view: str, src,
                         slices: Sequence[int], num_slices: int,
                         pins=None, sparse_ok: bool = False):
        """Resolve a src-tree row-count request to device arrays under
        _mu: (sv, sharded, words_t, idx_t, hit_t, dev_mask, padded,
        sig, epoch), or the explicit ("empty", row_ids) marker for a
        rowless view, or None on any fallback. Shared by the
        single-host execute path
        (_src_counts_limbs) and the SPMD descriptor plane (which must
        resolve-then-gate before entering the collective). With
        `sparse_ok` (the single-host src form) a view staged all
        sorted-array is ranked where it lies (`sharded` is then a
        _SparseRanked) and a src leaf may be sparse
        (_stage_leaves); without it, as for tanimoto and the SPMD
        plane, a sparse view is demoted."""
        src_shape, src_leaves = src
        with self._hold("topn_args"):
            self._use_epoch += 1
            sv = self.refresh(index, frame, view, num_slices)
            if sv is None:
                self.stats.inc("fallback")
                return None
            if sparse_ok and _all_sparse(sv):
                pass
            elif sv.sparse is not None:
                # Row-counts collectives read the dense pool only —
                # same demote as _row_counts_args.
                sv = self._demote_to_dense((index, frame, view),
                                           num_slices)
                if sv is None:
                    self.stats.inc("fallback_sparse_format")
                    self.stats.inc("fallback")
                    return None
            if pins is not None:
                sv.pins += 1
                pins.append(sv)
            mask = self._mask_for(sv, slices)
            if mask is None:
                self.stats.inc("fallback")
                return None
            if len(sv.row_ids) == 0:
                return ("empty", sv.row_ids)
            out = self._stage_leaves(index, src_leaves, num_slices,
                                     pins=pins, sparse_ok=sparse_ok)
            if out is None:
                return None
            # Snapshot AFTER the src leaves staged: one of them may be
            # this view, refreshed again (and, unpinned, scattered in
            # place) by a write that landed since the refresh above.
            if sparse_ok and _all_sparse(sv):
                sharded = _SparseRanked(sv.sparse,
                                        self._sparse_overflow_dev(sv),
                                        max(1, sv.sparse_kmax))
                self.stats.inc("sparse_row_counts")
            else:
                sharded = sv.sharded
            words_t, idx_t, hit_t, _coarse_t, _first = out
            dev_mask = self._device_mask(mask)
            padded = 1 << (len(sv.row_ids) - 1).bit_length()
            sig = json.dumps(_tree_signature(src_shape))
            epoch = self._memo_epoch
        return (sv, sharded, words_t, idx_t, hit_t, dev_mask, padded,
                sig, epoch)

    def _src_counts_limbs(self, kind: str, fn_cache: dict, compiler,
                          index: str, frame: str, view: str, src,
                          slices: Sequence[int], num_slices: int,
                          pins=None):
        """Shared resolve+execute for the src-tree row-count programs
        (row_counts_src and the fused tanimoto): snapshot under _mu,
        compile outside it, memo/single-flight. Returns (row_ids,
        padded, limbs as a device array, not read back), (row_ids, 0,
        None) for a rowless view, or None on any fallback.

        The consistency contract lives HERE, once: the memo/in-flight
        key carries every src leaf's words identity (ADVICE r2 medium —
        an incremental refresh can swap a src frame's words while this
        view's staging stays put; without those ids a post-refresh
        query would share a pre-refresh result that excludes its own
        writes), the refs pin every id in the key, and the epoch is
        snapshotted after _stage_leaves so src-side purges are
        observed."""
        prepared = self._src_counts_args(index, frame, view, src,
                                         slices, num_slices, pins=pins,
                                         sparse_ok=kind == "rcs")
        if prepared is None:
            return None
        if prepared[0] == "empty":  # rowless view
            return prepared[1], 0, None
        (sv, sharded, words_t, idx_t, hit_t, dev_mask, padded, sig,
         epoch) = prepared
        formats = tuple("s" if isinstance(w, tuple) else "d"
                        for w in words_t)
        by_format = {"leaf_formats": formats} if "s" in formats else {}
        # Compile OUTSIDE _mu (see _row_counts_call).
        if isinstance(sharded, _SparseRanked):
            pool, overflow = sharded.pool, sharded.overflow
            ranked = (pool.keys, pool.values, pool.cards)
            kmax = np.int32(sharded.kmax)
            fn = self._get_or_compile(
                self._rowcount_src_sparse_fns,
                (sig, len(idx_t), padded, formats),
                lambda: compile_serve_row_counts_src_sparse(
                    self.mesh, json.loads(sig), len(idx_t), padded,
                    leaf_formats=formats),
                entry="row_counts_src_sparse")

            def launch():
                return fn(*ranked, overflow, kmax, words_t, idx_t, hit_t,
                          dev_mask)
        else:
            ranked = (sharded.words,)
            fn = self._get_or_compile(
                fn_cache,
                (sig, len(idx_t), padded) + tuple(by_format.values()),
                lambda: compiler(self.mesh, json.loads(sig),
                                 len(idx_t), padded, **by_format),
                entry="tanimoto" if kind == "tan" else "row_counts_src")

            def launch():
                return fn(sharded.keys, sharded.words, words_t, idx_t,
                          hit_t, dev_mask)
        key = (kind, _pool_ids(ranked), id(dev_mask), padded, sig,
               _pool_ids(words_t), tuple(id(a) for a in idx_t))
        out = self._memo_get(key)
        if out is None:
            # Single-flight outside the guarded launch (see
            # _row_counts_call): waiters must not queue on the
            # CPU-mesh dispatch lock behind the leader.
            def compute():
                return self._guarded_exec(sig, launch, kind=kind)

            out = self._launch_shared(key, compute)
            self._memo_put(key, out,
                           ranked + (dev_mask,) + _pool_refs(words_t)
                           + tuple(idx_t), epoch)
        return sv.row_ids, padded, out

    def row_counts_src(self, index: str, frame: str, view: str,
                       src_shape, src_leaves, slices: Sequence[int],
                       num_slices: int):
        """Exact per-row SRC-INTERSECTION counts: the src bitmap-op
        tree evaluates per slice and ANDs against every row in one
        fused pass (the device form of the reference's per-row
        src.intersection_count loop, fragment.go:564-608). Returns
        (row_ids, counts int64) or None."""
        return self._row_limbs(index, frame, view, slices, num_slices,
                               (src_shape, src_leaves), self._host_counts)

    def staged_format_blob(self, index: str, frames_views) -> bytes:
        """Deterministic bytes describing the PER-SHARD sparse/dense
        format picks of the given (frame, view) pairs — one
        slice_formats byte vector per view, sorted, `|`-joined, with a
        distinct marker for a not-staged view. The SPMD descriptor
        plane folds this into its program-agreement fingerprint: the
        per-device-shard format pick (PR 14) is a per-rank staging
        decision, and two ranks that picked different layouts for the
        same shard must skip the collective together rather than enter
        it with mismatched programs."""
        parts = []
        with self._hold("formats"):
            for frame, view in sorted(frames_views):
                sv = self._views.get((index, frame, view))
                if sv is None:
                    parts.append(b"\xff")  # not staged here (yet)
                else:
                    parts.append(np.ascontiguousarray(
                        sv.slice_formats).tobytes())
        return b"|".join(parts)

    def bsi_plane_counts(self, index: str, frame: str, view: str,
                         slices: Sequence[int], num_slices: int,
                         src=None):
        """Per-row counts over a ``bsi.<field>`` view as a dict
        {row_id: count} — the executor's Sum aggregate reads every
        plane, the existence row, and the sign row from ONE fused
        collective (the same masked popcount + segment-sum the TopN
        paths use; a bsi view is just another row space). With `src` =
        (shape, leaves) the counts are |row ∩ src| — the filtered-Sum
        form. Returns None on any fallback (not staged, OOM, sparse)."""
        out = (self.row_counts_src(index, frame, view, src[0],
                                   src[1], slices, num_slices)
               if src is not None else
               self.row_counts(index, frame, view, slices, num_slices))
        if out is None:
            return None
        row_ids, counts = out
        self.stats.inc("bsi_aggregate")
        return {int(r): int(n) for r, n in zip(row_ids, counts)}

    def top_n(self, index: str, frame: str, view: str,
              slices: Sequence[int], num_slices: int, n: int,
              row_ids: Sequence[int], min_threshold: int,
              src: Optional[tuple] = None,
              attr_predicate=None, tanimoto_threshold: int = 0
              ) -> Optional[List[Tuple[int, int]]]:
        """Serve TopN — every argument form — from exact device
        counts with host-side threshold/candidate/n semantics. With
        `row_ids` this is also TopN's exact phase 2
        (executor.go:273-310). With `src` = (shape, leaves) — a
        lowered bitmap-op tree — counts are |row ∩ src| (the
        reference's src path, fragment.go:564-608), one fused device
        pass instead of a per-row host intersection loop. With
        `attr_predicate`, the exact-count walk applies the host-side
        attribute filter until n rows match (bounded store lookups).
        With `tanimoto_threshold`, the reference's similarity band
        evaluates over three exact device vectors (_top_n_tanimoto).

        Deliberate deviation from the reference: `threshold` filters
        the EXACT node-local totals, not each slice's partial count.
        The reference applies MinThreshold inside every fragment
        (fragment.go:522-614), so a row spread thinly across slices can
        vanish even when its true count clears the threshold — an
        artifact of its per-fragment scan, not a semantic goal. The
        device path has the exact totals in hand and filters on those.

        Why no rank cache here (cf. reference cache.go RankCache): the
        cache exists to bound a per-row host walk — on device there is
        no per-row walk. Per-row counts are ONE fused pass over the
        pool (popcount + segment-sum + psum), the same HBM traffic as
        a single Count, regardless of row count; `n` and `threshold`
        cost nothing until the host-side sort of the (R,) totals. With
        incremental write scatters keeping the image warm, a TopN after
        writes pays no re-upload either — the two costs the rank cache
        amortizes on the host both vanish.

        Where the ranking runs is a property of the ranked view's
        shape: from RANK_DEVICE_MIN_ROWS padded rows up, phase 1 (`n`
        rows, no `row_ids`) and phase 2 (`row_ids`) run where the limbs
        lie and k rows leave the device (_rank_on_device); below it,
        and for every row (`n` 0), an attribute filter or tanimoto,
        the limbs are read back whole and ranked on the host. Counted
        a pass in `topn_rank_device` / `topn_rank_host`.

        Profile phases: the locks, the view refresh, staging, compile,
        the launch, the readback and the ranking have their own;
        `mesh_prepare` is the rest of this call, as in count().
        """
        with profile.residual("mesh_prepare"):
            if tanimoto_threshold > 0:
                if src is None:
                    return None
                return self._top_n_tanimoto(
                    index, frame, view, src, slices, num_slices,
                    0 if row_ids else n, tanimoto_threshold, row_ids,
                    attr_predicate)

            def finish(all_rows, limbs):
                if limbs is not None and self._ranks_on_device(
                        limbs.shape[1], num_slices, n, row_ids,
                        attr_predicate):
                    self.stats.inc("topn_rank_device")
                    return self._rank_on_device(all_rows, limbs, n,
                                                row_ids, min_threshold)
                return self._host_counts(all_rows, limbs)

            out = self._row_limbs(index, frame, view, slices, num_slices,
                                  src, finish)
            if out is None or isinstance(out, list):
                return out  # a fallback, or the pairs the device ranked
            self.stats.inc("topn_rank_host")
            all_rows, counts = out
            with profile.phase("rank"):
                return rank_pairs(all_rows, counts, n, row_ids,
                                  min_threshold, attr_predicate)

    @staticmethod
    def _ranks_on_device(padded: int, num_slices: int, n: int, row_ids,
                         attr_predicate) -> bool:
        """Whether top_n ranks a view of `padded` rows on the device:
        from RANK_DEVICE_MIN_ROWS rows up, for phase 2 (`row_ids`) or
        for phase 1's first `n` rows where the int32 counts are exact
        (RANK_EXACT_SLICES); never with an attribute filter, whose walk
        past n reads the host's attribute store."""
        if padded < RANK_DEVICE_MIN_ROWS or attr_predicate is not None:
            return False
        if row_ids:
            return True
        return 0 < n and rank_k(n) <= padded \
            and num_slices < RANK_EXACT_SLICES

    def _rank_on_device(self, all_rows, limbs, n: int, row_ids,
                        min_threshold: int) -> List[Tuple[int, int]]:
        """rank_pairs' answer, ranked where the (2, padded) limbs lie.
        Phase 1: compile_serve_topn_rank keeps the first n rows and k pairs
        come back. Phase 2: the candidates' positions in `all_rows`
        (ascending) are found on the host, compile_serve_topn_gather
        takes those columns, and combine_limbs recombines them. Either
        way rank_pairs sorts and trims the few rows that came back, so
        the one host form decides the order.

        What came back stays with the array while it lives
        (_readbacks): a memo hit asks phase 1 the same again, and phase
        2's candidates are phase 1's rows, whose exact counts came back
        with them, so neither goes to the device again."""
        reads = self._readbacks(limbs)
        if row_ids:
            _want, i, ok = id_positions(all_rows, row_ids)
            pos = np.unique(i[ok]).astype(np.int32)
            known = {}
            for got in list(reads.values()):
                known.update((j, c) for j, c in zip(got[0].tolist(),
                                                    got[1].tolist())
                             if j >= 0)
            if all(p in known for p in pos.tolist()):
                counts = np.asarray([known[p] for p in pos.tolist()],
                                    dtype=np.int64)
            else:
                padded, k = limbs.shape[1], rank_k(len(pos))
                fn = self._get_or_compile(
                    self._rank_fns, ("gather", padded, k),
                    lambda: compile_serve_topn_gather(self.mesh),
                    entry="topn_gather")
                counts = combine_limbs(self._run_rank(
                    fn, limbs, np.pad(pos, (0, k - len(pos)))), len(pos))
            with profile.phase("rank"):
                return rank_pairs(all_rows[pos], counts, n, row_ids,
                                  min_threshold)
        key = (len(all_rows), n, max(min_threshold, 1))
        got = reads.get(key)
        if got is None:
            padded, k = limbs.shape[1], rank_k(n)
            fn = self._get_or_compile(
                self._rank_fns, ("rank", padded, k),
                lambda: compile_serve_topn_rank(self.mesh, padded, k),
                entry="topn_rank")
            got = reads[key] = self._run_rank(fn, limbs,
                                              *map(np.int32, key))
        keep = got[0] >= 0
        with profile.phase("rank"):
            return rank_pairs(all_rows[got[0][keep]],
                              got[1][keep].astype(np.int64), n, (),
                              min_threshold)

    def _run_rank(self, fn, limbs, *args) -> np.ndarray:
        """One launch of a rank program over `limbs`, in the phase
        `rank`, and the readback of its k columns, in `readback_d2h`."""
        with profile.phase("rank"):
            with self._launch_gate():
                out = fn(limbs, *args)
            if profile.current() is not None:
                out.block_until_ready()  # unprofiled, the readback waits
        with profile.phase("readback_d2h"):
            return np.asarray(out)

    def _readbacks(self, limbs) -> dict:
        """{(live, n, floor): (2, k) readback}: what _rank_on_device
        read back of one limb array, kept by the array's id, checked by
        a weak reference and dropped with the array (the memo holds a
        memoised one until a write purges it)."""
        key = id(limbs)
        seen = self._ranked.get(key)
        if seen is None or seen[0]() is not limbs:
            seen = (weakref.ref(limbs,
                                lambda _r: self._ranked.pop(key, None)), {})
            self._ranked[key] = seen
        return seen[1]
