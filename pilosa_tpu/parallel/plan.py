"""PQL call-tree → fused device computation.

The reference Count path materializes the intersection, then counts it
(executor.go:567-597 over roaring intersect kernels). Here a pure
bitmap-op tree — Bitmap (row on the standard view, column on the
inverse view) / Intersect / Union / Difference / Range — compiles to
ONE XLA computation per slice: gather each leaf row
as a (16, 2048) uint32 block from the fragment's HBM pool, combine
elementwise, popcount-reduce. No intermediate row ever hits HBM; this is
the "small compiler from pql.Call trees to jitted functions with a cache
keyed on tree shape" (SURVEY.md §7 hard parts).

Jit caching: the compiled function is cached on the tree's op-shape
signature (json of the nested op list), so repeated queries of the same
shape — the common case for a query workload — reuse the compiled
executable across row ids, fragments, and slices of the same pool
capacity.
"""

from __future__ import annotations

import functools
import json
import os
import time
import weakref
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import profile
from ..obs import span as obs_span
from ..ops.pool import gather_row
from ..core.view import VIEW_INVERSE, VIEW_STANDARD

# Call names evaluable on device, keyed to bitwise combiners.
_TREE_OPS = {"Intersect": "and", "Union": "or", "Difference": "andnot"}


def _tree_signature(node) -> object:
    """Canonical nested-list shape of a call tree; leaves are numbered in
    depth-first order."""
    counter = [0]

    def walk(n):
        if n[0] == "leaf":
            i = counter[0]
            counter[0] += 1
            return ["leaf", i]
        return [n[0]] + [walk(c) for c in n[1:]]

    return walk(node)


def device_slice_groups(slices, num_slices: int, n_devices: int):
    """Per-device slice-group sizes under the mesh's contiguous
    slice-axis sharding (build_sharded_index pads the slice axis to a
    multiple of the device count and NamedSharding(P(SLICE_AXIS))
    splits it into contiguous chunks). Device d therefore serves
    slices [d*chunk, (d+1)*chunk) — and since a slice carries EVERY
    row of its view (all BSI planes, the existence row, the sign row),
    any per-row combination stays device-local; only the final count
    partials cross the interconnect (psum). Returns a list of group
    sizes for the queried `slices`, devices with no queried slice
    omitted — the `?explain=true` device-group view of one mesh
    dispatch."""
    from .mesh import slice_device

    groups: Dict[int, int] = {}
    for s in slices:
        d = slice_device(s, num_slices, n_devices)
        groups[d] = groups.get(d, 0) + 1
    return [groups[d] for d in sorted(groups)]


def format_signature(sig: str, formats) -> str:
    """Tag a plan signature with the device container format(s) the
    launch serves from ("ss"/"sd"/"ds"/"dd" per slice group, or any
    descriptive tag). Sparse-path launches strike/quarantine under the
    TAGGED signature, so a broken sorted-array kernel quarantines only
    itself — the dense program for the same tree shape keeps serving."""
    if isinstance(formats, str):
        formats = (formats,)
    return sig + "|fmt=" + ",".join(formats)


def eval_tree(tree, leaves):
    """Evaluate a nested op-shape list over leaf (pool, dense_idx) pairs,
    returning the combined (16, 2048) uint32 block. Shared by the
    per-slice jitted path here and the mesh-sharded path
    (parallel.mesh); the combiner itself is ops.bitops.fold_tree, the
    same fold the Pallas tree-count kernel uses."""
    from ..ops.bitops import fold_tree

    def leaf(i):
        pool, dense_idx = leaves[i]
        return gather_row(pool, dense_idx)

    return fold_tree(tree, leaf)


@functools.lru_cache(maxsize=256)
def _compiled_count(sig: str):
    """Build + jit the evaluator for one tree shape."""
    tree = json.loads(sig)

    def count(leaves):
        blk = eval_tree(tree, leaves)
        return jax.lax.population_count(blk).astype(jnp.int32).sum()

    return jax.jit(count)


class CompiledPlanCache:
    """LRU of fused single-dispatch serving programs (the lowered
    PQL-tree → one-XLA-call fast path, mesh.compile_serve_count, host_meta).

    Keyed by (tree signature, leaf count, fragment widths — the
    per-leaf staged pool shapes — and backend): jit already keys
    compilation on argument shapes, but an unbounded miss stream (every
    novel width combination mints a program) would pin executables
    forever; the LRU bounds live programs the same way _compiled_count
    bounds the per-slice jits. The build runs under the lock so two
    racing first queries of one shape pay ONE compile (the GIL keeps
    the dict safe either way — the lock exists for the compile, exactly
    like serve._get_or_compile)."""

    def __init__(self, cap: int = 128):
        import threading
        from collections import OrderedDict

        self._mu = threading.Lock()
        self._fns: "OrderedDict[tuple, object]" = OrderedDict()
        self.cap = cap
        # Poisoned-plan set: tree signature -> monotonic expiry.
        # A signature lands here after repeated compile/runtime
        # failures (serve._note_plan_failure); while quarantined the
        # serving layer skips the device path for that shape entirely,
        # so one pathological query can't take the fast path down for
        # everyone. TTL'd: the fault may be transient (driver hiccup,
        # fixed by a restage), so the shape gets retried eventually.
        self._poisoned: Dict[str, float] = {}
        self.stats = {"hit": 0, "miss": 0, "evicted": 0,
                      "compile_us": 0, "quarantined": 0}

    @staticmethod
    def key(sig: str, words_t) -> tuple:
        """The canonical cache key for a fused count plan: tree shape,
        leaf count, per-leaf staged widths, backend. One definition so
        the serving layer and tests cannot disagree on it."""
        return (sig, len(words_t),
                tuple(tuple(w.shape) for w in words_t),
                jax.default_backend())

    def get_or_build(self, key: tuple, build):
        import time as _time

        with self._mu:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)  # LRU, not FIFO
                self.stats["hit"] += 1
                return fn
            t0 = _time.monotonic()
            fn = build()
            self.stats["compile_us"] += int(
                (_time.monotonic() - t0) * 1e6)
            if len(self._fns) >= self.cap:
                self._fns.popitem(last=False)
                self.stats["evicted"] += 1
            self._fns[key] = fn
            self.stats["miss"] += 1
            return fn

    def contains_sig(self, sig: str) -> bool:
        """Whether ANY cached plan was compiled for this tree shape —
        the EXPLAIN-surface peek (executor.explain). Key-prefix scan
        only: no staging, no mutation, no LRU reorder."""
        with self._mu:
            return any(k[0] == sig for k in self._fns)

    def quarantine(self, sig: str, ttl_s: float,
                   now: Optional[float] = None) -> None:
        """Poison a tree signature for ttl_s seconds and drop its
        cached programs (they may be the broken artifact)."""
        if now is None:
            now = time.monotonic()
        with self._mu:
            self._poisoned[sig] = now + float(ttl_s)
            self.stats["quarantined"] += 1
            for k in [k for k in self._fns if k[0] == sig]:
                del self._fns[k]

    def is_quarantined(self, sig: str,
                       now: Optional[float] = None) -> bool:
        """Whether this tree shape is currently poisoned. Expired
        entries are reaped on the way through, so an abandoned shape
        doesn't pin its entry forever."""
        if now is None:
            now = time.monotonic()
        with self._mu:
            expiry = self._poisoned.get(sig)
            if expiry is None:
                return False
            if now >= expiry:
                del self._poisoned[sig]
                return False
            return True

    def quarantined_sigs(self, now: Optional[float] = None) -> List[str]:
        """Live (unexpired) poisoned signatures — the ?explain=true /
        debug surface."""
        if now is None:
            now = time.monotonic()
        with self._mu:
            expired = [s for s, t in self._poisoned.items() if now >= t]
            for s in expired:
                del self._poisoned[s]
            return sorted(self._poisoned)

    def clear_quarantine(self, sig: Optional[str] = None) -> int:
        """Operator escape hatch: lift one signature's quarantine (or
        all of them). Returns how many entries were cleared."""
        with self._mu:
            if sig is None:
                n = len(self._poisoned)
                self._poisoned.clear()
                return n
            return 1 if self._poisoned.pop(sig, None) is not None else 0

    def __len__(self) -> int:
        return len(self._fns)


class CountPlan:
    """A compiled Count over one index's call tree. `count_slice` returns
    the slice's count, or None when this slice must fall back to the
    host path (e.g. a referenced fragment is absent)."""

    def __init__(self, holder, index: str, shape, leaves: List[tuple]):
        self.holder = holder
        self.index = index
        # leaves: [(frame, view, row_id, required)] in depth-first
        # order. required=False leaves (Range's time views) contribute
        # an empty block when the fragment is absent; a missing
        # required fragment sends the slice to the host path.
        self.leaves = leaves
        self._sig = json.dumps(_tree_signature(shape))
        self._fn = _compiled_count(self._sig)

    def count_slice(self, slice_: int) -> Optional[int]:
        staged = []
        fallback_pool = None
        for frame, view, row_id, required in self.leaves:
            frag = self.holder.fragment(self.index, frame, view, slice_)
            if frag is None:
                if required:
                    return None
                staged.append(None)
                continue
            pool, row_ids = frag.pool
            fallback_pool = (pool, row_ids)
            i = int(np.searchsorted(row_ids, np.uint64(row_id)))
            if i >= len(row_ids) or row_ids[i] != np.uint64(row_id):
                # Absent row: any dense index past the live keys gathers
                # all-zero (pool.py gather_row hit-mask).
                i = len(row_ids)
            staged.append((pool, jnp.int32(i)))
        if fallback_pool is None:
            return 0  # every leaf optional and absent
        # Absent optional fragments gather all-zero from any real pool
        # via an out-of-range dense index.
        pool, row_ids = fallback_pool
        leaf_args = tuple(
            arg if arg is not None else (pool, jnp.int32(len(row_ids)))
            for arg in staged)
        return int(self._fn(leaf_args))


class HostQueryCache:
    """Generation-validated caches for the cost-routed host path
    (VERDICT r3 #4): small-query workloads repeat, and the reference's
    own answer to repeated counts is a cache (rank/row caches,
    cache.go:126-275, fragment.go:404-408). Two layers, both validated
    against the owning fragments' mutation generations — any write
    bumps the generation, so a hit can never serve stale data (and
    generations are monotonic, so an entry stored against a snapshot
    that a concurrent write raced past can never validate later):

      - leaf blocks: (fragment, row) -> dense (16*1024,) uint64 words.
        Extraction is ~70% of a routed count's cost (measured 0.15 ms
        of 0.24 ms for an 8-leaf slice); blocks are immutable by
        convention (fold_tree never mutates operands).
      - per-slice counts: (index, sig, rows, slice) -> int. A repeat
        query re-reads only generations (~µs) instead of re-folding.

    Memory: blocks are 128 KB each, LRU-bounded (256 ≈ 32 MB); count
    entries are tuples. Thread-safe: one small lock, dict-sized ops,
    never held across extraction or folding. Lock order: a fragment's
    _mu may be held while taking this lock, never the reverse."""

    _BLOCKS_MAX = 256
    _MEMO_MAX = 4096
    _QUERY_MAX = 4096

    def __init__(self):
        import threading
        from collections import OrderedDict as _OD

        self._mu = threading.Lock()
        self._blocks: "_OD[tuple, tuple]" = _OD()
        self._memo: "_OD[tuple, tuple]" = _OD()
        self._query: "_OD[tuple, tuple]" = _OD()
        self._matrix: "_OD[tuple, tuple]" = _OD()
        self._matrix_bytes = 0
        self.stats = {"block_hit": 0, "block_miss": 0,
                      "memo_hit": 0, "memo_miss": 0,
                      "query_hit": 0, "query_miss": 0, "query_reval": 0,
                      "query_put": 0, "query_token_pairs": 0,
                      "matrix_hit": 0, "matrix_miss": 0}

    # Leaf dense-matrix cache budget (bytes): a matrix is one leaf
    # row's (S, 16384) uint64 stack — 12.6 MB at 96 slices, 126 MB at
    # the 960-slice headline — so the bound is bytes, not entries.
    # Read per call like the sibling PILOSA_TPU_HBM_BUDGET_MB knob
    # (serve.py), so tests and operators can set it after import.
    @staticmethod
    def _matrix_budget_bytes() -> int:
        return int(os.environ.get(
            "PILOSA_TPU_MATRIX_CACHE_MB", "384")) << 20

    def matrix_get(self, key: tuple, epoch: int):
        """Whole-batch dense leaf matrix ((S, 16384) uint64), validated
        by the process-wide MUTATION_EPOCH. Coarse on purpose: on a
        miss the matrix restacks from the (generation-validated) block
        layer below, so a write costs one memcpy-speed rebuild, not
        re-extraction."""
        with self._mu:
            e = self._matrix.get(key)
            if e is not None and e[0] == epoch:
                self._matrix.move_to_end(key)
                self.stats["matrix_hit"] += 1
                return e[1]
            self.stats["matrix_miss"] += 1
            return None

    def matrix_put(self, key: tuple, epoch: int, matrix) -> None:
        with self._mu:
            old = self._matrix.pop(key, None)
            if old is not None:
                self._matrix_bytes -= old[1].nbytes
            self._matrix[key] = (epoch, matrix)
            self._matrix_bytes += matrix.nbytes
            budget = self._matrix_budget_bytes()
            while (self._matrix_bytes > budget
                   and len(self._matrix) > 1):
                _, (_, m) = self._matrix.popitem(last=False)
                self._matrix_bytes -= m.nbytes

    def query_get(self, key: tuple, epoch: int, s_epoch: Optional[int] = None):
        """Whole-QUERY count memo, validated by the process-wide
        MUTATION_EPOCH (core.fragment): the warm path for a repeated
        read-only Count is one dict probe + one int compare — no
        re-lowering, no plan construction.

        Second tier: an entry stored with a TOKEN — the structural
        epoch plus the write counter of every VIEW the query reads
        (Executor._query_token), as they stood at store time —
        REVALIDATES after an epoch bump from an unrelated write: if
        the structural epoch is unchanged (no fragment/frame/index
        create/delete, no label or time-quantum change anywhere), the
        fragment SET the query touches is intact, so comparing the
        recorded counters is a complete staleness check. A pass
        re-stamps the entry at the current epoch — sound because a
        counter can't move without bumping MUTATION_EPOCH (they move
        under one lock, the counter first: `_MutationEpoch`'s ordering
        rule), so the next bump forces another comparison. Entries
        hold the counters WEAKLY; a dead ref never validates. Without
        a token (a tree `_lower_tree` declines) any bump invalidates."""
        with self._mu:
            e = self._query.get(key)
            if e is not None and e[0] == epoch:
                self._query.move_to_end(key)
                self.stats["query_hit"] += 1
                return e[1]
            tok = None if e is None or s_epoch is None else e[2]
            # A token is one entry a view: comparing it is dict-sized
            # work and stays inside this critical section.
            if tok is not None and tok[0] == s_epoch and all(
                    (w := ref()) is not None and w.n == n
                    for ref, n in tok[1]):
                self._query[key] = (epoch, e[1], tok)
                self._query.move_to_end(key)
                self.stats["query_reval"] += 1
                return e[1]
            self.stats["query_miss"] += 1
            return None

    def query_peek(self, key: tuple, epoch: int) -> bool:
        """EXPLAIN-surface probe: would a repeat of this query serve
        from the whole-query memo at the CURRENT epoch? No stats
        mutation, no LRU reorder, no token walk (a token-revalidating
        entry reports False — EXPLAIN under-promises rather than
        touching generations)."""
        with self._mu:
            e = self._query.get(key)
            return e is not None and e[0] == epoch

    def query_put(self, key: tuple, epoch: int, count: int,
                  s_epoch: Optional[int] = None,
                  view_writes: Optional[tuple] = None) -> None:
        """`view_writes`: ((WriteCounter, value), ...) read BEFORE the
        fold — a write racing the fold moved some counter past its
        recorded value, so the token can never validate (same
        pre-compute rationale as `epoch`)."""
        token = None
        if view_writes is not None and s_epoch is not None:
            token = (s_epoch,
                     tuple((weakref.ref(w), n) for w, n in view_writes))
        with self._mu:
            if token is not None:
                self.stats["query_put"] += 1
                self.stats["query_token_pairs"] += len(token[1])
            self._query[key] = (epoch, count, token)
            self._query.move_to_end(key)
            while len(self._query) > self._QUERY_MAX:
                self._query.popitem(last=False)

    def block_get(self, frag, row_id: int, gen: int):
        key = (id(frag), int(row_id))
        with self._mu:
            e = self._blocks.get(key)
            # Identity check pins against id() recycling: entries hold
            # a WEAK fragment ref (a deleted index's fragments — and
            # their multi-MB parsed storage — must stay collectable),
            # and a live weakref keeps the target's id stable.
            if e is not None and e[0]() is frag and e[1] == gen:
                self._blocks.move_to_end(key)
                self.stats["block_hit"] += 1
                return e[2]
            self.stats["block_miss"] += 1
            return None

    def block_put(self, frag, row_id: int, gen: int, words) -> None:
        key = (id(frag), int(row_id))
        with self._mu:
            self._blocks[key] = (weakref.ref(frag), gen, words)
            self._blocks.move_to_end(key)
            while len(self._blocks) > self._BLOCKS_MAX:
                self._blocks.popitem(last=False)

    def memo_get(self, key: tuple, snapshot: tuple):
        """`snapshot` holds LIVE (fragment_or_None, gen) pairs; stored
        entries hold weak refs — a dead ref never validates."""
        with self._mu:
            e = self._memo.get(key)
            if e is not None and len(e[0]) == len(snapshot) and all(
                    (f0() if f0 is not None else None) is f1 and g0 == g1
                    for (f0, g0), (f1, g1) in zip(e[0], snapshot)):
                self._memo.move_to_end(key)
                self.stats["memo_hit"] += 1
                return e[1]
            self.stats["memo_miss"] += 1
            return None

    def memo_put(self, key: tuple, snapshot: tuple, count: int) -> None:
        with self._mu:
            self._memo[key] = (tuple(
                (weakref.ref(f) if f is not None else None, g)
                for f, g in snapshot), count)
            self._memo.move_to_end(key)
            while len(self._memo) > self._MEMO_MAX:
                self._memo.popitem(last=False)


class HostCountPlan:
    """Fused HOST Count over a lowered tree — what cost-routed small
    queries run (executor._route_to_host).

    Per slice: each leaf row expands to one dense (16*1024,) uint64
    word block straight from its fragment's containers (array
    containers expand via values_to_bitmap_words), the tree folds with
    numpy bitwise ops, and ONE native C++ popcount (ops/native.py, the
    amd64-assembly stand-in, reference assembly_amd64.s:47-115) counts
    the result. No roaring containers materialize and no intermediate
    cardinalities are computed — measured ~5x faster than the
    materializing Row fold it replaces on the 8-row single-slice bench
    config (1.37 ms -> ~0.25 ms), closing most of the gap to the raw
    kernel floor that the reference's own materialize-then-count path
    (executor.go:567-597, SURVEY.md §3.2 note) never closes either.

    An absent fragment or row contributes an all-zero block (empty-row
    semantics, matching execute_bitmap_call_slice)."""

    _ZEROS = None  # shared all-zero block (read-only by convention)

    def __init__(self, holder, index: str, shape, leaves: List[tuple],
                 cache: Optional[HostQueryCache] = None):
        self.holder = holder
        self.index = index
        self.leaves = leaves
        # Numbered depth-first once (CountPlan does the same); leaves
        # were collected in the same depth-first order.
        self._sig = _tree_signature(shape)
        self.cache = cache
        if cache is not None:
            self._sig_json = json.dumps(self._sig)
            self._leaves_key = tuple(
                (f, v, int(r), bool(q)) for f, v, r, q in leaves)
            # Unique (frame, view) pairs, order-stable: the generation
            # snapshot covers each underlying fragment once.
            self._uniq_views = list(dict.fromkeys(
                (f, v) for f, v, _r, _q in leaves))

    @classmethod
    def _zeros(cls):
        if cls._ZEROS is None:
            cls._ZEROS = np.zeros(16 * 1024, dtype=np.uint64)
        return cls._ZEROS

    def _gen_snapshot(self, slice_: int) -> tuple:
        """(fragment_or_None, generation) per unique leaf view of this
        slice — the validation token for the count memo."""
        snap = []
        for frame, view in self._uniq_views:
            frag = self.holder.fragment(self.index, frame, view, slice_)
            if frag is None:
                snap.append((None, -1))
            else:
                with frag._mu:
                    snap.append((frag, frag.generation))
        return tuple(snap)

    def _leaf_words(self, frame, view, row_id, slice_):
        frag = self.holder.fragment(self.index, frame, view, slice_)
        if frag is None:
            return self._zeros()
        cache = self.cache
        with frag._mu:
            frag.ensure_loaded()
            if cache is not None:
                gen = frag.generation
                w = cache.block_get(frag, row_id, gen)
                if w is not None:
                    return w
            storage = frag.storage
            base = row_id * 16
            keys = storage.keys
            import bisect

            lo = bisect.bisect_left(keys, base)
            if lo >= len(keys) or keys[lo] >= base + 16:
                return self._zeros()
            out = np.zeros(16 * 1024, dtype=np.uint64)
            i = lo
            while i < len(keys) and keys[i] < base + 16:
                sub = keys[i] - base
                out[sub * 1024:(sub + 1) * 1024] = storage.containers[i].words()
                i += 1
        if cache is not None:
            cache.block_put(frag, row_id, gen, out)
        return out

    def count_slice(self, slice_: int) -> Optional[int]:
        from ..ops import native

        cache = self.cache
        key = snap = None
        if cache is not None:
            snap = self._gen_snapshot(slice_)
            key = (self.index, self._sig_json, self._leaves_key, slice_)
            n = cache.memo_get(key, snap)
            if n is not None:
                return n

        # fold_count folds with the ONE shared combiner the XLA and
        # Pallas paths use (bitops.fold_tree over numpy blocks), except
        # that flat trees — one op, leaves in order, i.e. the common
        # Intersect/Union count — run through the fused native
        # fold+popcount kernel in a single pass with no materialized
        # intermediate. It never mutates operands, so cached blocks are
        # safe to feed directly.
        blocks = [self._leaf_words(frame, view, row_id, slice_)
                  for frame, view, row_id, _req in self.leaves]
        n = native.fold_count(blocks, self._sig)
        if cache is not None:
            # Generations are monotonic: if a write raced between the
            # snapshot and the block reads, this entry's snapshot is
            # already stale and can never validate — stale data cannot
            # be served, only recomputed.
            cache.memo_put(key, snap, n)
        return n

    def count_slices(self, slices) -> Optional[int]:
        """Whole-batch host count: per-slice counts summed INLINE.
        Serves as the executor's batch_fn for cost-routed queries — a
        thread-pool fan-out per slice costs more than the fold itself
        once the memo layer answers most slices in microseconds. A
        declining slice (count_slice -> None, per its contract) makes
        the whole batch decline: the executor then falls back to the
        per-slice map_fn, which handles None slice-by-slice."""
        slices = list(slices)
        with obs_span("host_fold", slices=len(slices)) as sp, \
                profile.phase("host_fold"):
            prof = profile.current()
            total = 0
            for s in slices:
                t0 = time.monotonic_ns() if prof is not None else 0
                n = self.count_slice(s)
                if n is None:
                    sp.tag(declined=True)
                    return None
                total += n
                if prof is not None:
                    # Every leaf block is a dense 16x1024 uint64 read
                    # (128 KiB), memo hits aside — the fold's memory
                    # traffic, which the host roofline divides by.
                    prof.add_bytes("bytes_touched_hbm",
                                   len(self.leaves) * 16 * 1024 * 8)
                    prof.add_slice(
                        slice=int(s), engine="host_fold", count=int(n),
                        us=round((time.monotonic_ns() - t0) / 1e3, 1))
            return total


class HostMaterializePlan(HostCountPlan):
    """Fused HOST materialization of a Bitmap-ROOTED (non-Count) tree
    (VERDICT r4 #5): fold dense leaf word blocks with numpy bitwise ops
    — sharing HostCountPlan's generation-validated leaf-block cache —
    and lift the folded words straight into one roaring segment per
    slice (Bitmap.from_dense_words), instead of materializing every
    intermediate operand as roaring containers and two-pointer-merging
    them pairwise. The reference pays that per-operand materialization
    too (bitmap.go:85-134, SURVEY.md §3.2 note); here the only roaring
    object ever built is the RESULT.

    A device-program variant (fold on TPU, fetch packed words) was
    considered and rejected: the payload is the whole result bitmap, so
    readback bandwidth — not fold FLOPs — is the binding cost, and the
    host fold reads the same bytes without the H2D/D2H round trip. The
    device path's advantage is reductions (counts, TopN limbs), where
    the readback is scalars."""

    def materialize_slice(self, slice_: int):
        """The folded slice-local roaring Bitmap, or None when no leaf
        has data here (caller skips the empty segment)."""
        from ..ops.bitops import fold_tree
        from ..roaring import Bitmap as RBitmap

        blocks = []
        nonzero = False
        for frame, view, row_id, _req in self.leaves:
            w = self._leaf_words(frame, view, row_id, slice_)
            nonzero = nonzero or w is not self._zeros()
            blocks.append(w)
        if not nonzero:
            return None
        acc = fold_tree(self._sig, lambda i: blocks[i])
        return RBitmap.from_dense_words(acc, own=True)

    def _leaf_matrix(self, frame, view, row_id, slices):
        """One leaf row's dense (len(slices), 16*1024) uint64 stack,
        through the epoch-validated matrix cache; a miss restacks from
        the per-slice block cache (memcpy speed, not re-extraction)."""
        from ..core.fragment import MUTATION_EPOCH

        cache = self.cache
        key = epoch = None
        if cache is not None:
            epoch = MUTATION_EPOCH.n
            key = (self.index, frame, view, int(row_id), tuple(slices))
            m = cache.matrix_get(key, epoch)
            if m is not None:
                return m
        m = np.empty((len(slices), 16 * 1024), dtype=np.uint64)
        for j, s in enumerate(slices):
            m[j] = self._leaf_words(frame, view, row_id, s)
        if cache is not None:
            cache.matrix_put(key, epoch, m)
        return m

    def materialize_row(self, slices):
        """Fold the WHOLE slice batch in array-level numpy ops and lift
        the result into one Row: per-tree-node cost is one vectorized
        pass over (S, 16384) matrices — the same bytes/pass as the raw
        bitwise kernel — followed by ONE native per-block popcount
        (form selection + segment count cache in a single call) and
        view-backed container construction (from_dense_words own=True:
        zero copies of result words). The per-slice variant above pays
        ~10 numpy dispatches per slice; at 96 slices that tax alone
        exceeded the fold."""
        from ..core.row import Row
        from ..ops import native
        from ..ops.bitops import fold_tree
        from ..roaring.bitmap import (
            ARRAY_MAX_SIZE,
            Bitmap as RBitmap,
            Container,
            bitmap_to_values,
        )

        slices = list(slices)
        mats = [self._leaf_matrix(f, v, r, slices)
                for f, v, r, _req in self.leaves]
        # Flat tree + native lib: ONE pass computes the fold and the
        # per-block counts together (the result never gets re-read for
        # counting). Nested trees fall back to the shared numpy fold
        # plus one native count pass.
        fused = None
        sig = self._sig
        if all(c[0] == "leaf" for c in sig[1:]):
            ordered = [mats[c[1]] for c in sig[1:]]
            fused = native.fold_blocks(ordered, sig[0])
        if fused is not None:
            flat, counts = fused
            acc = flat.reshape(len(slices), 16 * 1024)
        else:
            acc = fold_tree(sig, lambda i: mats[i])  # (S, 16384)
            if any(acc is m for m in mats):
                # A degenerate shape can fold to a leaf itself;
                # containers must never view CACHED matrix memory
                # (they are handed out own=True below).
                acc = acc.copy()
            counts = native.popcnt_blocks(acc.reshape(-1))

        # Containers are built in ONE flat loop over the nonzero
        # (slice, key) pairs as python ints — numpy scalar indexing
        # per container measured ~3x the whole fold at 96 slices.
        blocks = list(acc.reshape(-1, 1024))  # views minted at C speed
        counts_l = counts.tolist()
        nz = np.flatnonzero(counts).tolist()
        # Dense containers normally keep VIEWS into `acc` (zero-copy —
        # the result Row collectively owns most of it anyway). But when
        # only a sliver of the batch is nonzero, one retained container
        # view would pin the WHOLE (S, 16384) allocation for the Row's
        # lifetime; below a quarter occupancy, copy the referenced
        # blocks and let the big buffer free.
        copy_blocks = len(nz) * 4 < len(blocks)
        per_slice = counts.reshape(-1, 16).sum(axis=1).tolist()
        row = Row()
        segments = row.segments
        seg_counts = row._counts
        cnew, bnew = Container.__new__, RBitmap.__new__
        keys_append = containers_append = None
        cur_slice = -1
        for idx in nz:
            s_j = idx >> 4
            if s_j != cur_slice:
                cur_slice = s_j
                cur = bnew(RBitmap)
                cur.keys = keys = []
                cur.containers = containers = []
                cur.op_writer = None
                cur.op_n = 0
                keys_append = keys.append
                containers_append = containers.append
                s = slices[s_j]
                segments[s] = cur
                seg_counts[s] = per_slice[s_j]
            n = counts_l[idx]
            c = cnew(Container)
            c.shared = False
            if n <= ARRAY_MAX_SIZE:
                c.array = bitmap_to_values(blocks[idx])
                c.bitmap = None
            else:
                c.array = None
                c.bitmap = blocks[idx].copy() if copy_blocks \
                    else blocks[idx]
            keys_append(idx & 15)
            containers_append(c)
        return row


def _lower_tree(holder, index: str, c, leaves: List[tuple]):
    """Call → nested shape list, collecting leaves; None if not lowerable."""
    if c.name == "Bitmap":
        from ..executor import DEFAULT_FRAME

        idx = holder.index(index)
        if idx is None:
            return None
        frame = c.args.get("frame") or DEFAULT_FRAME
        f = idx.frame(frame)
        if f is None:
            return None
        try:
            row_id, row_ok = c.uint_arg(f.row_label)
            col_id, col_ok = c.uint_arg(idx.column_label)
        except TypeError:
            return None
        if row_ok and not col_ok:
            leaves.append((frame, VIEW_STANDARD, row_id, True))
            return ["leaf"]
        if col_ok and not row_ok and f.inverse_enabled:
            # Bitmap(columnID=..) reads the inverse view; the slice set
            # stays whatever the caller mapped (matching the host path,
            # which fetches the inverse fragment per mapped slice —
            # executor.go:420-465 semantics).
            leaves.append((frame, VIEW_INVERSE, col_id, True))
            return ["leaf"]
        return None  # both/neither/disabled-inverse → host path
    if c.name == "Range":
        from ..pql.ast import Cond

        if any(isinstance(v, Cond) for v in c.args.values()):
            from ..bsi.lower import lower_cond

            return lower_cond(holder, index, c, leaves)
        return _lower_range(holder, index, c, leaves)
    op = _TREE_OPS.get(c.name)
    if op is None or not c.children:
        return None
    parts = []
    for child in c.children:
        sub = _lower_tree(holder, index, child, leaves)
        if sub is None:
            return None
        parts.append(sub)
    return [op] + parts


def _lower_range(holder, index: str, c, leaves: List[tuple]):
    """Range(frame, <row>, start, end) → OR over its time-quantum view
    leaves (executor.go:490-546 semantics: absent view fragments are
    empty, not errors — the leaves are optional)."""
    from ..core import views_by_time_range
    from ..executor import DEFAULT_FRAME, parse_time

    idx = holder.index(index)
    if idx is None:
        return None
    frame = c.args.get("frame") or DEFAULT_FRAME
    f = idx.frame(frame)
    if f is None:
        return None
    try:
        row_id, ok = c.uint_arg(f.row_label)
    except TypeError:
        return None  # invalid arg type → host path owns error reporting
    start, end = c.args.get("start"), c.args.get("end")
    if not ok or not isinstance(start, str) or not isinstance(end, str):
        return None
    try:
        views = views_by_time_range(VIEW_STANDARD, parse_time(start),
                                    parse_time(end), f.time_quantum)
    except ValueError:
        return None
    if not views or len(views) > 32:
        # No quantum → host path (returns empty). A very wide unaligned
        # cover (fine quanta) would jit a huge fused OR and churn the
        # compile cache; incremental host unions win there.
        return None
    for v in views:
        leaves.append((frame, v, row_id, False))
    if len(views) == 1:
        return ["leaf"]
    return ["or"] + [["leaf"]] * len(views)


def compile_count_plan(holder, index: str, tree) -> Optional[CountPlan]:
    """Compile Count's child tree for fused device eval; None when the
    tree doesn't qualify (unknown frames, non-integer args, a Bitmap
    with both/neither of row and column args, columnID without
    inverse_enabled, over-wide Range covers, ...)."""
    leaves: List[tuple] = []
    shape = _lower_tree(holder, index, tree, leaves)
    if shape is None or shape == ["leaf"] and not leaves:
        return None
    return CountPlan(holder, index, shape, leaves)
