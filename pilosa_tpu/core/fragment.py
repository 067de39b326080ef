"""Fragment: storage + compute unit for one (frame, view, slice).

Parity with /root/reference/fragment.go: owns the durable roaring file
(snapshot region + WAL, snapshot every MAX_OP_N=2000 ops via temp+rename),
an exclusive flock, the TopN count cache with `.cache` persistence,
SHA-1 checksummed 100-row blocks for anti-entropy, and majority-consensus
block merge. The TPU twist: the fragment lazily maintains a device
FragmentPool (pilosa_tpu.ops) as its compute image; host mutations mark
it dirty and it rebuilds on next use.

Bit addressing: pos = rowID * SLICE_WIDTH + (columnID % SLICE_WIDTH)
(reference fragment.go:1511-1514); columnID is absolute, storage is
slice-local.
"""

from __future__ import annotations

import bisect
import fcntl
import functools
import hashlib
import json
import os
import tarfile
import io
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import SLICE_WIDTH, fault
from ..errors import CorruptFragmentError, WriteBackpressureError
from ..obs import StatMap
from ..obs import profile as _profile
from ..obs.log import get_logger
from ..roaring import Bitmap
from ..roaring.serialize import scan_ops
from .cache import CACHE_TYPE_RANKED, DEFAULT_CACHE_SIZE, new_cache
from .row import Row
from .wal import SNAPSHOT_US, WAL_STATS, WalCommitter, WalConfig
from .wal import FSYNC_NEVER as _FSYNC_NEVER

# Snapshot after this many WAL ops (reference fragment.go:62-65).
MAX_OP_N = 2000

# Process-wide integrity counters: corrupt loads detected, read-repairs
# completed, fragments left unrepaired (no replica). Exported as
# pilosa_integrity_* Prometheus families.
INTEGRITY_STATS = StatMap()


class IntegrityContext:
    """Shared data-integrity wiring, threaded Holder→Index→Frame→View→
    Fragment BY REFERENCE (like WalConfig) so the server can inject the
    read-repair source after the cluster client exists and every
    fragment — already-open and future — sees it through the one shared
    object.

    `repair_source(fragment) -> Optional[bytes]` returns a VERIFIED tar
    (write_to_tar format) streamed from a live replica — the server's
    closure fetches via InternalClient.fragment_data and cross-checks
    block checksums against the replica's fragment_blocks before
    handing it over — or None when no replica can supply one."""

    __slots__ = ("repair_source",)

    def __init__(self, repair_source=None):
        self.repair_source = repair_source


def bitmap_block_checksums(bm: Bitmap) -> Dict[int, bytes]:
    """Per-100-row-block SHA-1 digests of a bare bitmap — the same
    hashes Fragment.blocks() serves, computable on a parsed replica
    image or an on-disk snapshot without constructing a Fragment
    (read-repair verification, scrubber disk-vs-memory diff)."""
    out: Dict[int, bytes] = {}
    if not bm.keys:
        return out
    containers_per_block = HASH_BLOCK_SIZE * SLICE_WIDTH >> 16
    for blk in sorted({int(k) // containers_per_block for k in bm.keys}):
        lo = blk * HASH_BLOCK_SIZE * SLICE_WIDTH
        vals = bm.slice_range(lo, lo + HASH_BLOCK_SIZE * SLICE_WIDTH)
        if len(vals) == 0:
            continue
        out[blk] = hashlib.sha1(vals.astype("<u8").tobytes()).digest()
    return out


def bitmap_from_tar(tar_bytes: bytes) -> Optional[Bitmap]:
    """Extract + parse the `data` member of a write_to_tar archive
    (verifying its integrity footer when present)."""
    with tarfile.open(fileobj=io.BytesIO(tar_bytes), mode="r|") as tar:
        for member in tar:
            if member.name == "data":
                buf = tar.extractfile(member).read()
                return Bitmap.from_bytes(buf, verify=True)
    return None


class WriteCounter:
    """Writes to one view: one count for all of its fragments, moved
    by every write that moves a `Fragment.generation` (a bit write,
    an import, a restore) and by nothing else. A `View` shares one
    with the fragments it opens; a bare `Fragment` has its own.
    Only `_MutationEpoch.bump` writes `n`, under the epoch's lock:
    fragments of one view are written under their OWN `_mu`, and an
    unguarded `n += 1` on two threads can lose an update."""

    __slots__ = ("n", "__weakref__")

    def __init__(self):
        self.n = 0


class _MutationEpoch:
    """Process-wide monotonic mutation counter.

    Every completed data mutation that can change a query's answer —
    bit writes, imports/restores (log reset), index/frame create or
    delete, label or time-quantum changes — bumps it. A query-level
    memo validated by `n` (HostQueryCache.query_get) turns a repeated
    read-only Count into one dict probe + one int compare, the host
    analog of the device-side TopN memo.

    Process-wide rather than per-holder on purpose: threading a
    counter through Holder→Index→Frame→View→Fragment buys nothing but
    plumbing — multiple holders share one interpreter only in tests,
    and cross-holder bumps merely over-invalidate (a performance
    non-event), never under-invalidate. The bump is lock-guarded
    because `n += 1` on two threads can lose an update, and a LOST
    bump is the one thing that could validate a stale entry.

    `s` is the STRUCTURAL sub-counter: it moves only when the SET of
    fragments a query could touch — or how its tree lowers — changes
    (fragment/frame/index create or delete, label or time-quantum
    change). Plain bit writes move `n` alone, and `bump` pairs each
    with the written view's `WriteCounter`. That split lets a query
    memo that recorded the counters of the views it reads revalidate
    after an UNRELATED write: `s` unchanged means the fragment set is
    intact, so comparing the recorded counters is a complete
    staleness check (HostQueryCache.query_get).

    THE ORDERING RULE, stated here once: a write moves what a
    validator compares (the fragment's `generation`, the view's
    `WriteCounter`) BEFORE it moves `n`, and a validator reads `n`
    BEFORE it reads them. A reader that saw the new `n` then sees
    the moved counter; one that saw the old `n` stamps a value the
    write has already left behind. Either way a stamp never marks a
    write validated that the reader did not see."""

    __slots__ = ("n", "s", "_mu")

    def __init__(self):
        self.n = 0
        self.s = 0
        self._mu = threading.Lock()

    def bump(self, writes: WriteCounter):
        with self._mu:
            writes.n += 1
            self.n += 1

    def bump_structural(self):
        with self._mu:
            self.n += 1
            self.s += 1

    def read(self) -> tuple:
        """Consistent (n, s) snapshot. Lock-guarded so a reader racing
        bump_structural can't observe the new `n` with the old `s` —
        a torn pair recorded as a validation stamp would mark state
        validated that the stamping walk never saw."""
        with self._mu:
            return (self.n, self.s)


MUTATION_EPOCH = _MutationEpoch()

# Rows per checksummed block (reference fragment.go HashBlockSize).
HASH_BLOCK_SIZE = 100


class TopOptions:
    """Options for Fragment.top (reference fragment.go TopOptions)."""

    def __init__(self, n=0, src=None, row_ids=None, min_threshold=0,
                 filter_field="", filter_values=None, tanimoto_threshold=0):
        self.n = n
        self.src = src  # Row
        self.row_ids = row_ids or []
        self.min_threshold = min_threshold
        self.filter_field = filter_field
        self.filter_values = filter_values or []
        self.tanimoto_threshold = tanimoto_threshold


def _locked(fn):
    """Run a Fragment method under its reentrant mutex."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._mu:
            return fn(self, *args, **kwargs)
    return wrapper


def _loaded(fn):
    """_locked + demand-load: parse the storage file on first touch.

    The reference gets O(1) fragment open via mmap attach
    (fragment.go:211-229); the host-python analog is lazy parsing — a
    cold server open takes the flock and defers the read, so startup
    on a many-GB data dir is O(schema), and the first query (or the
    background warm thread) pays the parse (SURVEY.md §7 cold-start)."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._mu:
            self.ensure_loaded()
            return fn(self, *args, **kwargs)
    return wrapper


class Fragment:
    """One (frame, view, slice) of data."""

    def __init__(self, path: str, index: str, frame: str, view: str, slice_: int,
                 cache_type: str = CACHE_TYPE_RANKED,
                 cache_size: int = DEFAULT_CACHE_SIZE,
                 row_attr_store=None, stats=None,
                 wal: Optional[WalConfig] = None,
                 integrity: Optional[IntegrityContext] = None,
                 view_writes: Optional[WriteCounter] = None):
        self.path = path
        self.index = index
        self.frame = frame
        self.view = view
        self.slice = slice_
        # The owning view's write counter (View._open_fragment shares
        # its own); a fragment built without a view counts alone.
        self.view_writes = (view_writes if view_writes is not None
                            else WriteCounter())
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.row_attr_store = row_attr_store
        self.stats = stats
        self.integrity = integrity
        # Wall-clock of the scrubber's last verification pass over this
        # fragment (0 = never scrubbed), for staleness metrics.
        self.last_scrub = 0.0

        # Serializes storage/cache/WAL access across the threaded HTTP
        # server and the executor's per-slice pool (reference
        # Fragment.mu, fragment.go:69). Reentrant: set_bit -> snapshot
        # and top -> row re-enter.
        self._mu = threading.RLock()
        self.storage = Bitmap()
        self.op_n = 0
        # Durability policy ([storage] config). A bare Fragment (tests,
        # embedded use) keeps the historical write-through/no-fsync
        # behavior; server deployments get the config default (group).
        self.wal_cfg = wal if wal is not None else WalConfig(
            fsync_policy=_FSYNC_NEVER)
        self.max_op_n = (self.wal_cfg.max_op_n
                         if self.wal_cfg.max_op_n else MAX_OP_N)
        self._wal = WalCommitter(self.wal_cfg, stats=stats, path=path)
        self.cache = new_cache(cache_type, cache_size)
        self.checksums: Dict[int, bytes] = {}
        # Full blocks() result memo, keyed by mutation generation: the
        # anti-entropy walk, rebalance verification, and the scrubber
        # all hit GET /fragment/blocks repeatedly — an idle fragment
        # answers from this pair instead of re-walking every container.
        self._blocks_gen = -1
        self._blocks_cache: Optional[List[Tuple[int, bytes]]] = None
        self._op_file = None
        self._lock_file = None
        self._pending_load = True
        self._loading = False
        # Non-blocking snapshot state. `_snapshotting` flags a frozen
        # view being written in the background while ops are redirected
        # to the side `.wal` file; `_snap_gen` counts completed
        # attempts (success or failure) so forced-snapshot callers can
        # wait for "a snapshot that started after my mutation".
        self._snapshotting = False
        self._snap_thread: Optional[threading.Thread] = None
        self._snap_done = threading.Event()
        self._snap_done.set()
        self._snap_gen = 0
        self._snap_err: Optional[BaseException] = None
        self._side_file = None
        self._snap_base_op_n = 0
        self._resnap = False
        self._last_snapshot_s = 0.0
        # Materialized-row LRU, bounded: a TopN over a wide row space
        # (or a long-lived server touching many rows) must not pin one
        # Row per row id forever — each cached Row holds its segment
        # arrays. Hits re-rank (move_to_end); inserts evict the LRU
        # entry at the cap.
        self._row_cache: "OrderedDict[int, Row]" = OrderedDict()

        # Device compute image (built lazily; see `pool`).
        self._pool = None
        self._pool_row_ids = None
        self._pool_dirty = True
        self._pool_keys_host = None
        self._pool_gen = 0

        # Mutation log for incremental device-image maintenance: device
        # consumers (the fragment's own pool, the mesh serving layer)
        # record the generation they staged at and later ask
        # log_since(gen) for the bits written since — applying them as a
        # device scatter instead of re-uploading the whole pool
        # (SURVEY.md §7 "mutation on device": host-buffered batches,
        # device scatter). Entries: (op 0=set/1=clear, pos, churn) where
        # churn means the container SET changed (new container created /
        # emptied container removed) — a churned pool must rebuild, a
        # scatter can't add or drop key slots.
        self.generation = 0
        self._log: List[Tuple[int, int, bool]] = []
        self._log_base = 0
        self._log_limit = 8192

        # Replication epoch (ISSUE 18): a monotonic count of mutations
        # applied to THIS replica of the fragment, comparable across
        # replicas because every write fans out to all owners and each
        # bumps once per op — a replica whose epoch trails the max is
        # exactly that many writes behind. Durability rides a tiny
        # sidecar file (`<path>.epoch`) holding a BASE such that
        # epoch = base + op_n at load; the base is rewritten at the
        # points where op_n's meaning changes (snapshot freeze, clean
        # close, floor-raise). Crash windows can only OVER-state the
        # reloaded epoch (the sidecar lands before the snapshot
        # rename), never regress it — an overshoot merely invalidates
        # caches early, a regression would serve stale ones.
        self.epoch = 0
        self._snap_epoch_base = 0

    # -- lifecycle ---------------------------------------------------------

    @property
    def cache_path(self) -> str:
        return self.path + ".cache"

    @property
    def epoch_path(self) -> str:
        return self.path + ".epoch"

    def _read_epoch_base(self) -> int:
        """The persisted sidecar base (0 when absent/unreadable —
        pre-epoch data starts counting from its parsed op count)."""
        try:
            with open(self.epoch_path, "rb") as f:
                return max(0, int(f.read().decode().strip() or "0"))
        except (OSError, ValueError):
            return 0

    def _write_epoch_base(self, base: int) -> None:
        """Durably persist the sidecar base (tmp + fsync + rename, the
        snapshot idiom — a torn sidecar must never parse as a smaller
        number). Max-merged with the current sidecar: the base is
        monotone over a fragment's life (epoch only grows, and op_n
        never outruns the bumps it contributed), so taking the max
        makes the snapshot worker and a concurrent floor-raise
        commutative. Best-effort: a failed write only costs exactness
        at the next load, and the load-time fallback over-states."""
        base = max(int(base), self._read_epoch_base())
        tmp = self.epoch_path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(str(base).encode())
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.epoch_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def advance_epoch(self, to: int) -> int:
        """Floor-raise the replication epoch to at least `to` (anti-
        entropy / hint-replay reconcile: a replica that converged by
        block merge may have bumped fewer times than the origin —
        equalizing the counters keeps cross-replica digests comparable).
        Never regresses; persists the new base eagerly so a restart
        cannot fall back below the reconciled floor. Returns the
        resulting epoch."""
        with self._mu:
            self.ensure_loaded()
            to = int(to)
            if to <= self.epoch:
                return self.epoch
            delta = to - self.epoch
            self.epoch = to
            if self._snapshotting:
                # The in-flight worker will persist _snap_epoch_base at
                # rename; carry the raise so the reload can't fall
                # below the reconciled floor.
                self._snap_epoch_base += delta
            self._write_epoch_base(self.epoch - self.op_n)
            return self.epoch

    @_locked
    def open(self, lazy: bool = False):
        """Acquire the flock; parse now, or on first touch when `lazy`
        (the holder's directory scan opens every fragment lazily so a
        cold start is O(schema), not O(data))."""
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        # Exclusive advisory lock (reference fragment.go:191).
        self._lock_file = open(self.path + ".lock", "w")
        try:
            fcntl.flock(self._lock_file, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            self._lock_file.close()
            self._lock_file = None
            raise RuntimeError(f"fragment locked by another process: {self.path}")
        if lazy and os.path.exists(self.path) and os.path.getsize(self.path) > 0:
            self._pending_load = True
            return
        self.ensure_loaded()

    def ensure_loaded(self):
        """Parse the storage file + attach the WAL + load the cache if
        not yet done. Callers hold _mu (all public paths do).

        _pending_load clears only on FULL success: a corrupt file must
        raise on every touch, never leave the fragment looking loaded-
        but-empty — acked writes would miss the WAL and the next
        snapshot would overwrite the real data with the empty image.
        The separate _loading flag breaks the _load_cache →
        rebuild_cache → row() re-entry, not the retry.

        A storage image that fails integrity verification (footer CRC
        mismatch, rotted header, mid-log op corruption) does NOT
        crash-loop: the rotted file is quarantined aside and the
        fragment read-repairs from a live replica via the injected
        IntegrityContext.repair_source, all under _mu — concurrent
        queries block on the lock and then see the repaired image.
        Only when no replica can supply a verified copy does the touch
        raise CorruptFragmentError (a SliceUnavailableError, so the
        executor re-splits / degrades to partial), and the NEXT touch
        retries the repair."""
        if not self._pending_load or self._loading:
            return
        self._loading = True
        try:
            try:
                self._load_storage()
            except ValueError as err:
                self._recover_corrupt(err)
            self._load_cache()
            self._pending_load = False
        finally:
            self._loading = False

    def _load_storage(self):
        """Read + verify + parse the storage file, attach the append
        fd, and replay any side WAL. Raises ValueError (incl.
        CorruptSnapshotError) on a rotted image, with no append fd left
        attached."""
        if self._op_file is not None:
            # Retry after a failed attempt: drop the stale fd first.
            self.storage.op_writer = None
            try:
                self._op_file.close()
            except OSError:
                pass
            self._op_file = None
        if os.path.exists(self.path) and os.path.getsize(self.path) > 0:
            with open(self.path, "rb") as f:
                data = f.read()
            data = fault.corrupt("storage.corrupt", data, path=self.path,
                                 kind="snapshot")
            self.storage = Bitmap.from_bytes(data, truncate_torn_tail=True,
                                             verify=True)
            self.op_n = self.storage.op_n
            torn = self.storage.torn_tail_bytes
            if torn:
                # Crash mid-append left a damaged final op. The
                # acknowledged prefix is intact — drop the tail on
                # disk BEFORE attaching the append fd, or the next
                # replay would see the garbage mid-log and refuse
                # to load (kill -9 recovery, ISSUE 7 satellite).
                WAL_STATS.inc("torn_tails")
                get_logger("pilosa.fragment").warning(
                    "torn WAL tail: truncating %d trailing bytes "
                    "of %s (crash recovery)", torn, self.path)
                os.truncate(self.path, len(data) - torn)
        else:
            with open(self.path, "wb") as f:
                self.storage.write_to(f, footer=True)
        # Unbuffered append fd; ops route through the per-fragment
        # WAL committer, which write-throughs (fsync-policy never)
        # or group-commits (group/always) per [storage] config.
        self._op_file = open(self.path, "ab", buffering=0)
        self._wal.retarget(self._op_file)
        self.storage.op_writer = self._wal
        try:
            self._replay_side_wal()
        except ValueError:
            # Rotted side WAL: detach before recovery quarantines it.
            self.storage.op_writer = None
            try:
                self._op_file.close()
            except OSError:
                pass
            self._op_file = None
            raise
        # Replication epoch restore: sidecar base + every op parsed
        # beyond the snapshot region (side-WAL replay included — those
        # ops bumped the epoch before the crash). Floor-merged with any
        # in-memory value so a reload can only advance it.
        self.epoch = max(self.epoch, self._read_epoch_base() + self.op_n)

    def _recover_corrupt(self, err: BaseException):
        """Corrupt-storage recovery: stream a verified replica copy
        through the rebalance transfer format and swap it in. Caller is
        ensure_loaded, under _mu with _loading set.

        Ordering is the safety property: the rotted file is moved aside
        (as `.corrupt` evidence) only AFTER a verified replacement is
        in hand. An unrepaired fragment keeps the rot in place so every
        retry re-detects it and raises — it must never degrade to a
        fresh empty image whose next snapshot would bury the real data."""
        INTEGRITY_STATS.inc("corrupt")
        if self.stats:
            self.stats.count("corruptN", 1)
        log = get_logger("pilosa.fragment")
        log.error(
            "corrupt fragment storage %s (%s/%s/%d): %s — attempting "
            "read-repair from a replica", self.path, self.frame,
            self.view, self.slice, err)
        self.storage = Bitmap()  # drop any partially-parsed image
        self.op_n = 0
        bm = None
        src = self.integrity.repair_source if self.integrity else None
        if src is not None:
            try:
                tar_bytes = src(self)
                if tar_bytes:
                    bm = bitmap_from_tar(tar_bytes)
            except Exception as rerr:  # noqa: BLE001 — degrade, not crash
                log.error("read-repair of %s failed: %s", self.path, rerr)
        if bm is None:
            INTEGRITY_STATS.inc("unrepaired")
            raise CorruptFragmentError(
                f"fragment {self.frame}/{self.view}/{self.slice} is "
                f"corrupt and no replica supplied a verified copy: "
                f"{err}") from err
        if os.path.exists(self.path):
            try:
                os.replace(self.path, self.path + ".corrupt")
            except OSError:
                pass
        tmp = self.path + ".snapshotting"
        with open(tmp, "wb") as f:
            bm.write_to(f, footer=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)
        # Attach directly off the parsed image — re-reading through
        # _load_storage would run the freshly-written bytes back
        # through the bit-rot seam and re-detect an injected fault.
        self.storage = bm
        self.op_n = bm.op_n
        self._op_file = open(self.path, "ab", buffering=0)
        self._wal.retarget(self._op_file)
        self.storage.op_writer = self._wal
        try:
            # Locally-acked ops stranded in a side WAL survive the
            # repair: absolute positions replay idempotently onto the
            # replica image.
            self._replay_side_wal()
        except ValueError as serr:
            side_path = self.path + ".wal"
            log.error("side WAL of repaired fragment %s is also rotted "
                      "(%s): quarantined, anti-entropy will reconverge",
                      self.path, serr)
            try:
                os.replace(side_path, side_path + ".corrupt")
            except OSError:
                pass
        # Repaired state is at least as new as whatever the sidecar
        # covered; the _mark_dirty reset below bumps once more so every
        # epoch-keyed cache over this fragment invalidates.
        self.epoch = max(self.epoch, self._read_epoch_base() + self.op_n)
        self._mark_dirty(None)  # device pools/caches rebuild from scratch
        self._write_epoch_base(self.epoch - self.op_n)
        INTEGRITY_STATS.inc("repaired")
        log.warning("read-repair: %s (%s/%s/%d) restored from replica",
                    self.path, self.frame, self.view, self.slice)

    def _replay_side_wal(self):
        """Crash recovery for a background snapshot that died mid-way:
        a leftover side `.wal` file holds every op accepted after the
        snapshot's freeze point. Replay it onto the loaded image and
        splice its bytes into the main file (append + fsync BEFORE
        unlinking — dropping the side file first would lose acked ops
        to a crash in between). Ops are absolute positions, so replay
        is idempotent whether the main file is the pre-crash original
        (rename never happened) or the renamed snapshot — and even if
        a previous splice appended but didn't unlink."""
        tmp = self.path + ".snapshotting"
        if os.path.exists(tmp):
            # Snapshot temp never renamed: dead weight.
            os.unlink(tmp)
        side_path = self.path + ".wal"
        if not os.path.exists(side_path):
            return
        with open(side_path, "rb") as f:
            data = f.read()
        data = fault.corrupt("storage.corrupt", data, path=side_path,
                             kind="side-wal")
        ops, valid, torn = scan_ops(data)
        if torn:
            WAL_STATS.inc("torn_tails")
            get_logger("pilosa.fragment").warning(
                "torn side-WAL tail: dropping %d trailing bytes of %s "
                "(crash recovery)", torn, side_path)
        for typ, value in ops:
            if typ == 0:
                self.storage._add_one(value)
            else:
                self.storage._remove_one(value)
        if valid:
            self._op_file.write(data[:valid])
            os.fsync(self._op_file.fileno())
        os.unlink(side_path)
        self.op_n += len(ops)
        self.storage.op_n = self.op_n
        if ops:
            get_logger("pilosa.fragment").info(
                "replayed %d side-WAL ops into %s (crash recovery)",
                len(ops), self.path)

    def close(self):
        # Drain any in-flight background snapshot (and chained
        # re-snapshot) BEFORE tearing down fds. Joined outside _mu:
        # the worker's finish step needs the fragment lock.
        while True:
            with self._mu:
                t = self._snap_thread if self._snapshotting else None
            if t is None:
                break
            t.join()
        with self._mu:
            self.flush_cache()
            # Flush + release barrier waiters; pending buffered ops
            # reach disk (fsynced under a syncing policy).
            self._wal.detach()
            if self._op_file is not None:
                self._op_file.close()
                self._op_file = None
            self.storage.op_writer = None
            if self._lock_file is not None:
                fcntl.flock(self._lock_file, fcntl.LOCK_UN)
                self._lock_file.close()
                self._lock_file = None
            # Clean close: persist the exact epoch base (a loaded
            # fragment only — an untouched lazy fragment has nothing
            # truer than the sidecar already on disk).
            if not self._pending_load:
                self._write_epoch_base(self.epoch - self.op_n)
            # A reopened fragment must re-parse and re-attach the WAL —
            # a stale loaded flag would leave op_writer detached and
            # silently drop acked writes on the floor.
            self._pending_load = True

    # -- reads -------------------------------------------------------------

    # Bound on materialized rows held by _row_cache (see __init__).
    _ROW_CACHE_MAX = 512

    @_loaded
    def row(self, row_id: int) -> Row:
        """Materialize one row as a slice-local segment (fragment.go:332-367)."""
        cached = self._row_cache.get(row_id)
        if cached is not None:
            self._row_cache.move_to_end(row_id)  # LRU, not FIFO
            return cached
        seg = self.storage.offset_range(
            0, row_id * SLICE_WIDTH, (row_id + 1) * SLICE_WIDTH
        )
        r = Row.from_segment(self.slice, seg)
        if len(self._row_cache) >= self._ROW_CACHE_MAX:
            self._row_cache.popitem(last=False)
        self._row_cache[row_id] = r
        return r

    @_loaded
    def count(self) -> int:
        return self.storage.count()

    @_loaded
    def max_row_id(self) -> int:
        return self.storage.max() // SLICE_WIDTH

    def for_each_bit(self):
        """Yield (rowID, absolute columnID) pairs (fragment.go:471-488).

        Snapshots the positions under the mutex first — decorating a
        generator would release the lock before iteration starts, and
        concurrent writers mutate the container lists mid-walk."""
        base = self.slice * SLICE_WIDTH
        with self._mu:
            self.ensure_loaded()
            positions = self.storage.slice()
        for pos in positions:
            pos = int(pos)
            yield pos // SLICE_WIDTH, base + (pos % SLICE_WIDTH)

    # -- writes ------------------------------------------------------------

    def _pos(self, row_id: int, column_id: int) -> int:
        return row_id * SLICE_WIDTH + (column_id % SLICE_WIDTH)

    def set_bit(self, row_id: int, column_id: int,
                deadline: Optional[float] = None) -> bool:
        """Set a bit; WAL-append, update caches, wait the durability
        barrier. Returns True if the bit was newly set
        (fragment.go:371-413). `deadline` (absolute monotonic, from
        the query's ExecOptions) caps any backpressure wait."""
        self._wal_gate(deadline)
        with self._mu:
            self.ensure_loaded()
            pos = self._pos(row_id, column_id)
            churn = self.storage._find_key(pos >> 16) < 0
            changed = self.storage.add(pos)
            seq = self._wal.seq()
            self._log_append(0, pos, churn)
            self._mark_dirty(row_id)
            if changed:
                # Row-cache update happens BEFORE the snapshot trigger
                # (and the trigger itself is now only an async flip), so
                # a max_op_n=1 fragment never recounts a row mid-
                # snapshot-churn.
                self.cache.add(row_id, self.row(row_id).count())
                if self.stats:
                    self.stats.count("setN", 1)
            self._increment_op_n()
        with _profile.phase("wal_commit"):
            self._wal.wait_durable(seq)
        return changed

    def clear_bit(self, row_id: int, column_id: int,
                  deadline: Optional[float] = None) -> bool:
        self._wal_gate(deadline)
        with self._mu:
            self.ensure_loaded()
            pos = self._pos(row_id, column_id)
            changed = self.storage.remove(pos)
            seq = self._wal.seq()
            churn = changed and self.storage._find_key(pos >> 16) < 0
            self._log_append(1, pos, churn)
            self._mark_dirty(row_id)
            if changed:
                self.cache.add(row_id, self.row(row_id).count())
                if self.stats:
                    self.stats.count("clearN", 1)
            self._increment_op_n()
        with _profile.phase("wal_commit"):
            self._wal.wait_durable(seq)
        return changed

    def _pending_wal_ops(self) -> int:
        """Ops not yet covered by a completed or in-flight-frozen
        snapshot — the quantity [storage] max-wal-ops bounds. During a
        background snapshot that's the side-WAL op count; otherwise
        the whole un-snapshotted log."""
        if self._snapshotting:
            return self.op_n - self._snap_base_op_n
        return self.op_n

    def _wal_gate(self, deadline: Optional[float] = None):
        """Write backpressure: when the snapshot falls behind sustained
        ingest and the pending WAL outgrows max-wal-ops, block the
        writer (outside _mu — readers keep serving) until a snapshot
        lands or the deadline expires, then shed with
        WriteBackpressureError (HTTP 503 + Retry-After)."""
        limit = self.wal_cfg.max_wal_ops
        if limit <= 0 or self._pending_load:
            return
        if self._mu._is_owned():
            # Reentrant write (consensus merge holding _mu): blocking
            # here could never make progress — the snapshot's finish
            # step needs the lock this thread already holds.
            return
        # Unlocked int reads: the bound is advisory within one op.
        if self._pending_wal_ops() <= limit:
            return
        WAL_STATS.inc("backpressure")
        if self.stats:
            self.stats.count("wal_backpressureN", 1)
        give_up = time.monotonic() + self.wal_cfg.backpressure_deadline
        if deadline is not None:
            give_up = min(give_up, deadline)
        while True:
            with self._mu:
                if self._pending_wal_ops() <= limit:
                    return
                if not self._snapshotting:
                    self._start_snapshot()
                done = self._snap_done
            remaining = give_up - time.monotonic()
            if remaining <= 0:
                WAL_STATS.inc("backpressure_shed")
                if self.stats:
                    self.stats.count("wal_shedN", 1)
                retry = max(1.0, self._last_snapshot_s or 1.0)
                raise WriteBackpressureError(
                    f"write backpressure: {self._pending_wal_ops()} "
                    f"pending WAL ops > max-wal-ops={limit} on "
                    f"{self.frame}/{self.view}/{self.slice}",
                    retry_after_s=retry)
            done.wait(min(remaining, 0.05))

    # -- mutation log (device-image maintenance) -----------------------------

    def _log_append(self, op: int, pos: int, churn: bool):
        self.generation += 1
        self.epoch += 1
        MUTATION_EPOCH.bump(self.view_writes)
        self._log.append((op, pos, churn))
        if len(self._log) > self._log_limit:
            drop = len(self._log) - self._log_limit
            del self._log[:drop]
            self._log_base += drop

    def _log_reset(self):
        """Wholesale storage replacement (import, restore): consumers at
        any earlier generation must rebuild."""
        self.generation += 1
        self.epoch += 1
        MUTATION_EPOCH.bump(self.view_writes)
        self._log.clear()
        self._log_base = self.generation

    @_locked
    def log_since(self, gen: int) -> Optional[List[Tuple[int, int, bool]]]:
        """Mutations after generation `gen`, or None when the log no
        longer reaches back that far (pruned/reset → rebuild)."""
        if gen < self._log_base or gen > self.generation:
            return None
        return self._log[gen - self._log_base:]

    def _mark_dirty(self, row_id: Optional[int]):
        self._pool_dirty = True
        if row_id is None:
            self._log_reset()
        self.checksums.pop(
            -1 if row_id is None else row_id // HASH_BLOCK_SIZE, None
        )
        if row_id is None:
            self.checksums.clear()
            self._row_cache.clear()
        else:
            self._row_cache.pop(row_id, None)

    def _increment_op_n(self):
        self.op_n += 1
        if self.op_n > self.max_op_n and not self._snapshotting:
            # Async flip only — the writer never waits for the rewrite.
            self._start_snapshot()

    def import_bits(self, row_ids: Sequence[int], column_ids: Sequence[int]):
        """Bulk import: WAL-detached adds + forced snapshot
        (fragment.go:922-989). Goes through the non-blocking snapshot
        engine but WAITS for it to land — a bulk import's ops have no
        WAL records, so its commit barrier IS the snapshot. Concurrent
        readers and per-bit writers on other rows keep serving
        throughout (the rewrite happens off a frozen view)."""
        rows = np.asarray(row_ids, dtype=np.uint64)
        cols = np.asarray(column_ids, dtype=np.uint64)
        if rows.shape != cols.shape:
            raise ValueError("row/column mismatch")
        pos = rows * np.uint64(SLICE_WIDTH) + (cols % np.uint64(SLICE_WIDTH))
        while True:
            # Apply only when a covering snapshot can start at once: a
            # freeze taken BEFORE the bulk apply would persist a state
            # the import's barrier doesn't cover.
            with self._mu:
                self.ensure_loaded()
                if not self._snapshotting:
                    self._import_apply_locked(rows, pos)
                    target = self._snap_gen + 1
                    self._start_snapshot()
                    break
                done = self._snap_done
            done.wait()
        self._await_snapshot(target)

    def _import_apply_locked(self, rows: np.ndarray, pos: np.ndarray):
        """In-memory bulk apply. On ANY failure after partial mutation
        the fragment reloads from disk — bulk ops write no WAL records,
        so the on-disk state is still the consistent pre-import image
        and re-parsing it restores memory to match (the alternative,
        snapshotting a partially-applied import, would silently persist
        half a bulk load)."""
        self.storage.op_writer = None
        try:
            self.storage.add_many(pos)
            fault.point("storage.import_apply", path=self.path)
            self._mark_dirty(None)
            for r in np.unique(rows):
                self.cache.bulk_add(int(r), self.row(int(r)).count())
            self.cache.invalidate()
        except BaseException:
            self._reload_from_disk()
            raise
        finally:
            self.storage.op_writer = self._wal

    def _reload_from_disk(self):
        """Discard the in-memory image and re-parse the on-disk state
        (failed bulk-import recovery). The append fd and flock stay as
        they are; buffered WAL ops are flushed first so the file covers
        every accepted per-bit op."""
        self._wal.flush()
        with open(self.path, "rb") as f:
            data = f.read()
        self.storage = Bitmap.from_bytes(data)
        self.op_n = self.storage.op_n
        self.storage.op_writer = self._wal
        self._mark_dirty(None)
        self.cache = new_cache(self.cache_type, self.cache_size)
        self.rebuild_cache()

    # -- non-blocking snapshots ----------------------------------------------

    def snapshot(self):
        """Force a snapshot covering the current state and wait for it
        to land (temp + fsync + rename, spliced side WAL). Raises the
        background writer's error, if any — with the fragment left
        fully serviceable either way (the op writer is never detached;
        a failed attempt drains the side WAL back into the still-valid
        main file)."""
        with self._mu:
            self.ensure_loaded()
            target = self._request_snapshot_locked()
        self._await_snapshot(target)

    def wait_snapshot(self, timeout: Optional[float] = None) -> bool:
        """Block until no snapshot is in flight (tests/operators).
        Returns False on timeout."""
        give_up = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._mu:
                if not self._snapshotting:
                    return True
                done = self._snap_done
            left = None if give_up is None else give_up - time.monotonic()
            if left is not None and left <= 0:
                return False
            done.wait(left)

    def storage_state(self) -> dict:
        """Durability/snapshot state for /debug/vars (unlocked reads:
        a racing writer skews a counter by one, never tears)."""
        return {
            "op_n": self.op_n,
            "epoch": self.epoch,
            "max_op_n": self.max_op_n,
            "pending_wal_ops": self._pending_wal_ops(),
            "snapshotting": self._snapshotting,
            "fsync_policy": self.wal_cfg.fsync_policy,
            "wal_fsyncs": self._wal.fsyncs,
            "last_snapshot_ms": round(self._last_snapshot_s * 1e3, 3),
        }

    def _request_snapshot_locked(self) -> int:
        """Ensure a snapshot covering the CURRENT storage state will
        run; returns the generation to wait for. If one is already in
        flight its freeze predates us, so chain another behind it."""
        if self._snapshotting:
            self._resnap = True
            return self._snap_gen + 2
        self._start_snapshot()
        return self._snap_gen + 1

    def _start_snapshot(self):
        """The redirect flip (holds _mu, cost O(containers) + one
        fsync): freeze the storage view, aim the committer at a fresh
        side `.wal` file, and hand the frozen image to a background
        writer. This is the only stall a writer ever pays for a
        snapshot."""
        frozen = self.storage.freeze_view()
        self._side_file = open(self.path + ".wal", "wb", buffering=0)
        # Drains + fsyncs pending ops into the main file first, so the
        # main/side split is exactly at the freeze point.
        self._wal.retarget(self._side_file)
        self._snap_base_op_n = self.op_n
        # Epoch base the landed snapshot will persist: everything up to
        # the freeze is folded into the snapshot region, so on reload
        # epoch = this base + the (side) ops parsed beyond it.
        self._snap_epoch_base = self.epoch
        self._snapshotting = True
        self._snap_done = threading.Event()
        self._snap_thread = threading.Thread(
            target=self._snapshot_worker, args=(frozen,),
            name=f"snapshot:{self.frame}/{self.view}/{self.slice}",
            daemon=True)
        self._snap_thread.start()

    def _snapshot_worker(self, frozen: Bitmap):
        from ..obs.health import HEALTH

        start = time.monotonic()
        err: Optional[BaseException] = None
        tmp = self.path + ".snapshotting"
        try:
            # Visibility-only bracket (base=None): snapshot wall time
            # scales with fragment size so the watchdog never judges
            # it, but a disk-wedged snapshot shows up in /debug/health
            # with this thread's name and stack.
            with HEALTH.inflight("snapshot", "write"), \
                    open(tmp, "wb") as f:
                # Integrity footer rides the temp through the atomic
                # rename: every durable snapshot is born verifiable.
                frozen.write_to(f, footer=True)
                f.flush()
                fault.point("storage.fsync", path=self.path,
                            kind="snapshot")
                os.fsync(f.fileno())
            fault.point("storage.rename", path=self.path)
            # Sidecar BEFORE the rename: a crash between the two leaves
            # the new base paired with the OLD (op-richer) file, which
            # can only over-state the reloaded epoch — the safe
            # direction. The reverse order could pair the new snapshot
            # (op_n reset) with the old base and regress it.
            self._write_epoch_base(self._snap_epoch_base)
            os.replace(tmp, self.path)
        except BaseException as e:  # noqa: BLE001 — must reach _finish
            err = e
            try:
                os.unlink(tmp)
            except OSError:
                pass
        self._finish_snapshot(err, start)

    def _finish_snapshot(self, err: Optional[BaseException], start: float):
        """Splice (briefly under _mu): drain the side WAL into the new
        main file — or, on a failed attempt, back into the still-valid
        old one — reattach the committer, and wake waiters. The side
        file is unlinked only AFTER its bytes are durable in main."""
        with self._mu:
            try:
                side_path = self.path + ".wal"
                if err is None:
                    target = open(self.path, "ab", buffering=0)
                else:
                    target = self._op_file
                # Flushes buffered ops into the side file (fsynced
                # under a syncing policy), then aims appends at main.
                self._wal.retarget(target)
                self._side_file.close()
                self._side_file = None
                with open(side_path, "rb") as sf:
                    side_bytes = sf.read()
                if side_bytes:
                    target.write(side_bytes)
                    if self.wal_cfg.fsync_policy != _FSYNC_NEVER:
                        os.fsync(target.fileno())
                os.unlink(side_path)
                if err is None:
                    self._op_file.close()
                    self._op_file = target
                    self.op_n -= self._snap_base_op_n
                    self.storage.op_n = self.op_n
                # On failure op_n keeps counting from the last real
                # snapshot; the next trigger retries the whole flip.
            finally:
                elapsed = time.monotonic() - start
                self._last_snapshot_s = elapsed
                self._snap_err = err
                self._snap_gen += 1
                self._snapshotting = False
                self._snap_thread = None
                resnap, self._resnap = self._resnap, False
                # Capture THIS attempt's event before a chained
                # re-snapshot replaces _snap_done with a fresh one —
                # waiters parked on the old event must still wake.
                done_evt = self._snap_done
                if resnap:
                    self._start_snapshot()
                done_evt.set()
        SNAPSHOT_US.observe(int(elapsed * 1e6))
        WAL_STATS.inc("snapshots_failed" if err else "snapshots")
        if self.stats:
            self.stats.timing("snapshot_us", int(elapsed * 1e6))
        if err is not None:
            get_logger("fragment").warning(
                "snapshot failed: %s (%s/%s/%d): %s — side WAL drained "
                "back into main, will retry",
                self.path, self.frame, self.view, self.slice, err)
        elif elapsed > 0.1:
            # Slow-snapshot visibility (the reference's track() logging,
            # fragment.go:1012-1020) — now background wall time, not a
            # write stall a client felt.
            get_logger("fragment").info(
                "slow snapshot: %s (%s/%s/%d) took %.0f ms (background)",
                self.path, self.frame, self.view, self.slice,
                elapsed * 1e3)

    def _await_snapshot(self, target_gen: int):
        """Wait (WITHOUT holding _mu — the worker's finish step needs
        it) until `target_gen` snapshots have completed; raise the
        covering attempt's error."""
        while True:
            with self._mu:
                if self._snap_gen >= target_gen:
                    err = self._snap_err
                    break
                done = self._snap_done
            done.wait()
        if err is not None:
            raise err

    # -- TopN ---------------------------------------------------------------

    def _top_pairs(self, row_ids: Sequence[int]) -> List[Tuple[int, int]]:
        """Reference topBitmapPairs (fragment.go:627-658): rank cache when
        no ids requested; otherwise exact per-id counts, zeros dropped,
        sorted desc. Deviation: requested ids always recount from storage
        — the reference trusts cache.Get first, but threshold-gated
        RankCache.add never records drops to zero, so a cleared row would
        keep its stale count and poison TopN's exact phase 2."""
        if not row_ids:
            # cache.top() recalculates when dirty; no invalidate() needed.
            return self.cache.top()
        pairs = [(r, self.row(r).count()) for r in row_ids]
        pairs = [(r, n) for r, n in pairs if n > 0]
        pairs.sort(key=lambda p: (-p[1], p[0]))
        return pairs

    @_loaded
    def top(self, opt: TopOptions) -> List[Tuple[int, int]]:
        """Top rows by count (reference fragment.go:493-625), including
        src-intersection recount, min-threshold, attr filters, and the
        Tanimoto band."""
        pairs = self._top_pairs(opt.row_ids)
        n = 0 if opt.row_ids else opt.n

        filters = set(opt.filter_values) if (opt.filter_field and opt.filter_values) else None

        tanimoto = 0
        min_tan = max_tan = 0.0
        src_count = 0
        if opt.tanimoto_threshold > 0 and opt.src is not None:
            tanimoto = opt.tanimoto_threshold
            src_count = opt.src.count()
            min_tan = src_count * tanimoto / 100.0
            max_tan = src_count * 100.0 / tanimoto

        results: List[Tuple[int, int]] = []  # kept sorted desc by count

        def push(pair):
            bisect.insort(results, pair, key=lambda p: (-p[1], p[0]))

        for row_id, cnt in pairs:
            if cnt <= 0:
                continue
            if tanimoto > 0:
                if cnt <= min_tan or cnt >= max_tan:
                    continue
            elif cnt < opt.min_threshold:
                continue
            if filters is not None:
                if self.row_attr_store is None:
                    continue
                attr = self.row_attr_store.attrs(row_id)
                if not attr or attr.get(opt.filter_field) not in filters:
                    continue

            if n == 0 or len(results) < n:
                count = cnt
                if opt.src is not None:
                    count = opt.src.intersection_count(self.row(row_id))
                if count == 0:
                    continue
                if tanimoto > 0:
                    t = -(-100 * count // (cnt + src_count - count))  # ceil
                    if t <= tanimoto:
                        continue
                elif count < opt.min_threshold:
                    continue
                push((row_id, count))
                if n > 0 and len(results) == n and opt.src is None:
                    break
                continue

            threshold = results[-1][1]
            if threshold < opt.min_threshold or cnt < threshold:
                break
            count = opt.src.intersection_count(self.row(row_id))
            if count < threshold:
                continue
            push((row_id, count))
            results[:] = results[:n] if n else results

        return results[:n] if n else results

    # -- block checksums / anti-entropy -------------------------------------

    def _block_of(self, pos: int) -> int:
        return pos // (HASH_BLOCK_SIZE * SLICE_WIDTH)

    @_loaded
    def blocks(self) -> List[Tuple[int, bytes]]:
        """[(block_id, sha1)] for all non-empty 100-row blocks
        (fragment.go:703-767). Only blocks with live containers are
        visited — a 100-row block spans exactly 1600 containers, so
        candidate block ids come straight from the container keys (a
        sparse huge-rowID fragment must not scan the dense block range).
        Checksums are cached per block and invalidated by writes; on
        top of that the WHOLE result list is memoized per mutation
        generation, so back-to-back anti-entropy / rebalance / scrub
        passes over an idle fragment cost one int compare instead of a
        container-key walk."""
        if self._blocks_cache is not None \
                and self._blocks_gen == self.generation:
            return list(self._blocks_cache)
        out: List[Tuple[int, bytes]] = []
        if not self.storage.keys:
            self._blocks_cache = []
            self._blocks_gen = self.generation
            return out
        containers_per_block = HASH_BLOCK_SIZE * SLICE_WIDTH >> 16
        for blk in sorted({int(k) // containers_per_block for k in self.storage.keys}):
            cached = self.checksums.get(blk)
            if cached is not None:
                out.append((blk, cached))
                continue
            lo = blk * HASH_BLOCK_SIZE * SLICE_WIDTH
            vals = self.storage.slice_range(lo, lo + HASH_BLOCK_SIZE * SLICE_WIDTH)
            if len(vals) == 0:
                continue
            digest = hashlib.sha1(vals.astype("<u8").tobytes()).digest()
            self.checksums[blk] = digest
            out.append((blk, digest))
        self._blocks_cache = list(out)
        self._blocks_gen = self.generation
        return out

    @_loaded
    def checksum(self) -> bytes:
        h = hashlib.sha1()
        for _, c in self.blocks():
            h.update(c)
        return h.digest()

    @_loaded
    def block_data(self, block_id: int) -> Tuple[np.ndarray, np.ndarray]:
        """(rowIDs, slice-local columnIDs) for one block (fragment.go:783-794)."""
        lo = block_id * HASH_BLOCK_SIZE * SLICE_WIDTH
        vals = self.storage.slice_range(lo, lo + HASH_BLOCK_SIZE * SLICE_WIDTH)
        return vals // SLICE_WIDTH, vals % SLICE_WIDTH

    @_loaded
    def merge_block(self, block_id: int, data: List[Tuple[np.ndarray, np.ndarray]]):
        """Majority-consensus merge of one block across replicas
        (fragment.go:796-920). `data` holds each remote's (rowIDs, colIDs).
        Applies the consensus locally; returns per-remote (sets, clears)
        diffs as (rowIDs, colIDs) pair arrays."""
        lo = block_id * HASH_BLOCK_SIZE * SLICE_WIDTH
        hi = lo + HASH_BLOCK_SIZE * SLICE_WIDTH

        participants = [self.storage.slice_range(lo, hi)]
        for rows, cols in data:
            rows = np.asarray(rows, dtype=np.uint64)
            cols = np.asarray(cols, dtype=np.uint64)
            if rows.shape != cols.shape:
                raise ValueError("pair set mismatch")
            pos = rows * np.uint64(SLICE_WIDTH) + cols
            pos = pos[(pos >= lo) & (pos < hi)]
            participants.append(np.unique(pos))

        majority = (len(participants) + 1) // 2
        all_pos, counts = np.unique(np.concatenate(participants), return_counts=True)
        consensus = all_pos[counts >= majority]

        out = []
        for i, mine in enumerate(participants):
            sets = np.setdiff1d(consensus, mine, assume_unique=True)
            clears = np.setdiff1d(mine, consensus, assume_unique=True)
            if i == 0:
                self._apply_consensus(sets, clears)
            else:
                out.append((
                    (sets // SLICE_WIDTH, sets % SLICE_WIDTH),
                    (clears // SLICE_WIDTH, clears % SLICE_WIDTH),
                ))
        return out

    # Below this many diff bits the per-bit path wins: it preserves the
    # WAL and the incremental device log, and the bulk path's forced
    # snapshot costs more than a handful of appends.
    _CONSENSUS_BULK_MIN = 128

    def _apply_consensus(self, sets: np.ndarray, clears: np.ndarray):
        """Apply a consensus diff (storage positions) locally. Small
        diffs go bit-by-bit through set_bit/clear_bit (WAL-durable,
        device-log incremental). Large diffs — anti-entropy after real
        divergence, e.g. a replica restored from an old snapshot —
        apply as WAL-detached bulk storage ops plus one forced
        snapshot, mirroring import_bits: per bit, set_bit pays a WAL
        append, a row rematerialization, and a cache update, which on a
        100k-bit diff is minutes of Python loop against milliseconds of
        add_many/remove_many."""
        base = self.slice * SLICE_WIDTH
        if len(sets) + len(clears) < self._CONSENSUS_BULK_MIN:
            for p in sets:
                self.set_bit(int(p) // SLICE_WIDTH, base + int(p) % SLICE_WIDTH)
            for p in clears:
                self.clear_bit(int(p) // SLICE_WIDTH, base + int(p) % SLICE_WIDTH)
            return
        sets = np.asarray(sets, dtype=np.uint64)
        clears = np.asarray(clears, dtype=np.uint64)
        self.storage.op_writer = None
        try:
            if sets.size:
                self.storage.add_many(sets)
            if clears.size:
                self.storage.remove_many(clears)
        finally:
            self.storage.op_writer = self._wal
        self._mark_dirty(None)
        for r in np.unique(np.concatenate([sets, clears])
                           // np.uint64(SLICE_WIDTH)):
            self.cache.bulk_add(int(r), self.row(int(r)).count())
        self.cache.invalidate()
        # Runs under the caller's _mu (merge_block): WAITING for the
        # snapshot here would deadlock with its finish step, which
        # needs this lock. Request coverage and return — anti-entropy
        # re-converges if a crash beats the background write.
        self._request_snapshot_locked()

    # -- cache persistence ---------------------------------------------------

    @_locked
    def flush_cache(self):
        """Persist cache pairs as JSON (analog of the protobuf `.cache`
        file, fragment.go:1073-1093)."""
        if self._pending_load:
            return  # never touched: cache on disk is still current
        try:
            pairs = self.cache.top() or [(i, self.cache.get(i)) for i in self.cache.ids()]
            tmp = self.cache_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump([[int(i), int(n)] for i, n in pairs], f)
            os.replace(tmp, self.cache_path)
        except OSError:
            pass

    def _load_cache(self):
        if not os.path.exists(self.cache_path):
            # No persisted cache (fresh fragment or crash before flush):
            # rebuild from storage so TopN stays correct. Row IDs come
            # straight from the container keys (key >> 4 = rowID), so this
            # costs one count per distinct row, not a full scan.
            self.rebuild_cache()
            return
        try:
            with open(self.cache_path) as f:
                pairs = json.load(f)
        except (OSError, ValueError):
            # Corrupt/truncated cache file (e.g. crash mid-flush): rebuild
            # from storage rather than serving an empty TopN cache.
            self.rebuild_cache()
            return
        for id_, _n in pairs:
            self.cache.bulk_add(int(id_), self.row(int(id_)).count())
        self.cache.recalculate()

    @_loaded
    def rebuild_cache(self):
        """Recompute all row counts from storage (crash recovery path)."""
        row_span = SLICE_WIDTH >> 16  # containers per row; keep jax out of host paths

        row_ids = sorted({k // row_span for k in self.storage.keys})
        for r in row_ids:
            self.cache.bulk_add(r, self.row(r).count())
        if row_ids:
            self.cache.recalculate()

    # -- backup/restore ------------------------------------------------------

    @_loaded
    def write_to_tar(self, fileobj):
        """Stream data+cache as a tar archive (fragment.go:1095-1153)."""
        with tarfile.open(fileobj=fileobj, mode="w|") as tar:
            # footer=True: transfers (rebalance, read-repair) carry the
            # integrity footer, so the receiver verifies the wire bytes
            # with the same machinery that guards the disk.
            data = self.storage.to_bytes(footer=True)
            info = tarfile.TarInfo("data")
            info.size = len(data)
            info.mtime = int(time.time())
            tar.addfile(info, io.BytesIO(data))
            cache = json.dumps(
                [[int(i), int(n)] for i, n in (self.cache.top() or [])]
            ).encode()
            info = tarfile.TarInfo("cache")
            info.size = len(cache)
            info.mtime = int(time.time())
            tar.addfile(info, io.BytesIO(cache))

    def read_from_tar(self, fileobj):
        """Restore from a tar archive produced by write_to_tar
        (fragment.go:1155-1266). The data member replaces storage
        wholesale, then rides the non-blocking snapshot engine —
        applied only between snapshots (a freeze taken before the
        swap would persist the pre-restore image) and waited on
        OUTSIDE _mu."""
        with tarfile.open(fileobj=fileobj, mode="r|") as tar:
            for member in tar:
                buf = tar.extractfile(member).read()
                if member.name == "data":
                    while True:
                        with self._mu:
                            self.ensure_loaded()
                            if not self._snapshotting:
                                self.storage.op_writer = None
                                self.storage = Bitmap.from_bytes(buf)
                                self.op_n = self.storage.op_n
                                self.storage.op_writer = self._wal
                                self._mark_dirty(None)
                                target = self._snap_gen + 1
                                self._start_snapshot()
                                break
                            done = self._snap_done
                        done.wait()
                    self._await_snapshot(target)
                elif member.name == "cache":
                    with self._mu:
                        self.ensure_loaded()
                        for id_, _n in json.loads(buf or b"[]"):
                            self.cache.bulk_add(
                                int(id_), self.row(int(id_)).count())
                        self.cache.recalculate()

    # -- device compute image ------------------------------------------------

    @property
    @_loaded
    def pool(self):
        """(FragmentPool, row_ids) device image.

        Maintained INCREMENTALLY: writes that stay inside existing
        containers are folded from the mutation log into one device
        scatter (ops.pool.apply_pool_mutations) — the pool re-upload
        the reference avoids via mmap (fragment.go:371-413) is avoided
        here by never leaving the device. Only container churn (new
        container / emptied container / bulk import) forces a rebuild.
        """
        if not self._pool_dirty and self._pool is not None:
            return self._pool, self._pool_row_ids
        if self._pool is not None and self._try_pool_update():
            self._pool_dirty = False
            return self._pool, self._pool_row_ids

        import jax

        from ..ops import FragmentPool, build_pool_arrays

        keys, words, n, row_ids = build_pool_arrays(self.storage)
        self._pool = FragmentPool(
            keys=jax.device_put(keys), words=jax.device_put(words),
            n=jax.device_put(n))
        self._pool_keys_host = keys
        self._pool_row_ids = row_ids
        self._pool_gen = self.generation
        self._pool_dirty = False
        return self._pool, self._pool_row_ids

    def _try_pool_update(self) -> bool:
        """Apply logged writes to the existing device pool via scatter.
        False when the log was pruned, churned, or targets rows outside
        the staged dense table — the caller rebuilds."""
        entries = self.log_since(self._pool_gen)
        if entries is None or any(e[2] for e in entries):
            return False
        if not entries:
            return True
        from ..ops.pool import (
            apply_pool_mutations,
            fold_log_entries,
            pad_mutation_plan,
            plan_slice_mutations,
        )

        pos, val = fold_log_entries(entries)
        try:
            plan = plan_slice_mutations(
                self._pool_keys_host, self._pool_row_ids, pos, val)
        except KeyError:
            return False
        batch = pad_mutation_plan(plan, self._pool.capacity)
        self._pool = apply_pool_mutations(self._pool, *batch)
        self._pool_gen = self.generation
        return True
