"""Device-mesh execution: slices sharded across TPU devices, reductions
over ICI collectives.

This is the TPU-native replacement for the reference's cluster mapReduce
(executor.go:1103-1163): instead of HTTP fan-out + coordinator merge,
all slices of an index live stacked in HBM across a
`jax.sharding.Mesh`, one shard_map'd computation evaluates the query on
every device's local slices, and Count / per-row totals reduce with
`lax.psum` over the mesh axis (ICI), never leaving the device fabric.

Layout: a ShardedIndex stacks per-slice FragmentPools into
  keys  (S, C)        int32   — C = max container capacity over slices
  words (S, C, 2048)  uint32  — bitmap-form containers
sharded on the leading (slice) axis. Container keys use GLOBAL dense row
indices (one row-id table for the whole index), so a row's dense index is
the same on every shard and query row-lookups broadcast as scalars.

TopN here is EXACT: per-row popcounts segment-summed on every shard,
psum'd over the mesh, then a replicated lax.top_k — no rank-cache
approximation pass (closes the reference's two-phase TopN refetch,
executor.go:273-310, with one collective).
"""

from __future__ import annotations

import json
from functools import partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .. import SLICE_WIDTH
from ..obs import get_logger, profile
from ..obs import span as obs_span
from ..ops.pool import CONTAINER_WORDS, INVALID_KEY, ROW_SPAN, FragmentPool
from .plan import _tree_signature, eval_tree

SLICE_AXIS = "slices"


def slice_device(slice_: int, num_slices: int, n_devices: int) -> int:
    """Which mesh device serves a slice under the P(SLICE_AXIS)
    sharding every staged pool uses: the slice axis pads to a multiple
    of the device count (build_sharded_index / build_sparse_sharded)
    and NamedSharding splits it into contiguous chunks — a CONSISTENT
    placement across every view of an index at a given slice count.
    Because a slice holds every row of its view — all BSI magnitude
    planes, the existence row, the sign row — any per-row/ per-plane
    combination is device-local by construction; only count partials
    ever cross the interconnect (psum). Placement moves ONLY when the
    padded slice count changes (index growth past a pad boundary or a
    mesh resize), which forces a restage anyway."""
    n_dev = max(1, int(n_devices))
    s_pad = -(-max(1, int(num_slices)) // n_dev) * n_dev
    return int(slice_) // (s_pad // n_dev)


class ShardedIndex(NamedTuple):
    """One frame/view's fragments, stacked and mesh-sharded."""

    keys: jax.Array   # (S, C) int32, INVALID_KEY padded
    words: jax.Array  # (S, C, CONTAINER_WORDS) uint32

    @property
    def num_slices(self) -> int:
        return self.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]


def _stage_chunk_bytes() -> int:
    """H2D staging chunk size (PILOSA_TPU_STAGE_CHUNK_MB env, default
    64 MB): below the chunk size a shard moves as ONE device_put;
    above it, as a pipeline of chunk-sized device_puts with host
    packing double-buffered against the in-flight transfer
    (_stage_pipeline). A chunk as large as the shard means the
    single-put path — zero pipelining, pack time and transfer time
    strictly serial. 64 MB is small enough that typical shards cut
    into several chunks (the headline ~1 GB pool: 16) and large enough
    that a put's dispatch is small beside its transfer."""
    import os

    try:
        mb = int(os.environ.get("PILOSA_TPU_STAGE_CHUNK_MB", "64"))
    except ValueError:
        mb = 64
    return max(1, mb) << 20


def _stage_pipeline(pack_range, ranges, dev, on_chunk=None):
    """Pipelined chunk transfers for one shard: pack || transfer.

    ranges is the ordered [lo, hi) chunk list. A producer thread packs
    chunk i+1 while chunk i's device_put dispatches and its async
    transfer streams; because device_put never blocks, the in-flight
    transfers additionally overlap device EXECUTION of already-resident
    work (bench's staging_bandwidth section proves the overlap via the
    stage_h2d/device_exec profile phases). The queue depth bounds host
    memory at two packed-but-unshipped chunks. A single-chunk shard
    skips the thread — no pipeline exists to win there.

    on_chunk(nbytes) fires after each chunk's put dispatches: the
    per-chunk cumulative byte accounting (every chunk counts toward
    bytes_staged, not just the final one). Returns the device pieces
    in range order; a pack error re-raises here, a device_put error
    propagates with the producer thread parked (daemon, bounded by the
    queue) for the fallback path to proceed past."""
    if len(ranges) == 1:
        host = pack_range(*ranges[0])
        piece = jax.device_put(host, dev)
        if on_chunk is not None:
            on_chunk(host.nbytes)
        return [piece]
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=2)

    def produce():
        try:
            for lo, hi in ranges:
                q.put(("ok", pack_range(lo, hi)))
        except BaseException as e:  # noqa: BLE001 — surfaced on the
            # consumer side; the packer must not die silently
            q.put(("err", e))

    threading.Thread(target=produce, daemon=True, name="h2d-pack").start()
    from ..obs.health import HEALTH

    # Visibility-only bracket (base=None): staging time scales with
    # the slab, so the watchdog never judges it — but a wedged
    # device_put shows this thread pinned in /debug/health.
    with HEALTH.inflight("h2d-pack", "stage"):
        pieces = []
        for _ in ranges:
            tag, payload = q.get()
            if tag == "err":
                raise payload
            pieces.append(jax.device_put(payload, dev))
            if on_chunk is not None:
                on_chunk(payload.nbytes)
    return pieces


_FOLD_CHUNK = None


def _fold_chunk_fn():
    """Jitted donated dynamic_update_slice: folds one transferred chunk
    into the shard buffer IN PLACE (donation), so chunked assembly
    peaks at shard + one chunk of HBM — a jnp.concatenate would
    transiently hold shard + all chunks (2x the pool). CPU backends
    don't implement donation; the fallback copy is fine at test scale."""
    global _FOLD_CHUNK
    if _FOLD_CHUNK is None:
        donate = (0,) if jax.default_backend() != "cpu" else ()
        def fold_chunk(buf, piece, off):
            return lax.dynamic_update_slice(buf, piece, (off, 0, 0))

        _FOLD_CHUNK = jax.jit(fold_chunk, donate_argnums=donate)
    return _FOLD_CHUNK


def _assemble_shard(pieces: List, offs: List[int], shard_shape, dev):
    """One device shard from its transferred chunk pieces."""
    if len(pieces) == 1:
        return pieces[0]
    import contextlib

    ctx = jax.default_device(dev) if dev is not None \
        else contextlib.nullcontext()
    with ctx:
        buf = jnp.zeros(shard_shape, dtype=jnp.uint32)
    fold = _fold_chunk_fn()
    for p, off in zip(pieces, offs):
        buf = fold(buf, p, np.int32(off))
    return buf


def build_sharded_index(bitmaps: Sequence, mesh: Optional[Mesh] = None,
                        capacity: Optional[int] = None,
                        with_host_keys: bool = False,
                        stats_out: Optional[dict] = None,
                        row_ids: Optional[np.ndarray] = None):
    """Stack per-slice host bitmaps into a ShardedIndex.

    bitmaps[s] is the slice-s roaring Bitmap (or None for an absent
    fragment). Returns (ShardedIndex, row_ids): row_ids is the GLOBAL
    sorted uint64 row-id table shared by all shards. The slice count is
    padded up to a multiple of the mesh axis size. with_host_keys=True
    appends the packed (S_padded, cap) int32 numpy keys to the return —
    consumers needing them must take this copy, NOT np.asarray the
    device keys, which fails on a multi-process mesh (non-addressable
    shards).

    Staging is the cold-start hard part (SURVEY §7: the reference gets
    O(1) open via mmap, fragment.go:211-229; a device needs explicit
    H2D). Three levers here:
      - words are packed PER ADDRESSABLE SHARD and device_put straight
        to the owning device (no whole-pool transfer to device 0 and
        re-distribution — on a multi-host mesh each process packs and
        ships only its own slices);
      - each shard moves as a pipeline of chunk-sized device_puts
        (_stage_chunk_bytes, default 64 MB) with a dedicated packer
        thread (_stage_pipeline): chunk i+1 packs WHILE chunk i's
        transfer streams, so the wall cost approaches
        max(pack, transfer) instead of their sum;
      - nothing blocks on completion: the returned arrays are async
        futures and the first query's compile proceeds while the
        transfer streams — in-flight chunks also overlap device
        execution of already-resident work. stats_out (if given) gets
        the host-side dispatch seconds, byte counts, and the
        chunk-count proof of which path ran, for /debug/vars.
    """
    import time as _time

    n_dev = mesh.shape[SLICE_AXIS] if mesh is not None else 1
    s = max(1, len(bitmaps))
    s_pad = -(-s // n_dev) * n_dev

    # Global dense row table — injectable (row_ids=) so a dual-format
    # stager can number rows over ALL slices once and hand both the
    # dense and the sparse pool the same table (a per-pool np.unique
    # would give the two pools different dense indices for one row).
    if row_ids is None:
        all_rows = [np.asarray(b.keys, dtype=np.uint64) >> np.uint64(4)
                    for b in bitmaps if b is not None and len(b.keys)]
        row_ids = (np.unique(np.concatenate(all_rows)) if all_rows
                   else np.empty(0, dtype=np.uint64))

    counts = [len(b.keys) if b is not None else 0 for b in bitmaps]
    # capacity=0 is an explicit "no dense containers anywhere" (a pure
    # sparse-format view staging an empty dense pool so every consumer
    # of sv.sharded keeps a real array to hold on to).
    cap = capacity if capacity is not None else max(1, max(counts,
                                                           default=1))
    # Round capacity up to a ROW_SPAN multiple: the coarse-gather
    # serving programs view the pool as (S, cap/16, 16*W) whole-row
    # runs, which needs 16 | cap. Cost: < 16 padded containers/slice.
    cap = -(-cap // ROW_SPAN) * ROW_SPAN

    t0 = _time.monotonic()
    h2d_sp = obs_span("h2d", slices=s_pad)
    h2d_ph = profile.phase("stage_h2d").start()
    # Keys (small, s_pad*cap*4 B) pack fully on every host; the sorted
    # container order is kept for the words pack below.
    keys = np.full((s_pad, cap), INVALID_KEY, dtype=np.int32)
    orders: List[Optional[np.ndarray]] = [None] * s_pad
    for si, b in enumerate(bitmaps):
        if b is None or not len(b.keys):
            continue
        real = np.asarray(b.keys, dtype=np.uint64)
        dense = np.searchsorted(row_ids, real >> np.uint64(4))
        k = (dense * ROW_SPAN
             + (real & np.uint64(15)).astype(np.int64)).astype(np.int32)
        order = np.argsort(k)
        keys[si, : len(k)] = k[order]
        orders[si] = order

    def pack_range(lo: int, hi: int) -> np.ndarray:
        buf = np.zeros((hi - lo, cap, CONTAINER_WORDS), dtype=np.uint32)
        for si in range(lo, min(hi, len(bitmaps))):
            order = orders[si]
            if order is None:
                continue
            b = bitmaps[si]
            row = buf[si - lo]
            for j, ci in enumerate(order):
                row[j] = b.containers[ci].words().view(np.uint32)
        return buf

    slice_bytes = cap * CONTAINER_WORDS * 4
    chunk_slices = max(1, _stage_chunk_bytes() // max(1, slice_bytes))
    h2d_bytes = 0
    h2d_chunks = 0

    def on_chunk(nbytes: int) -> None:
        # Cumulative per-chunk accounting AS chunks dispatch — a
        # mid-stage profile dump (or an exception between chunks)
        # reports the bytes actually shipped, and the chunk count
        # proves which path (pipelined vs single-put) ran.
        nonlocal h2d_bytes, h2d_chunks
        h2d_bytes += nbytes
        h2d_chunks += 1
        profile.add_bytes("bytes_staged", nbytes)

    def chunk_ranges(lo: int, hi: int):
        return [(c, min(c + chunk_slices, hi))
                for c in range(lo, hi, chunk_slices)]

    if mesh is None:
        ranges = chunk_ranges(0, s_pad)
        pieces = _stage_pipeline(pack_range, ranges, None, on_chunk)
        words_arr = _assemble_shard(
            pieces, [r[0] for r in ranges],
            (s_pad, cap, CONTAINER_WORDS), None)
        keys_arr = jnp.asarray(keys)
    else:
        sharding = NamedSharding(mesh, P(SLICE_AXIS))
        shape = (s_pad, cap, CONTAINER_WORDS)
        try:
            imap = sharding.addressable_devices_indices_map(shape)
            shards = []
            for dev, idxs in imap.items():
                lo = idxs[0].start or 0
                hi = idxs[0].stop if idxs[0].stop is not None else s_pad
                ranges = chunk_ranges(lo, hi)
                pieces = _stage_pipeline(pack_range, ranges, dev,
                                         on_chunk)
                shards.append(_assemble_shard(
                    pieces, [c - lo for c, _ in ranges],
                    (hi - lo, cap, CONTAINER_WORDS), dev))
            words_arr = jax.make_array_from_single_device_arrays(
                shape, sharding, shards)
        except Exception as fb_err:  # noqa: BLE001 — backend without
            # per-device placement support:
            # fall back to the whole-pool transfer + redistribution
            # path (one host pack of the full pool — device_put with a
            # global sharding needs the whole array per process
            # anyway). Slower, and host-RAM-bound at extreme pool
            # sizes, but always works. Drop the partial attempt's
            # device buffers FIRST: keeping them across the second full
            # transfer would stack partial + whole pool in HBM. Loudly
            # recorded — a silent fallback would read as a mysterious
            # staging regression.
            get_logger("mesh").warning(
                "per-device staging failed (%s: %s); falling back to "
                "whole-pool placement", type(fb_err).__name__, fb_err)
            if stats_out is not None:
                stats_out["h2d_fallback"] = f"{type(fb_err).__name__}: " \
                                            f"{fb_err}"
            shards = pieces = None  # noqa: F841 — release device refs
            words_arr = jax.device_put(pack_range(0, s_pad), sharding)
            # on_chunk: chunks shipped before the failure were real
            # traffic and already counted; the whole-pool retry adds
            # its own bytes on top.
            on_chunk(s_pad * slice_bytes)
        keys_arr = jax.device_put(keys, sharding)
    if stats_out is not None:
        stats_out["h2d_dispatch_s"] = _time.monotonic() - t0
        stats_out["h2d_bytes"] = h2d_bytes + keys.nbytes
        stats_out["h2d_chunk_slices"] = chunk_slices
        stats_out["h2d_chunks"] = h2d_chunks
    h2d_sp.tag(h2d_bytes=h2d_bytes + keys.nbytes,
               chunk_slices=chunk_slices, chunks=h2d_chunks).finish()
    h2d_ph.stop()
    profile.add_bytes("bytes_staged", keys.nbytes)
    idx = ShardedIndex(keys=keys_arr, words=words_arr)
    if with_host_keys:
        return idx, row_ids, keys
    return idx, row_ids


# -- sparsity-adaptive staging: sorted-array (roaring array) device pools -----
#
# The dense image bills 8 KB of HBM per container regardless of
# cardinality; a 3%-density container carries ~2 K values = 4 KB live,
# and a 0.3% one ~200 values = 400 B — 20-2000x padding waste. The
# roaring container classes (arXiv:1709.07821 §2.1: array below 4096
# values, bitmap above) applied at STAGING time: slices whose mean
# container fill sits under a density threshold stage as sorted u16
# value arrays + a cardinality table, everything else keeps packed
# words. One staged view can hold BOTH pools (mixed views), with a
# per-slice format byte deciding which pool serves each slice.

# A container with more than 4096 values is smaller as a bitmap
# (4096 * 2 B = 8 KB = the packed-word size) — the reference's
# ARRAY_MAX_SIZE break-even (roaring.go:951,1023).
ARRAY_VALUE_CAP = 4096

# Sparse eligibility floor: a slice whose TOTAL cardinality is under
# this never stages as sorted arrays. Below it the whole slice is
# Kbyte-scale either way, and the sparse path's extra host metadata
# resolution + separate kernel dispatch cost more than the HBM it
# saves. It also keeps tiny working sets (unit fixtures, cold frames)
# on the one-format dense path the batch/coarse dispatchers are
# specialized for.
SPARSE_MIN_SLICE_CARD = 1024

# Sparse value-capacity alignment: K pads to a lane multiple so the
# Pallas broadcast-compare kernel and the (8, 128)-tiled gathers see
# full tiles.
_VALUE_ALIGN = 128


class SparseShardedIndex(NamedTuple):
    """One frame/view's SPARSE slices: sorted-array containers, stacked
    and mesh-sharded. Same key packing as ShardedIndex (global dense
    row * 16 + subkey, INVALID_KEY padded) so the host row-resolution
    machinery (resolve_row_indices) works unchanged on either pool."""

    keys: jax.Array    # (S, C) int32, INVALID_KEY padded
    values: jax.Array  # (S, C, K) uint16, sorted, 0xFFFF padded
    cards: jax.Array   # (S, C) int32 real cardinalities

    @property
    def num_slices(self) -> int:
        return self.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[1]

    @property
    def value_cap(self) -> int:
        return self.values.shape[2]


def slice_format_stats(bitmaps: Sequence) -> np.ndarray:
    """Per-slice container stats the format pick runs on: (S, 3) int64
    [n_containers, total_cardinality, max_cardinality]. Uses the host
    Container.n the stager already has — no container is materialized
    to words to decide its format."""
    out = np.zeros((len(bitmaps), 3), dtype=np.int64)
    for si, b in enumerate(bitmaps):
        if b is None or not len(b.keys):
            continue
        ns = [c.n for c in b.containers]
        out[si] = (len(ns), sum(ns), max(ns))
    return out


def pick_slice_formats(stats: np.ndarray, threshold: float,
                       prev: Optional[np.ndarray] = None,
                       band: float = 1.25,
                       value_cap: int = ARRAY_VALUE_CAP,
                       min_card: int = SPARSE_MIN_SLICE_CARD) -> np.ndarray:
    """Per-slice format decision: 1 = sorted-array, 0 = packed words.

    A slice goes sparse when its mean container fill
    (total_card / (n_containers * 65536)) is under `threshold`, its
    total cardinality is at least `min_card` (below that the slice is
    Kbyte-scale either way and the sparse dispatch overhead wins), AND
    no container exceeds `value_cap` values (beyond 4096 the array
    form is LARGER than the bitmap — the reference's ARRAY_MAX_SIZE
    break-even). threshold <= 0 is the kill switch: everything dense.

    Hysteresis: with `prev` (the view's formats before a restage), a
    slice keeps its previous format inside the [threshold/band,
    threshold*band) window, so a fragment sitting near the boundary
    does not flip layout — and pay a full repack — on every
    incremental refresh. Crossing the far edge of the band always
    converts."""
    s = stats.shape[0]
    n = stats[:, 0].astype(np.float64)
    total = stats[:, 1].astype(np.float64)
    density = np.where(n > 0, total / np.maximum(n, 1) / 65536.0, 1.0)
    eligible = ((stats[:, 0] > 0) & (stats[:, 2] <= value_cap)
                & (stats[:, 1] >= min_card))
    if threshold <= 0:
        return np.zeros(s, dtype=np.uint8)
    fmt = (eligible & (density < threshold)).astype(np.uint8)
    if prev is not None and band > 1.0:
        m = min(s, len(prev))
        was_sparse = prev[:m].astype(bool)
        keep_sparse = was_sparse & eligible[:m] & (
            density[:m] < threshold * band)
        go_sparse = ~was_sparse & eligible[:m] & (
            density[:m] < threshold / band)
        fmt[:m] = (keep_sparse | go_sparse).astype(np.uint8)
    return fmt


def split_bitmaps_by_format(bitmaps: Sequence, formats: np.ndarray):
    """(dense_list, sparse_list): each the full-length slice list with
    the other format's slices None — the shape the two builders eat."""
    dense = [b if not formats[si] else None for si, b in enumerate(bitmaps)]
    sparse = [b if formats[si] else None for si, b in enumerate(bitmaps)]
    return dense, sparse


def global_row_ids(bitmaps: Sequence) -> np.ndarray:
    """The GLOBAL sorted uint64 row-id table over every slice — shared
    by the dense and sparse pools of one view (see build_sharded_index
    row_ids=)."""
    all_rows = [np.asarray(b.keys, dtype=np.uint64) >> np.uint64(4)
                for b in bitmaps if b is not None and len(b.keys)]
    return (np.unique(np.concatenate(all_rows)) if all_rows
            else np.empty(0, dtype=np.uint64))


def sparse_pool_dims(bitmaps: Sequence) -> Tuple[int, int]:
    """(container capacity C, value capacity K) of the sparse pool that
    build_sparse_sharded_index would stage for these slices — shared
    with the byte estimators so budget admission and actual staging
    cannot disagree."""
    counts = [len(b.keys) if b is not None else 0 for b in bitmaps]
    cap = max(1, max(counts, default=1))
    cap = -(-cap // ROW_SPAN) * ROW_SPAN
    max_card = 1
    for b in bitmaps:
        if b is None or not len(b.keys):
            continue
        max_card = max(max_card, max(c.n for c in b.containers))
    k = -(-max_card // _VALUE_ALIGN) * _VALUE_ALIGN
    return cap, k


def sparse_pool_bytes(num_slices: int, n_dev: int, cap: int,
                      k: int) -> int:
    """Padded HBM bytes of a (C=cap, K=k) sparse pool over num_slices
    slices on an n_dev mesh axis: values u16 + keys i32 + cards i32."""
    s_pad = -(-max(1, num_slices) // n_dev) * n_dev
    return s_pad * cap * (k * 2 + 4 + 4)


def build_sparse_sharded_index(bitmaps: Sequence,
                               mesh: Optional[Mesh] = None,
                               row_ids: Optional[np.ndarray] = None,
                               stats_out: Optional[dict] = None):
    """Stack the SPARSE slices' bitmaps into a SparseShardedIndex.

    bitmaps[s] is the slice-s roaring Bitmap for sparse-format slices
    and None elsewhere (dense or absent) — full-length, so slice
    positions line up with the dense pool. Containers pack as sorted
    u16 value arrays (Container.values(), already sorted) padded to
    the pool-wide value capacity with 0xFFFF; keys pack exactly like
    the dense builder so resolve_row_indices works on the host copy.

    Returns (SparseShardedIndex, row_ids, keys_host, cards_host) —
    the host keys/cards copies are always produced (they are the
    serving metadata AND the live-byte accounting source; a sparse
    pool is small enough that the copies are noise).

    No chunk pipeline here: a sparse pool is 10-100x smaller than the
    dense image of the same slices (the whole point), so a plain
    sharded device_put is already under the pipelining break-even."""
    import time as _time

    n_dev = mesh.shape[SLICE_AXIS] if mesh is not None else 1
    s = max(1, len(bitmaps))
    s_pad = -(-s // n_dev) * n_dev

    if row_ids is None:
        row_ids = global_row_ids(bitmaps)
    cap, k = sparse_pool_dims(bitmaps)

    t0 = _time.monotonic()
    keys = np.full((s_pad, cap), INVALID_KEY, dtype=np.int32)
    values = np.full((s_pad, cap, k), 0xFFFF, dtype=np.uint16)
    cards = np.zeros((s_pad, cap), dtype=np.int32)
    for si, b in enumerate(bitmaps):
        if b is None or not len(b.keys):
            continue
        real = np.asarray(b.keys, dtype=np.uint64)
        dense = np.searchsorted(row_ids, real >> np.uint64(4))
        kk = (dense * ROW_SPAN
              + (real & np.uint64(15)).astype(np.int64)).astype(np.int32)
        order = np.argsort(kk)
        keys[si, : len(kk)] = kk[order]
        for j, ci in enumerate(order):
            vals = b.containers[ci].values()
            cards[si, j] = len(vals)
            values[si, j, : len(vals)] = vals.astype(np.uint16)

    if mesh is None:
        keys_arr = jnp.asarray(keys)
        values_arr = jnp.asarray(values)
        cards_arr = jnp.asarray(cards)
    else:
        sharding = NamedSharding(mesh, P(SLICE_AXIS))
        keys_arr = jax.device_put(keys, sharding)
        values_arr = jax.device_put(values, sharding)
        cards_arr = jax.device_put(cards, sharding)
    nbytes = values.nbytes + keys.nbytes + cards.nbytes
    profile.add_bytes("bytes_staged", nbytes)
    if stats_out is not None:
        stats_out["sparse_h2d_bytes"] = nbytes
        stats_out["sparse_h2d_dispatch_s"] = _time.monotonic() - t0
        stats_out["sparse_value_cap"] = k
    idx = SparseShardedIndex(keys=keys_arr, values=values_arr,
                             cards=cards_arr)
    return idx, row_ids, keys, cards


def _gather_sparse_containers(vals, cards, idx_l, hit_l):
    """One sparse leaf's row containers for the serving kernels:
    (S_l*16, K) values and HIT-ZEROED (S_l*16,) cardinalities, flat-
    gathered with host-resolved within-slice indices — the sorted-array
    counterpart of _gather_leaf_blocks. Zeroed cardinalities make every
    downstream kernel exact on absent containers (no valid a-positions,
    no valid b-positions, so intersections and op counts are 0)."""
    s_l, c, k = vals.shape
    base = (jnp.arange(s_l, dtype=jnp.int32) * c)[:, None]
    flat = (idx_l + base).reshape(-1)
    v = vals.reshape(s_l * c, k)[flat]
    n = cards.reshape(-1)[flat] * hit_l.reshape(-1).astype(jnp.int32)
    return v, n


def compile_serve_count_sparse_pair(mesh: Mesh, op: str, kind: str,
                                    backend: str = "xla",
                                    interpret: bool = False):
    """Jit a masked two-leaf Count where at least one leaf serves from
    a sorted-array pool — the device analog of the reference's
    per-container-type kernel table (roaring.go:1270-1351), dispatched
    per SLICE GROUP by the serving layer.

    kind: "ss" (both sparse — array×array intersect kernel),
          "sd" (leaf 0 sparse, leaf 1 dense — array×bitmap probe),
          "ds" (leaf 0 dense, leaf 1 sparse — probe, operands swapped
          back for the asymmetric ops).
    op:   "and" | "or" | "andnot" (the plan lowering's full op set);
          everything beyond intersection derives per container by
          inclusion–exclusion from |a∩b| and the hit-masked operand
          cardinalities (bitops.sparse_op_counts).
    backend: for "ss", which intersect kernel serves — "xla" (binary-
          search gather ladder) or "pallas" (broadcast-compare); the
          calibrated race winner. Probe kinds are XLA-only (the TPU has
          no per-lane dynamic gather to write a Pallas probe with).

    Returns fn(pool_a, pool_b, idx_a, hit_a, idx_b, hit_b, mask)
    -> (2,) [lo, hi] limbs (combine_count). A sparse pool argument is
    the (values, cards) tuple, a dense one is (words,); idx/hit are the
    REPLICATED host (S, 16) resolve_row_indices outputs against the
    POOL THE LEAF SERVES FROM, mask the (S,) slice-group mask (1 only
    on slices this format pair owns)."""
    from ..ops.bitops import (sparse_op_counts,
                              sparse_pair_intersect_counts,
                              sparse_probe_intersect_counts)

    assert kind in ("ss", "sd", "ds"), kind

    def gather_dense(words, idx_l, hit_l):
        blk = _gather_leaf_blocks((words,), (idx_l,), (hit_l,), 0)
        return blk, lax.population_count(blk).astype(jnp.int32).sum(
            axis=-1)

    def per_shard(pool_a, pool_b, idx_a, hit_a, idx_b, hit_b, mask):
        s_l = pool_a[0].shape[0]
        off = lax.axis_index(SLICE_AXIS) * s_l
        ia = lax.dynamic_slice_in_dim(idx_a, off, s_l, axis=0)
        ha = lax.dynamic_slice_in_dim(hit_a, off, s_l, axis=0)
        ib = lax.dynamic_slice_in_dim(idx_b, off, s_l, axis=0)
        hb = lax.dynamic_slice_in_dim(hit_b, off, s_l, axis=0)
        mask_l = lax.dynamic_slice_in_dim(mask, off, s_l, axis=0)

        if kind == "ss":
            va, na = _gather_sparse_containers(pool_a[0], pool_a[1],
                                               ia, ha)
            vb, nb = _gather_sparse_containers(pool_b[0], pool_b[1],
                                               ib, hb)
            if backend == "pallas":
                from ..ops.kernels import pallas_sparse_pair_counts

                inter = pallas_sparse_pair_counts(va, na, vb, nb,
                                                  interpret=interpret)
            else:
                inter = sparse_pair_intersect_counts(va, na, vb, nb)
        elif kind == "sd":
            va, na = _gather_sparse_containers(pool_a[0], pool_a[1],
                                               ia, ha)
            blk, nb = gather_dense(pool_b[0], ib, hb)
            inter = sparse_probe_intersect_counts(va, na, blk)
        else:  # ds: probe the sparse side into the dense words;
            # |a∩b| is symmetric, na/nb keep their leaf positions so
            # andnot stays leaf0 - intersection.
            blk, na = gather_dense(pool_a[0], ia, ha)
            vb, nb = _gather_sparse_containers(pool_b[0], pool_b[1],
                                               ib, hb)
            inter = sparse_probe_intersect_counts(vb, nb, blk)

        counts = sparse_op_counts(op, inter, na, nb)
        per_slice = counts.reshape(s_l, ROW_SPAN).sum(
            axis=1).astype(jnp.uint32)
        per_slice = jnp.where(mask_l != 0, per_slice, jnp.uint32(0))
        lo = lax.psum(
            (per_slice & jnp.uint32(0xFFFF)).astype(jnp.int32).sum(),
            SLICE_AXIS)
        hi = lax.psum((per_slice >> 16).astype(jnp.int32).sum(),
                      SLICE_AXIS)
        return jnp.stack([lo, hi])

    pool_spec_a = (P(SLICE_AXIS),) if kind == "ds" else (
        P(SLICE_AXIS), P(SLICE_AXIS))
    pool_spec_b = (P(SLICE_AXIS),) if kind == "sd" else (
        P(SLICE_AXIS), P(SLICE_AXIS))
    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(pool_spec_a, pool_spec_b, P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=(backend == "xla"),
    )

    @jax.jit
    def count_sparse_pair(pool_a, pool_b, idx_a, hit_a, idx_b, hit_b, mask):
        return fn(pool_a, pool_b, idx_a, hit_a, idx_b, hit_b, mask)

    return count_sparse_pair


def _local_pools(keys, words):
    """Vmap helper: treat each local slice as a FragmentPool."""
    return FragmentPool(keys=keys, words=words, n=jnp.int32(0))


# Shared per-slice kernels — the compile_mesh_* entry points and the fused
# compile_mesh_step all build from these, so the standalone kernels and
# the fused step cannot drift apart.

def _count_one_slice(tree, num_leaves, keys, words, idxs):
    """Fused tree-eval + popcount for one slice's pool.

    int32: a global count saturates at 2^31-1 set bits (~2.1B); the JAX
    default config has no device int64. Callers needing beyond that
    aggregate per-slice counts host-side in Python ints."""
    pool = _local_pools(keys, words)
    leaves = tuple((pool, idxs[i]) for i in range(num_leaves))
    blk = eval_tree(tree, leaves)
    return lax.population_count(blk).astype(jnp.int32).sum()


def _row_counts_one_slice(num_rows, keys, words):
    """Per-dense-row popcounts for one slice's pool (segment-sum by
    key >> 4)."""
    per_container = lax.population_count(words).sum(axis=1, dtype=jnp.int32)
    valid = keys != INVALID_KEY
    dense = jnp.where(valid, keys // ROW_SPAN, num_rows)
    return jax.ops.segment_sum(
        jnp.where(valid, per_container, 0), dense,
        num_segments=num_rows + 1)[:num_rows]


def _apply_writes_one_slice(words, slot, word, mask):
    """Scatter a planned write batch into one slice's words.

    Scatter-max, not scatter-set: padding entries are (slot=0, mask=0)
    no-ops that may collide with a real write's target, and
    set-with-duplicates keeps an arbitrary one. cur|mask >= cur
    numerically, so max() keeps the real update."""
    cur = words[slot, word]
    return words.at[slot, word].max(cur | mask)


# -- fused count over the mesh ----------------------------------------------

def _leaf_container_indices(keys, idxs):
    """Per-leaf container locations for a shard's pool.

    keys: (S, cap) sorted pool keys; idxs: (L,) leaf dense-row ids.
    Returns idx (L, S, 16) int32 clipped container positions and
    hit (L, S, 16) int32 presence mask — the searchsorted half of
    gather_row (ops/pool.py), hoisted out so a kernel can stream the
    containers directly."""
    num_leaves = idxs.shape[0]
    targets = (idxs[:, None] * ROW_SPAN
               + jnp.arange(ROW_SPAN, dtype=jnp.int32)[None, :])  # (L, 16)
    flat = targets.reshape(-1)

    def one(k):
        i = jnp.searchsorted(k, flat).astype(jnp.int32)
        i = jnp.clip(i, 0, k.shape[0] - 1)
        return i, (k[i] == flat).astype(jnp.int32)

    idx, hit = jax.vmap(one)(keys)           # (S, L*16) each
    shape = (keys.shape[0], num_leaves, ROW_SPAN)
    return (idx.reshape(shape).transpose(1, 0, 2),
            hit.reshape(shape).transpose(1, 0, 2))


def compile_mesh_count(mesh: Mesh, tree_shape, num_leaves: int,
                       backend: Optional[str] = None):
    """Jit a Count over a bitmap-op tree for a mesh-sharded index.

    Returns fn(sharded_index, leaf_dense_ids (num_leaves,) int32) -> int32
    replicated global count, psum'd over the slice axis (ICI).

    backend: "xla" = vmapped gather + fused XLA combine, "pallas" =
    fused in-kernel container streaming (ops/kernels.tree_count_pallas),
    "pallas_interpret" = the Pallas kernel in interpret mode
    (differential tests on CPU). None: the PILOSA_TPU_COUNT_BACKEND
    env var if set, else "xla". "auto" (what config.apply_mesh_env
    installs as the serving default) resolves through the measured
    startup calibration (ops/calibrate) the same way the serving
    layer's dispatch does — xla while a probe is still pending.
    """
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    if backend is None:
        import os
        backend = os.environ.get("PILOSA_TPU_COUNT_BACKEND", "xla")
    if backend == "auto":
        from ..ops.calibrate import resolve_backend
        backend = "pallas" if resolve_backend(wait=False) == "pallas" \
            else "xla"
    if backend not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown count backend: {backend!r} "
                         "(want xla, pallas, or pallas_interpret)")

    if backend == "xla":
        one_slice = partial(_count_one_slice, tree, num_leaves)

        def per_shard(keys, words, idxs):
            counts = jax.vmap(one_slice, in_axes=(0, 0, None))(
                keys, words, idxs)
            return lax.psum(counts.sum(), SLICE_AXIS)
    else:
        from ..ops.kernels import tree_count_pallas, tree_count_pallas_coarse
        interpret = backend == "pallas_interpret"

        def coarse_starts(keys, idxs):
            """In-program coarse eligibility (the traced twin of
            coarse_row_starts): per (leaf, slice), the signed row-run
            index when the slice holds the row as one full 16-aligned
            run (or none of it), plus an eligibility flag. Any
            ineligible (partial/unaligned) pair falls the whole call
            back to the general slab kernel via lax.cond."""
            cap = keys.shape[1]

            def one(keys_s, dense_id):
                lo = dense_id * ROW_SPAN
                pos = jnp.searchsorted(keys_s, lo).astype(jnp.int32)
                pos_c = jnp.clip(pos, 0, cap - ROW_SPAN)
                run = lax.dynamic_slice(keys_s, (pos_c,), (ROW_SPAN,))
                present = jnp.any((keys_s >= lo) & (keys_s < lo + ROW_SPAN))
                full = (jnp.all(run == lo + jnp.arange(ROW_SPAN,
                                                       dtype=keys_s.dtype))
                        & (pos_c % ROW_SPAN == 0) & (pos_c == pos))
                ok = jnp.logical_or(~present, full)
                start = jnp.where(present & full, pos_c // ROW_SPAN,
                                  jnp.int32(-1))
                return start, ok

            starts, ok = jax.vmap(
                lambda d: jax.vmap(lambda k: one(k, d))(keys))(idxs)
            return starts, jnp.all(ok)  # (L, S), scalar

        def per_shard(keys, words, idxs):
            idx, hit = _leaf_container_indices(keys, idxs)
            if words.shape[1] % ROW_SPAN != 0:
                # Pre-padding staged image: statically ineligible for
                # the coarse kernel — the check must be PYTHON-level,
                # because lax.cond traces both branches and the coarse
                # kernel's reshape would fail on the unpadded cap.
                count = tree_count_pallas(words, idx, hit, tree,
                                          interpret=interpret)
            else:
                starts, eligible = coarse_starts(keys, idxs)
                count = lax.cond(
                    eligible,
                    lambda: tree_count_pallas_coarse(
                        words, starts, tree, interpret=interpret),
                    lambda: tree_count_pallas(words, idx, hit, tree,
                                              interpret=interpret))
            return lax.psum(count, SLICE_AXIS)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS), P(SLICE_AXIS), P()),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires (backend != "xla").
        check_vma=(backend == "xla"),
    )

    @jax.jit
    def mesh_count(index: ShardedIndex, leaf_ids):
        return fn(index.keys, index.words, leaf_ids)

    return mesh_count


# -- exact TopN over the mesh ------------------------------------------------

def compile_mesh_topn(mesh: Mesh, num_rows: int, k: int):
    """Jit an EXACT TopN: global per-row popcounts + replicated top_k.

    Returns fn(sharded_index) -> (counts (k,) int32, dense_row_ids (k,)).
    A k beyond the row count clamps (TopN(n) with n > rows returns
    every row, executor.go:273-310 semantics).
    """
    k = min(k, num_rows)
    one_slice = partial(_row_counts_one_slice, num_rows)

    def per_shard(keys, words):
        local = jax.vmap(one_slice)(keys, words).sum(axis=0)
        total = lax.psum(local, SLICE_AXIS)
        vals, ids = lax.top_k(total, k)
        return vals, ids

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS), P(SLICE_AXIS)),
        out_specs=(P(), P()),
    )

    @jax.jit
    def mesh_topn(index: ShardedIndex):
        return fn(index.keys, index.words)

    return mesh_topn


# -- device-side write application -------------------------------------------

def plan_writes(keys: np.ndarray, row_ids: np.ndarray,
                slice_writes: List[Tuple[np.ndarray, np.ndarray]],
                batch: int):
    """Host-side write planning: (row, col) batches per slice →
    (slot, word, mask) scatter plans with OR-combined duplicates.

    The device applies bits only into containers already present in the
    pool (SURVEY.md §7 "mutation on device" hard part: host buffers
    writes, device applies them as one scatter per step; container
    allocation stays a host responsibility). Unknown rows/containers are
    dropped — callers must ensure containers exist (import path does).
    Returns (slot (S,B), word (S,B), mask (S,B)) int32/uint32, padded
    with no-op (slot=0, mask=0) entries. Raises ValueError when a
    slice's distinct scatter targets exceed `batch` — a partial write
    must never be applied silently.
    """
    s = keys.shape[0]
    slot = np.zeros((s, batch), dtype=np.int32)
    word = np.zeros((s, batch), dtype=np.int32)
    mask = np.zeros((s, batch), dtype=np.uint32)
    for si, (rows, cols) in enumerate(slice_writes):
        if rows is None or len(rows) == 0 or len(row_ids) == 0:
            continue
        rows = np.asarray(rows, dtype=np.uint64)
        cols = np.asarray(cols, dtype=np.uint64) % np.uint64(SLICE_WIDTH)
        dense = np.searchsorted(row_ids, rows)
        ok = (dense < len(row_ids)) & (row_ids[np.minimum(dense, len(row_ids) - 1)] == rows)
        pos = rows * np.uint64(SLICE_WIDTH) + cols
        key = (dense * ROW_SPAN + ((pos >> np.uint64(16)) & np.uint64(15)).astype(np.int64)).astype(np.int32)
        sl = np.searchsorted(keys[si], key)
        ok &= (sl < keys.shape[1]) & (keys[si][np.minimum(sl, keys.shape[1] - 1)] == key)
        wd = ((pos & np.uint64(0xFFFF)) >> np.uint64(5)).astype(np.int32)
        mk = (np.uint32(1) << (pos & np.uint64(31)).astype(np.uint32))
        sl, wd, mk = sl[ok], wd[ok], mk[ok]
        # OR-combine duplicates so the device scatter has unique targets.
        flat = sl.astype(np.int64) * CONTAINER_WORDS + wd
        order = np.argsort(flat, kind="stable")
        flat, sl, wd, mk = flat[order], sl[order], wd[order], mk[order]
        uniq, start = np.unique(flat, return_index=True)
        combined = np.bitwise_or.reduceat(mk, start) if len(mk) else mk
        if len(uniq) > batch:
            raise ValueError(
                f"slice {si}: {len(uniq)} scatter targets exceed write "
                f"batch {batch}; split the write batch")
        n = len(uniq)
        slot[si, :n] = sl[start][:n]
        word[si, :n] = wd[start][:n]
        mask[si, :n] = combined[:n]
    return slot, word, mask


def compile_mesh_apply_writes(mesh: Mesh):
    """Jit the per-step scatter-OR of planned writes into the sharded
    pools. Write plans have unique (slot, word) targets per slice
    (plan_writes), so gather-OR-scatter is exact."""

    def per_shard(keys, words, slot, word, mask):
        return keys, jax.vmap(_apply_writes_one_slice)(words, slot, word, mask)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS),) * 5,
        out_specs=(P(SLICE_AXIS), P(SLICE_AXIS)),
    )

    @jax.jit
    def mesh_apply_writes(index: ShardedIndex, slot, word, mask):
        keys, words = fn(index.keys, index.words, slot, word, mask)
        return ShardedIndex(keys=keys, words=words)

    return mesh_apply_writes


def compile_mesh_step(mesh: Mesh, tree_shape, num_leaves: int,
                      num_rows: int, k: int):
    """The full per-step pipeline as ONE jitted shard_map: apply a
    planned write batch to the sharded pools, evaluate a fused count
    query, and compute the exact global TopN — write scatter, query
    dataflow, and both ICI reductions in a single XLA program. This is
    the multi-chip "training step" the driver dry-runs
    (__graft_entry__.dryrun_multichip).
    """
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    count_one = partial(_count_one_slice, tree, num_leaves)
    rows_one = partial(_row_counts_one_slice, num_rows)

    def per_shard(keys, words, slot, word, mask, leaf_ids):
        # 1. writes
        words = jax.vmap(_apply_writes_one_slice)(words, slot, word, mask)

        # 2. fused count query over the updated pools
        count = lax.psum(
            jax.vmap(count_one, in_axes=(0, 0, None))(keys, words, leaf_ids).sum(),
            SLICE_AXIS)

        # 3. exact TopN over all rows
        totals = lax.psum(jax.vmap(rows_one)(keys, words).sum(axis=0), SLICE_AXIS)
        top_vals, top_ids = lax.top_k(totals, k)
        return keys, words, count, top_vals, top_ids

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS),) * 5 + (P(),),
        out_specs=(P(SLICE_AXIS), P(SLICE_AXIS), P(), P(), P()),
    )

    @jax.jit
    def mesh_step(index: ShardedIndex, slot, word, mask, leaf_ids):
        keys, words, count, top_vals, top_ids = fn(
            index.keys, index.words, slot, word, mask, leaf_ids)
        return ShardedIndex(keys=keys, words=words), count, top_vals, top_ids

    return mesh_step


# -- serving-path kernels ----------------------------------------------------
#
# The compile_serve_* family is what the query Executor calls when an
# HTTP query reaches a node (the TPU answer to the reference's
# goroutine-per-slice local fan-out, executor.go:1200-1236): one
# shard_map'd computation evaluates every locally-owned slice, with a
# per-slice ownership mask so the same staged index serves any slice
# subset, and psum reductions ride ICI. Counts come back as two int32
# limbs (lo16/hi) combined host-side — a dense multi-B-column index
# overflows a single int32 accumulator (the JAX default config has no
# device int64), so the device never sums raw counts across slices.


def combine_count(limbs) -> int:
    """Host-side combine of a (2,) [lo, hi] int32 limb array.

    The limbs travel as ONE device array, not two scalars: each
    fetch is a readback of its own, so the device packs both limbs
    before the host reads anything."""
    limbs = np.asarray(limbs)
    return (int(limbs[1]) << 16) + int(limbs[0])


def resolve_row_indices(keys_host: np.ndarray, dense_id: int,
                        slots_host: Optional[np.ndarray] = None):
    """Host-side row → container-location resolution for the serving
    count path.

    keys_host: (S, cap) sorted int32 pool keys (INVALID_KEY padded);
    slots_host: (S, cap) device slot of each of them, where the pool
    takes created containers into free slots (the layout:
    ops.pool.assign_free_slots; None = a key's position is its slot).
    Returns (idx (S, 16) int32 WITHIN-SLICE container indices in
    [0, cap) and hit (S, 16) uint32). Indices are within-slice — not
    flat — because inside shard_map each shard only holds its local
    slice block; the kernel adds its own local base (a global flat
    index would only be right on a 1-device mesh).

    This work lives on the HOST deliberately: it is ~0.1 ms of
    vectorized numpy where an in-program vmapped searchsorted runs on
    every query, and the result only changes when the pool's key
    layout changes (restage), so the serving layer caches the device
    copies per (view, row). One
    searchsorted over slice-offset int64 keys resolves every slice at
    once; a clipped miss lands on an arbitrary in-range container, but
    hit=0 multiplies that gather to zero.
    """
    s, cap = keys_host.shape
    off = (np.arange(s, dtype=np.int64) << 33)[:, None]
    k64 = (keys_host.astype(np.int64) + off).reshape(-1)
    t = dense_id * ROW_SPAN + np.arange(ROW_SPAN, dtype=np.int64)
    t64 = (t[None, :] + off).reshape(-1)
    i = np.searchsorted(k64, t64)
    i = np.minimum(i, s * cap - 1)
    hit = (k64[i] == t64).astype(np.uint32)
    within = np.clip(i.reshape(s, ROW_SPAN)
                     - (np.arange(s, dtype=np.int64) * cap)[:, None],
                     0, cap - 1)
    if slots_host is not None:
        within = np.take_along_axis(slots_host, within, axis=1)
    return within.astype(np.int32), hit.reshape(s, ROW_SPAN)


def _gather_leaf_blocks(words_t, idx_t, hit_t, i):
    """One leaf's (S_local*16, CONTAINER_WORDS) gathered blocks for the
    serving kernels: a flat gather from the leaf's own pool using the
    host-resolved within-slice indices, zeroed where the container is
    absent (hit == 0). The ONE implementation every compile_serve_*
    kernel folds its tree over — the gather indexing and absent-row
    semantics cannot drift between the count, batch, src, and tanimoto
    programs."""
    with jax.named_scope("gather_leaves"):
        w = words_t[i]
        cap = w.shape[1]
        wflat = w.reshape(w.shape[0] * cap, w.shape[2])
        base = (jnp.arange(w.shape[0], dtype=jnp.int32) * cap)[:, None]
        blk = wflat[(idx_t[i] + base).reshape(-1)]
        return blk * hit_t[i].reshape(-1)[:, None]


def coarse_row_starts(keys_host: np.ndarray, dense_id: int,
                      slots_host: Optional[np.ndarray] = None):
    """Host-side COARSE eligibility check for one leaf row: when every
    slice holds the row's 16 containers as one contiguous, 16-aligned
    run (or holds none of them), the serving kernels can gather the row
    as ONE run per slice, named by one start index instead of 16
    container indices and 16 hit flags. What that buys under the xla
    backend is the host side (an (S,) pair a leaf to resolve, cache and
    upload) and the shared-read program's in-place slice; on the device
    the coarse program gathers the same 16 containers the general one
    does (_row_run_blocks). On a v5e at 960 slices a 2-leaf coarse
    launch takes 1.55 ms and an 8-leaf one 6.09 ms (7.28 and 29.0 while
    the row was read through a re-laid pool; chip runs, PR 30).

    This is the data-adaptive dispatch the reference does by container
    TYPE (roaring.go:1270-1351 array/bitmap kernel table) done instead
    by container LAYOUT. Dense popular rows stage contiguously (stagers
    sort keys, and build_sharded_index pads capacity to a ROW_SPAN
    multiple, so fully-dense rows land aligned); sparse or partial rows
    fall back to the general gather path (resolve_row_indices).

    slots_host is resolve_row_indices': a row with a container patched
    into a free slot is eligible only if its 16 slots are still one
    aligned run, which a slot taken later never continues.

    Returns (starts (S,) int32 row-run indices [slot/16], valid (S,)
    uint32 presence flags) or None when any slice is partial/unaligned.
    """
    s, cap = keys_host.shape
    if cap % ROW_SPAN != 0:
        return None  # pre-padding staged image (build_sharded_index
        #              now always pads; old images fall back)
    lo = np.int64(dense_id) * ROW_SPAN
    # Position of the row's first container in each slice's sorted
    # keys: one searchsorted over slice-offset int64 keys (same scheme
    # as resolve_row_indices).
    off = np.arange(s, dtype=np.int64) * (np.int64(1) << 33)
    k64 = (keys_host.astype(np.int64) + off[:, None]).reshape(-1)
    pos = np.searchsorted(k64, lo + off) - np.arange(s, dtype=np.int64) * cap
    pos = np.clip(pos, 0, cap - 1)
    present = keys_host[np.arange(s), pos] == lo
    if not present.any():
        return None  # staged nowhere: the general path answers zero
        #              via hit=0 without a special case here
    ps = pos[present]
    if (ps + ROW_SPAN > cap).any():
        return None
    at = np.flatnonzero(present)[:, None]
    span = np.arange(ROW_SPAN, dtype=np.int64)
    run = ps[:, None] + span[None, :]
    if not (keys_host[at, run] == (lo + span)[None, :]).all():
        return None
    if slots_host is not None:
        slots = slots_host[at, run].astype(np.int64)
        ps = slots[:, 0]
        if not (slots == ps[:, None] + span[None, :]).all():
            return None
    if ((ps % ROW_SPAN) != 0).any():
        return None
    starts = np.zeros(s, dtype=np.int32)
    starts[present] = (ps // ROW_SPAN).astype(np.int32)
    return starts, present.astype(np.uint32)


def _row_run_blocks(start, valid):
    """One coarse leaf's (start, valid) runs as the (idx, hit) that
    _gather_leaf_blocks reads: the run's own container indices
    (start*16 + 0..15) and hit = valid, so a slice that holds no part
    of the row (valid == 0) gathers zeros by the same rule as an absent
    container. The pool is never viewed as (S, cap/16, 16*W): on the
    chip the two minor dimensions are tiled, so that reshape is no view
    but a copy of the whole pool for every leaf of every launch (1 GB,
    3.06 ms at 960 slices: 79% of seg-1b.herd64's device time in the
    ledger's PR 29 lines; coarse_row_starts has the launch times
    since)."""
    span = jnp.arange(ROW_SPAN, dtype=jnp.int32)
    idx = start[:, None] * ROW_SPAN + span[None, :]          # (S_l, 16)
    return idx, jnp.broadcast_to(valid[:, None], idx.shape)


def _limb_psum(per_bs):
    """(B, S_l) uint32 per-(query, slice) counts -> (2, B) [lo, hi]
    16-bit limb columns psum'd over the slice axis — the shared
    epilogue of every serving count program (a per-slice count is
    <= 2^20, so the 16-bit split keeps the int32 psum exact at any
    slice fan-out)."""
    lo = lax.psum(
        (per_bs & jnp.uint32(0xFFFF)).astype(jnp.int32).sum(axis=1),
        SLICE_AXIS)
    hi = lax.psum((per_bs >> 16).astype(jnp.int32).sum(axis=1),
                  SLICE_AXIS)
    return jnp.stack([lo, hi])


def compile_serve_count(mesh: Mesh, tree_shape, num_leaves: int,
                        batch: int = 1, runs: bool = False,
                        host_meta: bool = False):
    """Jit the XLA masked Count: `batch` independent queries of one
    tree shape over PER-LEAF pools (a served tree may span frames and
    time-quantum views), evaluated in ONE device program. Dispatch and
    readback are a fixed cost per program, so the serving layer
    coalesces concurrent same-shape queries (serve.MeshManager batch
    loop) and amortizes it. Every form is one body: resolve each leaf
    to (idx, hit), fold the tree over _gather_leaf_blocks, popcount,
    mask, _limb_psum. The parameters say what differs:

    runs: how a leaf is named. False: by its 16 containers, (S, 16)
      int32 WITHIN-SLICE indices and (S, 16) uint32 presence flags
      (resolve_row_indices). True: by one whole-row run a slice, (S,)
      int32 row-run index and (S,) uint32 presence flag
      (coarse_row_starts; every leaf must be eligible).
    host_meta: where that metadata and the mask come from. False:
      sharded device arrays the caller caches per (view, row), as flat
      row-major [b][l] tuples of batch*num_leaves. True (batch == 1,
      the lone query): REPLICATED host arrays stacked (L, S, ...) that
      ride the one jitted call's argument transfer, each shard slicing
      out its local block in-program — the chained path uploads each
      leaf's metadata and the mask as device operations of their own
      before the launch, here the whole query is one dispatch + one
      fetch. At 960 slices the metadata is ~120 KB a leaf, noise
      against the pool.

    Returns fn(words_t (L,) of (S, cap_i, 2048) sharded words, meta_a,
    meta_b, mask (S,) int32 slice-ownership) -> (2, batch) [lo, hi]
    limb columns, (2,) with host_meta; combine with combine_count.
    Per-slice counts are uint32 (safe to 2^32 bits/slice); the lo-limb
    sum is int32-safe to 32k slices (~34T columns). The jitted
    function's name is what a device trace prints: count_fused
    (host_meta), count_coarse (runs), count_batch.
    """
    assert batch == 1 or not host_meta, "host metadata is the lone form"
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    from ..ops.bitops import fold_tree

    def per_shard(words_t, meta_a, meta_b, mask):
        s_l = words_t[0].shape[0]
        if host_meta:
            off = lax.axis_index(SLICE_AXIS) * s_l
            meta_a = lax.dynamic_slice_in_dim(meta_a, off, s_l, axis=1)
            meta_b = lax.dynamic_slice_in_dim(meta_b, off, s_l, axis=1)
            mask = lax.dynamic_slice_in_dim(mask, off, s_l, axis=0)

        def one(b):
            def leaf(i):
                k = b * num_leaves + i
                idx, hit = meta_a[k], meta_b[k]
                if runs:
                    idx, hit = _row_run_blocks(idx, hit)
                return _gather_leaf_blocks((words_t[i],), (idx,), (hit,), 0)

            blk = fold_tree(tree, leaf)                    # (S_l*16, W)
            with jax.named_scope("popcount"):
                return lax.population_count(blk).sum(
                    axis=1, dtype=jnp.uint32).reshape(
                        s_l, ROW_SPAN).sum(axis=1, dtype=jnp.uint32)

        per_slice = jnp.stack([one(b) for b in range(batch)])  # (B, S_l)
        per_slice = jnp.where(mask[None, :] != 0, per_slice, jnp.uint32(0))
        limbs = _limb_psum(per_slice)
        return limbs[:, 0] if host_meta else limbs

    meta = P() if host_meta else (P(SLICE_AXIS),) * (batch * num_leaves)
    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_leaves, meta, meta,
                  P() if host_meta else P(SLICE_AXIS)),
        out_specs=P(),
    )

    def count(words_t, meta_a, meta_b, mask):
        return fn(words_t, meta_a, meta_b, mask)

    count.__name__ = count.__qualname__ = (
        "count_fused" if host_meta
        else "count_coarse" if runs else "count_batch")
    return jax.jit(count)


def compile_serve_count_coarse_pallas(mesh: Mesh, tree_shape,
                                      num_leaves: int,
                                      interpret: bool = False):
    """Pallas twin of compile_serve_count(runs=True) at batch 1: identical
    call contract — fn(words_t (L,), start_flat (L,) of (S,) int32,
    valid_flat (L,) of (S,) uint32, mask (S,)) -> (2, 1) limb column —
    but the fold+popcount runs as ONE pallas_call per shard streaming
    each leaf's whole 128 KB row run HBM->VMEM exactly once (the
    general Pallas kernel's (L, S, 16) SMEM tables force slab
    launches that each pay a dispatch; the coarse form's
    per-(leaf, slice) state is ONE signed int, so any S fits one
    launch). The XLA gather path writes each leaf's gathered rows back
    to HBM (S_local * 128 KB a leaf: 126 MB at 960 slices) before the
    fold and the popcount read them again; since PR 30 that is all it
    materializes, no longer a copy of the pool. Selected when the count
    backend resolves to Pallas (PILOSA_TPU_COUNT_BACKEND, or the
    calibrated "auto"); differential coverage runs in interpret mode on
    the CPU mesh, and tests/test_tpu_compile.py compiles it for the
    chip."""
    from ..ops.kernels import coarse_count_per_slice

    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)

    def per_shard(words_t, start_flat, valid_flat, mask):
        # Fold validity AND slice ownership into the sign: the kernel
        # masks blocks by `start >= 0` alone.
        starts = jnp.stack([
            jnp.where((valid_flat[i] != 0) & (mask != 0),
                      start_flat[i], jnp.int32(-1))
            for i in range(num_leaves)])
        per_slice = coarse_count_per_slice(
            tuple(words_t), starts, tree,
            interpret=interpret)[0].astype(jnp.uint32)
        return _limb_psum(per_slice[None, :])

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  P(SLICE_AXIS)),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires.
        check_vma=False,
    )

    @jax.jit
    def count_coarse_pallas(words_t, start_flat, valid_flat, mask):
        return fn(words_t, start_flat, valid_flat, mask)

    return count_coarse_pallas


def compile_serve_count_coarse_pallas_uniform(mesh: Mesh, tree_shape,
                                              num_leaves: int,
                                              batch: int = 1,
                                              interpret: bool = False):
    """Uniform-layout Pallas coarse count: fn(words_t (L,), starts
    (B*L,) int32 scalar row-run per slot, mask (S,)) -> (2, B) limb
    columns. Selected when the serving layer detects (host-side, from
    the staged keys) that every leaf sits at ONE row-run index across
    all slices — true for any densely staged pool — which lets the
    kernel fetch multiple consecutive slices per grid step
    (ops.kernels.coarse_count_uniform). Slice-ownership masks
    apply AFTER the kernel: the per-slice counts are multiplied by the
    mask before the limb psum, so validity never needs a per-slice
    starts table."""
    from ..ops.kernels import coarse_count_uniform, coarse_count_uniform_batch

    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)

    def per_shard(words_t, starts, mask):
        own = (mask != 0).astype(jnp.int32)
        if batch == 1:
            per_slice = coarse_count_uniform(
                tuple(words_t), starts, tree,
                interpret=interpret)[0]
            per_bs = (per_slice * own)[None, :].astype(jnp.uint32)
        else:
            per_bs = coarse_count_uniform_batch(
                tuple(words_t), starts, tree,
                interpret=interpret)
            per_bs = (per_bs * own[None, :]).astype(jnp.uint32)
        return _limb_psum(per_bs)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_leaves,
                  P(),  # starts are global scalars, replicated
                  P(SLICE_AXIS)),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires.
        check_vma=False,
    )

    @jax.jit
    def count_coarse_pallas_uniform(words_t, starts, mask):
        return fn(tuple(words_t), starts, mask)

    return count_coarse_pallas_uniform


# Queries a shared-read group may have and still run the scan's narrow
# body (compile_serve_count_batch_shared): measured at 2 and 4, where it
# wins, and at 16, where it loses.
_SHARED_NARROW_MAX = 4


def compile_serve_count_batch_shared(mesh: Mesh, tree_shape,
                                     leaf_map: Tuple[Tuple[int, ...], ...],
                                     num_unique: int):
    """Jit a SHARED-READ coarse batch count: B queries of one tree
    shape over U unique coarse leaves, reading each unique leaf's data
    ONCE per slice instead of once per query.

    The plain batch program (compile_serve_count, runs=True) makes every
    query gather its own leaves: a batch of B two-leaf queries over U
    unique rows moves B*2 row-reads of HBM traffic. Here a lax.scan
    walks the local slices; each step gathers the U unique row-runs for
    that slice (U * 128 KB — VMEM-resident while the step computes) and
    evaluates ALL B query folds from those blocks, so traffic scales
    with UNIQUE leaves: the 28-distinct-pair headline reads the 8-row
    pool once (~1 GB) instead of 28 pairs x 2 rows (~7 GB). This is the
    device analog of the reference's per-fragment row cache serving
    many queries from one materialized row (fragment.go:332-367 +
    BitmapCache) — except the "cache" is one scan step's VMEM block.
    The scan is bound by its 960 sequential steps and the device ops
    in each, not by bytes, so it has two bodies (one v5e, 960 slices,
    launch to ready; PR 32). `wide`, above _SHARED_NARROW_MAX queries:
    two fetches of scalars, U gathers behind a barrier, one popcount
    fusion, two adds; 16 pairs of 8 rows 21.5 ms, 28 pairs 25.8 ms.
    `narrow`: one fetch and one fusion that gathers, folds and counts,
    so 2 device ops a step where `wide` has 7 + U; 2 queries of 3 rows
    6.4 ms against 9.3, 4 of 5 rows 9.9 against 12.9, but 16 of 8
    23.3 and 28 of 8 34.6, which is why `wide` stays. A device op is
    also an event of a device trace, 960 steps a launch: the herds'
    groups are narrow, and with `wide` for them stopping a 5-s trace
    took 70 s (20 s now).

    leaf_map is STATIC: leaf_map[b] gives, per leaf position of the
    tree, the unique-leaf index it reads. The compile cache key must
    include it (serve.MeshManager memoizes by (sig, leaf_map)).

    Returns fn(words_t (U,), start_t (U,) of (S,) int32 row-run
    indices, valid_t (U,) of (S,) uint32, mask (S,) int32)
    -> (2, B) [lo, hi] limb columns (same contract as
    compile_serve_count's runs form).
    """
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    from ..ops.bitops import fold_tree

    batch = len(leaf_map)

    def wide(words_t, start_t, valid_t, mask):
        s_l = words_t[0].shape[0]
        start_st = jnp.stack(start_t)            # (U, S_l)
        valid_st = jnp.stack(valid_t)            # (U, S_l)

        def step(acc, s):
            # Slice each UNIQUE leaf's whole-row run for slice s out of
            # the pool as it was staged (16 containers from start*16; a
            # (S, cap/16, 16*W) view of the pool is a copy of it on the
            # chip, see _row_run_blocks) — read once, used by every
            # query below. The barrier forces the U blocks to
            # materialize once (U * 128 KB, VMEM-resident) before the B
            # folds consume them; without it the compiler fuses each
            # gather into the folds that read it.
            with jax.named_scope("gather_leaves"):
                blocks = list(lax.optimization_barrier(tuple(
                    lax.dynamic_slice(
                        words_t[u], (s, start_st[u, s] * ROW_SPAN, 0),
                        (1, ROW_SPAN, words_t[u].shape[2]))
                    * valid_st[u, s].astype(jnp.uint32)
                    for u in range(num_unique))))

            live = (mask[s] != 0).astype(jnp.uint32)
            outs = []
            for b in range(batch):
                blk = fold_tree(tree, lambda i: blocks[leaf_map[b][i]])
                with jax.named_scope("popcount"):
                    pc = lax.population_count(blk).sum(
                        dtype=jnp.uint32) * live
                outs.append(pc)
            per_slice = jnp.stack(outs)          # (B,) uint32
            lo = (per_slice & jnp.uint32(0xFFFF)).astype(jnp.int32)
            hi = (per_slice >> 16).astype(jnp.int32)
            return (acc[0] + lo, acc[1] + hi), None

        # pcast to varying: the scan carry accumulates shard-local
        # values, so its init must be marked varying over the mesh
        # axis for the VMA checker.
        init = (lax.pcast(jnp.zeros(batch, jnp.int32), (SLICE_AXIS,),
                          to="varying"),
                lax.pcast(jnp.zeros(batch, jnp.int32), (SLICE_AXIS,),
                          to="varying"))
        (lo, hi), _ = lax.scan(step, init,
                               jnp.arange(s_l, dtype=jnp.int32))
        return jnp.stack([lax.psum(lo, SLICE_AXIS),
                          lax.psum(hi, SLICE_AXIS)])

    def narrow(words_t, start_t, valid_t, mask):
        s_l = words_t[0].shape[0]
        # One int32 a (leaf, slice): the run's container offset, and in
        # bit 0 whether the leaf is there and the slice is live. A
        # masked slice zeroes every leaf, and and/or/andnot of zeros
        # is zero, so the mask needs no multiply of its own. Indexed
        # [u, s] one by one, the compiler fetches a step's column in
        # ONE fusion.
        live = mask != 0
        table = jnp.stack([
            st * (2 * ROW_SPAN) + ((v != 0) & live).astype(jnp.int32)
            for st, v in zip(start_t, valid_t)])             # (U, S_l)

        def step(s, acc):
            row = [table[u, s] for u in range(num_unique)]
            with jax.named_scope("gather_leaves"):
                blocks = [
                    lax.dynamic_slice(
                        words_t[u], (s, row[u] >> 1, 0),
                        (1, ROW_SPAN, words_t[u].shape[2]))
                    * (row[u] & 1).astype(jnp.uint32)
                    for u in range(num_unique)]
            out = []
            for b in range(batch):
                blk = fold_tree(tree, lambda i: blocks[leaf_map[b][i]])
                with jax.named_scope("popcount"):
                    pc = lax.population_count(blk).sum(dtype=jnp.uint32)
                out += [(pc & jnp.uint32(0xFFFF)).astype(jnp.int32),
                        (pc >> 16).astype(jnp.int32)]
            return tuple(a + o for a, o in zip(acc, out))

        # Scalar carries, [lo, hi] a query: their adds run on the
        # scalar core and are no device op of their own.
        init = tuple(lax.pcast(jnp.zeros((), jnp.int32), (SLICE_AXIS,),
                               to="varying") for _ in range(2 * batch))
        acc = lax.fori_loop(0, s_l, step, init)
        return lax.psum(jnp.stack(acc), SLICE_AXIS).reshape(batch, 2).T

    per_shard = narrow if batch <= _SHARED_NARROW_MAX else wide

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_unique,
                  (P(SLICE_AXIS),) * num_unique,
                  (P(SLICE_AXIS),) * num_unique,
                  P(SLICE_AXIS)),
        out_specs=P(),
    )

    @jax.jit
    def count_batch_shared(words_t, start_t, valid_t, mask):
        return fn(words_t, start_t, valid_t, mask)

    return count_batch_shared


def compile_serve_count_coarse_pallas_batch(mesh: Mesh, tree_shape,
                                            num_leaves: int, batch: int,
                                            interpret: bool = False):
    """Pallas twin of compile_serve_count(runs=True) for batch > 1 — the
    plain (no leaf sharing assumed) herd-group program. Same call
    contract: fn(words_t (L,), start_flat (B*L,) of (S,) int32,
    valid_flat (B*L,) of (S,) uint32, mask (S,)) -> (2, B).

    One compile serves every ad-hoc width-B herd of this tree shape
    (the shared machinery's per-composition maps would recompile per
    herd): the (b, s) grid picks each slot's row-run from the
    scalar-prefetched starts table, so which rows the queries name is
    DATA, not program. Sharing saves no reads here, but the grid
    kernel still skips the XLA batch program's gathered HBM
    intermediates and pipelines per-slice DMA under the B folds, which
    is where the plain XLA batch spends its time at herd widths."""
    from ..ops.kernels import coarse_count_identity_batch

    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    slots = batch * num_leaves

    def per_shard(words_t, start_flat, valid_flat, mask):
        starts = jnp.stack([
            jnp.where((valid_flat[k] != 0) & (mask != 0),
                      start_flat[k], jnp.int32(-1))
            for k in range(slots)])
        per_bs = coarse_count_identity_batch(
            tuple(words_t), starts, tree,
            interpret=interpret).astype(jnp.uint32)      # (B, S_l)
        return _limb_psum(per_bs)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * slots,
                  (P(SLICE_AXIS),) * slots,
                  P(SLICE_AXIS)),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires.
        check_vma=False,
    )

    @jax.jit
    def count_coarse_pallas_batch(words_t, start_flat, valid_flat, mask):
        return fn(tuple(words_t), tuple(start_flat), tuple(valid_flat),
                  mask)

    return count_coarse_pallas_batch


def compile_serve_count_batch_shared_pallas(mesh: Mesh, tree_shape,
                                            leaf_map, num_unique: int,
                                            interpret: bool = False):
    """Pallas twin of compile_serve_count_batch_shared: identical call
    contract — fn(words_t (U,), start_t (U,) of (S,) int32, valid_t
    (U,) of (S,) uint32, mask (S,)) -> (2, B) limb columns — but the
    shared-read fold runs as ONE pallas_call per shard
    (ops.kernels.coarse_count_batch_per_slice). The XLA program's
    lax.scan walks slices SEQUENTIALLY, each step doing microseconds
    of compute behind an optimization_barrier: a latency-bound loop,
    though it moves 7x less HBM traffic than the plain batch. The
    pallas grid keeps the traffic win and pipelines the per-slice DMA
    under compute. Selected by PILOSA_TPU_COUNT_BACKEND=pallas
    (serve.MeshManager._shared_* machinery; key carries the backend)."""
    from ..ops.kernels import coarse_count_batch_per_slice

    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    leaf_map = tuple(tuple(m) for m in leaf_map)

    def per_shard(words_t, start_t, valid_t, mask):
        starts = jnp.stack([
            jnp.where((valid_t[u] != 0) & (mask != 0),
                      start_t[u], jnp.int32(-1))
            for u in range(num_unique)])
        per_bs = coarse_count_batch_per_slice(
            tuple(words_t), starts, tree, leaf_map,
            interpret=interpret).astype(jnp.uint32)      # (B, S_l)
        return _limb_psum(per_bs)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_unique,
                  (P(SLICE_AXIS),) * num_unique,
                  (P(SLICE_AXIS),) * num_unique,
                  P(SLICE_AXIS)),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires.
        check_vma=False,
    )

    @jax.jit
    def count_batch_shared_pallas(words_t, start_t, valid_t, mask):
        return fn(words_t, start_t, valid_t, mask)

    return count_batch_shared_pallas


def compile_serve_count_batch_shared_pallas_uniform(
        mesh: Mesh, tree_shape, leaf_map, num_unique: int,
        interpret: bool = False):
    """Uniform-layout shared-read batch: fn(words_t (U,), starts (U,)
    int32 scalar row-run per unique, mask (S,)) -> (2, B). Combines
    the shared program's unique-leaf traffic win with the uniform
    kernel's multi-slice DMA amortization
    (ops.kernels.coarse_count_shared_uniform); the serving layer
    selects it when _shared_plan sees every unique leaf staged at one
    row-run index across all slices."""
    from ..ops.kernels import coarse_count_shared_uniform

    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    leaf_map = tuple(tuple(m) for m in leaf_map)

    def per_shard(words_t, starts, mask):
        per_bs = coarse_count_shared_uniform(
            tuple(words_t), starts, tree, leaf_map,
            interpret=interpret)
        per_bs = (per_bs * (mask != 0).astype(jnp.int32)[None, :]
                  ).astype(jnp.uint32)
        return _limb_psum(per_bs)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=((P(SLICE_AXIS),) * num_unique,
                  P(),  # starts are global scalars, replicated
                  P(SLICE_AXIS)),
        out_specs=P(),
        # pallas_call can't annotate how its output varies over mesh
        # axes, which the VMA checker requires.
        check_vma=False,
    )

    @jax.jit
    def count_batch_shared_pallas_uniform(words_t, starts, mask):
        return fn(tuple(words_t), starts, mask)

    return count_batch_shared_pallas_uniform


def _segment_rows(pc, dense, num_rows):
    """vmap'd per-slice segment-sum of per-container counts into dense
    rows: (S, cap) pc + (S, cap) dense ids -> (S, num_rows)."""

    def one(pc_row, dense_row):
        return jax.ops.segment_sum(pc_row, dense_row,
                                   num_segments=num_rows + 1)[:num_rows]

    return jax.vmap(one)(pc, dense)


def _src_block_per_container(keys, src_blk, s_l):
    """Align an evaluated src tree's (S*16, W) blocks with a pool's
    containers: each container ANDs against the src block of its own
    sub-key (key mod 16). Returns (src_per_container (S, cap, W),
    valid (S, cap) presence mask). Shared by the src and tanimoto
    row-count kernels so the sub-key gather can't diverge."""
    src_blk3 = src_blk.reshape(s_l, ROW_SPAN, CONTAINER_WORDS)
    valid = keys != INVALID_KEY
    sub = jnp.where(valid, keys % ROW_SPAN, 0)
    return jnp.take_along_axis(src_blk3, sub[:, :, None], axis=1), valid


def compile_serve_row_counts_src(mesh: Mesh, tree_shape, num_leaves: int,
                                 num_rows: int):
    """Jit masked per-row SRC-INTERSECTION counts: |row ∩ src| for
    every row of one view, where src is a lowered bitmap-op tree
    (reference TopN src semantics, fragment.go:564-608 — there a
    host loop re-intersecting rows one by one; here ONE fused pass).

    Returns fn(keys (S, cap), words (S, cap, 2048) — the TopN view's
    pool — src_words_t/src_idx_t/src_hit_t (per src leaf, as in
    compile_serve_count), mask (S,)) -> (2, num_rows) limb array.
    Each container ANDs against the src block of its own sub-key
    (key mod 16), then popcounts segment-sum by dense row.
    """
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    from ..ops.bitops import fold_tree

    def per_shard(keys, words, src_words_t, src_idx_t, src_hit_t, mask):
        s_l, cap_l = keys.shape

        def leaf(i):
            return _gather_leaf_blocks(src_words_t, src_idx_t, src_hit_t, i)

        src_blk = fold_tree(tree, leaf)                      # (S*16, W)
        # Per-container src sub-block: gather (S, cap, W) from
        # (S, 16, W) — XLA fuses this into the AND+popcount consumer.
        src_per_container, valid = _src_block_per_container(
            keys, src_blk, s_l)
        pc = lax.population_count(words & src_per_container).sum(
            axis=2, dtype=jnp.int32)                         # (S, cap)
        dense = jnp.where(valid, keys // ROW_SPAN, num_rows)
        pc = jnp.where(valid & (mask[:, None] != 0), pc, 0)

        local = _segment_rows(pc, dense, num_rows)           # (S, R)
        lo = lax.psum((local & 0xFFFF).sum(axis=0), SLICE_AXIS)
        hi = lax.psum((local >> 16).sum(axis=0), SLICE_AXIS)
        return jnp.stack([lo, hi])

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS), P(SLICE_AXIS),
                  (P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  P(SLICE_AXIS)),
        out_specs=P(),
    )

    @jax.jit
    def row_counts_src(keys, words, src_words_t, src_idx_t, src_hit_t, mask):
        return fn(keys, words, src_words_t, src_idx_t, src_hit_t, mask)

    return row_counts_src


def compile_serve_row_counts_tanimoto(mesh: Mesh, tree_shape,
                                      num_leaves: int, num_rows: int):
    """Jit ALL THREE tanimoto vectors as ONE program: per-row full
    counts, per-row src-intersection counts, and |src| — the fused form
    of the reference's band evaluation inputs (fragment.go:550-608).

    Round 2 ran these as 3-4 separate collectives with a staged-image
    identity re-check between them (a write landing mid-query could zip
    vectors from different generations). One program removes both the
    extra dispatch floors and the consistency window: every vector
    reads the SAME immutable device arrays.

    Returns fn(keys, words — the TopN view's pool —
    src_words_t/src_idx_t/src_hit_t (per src leaf), mask (S,))
    -> (2, 2*num_rows + 1) limb array laid out
       [:, :num_rows]          full per-row counts
       [:, num_rows:2*num_rows] src-intersection per-row counts
       [:, 2*num_rows]          |src|
    — one array, one readback (see combine_count).
    """
    sig = json.dumps(_tree_signature(tree_shape))
    tree = json.loads(sig)
    from ..ops.bitops import fold_tree

    def per_shard(keys, words, src_words_t, src_idx_t, src_hit_t, mask):
        s_l, cap_l = keys.shape

        def leaf(i):
            return _gather_leaf_blocks(src_words_t, src_idx_t, src_hit_t, i)

        src_blk = fold_tree(tree, leaf)                 # (S*16, W)

        # |src|: same limb scheme as compile_serve_count.
        src_pc = lax.population_count(src_blk).sum(
            axis=1, dtype=jnp.uint32).reshape(
            s_l, ROW_SPAN).sum(axis=1, dtype=jnp.uint32)
        src_pc = jnp.where(mask != 0, src_pc, jnp.uint32(0))
        src_lo = (src_pc & jnp.uint32(0xFFFF)).astype(jnp.int32).sum()
        src_hi = (src_pc >> 16).astype(jnp.int32).sum()

        src_per_container, valid = _src_block_per_container(
            keys, src_blk, s_l)
        live = valid & (mask[:, None] != 0)
        inter_pc = jnp.where(live, lax.population_count(
            words & src_per_container).sum(axis=2, dtype=jnp.int32), 0)
        full_pc = jnp.where(live, lax.population_count(words).sum(
            axis=2, dtype=jnp.int32), 0)
        dense = jnp.where(valid, keys // ROW_SPAN, num_rows)

        # (S, 2R): full rows then intersection rows, one psum pair.
        both = jnp.concatenate([_segment_rows(full_pc, dense, num_rows),
                                _segment_rows(inter_pc, dense, num_rows)],
                               axis=1)
        lo = lax.psum((both & 0xFFFF).sum(axis=0), SLICE_AXIS)
        hi = lax.psum((both >> 16).sum(axis=0), SLICE_AXIS)
        lo = jnp.concatenate([lo, lax.psum(src_lo, SLICE_AXIS)[None]])
        hi = jnp.concatenate([hi, lax.psum(src_hi, SLICE_AXIS)[None]])
        return jnp.stack([lo, hi])

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS), P(SLICE_AXIS),
                  (P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  (P(SLICE_AXIS),) * num_leaves,
                  P(SLICE_AXIS)),
        out_specs=P(),
    )

    @jax.jit
    def row_counts_tanimoto(keys, words, src_words_t, src_idx_t, src_hit_t, mask):
        return fn(keys, words, src_words_t, src_idx_t, src_hit_t, mask)

    return row_counts_tanimoto


def compile_serve_row_counts(mesh: Mesh, num_rows: int):
    """Jit masked global per-row counts for one sharded view.

    Returns fn(index: ShardedIndex, mask (S,) int32) -> one (2, num_rows)
    int32 limb array; combine as (out[1].astype(int64) << 16) + out[0]
    on the host (one array = one readback, like combine_count).
    This is the device half of served TopN: the host applies threshold /
    candidate-id / n semantics to the exact totals (reference
    fragment.go:493-625 + executor.go:273-310 collapse into one
    collective + a host sort).
    """
    one = partial(_row_counts_one_slice, num_rows)

    def per_shard(keys, words, mask):
        local = jax.vmap(one)(keys, words)  # (S_local, R) int32
        local = jnp.where(mask[:, None] != 0, local, 0)
        lo = lax.psum((local & 0xFFFF).sum(axis=0), SLICE_AXIS)
        hi = lax.psum((local >> 16).sum(axis=0), SLICE_AXIS)
        return jnp.stack([lo, hi])

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS), P(SLICE_AXIS), P(SLICE_AXIS)),
        out_specs=P(),
    )

    @jax.jit
    def row_counts(index: ShardedIndex, mask):
        return fn(index.keys, index.words, mask)

    return row_counts


def pack_mutation_batches(per_slice, num_slices: int, capacity: int):
    """Stack per-slice plan_slice_mutations outputs into padded (S, B)
    batch arrays for compile_serve_apply_writes, with the width of the
    widest slice's plan (the columns the program has to run) last.

    per_slice: {slice_id: (slot, word, set_mask, clear_mask)}. The
    no-op/width scheme is ops.pool's (pad_mutation_plan): padding rides
    out-of-bounds slots, B is the shared power-of-two width of the
    widest slice's plan.
    """
    from ..ops.pool import mutation_batch_width, pad_mutation_plan

    widest = max((len(v[0]) for v in per_slice.values()), default=0)
    b = mutation_batch_width(widest)
    empty = pad_mutation_plan(
        (np.zeros(0, np.int32), np.zeros(0, np.int32),
         np.zeros(0, np.uint32), np.zeros(0, np.uint32)), capacity, b)
    rows = [per_slice.get(si) for si in range(num_slices)]
    padded = [pad_mutation_plan(r, capacity, b) if r is not None else empty
              for r in rows]
    return (*(np.stack([p[i] for p in padded]) for i in range(4)),
            np.int32(widest))


def compile_serve_apply_writes(mesh: Mesh, donate: bool = False):
    """Jit the scatter of folded set/clear batches into a sharded
    pool's words, where the pool lies.

    fn(words (S, C, W), slot, word, set_mask, clear_mask (S, B), n)
    -> words: (cur & ~clear) | set at each slice's targets, column by
    column for the first n of the B columns (n: the widest real plan,
    pack_mutation_batches' last value; the columns after it are all
    padding and are not run, and B alone keys the compile). Targets
    are unique per slice (plan_slice_mutations) and padding rides
    out-of-bounds slots dropped by the scatter, so the update is exact
    for mixed sets and clears: the device-side half of SetBit /
    ClearBit (reference fragment.go:371-459), one launch per refresh
    instead of a pool re-upload. The keys do not pass through it.

    Why a loop of width-1 scatters and not one scatter of width B:
    for a scatter of 8 or more updates a slice the TPU compiler copies
    the whole pool into another layout ({3,1,2,0}), scatters there and
    copies it back (two pool-sized ops a launch, 12.2 ms for a 1.89 GB
    pool, and a pool-sized temporary), donated or not; a width-1
    scatter runs in the pool's own tiled layout
    (tests/test_tpu_compile.py holds both forms to that). With
    donate=True the words' buffer is the output's and the launch
    touches no byte it does not change, and the caller's array is
    DELETED: only for a pool no reader can still launch on
    (serve.MeshManager._apply_writes decides). With donate=False the
    same program starts from one copy of the pool.
    """

    from ..ops.pool import scatter_words

    def per_shard(words, slot, word, set_mask, clear_mask, n):
        def column(j, w):
            return jax.vmap(scatter_words)(
                w, *(lax.dynamic_slice_in_dim(a, j, 1, axis=1)
                     for a in (slot, word, set_mask, clear_mask)))

        return lax.fori_loop(0, n, column, words)

    fn = shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(SLICE_AXIS),) * 5 + (P(),),
        out_specs=P(SLICE_AXIS),
    )

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def apply_writes(words, slot, word, set_mask, clear_mask, n):
        return fn(words, slot, word, set_mask, clear_mask, n)

    return apply_writes


def pack_container_patches(per_slice, num_slices: int, capacity: int):
    """Stack per-slice (new_keys, new_slots) of
    ops.pool.assign_free_slots into padded (S, K) (slot, key) arrays
    for compile_serve_patch_containers; padding as
    pack_mutation_batches: the out-of-bounds slot `capacity`."""
    from ..ops.pool import mutation_batch_width

    k = mutation_batch_width(max(len(v[0]) for v in per_slice.values()))
    slot = np.full((num_slices, k), capacity, dtype=np.int32)
    key = np.full((num_slices, k), INVALID_KEY, dtype=np.int32)
    for si, (new_keys, new_slots) in per_slice.items():
        slot[si, :len(new_slots)] = new_slots
        key[si, :len(new_keys)] = new_keys
    return slot, key


def compile_serve_patch_containers(mesh: Mesh):
    """Jit the write of created containers' keys into free slots of
    sharded pools: fn(keys (S, C), slot (S, K), key (S, K)) -> keys.
    The slots' words are zero already (ops.pool.assign_free_slots) and
    take the containers' bits through compile_serve_apply_writes, so
    only the (S, C) int32 keys pass through this program; each shard
    writes the slots of its own slices and drops the padding."""

    def one(keys_row, slot, key):
        return keys_row.at[slot].set(key, mode="drop")

    fn = shard_map(
        lambda keys, slot, key: jax.vmap(one)(keys, slot, key),
        mesh=mesh,
        in_specs=(P(SLICE_AXIS),) * 3,
        out_specs=P(SLICE_AXIS),
    )

    @jax.jit
    def patch_containers(keys, slot, key):
        return fn(keys, slot, key)

    return patch_containers


def default_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the first n (default: all) local devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (SLICE_AXIS,))


def sharded_index_from_holder(holder, index: str, frame: str,
                              view: str = "standard",
                              mesh: Optional[Mesh] = None,
                              max_slice: Optional[int] = None):
    """Stage a live frame view's fragments into a mesh-sharded device
    index.

    The H2D bridge between the host data model (Holder > ... > Fragment,
    reference fragment.go mmap-resident storage) and the device
    execution path: every slice 0..max_slice of (index, frame, view) is
    stacked into one ShardedIndex (absent fragments become empty
    shards), sharded over the mesh's slice axis. Returns
    (ShardedIndex, row_ids, staged_slices): row_ids translates real row
    ids to the dense indices compile_mesh_count/compile_mesh_topn use;
    staged_slices is the UNPADDED slice count (the returned
    sharded.num_slices is padded up to a mesh-axis multiple).

    This is the explicit-staging answer to the reference's O(1) mmap
    open (SURVEY.md §7 hard parts): call it once per epoch of queries,
    not per query, and re-stage after bulk writes.

    Only LOCALLY-present fragments are staged: the default max_slice is
    the highest local fragment of (frame, view) — not Index.max_slice(),
    which includes peer-owned slices that would stage as silent zero
    shards on a clustered holder. For a cluster-wide device index,
    stage per node and reduce, or pass max_slice explicitly after
    fetching remote fragments. A view with no fragments yet stages one
    empty shard; a missing index or frame raises KeyError.
    """
    idx_obj = holder.index(index)
    if idx_obj is None:
        raise KeyError(f"index not found: {index}")
    if idx_obj.frame(frame) is None:
        raise KeyError(f"frame not found: {index}/{frame}")
    if max_slice is None:
        v = holder.view(index, frame, view)
        max_slice = v.max_slice() if v is not None else 0
    bitmaps = []
    for s in range(max_slice + 1):
        frag = holder.fragment(index, frame, view, s)
        if frag is None:
            bitmaps.append(None)
            continue
        with frag._mu:
            frag.ensure_loaded()  # lazily-opened fragments parse here
            bitmaps.append(frag.storage)
    sharded, row_ids = build_sharded_index(bitmaps, mesh)
    return sharded, row_ids, len(bitmaps)


def connect_distributed(coordinator_address: Optional[str] = None,
                        num_processes: Optional[int] = None,
                        process_id: Optional[int] = None,
                        heartbeat_timeout_seconds: Optional[int] = None
                        ) -> int:
    """Join this host to the multi-host JAX runtime (the data plane's
    answer to the reference's multi-node HTTP query fan-out).

    After every participating host calls this, jax.devices() — and so
    default_mesh() — spans ALL hosts' chips: the same compile_mesh_*
    computations shard over the global slice axis, with psum riding ICI
    within a pod slice and DCN across hosts, no application-level RPC.
    The host-side control plane (schema broadcast, membership — gossip
    or HTTP) stays as-is; only bulk query compute moves to the global
    mesh. Arguments default to the JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID environment variables (read
    here — jax itself only honors the first), then to JAX's own
    TPU/Slurm/MPI cluster auto-detection.

    Returns this process's index. Call once, before any backend use.
    """
    import os

    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    kw = {}
    if heartbeat_timeout_seconds is None and os.environ.get(
            "PILOSA_TPU_HEARTBEAT_TIMEOUT_S"):
        heartbeat_timeout_seconds = int(
            os.environ["PILOSA_TPU_HEARTBEAT_TIMEOUT_S"])
    if heartbeat_timeout_seconds is not None:
        # Rank-death detection bound: a died peer surfaces as a
        # coordination error on the survivors within this window
        # instead of wedging the next collective indefinitely.
        kw["heartbeat_timeout_seconds"] = heartbeat_timeout_seconds
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)
    return jax.process_index()
