"""FragmentPool: a fragment's containers as fixed-shape device arrays.

The host roaring bitmap (pilosa_tpu.roaring) stays authoritative and
mutable; the pool is its device-resident compute image. Containers are
unified to bitmap form on upload — arrays with n <= 4096 cost 8 KB here,
which buys static shapes, coalesced HBM reads, and elementwise kernels
(the "padded pool + bitmap-only on device" design from SURVEY.md §7).

Key layout: a bit at (row, col) within one slice sits at linear position
pos = row * 2^20 + (col % 2^20) (reference fragment.go:1511-1514), so
container key = pos >> 16 and row r spans exactly keys
[16r, 16r+16) — a row is a gather of <= 16 containers.

Row IDs are arbitrary uint64 on the host, far beyond int32 device keys.
The pool therefore stores DENSE row indices: the host keeps the sorted
array of distinct row IDs present in the fragment (`row_ids`), and a
device key is dense_index*16 + block. Callers translate real row IDs to
dense indices (np.searchsorted on row_ids) before calling device code.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..roaring.bitmap import Bitmap

# uint32 words per container: 2^16 bits / 32.
CONTAINER_WORDS = 2048

# Containers spanned by one slice-row: 2^20 / 2^16.
ROW_SPAN = 16

# Sentinel key for padding entries (larger than any real key so the
# key array stays sorted).
INVALID_KEY = np.int32(2**31 - 1)


class FragmentPool(NamedTuple):
    """Device image of one fragment.

    keys:  (C,) int32, sorted ascending, padded with INVALID_KEY.
           key = dense_row_index * 16 + block (NOT real row id; see module
           docstring).
    words: (C, CONTAINER_WORDS) uint32 bitmap-form containers
    n:     () int32 — number of live containers (<= C)
    """

    keys: jax.Array
    words: jax.Array
    n: jax.Array

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def _round_capacity(n: int) -> int:
    """Pad to the next power of two (min 16) so recompilation only happens
    on doubling, not on every container insert."""
    c = 16
    while c < n:
        c *= 2
    return c


def build_pool_arrays(bitmap: Bitmap, capacity: Optional[int] = None):
    """Host-side packing: roaring bitmap -> (keys, words, n, row_ids).

    row_ids is the sorted uint64 array of distinct real row IDs present;
    device keys are dense_row_index*16 + block.
    """
    n = len(bitmap.keys)
    cap = capacity if capacity is not None else _round_capacity(n)
    if cap < n:
        raise ValueError(f"capacity {cap} < container count {n}")
    real_keys = np.asarray(bitmap.keys, dtype=np.uint64)
    row_ids = np.unique(real_keys >> np.uint64(4))
    dense_row = np.searchsorted(row_ids, real_keys >> np.uint64(4))
    keys = np.full(cap, INVALID_KEY, dtype=np.int32)
    words = np.zeros((cap, CONTAINER_WORDS), dtype=np.uint32)
    for i, c in enumerate(bitmap.containers):
        keys[i] = np.int32(dense_row[i] * ROW_SPAN + int(real_keys[i] & np.uint64(15)))
        # u64[1024] little-endian words -> u32[2048]
        words[i] = c.words().view(np.uint32)
    return keys, words, np.int32(n), row_ids


def build_pool(bitmap: Bitmap, capacity: Optional[int] = None, device=None):
    """Upload a fragment to the device. Returns (FragmentPool, row_ids):
    row_ids stays host-side for real-rowID <-> dense-index translation."""
    keys, words, n, row_ids = build_pool_arrays(bitmap, capacity)
    put = partial(jax.device_put, device=device) if device else jax.device_put
    return FragmentPool(keys=put(keys), words=put(words), n=put(n)), row_ids


@partial(jax.jit, static_argnames=())
def gather_row(pool: FragmentPool, dense_row) -> jax.Array:
    """Materialize dense row index `dense_row` as a (16, 2048) uint32 block.

    TPU analog of Fragment.row's OffsetRange materialization
    (reference fragment.go:332-367) — but a bounded gather instead of a
    container-list walk, so it stays inside jit with static shapes.
    A dense index with no containers (e.g. an absent row mapped to an
    out-of-range index by the caller) gathers all-zero.
    """
    targets = jnp.int32(dense_row) * ROW_SPAN + jnp.arange(ROW_SPAN, dtype=jnp.int32)
    idx = jnp.searchsorted(pool.keys, targets)
    idx = jnp.clip(idx, 0, pool.capacity - 1)
    hit = pool.keys[idx] == targets
    rows = pool.words[idx]  # (16, 2048)
    return jnp.where(hit[:, None], rows, jnp.uint32(0))


def fold_log_entries(entries):
    """Fold a fragment mutation log (op, pos, churn) into final per-bit
    state: (pos uint64, val bool) arrays with last-op-wins semantics.
    Shared by the per-fragment pool update and the mesh serving layer —
    device scatter order is unspecified, so both apply FINAL states,
    never op sequences."""
    final = {}
    for op, pos, _ in entries:
        final[pos] = op == 0
    return (np.fromiter(final.keys(), dtype=np.uint64, count=len(final)),
            np.fromiter(final.values(), dtype=bool, count=len(final)))


def scatter_words(words, slot, word, set_mask, clear_mask):
    """(cur & ~clear) | set at unique (slot, word) targets; padding
    rides out-of-bounds slots dropped by mode="drop". The single
    scatter shared by apply_pool_mutations and the mesh apply-writes
    path."""
    cur = words[slot, word]
    upd = (cur & ~clear_mask) | set_mask
    return words.at[slot, word].set(upd, mode="drop")


class PatchRefused(KeyError):
    """A set targets a container the pool image lacks and cannot be
    given: `reason` is "new_row" (the row is not in the image's dense
    row table, so it has no key) or "no_slot" (the slice's capacity is
    used up). The caller rebuilds the image. A KeyError, as a set of an
    absent container was before containers could be patched in."""

    def __init__(self, reason: str):
        super().__init__(f"set targets a container absent from the pool "
                         f"image ({reason})")
        self.reason = reason


def _container_keys(row_ids: np.ndarray, pos: np.ndarray):
    """Slice-local positions -> (pool key int32, row-is-in-the-table)."""
    rows = pos >> np.uint64(20)
    dense = np.searchsorted(row_ids, rows)
    if len(row_ids):
        known_row = (dense < len(row_ids)) & (
            row_ids[np.minimum(dense, len(row_ids) - 1)] == rows)
    else:
        known_row = np.zeros(len(pos), dtype=bool)
    key = (dense * ROW_SPAN
           + ((pos >> np.uint64(16)) & np.uint64(15)).astype(np.int64)
           ).astype(np.int32)
    return key, known_row


def assign_free_slots(keys_row: np.ndarray, slots_row: np.ndarray,
                      row_ids: np.ndarray, pos: np.ndarray,
                      val: np.ndarray):
    """Give every container that a set of one slice's folded mutations
    targets and the pool image lacks a free slot of the slice.

    THE ORDER OF KEYS AFTER A PATCH: appended, with a host-side order.
    A slice's device keys are sorted as staged, in slots [0, n), and
    every slot from n on is free: INVALID_KEY, zero words (no slot is
    ever given back: an emptied container rebuilds the image). A
    created container takes slot n, then n + 1, ..., wherever its key
    sorts, so no staged container moves and no (idx, hit) resolved
    against them goes stale. The host keeps the slice's keys SORTED
    (`keys_row`, INVALID_KEY-padded) and beside them `slots_row`:
    slots_row[i] is the device slot of keys_row[i] (the identity until
    the first patch). Every host lookup searches keys_row and maps the
    position through slots_row (plan_slice_mutations here,
    mesh.resolve_row_indices, mesh.coarse_row_starts); no device
    program searches keys: each reads its slot's key // 16 and
    key % 16 wherever the slot lies.

    Returns None when every set targets a container the image holds,
    else (keys_row', slots_row', new_keys, new_slots): copies of the two
    rows with the created keys inserted in order, and the (K,) int32
    keys and the slots they were given. Raises PatchRefused.
    """
    pos = np.asarray(pos, dtype=np.uint64)
    sets = pos[np.asarray(val, dtype=bool)]
    key, known_row = _container_keys(row_ids, sets)
    cap = keys_row.shape[0]
    at = np.searchsorted(keys_row, key)
    held = known_row & (at < cap) & (keys_row[np.minimum(at, cap - 1)] == key)
    if held.all():
        return None
    if not known_row.all():
        raise PatchRefused("new_row")
    new_keys = np.unique(key[~held])
    n = int(np.searchsorted(keys_row, INVALID_KEY))  # live containers
    if n + len(new_keys) > cap:
        raise PatchRefused("no_slot")
    taken = n + len(new_keys)
    new_slots = np.arange(n, taken, dtype=np.int32)
    at = np.searchsorted(keys_row[:n], new_keys)
    return (np.concatenate([np.insert(keys_row[:n], at, new_keys),
                            keys_row[taken:]]),
            np.concatenate([np.insert(slots_row[:n], at, new_slots),
                            slots_row[taken:]]),
            new_keys, new_slots)


def plan_slice_mutations(keys_row: np.ndarray, row_ids: np.ndarray,
                         pos: np.ndarray, val: np.ndarray,
                         slots_row: Optional[np.ndarray] = None):
    """Fold one slice's mutations into a (slot, word, set_mask,
    clear_mask) scatter plan against an existing pool image.

    pos: slice-local linear positions (row*2^20 + col%2^20); val: the
    FINAL bit value for each pos (callers fold their write log first so
    a set-then-clear nets to one clear — device scatter order is
    unspecified, final-state folding makes it irrelevant). Targets are
    grouped per (container slot, word): a word receiving both sets and
    clears gets both masks in ONE entry, so the device's
    (cur & ~clear) | set is exact. This is the device-side half of
    SetBit/ClearBit (reference fragment.go:371-459) — batched scatter
    instead of a full pool re-upload.

    keys_row: the pool's sorted (INVALID_KEY-padded) key array;
    row_ids: the pool's dense row table; slots_row: the device slot of
    each entry of keys_row where containers were patched in
    (assign_free_slots has the layout; None = the position itself).
    Returns unpadded 1-D arrays.
    Raises KeyError when a set targets a row/container absent from the
    pool (stale image — caller rebuilds, or assigns it a free slot
    first); clears of absent containers are dropped (nothing to clear,
    matching roaring remove of a missing container key).
    """
    pos = np.asarray(pos, dtype=np.uint64)
    val = np.asarray(val, dtype=bool)
    key, known_row = _container_keys(row_ids, pos)
    sl = np.searchsorted(keys_row, key).astype(np.int64)
    known = known_row & (sl < keys_row.shape[0]) & (
        keys_row[np.minimum(sl, keys_row.shape[0] - 1)] == key)
    if np.any(val & ~known):
        raise KeyError("set targets a container absent from the pool image")
    sl, pos, val = sl[known], pos[known], val[known]
    if slots_row is not None:
        sl = slots_row[sl].astype(np.int64)
    wd = ((pos & np.uint64(0xFFFF)) >> np.uint64(5)).astype(np.int32)
    bit = np.uint32(1) << (pos & np.uint64(31)).astype(np.uint32)

    flat = sl * CONTAINER_WORDS + wd
    order = np.argsort(flat, kind="stable")
    flat, sl, wd, bit, val = (flat[order], sl[order], wd[order], bit[order],
                              val[order])
    uniq, start = np.unique(flat, return_index=True)
    set_mask = np.zeros(len(uniq), dtype=np.uint32)
    clear_mask = np.zeros(len(uniq), dtype=np.uint32)
    group = np.searchsorted(uniq, flat)
    np.bitwise_or.at(set_mask, group[val], bit[val])
    np.bitwise_or.at(clear_mask, group[~val], bit[~val])
    return (sl[start].astype(np.int32), wd[start], set_mask, clear_mask)


def mutation_batch_width(n: int, min_batch: int = 8) -> int:
    """Power-of-two batch width >= n: jit recompiles on batch-size
    doubling, not on every distinct batch size."""
    b = min_batch
    while b < n:
        b *= 2
    return b


def pad_mutation_plan(plan, capacity: int, width: int = None):
    """Pad a plan_slice_mutations result to `width` (default: the
    power-of-two of its own length).

    Padding entries use slot = capacity — out of bounds, so the jitted
    scatter drops them (mode="drop"): a no-op encoded without colliding
    with any real target.
    """
    sl, wd, sm, cm = plan
    b = mutation_batch_width(len(sl)) if width is None else width
    slot = np.full(b, capacity, dtype=np.int32)
    word = np.zeros(b, dtype=np.int32)
    set_mask = np.zeros(b, dtype=np.uint32)
    clear_mask = np.zeros(b, dtype=np.uint32)
    n = len(sl)
    slot[:n], word[:n], set_mask[:n], clear_mask[:n] = sl, wd, sm, cm
    return slot, word, set_mask, clear_mask


@jax.jit
def apply_pool_mutations(pool: FragmentPool, slot, word, set_mask,
                         clear_mask) -> FragmentPool:
    """Scatter a folded mutation batch into one pool's words.

    Targets are unique (plan_slice_mutations) and padding rides
    out-of-bounds slots dropped by the scatter, so the update is exact
    for mixed sets and clears.
    """
    return pool._replace(
        words=scatter_words(pool.words, slot, word, set_mask, clear_mask))


@partial(jax.jit, static_argnames=("num_rows",))
def pool_row_counts(pool: FragmentPool, num_rows: int) -> jax.Array:
    """Per-dense-row bit counts over the whole pool: popcount each
    container, segment-sum by dense row (key >> 4). Feeds TopN (reference
    fragment.go:493-625 walks the rank cache; on device we can afford the
    exact scan). num_rows is the dense row count (len(row_ids))."""
    per_container = jax.lax.population_count(pool.words).sum(
        axis=1, dtype=jnp.int32
    )
    valid = pool.keys != INVALID_KEY
    dense = jnp.where(valid, pool.keys // ROW_SPAN, num_rows)
    return jax.ops.segment_sum(
        jnp.where(valid, per_container, 0),
        dense,
        num_segments=num_rows + 1,
    )[:num_rows]
