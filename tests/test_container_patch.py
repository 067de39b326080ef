"""A container that a SetBit creates is patched into a free slot of the
staged pool (serve._refresh_walk, ops.pool.assign_free_slots,
mesh.compile_serve_patch_containers), not restaged for.

The order of keys after a patch is "appended, with a host-side order":
the device keeps a slice's staged keys where they were and the created
ones behind them, the host keeps them sorted beside the slot of each.
Every reader is held to that here: the patched view has to answer as a
freshly staged one and as the host's roaring path, on one device and on
a mesh of four.
"""

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.pool import (CONTAINER_WORDS, INVALID_KEY, ROW_SPAN,
                                 PatchRefused, assign_free_slots,
                                 plan_slice_mutations)
from pilosa_tpu.pql import parse_string

KEY = ("i", "general", "standard")
ROWS, SLICES = 6, 6


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def q(executor, pql):
    return executor.execute("i", parse_string(pql))


def served(holder, devices):
    """An executor whose mesh spans `devices` CPU devices, its gate
    deterministic (a measured gate may restage a tiny pool at will)."""
    from pilosa_tpu.parallel.mesh import default_mesh
    from pilosa_tpu.parallel.serve import MeshManager

    e = Executor(holder, use_device=True)
    e._mesh_mgr = MeshManager(holder, mesh=default_mesh(devices))
    e._mesh_mgr.deterministic_gate = True
    return e, e._mesh_mgr


def populate(holder, rng):
    """ROWS rows over SLICES slices, a container in a slice's first
    block for about two thirds of the (row, slice) pairs, row 0
    everywhere (so the view's row table holds every row)."""
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    held = set()
    for r in range(ROWS):
        for s in range(SLICES):
            if r == 0 or s == r % SLICES or rng.random() < 0.6:
                held.add((r, s, 0))
                for c in rng.choice(65536, size=int(rng.integers(1, 40)),
                                    replace=False):
                    f.set_bit(r, s * SLICE_WIDTH + int(c))
    return f, held


def answers(executor):
    out = [q(executor, f"Count(Bitmap(rowID={r}))") for r in range(ROWS)]
    out.append(q(executor, "Count(Union(" + ", ".join(
        f"Bitmap(rowID={r})" for r in range(ROWS)) + "))"))
    out.append(q(executor, "Count(Intersect(Bitmap(rowID=0), "
                           "Bitmap(rowID=1)))"))
    out.append(q(executor, "TopN(frame=general, n=4)"))
    out.append(q(executor, "TopN(Bitmap(rowID=0), frame=general, n=6)"))
    return out


def keys_hold_their_order(sv):
    """Host keys sorted; their slots a permutation of [0, live); the
    device holds each key in its slot and nothing behind the live ones;
    a free slot's words are zero."""
    dev_keys = np.asarray(sv.sharded.keys)
    dev_words = np.asarray(sv.sharded.words)
    cap = sv.keys_host.shape[1]
    for s in range(sv.padded_slices):
        keys, slots = sv.keys_host[s], sv.slots_host[s]
        live = int((keys != INVALID_KEY).sum())
        assert (np.diff(keys[:live].astype(np.int64)) > 0).all()
        assert (keys[live:] == INVALID_KEY).all()
        assert sorted(slots[:live].tolist()) == list(range(live))
        assert (dev_keys[s, slots[:live]] == keys[:live]).all()
        assert (dev_keys[s, live:] == INVALID_KEY).all()
        assert not dev_words[s, live:].any()
        assert sv.free_slots[s] == cap - live


@pytest.mark.parametrize("devices,seed", [(1, 11), (1, 12), (4, 13),
                                          (4, 14)])
def test_patched_view_answers_as_a_fresh_one_and_as_the_host(holder, devices,
                                                             seed):
    """A seeded random sequence of SetBits, most of which create a
    container (in a slice the row is absent from, or in another block
    of a slice), mixed with ones that do not: after every write the
    patched view answers each Count, both forms of TopN and row_counts
    as the host does, and at the end as a freshly staged view does;
    nothing was restaged, and the keys hold their stated order."""
    rng = np.random.default_rng(seed)
    f, held = populate(holder, rng)
    e, mgr = served(holder, devices)
    host = Executor(holder, use_device=False)
    assert answers(e) == answers(host)
    created = 0
    for _ in range(14):
        r, s = int(rng.integers(ROWS)), int(rng.integers(SLICES))
        block = 0 if rng.random() < 0.6 else int(rng.integers(1, 16))
        col = s * SLICE_WIDTH + block * 65536 + int(rng.integers(65536))
        created += (r, s, block) not in held
        held.add((r, s, block))
        f.set_bit(r, col)
        if rng.random() < 0.5:  # a second write into the same window
            f.set_bit(0, s * SLICE_WIDTH + int(rng.integers(65536)))
        assert answers(e) == answers(host)
        keys_hold_their_order(mgr._views[KEY])
    assert created >= 5
    assert mgr.stats["stage"] == 1
    assert mgr.stats["container_patches"] == created
    sv = mgr._views[KEY]
    assert mgr.stats["free_slots_min"] == int(sv.free_slots[:SLICES].min())
    ids, counts = mgr.row_counts(*KEY, list(range(SLICES)), SLICES)
    fresh_e, fresh = served(holder, devices)
    assert answers(fresh_e) == answers(e)
    ids2, counts2 = fresh.row_counts(*KEY, list(range(SLICES)), SLICES)
    assert (ids == ids2).all() and (counts == counts2).all()
    # The live-slot account of /metrics counts a patched container as
    # a staged one.
    assert mgr.device_memory()["live_bytes"] \
        == fresh.device_memory()["live_bytes"]


def test_created_container_holds_the_logs_set_bits_for_its_key(holder):
    """The log is all the patch needs: the words of a created container
    are the surviving sets the log holds for its key since the staged
    generation, over the zero words of a free slot."""
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    f.set_bit(0, 3)
    f.set_bit(1, 5)
    e, mgr = served(holder, 1)
    assert q(e, "Count(Bitmap(rowID=1))") == [1]
    frag = holder.fragment(*KEY, 0)
    staged_gen = mgr._views[KEY].slice_gens[0][1]
    base = 2 * 65536                       # block 2 of row 1: no container
    for c in (7, 7, 40, 65535, 2048):
        f.set_bit(1, base + c)
    f.clear_bit(1, base + 40)              # cleared again, not emptied
    f.set_bit(0, 9)                        # and a write to what was there
    assert q(e, "Count(Bitmap(rowID=1))") == [4]
    assert mgr.stats["stage"] == 1 and mgr.stats["container_patches"] == 1
    sv = mgr._views[KEY]
    key = 1 * ROW_SPAN + 2
    at = int(np.searchsorted(sv.keys_host[0], key))
    assert sv.keys_host[0, at] == key
    slot = int(sv.slots_host[0, at])
    assert slot == 2                       # behind the two staged ones
    words = np.asarray(sv.sharded.words)[0, slot]
    final = {}
    for op, pos, _ in frag.log_since(staged_gen):
        if pos >> 16 == (1 << 4) + 2:      # roaring key of (row 1, block 2)
            final[pos & 0xFFFF] = op == 0
    want = np.zeros(CONTAINER_WORDS, dtype=np.uint32)
    for bit in (b for b, on in final.items() if on):
        want[bit >> 5] |= np.uint32(1) << np.uint32(bit & 31)
    assert sorted(b for b, on in final.items() if on) == [7, 2048, 65535]
    assert (words == want).all()


def test_full_slice_restages_and_has_free_slots_again(holder):
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    for block in range(14):                # 14 of slice 0's 16 slots
        f.set_bit(0, block * 65536)
    f.set_bit(0, SLICE_WIDTH)
    e, mgr = served(holder, 1)
    host = Executor(holder, use_device=False)
    assert q(e, "Count(Bitmap(rowID=0))") == [15]
    assert mgr.stats["free_slots_min"] == 2
    for n, block in enumerate((14, 15), start=1):
        f.set_bit(0, block * 65536 + 1)
        assert q(e, "Count(Bitmap(rowID=0))") == [15 + n]
        assert mgr.stats["free_slots_min"] == 2 - n
    assert mgr.stats["stage"] == 1 and mgr.stats["container_patches"] == 2
    # The slice is full: the next created container cannot be patched in.
    f.set_bit(0, SLICE_WIDTH + 65536)      # slice 1 still has room
    f.set_bit(0, SLICE_WIDTH + 3 * 65536)
    assert q(e, "Count(Bitmap(rowID=0))") == [19]
    assert mgr.stats["stage"] == 1
    f.set_bit(1, 5)                        # row 1: new to the view
    assert q(e, "Count(Bitmap(rowID=1))") == [1]
    assert mgr.stats["stage"] == 2
    assert mgr.stats["container_patch_refused_new_row"] == 1
    f.set_bit(1, 65536 + 5)                # 18th container of slice 0
    f.set_bit(1, 2 * 65536 + 5)
    assert q(e, "Count(Bitmap(rowID=1))") == [3]
    assert mgr.stats["stage"] == 2         # cap 32 since the restage
    sv = mgr._views[KEY]
    assert sv.keys_host.shape[1] == 32
    for block in range(3, 16):             # fill slice 0 up: 32 of 32
        f.set_bit(1, block * 65536 + 5)
    assert q(e, "Count(Bitmap(rowID=1))") == [16]
    assert mgr.stats["stage"] == 2 and mgr.stats["free_slots_min"] == 0
    f.set_bit(2, 5)                        # known to nobody: a restage
    f.set_bit(0, 9)
    assert answers(e) == answers(host)
    assert mgr.stats["stage"] == 3
    f.set_bit(2, 65536)                    # row 2 is staged now
    assert answers(e) == answers(host)
    assert mgr.stats["stage"] == 3         # 34 containers: cap 48
    assert mgr.stats["free_slots_min"] == 14
    keys_hold_their_order(mgr._views[KEY])


def test_no_slot_is_counted_and_restaged(holder):
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    for block in range(16):
        f.set_bit(0, block * 65536)
    f.set_bit(1, SLICE_WIDTH)              # row 1 is in the row table
    e, mgr = served(holder, 1)
    assert q(e, "Count(Bitmap(rowID=0))") == [16]
    assert mgr.stats["free_slots_min"] == 0
    f.set_bit(1, 7)                        # slice 0 is full
    assert q(e, "Count(Bitmap(rowID=1))") == [2]
    assert mgr.stats["stage"] == 2
    assert mgr.stats["container_patch_refused_no_slot"] == 1
    assert mgr.stats["container_patches"] == 0
    assert mgr.stats["free_slots_min"] == 15   # 17 containers: cap 32


def test_emptied_container_still_restages(holder):
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    f.set_bit(0, 3)
    f.set_bit(0, 65536 + 3)
    e, mgr = served(holder, 1)
    assert q(e, "Count(Bitmap(rowID=0))") == [2]
    f.clear_bit(0, 65536 + 3)
    assert q(e, "Count(Bitmap(rowID=0))") == [1]
    assert mgr.stats["stage"] == 2 and mgr.stats["container_patches"] == 0
    # ... and a container created and emptied inside one window too.
    f.set_bit(0, 5 * 65536)
    f.clear_bit(0, 5 * 65536)
    assert q(e, "Count(Bitmap(rowID=0))") == [1]
    assert mgr.stats["stage"] == 3


def test_sparse_view_refuses_by_format(holder, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_SPARSE_THRESHOLD", "0.5")
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    rng = np.random.default_rng(3)
    for r in range(3):
        for c in rng.choice(65536, size=600, replace=False):
            f.set_bit(r, int(c))
    e, mgr = served(holder, 1)
    host = Executor(holder, use_device=False)
    assert q(e, "Count(Bitmap(rowID=0))") == [600]
    if mgr._views[KEY].sparse is None:
        pytest.skip("the stager kept this view dense")
    f.set_bit(1, 65536 + 1)
    assert q(e, "Count(Bitmap(rowID=1))") \
        == q(host, "Count(Bitmap(rowID=1))") == [601]
    assert mgr.stats["container_patch_refused_format"] == 1
    assert mgr.stats["container_patches"] == 0


def test_patch_lands_on_the_shard_that_holds_the_slice(holder):
    """Four devices, eight slices, two a device: the created key is
    written by the device that holds its slice, and by no other."""
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    for s in range(8):
        f.set_bit(0, s * SLICE_WIDTH)
        f.set_bit(1, s * SLICE_WIDTH + 1)
    e, mgr = served(holder, 4)
    assert q(e, "Count(Bitmap(rowID=0))") == [8]
    before = {sh.index[0].start: np.array(sh.data)   # a copy: the buffer goes
              for sh in mgr._views[KEY].sharded.keys.addressable_shards}
    assert sorted(before) == [0, 2, 4, 6]
    f.set_bit(1, 5 * SLICE_WIDTH + 3 * 65536)    # slice 5: device 2's
    assert q(e, "Count(Bitmap(rowID=1))") == [9]
    assert mgr.stats["stage"] == 1 and mgr.stats["container_patches"] == 1
    sv = mgr._views[KEY]
    for sh in sv.sharded.keys.addressable_shards:
        lo = sh.index[0].start
        got = np.asarray(sh.data)
        if lo == 4:
            assert got[1, 2] == 1 * ROW_SPAN + 3
            got = got.copy()
            got[1, 2] = INVALID_KEY
        assert (got == before[lo]).all()
    words = {sh.index[0].start: sh for sh in
             sv.sharded.words.addressable_shards}
    assert np.asarray(words[4].data)[1, 2].any()
    assert words[4].device == next(
        sh.device for sh in sv.sharded.keys.addressable_shards
        if sh.index[0].start == 4)


def test_patched_row_is_resolved_again_and_the_others_are_kept(holder):
    """A patch moves no staged container: what was resolved for other
    rows stays cached; the row that gained a container is resolved
    again, on both the device-side and the host-side cache."""
    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    for r in range(3):
        f.set_bit(r, r)
    e, mgr = served(holder, 1)
    for r in range(3):
        assert q(e, f"Count(Bitmap(rowID={r}))") == [1]
    sv = mgr._views[KEY]
    with mgr._mu:
        for r in range(3):
            mgr._leaf_arrays(sv, r)
            mgr._leaf_host_arrays(sv, r)
    kept = {r: (sv.idx_cache[r], sv.host_idx_cache[r]) for r in (0, 2)}
    f.set_bit(1, 4 * 65536)
    assert q(e, "Count(Bitmap(rowID=1))") == [2]
    assert mgr.stats["container_patches"] == 1
    for r, (dev, host_) in kept.items():
        assert sv.idx_cache[r] is dev and sv.host_idx_cache[r] is host_
    with mgr._mu:
        idx, hit = mgr._leaf_host_arrays(sv, 1)
    assert hit[0].tolist() == [1, 0, 0, 0, 1] + [0] * 11
    assert idx[0, 0] == 1 and idx[0, 4] == 3   # slot 3: behind the staged


# -- the plan, the lookups -----------------------------------------------------

def _rows(*keys, cap=8):
    row = np.full(cap, INVALID_KEY, dtype=np.int32)
    row[:len(keys)] = keys
    return row, np.arange(cap, dtype=np.int32)


def test_assign_free_slots_appends_and_keeps_the_host_sorted():
    keys, slots = _rows(0, 16, 48)
    row_ids = np.arange(4, dtype=np.uint64)
    pos = np.array([(2 << 20) + 5, (1 << 20) + 65536 + 9, (2 << 20) + 70,
                    (0 << 20) + 1], dtype=np.uint64)
    val = np.array([True, True, True, True])
    assert assign_free_slots(keys, slots, row_ids, pos[3:], val[3:]) is None
    assert assign_free_slots(keys, slots, row_ids, pos[:1],
                             np.array([False])) is None   # a clear
    k2, s2, new_keys, new_slots = assign_free_slots(keys, slots, row_ids,
                                                    pos, val)
    assert new_keys.tolist() == [17, 32] and new_slots.tolist() == [3, 4]
    assert k2.tolist() == [0, 16, 17, 32, 48] + [INVALID_KEY] * 3
    assert s2.tolist() == [0, 1, 3, 4, 2, 5, 6, 7]
    assert keys.tolist()[:4] == [0, 16, 48, INVALID_KEY]  # inputs untouched
    # ... and the scatter plan goes to the slots, not the positions.
    slot, word, sm, cm = plan_slice_mutations(k2, row_ids, pos, val, s2)
    assert list(zip(slot.tolist(), word.tolist(), sm.tolist())) == [
        (0, 0, 1 << 1), (3, 0, 1 << 9), (4, 0, 1 << 5), (4, 2, 1 << 6)]
    assert not cm.any()
    # a second patch goes on appending
    k3, s3, nk, ns = assign_free_slots(k2, s2, row_ids, np.array(
        [(0 << 20) + 65536], dtype=np.uint64), np.array([True]))
    assert nk.tolist() == [1] and ns.tolist() == [5]
    assert k3.tolist()[:6] == [0, 1, 16, 17, 32, 48]
    assert s3.tolist()[:6] == [0, 5, 1, 3, 4, 2]


@pytest.mark.parametrize("pos,reason", [((9 << 20) + 1, "new_row"),
                                        ((3 << 20) + 1, "no_slot")])
def test_assign_free_slots_refuses(pos, reason):
    keys, slots = _rows(0, 16, 32, cap=3)
    with pytest.raises(PatchRefused) as e:
        assign_free_slots(keys, slots, np.arange(4, dtype=np.uint64),
                          np.array([pos], dtype=np.uint64),
                          np.array([True]))
    assert e.value.reason == reason and isinstance(e.value, KeyError)


def test_lookups_go_through_the_slots():
    from pilosa_tpu.parallel.mesh import (coarse_row_starts,
                                          resolve_row_indices)

    cap = 48
    keys = np.full((2, cap), INVALID_KEY, dtype=np.int32)
    slots = np.tile(np.arange(cap, dtype=np.int32), (2, 1))
    keys[:, :16] = np.arange(16)               # row 0, whole, both slices
    keys[:, 16:32] = 32 + np.arange(16)        # row 2, whole
    # Slice 1 gains (row 1, block 3), appended at slot 32.
    keys[1, 16:33] = np.concatenate([[16 + 3], 32 + np.arange(16)])
    slots[1, 16:33] = np.concatenate([[32], np.arange(16, 32)])
    idx, hit = resolve_row_indices(keys, 1, slots)
    assert hit[0].sum() == 0 and hit[1].tolist() == [0, 0, 0, 1] + [0] * 12
    assert idx[1, 3] == 32
    idx, hit = resolve_row_indices(keys, 2, slots)
    assert hit.all() and (idx == 16 + np.arange(16)).all()
    # Row 2 is still one aligned run of slots in both slices, though
    # its sorted position in slice 1 is 17: coarse by slot.
    starts, valid = coarse_row_starts(keys, 2, slots)
    assert starts.tolist() == [1, 1] and valid.tolist() == [1, 1]
    assert coarse_row_starts(keys, 2) is None  # by position it is not
    assert coarse_row_starts(keys, 1, slots) is None  # a partial row
    # A whole row whose last container was appended is no run of slots.
    keys2 = np.full((1, cap), INVALID_KEY, dtype=np.int32)
    keys2[0, :32] = np.arange(32)
    slots2 = np.arange(cap, dtype=np.int32)[None, :].copy()
    slots2[0, 15:32] = np.concatenate([[31], np.arange(15, 31)])
    assert coarse_row_starts(keys2, 0, slots2) is None
    assert coarse_row_starts(keys2, 1, slots2) is None
