"""The refresh after a write costs what was written: a view's
`WriteCounter` records the slice of each write (core.fragment,
`_MutationEpoch.written_since`), and serve._refresh_walk visits only the
slices recorded since the staged view was last brought up to date
(`MeshManager._slices_written`), or none after a write to another view.
Where the record cannot say (the structural epoch moved, the ring was
overrun, nothing recorded) it walks every slice, as it always did.

Every test holds the answers to the host's roaring path, and where a
refresh path is at stake, to a manager that walks every slice each time
(`FullWalk`, the walk as it was before the record): the two have to
give the same answers and restage for the same writes.
"""

import sys
import threading
from collections import Counter

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import MUTATION_EPOCH, WRITE_RECORD, WriteCounter
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.pool import INVALID_KEY
from pilosa_tpu.parallel.mesh import (default_mesh, pack_container_patches,
                                      pack_mutation_batches)
from pilosa_tpu.parallel.serve import MeshManager
from pilosa_tpu.pql import parse_string

SLICES = 6
KEY = ("i", "f", "standard")


class FullWalk(MeshManager):
    """The reference: every refresh past the epoch fast path visits every
    slice, as before the write record."""

    def _slices_written(self, sv, ep, writes, num_slices):
        return (None if writes is None else (writes, writes.n),
                range(num_slices))


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def q(executor, pql):
    return executor.execute("i", parse_string(pql))


def served(holder, cls=MeshManager, devices=1, deterministic=True):
    """An executor on a manager of its own. The gate deterministic
    where two managers must restage for the same writes (a measured
    gate may restage a tiny pool at will); a sorted-array view's writes
    need the measured one (under the deterministic gate they restage)."""
    e = Executor(holder, use_device=True)
    e._mesh_mgr = cls(holder, mesh=default_mesh(devices))
    e._mesh_mgr.deterministic_gate = deterministic
    return e, e._mesh_mgr


def populate(holder, frames=("f",), rows=4, slices=SLICES, seed=5):
    """`rows` rows in each frame, a few bits in every slice's first
    block for each row (so a write to block 0 never creates a
    container)."""
    rng = np.random.default_rng(seed)
    idx = holder.create_index_if_not_exists("i")
    out = []
    for name in frames:
        f = idx.create_frame_if_not_exists(name)
        for r in range(rows):
            for s in range(slices):
                for c in rng.choice(4096, size=3, replace=False):
                    f.set_bit(r, s * SLICE_WIDTH + int(c))
        out.append(f)
    return out


def answers(e, frame="f", rows=4):
    out = [q(e, f"Count(Bitmap(rowID={r}, frame={frame}))")
           for r in range(rows)]
    out.append(q(e, f"Count(Union(Bitmap(rowID=0, frame={frame}), "
                    f"Bitmap(rowID=1, frame={frame})))"))
    out.append(q(e, f"TopN(frame={frame}, n=3)"))
    return out


def visits(mgr):
    st = mgr.stats
    return (st.get("refresh_walks", 0), st["refresh_walk_fragments"],
            st["refresh_full_walks"], st["stage"])


# -- the record ------------------------------------------------------------------


@pytest.mark.parametrize("writes,gap", [(5, 5), (5, 3), (5, 0),
                                        (WRITE_RECORD - 3, 40),
                                        (WRITE_RECORD + 7, WRITE_RECORD),
                                        (3 * WRITE_RECORD + 1, WRITE_RECORD),
                                        (3 * WRITE_RECORD + 1,
                                         WRITE_RECORD + 1),
                                        (WRITE_RECORD + 7, 2 * WRITE_RECORD)])
def test_record_names_the_slice_of_each_write_while_it_reaches_back(writes,
                                                                    gap):
    """The ring across its wrap: the slices of the last `gap` writes, in
    order, while `gap` is within the ring; None beyond it."""
    w = WriteCounter()
    slices = [(7 * i + 3) % 97 for i in range(writes)]
    for s in slices:
        MUTATION_EPOCH.bump(w, s)
    n, got = MUTATION_EPOCH.written_since(w, writes - gap)
    assert n == writes == w.n
    if gap <= min(writes, WRITE_RECORD):
        assert got == slices[writes - gap:]
    else:
        assert got is None


def test_a_counter_never_written_has_nothing_to_say_but_its_n():
    w = WriteCounter()
    assert MUTATION_EPOCH.written_since(w, 0) == (0, [])
    assert MUTATION_EPOCH.written_since(w, -1) == (0, None)


# -- what a refresh visits --------------------------------------------------------


def test_a_write_to_one_slice_visits_one_fragment(holder):
    (f,) = populate(holder)
    e, mgr = served(holder)
    host = Executor(holder, use_device=False)
    assert answers(e) == answers(host)
    for s in (0, 3, 5, 3):
        before = visits(mgr)
        f.set_bit(2, s * SLICE_WIDTH + 17)
        assert q(e, "Count(Bitmap(rowID=2, frame=f))") \
            == q(host, "Count(Bitmap(rowID=2, frame=f))")
        assert visits(mgr) == (before[0] + 1, before[1] + 1, before[2],
                               before[3])
    # Three writes to two slices between two reads: two visits.
    before = visits(mgr)
    for s in (1, 4, 1):
        f.set_bit(0, s * SLICE_WIDTH + 99 + s)
    assert answers(e) == answers(host)
    assert visits(mgr)[1] == before[1] + 2
    assert mgr.stats["incremental"] > 0


def test_a_write_to_another_view_leaves_the_refresh_o1(holder):
    f, g = populate(holder, frames=("f", "g"))
    e, mgr = served(holder)
    host = Executor(holder, use_device=False)
    assert answers(e, "g") == answers(host, "g")
    assert answers(e) == answers(host)
    looked_up = []
    real = holder.fragment
    holder.fragment = lambda *a: (looked_up.append(a), real(*a))[1]
    try:
        before = visits(mgr)
        f.set_bit(1, 2 * SLICE_WIDTH + 5)
        sv = mgr.refresh("i", "g", "standard", SLICES)
        assert looked_up == []
        assert visits(mgr) == (before[0] + 1, before[1], before[2],
                               before[3])
        assert sv.validated_epoch == MUTATION_EPOCH.read()
        assert mgr.refresh("i", "g", "standard", SLICES) is sv
        assert visits(mgr)[0] == before[0] + 1  # the fast path again
    finally:
        holder.fragment = real
    assert answers(e, "g") == answers(host, "g")
    assert answers(e) == answers(host)
    assert visits(mgr)[1] == before[1] + 1


def test_writes_that_overrun_the_record_walk_every_slice(holder):
    (f,) = populate(holder)
    e, mgr = served(holder)
    ref, _ = served(holder, FullWalk)
    host = Executor(holder, use_device=False)
    assert answers(e) == answers(host) == answers(ref)
    before = visits(mgr)
    for i in range(WRITE_RECORD + 10):
        f.set_bit(i % 4, (i % SLICE_WIDTH) + 5 * SLICE_WIDTH)
    assert answers(e) == answers(host) == answers(ref)
    walks, frags, full, stage = visits(mgr)
    assert (full, mgr.stats["refresh_full_walks_overrun"]) == (
        before[2] + 1, 1)
    assert frags == before[1] + SLICES and stage == before[3]
    # and the record serves again from there on
    f.set_bit(3, SLICE_WIDTH + 1)
    assert answers(e) == answers(host)
    assert visits(mgr)[1:3] == (frags + 1, full)


def test_a_fragment_created_in_a_new_slice_restages(holder):
    """Slices 0 and 2 hold fragments, 1 does not; a write there creates
    one (the structural epoch moves): every slice is walked, the new
    fragment found and the view restaged, as before the record."""
    f = holder.create_index_if_not_exists("i").create_frame_if_not_exists(
        "f")
    for s in (0, 2):
        f.set_bit(1, s * SLICE_WIDTH + 7)
    e, mgr = served(holder)
    ref, rmgr = served(holder, FullWalk)
    host = Executor(holder, use_device=False)
    text = "Count(Bitmap(rowID=1, frame=f))"
    assert q(e, text) == q(ref, text) == q(host, text) == [2]
    stages = mgr.stats["stage"], rmgr.stats["stage"]
    f.set_bit(1, SLICE_WIDTH + 9)
    assert q(e, text) == q(ref, text) == q(host, text) == [3]
    assert (mgr.stats["stage"], rmgr.stats["stage"]) == (
        stages[0] + 1, stages[1] + 1)
    assert mgr.stats["refresh_full_walks_structural"] == 1


@pytest.mark.parametrize("devices", [1, 4])
def test_emptied_and_created_containers_as_the_full_walk(holder, devices):
    """An emptied container restages and a created one is patched, under
    the record exactly as under the full walk: same answers, same
    restages, same patches; the record visits only written slices."""
    (f,) = populate(holder)
    e, mgr = served(holder, devices=devices)
    ref, rmgr = served(holder, FullWalk, devices=devices)
    host = Executor(holder, use_device=False)
    assert answers(e) == answers(ref) == answers(host)
    rng = np.random.default_rng(8)
    for i in range(24):
        r, s = int(rng.integers(4)), int(rng.integers(SLICES))
        if i % 6 == 5:
            # empty row r's first container of slice s: restage
            for p in holder.fragment(*KEY, s).storage.slice().tolist():
                col = p & (SLICE_WIDTH - 1)
                if p >> 20 == r and col < 65536:
                    f.clear_bit(r, s * SLICE_WIDTH + col)
        elif i % 3 == 0:  # a container in a block the row lacks: a patch
            f.set_bit(r, s * SLICE_WIDTH + int(rng.integers(1, 16)) * 65536
                      + int(rng.integers(65536)))
        else:
            f.set_bit(r, s * SLICE_WIDTH + int(rng.integers(4096)))
        before = visits(mgr)
        assert answers(e) == answers(ref) == answers(host)
        assert visits(mgr)[1] - before[1] <= 1
    for k in ("stage", "container_patches", "incremental"):
        assert mgr.stats[k] == rmgr.stats[k], k
    assert mgr.stats["container_patches"] > 0
    assert mgr.stats["stage"] > 1
    assert rmgr.stats["refresh_walk_fragments"] \
        > 4 * mgr.stats["refresh_walk_fragments"]


def test_sparse_view_writes_as_the_full_walk(holder):
    """A sorted-array view (a few bits a row and container): stars into
    containers that are there and ones they create go through
    _refresh_sparse under the record as under the full walk."""
    rng = np.random.default_rng(4)
    star = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("star")
    for s in range(3):
        for u in range(24):
            for c in rng.integers(0, 8 * 65536, size=int(rng.integers(60,
                                                                    100))):
                star.set_bit(u, s * SLICE_WIDTH + int(c))
    e, mgr = served(holder, deterministic=False)
    ref, rmgr = served(holder, FullWalk, deterministic=False)
    host = Executor(holder, use_device=False)

    def counts(ex):
        return [q(ex, f"Count(Bitmap(rowID={u}, frame=star))")
                for u in range(0, 24, 5)]

    assert counts(e) == counts(ref) == counts(host)
    assert mgr._views[("i", "star", "standard")].sparse is not None
    for i in range(40):
        u, s = int(rng.integers(24)), int(rng.integers(3))
        col = (int(rng.integers(8 * 65536, SLICE_WIDTH)) if i % 5 == 0
               else int(rng.integers(8 * 65536)))
        star.set_bit(u, s * SLICE_WIDTH + col)
        text = f"Count(Bitmap(rowID={u}, frame=star))"
        assert q(e, text) == q(ref, text) == q(host, text)
    assert counts(e) == counts(ref) == counts(host)
    for k in ("stage", "sparse_inserts", "sparse_patches"):
        assert mgr.stats[k] == rmgr.stats[k], k
    assert mgr.stats["sparse_inserts"] > 0
    assert mgr.stats["refresh_walk_fragments"] == 40


def test_two_managers_over_one_holder_both_see_every_write(holder):
    (f,) = populate(holder)
    a, amgr = served(holder)
    b, bmgr = served(holder)
    host = Executor(holder, use_device=False)
    assert answers(a) == answers(b) == answers(host)
    for s in (1, 4):
        f.set_bit(3, s * SLICE_WIDTH + 2000 + s)
    ab, bb = visits(amgr), visits(bmgr)
    assert answers(a) == answers(host)
    assert answers(b) == answers(host)
    assert visits(amgr)[1] - ab[1] == visits(bmgr)[1] - bb[1] == 2
    assert amgr.stats["refresh_full_walks"] \
        == bmgr.stats["refresh_full_walks"] == 0


def test_concurrent_writers_lose_no_slice_from_the_record(holder):
    """Two threads write to slices of their own while a reader counts:
    the record names every write, slice for slice, and the view answers
    as the host."""
    (f,) = populate(holder)
    e, mgr = served(holder)
    host = Executor(holder, use_device=False)
    assert answers(e) == answers(host)
    writes = holder.view(*KEY).writes
    n0 = writes.n
    per_thread = WRITE_RECORD // 2 - 8
    stop, errors = threading.Event(), []

    def writer(k):
        try:
            for i in range(per_thread):
                s = k + 2 * (i % (SLICES // 2))
                f.set_bit(i % 4, s * SLICE_WIDTH + 6000 + i)
        except Exception as err:  # noqa: BLE001 — reported below
            errors.append(repr(err))

    def reader():
        try:
            while not stop.is_set():
                q(e, "Count(Bitmap(rowID=1, frame=f))")
        except Exception as err:  # noqa: BLE001 — reported below
            errors.append(repr(err))

    threads = [threading.Thread(target=writer, args=(k,)) for k in (0, 1)]
    rd = threading.Thread(target=reader)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rd.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        stop.set()
        rd.join(60)
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads + [rd]) and errors == []
    n, got = MUTATION_EPOCH.written_since(writes, n0)
    want = Counter(k + 2 * (i % (SLICES // 2)) for k in (0, 1)
                   for i in range(per_thread))
    assert n - n0 == 2 * per_thread and Counter(got) == want
    assert answers(e) == answers(host)
    assert mgr.stats["refresh_full_walks"] == 0


# -- the packers -----------------------------------------------------------------


def old_pack_mutation_batches(per_slice, num_slices, capacity):
    """pack_mutation_batches as it was: a padded plan for every slice,
    stacked."""
    from pilosa_tpu.ops.pool import mutation_batch_width, pad_mutation_plan

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


def old_pack_container_patches(per_slice, num_slices, capacity):
    from pilosa_tpu.ops.pool import mutation_batch_width

    k = mutation_batch_width(max(len(v[0]) for v in per_slice.values()))
    slot = np.full((num_slices, k), capacity, dtype=np.int32)
    key = np.full((num_slices, k), INVALID_KEY, dtype=np.int32)
    for si, (new_keys, new_slots) in per_slice.items():
        slot[si, :len(new_slots)] = new_slots
        key[si, :len(new_keys)] = new_keys
    return slot, key


def same_bytes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("seed", range(12))
def test_packers_give_the_old_bytes(seed):
    rng = np.random.default_rng(seed)
    num_slices = int(rng.choice([1, 4, 8, 960]))
    capacity = int(rng.choice([16, 240, 10480]))
    written = rng.choice(num_slices, size=int(rng.integers(
        0 if seed % 4 else 1, min(num_slices, 9) + 1)), replace=False)
    plans, patches = {}, {}
    for s in written.tolist():
        n = int(rng.integers(0, 40))
        plans[s] = (rng.integers(0, capacity, n).astype(np.int32),
                    rng.integers(0, 2048, n).astype(np.int32),
                    rng.integers(0, 2**32, n, dtype=np.uint64)
                    .astype(np.uint32),
                    rng.integers(0, 2**32, n, dtype=np.uint64)
                    .astype(np.uint32))
        k = int(rng.integers(1, 12))
        patches[s] = (rng.integers(0, 1 << 20, k).astype(np.int32),
                      rng.integers(0, capacity, k).astype(np.int32))
    same_bytes(pack_mutation_batches(plans, num_slices, capacity),
               old_pack_mutation_batches(plans, num_slices, capacity))
    if patches:
        same_bytes(pack_container_patches(patches, num_slices, capacity),
                   old_pack_container_patches(patches, num_slices, capacity))
