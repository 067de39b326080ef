"""A sorted-array (sparse) view read and written where it lies on the
device (Star Trace's `stargazer`: a row a user, a few bits each).

The src form of TopN reads it in both directions without demoting it to
8 KB a container: as a src leaf (mesh._sparse_leaf_blocks expands the
row's sorted values into words, alone or in a tree with dense leaves)
and as the ranked pool (mesh.compile_serve_row_counts_src_sparse, one
flat segment-sum, here past 2^16 rows). A SetBit into it is no restage:
serve._refresh_sparse writes each touched container whole, into its own
slot or a free one (ops.pool.plan_sparse_slice,
mesh.compile_serve_sparse_write), and what it cannot take restages,
counted by reason. Every program is held to host roaring or numpy on
seeded data.
"""

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.pool import (INVALID_KEY, ROW_SPAN, PatchRefused,
                                 plan_sparse_slice)
from pilosa_tpu.pql import parse_string
from pilosa_tpu.roaring.bitmap import Bitmap, Container

STAR = ("i", "stargazer", "standard")


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def q(executor, pql):
    return executor.execute("i", parse_string(pql))


def bitmap_of(containers):
    """A roaring Bitmap from {fragment container key: values}."""
    bm = Bitmap()
    bm.keys = sorted(containers)
    bm.containers = [Container(array=np.asarray(sorted(containers[k]),
                                                dtype=np.uint32))
                     .normalize() for k in bm.keys]
    return bm


def bits_of(bm):
    """{row: set of slice-local columns} of a fragment's bitmap."""
    out = {}
    for k, c in zip(bm.keys, bm.containers):
        row, block = k >> 4, k & 15
        out.setdefault(row, set()).update(
            int(v) + (block << 16) for v in c.values())
    return out


# -- the programs, against numpy ------------------------------------------------


def star_slices(rng, slices, users, stars, hot_cols=200):
    """Per slice {key: values}: users with a few stars each, most on a
    few hot columns (so rows meet)."""
    hot = rng.choice(SLICE_WIDTH, size=hot_cols, replace=False)
    out = [dict() for _ in range(slices)]
    for _ in range(stars):
        s = int(rng.integers(slices))
        u = int(rng.integers(users))
        col = int(hot[rng.integers(hot_cols)] if rng.random() < 0.6
                  else rng.integers(SLICE_WIDTH))
        out[s].setdefault(u * ROW_SPAN + (col >> 16), set()).add(
            col & 0xFFFF)
    return out


def language_slices(rng, slices, n_rows):
    """Per slice {key: values}: every column in exactly one row."""
    out = []
    for _ in range(slices):
        lang = rng.integers(n_rows, size=SLICE_WIDTH)
        d = {}
        for r in range(n_rows):
            cols = np.flatnonzero(lang == r)
            for block in range(16):
                part = cols[(cols >> 16) == block] & 0xFFFF
                if len(part):
                    d[r * ROW_SPAN + block] = part
        out.append(d)
    return out


def stage(slices_of, mesh, sparse):
    from pilosa_tpu.parallel import mesh as M

    bms = [bitmap_of(d) for d in slices_of]
    if sparse:
        sp, row_ids, keys, cards = M.build_sparse_sharded_index(bms, mesh)
        return bms, sp, row_ids, keys
    sh, row_ids, keys = M.build_sharded_index(bms, mesh,
                                              with_host_keys=True)
    return bms, sh, row_ids, keys


def leaf_meta(mesh, keys, row_ids, row):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pilosa_tpu.parallel import mesh as M

    i = int(np.searchsorted(row_ids, np.uint64(row)))
    if i >= len(row_ids) or row_ids[i] != row:
        i = len(row_ids)
    idx, hit = M.resolve_row_indices(keys, i)
    sh = NamedSharding(mesh, P(M.SLICE_AXIS))
    return jax.device_put(idx, sh), jax.device_put(hit, sh)


def combine(limbs, n):
    limbs = np.asarray(limbs).astype(np.int64)
    return (limbs[1, :n] << 16) + limbs[0, :n]


@pytest.mark.parametrize("devices", [1, 2])
def test_sparse_src_leaf_alone_and_beside_dense_leaves(devices):
    """TopN(src, frame=language): the src a stargazer row (sparse), then
    that row AND a dense language row, then OR of two sparse rows, each
    against numpy; the same src staged dense agrees too."""
    from pilosa_tpu.parallel import mesh as M

    rng = np.random.default_rng(5)
    mesh = M.default_mesh(devices)
    n_slices, langs = 4, 6
    stars = star_slices(rng, n_slices, users=40, stars=900)
    lang_d = language_slices(rng, n_slices, langs)
    _, lang, lang_rows, lang_keys = stage(lang_d, mesh, sparse=False)
    bms, sp, sp_rows, sp_keys = stage(stars, mesh, sparse=True)
    _, dn, dn_rows, dn_keys = stage(stars, mesh, sparse=False)
    mask = np.zeros(sp.num_slices, dtype=np.int32)
    mask[:n_slices] = 1
    star_bits = [bits_of(b) for b in bms]
    lang_bits = [bits_of(bitmap_of(d)) for d in lang_d]
    padded = 8
    for tree, leaves, want_of in (
            (("leaf", 0), [("s", 3)],
             lambda s: star_bits[s].get(3, set())),
            (("and", ("leaf", 0), ("leaf", 1)), [("s", 7), ("d", 2)],
             lambda s: star_bits[s].get(7, set())
             & lang_bits[s].get(2, set())),
            (("or", ("leaf", 0), ("leaf", 1)), [("s", 1), ("s", 9)],
             lambda s: star_bits[s].get(1, set())
             | star_bits[s].get(9, set()))):
        pools, idxs, hits, fmts = [], [], [], []
        for fmt, row in leaves:
            if fmt == "s":
                pools.append((sp.values, sp.cards))
                idx, hit = leaf_meta(mesh, sp_keys, sp_rows, row)
            else:
                pools.append(lang.words)
                idx, hit = leaf_meta(mesh, lang_keys, lang_rows, row)
            idxs.append(idx)
            hits.append(hit)
            fmts.append(fmt)
        fn = M.compile_serve_row_counts_src(mesh, tree, len(leaves), padded,
                                            leaf_formats=tuple(fmts))
        got = combine(fn(lang.keys, lang.words, tuple(pools), tuple(idxs),
                         tuple(hits), mask), len(lang_rows))
        want = [sum(len(want_of(s) & lang_bits[s].get(int(r), set()))
                    for s in range(n_slices)) for r in lang_rows]
        assert got.tolist() == want
        if all(f == "s" for f in fmts):  # the same rows staged dense
            dense = [leaf_meta(mesh, dn_keys, dn_rows, r)
                     for _f, r in leaves]
            fd = M.compile_serve_row_counts_src(mesh, tree, len(leaves),
                                                padded)
            assert combine(fd(lang.keys, lang.words,
                              (dn.words,) * len(leaves),
                              tuple(d[0] for d in dense),
                              tuple(d[1] for d in dense), mask),
                           len(lang_rows)).tolist() == want


@pytest.mark.parametrize("devices", [1, 2])
def test_sparse_ranked_row_counts_past_2_16_rows(devices):
    """TopN(Bitmap(language row), frame=stargazer) over 70,000 users
    (R pads to 2^17): |user ∩ src| for every user from the sorted
    values (first values for all, the rest through the overflow slots),
    against numpy; kmax above the largest card, or a wider overflow,
    changes nothing, and a slice the mask leaves out adds nothing."""
    from pilosa_tpu.parallel import mesh as M

    rng = np.random.default_rng(9)
    mesh = M.default_mesh(devices)
    n_slices, users = 2, 70_000
    stars = star_slices(rng, n_slices, users=users, stars=150_000)
    for u in range(users):  # every user has a row in the table
        stars[u % n_slices].setdefault(u * ROW_SPAN, set()).add(5)
    lang_d = language_slices(rng, n_slices, 4)
    _, lang, lang_rows, lang_keys = stage(lang_d, mesh, sparse=False)
    bms, sp, sp_rows, sp_keys = stage(stars, mesh, sparse=True)
    assert len(sp_rows) > 1 << 16
    padded = 1 << (len(sp_rows) - 1).bit_length()
    fn = M.compile_serve_row_counts_src_sparse(mesh, ("leaf", 0), 1, padded)
    idx, hit = leaf_meta(mesh, lang_keys, lang_rows, 1)
    src = [bits_of(bitmap_of(d)).get(1, set()) for d in lang_d]
    star_bits = [bits_of(b) for b in bms]
    kmax = int(max(c.n for b in bms for c in b.containers))
    cards = np.asarray(sp.cards)
    overflow = M.sparse_overflow_slots(cards)
    assert 0 < (overflow < cards.shape[1]).sum() < (cards > 0).sum()
    for mask_slices in ((0, 1), (1,)):
        mask = np.zeros(sp.num_slices, dtype=np.int32)
        mask[list(mask_slices)] = 1
        want = np.zeros(len(sp_rows), dtype=np.int64)
        for s in mask_slices:
            for u, cols in star_bits[s].items():
                want[np.searchsorted(sp_rows, u)] += len(cols & src[s])
        for k, width in ((kmax, None), (kmax + 3, None),
                         (kmax, 2 * overflow.shape[1])):
            ov = M.sparse_overflow_slots(cards, width)
            got = combine(fn(sp.keys, sp.values, sp.cards, ov, np.int32(k),
                             (lang.words,), (idx,), (hit,), mask),
                          len(sp_rows))
            np.testing.assert_array_equal(got, want)
        assert want.sum() > 1000


# -- the served path: both directions, and stars in place ------------------------


def served(holder):
    e = Executor(holder, use_device=True)
    return e, e.mesh_manager()


def populate(holder, rng, users=24, slices=3, langs=5):
    """A `language` frame over the first 65,536 columns of each slice
    (each column one language, row 0 over the 4,096-value array cap, so
    it stages dense), and a `stargazer` frame of a few stars a user,
    enough a slice to stage sparse."""
    idx = holder.create_index_if_not_exists("i")
    lang = idx.create_frame_if_not_exists("language")
    star = idx.create_frame_if_not_exists("stargazer")
    for s in range(slices):
        lg = np.where(rng.random(65536) < 0.3, 0,
                      rng.integers(langs, size=65536))
        lang.import_bits(lg.tolist(),
                         (s * SLICE_WIDTH + np.arange(65536)).tolist())
        for u in range(users):
            for c in rng.choice(4096, size=int(rng.integers(1, 4)),
                                replace=False):
                star.set_bit(u, s * SLICE_WIDTH + int(c))
            # and more in blocks 1-7 of the slice; 8-15 are left for
            # the containers stars create
            for c in rng.integers(65536, 8 * 65536,
                                  size=int(rng.integers(60, 100))):
                star.set_bit(u, s * SLICE_WIDTH + int(c))
    return lang, star


def both_directions(e, users=24, langs=5):
    out = [q(e, f'TopN(Bitmap(rowID={u}, frame="stargazer"), '
                f'frame="language", n=3)') for u in range(0, users, 5)]
    out += [q(e, f'TopN(Bitmap(rowID={lg}, frame="language"), '
                 f'frame="stargazer", n=4)') for lg in range(langs)]
    out += [q(e, f'Count(Bitmap(rowID={u}, frame="stargazer"))')
            for u in range(0, users, 3)]
    return out


def recount(holder, users=24, langs=5, slices=3):
    """both_directions' answers counted afresh from the fragments (the
    host executor's TopN reads its rank cache, which is approximate)."""
    m = np.zeros((users, langs), dtype=np.int64)
    for s in range(slices):
        lang_of = {}
        for p in holder.fragment("i", "language", "standard", s) \
                .storage.slice():
            lang_of[int(p) & (SLICE_WIDTH - 1)] = int(p) >> 20
        for p in holder.fragment(*STAR, s).storage.slice():
            u, c = int(p) >> 20, int(p) & (SLICE_WIDTH - 1)
            if c in lang_of:
                m[u, lang_of[c]] += 1

    def top(line, n):
        pairs = sorted(((i, int(c)) for i, c in enumerate(line) if c),
                       key=lambda p: (-p[1], p[0]))
        return [pairs[:n]]
    out = [top(m[u], 3) for u in range(0, users, 5)]
    out += [top(m[:, lg], 4) for lg in range(langs)]
    out += [[sum(1 for p in holder.fragment(*STAR, s).storage.slice()
                 if int(p) >> 20 == u) for s in range(slices)]
            for u in range(0, users, 3)]
    out[-(len(range(0, users, 3))):] = [[sum(x)] for x in
                                        out[-(len(range(0, users, 3))):]]
    return out


def sparse_matches_a_fresh_stage(holder, mgr):
    """The view's device pool and host metadata, written in place, hold
    what a fresh build_sparse_sharded_index of the same fragments does:
    the same (key -> values, card) in every slice, the host keys sorted
    with their slots, the cards by slot, kmax a bound."""
    from pilosa_tpu.parallel.mesh import build_sparse_sharded_index

    sv = mgr._views[STAR]
    bms = [holder.fragment(*STAR, s).storage for s in range(sv.num_slices)]
    fresh, rows, f_keys, f_cards = build_sparse_sharded_index(
        bms, mgr.mesh, row_ids=sv.row_ids)
    keys, vals, cards = (np.asarray(a) for a in
                         (sv.sparse.keys, sv.sparse.values, sv.sparse.cards))
    f_vals = np.asarray(fresh.values)
    k = min(vals.shape[2], f_vals.shape[2])
    for s in range(sv.num_slices):
        live = f_keys[s] != INVALID_KEY
        want = {int(kk): (f_vals[s, j, :k].tolist(), int(f_cards[s, j]))
                for j, kk in enumerate(f_keys[s]) if live[j]}
        got = {int(kk): (vals[s, j, :k].tolist(), int(cards[s, j]))
               for j, kk in enumerate(keys[s]) if kk != INVALID_KEY}
        assert got == want
        host = sv.sparse_keys_host[s]
        n = int((host != INVALID_KEY).sum())
        assert (np.diff(host[:n].astype(np.int64)) > 0).all()
        assert (keys[s, sv.sparse_slots_host[s][:n]] == host[:n]).all()
        np.testing.assert_array_equal(sv.sparse_cards_host[s], cards[s])
        assert sv.sparse_free_slots[s] == keys.shape[1] - n
    assert sv.sparse_kmax >= int(cards.max())


def test_both_directions_and_stars_in_place_match_host_and_a_fresh_stage(
        holder, monkeypatch):
    """Both src directions and the read-back Count from the device with
    `stargazer` sparse and never demoted; then 200 random stars (into
    containers that are there and ones they create), each followed by a
    read, answer as the host's roaring path does, no restage, and the
    pool written in place equals a fresh stage word for word and card
    for card."""
    rng = np.random.default_rng(21)
    lang, star = populate(holder, rng)
    e, mgr = served(holder)
    host = Executor(holder, use_device=False)
    assert both_directions(e) == recount(holder)
    sv = mgr._views[STAR]
    assert sv.sparse is not None and sv.keys_host.shape[1] == 0
    assert mgr._views[("i", "language", "standard")].sparse is None
    stage0 = mgr.stats["stage"]
    for i in range(200):
        u = int(rng.integers(24))
        s = int(rng.integers(3))
        col = (int(rng.integers(8 * 65536, SLICE_WIDTH)) if i % 7 == 0
               else int(rng.integers(8 * 65536)))
        star.set_bit(u, s * SLICE_WIDTH + col)
        pql = f'Count(Bitmap(rowID={u}, frame="stargazer"))'
        assert q(e, pql) == q(host, pql)
        if i % 10 == 0:
            assert both_directions(e) == recount(holder)
    assert both_directions(e) == recount(holder)
    st = mgr.stats
    assert st["stage"] == stage0
    assert st["sparse_inserts"] > 20 and st["sparse_patches"] > 100
    assert st["sparse_demote"] == st["fallback_sparse_format"] == 0
    assert st["sparse_src_leaves"] > 0 and st["sparse_row_counts"] > 0
    sparse_matches_a_fresh_stage(holder, mgr)


@pytest.mark.parametrize("reason", ["new_row", "no_slot", "value_cap"])
def test_each_refusal_restages_and_is_counted(holder, reason):
    """A star the sparse pool cannot take restages the view, counted by
    reason, and the answer is still the host's: a user new to the view,
    a slice whose free slots are used up, a container past K values."""
    from pilosa_tpu.parallel import mesh as M

    rng = np.random.default_rng(33)
    lang, star = populate(holder, rng)
    e, mgr = served(holder)
    host = Executor(holder, use_device=False)
    assert both_directions(e) == recount(holder)
    sv = mgr._views[STAR]
    u = 3
    if reason == "new_row":
        u = 24
        star.set_bit(u, 7)
    elif reason == "no_slot":
        free = int(sv.sparse_free_slots[1])
        assert free >= ROW_SPAN
        for j in range(free + 1):  # each a container of its own
            u = j % 24
            star.set_bit(u, SLICE_WIDTH + (8 + (j // 24) % 8) * 65536 + j)
    else:
        for c in range(sv.sparse.value_cap + 1):
            star.set_bit(u, 2 * SLICE_WIDTH + 10 * 65536 + c)
    stage0 = mgr.stats["stage"]
    read = f'Count(Bitmap(rowID={u}, frame="stargazer"))'
    assert q(e, read) == q(host, read)
    assert mgr.stats["sparse_patch_refused_" + reason] == 1
    assert mgr.stats["stage"] == stage0 + 1
    fresh = mgr._views[STAR]
    assert fresh is not sv and fresh.sparse is not None
    assert int(fresh.sparse_free_slots[:3].min()) >= ROW_SPAN
    assert both_directions(e, users=25) == recount(holder, users=25)
    from pilosa_tpu.api.handler import Handler

    body = Handler(holder, e).handle("GET", "/metrics").body
    body = body.decode() if isinstance(body, bytes) else body
    assert f'pilosa_sparse_patch_refused_total{{reason="{reason}"}} 1' \
        in body


def test_plan_sparse_slice_rewrites_in_place_and_appends_created():
    row_ids = np.asarray([3, 8], dtype=np.uint64)
    keys = np.full(8, INVALID_KEY, dtype=np.int32)
    keys[:3] = [0 * 16 + 1, 1 * 16 + 0, 1 * 16 + 5]
    slots = np.arange(8, dtype=np.int32)
    conts = [((8 << 4) | 5, np.asarray([2, 9], np.uint16)),   # held
             ((3 << 4) | 7, np.asarray([4], np.uint16))]      # created
    k2, s2, (slot, key, card, vals), made = plan_sparse_slice(
        keys, slots, row_ids, conts, 4)
    assert made == 1
    assert key.tolist() == [21, 7] and slot.tolist() == [2, 3]
    assert card.tolist() == [2, 1]
    assert vals.tolist() == [[2, 9, 65535, 65535], [4, 65535, 65535, 65535]]
    assert k2[:4].tolist() == [1, 7, 16, 21] and s2[:4].tolist() == \
        [0, 3, 1, 2]
    assert keys[:3].tolist() == [1, 16, 21]  # the given rows untouched
    for conts_, why in (([((5 << 4), np.asarray([1], np.uint16))],
                         "new_row"),
                        ([((3 << 4), np.arange(5, dtype=np.uint16))],
                         "value_cap")):
        with pytest.raises(PatchRefused) as err:
            plan_sparse_slice(keys, slots, row_ids, conts_, 4)
        assert err.value.reason == why
    full = np.arange(8, dtype=np.int32)
    with pytest.raises(PatchRefused) as err:
        plan_sparse_slice(full, slots, np.arange(1, dtype=np.uint64),
                          [((0 << 4) | 9, np.asarray([1], np.uint16))], 4)
    assert err.value.reason in ("no_slot", "new_row")


class _AlignedNumpy:
    """numpy, but `full` gives 64-byte aligned buffers: the ones a CPU
    backend's device_put aliases instead of copying (a buffer it is
    handed at another alignment it copies, so without this the fault
    below shows in about one staging in four)."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def full(shape, fill, dtype):
        n = int(np.prod(shape)) * np.dtype(dtype).itemsize
        raw = np.empty(n + 64, dtype=np.uint8)
        off = -raw.ctypes.data % 64
        out = raw[off:off + n].view(dtype).reshape(shape)
        out[...] = fill
        return out


def test_patched_keys_stay_off_the_device_on_two_cpu_devices(holder,
                                                             monkeypatch):
    """PR 36's fault (a): a CPU backend's device_put may alias the host
    keys, and the host writes them in place when a container is patched
    in, before the patch program has read the device's. Staged from a
    copy, the device keys after a patch are the slot layout (the new
    key in its free slot, nothing moved), never the host's sorted row;
    and a write into the host's keys reaches no device buffer."""
    from pilosa_tpu.parallel import mesh as M
    from pilosa_tpu.parallel.mesh import default_mesh
    from pilosa_tpu.parallel.serve import MeshManager

    monkeypatch.setattr(M, "np", _AlignedNumpy())

    f = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("general")
    cols = np.arange(0, 65536, 2)  # 32,768 a container: packed words
    for r in range(4):
        for s in range(4):
            f.import_bits([r] * len(cols), (s * SLICE_WIDTH + cols).tolist())
    e = Executor(holder, use_device=True)
    e._mesh_mgr = mgr = MeshManager(holder, mesh=default_mesh(2))
    mgr.deterministic_gate = True
    assert q(e, "Count(Bitmap(rowID=2))") == [4 * 32768]
    sv = mgr._views[("i", "general", "standard")]
    before = np.asarray(sv.sharded.keys).copy()
    # The fault's mechanism, made deterministic: a write into the host's
    # keys (as a patch makes) before a program has read the device's.
    saved = sv.keys_host.copy()
    sv.keys_host[:] = INVALID_KEY
    for shard in sv.sharded.keys.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      before[shard.index])
    sv.keys_host[:] = saved
    f.set_bit(0, 2 * SLICE_WIDTH + 3 * 65536 + 1)  # row 0, block 3: new
    assert q(e, "Count(Bitmap(rowID=0))") == [4 * 32768 + 1]
    after = np.asarray(sv.sharded.keys)
    live = int((before[2] != INVALID_KEY).sum())
    assert mgr.stats["container_patches"] == 1
    np.testing.assert_array_equal(after[:, :live], before[:, :live])
    assert after[2, live] == 3  # dense row 0, block 3, in the free slot
    assert sv.keys_host[2, 1] == 3 and sv.slots_host[2, 1] == live
    assert not np.shares_memory(sv.keys_host, after)


def test_free_slots_gauge_reads_the_sparse_pool(holder):
    """mesh.sparse_free_slots_min gauges a sorted-array pool's free
    slots (the distance to a no_slot restage): as staged, and one less
    after a star creates a container in the slice with the fewest."""
    rng = np.random.default_rng(5)
    lang, star = populate(holder, rng)
    e, mgr = served(holder)
    read = 'Count(Bitmap(rowID=3, frame="stargazer"))'
    q(e, read)
    assert set(mgr._views) == {STAR}
    sv = mgr._views[STAR]
    free = sv.sparse_free_slots[:sv.num_slices]
    before, s = int(free.min()), int(free.argmin())
    assert mgr.stats["sparse_free_slots_min"] == before >= ROW_SPAN
    assert mgr.stats["free_slots_min"] == 0   # no dense view staged
    star.set_bit(3, s * SLICE_WIDTH + 15 * 65536 + 1)  # block 15: created
    q(e, read)
    assert mgr.stats["sparse_inserts"] == 1 and mgr.stats["stage"] == 1
    assert mgr.stats["sparse_free_slots_min"] == before - 1


# -- where the ranking runs -----------------------------------------------------


def rank_counters(h):
    """{where: ranking passes} from /debug/vars, which /metrics'
    pilosa_topn_rank_total{where} has to match."""
    import re

    mesh = h.handle("GET", "/debug/vars").json()["mesh"]
    prom = h.handle("GET", "/metrics").body.decode()
    out = {}
    for where in ("device", "host"):
        m = re.search(r'^pilosa_topn_rank_total\{where="%s"\} (\d+)$'
                      % where, prom, re.M)
        assert m is not None and int(m.group(1)) == \
            mesh[f"topn_rank_{where}"]
        out[where] = mesh[f"topn_rank_{where}"]
    return out


def test_a_view_from_the_cut_up_ranks_on_the_device(holder, monkeypatch):
    """Star Trace's shapes: `stargazer`'s 24 users pad to 32 rows,
    `language`'s 5 to 8. With the cut at 32 a language-src TopN ranks
    its users on the device, both passes (phase 1's n, phase 2's ids),
    and a user-src TopN its languages on the host; n = 0 takes phase 1
    to the host, an attribute filter and tanimoto both passes. Every
    pass counts once, where it ran. Both directions answer as the host
    recount does and as they did below the cut, across a memo hit and
    a star."""
    from pilosa_tpu.api import Handler
    from pilosa_tpu.parallel import new_test_cluster, serve

    rng = np.random.default_rng(29)
    lang, star = populate(holder, rng)
    cluster = new_test_cluster(1)  # one node: two passes a TopN, as served
    host = cluster.nodes[0].host
    e = Executor(holder, host=host, cluster=cluster, use_device=True,
                 device_min_work=0)
    h = Handler(holder, e, cluster=cluster, host=host)
    for u in range(0, 24, 2):
        q(e, f'SetRowAttrs(rowID={u}, frame="stargazer", team={u % 3})')
    src = 'Bitmap(rowID=0, frame="language")'
    forms = {  # (pql, passes on the device, on the host) from the cut up
        "every_row": (f'TopN({src}, frame="stargazer")', 1, 1),
        "filtered": (f'TopN({src}, frame="stargazer", n=4, field="team", '
                     'filters=[1])', 0, 2),
        "tanimoto": ('TopN(Bitmap(rowID=3, frame="stargazer"), '
                     'frame="stargazer", tanimotoThreshold=10)', 0, 2)}

    def run(pql):
        before, passes = rank_counters(h), e.exec_stats["topn_passes"]
        out = q(e, pql)
        after = rank_counters(h)
        moved = (after["device"] - before["device"],
                 after["host"] - before["host"])
        assert sum(moved) == e.exec_stats["topn_passes"] - passes
        return out, moved

    below = both_directions(e)
    assert below == recount(holder)
    assert rank_counters(h)["device"] == 0
    answers = {name: run(pql)[0] for name, (pql, _d, _h) in forms.items()}
    assert all(a[0] for a in answers.values())  # each ranks some rows
    mgr = e.mesh_manager()
    assert len(mgr._views[STAR].row_ids) == 24
    monkeypatch.setattr(serve, "RANK_DEVICE_MIN_ROWS", 32)

    hits = mgr.stats["memo_hit"]
    before = rank_counters(h)
    assert both_directions(e) == below  # from the memo
    after = rank_counters(h)
    assert mgr.stats["memo_hit"] > hits
    # 5 language-src TopNs on the device, 5 user-src on the host.
    assert after["device"] - before["device"] == 10
    assert after["host"] - before["host"] == 10
    for i in range(12):  # stars, each then read both ways
        star.set_bit(int(rng.integers(24)),
                     int(rng.integers(3)) * SLICE_WIDTH
                     + int(rng.integers(8 * 65536)))
        if i % 4 == 3:
            assert both_directions(e) == recount(holder)
    for name, (pql, on_device, on_host) in forms.items():
        out, moved = run(pql)
        assert moved == (on_device, on_host), name
        monkeypatch.setattr(serve, "RANK_DEVICE_MIN_ROWS", 1 << 14)
        assert q(e, pql) == out, name  # the host form, the same answer
        monkeypatch.setattr(serve, "RANK_DEVICE_MIN_ROWS", 32)
    assert mgr.stats["fallback"] == 0
