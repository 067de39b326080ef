"""Mesh serving layer tests: HTTP-facing queries must run the
shard_map+psum engine (parallel/serve.py), with incremental device-image
maintenance on writes.

Model: the reference's distributed-executor tests
(/root/reference/executor_test.go) assert what the fan-out DOES; here we
additionally assert which ENGINE served it — the per-slice fallback is
poisoned so only the mesh path can answer.
"""

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.pql import parse_string


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def seed(holder, index="i", frame="general", bits=()):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    for row, col in bits:
        f.set_bit(row, col)
    return f


def q(executor, index, pql):
    return executor.execute(index, parse_string(pql))


def poison_per_slice(monkeypatch):
    """Make the per-slice device fallback unusable so a passing query
    proves the mesh path served it."""
    from pilosa_tpu.parallel.plan import CountPlan

    def boom(self, slice_):
        raise AssertionError("per-slice path used; mesh path expected")

    monkeypatch.setattr(CountPlan, "count_slice", boom)


class TestServedCount:
    BITS = [
        (10, 0), (10, 1), (10, SLICE_WIDTH + 2), (10, 65536 + 7),
        (11, 1), (11, SLICE_WIDTH + 2), (11, 99999),
        (12, 2 * SLICE_WIDTH + 5),
    ]

    def test_count_serves_via_mesh(self, holder, monkeypatch):
        seed(holder, bits=self.BITS)
        poison_per_slice(monkeypatch)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for pql in (
            "Count(Bitmap(rowID=10))",
            "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))",
            "Count(Union(Bitmap(rowID=10), Bitmap(rowID=11), Bitmap(rowID=12)))",
            "Count(Difference(Bitmap(rowID=10), Bitmap(rowID=11)))",
        ):
            assert q(e, "i", pql) == q(host, "i", pql)
        mgr = e.mesh_manager()
        assert mgr.stats["count"] == 4
        # Same (index, frame, view): staged once, reused across queries.
        assert mgr.stats["stage"] == 1

    def test_count_absent_row_is_zero(self, holder, monkeypatch):
        seed(holder, bits=self.BITS)
        poison_per_slice(monkeypatch)
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=999))") == [0]
        assert q(e, "i", "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=999)))") \
            == [0]

    def test_count_multi_frame_tree(self, holder, monkeypatch):
        seed(holder, frame="f1", bits=[(1, 0), (1, 5), (1, SLICE_WIDTH + 3)])
        seed(holder, frame="f2", bits=[(1, 5), (1, 7), (1, SLICE_WIDTH + 3)])
        poison_per_slice(monkeypatch)
        e = Executor(holder, use_device=True)
        pql = ("Count(Intersect(Bitmap(rowID=1, frame=f1), "
               "Bitmap(rowID=1, frame=f2)))")
        assert q(e, "i", pql) == [2]
        mgr = e.mesh_manager()
        assert mgr.stats["count"] == 1
        assert mgr.stats["stage"] == 2  # one per frame view

    def test_count_inverse_view(self, holder, monkeypatch):
        """Bitmap(columnID=..) leaves lower onto the inverse view and
        serve through the mesh, matching the host path."""
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general", inverse_enabled=True)
        for row, col in [(1, 7), (2, 7), (3, 7), (2, 9), (3, 9)]:
            f.set_bit(row, col)
        poison_per_slice(monkeypatch)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for pql in (
            "Count(Bitmap(columnID=7))",
            "Count(Intersect(Bitmap(columnID=7), Bitmap(columnID=9)))",
        ):
            assert q(e, "i", pql) == q(host, "i", pql)
        assert e.mesh_manager().stats["count"] == 2

    def test_count_range_time_views(self, holder):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general", time_quantum="YMD")
        from datetime import datetime

        f.set_bit(1, 3, datetime(2017, 4, 2, 9, 0))
        f.set_bit(1, SLICE_WIDTH + 8, datetime(2017, 4, 3, 9, 0))
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = ("Count(Range(rowID=1, frame=general, "
               "start=\"2017-04-01T00:00\", end=\"2017-04-30T00:00\"))")
        assert q(e, "i", pql) == q(host, "i", pql) == [2]
        assert e.mesh_manager().stats["count"] == 1


class TestIncrementalWrites:
    def test_writes_apply_without_restage(self, holder, monkeypatch):
        f = seed(holder, bits=[(10, c) for c in range(64)]
                 + [(11, c) for c in range(0, 64, 2)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))") \
            == [32]
        mgr = e.mesh_manager()
        assert mgr.stats["stage"] == 1

        # Bits into EXISTING containers: scatter, not restage.
        for c in range(64, 96):
            f.set_bit(10, c)
            f.set_bit(11, c)
        assert q(e, "i", "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))") \
            == [64]
        assert mgr.stats["stage"] == 1
        assert mgr.stats["incremental"] == 1

        # clear_bit also rides the scatter (clears one shared column).
        f.clear_bit(10, 0)
        assert q(e, "i", "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))") \
            == [63]
        assert mgr.stats["stage"] == 1
        assert mgr.stats["incremental"] == 2

    def test_container_churn_restages(self, holder):
        f = seed(holder, bits=[(10, 0), (11, 0)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=10))") == [1]
        mgr = e.mesh_manager()
        assert mgr.stats["stage"] == 1
        # A new row means a new container — scatter can't add key slots.
        f.set_bit(99, 5)
        assert q(e, "i", "Count(Bitmap(rowID=99))") == [1]
        assert mgr.stats["stage"] == 2

    def test_new_slice_restages(self, holder):
        f = seed(holder, bits=[(10, 0)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=10))") == [1]
        f.set_bit(10, 3 * SLICE_WIDTH + 1)  # grows the slice space
        assert q(e, "i", "Count(Bitmap(rowID=10))") == [2]
        assert e.mesh_manager().stats["stage"] == 2

    def test_set_then_clear_folds_to_final_state(self, holder):
        f = seed(holder, bits=[(10, c) for c in range(8)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=10))") == [8]
        f.set_bit(10, 9)
        f.clear_bit(10, 9)   # same word set then cleared
        f.clear_bit(10, 0)
        f.set_bit(10, 0)     # same word cleared then set
        assert q(e, "i", "Count(Bitmap(rowID=10))") == [8]


class TestRefreshFastPath:
    """refresh()'s O(1) validation stamp: while the process-wide
    mutation-epoch pair is unmoved, the per-slice staleness walk is
    skipped entirely — no holder lookups, no fragment locks. At
    headline scale (960 slices) that walk, serialized under the
    manager lock, was the dominant host-side cost of a concurrent
    read-only herd."""

    def _spy(self, holder):
        calls = []
        orig = holder.fragment
        holder.fragment = lambda *a: (calls.append(a), orig(*a))[1]
        return calls, orig

    def test_quiet_refresh_skips_fragment_walk(self, holder):
        f = seed(holder, bits=[(1, 5), (2, 5), (2, SLICE_WIDTH + 3)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=2))") == [2]
        mgr = e.mesh_manager()
        ns = holder.index("i").max_slice() + 1
        calls, orig = self._spy(holder)
        try:
            sv = mgr.refresh("i", "general", "standard", ns)
            assert calls == [], "quiet refresh must skip the slice walk"
            f.set_bit(1, 6)  # epoch moves: next refresh must re-walk
            sv2 = mgr.refresh("i", "general", "standard", ns)
            assert calls, "post-write refresh must walk the slices"
            assert sv2 is sv  # existing container: incremental, no restage
            calls.clear()
            mgr.refresh("i", "general", "standard", ns)
            assert calls == [], "walk re-stamps the validation epoch"
        finally:
            holder.fragment = orig

    def test_unrelated_write_rewalks_once_then_quiet(self, holder):
        seed(holder, bits=[(1, 5)])
        other = seed(holder, index="j", bits=[(0, 1)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        ns = holder.index("i").max_slice() + 1
        calls, orig = self._spy(holder)
        try:
            mgr.refresh("i", "general", "standard", ns)
            assert calls == []
            other.set_bit(0, 2)  # unrelated index still moves the
            #                      process-wide pair: conservative walk
            mgr.refresh("i", "general", "standard", ns)
            assert calls, "process-wide counter: unrelated write re-walks"
            calls.clear()
            mgr.refresh("i", "general", "standard", ns)
            assert calls == [], "...but exactly once"
        finally:
            holder.fragment = orig

    def test_counts_stay_correct_across_quiet_windows(self, holder):
        f = seed(holder, bits=[(7, c) for c in range(20)])
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = "Count(Bitmap(rowID=7))"
        assert q(e, "i", pql) == q(host, "i", pql) == [20]
        for col in (100, SLICE_WIDTH + 1, 5):  # 5 = already set
            f.set_bit(7, col)
            assert q(e, "i", pql) == q(host, "i", pql)


class TestColdStartServing:
    def test_lazy_holder_stages_loaded_data(self, tmp_path):
        """A cold-reopened holder defers fragment parsing; staging must
        force the load — not ship empty pools to the device."""
        from pilosa_tpu.core import Holder

        h = Holder(str(tmp_path / "d"))
        h.open()
        seed(h, bits=[(1, 5), (1, SLICE_WIDTH + 9), (2, 5)])
        h.close()

        h2 = Holder(str(tmp_path / "d"))
        h2.open()  # lazy: nothing parsed yet
        try:
            e = Executor(h2, use_device=True)
            assert q(e, "i", "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))") \
                == [1]
            assert e.mesh_manager().stats["count"] == 1
        finally:
            h2.close()


class TestDeleteRecreate:
    def test_recreated_index_restages(self, holder):
        """Generations are only comparable on the SAME Fragment object:
        a deleted-and-recreated index must restage, never scatter a new
        fragment's log onto the old device image."""
        seed(holder, bits=[(1, c) for c in range(40)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [40]
        holder.delete_index("i")
        e.invalidate_device_index("i")
        f = seed(holder, bits=[(1, c) for c in range(7)])
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [7]
        # And without the eager invalidate, object identity still catches
        # the swap: delete/recreate again, no invalidate call this time.
        holder.delete_index("i")
        seed(holder, bits=[(1, c) for c in range(3)])
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [3]


class TestServedTopN:
    def seed_rows(self, holder, rows=40, frame="general"):
        rng = np.random.default_rng(3)
        f = seed(holder, frame=frame)
        for r in range(rows):
            cols = rng.choice(SLICE_WIDTH * 2, size=r + 1, replace=False)
            for c in cols:
                f.set_bit(r, int(c))
        return f

    def test_topn_matches_host(self, holder):
        self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for pql in ("TopN(frame=general, n=5)",
                    "TopN(frame=general)"):
            assert q(e, "i", pql) == q(host, "i", pql)
        assert e.mesh_manager().stats["topn"] > 0

    def test_topn_threshold_filters_exact_totals(self, holder):
        """Deviation from the reference (documented in serve.top_n):
        threshold applies to exact totals, so every row with true count
        >= 20 survives — the host path drops rows whose PER-SLICE count
        dips under the threshold (fragment.go:522-614 artifact)."""
        self.seed_rows(holder)  # row r has exactly r+1 bits
        e = Executor(holder, use_device=True)
        out = q(e, "i", "TopN(frame=general, n=10, threshold=20)")[0]
        assert out == [(r, r + 1) for r in range(39, 29, -1)]

    def test_topn_threshold_divergence_from_host(self, holder):
        """Demonstrates the documented deviation EXPLICITLY (VERDICT r2
        weak #5): a row spread thinly across slices vanishes from the
        HOST TopN — the reference applies MinThreshold inside every
        fragment (fragment.go:522-614), and no single fragment clears
        it — while the device path filters the exact totals and keeps
        it. The device answer is the semantically-right one; this test
        exists so a future reader sees the divergence, not just the
        docstring."""
        f = seed(holder)
        for c in range(30):
            f.set_bit(1, c)                      # row 1: 30 bits, slice 0
        for c in range(20):
            f.set_bit(2, c)                      # row 2: 20 bits slice 0
            f.set_bit(2, SLICE_WIDTH + c)        #        +20 bits slice 1
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = "TopN(frame=general, n=5, threshold=25)"
        dev = q(e, "i", pql)[0]
        assert dev == [(2, 40), (1, 30)]         # exact totals clear 25
        assert q(host, "i", pql)[0] == [(1, 30)]  # row 2 vanished per-slice

    def test_topn_ids_exact_phase(self, holder):
        self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = "TopN(frame=general, ids=[3, 17, 39])"
        assert q(e, "i", pql) == q(host, "i", pql)

    def test_topn_large_row_space_differential(self, holder):
        """Thousands of rows with mixed container forms: the one-pass
        device TopN must match the host path's exact recount (VERDICT
        r1 item 8: differential vs Fragment.top at large row counts)."""
        from pilosa_tpu.roaring.bitmap import Bitmap, Container

        rng = np.random.default_rng(11)
        f = seed(holder)
        view = f.create_view_if_not_exists("standard")
        for s in range(2):
            frag = view.create_fragment_if_not_exists(s)
            b = Bitmap()
            for r in range(3000):
                if rng.random() < 0.2:
                    continue
                n = int(rng.integers(1, 600))
                vals = np.sort(rng.choice(65536, size=n, replace=False)
                               ).astype(np.uint32)
                b.keys.append(r * 16)
                b.containers.append(Container(array=vals))
            with frag._mu:
                b.op_writer = None
                frag.storage = b
                frag._mark_dirty(None)
            frag.rebuild_cache()
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        # n=0 disables the host's per-slice candidate cut, so the host
        # list is exact and fully comparable. For bounded n the device
        # must equal the exact top-n — the host's own n=50 answer can
        # MISS a globally-high row that sat below each slice's top-50
        # (the reference's phase-1 approximation, executor.go:273-310).
        exact = q(host, "i", "TopN(frame=general)")[0]
        assert q(e, "i", "TopN(frame=general)")[0] == exact
        for n in (50, 7):
            dev = q(e, "i", f"TopN(frame=general, n={n})")[0]
            assert dev == exact[:n]
        assert e.mesh_manager().stats["topn"] > 0

    def test_topn_src_bitmap_on_device(self, holder):
        """TopN(Bitmap(src), ...) — the src tree evaluates on device
        and intersects every row in one pass; results must match the
        host path exactly (small data: host phase 1 is complete)."""
        rng = np.random.default_rng(7)
        f = seed(holder)
        for r in range(12):
            for c in rng.choice(SLICE_WIDTH * 2, size=5 * (r + 1),
                                replace=False):
                f.set_bit(r, int(c))
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for pql in (
            "TopN(Bitmap(rowID=11, frame=general), frame=general, n=6)",
            "TopN(Bitmap(rowID=11, frame=general), frame=general)",
            "TopN(Intersect(Bitmap(rowID=10, frame=general), "
            "Bitmap(rowID=11, frame=general)), frame=general, n=4)",
            "TopN(Bitmap(rowID=11, frame=general), frame=general, "
            "ids=[2, 5, 9])",
        ):
            dev = q(e, "i", pql)[0]
            want = q(host, "i", pql)[0]
            assert dev == want, (pql, dev, want)
        assert e.mesh_manager().stats["topn"] > 0

    def test_topn_src_empty_row(self, holder):
        f = seed(holder, bits=[(1, 0), (1, 5), (2, 5)])
        e = Executor(holder, use_device=True)
        pql = "TopN(Bitmap(rowID=99, frame=general), frame=general, n=5)"
        assert q(e, "i", pql) == [[]]

    def test_topn_ids_on_empty_view(self, holder):
        """ids recount against a frame with no rows: [] (a regression
        here crashed on an empty staged row table)."""
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general")
        f.set_bit(1, 0)
        f.clear_bit(1, 0)  # view exists, zero containers
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        out = mgr.top_n("i", "general", "standard", [0], 1, 0, [1, 2], 1)
        assert out == []

    def test_topn_tanimoto_with_attr_filters(self, holder):
        """filters + tanimoto combined: the attr predicate must apply
        inside the tanimoto walk (regression: the device path once
        dropped filters when tanimoto was set)."""
        rng = np.random.default_rng(31)
        f = seed(holder)
        for r in range(6):
            for c in rng.choice(4096, size=80 * (r + 1), replace=False):
                f.set_bit(r, int(c))
        f.row_attr_store.set_attrs(3, {"cat": "x"})
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = ('TopN(Bitmap(rowID=5, frame=general), frame=general, n=5, '
               'field="cat", filters=["x"], tanimotoThreshold=10)')
        assert q(e, "i", pql) == q(host, "i", pql)

    def test_topn_attr_filters_device_counts_host_walk(self, holder):
        """Attr-filtered TopN: exact device counts + a bounded host
        attr walk — matches the host path; tanimoto stays host-only."""
        f = self.seed_rows(holder, rows=8)
        f.row_attr_store.set_attrs(3, {"cat": "x"})
        f.row_attr_store.set_attrs(6, {"cat": "x"})
        f.row_attr_store.set_attrs(7, {"cat": "y"})
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for pql in ('TopN(frame=general, n=5, field="cat", filters=["x"])',
                    'TopN(frame=general, field="cat", filters=["x", "y"])'):
            assert q(e, "i", pql) == q(host, "i", pql)
        assert e.mesh_manager().stats["topn"] > 0

    def test_topn_tanimoto_on_device(self, holder):
        """Tanimoto band from three exact device vectors. Single-slice
        data: the host applies the candidacy band to per-slice counts,
        the device to exact totals — they only provably coincide when
        one slice holds everything."""
        rng = np.random.default_rng(29)
        f = seed(holder)
        for r in range(10):
            for c in rng.choice(4096, size=40 * (r + 1), replace=False):
                f.set_bit(r, int(c))
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        for t in (30, 60, 90):
            pql = ("TopN(Bitmap(rowID=9, frame=general), frame=general, "
                   f"n=5, tanimotoThreshold={t})")
            dev = q(e, "i", pql)[0]
            want = q(host, "i", pql)[0]
            assert dev == want, (t, dev, want)
        assert e.mesh_manager().stats["topn"] > 0


class TestTopNMemo:
    """The device rank-cache analog (VERDICT r2 #4): a repeat TopN on
    an unchanged image serves from the completed-result memo without
    entering any collective; any image swap invalidates it."""

    def seed_rows(self, holder):
        bits = [(r, c) for r in range(8) for c in range(0, (r + 1) * 4)]
        return seed(holder, bits=bits)

    @staticmethod
    def _poison_rowcounts(mgr):
        real = dict(mgr._rowcount_fns)

        def boom(*a, **kw):
            raise AssertionError("collective entered; memo hit expected")

        for k in mgr._rowcount_fns:
            mgr._rowcount_fns[k] = boom
        return real

    def test_repeat_topn_enters_no_collective(self, holder):
        self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        first = q(e, "i", "TopN(frame=general, n=4)")
        mgr = e.mesh_manager()
        assert mgr.stats["memo_store"] == 1
        self._poison_rowcounts(mgr)
        assert q(e, "i", "TopN(frame=general, n=4)") == first
        # Different n / threshold / ids reuse the same counts vector.
        assert q(e, "i", "TopN(frame=general, n=2)")[0] == first[0][:2]
        assert mgr.stats["memo_hit"] == 2

    def test_write_invalidates_memo(self, holder):
        f = self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        q(e, "i", "TopN(frame=general, n=3)")
        mgr = e.mesh_manager()
        assert mgr.stats["memo_size"] == 1
        f.set_bit(7, 100)  # existing container: incremental scatter
        out = q(e, "i", "TopN(frame=general, n=3)")[0]
        assert out[0] == (7, 33)  # sees the write
        assert mgr.stats["memo_hit"] == 0  # purged, not hit stale
        # ...and the post-write result is memoized in turn.
        self._poison_rowcounts(mgr)
        assert q(e, "i", "TopN(frame=general, n=3)")[0] == out

    def test_stale_epoch_store_dropped(self, holder):
        """A result computed before a purge must not insert after it —
        it would pin the replaced device image unreachably."""
        self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        epoch = mgr._memo_epoch
        with mgr._mu:
            mgr._purge_memo(object())  # any purge advances the epoch
        mgr._memo_put(("x",), 1, (), epoch)
        assert ("x",) not in mgr._topn_memo  # stale store dropped
        mgr._memo_put(("x",), 1, (), mgr._memo_epoch)
        assert ("x",) in mgr._topn_memo

    def test_mask_change_misses_memo(self, holder):
        self.seed_rows(holder)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        a = mgr.row_counts("i", "general", "standard", [0], 1)
        b = mgr.row_counts("i", "general", "standard", [0], 2)
        assert a is not None and b is not None
        assert mgr.stats["memo_hit"] == 0
        assert mgr.stats["memo_store"] == 2


class TestCostRouting:
    """Cost-based engine routing (VERDICT r2 #2): a small Count must
    serve from the host kernels — not pay the device dispatch floor —
    while large slice batches stay on the mesh."""

    BITS = [(1, c) for c in range(50)] + [(2, c) for c in range(0, 50, 2)]

    def test_small_query_routes_to_host(self, holder):
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True, device_min_work=192)
        host = Executor(holder, use_device=False)
        pql = "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))"
        assert q(e, "i", pql) == q(host, "i", pql) == [25]
        mgr = e.mesh_manager()
        assert mgr.stats["routed_host"] == 1
        assert mgr.stats["count"] == 0  # the mesh never served it

    def test_large_query_stays_on_device(self, holder, monkeypatch):
        # The suite runs on a cpu backend, where backend-aware routing
        # would send an above-threshold fold to the native host kernels
        # too — pin the escape hatch off so this prices the DEVICE leg.
        monkeypatch.setenv("PILOSA_TPU_CPU_ROUTE_NATIVE", "off")
        seed(holder, bits=self.BITS)
        poison_per_slice(monkeypatch)
        e = Executor(holder, use_device=True, device_min_work=1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [50]
        mgr = e.mesh_manager()
        assert mgr.stats["routed_host"] == 0
        assert mgr.stats["count"] == 1

    def test_large_query_routes_to_host_on_cpu_backend(self, holder,
                                                       monkeypatch):
        # Backend-aware routing: above the work threshold, a cpu
        # backend serves from the native C++ kernels — JAX-on-CPU has
        # no accelerator to win the fold back.
        from pilosa_tpu.ops import native
        if not native.has_native():
            pytest.skip("native kernels unavailable")
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True, device_min_work=1)
        host = Executor(holder, use_device=False)
        pql = "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))"
        assert q(e, "i", pql) == q(host, "i", pql) == [25]
        mgr = e.mesh_manager()
        assert mgr.stats["routed_host"] == 1
        assert mgr.stats["count"] == 0

    def test_backend_aware_routing_skips_tpu(self, holder, monkeypatch):
        # On a tpu backend the above-threshold query must NOT route.
        import jax

        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True, device_min_work=1)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert not e._route_to_host(num_slices=1, num_leaves=1)
        # verdict is cached: flipping the backend later cannot re-route
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert not e._route_to_host(num_slices=1, num_leaves=1)

    def test_zero_threshold_disables_routing(self, holder):
        seed(holder, bits=self.BITS)
        # Threshold 0 (the suite's conftest default) = every lowerable
        # tree serves on the mesh regardless of size.
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [50]
        assert e.mesh_manager().stats["routed_host"] == 0
        assert e.mesh_manager().stats["count"] == 1

    def test_env_threshold(self, holder, monkeypatch):
        seed(holder, bits=self.BITS)
        monkeypatch.setenv("PILOSA_TPU_DEVICE_MIN_WORK", "64")
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [50]
        assert e.mesh_manager().stats["routed_host"] == 1


class TestLoneFusedDispatch:
    """Single-dispatch serving fast path: a LONE Count runs as one
    fused jitted program whose gather metadata and slice mask ride the
    call as host arguments — the per-query device-dispatch counter
    must read exactly 1, vs 3 for the chained upload+launch path."""

    # rows: 0 -> 41 bits, 1 -> 20 bits, 2 -> 2 bits, 3 -> 5 bits
    BITS = ([(0, c) for c in range(40)] + [(0, 2 * SLICE_WIDTH + 7)]
            + [(1, c) for c in range(0, 40, 2)]
            + [(2, SLICE_WIDTH + 3), (2, 2 * SLICE_WIDTH + 7)]
            + [(3, c) for c in range(5)])

    @staticmethod
    def _lower(holder, pql):
        from pilosa_tpu.parallel.plan import _lower_tree

        tree = parse_string(pql).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        assert shape is not None, pql
        return shape, leaves

    def test_lone_count_is_one_dispatch(self, holder):
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        warm = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", warm) == q(host, "i", warm) == [20]
        assert mgr.stats["lone_fused"] == 1
        # DISTINCT queries (cold per-row metadata, and for the union/
        # difference shapes a cold compiled plan): one dispatch each.
        for pql, want in [
            ("Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=2)))", 1),
            ("Count(Union(Bitmap(rowID=1), Bitmap(rowID=2)))", 22),
            ("Count(Difference(Bitmap(rowID=0), Bitmap(rowID=1)))", 21),
        ]:
            shape, leaves = self._lower(holder, pql)
            d0 = mgr.stats["device_dispatches"]
            got = mgr.count("i", shape, leaves, [0, 1, 2], 3)
            assert got == q(host, "i", pql)[0] == want, pql
            assert mgr.stats["device_dispatches"] - d0 == 1, pql
        # repeat of a seen query: still one dispatch, now all-cache-hit
        shape, leaves = self._lower(
            holder, "Count(Union(Bitmap(rowID=1), Bitmap(rowID=2)))")
        d0 = mgr.stats["device_dispatches"]
        assert mgr.count("i", shape, leaves, [0, 1, 2], 3) == 22
        assert mgr.stats["device_dispatches"] - d0 == 1
        # one plan per distinct (shape, widths, backend) key
        assert mgr._fused_plans.stats["miss"] == 3
        assert mgr._fused_plans.stats["hit"] >= 1

    def test_chained_path_pays_three_dispatches(self, holder):
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        mgr.lone_fused = False
        # warm: stages the view, uploads the slice mask, compiles
        q(e, "i", "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))")
        assert mgr.stats["lone_fused"] == 0
        # distinct query with two never-resolved rows, warm mask:
        # 2 leaf metadata uploads + 1 program launch
        pql = "Count(Intersect(Bitmap(rowID=2), Bitmap(rowID=3)))"
        shape, leaves = self._lower(holder, pql)
        d0 = mgr.stats["device_dispatches"]
        assert mgr.count("i", shape, leaves, [0, 1, 2], 3) == 0
        assert mgr.stats["device_dispatches"] - d0 == 3

    def test_range_lone_count_is_one_dispatch(self, holder):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general", time_quantum="YMD")
        from datetime import datetime

        f.set_bit(1, 3, datetime(2017, 4, 2, 9, 0))
        f.set_bit(1, SLICE_WIDTH + 8, datetime(2017, 4, 3, 9, 0))
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        pql = ("Count(Range(rowID=1, frame=general, "
               "start=\"2017-04-01T00:00\", end=\"2017-04-30T00:00\"))")
        assert q(e, "i", pql) == q(host, "i", pql) == [2]
        assert mgr.stats["lone_fused"] == 1
        # distinct Range (different window -> different view-OR tree):
        # fused, one dispatch, no materialize-then-count hop
        pql2 = ("Count(Range(rowID=1, frame=general, "
                "start=\"2017-04-01T00:00\", end=\"2017-04-03T00:00\"))")
        shape, leaves = self._lower(holder, pql2)
        d0 = mgr.stats["device_dispatches"]
        assert mgr.count("i", shape, leaves, [0, 1], 2) \
            == q(host, "i", pql2)[0] == 1
        assert mgr.stats["device_dispatches"] - d0 == 1

    def test_lone_fused_env_kill_switch(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_LONE_FUSED", "off")
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", pql) == q(host, "i", pql) == [20]
        mgr = e.mesh_manager()
        assert mgr.lone_fused is False
        assert mgr.stats["lone_fused"] == 0
        assert mgr.stats["count"] == 1  # chained mesh path served it

    def test_fused_matches_chained_after_writes(self, holder):
        f = seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", pql) == [20]
        f.clear_bit(1, 0)
        f.set_bit(0, 41)
        shape, leaves = self._lower(holder, pql)
        got = mgr.count("i", shape, leaves, [0, 1, 2], 3)
        host = Executor(holder, use_device=False)
        assert got == q(host, "i", pql)[0] == 19
        assert mgr.stats["lone_fused"] >= 2


class TestFragmentPoolIncremental:
    def test_set_bits_skip_rebuild(self, holder, monkeypatch):
        f = seed(holder, bits=[(1, c) for c in range(16)])
        frag = holder.fragment("i", "general", "standard", 0)
        _ = frag.pool  # initial build

        import pilosa_tpu.ops.pool as pool_mod

        calls = {"n": 0}
        orig = pool_mod.build_pool_arrays

        def counting(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        # core/fragment resolves build_pool_arrays through pilosa_tpu.ops'
        # lazy __getattr__, which re-reads the pool module each time — so
        # patching the pool module is sufficient.
        monkeypatch.setattr(pool_mod, "build_pool_arrays", counting)

        for c in range(16, 48):
            f.set_bit(1, c)
        pool, row_ids = frag.pool
        assert calls["n"] == 0  # scatter path, no rebuild

        from pilosa_tpu.ops.pool import pool_row_counts

        counts = np.asarray(pool_row_counts(pool, len(row_ids)))
        assert counts[0] == 48

    def test_churn_rebuilds(self, holder):
        f = seed(holder, bits=[(1, 0)])
        frag = holder.fragment("i", "general", "standard", 0)
        _ = frag.pool
        f.set_bit(2, 70000)  # new container
        pool, row_ids = frag.pool
        assert list(row_ids) == [1, 2]

    def test_clear_to_empty_rebuilds(self, holder):
        f = seed(holder, bits=[(1, 0), (2, 70000)])
        frag = holder.fragment("i", "general", "standard", 0)
        _ = frag.pool
        f.clear_bit(2, 70000)  # container emptied → removed
        pool, row_ids = frag.pool
        assert list(row_ids) == [1]


class TestWideCount:
    def test_count_limbs_exceed_int32(self):
        """A dense multi-slice count past 2^31 must not saturate
        (VERDICT r1 item 9). 2056 slices x 2^20 dense bits = 2.156e9."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pilosa_tpu.ops.pool import CONTAINER_WORDS, ROW_SPAN
        from pilosa_tpu.parallel import (
            ShardedIndex,
            combine_count,
            compile_serve_count,
            default_mesh,
        )

        from pilosa_tpu.parallel import resolve_row_indices

        s = 2056
        mesh = default_mesh()
        keys = np.broadcast_to(np.arange(ROW_SPAN, dtype=np.int32),
                               (s, ROW_SPAN)).copy()
        words = np.full((s, ROW_SPAN, CONTAINER_WORDS), 0xFFFFFFFF,
                        dtype=np.uint32)
        sharding = NamedSharding(mesh, P("slices"))
        index = ShardedIndex(keys=jax.device_put(keys, sharding),
                             words=jax.device_put(words, sharding))
        flat_idx, hit = resolve_row_indices(keys, 0)
        assert hit.all()
        fn = compile_serve_count(mesh, ["leaf"], 1)  # -> (2, 1) limbs
        args = ((index.words,), (jax.device_put(flat_idx, sharding),),
                (jax.device_put(hit, sharding),))
        assert combine_count(
            np.asarray(fn(*args, np.ones(s, dtype=np.int32)))[:, 0]) \
            == s * (1 << 20)
        # Masking half the slices halves the count.
        mask = np.zeros(s, dtype=np.int32)
        mask[: s // 2] = 1
        assert combine_count(np.asarray(fn(*args, mask))[:, 0]) \
            == (s // 2) * (1 << 20)


class TestConcurrentWriteQueryFuzz:
    def test_racing_writes_and_counts_converge(self, holder):
        """Random set/clear bits racing served counts: in-flight
        queries may see any prefix of the writes, but after quiescing,
        the device totals must equal the host's exactly (staleness or
        double-application in the refresh/scatter path would diverge)."""
        import threading as th

        rng = np.random.default_rng(17)
        f = seed(holder, bits=[(r, c) for r in range(4) for c in range(40)])
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        from pilosa_tpu.pql import parse_string

        queries = [parse_string(
            f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))")
            for a, b in [(0, 1), (1, 2), (2, 3)]]
        stop = th.Event()
        errors = []

        def writer(seed_):
            rng_ = np.random.default_rng(seed_)  # Generator isn't thread-safe
            try:
                while not stop.is_set():
                    r = int(rng_.integers(0, 4))
                    c = int(rng_.integers(0, 128))  # stays in container 0
                    if rng_.random() < 0.7:
                        f.set_bit(r, c)
                    else:
                        f.clear_bit(r, c)
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        def reader(seed_):
            rng_ = np.random.default_rng(seed_)
            try:
                while not stop.is_set():
                    q_ = queries[int(rng_.integers(0, len(queries)))]
                    v = e.execute("i", q_)[0]
                    assert isinstance(v, int) and v >= 0
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        threads = [th.Thread(target=writer, args=(21,)),
                   th.Thread(target=writer, args=(22,)),
                   th.Thread(target=reader, args=(23,)),
                   th.Thread(target=reader, args=(24,))]
        for t in threads:
            t.start()
        import time as _time

        _time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(30)
        assert not errors, errors
        # Quiesced: served results must now match the host exactly.
        for q_ in queries:
            assert e.execute("i", q_)[0] == host.execute("i", q_)[0]
        mgr = e.mesh_manager()
        assert mgr.stats["count"] > 0


class TestDeviceStartsCache:
    """_device_starts: value-keyed LRU of replicated uniform-starts
    vectors — repeated herd compositions must reuse one device handle;
    different values must not collide."""

    def test_value_keyed_reuse_and_distinctness(self, holder):
        seed(holder, bits=[(1, 5)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        a = np.asarray([3, 7], dtype=np.int32)
        b = np.asarray([3, 7], dtype=np.int32)  # equal value, new object
        c = np.asarray([3, 8], dtype=np.int32)
        da = mgr._device_starts(a)
        assert mgr._device_starts(b) is da, "equal values share one handle"
        dc = mgr._device_starts(c)
        assert dc is not da
        assert np.asarray(da).tolist() == [3, 7]
        assert np.asarray(dc).tolist() == [3, 8]

    def test_key_includes_dtype_and_shape(self, holder):
        """Same raw bytes, different dtype or shape, must not collide:
        int32 [1, 0] and int64 [1] share a byte string, as do a flat
        vector and its 2-D reshape."""
        seed(holder, bits=[(1, 5)])
        e = Executor(holder, use_device=True)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        a32 = np.asarray([1, 0], dtype=np.int32)
        a64 = np.asarray([1], dtype=np.int64)
        assert a32.tobytes() == a64.tobytes()  # the collision this guards
        da = mgr._device_starts(a32)
        db = mgr._device_starts(a64)  # must NOT alias da's [1, 0]
        assert np.asarray(da).tolist() == [1, 0]
        assert np.asarray(db).tolist() == [1]
        flat = np.asarray([3, 7, 1, 2], dtype=np.int32)
        grid = flat.reshape(2, 2)
        dflat = mgr._device_starts(flat)
        dgrid = mgr._device_starts(grid)
        assert np.asarray(dflat).shape == (4,)
        assert np.asarray(dgrid).shape == (2, 2)


class TestDynamicBatching:
    def seed_many_rows(self, holder):
        bits = []
        for r in range(12):
            bits += [(r, c) for c in range(0, (r + 1) * 3)]
            bits += [(r, SLICE_WIDTH + c) for c in range(0, r + 1)]
        return seed(holder, bits=bits)

    def test_count_group_matches_individual(self, holder):
        """A coalesced batch program returns the same counts as the
        unbatched path, including the power-of-two pad entries."""
        self.seed_many_rows(holder)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.serve import _CountRequest
        from pilosa_tpu.pql import parse_string

        host = Executor(holder, use_device=False)
        group, want = [], []
        for a, b in [(0, 1), (2, 3), (4, 11)]:
            pql = f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"
            tree = parse_string(pql).calls[0].children[0]
            leaves = []
            shape = _lower_tree(holder, "i", tree, leaves)
            assert shape is not None
            prepared = mgr._count_args("i", shape, leaves, [0, 1], 2)
            assert prepared is not None
            group.append(_CountRequest(*prepared))
            want.append(host.execute("i", parse_string(pql))[0])
        mgr._run_count_group(group)
        got = [r.result for r in group]
        assert got == want
        assert mgr.stats["batched"] == 3

    def test_identical_requests_dedup_in_group(self, holder):
        """N identical queued counts collapse to one program slot and
        all receive the same (correct) result."""
        self.seed_many_rows(holder)
        e = Executor(holder, use_device=True)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.serve import _CountRequest
        from pilosa_tpu.pql import parse_string

        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        tree = parse_string(pql).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        want = Executor(holder, use_device=False).execute(
            "i", parse_string(pql))[0]
        group = []
        for _ in range(5):
            prepared = mgr._count_args("i", shape, leaves, [0, 1], 2)
            group.append(_CountRequest(*prepared))
        before = mgr.stats["batched"]
        mgr._run_count_group(group)
        assert [r.result for r in group] == [want] * 5
        # All five were the same args objects -> one unbatched program.
        assert mgr.stats["batched"] == before

    def test_concurrent_row_counts_share_inflight(self, holder):
        """Identical concurrent TopN row-count calls share one device
        execution (in-flight dedup) and all get exact results."""
        import threading as th

        self.seed_many_rows(holder)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        from pilosa_tpu.pql import parse_string

        q_ = parse_string("TopN(frame=general, n=4)")
        want = host.execute("i", q_)[0]
        results, errors = [], []

        def client():
            try:
                results.append(e.execute("i", q_)[0])
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        threads = [th.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors
        # Exact device counts == host's exact list prefix.
        exact = host.execute(
            "i", parse_string("TopN(frame=general)"))[0][:4]
        assert results == [exact] * 8
        assert want == exact  # sanity: host agrees on this workload

    def test_inflight_waiter_shares_leader_result(self, holder):
        """Deterministic single-flight proof: while the leader's device
        call is gated, a second identical call must become a waiter
        and receive the leader's result (stats['inflight_shared'])."""
        import threading as th

        self.seed_many_rows(holder)
        e = Executor(holder, use_device=True)
        from pilosa_tpu.pql import parse_string

        e.execute("i", parse_string("TopN(frame=general, n=2)"))  # warm
        mgr = e.mesh_manager()
        # The warm query memoized its result; drop it so the next two
        # calls actually race into the gated device function.
        mgr._topn_memo.clear()
        padded = next(iter(mgr._rowcount_fns))
        real_fn = mgr._rowcount_fns[padded]
        gate = th.Event()
        entered = th.Event()

        def gated(*a, **kw):
            entered.set()
            assert gate.wait(30)
            return real_fn(*a, **kw)

        mgr._rowcount_fns[padded] = gated
        out = {}

        def leader():
            _, call = mgr._row_counts_call(
                "i", "general", "standard", [0, 1], 2)
            out["a"] = np.asarray(call())

        ta = th.Thread(target=leader)
        ta.start()
        assert entered.wait(30)

        def waiter():
            _, call = mgr._row_counts_call(
                "i", "general", "standard", [0, 1], 2)
            out["b"] = np.asarray(call())

        tb = th.Thread(target=waiter)
        tb.start()
        # Give the waiter time to register against the in-flight entry,
        # then release the leader.
        import time as _time

        _time.sleep(0.2)
        gate.set()
        ta.join(30)
        tb.join(30)
        mgr._rowcount_fns[padded] = real_fn
        assert mgr.stats["inflight_shared"] == 1
        assert (out["a"] == out["b"]).all()

    def test_concurrent_counts_coalesce_correctly(self, holder):
        """Many threads hammering Count: every result must be exact
        regardless of how the batch loop groups them."""
        import threading as th

        self.seed_many_rows(holder)
        e = Executor(holder, use_device=True)
        host = Executor(holder, use_device=False)
        from pilosa_tpu.pql import parse_string

        pairs = [(a, (a + 1) % 12) for a in range(12)]
        want = {p: host.execute(
            "i", parse_string(f"Count(Intersect(Bitmap(rowID={p[0]}), "
                              f"Bitmap(rowID={p[1]})))"))[0]
            for p in pairs}
        results, errors = {}, []

        def worker(p):
            try:
                q_ = parse_string(f"Count(Intersect(Bitmap(rowID={p[0]}), "
                                  f"Bitmap(rowID={p[1]})))")
                for _ in range(3):
                    results.setdefault(p, []).append(e.execute("i", q_)[0])
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        threads = [th.Thread(target=worker, args=(p,)) for p in pairs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors
        for p, vals in results.items():
            assert vals == [want[p]] * 3, (p, vals, want[p])


class TestPallasChunking:
    def test_slab_scan_with_remainder_matches(self, monkeypatch):
        """Prime-ish slice counts run fixed slabs + a remainder call —
        results must match the unchunked kernel (and numpy)."""
        import pilosa_tpu.ops.kernels as kernels

        rng = np.random.default_rng(5)
        S, cap, L = 5, 4, 2
        from pilosa_tpu.ops.pool import CONTAINER_WORDS

        words = rng.integers(0, 2**32, size=(S, cap, CONTAINER_WORDS),
                             dtype=np.uint32)
        idx = rng.integers(0, cap, size=(L, S, 16), dtype=np.int32)
        hit = rng.integers(0, 2, size=(L, S, 16), dtype=np.int32)
        tree = ["and", ["leaf", 0], ["leaf", 1]]

        import jax.numpy as jnp

        full = int(kernels.tree_count_pallas(
            jnp.asarray(words), jnp.asarray(idx), jnp.asarray(hit), tree,
            interpret=True))
        monkeypatch.setattr(kernels, "_PREFETCH_SLICES_PER_LEAF", 4)
        chunked = int(kernels.tree_count_pallas(
            jnp.asarray(words), jnp.asarray(idx), jnp.asarray(hit), tree,
            interpret=True))  # chunk=2: 2 slabs + remainder of 1
        blocks = [np.where(hit[l][:, :, None] != 0,
                           words[np.arange(S)[:, None], idx[l]], 0)
                  for l in range(L)]
        want = int(np.bitwise_count(blocks[0] & blocks[1]).sum())
        assert full == chunked == want


class TestPlanSliceMutations:
    def test_mixed_set_clear_same_word(self):
        from pilosa_tpu.ops.pool import plan_slice_mutations

        keys = np.array([0, 1], dtype=np.int32)  # row 0, containers 0-1
        row_ids = np.array([0], dtype=np.uint64)
        pos = np.array([0, 1, 2], dtype=np.uint64)  # same word 0
        val = np.array([True, False, True])
        slot, word, sm, cm = plan_slice_mutations(keys, row_ids, pos, val)
        assert len(slot) == 1 and slot[0] == 0 and word[0] == 0
        assert sm[0] == 0b101 and cm[0] == 0b010

    def test_set_missing_container_raises(self):
        from pilosa_tpu.ops.pool import plan_slice_mutations

        keys = np.array([0], dtype=np.int32)
        row_ids = np.array([0], dtype=np.uint64)
        with pytest.raises(KeyError):
            plan_slice_mutations(keys, row_ids,
                                 np.array([70000], dtype=np.uint64),
                                 np.array([True]))

    def test_clear_missing_container_dropped(self):
        from pilosa_tpu.ops.pool import plan_slice_mutations

        keys = np.array([0], dtype=np.int32)
        row_ids = np.array([0], dtype=np.uint64)
        slot, word, sm, cm = plan_slice_mutations(
            keys, row_ids, np.array([70000], dtype=np.uint64),
            np.array([False]))
        assert len(slot) == 0


class TestCoarseGather:
    """The whole-row coarse-gather fast path (mesh.coarse_row_starts +
    compile_serve_count, runs=True): eligibility detection, correctness vs
    the host path, and fallback to the general container gather for
    partial/unaligned rows. The gather-granularity analog of the
    reference's container-TYPE kernel dispatch (roaring.go:1270-1351)."""

    @staticmethod
    def seed_full_rows(holder, rows, slices):
        """Each (row, slice) gets all 16 containers (one bit per 2^16
        block), so rows stage as contiguous aligned runs."""
        f = seed(holder)
        for r in rows:
            for s in slices:
                for blk in range(16):
                    f.set_bit(r, s * SLICE_WIDTH + blk * 65536 + r + s)
        return f

    def test_coarse_starts_eligible_dense(self):
        from pilosa_tpu.parallel.mesh import coarse_row_starts

        # two slices, two full rows each: keys 0..31 sorted
        keys = np.tile(np.arange(32, dtype=np.int32), (2, 1))
        out = coarse_row_starts(keys, 1)
        assert out is not None
        starts, valid = out
        assert starts.tolist() == [1, 1]
        assert valid.tolist() == [1, 1]

    def test_coarse_starts_absent_slice_valid_zero(self):
        from pilosa_tpu.ops.pool import INVALID_KEY
        from pilosa_tpu.parallel.mesh import coarse_row_starts

        keys = np.full((2, 32), INVALID_KEY, dtype=np.int32)
        keys[0, :32] = np.arange(32)     # slice 0: rows 0,1 full
        keys[1, :16] = np.arange(16)     # slice 1: row 0 only
        out = coarse_row_starts(keys, 1)
        assert out is not None
        starts, valid = out
        assert valid.tolist() == [1, 0]
        assert starts[0] == 1

    def test_coarse_starts_partial_row_ineligible(self):
        from pilosa_tpu.ops.pool import INVALID_KEY
        from pilosa_tpu.parallel.mesh import coarse_row_starts

        keys = np.full((1, 32), INVALID_KEY, dtype=np.int32)
        keys[0, :15] = np.arange(15)     # row 0 missing sub-key 15
        assert coarse_row_starts(keys, 0) is None

    def test_coarse_starts_unaligned_ineligible(self):
        from pilosa_tpu.ops.pool import INVALID_KEY
        from pilosa_tpu.parallel.mesh import coarse_row_starts

        keys = np.full((1, 32), INVALID_KEY, dtype=np.int32)
        keys[0, 0] = 5                   # stray container below row 1
        keys[0, 1:17] = np.arange(16, 32)
        assert coarse_row_starts(keys, 1) is None

    def test_coarse_starts_absent_everywhere(self):
        from pilosa_tpu.parallel.mesh import coarse_row_starts

        keys = np.tile(np.arange(16, dtype=np.int32), (2, 1))
        assert coarse_row_starts(keys, 7) is None

    def test_full_rows_serve_coarse_and_match_host(self, holder):
        self.seed_full_rows(holder, rows=(0, 1, 2), slices=(0, 1, 2))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        mgr.lone_fused = False  # pin the chained coarse path under test
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        got = q(e, "i", pql)[0]
        assert got == q(host, "i", pql)[0]
        assert mgr.stats["coarse"] >= 1

    def test_absent_slice_row_serves_coarse(self, holder):
        # row 0 full in slices 0-2; row 1 full only in slices 0-1:
        # slice 2 has valid=0 for row 1 (still coarse-eligible).
        self.seed_full_rows(holder, rows=(0,), slices=(0, 1, 2))
        self.seed_full_rows(holder, rows=(1,), slices=(0, 1))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        mgr.lone_fused = False  # pin the chained coarse path under test
        for pql in ("Count(Union(Bitmap(rowID=0), Bitmap(rowID=1)))",
                    "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))",
                    "Count(Difference(Bitmap(rowID=0), Bitmap(rowID=1)))"):
            assert q(e, "i", pql)[0] == q(host, "i", pql)[0]
        assert mgr.stats["coarse"] >= 3

    def test_partial_row_falls_back_to_general(self, holder):
        self.seed_full_rows(holder, rows=(0,), slices=(0, 1))
        f = holder.index("i").frame("general")
        f.set_bit(1, 3)  # row 1: a single container — not coarse
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        pql = "Count(Union(Bitmap(rowID=0), Bitmap(rowID=1)))"
        before = mgr.stats["coarse"]
        assert q(e, "i", pql)[0] == q(host, "i", pql)[0]
        assert mgr.stats["coarse"] == before  # general path served it
        assert mgr.stats["count"] >= 1

    def test_coarse_batch_group_matches_individual(self, holder):
        self.seed_full_rows(holder, rows=(0, 1, 2, 3), slices=(0, 1))
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.serve import _CountRequest

        host = Executor(holder, use_device=False)
        group, want = [], []
        for a, b in [(0, 1), (2, 3), (1, 2)]:
            pql = f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"
            tree = parse_string(pql).calls[0].children[0]
            leaves = []
            shape = _lower_tree(holder, "i", tree, leaves)
            prepared = mgr._count_args("i", shape, leaves, [0, 1], 2)
            assert prepared is not None
            assert all(c is not None for c in prepared[4])
            group.append(_CountRequest(*prepared))
            want.append(host.execute("i", parse_string(pql))[0])
        before = mgr.stats["coarse"]
        mgr._run_count_group(group)
        assert [r.result for r in group] == want
        assert mgr.stats["coarse"] == before + 3

    def test_mixed_group_uses_general_program(self, holder):
        """One request's leaf is not coarse-eligible: the whole group
        takes the general container-gather program and stays correct."""
        self.seed_full_rows(holder, rows=(0, 1), slices=(0, 1))
        f = holder.index("i").frame("general")
        f.set_bit(9, 5)  # sparse row
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.serve import _CountRequest

        host = Executor(holder, use_device=False)
        group, want = [], []
        for a, b in [(0, 1), (0, 9)]:
            pql = f"Count(Union(Bitmap(rowID={a}), Bitmap(rowID={b})))"
            tree = parse_string(pql).calls[0].children[0]
            leaves = []
            shape = _lower_tree(holder, "i", tree, leaves)
            group.append(_CountRequest(
                *mgr._count_args("i", shape, leaves, [0, 1], 2)))
            want.append(host.execute("i", parse_string(pql))[0])
        before = mgr.stats["coarse"]
        mgr._run_count_group(group)
        assert [r.result for r in group] == want
        assert mgr.stats["coarse"] == before

    def test_write_after_coarse_stays_correct(self, holder):
        """An incremental scatter swaps words but keeps the key layout:
        cached coarse starts stay valid and serve the NEW bits."""
        self.seed_full_rows(holder, rows=(0, 1), slices=(0,))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        first = q(e, "i", pql)[0]
        f = holder.index("i").frame("general")
        f.set_bit(0, 1 + 65536)  # into an existing container of row 0
        f.set_bit(1, 1 + 65536)
        got = q(e, "i", pql)[0]
        assert got == q(host, "i", pql)[0] == first + 1

    # -- the xla coarse programs themselves, fed a pool directly ---------

    PAIRS = ((0, 1), (0, 2), (1, 2), (2, 0))

    @staticmethod
    def coarse_program(program, mesh, tree, pairs):
        """(fn, pool operands, the unique row each (start, valid) slot
        reads, the queries) for the three shapes the serving layer
        launches: a lone Count, a padded group of 16 and a shared-read
        group, which scans with one body up to _SHARED_NARROW_MAX
        queries and with another above."""
        from pilosa_tpu.parallel import mesh as M

        if program.startswith("shared"):
            pairs = pairs * (2 if program == "shared_wide" else 1)
            assert (len(pairs) > M._SHARED_NARROW_MAX) \
                == (program == "shared_wide")
            return (M.compile_serve_count_batch_shared(mesh, tree, pairs, 3),
                    3, (0, 1, 2), pairs)
        batch = 1 if program == "batch1" else 16
        queries = (pairs * 4)[:batch]
        return (M.compile_serve_count(mesh, tree, 2, batch, runs=True),
                2, tuple(r for qr in queries for r in qr), queries)

    @pytest.mark.parametrize("program", ["batch1", "batch16", "shared",
                                         "shared_wide"])
    @pytest.mark.parametrize("cap", [32, 128])
    @pytest.mark.parametrize("devices", [1, 4])
    def test_coarse_rows_match_numpy(self, devices, cap, program):
        """_row_run_blocks' gather against numpy on a pool of random words: a
        row's run starts somewhere else in every slice, one slice holds
        no part of row 1 (valid == 0) and one is masked out."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from pilosa_tpu.parallel import mesh as M

        s, w = 8, M.CONTAINER_WORDS
        rng = np.random.default_rng(cap * 10 + devices)
        pool = rng.integers(0, 2**32, (s, cap, w), dtype=np.uint32)
        starts = rng.integers(0, cap // M.ROW_SPAN, (3, s)).astype(np.int32)
        valid = np.ones((3, s), np.uint32)
        valid[1, 5] = 0
        mask = np.ones(s, np.int32)
        mask[2] = 0
        assert (starts != starts[:, :1]).any(axis=1).all()  # not uniform

        mesh = M.default_mesh(devices)
        put = lambda a: jax.device_put(  # noqa: E731
            a, NamedSharding(mesh, P(M.SLICE_AXIS)))
        tree = ["andnot", ["leaf", 0], ["leaf", 1]]
        fn, n_words, slot_rows, queries = self.coarse_program(
            program, mesh, tree, self.PAIRS)
        limbs = np.asarray(fn((put(pool),) * n_words,
                              tuple(put(starts[r]) for r in slot_rows),
                              tuple(put(valid[r]) for r in slot_rows),
                              put(mask)))

        def run(r, sl):
            st = int(starts[r, sl]) * M.ROW_SPAN
            return pool[sl, st:st + M.ROW_SPAN] * valid[r, sl]

        assert limbs.shape == (2, len(queries))
        for j, (a, b) in enumerate(queries):
            want = sum(int(np.bitwise_count(run(a, sl) & ~run(b, sl)).sum())
                       for sl in range(s) if mask[sl])
            assert M.combine_count(limbs[:, j]) == want, (j, a, b)

    @pytest.mark.parametrize("program", ["batch1", "batch16", "shared",
                                         "shared_wide"])
    def test_coarse_programs_never_reshape_the_pool_minor(self, program):
        """On the chip the pool's two minor dimensions are tiled, so a
        reshape that changes the last one is a copy of the whole pool,
        for every leaf of every launch (PR 30 took it out). The chip's
        compiler says so in tests/test_tpu_compile.py; this guard runs
        where no topology can be described: in the programs' jaxprs
        every reshape of a pool-shaped operand keeps the last
        dimension."""
        import jax
        from pilosa_tpu.parallel import mesh as M

        s, cap, w = 8, 32, M.CONTAINER_WORDS
        mesh = M.default_mesh(1)
        fn, n_words, slot_rows, _ = self.coarse_program(
            program, mesh, ["and", ["leaf", 0], ["leaf", 1]], self.PAIRS)
        sds = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(fn)(
            (sds((s, cap, w), np.uint32),) * n_words,
            tuple(sds((s,), np.int32) for _ in slot_rows),
            tuple(sds((s,), np.uint32) for _ in slot_rows),
            sds((s,), np.int32))

        def eqns(jp):
            for eqn in jp.eqns:
                yield eqn
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            yield from eqns(sub)

        seen = list(eqns(jaxpr.jaxpr))
        reads = [e for e in seen if any(
            getattr(v.aval, "shape", ()) == (s, cap, w) for v in e.invars)]
        assert reads, "no equation reads the pool: the walk is broken"
        assert any(e.primitive.name in ("gather", "dynamic_slice")
                   for e in seen)
        for e in reads:
            if e.primitive.name == "reshape":
                assert e.outvars[0].aval.shape[-1] == w, e


class TestTopNThresholdDivergence:
    """The DOCUMENTED deviation (serve.top_n docstring): the device
    path filters TopN's `threshold` against EXACT node-local totals,
    while the host/reference path applies MinThreshold inside every
    fragment (fragment.go:522-614) — so a row spread thinly across
    slices can clear the threshold globally yet vanish from the host
    answer. This test demonstrates the divergence explicitly (VERDICT
    r2 weak item 5) and pins which side is which: the host's drop is an
    artifact of its per-fragment scan, not a semantic goal."""

    def seed_spread_row(self, holder):
        # row 7: ONE bit in each of 3 slices (total 3); row 8: 3 bits
        # in one slice (total 3) — both should clear threshold=2.
        f = seed(holder)
        for s in range(3):
            f.set_bit(7, s * SLICE_WIDTH + 1)
        for c in (1, 2, 3):
            f.set_bit(8, c)
        return f

    def test_device_keeps_thin_spread_row_host_drops_it(self, holder):
        self.seed_spread_row(holder)
        dev = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        pql = "TopN(frame=general, n=10, threshold=2)"
        dev_pairs = q(dev, "i", pql)[0]
        host_pairs = q(host, "i", pql)[0]
        # Device: exact totals — BOTH rows clear the threshold.
        assert (7, 3) in dev_pairs, dev_pairs
        assert (8, 3) in dev_pairs, dev_pairs
        # Host: row 7's per-fragment counts are all 1 < 2, so the
        # reference semantics drop it even though its true total is 3.
        assert all(p[0] != 7 for p in host_pairs), host_pairs
        assert (8, 3) in host_pairs, host_pairs


class TestHostCountPlan:
    """Cost-routed Count trees serve from the fused HOST fold
    (plan.HostCountPlan): dense word blocks + one C++ popcount, no
    roaring materialization. Poisoning the materializing per-slice path
    proves which engine answered."""

    BITS = [(r, c) for r in range(4) for c in (1, 3, 65536 + 2, 70000)]

    def _poison_materializing(self, monkeypatch):
        def boom(self, index, c, slice_):
            raise AssertionError("materializing path used; "
                                 "HostCountPlan expected")

        monkeypatch.setattr(Executor, "execute_bitmap_call_slice", boom)

    def test_routed_count_uses_fused_host_fold(self, holder, monkeypatch):
        seed(holder, bits=self.BITS)
        host = Executor(holder, use_device=False)
        want = [q(host, "i", p)[0] for p in (
            "Count(Union(Bitmap(rowID=0), Bitmap(rowID=1), Bitmap(rowID=2)))",
            "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))",
            "Count(Difference(Bitmap(rowID=0), Bitmap(rowID=3)))")]
        e = Executor(holder, use_device=True, device_min_work=10**6)  # force routing
        self._poison_materializing(monkeypatch)
        got = [q(e, "i", p)[0] for p in (
            "Count(Union(Bitmap(rowID=0), Bitmap(rowID=1), Bitmap(rowID=2)))",
            "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))",
            "Count(Difference(Bitmap(rowID=0), Bitmap(rowID=3)))")]
        assert got == want
        assert e.mesh_manager().stats["routed_host"] >= 3

    def test_routed_count_absent_row_and_fragment(self, holder, monkeypatch):
        seed(holder, bits=self.BITS)
        e = Executor(holder, use_device=True, device_min_work=10**6)
        self._poison_materializing(monkeypatch)
        assert q(e, "i", "Count(Bitmap(rowID=999))")[0] == 0
        assert q(e, "i",
                 "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=999)))")[0] == 0

    def test_routed_count_array_containers(self, holder, monkeypatch):
        # sparse rows stage as ARRAY containers; the host fold expands
        # them through Container.words()
        f = seed(holder)
        for c in range(10):
            f.set_bit(20, c * 7)
            if c % 2 == 0:
                f.set_bit(21, c * 7)
        host = Executor(holder, use_device=False)
        want = q(host, "i",
                 "Count(Intersect(Bitmap(rowID=20), Bitmap(rowID=21)))")[0]
        e = Executor(holder, use_device=True, device_min_work=10**6)
        self._poison_materializing(monkeypatch)
        assert q(e, "i",
                 "Count(Intersect(Bitmap(rowID=20), Bitmap(rowID=21)))")[0] \
            == want == 5


class TestHbmBudgetEviction:
    """Staged device images are LRU-evicted under the HBM budget
    (PILOSA_TPU_HBM_BUDGET_MB): the least-recently-USED view goes
    first, an evicted view restages transparently on next use, and
    eviction never touches the view being served."""

    def seed_frames(self, holder, frames):
        idx = holder.create_index_if_not_exists("i")
        for fr in frames:
            f = idx.create_frame_if_not_exists(fr)
            for blk in range(16):
                f.set_bit(1, blk * 65536 + 3)
                f.set_bit(2, blk * 65536 + 3)

    def test_lru_eviction_and_restage(self, holder, monkeypatch):
        from pilosa_tpu.core.fragment import MUTATION_EPOCH

        self.seed_frames(holder, ["f1", "f2", "f3"])
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()

        def pql(fr):
            # The executor's query-level memo would answer repeats
            # without ever touching the mesh layer (correct, but this
            # test exists to drive staging/eviction): move the epoch so
            # every execute reaches the device path.
            MUTATION_EPOCH.bump_structural()
            return (f"Count(Intersect(Bitmap(rowID=1, frame={fr}), "
                    f"Bitmap(rowID=2, frame={fr})))")

        assert q(e, "i", pql("f1"))[0] == 16
        one = mgr._view_bytes(next(iter(mgr._views.values())))
        # MB env granularity is too coarse for tiny test views: patch
        # the budget method for a byte-exact budget fitting ~2 views.
        monkeypatch.setattr(type(mgr), "_hbm_budget_bytes",
                            staticmethod(lambda: 2 * one + one // 2))
        assert q(e, "i", pql("f2"))[0] == 16
        assert len(mgr._views) == 2
        # f3 stages -> over budget -> f1 (least recently used) evicted
        assert q(e, "i", pql("f3"))[0] == 16
        assert mgr.stats["evicted"] == 1
        keys = [k[1] for k in mgr._views]
        assert "f1" not in keys and set(keys) == {"f2", "f3"}
        # f1 restages transparently on next use; f2 is now LRU
        assert q(e, "i", pql("f1"))[0] == 16
        assert mgr.stats["evicted"] == 2
        keys = [k[1] for k in mgr._views]
        assert set(keys) == {"f3", "f1"}

    def test_multi_frame_query_not_thrashed(self, holder, monkeypatch):
        """One query tree spanning more frames than the budget fits
        runs OVER budget (views used by the in-progress resolution are
        eviction-exempt) instead of restage-thrashing every query."""
        self.seed_frames(holder, ["f1", "f2", "f3"])
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        from pilosa_tpu.core.fragment import MUTATION_EPOCH

        q3 = ("Count(Union(Bitmap(rowID=1, frame=f1), "
              "Bitmap(rowID=1, frame=f2), Bitmap(rowID=1, frame=f3)))")
        assert q(e, "i", q3)[0] == 16
        one = mgr._view_bytes(next(iter(mgr._views.values())))
        monkeypatch.setattr(type(mgr), "_hbm_budget_bytes",
                            staticmethod(lambda: 2 * one + one // 2))
        mgr.invalidate()
        before = mgr.stats["evicted"]
        MUTATION_EPOCH.bump_structural()  # past the query memo, to the device path
        assert q(e, "i", q3)[0] == 16
        assert len(mgr._views) == 3  # over budget, but no mid-query evict
        assert mgr.stats["evicted"] == before
        MUTATION_EPOCH.bump_structural()
        assert q(e, "i", q3)[0] == 16  # repeats stay staged: no thrash
        assert mgr.stats["evicted"] == before
        assert mgr.stats["stage"] == 6  # 3 initial + 3 after invalidate

    def test_zero_budget_disables_eviction(self, holder, monkeypatch):
        self.seed_frames(holder, ["f1", "f2", "f3"])
        monkeypatch.setenv("PILOSA_TPU_HBM_BUDGET_MB", "0")
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        for fr in ("f1", "f2", "f3"):
            assert q(e, "i",
                     f"Count(Bitmap(rowID=1, frame={fr}))")[0] == 16
        assert len(mgr._views) == 3
        assert mgr.stats["evicted"] == 0


class TestSharedReadBatch:
    """compile_serve_count_batch_shared: B queries over U unique coarse
    leaves read each leaf once per slice — differential against the
    host executor over every pair of a multi-row frame."""

    def test_all_pairs_match_host(self, holder):
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2, 3),
                                        slices=(0, 1, 2))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.mesh import compile_serve_count_batch_shared
        from pilosa_tpu.parallel.plan import _lower_tree
        import json as _json

        pairs = [(a, b) for a in range(4) for b in range(4) if a < b]
        # resolve each unique row's coarse arrays through the serving
        # layer (same staging path production uses)
        tree = parse_string(
            "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        ).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        prepared = mgr._count_args("i", shape, leaves, [0, 1, 2], 3)
        sig, words_t, _, _, _, dmask = prepared
        sv = mgr._views[("i", "general", "standard")]
        with mgr._mu:
            coarse = {r: mgr._leaf_arrays(sv, r)[2] for r in range(4)}
        assert all(c is not None for c in coarse.values())
        leaf_map = tuple((a, b) for a, b in pairs)
        fn = compile_serve_count_batch_shared(
            mgr.mesh, _json.loads(sig), leaf_map, 4)
        words_u = tuple(sv.sharded.words for _ in range(4))
        start_u = tuple(coarse[r][0] for r in range(4))
        valid_u = tuple(coarse[r][1] for r in range(4))
        limbs = np.asarray(fn(words_u, start_u, valid_u, dmask))
        for j, (a, b) in enumerate(pairs):
            got = (int(limbs[1, j]) << 16) + int(limbs[0, j])
            want = host.execute("i", parse_string(
                f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"
            ))[0]
            assert got == want, (a, b, got, want)

    def test_absent_slice_and_mask(self, holder):
        # row 2 absent in slice 1; mask excludes slice 2 entirely
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1), slices=(0, 1, 2))
        TestCoarseGather.seed_full_rows(holder, rows=(2,), slices=(0, 2))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        from pilosa_tpu.parallel.mesh import compile_serve_count_batch_shared
        from pilosa_tpu.parallel.plan import _lower_tree
        import json as _json

        tree = parse_string(
            "Count(Union(Bitmap(rowID=0), Bitmap(rowID=1)))"
        ).calls[0].children[0]
        leaves = []
        shape = _lower_tree(holder, "i", tree, leaves)
        prepared = mgr._count_args("i", shape, leaves, [0, 1], 3)
        sig, words_t, _, _, _, dmask = prepared  # mask covers slices 0,1
        sv = mgr._views[("i", "general", "standard")]
        with mgr._mu:
            coarse = {r: mgr._leaf_arrays(sv, r)[2] for r in range(3)}
        assert all(c is not None for c in coarse.values())
        qs = [(0, 1), (0, 2), (1, 2)]
        fn = compile_serve_count_batch_shared(
            mgr.mesh, _json.loads(sig), tuple(qs), 3)
        limbs = np.asarray(fn(tuple(sv.sharded.words for _ in range(3)),
                              tuple(coarse[r][0] for r in range(3)),
                              tuple(coarse[r][1] for r in range(3)), dmask))
        for j, (a, b) in enumerate(qs):
            got = (int(limbs[1, j]) << 16) + int(limbs[0, j])
            want = host.execute(
                "i", parse_string(
                    f"Count(Union(Bitmap(rowID={a}), Bitmap(rowID={b})))"),
                slices=[0, 1])[0]
            assert got == want, (a, b, got, want)


class TestAdaptiveSharedBatching:
    """The batch runner upgrades coarse groups to the shared-read
    program (unique-leaf traffic) when the composition's program is
    available — compiled inline under PILOSA_TPU_BATCH_SHARED=sync,
    in the background under auto."""

    def _group(self, holder, mgr, pairs):
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.parallel.serve import _CountRequest

        group = []
        for a, b in pairs:
            pql = f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"
            tree = parse_string(pql).calls[0].children[0]
            leaves = []
            shape = _lower_tree(holder, "i", tree, leaves)
            req = _CountRequest(
                *mgr._count_args("i", shape, leaves, [0, 1], 2))
            req.leaf_keys = tuple((f, v, int(r)) for f, v, r, _ in leaves)
            group.append(req)
        return group

    def test_sync_policy_uses_shared_and_matches(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "sync")
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2, 3),
                                        slices=(0, 1))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        pairs = [(0, 1), (1, 2), (2, 3), (0, 3)]
        want = [host.execute("i", parse_string(
            f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"))[0]
            for a, b in pairs]
        group = self._group(holder, mgr, pairs)
        mgr._run_count_group(group)
        assert [r.result for r in group] == want
        assert mgr.stats["shared_batch"] == 4
        assert len(mgr._shared_fns) == 1
        # Arrival order must not mint a second program
        group2 = self._group(holder, mgr, list(reversed(pairs)))
        mgr._run_count_group(group2)
        assert [r.result for r in group2] == list(reversed(want))
        assert len(mgr._shared_fns) == 1
        assert mgr.stats["shared_batch"] == 8

    def test_plain_batch_pallas_backend_matches(self, holder, monkeypatch):
        """With sharing OFF and the pallas backend selected, herd
        groups run the identity-map grid kernel
        (compile_serve_count_coarse_pallas_batch) padded to
        _MAX_BATCH; results must match the host executor."""
        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "off")
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "pallas_interpret")
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2, 3),
                                        slices=(0, 1))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        pairs = [(0, 1), (1, 2), (2, 3)]
        want = [host.execute("i", parse_string(
            f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"))[0]
            for a, b in pairs]
        group = self._group(holder, mgr, pairs)
        mgr._run_count_group(group)
        assert [r.result for r in group] == want
        assert mgr.stats["shared_batch"] == 0
        assert mgr.stats["batched"] == 3
        assert ("coarse", group[0].args[0], 2, mgr._MAX_BATCH,
                "pallas_interpret", False) in mgr._count_programs, \
            list(mgr._count_programs)

    def test_shared_pallas_backend_matches(self, holder, monkeypatch):
        """PILOSA_TPU_COUNT_BACKEND=pallas_interpret routes the
        shared-read batch through the one-launch Pallas grid kernel
        (compile_serve_count_batch_shared_pallas); results must match
        the host executor AND the XLA shared program, and the two
        backends must cache under distinct keys."""
        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "sync")
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2, 3),
                                        slices=(0, 1))
        e = Executor(holder, use_device=True, device_min_work=0)
        host = Executor(holder, use_device=False)
        mgr = e.mesh_manager()
        pairs = [(0, 1), (1, 2), (2, 3), (0, 3)]
        want = [host.execute("i", parse_string(
            f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"))[0]
            for a, b in pairs]
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "pallas_interpret")
        group = self._group(holder, mgr, pairs)
        mgr._run_count_group(group)
        assert [r.result for r in group] == want
        assert mgr.stats["shared_batch"] == 4
        keys = list(mgr._shared_fns)
        assert keys and keys[0][-2] == "pallas_interpret"
        # Same composition on the XLA backend: separate cache entry,
        # same results.
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "xla")
        group2 = self._group(holder, mgr, pairs)
        mgr._run_count_group(group2)
        assert [r.result for r in group2] == want
        assert len(mgr._shared_fns) == 2
        assert {k[-2] for k in mgr._shared_fns} == {"pallas_interpret",
                                                    "xla"}

    def test_auto_policy_compiles_in_background(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "auto")
        # Pin the sighting threshold at its old value of 2 — the test
        # drives exactly two sightings; the production default is
        # higher (see _shared_seen_min: auto waits for real
        # repetition before it spends a compile).
        monkeypatch.setenv("PILOSA_TPU_SHARED_SEEN_MIN", "2")
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2), slices=(0,))
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        pairs = [(0, 1), (1, 2)]
        group = self._group(holder, mgr, pairs)
        before = mgr.stats["shared_batch"]
        mgr._run_count_group(group)  # sighting 1: plain, NO compile yet
        assert mgr.stats["shared_batch"] == before
        assert not mgr._shared_fns and not mgr._shared_pending
        group2 = self._group(holder, mgr, pairs)
        mgr._run_count_group(group2)  # sighting 2: plain + bg compile
        assert mgr.stats["shared_batch"] == before
        # wait for the background compile
        import time as _t

        for _ in range(200):
            if mgr._shared_fns:
                break
            _t.sleep(0.05)
        assert mgr._shared_fns, "background compile never landed"
        group3 = self._group(holder, mgr, pairs)
        mgr._run_count_group(group3)
        assert mgr.stats["shared_batch"] == before + 2
        group2 = group3  # result check below reads group2
        host = Executor(holder, use_device=False)
        want = [host.execute("i", parse_string(
            f"Count(Intersect(Bitmap(rowID={a}), Bitmap(rowID={b})))"))[0]
            for a, b in pairs]
        assert [r.result for r in group2] == want

    def test_no_shared_when_all_leaves_distinct(self, holder, monkeypatch):
        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "sync")
        TestCoarseGather.seed_full_rows(holder, rows=(0, 1, 2, 3),
                                        slices=(0,))
        e = Executor(holder, use_device=True, device_min_work=0)
        mgr = e.mesh_manager()
        group = self._group(holder, mgr, [(0, 1), (2, 3)])  # 4 distinct
        mgr._run_count_group(group)
        assert mgr.stats["shared_batch"] == 0
        assert not mgr._shared_fns


class TestRefreshCostGate:
    """refresh() picks incremental-vs-restage from MEASURED costs
    (VERDICT r3 #7), not a hard-wired policy."""

    def _mgr(self, tmp_path, slices=2):
        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.serve import MeshManager

        h = Holder(str(tmp_path / "d"))
        h.open()
        f = h.create_index_if_not_exists("i").create_frame_if_not_exists("g")
        for s in range(slices):
            f.set_bit(1, s * (1 << 20) + 3)
        return h, MeshManager(h)

    def test_restage_picked_when_cheaper(self, tmp_path):
        h, mgr = self._mgr(tmp_path)
        f = h.frame("i", "g")
        sv = mgr.refresh("i", "g", "standard", 2)
        assert sv is not None
        import time as _t

        sv.sharded.words.block_until_ready()
        for _ in range(100):
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        # force the gate deterministically (the real measurements land
        # asynchronously): staging declared cheap, incremental dear.
        # The gate reads the PER-VIEW estimate (ADVICE r4) — a global
        # EWMA let small views drive restages of large ones.
        sv.last_stage_s = 1e-4
        ewma0 = sv.inc_ewma_s = 10.0
        f.set_bit(1, 7)
        before = mgr.stats["stage"]
        mgr.refresh("i", "g", "standard", 2)
        assert mgr.stats["stage"] == before + 1
        assert mgr.stats["refresh_pick_restage"] == 1
        # the estimate decays on a restage pick (and is inherited by
        # the fresh view), so the gate re-explores
        sv2 = mgr._views[("i", "g", "standard")]
        assert sv2 is not sv
        assert sv2.inc_ewma_s is not None and sv2.inc_ewma_s < ewma0

    def test_incremental_picked_when_cheaper(self, tmp_path):
        import time as _t

        h, mgr = self._mgr(tmp_path)
        f = h.frame("i", "g")
        sv = mgr.refresh("i", "g", "standard", 2)
        # let the async stage-cost measurement land before overriding,
        # so it cannot race our forced value
        sv.sharded.words.block_until_ready()
        for _ in range(100):
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        sv.last_stage_s = 10.0  # staging declared expensive
        sv.inc_ewma_s = 0.001
        f.set_bit(1, 7)
        before = mgr.stats["incremental"]
        mgr.refresh("i", "g", "standard", 2)
        assert mgr.stats["incremental"] == before + 1
        assert mgr.stats["refresh_pick_incremental"] == 1
        # the gated refresh still yields correct counts
        from pilosa_tpu.parallel.plan import _lower_tree
        from pilosa_tpu.pql import parse_string

        tree = parse_string("Count(Bitmap(frame=g, rowID=1))").calls[0] \
            .children[0]
        leaves = []
        shape = _lower_tree(h, "i", tree, leaves)
        assert mgr.count("i", shape, leaves, [0, 1], 2) == 3

    def test_probe_restage_reexplores_stale_stage_cost(self, tmp_path):
        """A slow cold first stage must not freeze the gate on
        incremental forever: once cumulative incremental spend passes
        20x the stage estimate, the gate probes a restage, which
        re-measures stage cost."""
        import time as _t

        h, mgr = self._mgr(tmp_path)
        f = h.frame("i", "g")
        sv = mgr.refresh("i", "g", "standard", 2)
        sv.sharded.words.block_until_ready()
        for _ in range(100):  # let the async measurement land first
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        # stale, expensive-looking stage sample + cheap incremental
        sv.last_stage_s = 0.001
        sv.inc_spend_s = 0.5  # > 20 * 0.001
        sv.inc_ewma_s = 1e-6  # plain gate would pick incremental
        f.set_bit(1, 7)
        stages0 = mgr.stats["stage"]
        mgr.refresh("i", "g", "standard", 2)
        assert mgr.stats["stage"] == stages0 + 1
        assert mgr.stats["refresh_probe_restage"] == 1
        # the probe re-measured: the NEW view starts with zero spend,
        # and the probe did NOT decay the incremental estimate (it
        # carries no evidence against incremental)
        sv2 = mgr._views[("i", "g", "standard")]
        assert sv2.inc_spend_s == 0.0
        assert sv2.inc_ewma_s == 1e-6

    def test_gate_is_per_view(self, tmp_path):
        """A cheap scatter measured on one view must not drive a
        restage of ANOTHER view (ADVICE r4): each view's gate compares
        its own stage cost against its own incremental estimate."""
        import time as _t

        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.serve import MeshManager

        h = Holder(str(tmp_path / "d"))
        h.open()
        idx = h.create_index_if_not_exists("i")
        fs = idx.create_frame_if_not_exists("small")
        fl = idx.create_frame_if_not_exists("large")
        for s in range(2):
            fs.set_bit(1, s * (1 << 20) + 3)
            fl.set_bit(1, s * (1 << 20) + 3)
        mgr = MeshManager(h)
        svs = mgr.refresh("i", "small", "standard", 2)
        svl = mgr.refresh("i", "large", "standard", 2)
        for sv in (svs, svl):
            sv.sharded.words.block_until_ready()
            for _ in range(100):
                if sv.last_stage_s is not None:
                    break
                _t.sleep(0.01)
        # ANOTHER view's big-pool scatters polluted the manager-global
        # EWMA high (the ADVICE r4 scenario); this view's stage reads
        # cheaper than that foreign estimate, but it has no incremental
        # sample of its OWN yet
        mgr._inc_ewma_s = 10.0
        svs.inc_ewma_s = 10.0
        svl.inc_ewma_s = None
        svl.last_stage_s = 1.0
        fl.set_bit(1, 7)
        before = mgr.stats["stage"]
        mgr.refresh("i", "large", "standard", 2)
        # the old global gate would restage (last_stage_s 1.0 < global
        # ewma 10.0); the per-view gate has no estimate for THIS view,
        # so the first incremental runs and seeds it
        assert mgr.stats["stage"] == before
        assert mgr.stats["refresh_pick_incremental"] >= 1

    def test_deterministic_gate_ignores_measured_costs(self, tmp_path):
        """SPMD mode (ADVICE r4): with deterministic_gate set, measured
        timings never steer the pick — only the replicated incremental
        counter does, so every rank decides identically."""
        h, mgr = self._mgr(tmp_path)
        mgr.deterministic_gate = True
        f = h.frame("i", "g")
        sv = mgr.refresh("i", "g", "standard", 2)
        # timings scream "restage is free" — a measured gate would
        # restage; the deterministic gate must not listen
        sv.last_stage_s = 1e-9
        sv.inc_ewma_s = 100.0
        sv.inc_spend_s = 100.0
        before = mgr.stats["stage"]
        f.set_bit(1, 7)
        mgr.refresh("i", "g", "standard", 2)
        assert mgr.stats["stage"] == before
        assert mgr.stats["incremental"] == 1
        # ...until the fixed count-based period elapses
        sv.inc_count = mgr._DET_RESTAGE_EVERY
        f.set_bit(1, 9)
        mgr.refresh("i", "g", "standard", 2)
        assert mgr.stats["stage"] == before + 1

    def test_spmd_server_sets_deterministic_gate(self, tmp_path):
        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.spmd import SpmdServer

        h = Holder(str(tmp_path / "d"))
        h.open()
        assert SpmdServer(h).manager.deterministic_gate is True

    def test_measure_loop_records_sample_on_device_error(self, tmp_path):
        """A failed device fetch still records dispatch-so-far
        (ADVICE r4): a view whose measurement errors must not lose its
        cost gate and probe forever."""
        h, mgr = self._mgr(tmp_path)

        class Boom:
            def block_until_ready(self):
                raise RuntimeError("device lost")

        got = []
        import time as _t

        mgr._measure_async(Boom(), _t.monotonic(),
                           lambda e, ok=True: got.append((e, ok)))
        for _ in range(200):
            if got:
                break
            _t.sleep(0.01)
        # sample recorded, flagged as a failure (ok=False) so callbacks
        # treat it as time-to-exception, not a cost
        assert got and got[0][0] >= 0.0 and got[0][1] is False


class TestFailedStageClamp:
    def test_cold_view_failed_stage_records_pessimistic_floor(self,
                                                              tmp_path):
        """A COLD view whose stage measurement fails must not record a
        near-zero stage cost (that would arm the restage probe after
        microseconds of incremental spend and hammer a failing
        device): with no incremental estimate yet, the sample clamps
        to the fixed pessimistic floor."""
        import time as _t

        from pilosa_tpu.core import Holder
        from pilosa_tpu.parallel.serve import MeshManager

        h = Holder(str(tmp_path / "d"))
        h.open()
        f = h.create_index_if_not_exists("i").create_frame_if_not_exists("g")
        f.set_bit(1, 3)
        mgr = MeshManager(h)
        sv = mgr.refresh("i", "g", "standard", 1)
        sv.sharded.words.block_until_ready()
        for _ in range(100):
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        # simulate the measurement worker reporting a FAILED fetch on a
        # cold view (no inc_ewma_s): re-stage bookkeeping
        sv.last_stage_s = None
        sv.inc_ewma_s = None

        class Boom:
            def block_until_ready(self):
                raise RuntimeError("device lost")

        # the REAL recording path, driven through the measure worker
        def on_done(elapsed, ok=True):
            mgr._record_stage_sample(sv, elapsed, ok)

        mgr._measure_async(Boom(), _t.monotonic(), on_done)
        for _ in range(200):
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        assert sv.last_stage_s is not None
        assert sv.last_stage_s >= mgr._FAILED_STAGE_FLOOR_S
        # with a warm incremental estimate, the clamp uses it instead
        sv.last_stage_s = None
        sv.inc_ewma_s = 0.25
        mgr._measure_async(Boom(), _t.monotonic(), on_done)
        for _ in range(200):
            if sv.last_stage_s is not None:
                break
            _t.sleep(0.01)
        assert sv.last_stage_s is not None
        assert 0.25 <= sv.last_stage_s < mgr._FAILED_STAGE_FLOOR_S


class TestAutoBackend:
    """PILOSA_TPU_COUNT_BACKEND=auto: probe-once resolution. Every
    test pins _AUTO_BACKEND via monkeypatch so a failing assertion
    cannot leak a mutated class-level verdict into later tests."""

    def test_auto_on_non_tpu_resolves_xla_without_probe(self, monkeypatch):
        import jax

        from pilosa_tpu.parallel.serve import MeshManager
        monkeypatch.setattr(MeshManager, "_AUTO_BACKEND", None)
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "auto")
        # Pin the non-TPU branch explicitly: on a TPU-attached rig the
        # bare default_backend() would launch a real probe here.
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert MeshManager._count_backend() == "xla"
        assert MeshManager._AUTO_BACKEND == "xla"

    def test_auto_resolution_is_cached(self, monkeypatch):
        from pilosa_tpu.parallel.serve import MeshManager
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "auto")
        monkeypatch.setattr(MeshManager, "_AUTO_BACKEND", "pallas")
        assert MeshManager._count_backend() == "pallas"

    def test_malformed_probe_timeout_degrades_to_default(self, monkeypatch):
        import jax

        from pilosa_tpu.parallel.serve import MeshManager
        monkeypatch.setattr(MeshManager, "_AUTO_BACKEND", None)
        monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", "auto")
        monkeypatch.setenv("PILOSA_TPU_PALLAS_PROBE_TIMEOUT_S", "60s")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # the probe itself fails fast on the CPU rig (no TPU pallas),
        # so resolution completes; the malformed timeout must not raise
        monkeypatch.setattr(
            "pilosa_tpu.ops.kernels.pallas_probe_ok", lambda: False)
        assert MeshManager._count_backend() == "xla"

    def test_explicit_values_bypass_auto(self, monkeypatch):
        from pilosa_tpu.parallel.serve import MeshManager
        monkeypatch.setattr(MeshManager, "_AUTO_BACKEND", None)
        for v, want in (("pallas", "pallas"),
                        ("pallas_interpret", "pallas_interpret"),
                        ("xla", "xla"), ("bogus", "xla")):
            monkeypatch.setenv("PILOSA_TPU_COUNT_BACKEND", v)
            assert MeshManager._count_backend() == want
        assert MeshManager._AUTO_BACKEND is None  # auto never resolved


class TestPickCount:
    """MeshManager._pick_count alone, nothing launched or compiled: the
    decision table in its docstring, as cases. Requests are made of
    placeholders; only the leaves' coarse triples, the logical leaf keys
    and the pools' shapes are read."""

    @staticmethod
    def _group(b, layout):
        from pilosa_tpu.parallel.serve import _CountRequest

        pool = np.zeros((2, 16, 8), np.uint32)
        mask = object()

        def leaf(row):
            if layout == "uniform":        # one run index on every slice
                return (("st", row), ("va", row), row)
            return (("st", row), ("va", row), None)

        group = []
        for q in range(b):
            rows = (q, q + 1)              # neighbours share a leaf
            coarse = tuple(leaf(r) for r in rows)
            if layout == "mixed" and q == b - 1:
                coarse = (coarse[0], None)  # one partial row in the group
            req = _CountRequest(
                "sig", (pool, pool), tuple(("idx", r) for r in rows),
                tuple(("hit", r) for r in rows), coarse, mask)
            req.leaf_keys = tuple(("f", "standard", r) for r in rows)
            group.append(req)
        return group

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("policy", ["auto", "sync", "off"])
    @pytest.mark.parametrize("layout", ["runs", "uniform", "mixed"])
    @pytest.mark.parametrize("b", [1, 3, 16])
    def test_decision_table(self, monkeypatch, b, layout, policy, backend):
        import types

        from pilosa_tpu.parallel.serve import MeshManager

        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", policy)
        mgr = MeshManager(None, mesh=types.SimpleNamespace(
            shape={"slices": 1}))
        built = []
        monkeypatch.setattr(
            mgr, "_build_shared",
            lambda *key, uniform=False: built.append(
                key + (uniform,)) or "shared-program")
        monkeypatch.setattr(mgr, "_device_starts", lambda a: a)
        asked = []
        monkeypatch.setattr(
            mgr, "_count_program",
            lambda *key, **kw: asked.append(key + tuple(kw.values())))
        group = self._group(b, layout)

        pick = mgr._pick_count(group, backend)

        pallas = backend == "pallas_interpret"
        width = 1 if b == 1 else 16
        batched = [("batched", b)] if b > 1 else []
        if layout == "mixed":
            want = ("general", width, batched)
        elif b > 1 and policy == "sync":
            want = ("shared", b,
                    [("shared_batch", b), ("coarse", b), ("batched", b)])
        elif layout == "uniform" and pallas:
            want = ("uniform", width,
                    [("coarse_uniform", b), ("coarse", b)] + batched)
        else:
            want = ("coarse", width, [("coarse", b)] + batched)
        assert (pick.kind, pick.width, list(pick.counters)) == want
        assert sorted(map(id, pick.order)) == sorted(map(id, group))
        assert not asked            # nothing compiles before the launch
        prog = pick.program()
        if pick.kind == "shared":
            assert prog == "shared-program"
            uniform = pallas and layout == "uniform"
            assert [k[3:] for k in built] == [(backend, uniform)]
            assert len(pick.args) == (3 if uniform else 4)
            assert len(pick.args[0]) == b + 1     # unique leaves, not 2b
        else:
            assert not built
            assert asked == [{
                "general": ("general", "sig", 2, width),
                "coarse": ("coarse", "sig", 2, width, backend),
                "uniform": ("coarse", "sig", 2, width, backend, True),
            }[pick.kind]]
            slots = 2 * width
            assert [len(a) for a in pick.args[1:-1]] == (
                [slots] if pick.kind == "uniform" else [slots, slots])
            # Padding repeats the last request.
            last = group[-1]
            tail = (last.args[2][-1] if pick.kind == "general"
                    else last.coarse_t[-1][2 if pick.kind == "uniform"
                                           else 0])
            assert pick.args[1][-1] == tail

    def test_auto_policy_compiles_a_composition_only_once_seen(
            self, monkeypatch):
        """Under `auto` a shared composition runs coarse until its
        program is in the cache: each group counts a sighting, the
        background build starts at the _shared_seen_min-th, and a
        cached program is picked at the group's own width."""
        import types

        from pilosa_tpu.parallel.serve import MeshManager

        monkeypatch.setenv("PILOSA_TPU_BATCH_SHARED", "auto")
        monkeypatch.setenv("PILOSA_TPU_SHARED_SEEN_MIN", "3")
        mgr = MeshManager(None, mesh=types.SimpleNamespace(
            shape={"slices": 1}))
        started = []
        monkeypatch.setattr(
            "pilosa_tpu.parallel.serve.threading.Thread",
            lambda target, **kw: types.SimpleNamespace(
                start=lambda: started.append(target)))
        monkeypatch.setattr(mgr, "_build_shared",
                            lambda *k, **kw: "shared-program")
        group = self._group(3, "runs")
        for seen in (1, 2, 3):
            assert mgr._pick_count(group, "xla").kind == "coarse"
            assert len(started) == (1 if seen == 3 else 0)
        started[0]()                        # the background build lands
        pick = mgr._pick_count(list(reversed(group)), "xla")
        assert (pick.kind, pick.width) == ("shared", 3)
        # Columns follow the canonical order, whatever the arrival order.
        assert [r.leaf_keys for r in pick.order] == \
            [r.leaf_keys for r in group]
        assert pick.program() == "shared-program"


class TestApplyWritesWhereThePoolLies:
    """The write scatter runs in the staged pool's own buffer when no
    reader holds the pool (StagedView.pins == 0, read under _mu) and
    from one copy of it when one does (serve.MeshManager._apply_writes,
    mesh.compile_serve_apply_writes): one program, two jitted forms."""

    KEY = ("i", "general", "standard")
    TEXT = "Count(Bitmap(rowID=10))"

    @staticmethod
    def _served(holder, devices=1):
        """An executor over `devices` CPU devices, its gate deterministic
        (a measured gate may restage a tiny pool at will)."""
        from pilosa_tpu.parallel.mesh import default_mesh
        from pilosa_tpu.parallel.serve import MeshManager

        e = Executor(holder, use_device=True)
        e._mesh_mgr = MeshManager(holder, mesh=default_mesh(devices))
        e._mesh_mgr.deterministic_gate = True
        return e, e._mesh_mgr

    def _staged(self, holder, devices=1):
        """Rows 10 and 11 over two slices, staged by a first Count."""
        f = seed(holder, bits=[(10, c) for c in range(8)]
                 + [(10, SLICE_WIDTH + 3), (11, 1), (11, SLICE_WIDTH + 9)])
        e, mgr = self._served(holder, devices)
        assert q(e, "i", self.TEXT) == [9]
        return f, e, mgr

    @pytest.mark.parametrize("devices", [1, 4])
    def test_no_pin_applies_in_place(self, holder, devices):
        f, e, mgr = self._staged(holder, devices)
        old = mgr._views[self.KEY].sharded.words
        f.set_bit(10, 100)
        f.set_bit(10, SLICE_WIDTH + 100)    # the other shard of four
        f.clear_bit(10, 0)
        assert q(e, "i", self.TEXT) == [10]
        assert (mgr.stats["apply_in_place"], mgr.stats["apply_copied"],
                mgr.stats["stage"]) == (1, 0, 1)
        sv = mgr._views[self.KEY]
        assert old.is_deleted() and not sv.sharded.words.is_deleted()
        assert sv.sharded.words.sharding == old.sharding
        assert q(e, "i", "TopN(frame=general, n=2)")[0] \
            == q(Executor(holder, use_device=False), "i",
                 "TopN(frame=general, n=2)")[0]
        # The byte accounting reads no buffer, so a donated pool in an
        # older snapshot cannot fail a scrape.
        snap = [(mgr._views[self.KEY].sharded._replace(words=old),
                 sv.keys_host, None, None, None)]
        assert mgr._device_memory_from(snap)["padded_bytes"] \
            == mgr.device_memory()["padded_bytes"]

    def test_pinned_reader_keeps_its_pool_and_the_next_read_sees_the_write(
            self, holder):
        """A reader that planned before the write (its pin taken, its
        launch not made yet) still answers the pre-write counts from the
        pool it snapshotted; the refresh beside it copied."""
        from pilosa_tpu.parallel.mesh import compile_serve_row_counts
        from pilosa_tpu.parallel.serve import combine_limbs

        f, e, mgr = self._staged(holder)
        pins: list = []
        row_ids, sharded, dev_mask, padded, _ = mgr._row_counts_args(
            "i", "general", "standard", [0, 1], 2, pins=pins)
        assert [sv.pins for sv in pins] == [1]
        f.set_bit(10, 100)
        assert q(e, "i", self.TEXT) == [10]
        assert (mgr.stats["apply_in_place"], mgr.stats["apply_copied"]) \
            == (0, 1)
        assert not sharded.words.is_deleted()
        assert mgr._views[self.KEY].sharded.words is not sharded.words
        limbs = np.asarray(compile_serve_row_counts(mgr.mesh, padded)(
            sharded, dev_mask))
        assert dict(zip(row_ids.tolist(),
                        combine_limbs(limbs, len(row_ids)).tolist())) \
            == {10: 9, 11: 2}
        mgr._release_pins(pins)
        f.set_bit(10, 101)
        assert q(e, "i", self.TEXT) == [11]
        assert (mgr.stats["apply_in_place"], mgr.stats["apply_copied"]) \
            == (1, 1)

    @pytest.mark.parametrize("donate", [True, False],
                             ids=["in_place", "copied"])
    @pytest.mark.parametrize("width,run", [(1, None), (8, None), (9, None),
                                           (3, 8)],
                             ids=["1", "8", "9_of_16", "3_and_its_padding"])
    def test_scatter_matches_numpy(self, width, run, donate):
        """Sets and clears mixed, at the plan widths that fill batch
        widths 8 and 16; slices with narrower plans ride out-of-bounds
        slots in the columns that run, and with run=8 the columns that
        are all padding run too: dropped, every one."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pilosa_tpu.ops.pool import mutation_batch_width
        from pilosa_tpu.parallel.mesh import (SLICE_AXIS,
                                              compile_serve_apply_writes,
                                              default_mesh,
                                              pack_mutation_batches)

        mesh, slices, cap = default_mesh(4), 8, 16
        rng = np.random.default_rng(3500 + width)
        words = rng.integers(0, 2**32, (slices, cap, 2048), dtype=np.uint32)
        want, per_slice = words.copy(), {}
        for s in (1, 2, 5, 7):
            k = width if s == 5 else int(rng.integers(1, width + 1))
            at = rng.choice(cap * 2048, size=k, replace=False)
            slot, word = (at // 2048).astype(np.int32), \
                (at % 2048).astype(np.int32)
            sets, clears = (rng.integers(0, 2**32, k, dtype=np.uint32)
                            for _ in range(2))
            per_slice[s] = (slot, word, sets, clears)
            want[s, slot, word] = (want[s, slot, word] & ~clears) | sets
        *batch, widest = pack_mutation_batches(per_slice, slices, cap)
        assert widest == width and batch[0].shape \
            == (slices, mutation_batch_width(width))
        staged = jax.device_put(words, NamedSharding(mesh, P(SLICE_AXIS)))
        out = compile_serve_apply_writes(mesh, donate=donate)(
            staged, *batch, widest if run is None else np.int32(run))
        assert staged.is_deleted() == donate
        np.testing.assert_array_equal(np.asarray(out), want)

    def test_write_after_a_patch_keeps_the_patched_keys(self, holder):
        from pilosa_tpu.ops.pool import INVALID_KEY

        f, e, mgr = self._staged(holder)
        sv = mgr._views[self.KEY]
        f.set_bit(11, 65536 + 4)  # row 11, slice 0, block 1: a new container
        assert q(e, "i", "Count(Bitmap(rowID=11))") == [3]
        f.set_bit(11, 65536 + 5)  # into the patched container, in place
        assert q(e, "i", "Count(Bitmap(rowID=11))") == [4]
        assert sv is mgr._views[self.KEY]
        assert (mgr.stats["container_patches"], mgr.stats["stage"],
                mgr.stats["apply_in_place"]) == (1, 1, 2)
        dev_keys = np.asarray(sv.sharded.keys)
        live = sv.keys_host != INVALID_KEY
        assert live[0].sum() == 3 and live[1].sum() == 2
        for s in range(sv.padded_slices):
            assert (dev_keys[s, sv.slots_host[s][live[s]]]
                    == sv.keys_host[s][live[s]]).all()

    def test_back_to_back_writes_do_not_break_the_measure_loop(self,
                                                                holder):
        """Each write donates the words the cost measurement of the
        write before may still be waiting on: a deleted array is a
        skipped sample (ok=False), never a dead worker."""
        import time

        f, e, mgr = self._staged(holder)
        for i in range(6):
            f.set_bit(10, 200 + i)
            assert q(e, "i", self.TEXT) == [10 + i]
        assert mgr.stats["apply_in_place"] == 6
        old = mgr._views[self.KEY].sharded.words
        f.set_bit(10, 300)
        assert q(e, "i", self.TEXT) == [16] and old.is_deleted()
        got = []
        mgr._measure_async(old, time.monotonic(),
                           lambda dt, ok=True: got.append(ok))
        for _ in range(500):
            if got and not mgr._measure_q.unfinished_tasks:
                break
            time.sleep(0.01)
        assert got == [False] and mgr._measure_thread.is_alive()

    def test_readers_of_every_kind_racing_writers_never_meet_a_deleted_pool(
            self, holder):
        """Counts (lone and batched), TopN plain and with a src tree,
        beside two writers: whichever form each refresh picked, no
        reader's launch found its pool gone (any such failure falls
        back to the host and is counted), and the quiesced view equals
        the host."""
        import sys
        import threading
        import time

        f, e, mgr = self._staged(holder)
        host = Executor(holder, use_device=False)
        texts = ["Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))",
                 self.TEXT, "TopN(frame=general, n=2)",
                 "TopN(Bitmap(rowID=11), frame=general, n=2)"]
        for t in texts:
            q(e, "i", t)                       # every program compiled
        stop, errors = threading.Event(), []

        def writer(k):
            rng = np.random.default_rng(k)
            while not stop.is_set():
                c = int(rng.integers(2)) * SLICE_WIDTH + int(rng.integers(512))
                (f.set_bit if rng.random() < 0.7 else f.clear_bit)(
                    10 + int(rng.integers(2)), c)

        def reader(k):
            rng = np.random.default_rng(k)
            try:
                while not stop.is_set():
                    q(e, "i", texts[int(rng.integers(len(texts)))])
            except Exception as err:  # noqa: BLE001 — reported below
                errors.append(repr(err))

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in (1, 2)] + [
            threading.Thread(target=reader, args=(k,)) for k in range(6)]
        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-4)   # more plan-to-launch windows cut open
        try:
            for t in threads:
                t.start()
            time.sleep(1.5)
            stop.set()
            for t in threads:
                t.join(60)
        finally:
            stop.set()
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads) and errors == []
        for t in texts:
            assert q(e, "i", t) == q(host, "i", t)
        st = mgr.stats.copy()
        assert (st.get("fallback_error", 0), st.get("lone_fused_failed", 0),
                st["apply_in_place_failed"], st["stage"]) == (0, 0, 0, 1)
        assert st["apply_in_place"] + st["apply_copied"] \
            == st["incremental"] > 0

    def test_failed_in_place_apply_drops_the_view_and_the_write_shows(
            self, holder):
        f, e, mgr = self._staged(holder)
        f.set_bit(10, 100)
        assert q(e, "i", self.TEXT) == [10]     # compiles the donated form
        real, old = mgr._apply_fns[True], mgr._views[self.KEY].sharded.words

        def lost(words, *batch):
            words.delete()                       # the launch consumed it
            raise RuntimeError("device lost mid-scatter")

        mgr._apply_fns[True] = lost
        f.set_bit(10, 101)
        assert q(e, "i", self.TEXT) == [11]
        assert mgr.stats["apply_in_place_failed"] == 1 and old.is_deleted()
        mgr._apply_fns[True] = real
        assert q(e, "i", self.TEXT) == [11]
        sv = mgr._views[self.KEY]                # staged anew, write included
        assert not sv.sharded.words.is_deleted()
        assert (mgr.stats["stage"], mgr.stats["apply_in_place"]) == (2, 1)
        f.set_bit(10, 102)
        assert q(e, "i", self.TEXT) == [12]
        assert mgr.stats["apply_in_place"] == 2

    def test_failed_copied_apply_leaves_the_old_pool_in_place(self, holder):
        f, e, mgr = self._staged(holder)
        pins: list = []
        mgr._row_counts_args("i", "general", "standard", [0, 1], 2,
                             pins=pins)
        sv, old = mgr._views[self.KEY], mgr._views[self.KEY].sharded.words

        def refused(words, *batch):
            raise RuntimeError("launch refused")

        mgr._apply_fns[False] = refused
        f.set_bit(10, 100)
        assert q(e, "i", self.TEXT) == [10]      # folded on the host
        assert mgr._views[self.KEY] is sv and sv.sharded.words is old
        assert not old.is_deleted()
        assert (mgr.stats["apply_copied"],
                mgr.stats["apply_in_place_failed"]) == (0, 0)
        del mgr._apply_fns[False]                # the write is still owed
        f.set_bit(10, 101)     # (a new write: the query memo holds [10])
        assert q(e, "i", self.TEXT) == [11]
        assert mgr.stats["apply_copied"] == 1 and not old.is_deleted()
        assert mgr._views[self.KEY] is sv and sv.sharded.words is not old
        mgr._release_pins(pins)
