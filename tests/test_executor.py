"""Executor tests (model: /root/reference/executor_test.go — real local
executor, mocked remote client at the RPC seam)."""

from datetime import datetime

import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.errors import QueryError
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.parallel import Cluster, ModHasher, Node
from pilosa_tpu.pql import parse_string
from pilosa_tpu import SLICE_WIDTH


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


def make_executor(holder, **kw):
    return Executor(holder, use_device=kw.pop("use_device", False), **kw)


def seed(holder, index="i", frame="general", bits=()):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    for row, col in bits:
        f.set_bit(row, col)
    return f


def q(executor, index, pql, slices=None, opt=None):
    return executor.execute(index, parse_string(pql), slices, opt)


class TestBitmapCalls:
    def test_bitmap(self, holder):
        seed(holder, bits=[(10, 0), (10, 3), (10, SLICE_WIDTH + 1)])
        e = make_executor(holder)
        row = q(e, "i", "Bitmap(rowID=10)")[0]
        assert list(row) == [0, 3, SLICE_WIDTH + 1]

    def test_bitmap_attaches_row_attrs(self, holder):
        f = seed(holder, bits=[(10, 0)])
        f.row_attr_store.set_attrs(10, {"foo": "bar"})
        e = make_executor(holder)
        row = q(e, "i", "Bitmap(rowID=10)")[0]
        assert row.attrs == {"foo": "bar"}

    def test_intersect_union_difference(self, holder):
        seed(holder, bits=[
            (10, 0), (10, 1), (10, SLICE_WIDTH + 2),
            (11, 1), (11, 2), (11, SLICE_WIDTH + 2),
        ])
        e = make_executor(holder)
        assert list(q(e, "i", "Intersect(Bitmap(rowID=10), Bitmap(rowID=11))")[0]) \
            == [1, SLICE_WIDTH + 2]
        assert list(q(e, "i", "Union(Bitmap(rowID=10), Bitmap(rowID=11))")[0]) \
            == [0, 1, 2, SLICE_WIDTH + 2]
        assert list(q(e, "i", "Difference(Bitmap(rowID=10), Bitmap(rowID=11))")[0]) \
            == [0]

    def test_count(self, holder):
        seed(holder, bits=[(10, 3), (10, SLICE_WIDTH + 1), (10, 2 * SLICE_WIDTH + 5)])
        e = make_executor(holder)
        assert q(e, "i", "Count(Bitmap(rowID=10))")[0] == 3

    def test_count_device_matches_host(self, holder):
        seed(holder, bits=[
            (10, 0), (10, 1), (10, SLICE_WIDTH + 2), (10, 65536 + 7),
            (11, 1), (11, SLICE_WIDTH + 2), (11, 99999),
        ])
        host = make_executor(holder, use_device=False)
        dev = make_executor(holder, use_device=True)
        for pql in (
            "Count(Bitmap(rowID=10))",
            "Count(Intersect(Bitmap(rowID=10), Bitmap(rowID=11)))",
            "Count(Union(Bitmap(rowID=10), Bitmap(rowID=11)))",
            "Count(Difference(Bitmap(rowID=10), Bitmap(rowID=11)))",
            "Count(Bitmap(rowID=999))",
        ):
            assert q(dev, "i", pql)[0] == q(host, "i", pql)[0], pql

    def test_range(self, holder):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general", time_quantum="YMDH")
        f.set_bit(1, 100, t=datetime(2017, 4, 2, 12, 0))
        f.set_bit(1, 200, t=datetime(2017, 4, 3, 9, 0))
        f.set_bit(1, 300, t=datetime(2018, 1, 1, 0, 0))
        e = make_executor(holder)
        row = q(e, "i", 'Range(rowID=1, frame="general", start="2017-04-01T00:00", end="2017-05-01T00:00")')[0]
        assert list(row) == [100, 200]

    def test_count_empty_query_error(self, holder):
        seed(holder)
        e = make_executor(holder)
        with pytest.raises(QueryError):
            q(e, "i", "Count()")


class TestTopN:
    def test_topn(self, holder):
        bits = [(0, c) for c in range(5)] + [(1, c) for c in range(3)] \
            + [(2, c) for c in range(8)] + [(3, SLICE_WIDTH + 1)]
        seed(holder, bits=bits)
        e = make_executor(holder)
        pairs = q(e, "i", 'TopN(frame="general", n=2)')[0]
        assert pairs == [(2, 8), (0, 5)]

    def test_topn_with_src(self, holder):
        bits = [(0, c) for c in range(5)] + [(1, c) for c in range(10, 13)] \
            + [(2, c) for c in range(8)] + [(9, 0), (9, 1), (9, 11)]
        seed(holder, bits=bits)
        e = make_executor(holder)
        pairs = q(e, "i", 'TopN(Bitmap(rowID=9), frame="general", n=3)')[0]
        # Intersection counts with row 9 {0,1,11}: row9->3, row0->2, row2->2.
        assert pairs == [(9, 3), (0, 2), (2, 2)]

    def test_topn_multislice_exact_recount(self, holder):
        # Row 0 dominates slice 0, row 1 dominates slice 1; exact phase-2
        # recount must rank globally.
        bits = [(0, c) for c in range(10)] + [(1, c) for c in range(4)] \
            + [(1, SLICE_WIDTH + c) for c in range(9)]
        seed(holder, bits=bits)
        e = make_executor(holder)
        pairs = q(e, "i", 'TopN(frame="general", n=2)')[0]
        assert pairs == [(1, 13), (0, 10)]


class TestWrites:
    def test_setbit_clearbit(self, holder):
        seed(holder)
        e = make_executor(holder)
        assert q(e, "i", "SetBit(frame=\"general\", rowID=1, columnID=9)")[0] is True
        assert q(e, "i", "SetBit(frame=\"general\", rowID=1, columnID=9)")[0] is False
        assert list(q(e, "i", "Bitmap(rowID=1)")[0]) == [9]
        assert q(e, "i", "ClearBit(frame=\"general\", rowID=1, columnID=9)")[0] is True
        assert q(e, "i", "ClearBit(frame=\"general\", rowID=1, columnID=9)")[0] is False

    def test_setbit_with_timestamp(self, holder):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists("general", time_quantum="YM")
        e = make_executor(holder)
        q(e, "i", 'SetBit(frame="general", rowID=1, columnID=2, timestamp="2017-04-02T12:30")')
        row = q(e, "i", 'Range(rowID=1, frame="general", start="2017-04-01T00:00", end="2017-05-01T00:00")')[0]
        assert list(row) == [2]

    def test_set_row_attrs(self, holder):
        f = seed(holder)
        e = make_executor(holder)
        q(e, "i", 'SetRowAttrs(frame="general", rowID=7, x=123, y="z", b=true)')
        assert f.row_attr_store.attrs(7) == {"x": 123, "y": "z", "b": True}
        # Bulk fast path: multiple SetRowAttrs in one query.
        res = q(e, "i", 'SetRowAttrs(frame="general", rowID=8, v=1)\n'
                        'SetRowAttrs(frame="general", rowID=9, v=2)')
        assert res == [None, None]
        assert f.row_attr_store.attrs(8) == {"v": 1}
        assert f.row_attr_store.attrs(9) == {"v": 2}

    def test_set_column_attrs(self, holder):
        seed(holder)
        e = make_executor(holder)
        q(e, "i", 'SetColumnAttrs(id=3, color="red")')
        assert holder.index("i").column_attr_store.attrs(3) == {"color": "red"}


class TestDistributed:
    """Real local executor + mocked remote (executor_test.go:473-693)."""

    def _cluster(self, replica_n=1):
        return Cluster(nodes=[Node("host0"), Node("host1")],
                       hasher=ModHasher(), partition_n=4, replica_n=replica_n)

    def test_remote_count_forwarded(self, holder):
        seed(holder, bits=[(10, s * SLICE_WIDTH) for s in range(4)])
        cluster = self._cluster()
        calls = []

        class MockClient:
            def execute_query(self, node, index, query, slices, remote):
                calls.append((node.host, index, query, tuple(slices), remote))
                return [len(slices)]  # 1 bit per slice seeded above

        e = Executor(holder, host="host0", cluster=cluster,
                     client=MockClient(), use_device=False)
        total = q(e, "i", "Count(Bitmap(rowID=10))")[0]
        assert total == 4
        # Exactly the slices host1 owns were forwarded, query re-serialized.
        (host, index, query, slices, remote), = calls
        assert host == "host1" and index == "i" and remote is True
        assert query == "Count(Bitmap(rowID=10))"
        expected = tuple(s for s in range(4)
                         if cluster.fragment_nodes("i", s)[0].host == "host1")
        assert slices == expected and len(slices) > 0

    def test_remote_failure_fails_over_to_replica(self, holder):
        seed(holder, bits=[(10, s * SLICE_WIDTH) for s in range(4)])
        cluster = self._cluster(replica_n=2)

        class FailingClient:
            def execute_query(self, node, index, query, slices, remote):
                raise ConnectionError("node down")

        e = Executor(holder, host="host0", cluster=cluster,
                     client=FailingClient(), use_device=False)
        # host1's slices re-split onto host0 (the replica), served locally.
        assert q(e, "i", "Count(Bitmap(rowID=10))")[0] == 4

    def test_remote_failure_no_replica_raises(self, holder):
        seed(holder, bits=[(10, s * SLICE_WIDTH) for s in range(4)])
        cluster = self._cluster(replica_n=1)

        class FailingClient:
            def execute_query(self, node, index, query, slices, remote):
                raise ConnectionError("node down")

        e = Executor(holder, host="host0", cluster=cluster,
                     client=FailingClient(), use_device=False)
        with pytest.raises(ConnectionError):
            q(e, "i", "Count(Bitmap(rowID=10))")

    def test_remote_opt_restricts_to_local(self, holder):
        seed(holder, bits=[(10, s * SLICE_WIDTH) for s in range(4)])
        cluster = self._cluster()

        class ExplodingClient:
            def execute_query(self, *a, **kw):
                raise AssertionError("remote exec must not happen when opt.remote")

        e = Executor(holder, host="host0", cluster=cluster,
                     client=ExplodingClient(), use_device=False)
        local = [s for s in range(4)
                 if cluster.fragment_nodes("i", s)[0].host == "host0"]
        n = e.execute("i", parse_string("Count(Bitmap(rowID=10))"),
                      local, ExecOptions(remote=True))[0]
        assert n == len(local)

    def test_setbit_routed_to_replicas(self, holder):
        seed(holder)
        cluster = self._cluster(replica_n=2)
        calls = []

        class MockClient:
            def execute_query(self, node, index, query, slices, remote):
                calls.append((node.host, query))
                return [True]

        e = Executor(holder, host="host0", cluster=cluster,
                     client=MockClient(), use_device=False)
        changed = q(e, "i", 'SetBit(frame="general", rowID=1, columnID=0)')[0]
        assert changed is True
        # Local write applied + forwarded to the other replica once.
        assert list(holder.fragment("i", "general", "standard", 0).row(1)) == [0]
        assert calls == [("host1", 'SetBit(columnID=0, frame="general", rowID=1)')]


class TestDeviceTopN:
    def test_topn_device_matches_host(self, holder):
        """Plain TopN takes the exact device path (pool_row_counts);
        results must match the host rank-cache path, including
        thresholds and ties."""
        bits = []
        for r, k in [(1, 7), (2, 12), (3, 3), (9, 12)]:
            bits += [(r, c * 131) for c in range(k)]
        bits += [(5, SLICE_WIDTH + 1), (5, SLICE_WIDTH + 2)]
        seed(holder, bits=bits)
        host = make_executor(holder, use_device=False)
        dev = make_executor(holder, use_device=True)
        for pql in (
            "TopN(frame=general, n=3)",
            "TopN(frame=general, n=100)",
            "TopN(frame=general, n=2, threshold=4)",
        ):
            assert q(dev, "i", pql)[0] == q(host, "i", pql)[0], pql

    def test_topn_filters_keep_host_path(self, holder):
        """Attr-filtered TopN needs the host attr store; the device gate
        must not hijack it."""
        seed(holder, bits=[(1, 0), (1, 5), (2, 7)])
        f = holder.frame("i", "general")
        f.row_attr_store.set_attrs(1, {"cat": "x"})
        f.row_attr_store.set_attrs(2, {"cat": "y"})
        dev = make_executor(holder, use_device=True)
        res = q(dev, "i", 'TopN(frame=general, n=5, field="cat",'
                          ' filters=["x"])')[0]
        assert res == [(1, 2)]


class TestDeviceTreeFuzz:
    def test_random_trees_device_matches_host(self, holder):
        """Randomized op-tree differential: Count over random
        Intersect/Union/Difference trees, fused device plan vs host
        roaring (the executor-level analog of the kernel differential
        suite)."""
        import random

        rng = random.Random(4242)
        rows = list(range(1, 9))
        bits = []
        for r in rows:
            k = rng.randrange(0, 200)
            cols = rng.sample(range(2 * SLICE_WIDTH), k=k)
            bits += [(r, c) for c in cols]
        bits.append((1, 0))  # rows 1 always exists
        seed(holder, bits=bits)
        host = make_executor(holder, use_device=False)
        dev = make_executor(holder, use_device=True)

        def gen_tree(depth):
            if depth == 0 or rng.random() < 0.4:
                return f"Bitmap(rowID={rng.choice(rows + [777])})"
            op = rng.choice(["Intersect", "Union", "Difference"])
            n = rng.randrange(2, 4)
            children = ", ".join(gen_tree(depth - 1) for _ in range(n))
            return f"{op}({children})"

        for _ in range(40):
            pql = f"Count({gen_tree(rng.randrange(1, 4))})"
            a = q(dev, "i", pql)[0]
            b = q(host, "i", pql)[0]
            assert a == b, (pql, a, b)


class TestDeviceRange:
    def test_count_range_device_matches_host(self, holder):
        """Count(Range(...)) lowers to an OR over time-view leaves on
        device; absent view fragments contribute empty, matching the
        host union path."""
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general", time_quantum="YMD")
        f.set_bit(1, 100, t=datetime(2017, 4, 2, 12, 0))
        f.set_bit(1, 200, t=datetime(2017, 4, 28, 9, 0))
        f.set_bit(1, 100, t=datetime(2017, 5, 2, 1, 0))   # dup col, later
        f.set_bit(1, 300, t=datetime(2018, 1, 1, 0, 0))   # outside range
        f.set_bit(2, 400, t=datetime(2017, 4, 3, 0, 0))   # other row
        host = make_executor(holder, use_device=False)
        dev = make_executor(holder, use_device=True)
        for pql in (
            'Count(Range(rowID=1, frame="general",'
            ' start="2017-04-01T00:00", end="2017-05-01T00:00"))',
            'Count(Range(rowID=1, frame="general",'
            ' start="2017-04-01T00:00", end="2017-06-01T00:00"))',
            'Count(Union(Range(rowID=1, frame="general",'
            ' start="2017-04-01T00:00", end="2017-05-01T00:00"),'
            ' Bitmap(rowID=2, frame="general")))',
            'Count(Range(rowID=9, frame="general",'
            ' start="2017-04-01T00:00", end="2017-05-01T00:00"))',
            'Count(Range(rowID=1, frame="general",'
            ' start="2019-01-01T00:00", end="2019-02-01T00:00"))',
        ):
            a = q(dev, "i", pql)[0]
            b = q(host, "i", pql)[0]
            assert a == b, (pql, a, b)
        # sanity: the first range really finds 2 columns
        assert q(host, "i",
                 'Count(Range(rowID=1, frame="general",'
                 ' start="2017-04-01T00:00", end="2017-05-01T00:00"))')[0] == 2


class TestHostQueryCache:
    """Generation-validated caches on the cost-routed host count path
    (VERDICT r3 #4): repeats serve from the memo, writes invalidate."""

    def _routed(self, holder):
        # device backend "on" but every query under the work threshold
        # routes to the host plan — the small-query serving path.
        seed(holder, bits=[(r, c) for r in range(3) for c in (1, 2, 70000)])
        return Executor(holder, use_device=True, device_min_work=10**9)

    def test_repeat_hits_memo_and_blocks(self, holder):
        e = self._routed(holder)
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", pql)[0] == 3
        h0 = dict(e.host_cache_stats)
        # an immediate repeat is answered by the query-level memo (one
        # epoch compare), never reaching the per-slice layer
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_hit"] > h0["query_hit"]
        assert e.host_cache_stats["memo_hit"] == h0["memo_hit"]
        # an UNRELATED write moves the global epoch (query memo misses)
        # but not this query's fragment generations — the per-slice
        # memo layer answers those slices without refolding
        seed(holder, index="other", bits=[(0, 1)])
        h1 = dict(e.host_cache_stats)
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["memo_hit"] > h1["memo_hit"]

    def test_write_invalidates(self, holder):
        e = self._routed(holder)
        pql = "Count(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", pql)[0] == 3
        assert q(e, "i", pql)[0] == 3  # memoized
        holder.frame("i", "general").clear_bit(0, 2)
        assert q(e, "i", pql)[0] == 2  # generation bumped -> recompute
        holder.frame("i", "general").set_bit(0, 2)
        assert q(e, "i", pql)[0] == 3

    def test_fragment_recreation_invalidates(self, holder):
        e = self._routed(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        holder.delete_index("i")
        seed(holder, bits=[(0, 5)])
        # new Fragment OBJECT: identity check fails, memo recomputes
        assert q(e, "i", pql)[0] == 1

    def test_different_rows_are_distinct_keys(self, holder):
        e = self._routed(holder)
        assert q(e, "i", "Count(Bitmap(rowID=0))")[0] == 3
        assert q(e, "i", "Count(Bitmap(rowID=1))")[0] == 3
        f = holder.frame("i", "general")
        f.set_bit(1, 9)
        assert q(e, "i", "Count(Bitmap(rowID=1))")[0] == 4
        assert q(e, "i", "Count(Bitmap(rowID=0))")[0] == 3

    def test_bounds(self):
        from pilosa_tpu.parallel.plan import HostQueryCache

        c = HostQueryCache()
        class F:  # stand-in fragment
            pass
        frags = [F() for _ in range(c._BLOCKS_MAX + 10)]
        for i, fr in enumerate(frags):
            c.block_put(fr, 0, 1, i)
        assert len(c._blocks) == c._BLOCKS_MAX
        # oldest evicted, newest present
        assert c.block_get(frags[-1], 0, 1) == len(frags) - 1
        assert c.block_get(frags[0], 0, 1) is None
        for i in range(c._MEMO_MAX + 10):
            c.memo_put(("i", "s", ("l",), i), ((None, -1),), i)
        assert len(c._memo) == c._MEMO_MAX

    def test_deleted_fragments_not_pinned(self, holder):
        import gc
        import weakref

        e = self._routed(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        frag = holder.fragment("i", "general", "standard", 0)
        wr = weakref.ref(frag)
        del frag
        holder.delete_index("i")
        gc.collect()
        # cache entries hold weak refs only — the deleted index's
        # fragment (and its parsed storage) must be collectable
        assert wr() is None


class TestQueryLevelMemo:
    """Whole-query Count memo validated by the process-wide mutation
    epoch (VERDICT r4 #4): a repeated read-only Count is one dict probe,
    and EVERY mutation class — bits, schema, labels, quanta — bumps the
    epoch so a hit can never be stale."""

    def _exec(self, holder):
        seed(holder, bits=[(r, c) for r in range(3) for c in (1, 2, 70000)])
        return Executor(holder, use_device=True, device_min_work=10**9)

    def test_repeat_hits_query_memo_across_reparse(self, holder):
        e = self._exec(holder)
        pql = "Count(Union(Bitmap(rowID=0), Bitmap(rowID=1)))"
        assert q(e, "i", pql)[0] == 3  # rows share columns {1,2,70000}
        h0 = e.host_cache_stats["query_hit"]
        # a RE-PARSED query (fresh Call objects) still hits: the key is
        # structural, not object identity
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_hit"] == h0 + 1

    def test_every_mutation_class_bumps_epoch(self, holder):
        from pilosa_tpu.core.fragment import MUTATION_EPOCH
        from pilosa_tpu.core.timequantum import TimeQuantum

        e = self._exec(holder)
        f = holder.frame("i", "general")
        idx = holder.index("i")

        def bumped(fn):
            n0 = MUTATION_EPOCH.n
            fn()
            return MUTATION_EPOCH.n > n0

        assert bumped(lambda: f.set_bit(9, 9))
        assert bumped(lambda: f.clear_bit(9, 9))
        assert bumped(lambda: f.import_bits([5], [123]))
        assert bumped(lambda: f.set_time_quantum(TimeQuantum("YMD")))
        assert bumped(lambda: f.set_row_label("rid"))
        assert bumped(lambda: idx.set_time_quantum(TimeQuantum("YM")))
        assert bumped(lambda: idx.set_column_label("cid"))
        assert bumped(lambda: idx.create_frame("other"))
        assert bumped(lambda: idx.delete_frame("other"))
        assert bumped(lambda: holder.create_index("j"))
        assert bumped(lambda: holder.delete_index("j"))
        # a no-op write also bumps (it still appends to the mutation
        # log) — over-invalidation is the safe direction

    def test_write_between_repeats_recomputes(self, holder):
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        assert q(e, "i", pql)[0] == 3
        holder.frame("i", "general").set_bit(0, 555)
        assert q(e, "i", pql)[0] == 4

    def test_cluster_mode_never_query_memoizes(self, holder):
        seed(holder, bits=[(0, 1)])
        nodes = [Node("h1:1"), Node("h2:1")]
        cluster = Cluster(nodes=nodes, hasher=ModHasher())
        e = Executor(holder, host="h1:1", cluster=cluster, use_device=False)
        # remote fan-out would fail (no client); local-slices remote
        # form exercises the path without one
        q(e, "i", "Count(Bitmap(rowID=0))", slices=[0],
          opt=ExecOptions(remote=True))
        assert e.host_cache_stats["query_hit"] == 0
        assert e.host_cache_stats["query_miss"] == 0

    def test_explicit_slices_are_distinct_keys(self, holder):
        e = self._exec(holder)
        f = holder.frame("i", "general")
        f.set_bit(7, SLICE_WIDTH + 3)  # slice 1
        f.set_bit(7, 3)                # slice 0
        assert q(e, "i", "Count(Bitmap(rowID=7))", slices=[0])[0] == 1
        assert q(e, "i", "Count(Bitmap(rowID=7))")[0] == 2
        assert q(e, "i", "Count(Bitmap(rowID=7))", slices=[1])[0] == 1


class TestQueryMemoRevalidation:
    """r5 second tier: entries carry (structural epoch, the write
    counter of every view read); an epoch bump from an UNRELATED write
    revalidates by comparing the counters instead of refolding, while
    touched-view writes and any structural change (new
    fragment/frame/index, label or quantum change) still invalidate."""

    def _exec(self, holder):
        seed(holder, bits=[(r, c) for r in range(3) for c in (1, 2, 70000)])
        # a second frame that exists BEFORE the memo is stored, so
        # writing to it later is a plain bit write, not a create
        holder.index("i").create_frame_if_not_exists("other")
        holder.frame("i", "other").set_bit(0, 1)
        return Executor(holder, use_device=True, device_min_work=10**9)

    def test_unrelated_write_revalidates(self, holder):
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        r0 = e.host_cache_stats["query_reval"]
        m0 = e.host_cache_stats["query_miss"]
        holder.frame("i", "other").set_bit(5, 99)  # bumps epoch only
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_reval"] == r0 + 1
        assert e.host_cache_stats["query_miss"] == m0

    def test_revalidated_entry_restamps(self, holder):
        # after one revalidation, an unmutated repeat takes the fast
        # epoch path again (the entry was re-stamped)
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        holder.frame("i", "other").set_bit(5, 99)
        assert q(e, "i", pql)[0] == 3
        h0 = e.host_cache_stats["query_hit"]
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_hit"] == h0 + 1

    def test_touched_write_refolds(self, holder):
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        r0 = e.host_cache_stats["query_reval"]
        holder.frame("i", "general").set_bit(0, 555)
        assert q(e, "i", pql)[0] == 4
        assert e.host_cache_stats["query_reval"] == r0

    def test_noop_touched_write_refolds_same_count(self, holder):
        # re-setting a set bit bumps the generation (logged) — the
        # memo can't know it was a no-op, so it refolds, correctly
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        r0 = e.host_cache_stats["query_reval"]
        m0 = e.host_cache_stats["query_miss"]
        holder.frame("i", "general").set_bit(0, 1)  # already set
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_reval"] == r0
        assert e.host_cache_stats["query_miss"] == m0 + 1

    def test_structural_change_invalidates(self, holder):
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        m0 = e.host_cache_stats["query_miss"]
        holder.create_index("scratch")  # structural: token must die
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_miss"] == m0 + 1

    def test_new_fragment_in_queried_slices_recounts(self, holder):
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        # slice 1 has no fragment yet; memo over slices [0, 1]
        assert q(e, "i", pql, slices=[0, 1])[0] == 3
        holder.frame("i", "general").set_bit(0, SLICE_WIDTH + 8)
        assert q(e, "i", pql, slices=[0, 1])[0] == 4

    # -- the token is one write counter a view (PR 32) ----------------------

    def test_other_slice_write_refolds_subset_count(self, holder):
        # The documented coarsening: the token is per VIEW, so a Count
        # over slices=[0] refolds after a write to slice 1 of the view
        # it reads (a per-fragment token used to revalidate here).
        e = self._exec(holder)
        holder.frame("i", "general").set_bit(0, SLICE_WIDTH + 8)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql, slices=[0])[0] == 3
        r0 = e.host_cache_stats["query_reval"]
        m0 = e.host_cache_stats["query_miss"]
        holder.frame("i", "general").set_bit(0, SLICE_WIDTH + 9)
        assert q(e, "i", pql, slices=[0])[0] == 3
        assert e.host_cache_stats["query_reval"] == r0
        assert e.host_cache_stats["query_miss"] == m0 + 1

    @pytest.mark.parametrize("how", ["import", "restore"])
    def test_log_reset_invalidates(self, holder, how):
        # Imports and restores replace storage wholesale (_log_reset):
        # they move the view's counter like any bit write.
        import io

        e = self._exec(holder)
        frag = holder.fragment("i", "general", "standard", 0)
        backup = io.BytesIO()
        frag.write_to_tar(backup)
        pql = "Count(Bitmap(rowID=0))"
        assert q(e, "i", pql)[0] == 3
        r0 = e.host_cache_stats["query_reval"]
        if how == "import":
            holder.frame("i", "general").import_bits([0, 0], [7, 8])
            want = 5
        else:
            holder.frame("i", "general").set_bit(0, 555)
            assert q(e, "i", pql)[0] == 4
            backup.seek(0)
            frag.read_from_tar(backup)
            want = 3
        assert q(e, "i", pql)[0] == want
        assert e.host_cache_stats["query_reval"] == r0

    def test_range_count_one_entry_per_quantum_view(self, holder):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("events", time_quantum="YM")
        f.set_bit(1, 100, t=datetime(2017, 4, 2))
        f.set_bit(1, 200, t=datetime(2017, 5, 9))
        f.set_bit(1, 300, t=datetime(2018, 1, 1))  # views a later write hits
        e = Executor(holder, use_device=True, device_min_work=10**9)
        pql = ('Count(Range(rowID=1, frame="events",'
               ' start="2017-04-01T00:00", end="2017-06-01T00:00"))')
        s0 = dict(e.host_cache_stats)
        assert q(e, "i", pql)[0] == 2
        s1 = dict(e.host_cache_stats)
        assert s1["query_put"] - s0["query_put"] == 1
        # standard_201704 and standard_201705: one entry each, whatever
        # the number of slices
        assert s1["query_token_pairs"] - s0["query_token_pairs"] == 2
        # a write to an unrelated quantum (and to `standard`, which a
        # Range never reads) revalidates ...
        f.set_bit(1, 301, t=datetime(2018, 1, 2))
        assert q(e, "i", pql)[0] == 2
        s2 = dict(e.host_cache_stats)
        assert s2["query_reval"] == s1["query_reval"] + 1
        assert s2["query_miss"] == s1["query_miss"]
        # ... and one to a quantum it reads refolds
        f.set_bit(1, 201, t=datetime(2017, 5, 10))
        assert q(e, "i", pql)[0] == 3
        assert e.host_cache_stats["query_reval"] == s2["query_reval"]

    def test_write_between_token_read_and_put_never_validates(
            self, holder, monkeypatch):
        # The token is read BEFORE the fold: a write that lands after
        # it (here at the last moment, just before query_put) is not in
        # the stored count, and the entry must never serve.
        e = self._exec(holder)
        pql = "Count(Bitmap(rowID=0))"
        cache = e._host_cache
        real_put = cache.query_put

        def racing_put(*a, **kw):
            holder.frame("i", "general").set_bit(0, 555)
            return real_put(*a, **kw)

        monkeypatch.setattr(cache, "query_put", racing_put)
        assert q(e, "i", pql)[0] == 3  # folded before the write landed
        monkeypatch.setattr(cache, "query_put", real_put)
        h0 = e.host_cache_stats["query_hit"]
        r0 = e.host_cache_stats["query_reval"]
        assert q(e, "i", pql)[0] == 4
        assert e.host_cache_stats["query_hit"] == h0
        assert e.host_cache_stats["query_reval"] == r0

    def test_token_costs_views_not_slices(self, holder, monkeypatch):
        from pilosa_tpu.parallel.plan import _lower_tree

        n_slices = 64
        bits = [(r, s * SLICE_WIDTH + 1) for s in range(n_slices)
                for r in (0, 1)]
        seed(holder, bits=bits)
        seed(holder, frame="other", bits=[(0, 1)])
        e = Executor(holder, use_device=True, device_min_work=10**9)
        pql = ("Count(Union(Intersect(Bitmap(rowID=0), Bitmap(rowID=1)),"
               " Bitmap(rowID=0, frame=other)))")
        leaves = []
        assert _lower_tree(holder, "i",
                           parse_string(pql).calls[0].children[0], leaves)
        calls = []
        real = holder.fragment
        monkeypatch.setattr(
            holder, "fragment",
            lambda *a: calls.append(a) or real(*a))
        tok = e._query_token("i", leaves)
        assert calls == []
        views = [holder.view("i", "general", "standard"),
                 holder.view("i", "other", "standard")]
        assert list(tok) == [(v.writes, v.writes.n) for v in views]
        monkeypatch.undo()
        s0 = dict(e.host_cache_stats)
        assert q(e, "i", pql)[0] == n_slices
        s1 = dict(e.host_cache_stats)
        assert s1["query_put"] - s0["query_put"] == 1
        assert s1["query_token_pairs"] - s0["query_token_pairs"] == len(views)

    def test_concurrent_writers_lose_no_bump(self, holder):
        # Fragments of one view are written under their OWN locks; a
        # lost increment of the view's counter is the one thing that
        # could validate a stale entry.
        import sys
        import threading

        f = seed(holder, bits=[(0, 1), (0, SLICE_WIDTH + 1)])
        writes = holder.view("i", "general", "standard").writes
        n0, per_thread = writes.n, 2000
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [
                threading.Thread(
                    target=lambda base=base: [
                        f.set_bit(1 + i % 7, base + i % 1000)
                        for i in range(per_thread)],
                    daemon=True)
                for base in (0, SLICE_WIDTH)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert writes.n == n0 + 2 * per_thread


class TestCallCacheKey:
    def test_structural_equality_across_parses(self):
        a = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
        b = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))")
        assert a.calls[0].cache_key() == b.calls[0].cache_key()
        c = parse_string("Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=3)))")
        assert a.calls[0].cache_key() != c.calls[0].cache_key()

    def test_list_args_hash(self):
        a = parse_string("TopN(frame=f, n=2, ids=[1,2,3])").calls[0]
        b = parse_string("TopN(frame=f, n=2, ids=[1,2,3])").calls[0]
        assert a.cache_key() == b.cache_key() is not None
        hash(a.cache_key())

    def test_clone_does_not_copy_memo(self):
        a = parse_string("TopN(frame=f, n=2)").calls[0]
        k0 = a.cache_key()
        cl = a.clone()
        cl.args["ids"] = [9, 8]
        assert cl.cache_key() != k0
        assert a.cache_key() == k0


class TestFusedMaterialize:
    """Bitmap-ROOTED (non-Count) trees run the fused dense-fold path
    (VERDICT r4 #5): result equality against the per-slice roaring
    merge it replaced, form-correct containers, and write
    invalidation through the epoch-validated matrix cache."""

    def test_random_trees_match_roaring_path(self, holder):
        import random

        from pilosa_tpu.core.row import Row

        rng = random.Random(777)
        rows = list(range(1, 7))
        bits = [(1, 0)]
        for r in rows:
            cols = rng.sample(range(3 * SLICE_WIDTH),
                              k=rng.randrange(0, 300))
            bits += [(r, c) for c in cols]
        seed(holder, bits=bits)
        e = make_executor(holder, use_device=False)
        n_slices = holder.index("i").max_slice() + 1

        def gen_tree(depth):
            if depth == 0:
                return f"Bitmap(rowID={rng.choice(rows + [99])})"
            op = rng.choice(["Intersect", "Union", "Difference"])
            n = rng.randrange(2, 4)
            kids = ", ".join(
                gen_tree(depth - 1 if rng.random() < 0.5 else 0)
                for _ in range(n))
            return f"{op}({kids})"

        for _ in range(30):
            pql = gen_tree(rng.randrange(1, 3))
            got = q(e, "i", pql)[0]
            call = parse_string(pql).calls[0]
            want = Row()
            for s in range(n_slices):
                want.merge(e.execute_bitmap_call_slice("i", call, s))
            assert got.count() == want.count(), pql
            import numpy as np

            assert np.array_equal(got.columns(), want.columns()), pql

    def test_sparse_result_containers_are_array_form(self, holder):
        f = seed(holder, bits=[(1, c) for c in range(100)]
                 + [(2, c) for c in range(50, 70)])
        del f
        e = make_executor(holder, use_device=False)
        row = q(e, "i", "Intersect(Bitmap(rowID=1), Bitmap(rowID=2))")[0]
        assert row.count() == 20
        seg = row.segments[0]
        assert all(c.is_array() for c in seg.containers)
        # and the result is mutable without corrupting cached matrices
        row.set_bit(999)
        assert row.count() == 21

    def test_dense_result_containers_are_bitmap_form(self, holder):
        f = seed(holder, bits=[])
        f.import_bits([1] * 60000 + [2] * 60000,
                      list(range(60000)) + list(range(60000)))
        e = make_executor(holder, use_device=False)
        row = q(e, "i", "Intersect(Bitmap(rowID=1), Bitmap(rowID=2))")[0]
        assert row.count() == 60000
        assert any(not c.is_array() for c in row.segments[0].containers)

    def test_write_invalidates_fused_result(self, holder):
        f = seed(holder, bits=[(1, 5), (2, 5), (1, SLICE_WIDTH + 9),
                               (2, SLICE_WIDTH + 9)])
        e = make_executor(holder, use_device=False)
        pql = "Intersect(Bitmap(rowID=1), Bitmap(rowID=2))"
        assert q(e, "i", pql)[0].count() == 2
        assert q(e, "i", pql)[0].count() == 2  # matrices now cached
        f.set_bit(1, 777)
        f.set_bit(2, 777)
        assert q(e, "i", pql)[0].count() == 3

    def test_range_materializes_fused(self, holder):
        from datetime import datetime

        from pilosa_tpu.core.timequantum import TimeQuantum

        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("general",
                                           time_quantum=TimeQuantum("YMD"))
        f.set_bit(1, 3, datetime(2017, 1, 2))
        f.set_bit(1, 9, datetime(2017, 1, 3))
        f.set_bit(1, SLICE_WIDTH + 4, datetime(2017, 1, 4))
        e = make_executor(holder, use_device=False)
        row = q(e, "i", "Range(rowID=1, frame=general, "
                "start='2017-01-02T00:00', end='2017-01-05T00:00')")[0]
        assert sorted(row) == [3, 9, SLICE_WIDTH + 4]


class TestCacheKeyTypeSafety:
    def test_float_row_id_raises_even_after_int_memoized(self, holder):
        """1 == 1.0 == True in Python, but Count(rowID=1.0) must raise
        (uint_arg) even when Count(rowID=1) was just memoized — the
        cache key carries value TYPES."""
        seed(holder, bits=[(1, 5), (1, 9)])
        e = Executor(holder, use_device=True, device_min_work=10**9)
        assert q(e, "i", "Count(Bitmap(rowID=1))")[0] == 2
        assert q(e, "i", "Count(Bitmap(rowID=1))")[0] == 2  # memoized
        from pilosa_tpu.pql import Query
        from pilosa_tpu.pql.ast import Call

        float_q = Query(calls=[Call(name="Count", children=[
            Call(name="Bitmap", args={"rowID": 1.0})])])
        with pytest.raises(TypeError):
            e.execute("i", float_q)
        bool_q = Query(calls=[Call(name="Count", children=[
            Call(name="Bitmap", args={"rowID": True})])])
        with pytest.raises(TypeError):
            e.execute("i", bool_q)

    def test_typed_keys_distinguish(self):
        from pilosa_tpu.pql.ast import Call

        a = Call(name="Bitmap", args={"rowID": 1})
        b = Call(name="Bitmap", args={"rowID": 1.0})
        c = Call(name="Bitmap", args={"rowID": True})
        keys = {a.cache_key(), b.cache_key(), c.cache_key()}
        assert len(keys) == 3


class TestMemoConcurrency:
    def test_concurrent_reads_writes_converge_exact(self, holder):
        """Racing readers (query memo + parse cache hot) against a
        writer: no exceptions, every observed count is sane (monotone
        under a set-only writer), and the final quiesced count is
        exact. The host-layer analog of the dryrun's fault-evict-race
        surface."""
        import threading

        seed(holder, bits=[(1, c) for c in range(8)])
        e = make_executor(holder)
        f = holder.frame("i", "general")
        errors = []
        stop = threading.Event()

        def writer():
            try:
                c = 100
                while not stop.is_set():
                    f.set_bit(1, c)
                    c += 1
            except Exception as err:  # noqa: BLE001 — a dying writer
                #                       must FAIL the test, not
                #                       silently quiesce the race
                errors.append(err)

        def reader():
            from pilosa_tpu.pql import parse_string_cached

            try:
                for _ in range(300):
                    q_ = parse_string_cached("Count(Bitmap(rowID=1))")
                    n = e.execute("i", q_)[0]
                    # The memo's contract is epoch-consistency, not
                    # real-time monotonicity (a delayed query_put can
                    # briefly re-serve an older epoch-valid count), so
                    # assert only sanity bounds per observation.
                    assert n >= 8, n
            except Exception as err:  # noqa: BLE001
                errors.append(err)

        wt = threading.Thread(target=writer)
        rs = [threading.Thread(target=reader) for _ in range(3)]
        wt.start()
        [r.start() for r in rs]
        [r.join() for r in rs]
        stop.set()
        wt.join()
        assert not errors, errors
        want = holder.fragment("i", "general", "standard", 0).row(1).count()
        assert want > 8  # the writer really made progress
        assert e.execute(
            "i", parse_string("Count(Bitmap(rowID=1))"))[0] == want
