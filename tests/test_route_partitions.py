"""`Executor._slices_by_node` decides owners once per (partition, ring)
and counts placements once per query. The per-slice loop it replaced is
kept here as the oracle: every routing outcome has to equal it.
"""

import random
import sys
import threading

import pytest

from pilosa_tpu import executor as executor_mod
from pilosa_tpu import obs
from pilosa_tpu.errors import SliceUnavailableError
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.parallel import cluster as cluster_mod
from pilosa_tpu.parallel.cluster import (
    NODE_STATE_DOWN, SERVING_STATES, Cluster, JmpHasher, ModHasher, Node,
    pick_read_replica, preferred_owner)

SLICES = 960


def oracle(e, stats, nodes, index, slices, opt=None):
    """`_slices_by_node` as it stood before PR 27: the whole owner
    ladder and one `stats.inc` for every slice."""
    local_node = (e.cluster.node_by_host(e.host) if e.ici_hosts else None)
    if local_node is not None and local_node not in nodes:
        local_node = None
    breaker = e._breaker_callable(opt)
    read_bound = (opt.staleness
                  if opt is not None and not opt.remote else 0.0)
    sclass = "bounded" if read_bound > 0 else "strict"
    m = {}
    for slice_ in slices:
        owners = [o for o in e.cluster.fragment_nodes(index, slice_)
                  if o in nodes]
        if opt is not None and opt.partial:
            serving = [o for o in owners if o.state in SERVING_STATES]
            if not serving:
                opt.missing_slices.append(slice_)
                continue
            owners = serving
        elif not owners:
            raise SliceUnavailableError()
        pick = None
        if read_bound > 0 and len(owners) > 1:
            pick = pick_read_replica(
                owners, breaker,
                staleness_ok=lambda h, s=slice_:
                    e.epochs.staleness_ok_slice(h, index, s, read_bound),
                queue_depth=e.epochs.queue_depth,
                prefer=e.host,
                ici_hosts=e.ici_hosts or None,
                node_ok=e.peer_health_ok)
        if pick is not None:
            stats.inc(("follower|" if pick.host != owners[0].host
                       else "owner|") + sclass)
        else:
            stats.inc(("fallback_owner|" if read_bound > 0
                       and len(owners) > 1 else "owner|") + sclass)
            pick = preferred_owner(
                owners, breaker,
                prefer=e.host if e.prefer_local_reads else None,
                ici_hosts=e.ici_hosts or None)
        if (local_node is not None and pick.host != e.host
                and pick.host in e.ici_hosts):
            if opt is not None:
                opt.used_ici = True
            pick = local_node
        m.setdefault(pick, []).append(slice_)
    return m


class FakeEpochs:
    """Freshness and queue depth as pure functions of (host, slice), so
    the bounded case exercises the follower, owner and fallback rungs."""

    def staleness_ok_slice(self, host, index, slice_, bound_s):
        if slice_ % 7 == 0:
            return False
        return not (host == "host2" and slice_ % 5 == 0)

    def queue_depth(self, host):
        return {"host1": 3, "host2": 1}.get(host, 0)


def make_cluster(n=3, replica_n=2, hasher=None, partition_n=16):
    return Cluster(nodes=[Node(f"host{i}") for i in range(n)],
                   hasher=hasher or JmpHasher(), partition_n=partition_n,
                   replica_n=replica_n)


def make_executor(cluster, host="host0", **kw):
    return Executor(None, host=host, cluster=cluster, use_device=False,
                    **kw)


class Case:
    def __init__(self, e, nodes=None, opt=ExecOptions):
        self.e, self.opt = e, opt
        self.nodes = list(e.cluster.nodes) if nodes is None else nodes


def _one_node():
    return Case(make_executor(make_cluster(1, replica_n=1)))


def _no_opt():
    return Case(make_executor(make_cluster()), opt=lambda: None)


def _three_jmp():
    return Case(make_executor(make_cluster(hasher=JmpHasher())))


def _three_mod():
    return Case(make_executor(make_cluster(hasher=ModHasher())))


def _down_owner():
    c = make_cluster()
    c.nodes[1].set_state(NODE_STATE_DOWN)
    return Case(make_executor(c))


def _open_breaker():
    def opt():
        o = ExecOptions()
        o.breaker_snapshot = {"host0": "closed", "host1": "open",
                              "host2": "half-open"}
        return o
    return Case(make_executor(make_cluster()), opt=opt)


def _live_breaker():
    class Client:
        def breaker_state(self, host):
            return "open" if host == "host2" else "closed"
    return Case(make_executor(make_cluster(), client=Client()))


def _prefer_local():
    return Case(make_executor(make_cluster(), host="host1",
                              prefer_local_reads=True))


def _ici_local_in_nodes():
    return Case(make_executor(make_cluster(), ici_hosts=["host0", "host2"]))


def _ici_local_not_in_nodes():
    e = make_executor(make_cluster(), ici_hosts=["host2"])
    return Case(e, nodes=e.cluster.nodes[1:])


def _remote():
    e = make_executor(make_cluster(), host="host1")
    return Case(e, nodes=[e.cluster.nodes[1]],
                opt=lambda: ExecOptions(remote=True, partial=True,
                                        staleness=5.0))


def _partial_unserved():
    c = make_cluster(replica_n=1)
    c.nodes[2].set_state(NODE_STATE_DOWN)
    return Case(make_executor(c), opt=lambda: ExecOptions(partial=True))


def _unowned_raises():
    e = make_executor(make_cluster(replica_n=1))
    return Case(e, nodes=e.cluster.nodes[:2])


def _resize(index="i"):
    # One replica, so that a JOINING or LEAVING owner is the only choice.
    c = make_cluster(4, replica_n=1)
    c.begin_join("host4")
    c.begin_leave("host1")
    for s in range(0, SLICES, 3):
        c.mark_handed_off(index, s)
    c.mark_handed_off("other", 1)
    return Case(make_executor(c))


def _resize_partial():
    case = _resize()
    case.opt = lambda: ExecOptions(partial=True)
    return case


def _bounded():
    e = make_executor(make_cluster())
    e.epochs = FakeEpochs()
    return Case(e, opt=lambda: ExecOptions(staleness=2.0))


def _bounded_one_replica():
    return Case(make_executor(make_cluster(replica_n=1)),
                opt=lambda: ExecOptions(staleness=2.0))


def _resplit_subset():
    e = make_executor(make_cluster())
    return Case(e, nodes=[e.cluster.nodes[0], e.cluster.nodes[2]])


CASES = {
    "one_node": _one_node, "no_opt": _no_opt, "three_jmp": _three_jmp,
    "three_mod": _three_mod, "down_owner": _down_owner,
    "open_breaker": _open_breaker, "live_breaker": _live_breaker,
    "prefer_local": _prefer_local,
    "ici_local_in_nodes": _ici_local_in_nodes,
    "ici_local_not_in_nodes": _ici_local_not_in_nodes, "remote": _remote,
    "partial_unserved": _partial_unserved,
    "unowned_raises": _unowned_raises, "resize": _resize,
    "resize_partial": _resize_partial, "bounded": _bounded,
    "bounded_one_replica": _bounded_one_replica,
    "resplit_subset": _resplit_subset,
}


def _shuffled():
    s = list(range(SLICES))
    random.Random(5).shuffle(s)
    return s


SLICE_SETS = {
    "none": [], "one": [421], "all": list(range(SLICES)),
    "shuffled": _shuffled(),
    "sparse_repeats": [900, 3, 3, 77, 2 ** 33, 0, 959, 77],
}


def outcome(split, opt):
    """Everything a caller of `_slices_by_node` can see of one call, the
    global `random` stream it consumed included."""
    random.seed(99)
    try:
        m, err = split(opt), None
    except SliceUnavailableError as exc:
        m, err = None, type(exc)
    return {"groups": m and [(n.host, s) for n, s in m.items()],
            "raised": err,
            "missing": opt and opt.missing_slices,
            "used_ici": opt and opt.used_ici,
            "random_after": random.random()}


@pytest.mark.parametrize("slices", sorted(SLICE_SETS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_routing_equals_the_per_slice_oracle(case, slices):
    c, slices = CASES[case](), SLICE_SETS[slices]
    want_stats = obs.StatMap()
    want = outcome(lambda opt: oracle(c.e, want_stats, c.nodes, "i", slices,
                                      opt), c.opt())
    for _ in range(2):  # the second call reads a filled partition table
        c.e.read_stats.clear()
        got = outcome(lambda opt: c.e._slices_by_node(c.nodes, "i", slices,
                                                      opt), c.opt())
        assert got == want
        assert dict(c.e.read_stats) == dict(want_stats)
    if slices and want["raised"] is None:
        assert want["groups"] or want["missing"]


# The strict cases that route over the whole cluster, as explain does.
STRICT = ["one_node", "no_opt", "three_jmp", "three_mod", "down_owner",
          "open_breaker", "live_breaker", "prefer_local",
          "ici_local_in_nodes", "partial_unserved", "resize",
          "bounded_one_replica"]


@pytest.mark.parametrize("case", STRICT)
def test_explain_placement_makes_the_same_picks(case):
    """`_explain_placement` promises "exactly the picks _slices_by_node
    would make"; it keeps a per-slice loop of its own, names the owner
    picked before the ICI fold, and has no partial mode."""
    c = CASES[case]()
    slices = SLICE_SETS["all"]
    opt = c.opt()
    if opt is not None:
        opt.partial = False
    m = c.e._slices_by_node(c.nodes, "i", slices, opt)
    plan = c.e._explain_placement("i", slices, c.opt())
    folded = {}
    for host, ent in plan["nodes"].items():
        served_by = c.e.host if ent["tier"] == "ici" else host
        folded[served_by] = folded.get(served_by, 0) + ent["slices"]
    assert folded == {n.host: len(ss) for n, ss in m.items()}
    assert bool(plan["tiers"]["ici"]) == bool(opt and opt.used_ici)
    if not c.e.ici_hosts:
        assert {h: ent["sample"] for h, ent in plan["nodes"].items()} == \
            {n.host: ss[:16] for n, ss in m.items()}
    assert "unowned_count" not in plan


class CountingLock:
    def __init__(self):
        self.mu, self.n = threading.Lock(), 0

    def __enter__(self):
        self.n += 1
        return self.mu.__enter__()

    def __exit__(self, *a):
        return self.mu.__exit__(*a)


@pytest.mark.parametrize("case,labels", [("one_node", 1), ("three_jmp", 1),
                                         ("bounded", 3)])
def test_one_lock_per_label_a_query(case, labels):
    c = CASES[case]()
    lock = c.e.read_stats._mu = CountingLock()
    c.e._slices_by_node(c.nodes, "i", SLICE_SETS["all"], c.opt())
    assert len(c.e.read_stats) == labels
    assert 1 <= lock.n <= labels
    assert sum(c.e.read_stats.values()) == SLICES


@pytest.mark.parametrize("case", ["one_node", "three_jmp", "down_owner",
                                  "resize"])
def test_second_query_hashes_nothing_and_decides_per_partition(
        case, monkeypatch):
    c = CASES[case]()
    slices = SLICE_SETS["shuffled"]
    c.e._slices_by_node(c.nodes, "i", slices, c.opt())
    calls = {"partition": 0, "partition_of": 0, "fnv64a": 0,
             "preferred_owner": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(Cluster, "partition",
                        counted("partition", Cluster.partition))
    monkeypatch.setattr(cluster_mod, "partition_of",
                        counted("partition_of", cluster_mod.partition_of))
    monkeypatch.setattr(cluster_mod, "fnv64a",
                        counted("fnv64a", cluster_mod.fnv64a))
    monkeypatch.setattr(executor_mod, "preferred_owner",
                        counted("preferred_owner", preferred_owner))
    before = c.e.placement_stats.get("owner_decisions", 0)
    c.e._slices_by_node(c.nodes, "i", slices, c.opt())
    rings = 2 if c.e.cluster.resizing() else 1
    assert calls["partition"] == calls["partition_of"] == \
        calls["fnv64a"] == 0
    assert 1 <= calls["preferred_owner"] <= rings * c.e.cluster.partition_n
    assert c.e.placement_stats["owner_decisions"] - before == \
        calls["preferred_owner"]


def test_a_bounded_spread_counts_a_decision_a_slice():
    c = CASES["bounded"]()
    c.e._slices_by_node(c.nodes, "i", SLICE_SETS["all"], c.opt())
    assert c.e.placement_stats["owner_decisions"] == SLICES
    assert sum(c.e.read_stats.values()) == SLICES


def test_increments_stay_atomic_under_threads():
    c = CASES["three_jmp"]()
    threads, queries = 16, 20
    slices = SLICE_SETS["all"]
    errors = []

    def client():
        try:
            for _ in range(queries):
                m = c.e._slices_by_node(c.nodes, "i", slices, c.opt())
                assert sum(len(v) for v in m.values()) == SLICES
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=client) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert sum(c.e.read_stats.values()) == threads * queries * SLICES
    assert c.e.placement_stats["owner_decisions"] == \
        threads * queries * c.e.cluster.partition_n


def test_partition_table_is_per_index_and_per_partition_n():
    c = make_cluster()
    e = make_executor(c)
    slices = SLICE_SETS["all"]
    e._slices_by_node(c.nodes, "i", slices)
    e._slices_by_node(c.nodes, "j", slices)
    ti, tj = c.partition_table("i"), c.partition_table("j")
    assert ti is not tj and ti is c.partition_table("i")
    assert dict(ti) == {s: c.partition("i", s) for s in slices}
    assert dict(tj) == {s: c.partition("j", s) for s in slices}
    assert dict(ti) != dict(tj)
    c.partition_n = 5
    assert c.partition_table("i") is not ti and not c.partition_table("i")
    stats = obs.StatMap()
    want = oracle(e, stats, c.nodes, "i", slices)
    assert e._slices_by_node(c.nodes, "i", slices) == want
    assert set(c.partition_table("i").values()) == set(range(5))
    assert set(ti.values()) == set(range(16))  # the old table is not reread


def test_partition_tables_are_bounded(monkeypatch):
    c = make_cluster()
    monkeypatch.setattr(cluster_mod, "PARTITION_TABLES", 4)
    monkeypatch.setattr(cluster_mod, "PARTITION_TABLE_SLICES", 8)
    for k in range(10):
        c.partition_table(f"idx{k}")
    assert len(c._partition_tables) <= 4
    e = make_executor(c)
    m = e._slices_by_node(c.nodes, "i", SLICE_SETS["all"])
    assert len(c.partition_table("i")) == 8
    assert m == oracle(e, obs.StatMap(), c.nodes, "i", SLICE_SETS["all"])
