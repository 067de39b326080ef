"""The served path on a four-device mesh (configuration seg-2b-x4): the
handler, the executor and a `MeshManager` over `default_mesh(4)` against a
plain numpy reference on seeded random bits, at 8 slices (two a device) and
at 6 (the fourth device holds only padding); lone and batched; writes into
every shard, through a reopen; the gauges and the tier that say what the
answer ran on; and a count above 2^31 - 1 from fed limbs to the JSON.
"""

import json
import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.api import Handler
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.parallel import new_test_cluster
from pilosa_tpu.parallel import serve
from pilosa_tpu.parallel.mesh import default_mesh

ROWS = 8
ICI = 'pilosa_query_route_total{backend="mesh",tier="ici"}'


def random_rows(slices, seed, rows=ROWS):
    """`rows` boolean arrays over slices x 2^20 columns, every bit a coin:
    bench.build_dense_holder's rows, so every container is a bitmap and the
    view stages as packed words (the programs seg-2b-x4 serves)."""
    rng = np.random.default_rng(seed)
    return [rng.random(slices * SLICE_WIDTH) < 0.5 for _ in range(rows)]


def expected(rows, op, ids):
    """Plain numpy, 64-bit."""
    first, rest = rows[ids[0]], [rows[i] for i in ids[1:]]
    if op == "Intersect":
        out = np.logical_and.reduce([first] + rest)
    elif op == "Union":
        out = np.logical_or.reduce([first] + rest)
    else:
        out = first & ~np.logical_or.reduce(rest)
    return int(np.count_nonzero(out))


OPS = ("Difference", "Intersect", "Union")


def pql(op, ids):
    leaves = ", ".join(f"Bitmap(rowID={i}, frame=f)" for i in ids)
    return f"Count({op}({leaves}))"


class Served:
    """One node as the server wires it, but for the mesh's size: the suite's
    CPU has 8 devices, the deployment's host four."""

    def __init__(self, holder, devices):
        self.holder = holder
        cluster = new_test_cluster(1)
        host = cluster.nodes[0].host
        self.ex = Executor(holder, host=host, cluster=cluster,
                           use_device=True, device_min_work=0)
        self.mgr = self.ex._mesh_mgr = serve.MeshManager(
            holder, mesh=default_mesh(devices))
        self.h = Handler(holder, self.ex, cluster=cluster, host=host)

    def load(self, rows):
        """Through the import path, so the bits are on the disk."""
        assert self.h.handle("POST", "/index/i").status == 200
        assert self.h.handle("POST", "/index/i/frame/f").status == 200
        frame = self.holder.index("i").frame("f")
        for r, bits in enumerate(rows):
            cols = np.flatnonzero(bits)
            frame.import_bits(np.full(cols.size, r, dtype=np.int64), cols)

    def query(self, text, profiled=False):
        r = self.h.handle("POST", "/index/i/query", body=text.encode(),
                          params={"profile": "true"} if profiled else {})
        assert r.status == 200, r.body
        return r.json()

    def metric(self, series):
        text = self.h.handle("GET", "/metrics").body.decode()
        hit = [ln for ln in text.splitlines() if ln.startswith(series + " ")]
        return float(hit[0].rsplit(" ", 1)[1]) if hit else 0.0


def opened(path):
    holder = Holder(str(path))
    holder.open()
    return holder


@pytest.fixture(scope="module", params=[8, 6], ids=["8slices", "6slices"])
def world(request, tmp_path_factory):
    """One holder, served by a four-device mesh and by a one-device one."""
    slices = request.param
    rows = random_rows(slices, seed=2900 + slices)
    holder = opened(tmp_path_factory.mktemp(f"w{slices}"))
    four, one = Served(holder, 4), Served(holder, 1)
    four.load(rows)
    yield slices, rows, four, one
    holder.close()


@pytest.mark.parametrize("path", ["lone", "batched"])
@pytest.mark.parametrize("arity", [2, ROWS], ids=["2rows", "allrows"])
@pytest.mark.parametrize("op", OPS)
def test_count_equals_numpy_and_the_one_device_mesh(world, op, arity, path):
    slices, rows, four, one = world
    ids = [5, 2] if arity == 2 else list(range(ROWS))
    if path == "batched":
        ids = ids[::-1]  # another text: the lone case's memo does not answer
    want = expected(rows, op, ids)
    assert want > 0
    four.mgr.lone_fused = one.mgr.lone_fused = path == "lone"
    before = four.mgr.stats.copy()
    ici = four.ex.tier_stats.copy().get("mesh|ici", 0)
    got = four.query(pql(op, ids), profiled=True)
    assert got["results"] == [want]
    assert one.query(pql(op, ids))["results"] == [want]
    after = four.mgr.stats.copy()
    # The path that was meant, on the device, reduced as a collective.
    assert after["count"] == before["count"] + 1
    assert (after["lone_fused"] - before["lone_fused"]) \
        == (1 if path == "lone" else 0)
    assert after["fallback"] == before["fallback"]
    assert after.get("fallback_error", 0) == before.get("fallback_error", 0)
    assert four.ex.tier_stats.copy()["mesh|ici"] == ici + 1
    assert "mesh|ici" not in one.ex.tier_stats.copy()
    assert got["profile"]["tags"]["devices"] == 4
    assert "device_exec" in got["profile"]["phases_us"]


def test_gauges_say_what_the_pool_lies_on(world):
    slices, rows, four, one = world
    four.query(pql("Union", [0, 1]))
    one.query(pql("Union", [0, 1]))
    st, st1 = four.mgr.stats.copy(), one.mgr.stats.copy()
    assert (st["devices"], st1["devices"]) == (4, 1)
    words = four.mgr._views[("i", "f", "standard")].sharded.words
    per_device = int(words.nbytes) // 4  # 6 slices are padded to 8
    assert st["shard_bytes_min"] == st["shard_bytes_max"] == per_device
    assert st1["shard_bytes_min"] == st1["shard_bytes_max"] \
        == int(one.mgr._views[("i", "f", "standard")].sharded.words.nbytes)
    v = four.h.handle("GET", "/debug/vars").json()["mesh"]
    assert (v["devices"], v["shard_bytes_min"], v["shard_bytes_max"]) \
        == (4, per_device, per_device)
    assert four.metric("pilosa_mesh_devices") == 4
    assert four.metric("pilosa_mesh_shard_bytes_max") == per_device
    assert four.metric(ICI) >= 1
    assert one.metric(ICI) == 0


def test_a_mesh_smaller_than_its_devices_holds_reads_zero():
    """A pool of one slice on four devices: three hold nothing."""

    class Mgr(serve.MeshManager):
        def __init__(self):  # the gauges need the mesh and the stats only
            self._mesh = default_mesh(4)
            self.stats = serve.StatMap()

    import jax

    mgr = Mgr()
    mgr._note_placement(jax.device_put(np.zeros((1, 4), np.uint32),
                                       mgr.mesh.devices.flat[0]))
    assert mgr.stats.copy() == {"devices": 4, "shard_bytes_min": 0,
                                "shard_bytes_max": 16}


def test_herd_of_reads_and_writes_on_four_devices(world):
    """16 threads, reads of every shape with a SetBit among them: every
    answer exact, every Count on the mesh, no launch left hanging."""
    slices, rows, four, _ = world
    four.mgr.lone_fused = True
    shapes = [(op, ids) for op in OPS
              for ids in ([1, 6], [6, 3], list(range(ROWS))[::-1])]
    last = (slices - 1) * SLICE_WIDTH
    col = last + int(np.flatnonzero(~rows[7][last:])[0])
    wrote = list(rows)
    wrote[7] = rows[7].copy()
    wrote[7][col] = True
    # A read in flight beside the SetBit may or may not see it.
    allowed = {sh: {expected(rows, *sh), expected(wrote, *sh)}
               for sh in ((op, tuple(ids)) for op, ids in shapes)}
    four.query("Count(Bitmap(rowID=7, frame=f))")
    errors, before = [], four.mgr.stats.copy()

    def client(k):
        try:
            for j in range(4):
                op, ids = shapes[(k + j) % len(shapes)]
                got = four.query(pql(op, ids))["results"][0]
                if got not in allowed[(op, tuple(ids))]:
                    errors.append((op, ids, got))
            if k == 5:
                assert four.query(
                    f"SetBit(rowID=7, frame=f, columnID={col})"
                )["results"] == [True]
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(k,)) for k in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    rows[7] = wrote[7]  # the fixture's later users see the bit
    assert four.query("Count(Bitmap(rowID=7, frame=f))")["results"] \
        == [int(rows[7].sum())]
    for op, ids in shapes[-3:]:
        assert four.query(pql(op, ids))["results"] == [expected(rows, op, ids)]
    after = four.mgr.stats.copy()
    for k in ("fallback", "routed_host", "fallback_error", "stage"):
        assert after.get(k, 0) == before.get(k, 0), k


def test_setbit_into_each_shard_is_counted_next_and_survives_reopen(tmp_path):
    rows = random_rows(8, seed=77, rows=2)
    s = Served(opened(tmp_path / "d"), 4)
    s.load(rows)
    text = "Count(Bitmap(rowID=1, frame=f))"
    assert s.query(text)["results"] == [int(rows[1].sum())]
    stages = s.mgr.stats["stage"]
    for sl in (1, 2, 5, 6):  # two slices a device: one in each shard
        col = sl * SLICE_WIDTH + int(np.flatnonzero(
            ~rows[1][sl * SLICE_WIDTH:(sl + 1) * SLICE_WIDTH])[0])
        assert s.query(f"SetBit(rowID=1, frame=f, columnID={col})"
                       )["results"] == [True]
        rows[1][col] = True
        assert s.query(text)["results"] == [int(rows[1].sum())]
    assert s.query(pql("Difference", [1, 0]))["results"] \
        == [expected(rows, "Difference", [1, 0])]
    # Scattered into the shard that holds the slice, not staged again;
    # one client, so no reader held the pool: in place, every time.
    assert s.mgr.stats["stage"] == stages
    assert s.mgr.stats["incremental"] >= 1
    assert s.metric('pilosa_apply_writes_total{mode="in_place"}') \
        == s.mgr.stats["incremental"]
    assert s.metric('pilosa_apply_writes_total{mode="copied"}') == 0
    s.holder.close()
    again = Served(opened(tmp_path / "d"), 4)
    assert again.query(text)["results"] == [int(rows[1].sum())]
    assert again.query(pql("Difference", [1, 0]))["results"] \
        == [expected(rows, "Difference", [1, 0])]
    assert again.mgr.stats["count"] == 2 and again.mgr.stats["devices"] == 4
    again.holder.close()


@pytest.mark.parametrize("path", ["lone", "batched"])
def test_count_above_int32_from_limbs_to_json(tmp_path, monkeypatch, path):
    """Count(Union(all 8 rows)) over 2.01 B columns is ~2.005e9, 7% under
    2^31 - 1; here the device's limbs are fed, past it: lo and hi as the
    psum of 1,920 slices' 16-bit halves gives them, recombined on the host
    into a Python int that reaches the JSON whole."""
    import jax.numpy as jnp

    want = 3_000_000_123
    hi = 45_000
    lo = want - (hi << 16)
    assert 0xFFFF < lo < 1920 * 0xFFFF and want > 2**31 - 1
    limbs = jnp.asarray([lo, hi], dtype=jnp.int32)
    monkeypatch.setattr(
        serve, "compile_serve_count",
        lambda *a, host_meta=False, **k: lambda *args: (
            limbs if host_meta else limbs[:, None]))
    s = Served(opened(tmp_path / "d"), 4)
    s.load(random_rows(2, seed=5, rows=2))
    s.mgr.lone_fused = path == "lone"
    r = s.h.handle("POST", "/index/i/query",
                   body=pql("Union", [0, 1]).encode())
    assert r.status == 200
    assert json.loads(r.body)["results"] == [want]
    assert s.mgr.stats["count"] == 1 and s.mgr.stats["fallback"] == 0
    s.holder.close()
