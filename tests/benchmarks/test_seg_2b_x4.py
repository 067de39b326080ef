"""Configuration `seg-2b-x4` and its cell `seg-2b-x4.herd64` (PR 29): found
by name with no file of the harness edited; `seg-1b` but for the keys that
make it the four-chip deployment; its three per-layer metrics (data files
over the generic readers) on what the program's own /debug/vars and /metrics
say on a four-device and on a one-device mesh, and left out, never 0, where
the program records nothing (the parent commit); and a rehearsal of the whole
cell on four CPU devices, sound and with a guarantee broken.
"""

import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import harness, layers, server  # noqa: E402

BENCHMARK = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELL = "seg-2b-x4.herd64"
NEW = {"stage.shard_balance": 100.0, "route.ici_share": 100.0,
       "mesh.devices": 4.0}
# What makes it the four-chip deployment; everything else is seg-1b's.
DIFFER = {"name", "what", "source", "source_quoted", "slices", "columns",
          "staged_bytes", "server_toml", "layout", "reduced", "assumed"}


def config(name):
    return json.load(open(os.path.join(BENCH, "configs", name + ".json")))


def test_cell_is_found_by_name_with_its_configuration_toml_and_traffic():
    got = harness.load_cell(CELL)
    assert got["cell"]["chips"] == 4 and got["config"]["name"] == "seg-2b-x4"
    assert got["traffic"] == json.load(open(os.path.join(
        BENCH, "traffic", "ycsb-b-herd64.json")))
    toml = os.path.join(got["config_dir"], got["config"]["server_toml"])
    assert harness._pinned_backend(toml) == "xla"
    listed = {m["name"] for m in got["per_layer"]}
    assert set(NEW) <= listed
    # One client's roofline readers divide by one chip's peak: not here.
    assert not {m for m in listed if m.endswith("_roofline")}
    herd = {m["name"] for m in harness.load_cell("seg-1b.herd64")["per_layer"]}
    # tests/benchmarks/test_route_decisions_metric.py:94 pins that metric's
    # cells, and a model_config PR edits no file the benchmark has.
    assert listed == (herd - {"route.decisions_per_slice"}) | set(NEW)
    assert {m["name"] for m in got["end_to_end"]} == \
        {"ops_per_s", "read_p50_ms", "write_visible_ms", "setup_s"}


def test_configuration_is_seg_1b_but_for_the_four_chip_keys():
    cfg, base = config("seg-2b-x4"), config("seg-1b")
    assert set(cfg) == set(base)
    assert {k for k in cfg if cfg[k] != base[k]} == DIFFER
    assert cfg["slices"] == 2 * base["slices"] == 1920
    assert cfg["columns"] == cfg["slices"] << 20 == 2_013_265_920
    rows = cfg["frame"]["rows"]
    assert cfg["staged_bytes"] == cfg["slices"] * rows * 16 * 8192 \
        == 2_013_265_920
    assert cfg["layout"]["chips"] == 4 and cfg["layout"]["nodes"] == 1
    assert cfg["slices"] // cfg["layout"]["chips"] == 480
    assert set(cfg["reduced"]) == {"rows"} and "slices" in cfg["assumed"]
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == "seg-2b-x4")
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    baseline = json.load(open(os.path.join(REPO, "BASELINE.json")))
    quoted = cfg["source_quoted"]
    assert quoted["BASELINE.json configs[4]"] == baseline["configs"][4]
    assert quoted["BASELINE.json north_star"] == baseline["north_star"]
    assert "reductions carried over ICI" in baseline["north_star"]


def test_at_most_half_of_the_cells_ask_for_four_chips():
    chips = [w["chips"] for w in BENCHMARK["workloads"]]
    assert chips.count(4) == 1
    assert chips.count(4) <= max(1, len(chips) // 2)
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"]) == ("seg-2b-x4", "ycsb-b-herd64")


# -- the three metrics, on what the program itself says ------------------------


def served(tmp_path, devices):
    """An in-process node whose Counts run on a mesh of `devices` devices,
    over 4 slices of random words (dense, as the configuration's frame)."""
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.api import Handler
    from pilosa_tpu.core import Holder
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.parallel import new_test_cluster
    from pilosa_tpu.parallel.mesh import default_mesh
    from pilosa_tpu.parallel.serve import MeshManager

    holder = Holder(str(tmp_path / f"data{devices}"))
    holder.open()
    cluster = new_test_cluster(1)
    host = cluster.nodes[0].host
    ex = Executor(holder, host=host, cluster=cluster, use_device=True,
                  device_min_work=0)
    ex._mesh_mgr = MeshManager(holder, mesh=default_mesh(devices))
    h = Handler(holder, ex, cluster=cluster, host=host)
    assert h.handle("POST", "/index/i").status == 200
    assert h.handle("POST", "/index/i/frame/f").status == 200
    rng = np.random.default_rng(29)
    frame = holder.index("i").frame("f")
    for row in (0, 1):
        cols = np.flatnonzero(rng.random(4 * SLICE_WIDTH) < 0.5)
        frame.import_bits(np.full(cols.size, row, dtype=np.int64), cols)
    return holder, h


def scrape(h):
    """(/debug/vars, /metrics) as the harness reads them."""
    text = h.handle("GET", "/metrics").body
    return (h.handle("GET", "/debug/vars").json(),
            server.Server.metrics(types.SimpleNamespace(http=lambda _p: text)))


def window(tmp_path, devices):
    """Set-up (the staging query), then a window of three Counts, the last
    of them a repeat that the whole-query memo answers."""
    holder, h = served(tmp_path, devices)
    try:
        def count(text):
            r = h.handle("POST", "/index/i/query", body=text.encode())
            assert r.status == 200, r.body

        count("Count(Bitmap(rowID=0, frame=f))")
        before = scrape(h)
        for text in ("Count(Union(Bitmap(rowID=0, frame=f), "
                     "Bitmap(rowID=1, frame=f)))",
                     "Count(Bitmap(rowID=1, frame=f))",
                     "Count(Bitmap(rowID=1, frame=f))"):
            count(text)
        after = scrape(h)
    finally:
        holder.close()
    return layers.Context(
        vars_before=before[0], vars_after=after[0], prom_before=before[1],
        prom_after=after[1], log=[], trace=None, device_kind="cpu", config={})


def without(ctx):
    """The same window on a program that lacks what PR 29 adds."""
    def strip(v):
        mesh = {k: x for k, x in v["mesh"].items()
                if k not in ("devices", "shard_bytes_min", "shard_bytes_max")}
        return dict(v, mesh=mesh)

    def untiered(series):
        return {k: x for k, x in series.items()
                if not k.startswith("pilosa_query_route_total")}

    return layers.Context(
        vars_before=strip(ctx.vars_before), vars_after=strip(ctx.vars_after),
        prom_before=untiered(ctx.prom_before),
        prom_after=untiered(ctx.prom_after), log=[], trace=None,
        device_kind="cpu", config={})


def test_metric_files_read_four_devices_one_device_and_nothing(tmp_path):
    four, one = window(tmp_path, 4), window(tmp_path, 1)
    # Two of the window's three Counts ran on the mesh; the memo's is
    # recorded under its own backend and is in neither term.
    mesh_served = layers.evaluate(
        {"prom": "pilosa_query_route_total", "match": 'backend="mesh"'}, four)
    assert mesh_served == 2
    assert layers.evaluate(
        {"prom": "pilosa_query_route_total", "match": 'backend="memo"',
         "at": "end"}, four) == 1
    got = layers.read_all(sorted(NEW), four)
    assert {k: v["value"] for k, v in got.items()} == NEW
    for name in NEW:
        entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
        spec = layers.load_metric(name)
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"]) == \
            (spec["unit"], spec["better"], spec["source"]) == \
            (got[name]["unit"], "higher", "program_counter")
    # A server that came up on one chip: the tripwire reads 1, and the
    # ici series does not exist, so that share is not in the line (which a
    # check of the cell refuses as loudly as a 0).
    assert {k: v["value"] for k, v in
            layers.read_all(sorted(NEW), one).items()} == \
        {"stage.shard_balance": 100.0, "mesh.devices": 1.0}
    # The parent: nothing to read, so nothing in the line. Not 0.
    for name in NEW:
        assert layers.evaluate(layers.load_metric(name)["value"],
                               without(four)) is None
    assert layers.read_all(sorted(NEW), without(four)) == {}


# -- the whole cell, rehearsed on four CPU devices ------------------------------

FOUR_CPUS = {"JAX_PLATFORMS": "cpu", "PILOSA_TPU_DEVICE_MIN_WORK": "0",
             "PILOSA_TPU_CPU_ROUTE_NATIVE": "off",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def test_rehearsal_on_four_cpu_devices_is_correct_and_says_so(tmp_path,
                                                              monkeypatch):
    """The real server, 64 clients, 8 slices sharded two a device, traced so
    that the line carries the per-layer metrics: every answer right, every
    acknowledged write on the disk, every Count a collective on the mesh."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    out = harness.run_cell(CELL, 2_900_000_029, 2.0, True, require_chip=False,
                           slices=8, server_env=dict(FOUR_CPUS))
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 4
    assert out["correct"] is True and out["failed"] == 0
    cmp_ = out["compared"]
    assert cmp_["wrong_answers"]["value"] == cmp_["unanswered"]["value"] == 0
    assert cmp_["lost_writes"]["value"] == 0 and cmp_["lost_writes"]["of"] >= 1
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {k: m[k] for k in NEW} == NEW
    assert m["route.device_share"] == 100.0
    assert m["refresh.restages_in_window"] == 0
    assert m["batch.queries_per_launch"] >= 1.0
    assert "count_reads_roofline" not in m


def test_rehearsal_with_stale_writes_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    out = harness.run_cell(CELL, 2_900_000_031, 1.0, False,
                           require_chip=False, slices=8,
                           control="stale_writes")
    assert out["correct"] is False and out["control"] == "stale_writes"
    assert out["compared"]["wrong_answers"]["value"] > 0
