"""The per-layer metric files that read the served path's phases and
counters (benchmarks/layer_metrics/*.json, data only): each evaluates on a
recorded profile and /debug/vars pair through the benchmark's generic
readers, and gives None, never 0, where the program has no such phase or
counter (the parent commit, which the driver traces with these files too).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from pbench import layers, window  # noqa: E402

from pilosa_tpu.obs import profile  # noqa: E402

BENCHMARK = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

# ?profile=true of one mesh-routed Count, as the program gave it
# (CPU mesh, 32 slices), and of the same Count before the new phases.
PHASES_US = {"parse": 58.1, "plan": 97.9, "route_slices": 177.2,
             "pool_handoff": 75.7, "mesh_prepare": 289.9,
             "mesh_lock_wait": 6.0, "view_refresh": 8.3,
             "device_exec": 4841.9, "readback_d2h": 49.2, "account": 48.0,
             "respond": 14.0}
OLD_PHASES_US = {k: PHASES_US[k] for k in ("parse", "plan", "device_exec",
                                           "readback_d2h")}
# /debug/vars as the window began and ended.
VARS = ({"uptime_seconds": 100.0, "process_cpu_seconds": 60.5,
         "mesh": {"count": 10, "refresh_walks": 3, "refresh_walk_us": 9000,
                  "refresh_walk_fragments": 1930}},
        {"uptime_seconds": 140.0, "process_cpu_seconds": 98.5,
         "mesh": {"count": 110, "refresh_walks": 23,
                  "refresh_walk_us": 89000, "refresh_walk_fragments": 1955}})
OLD_VARS = ({"uptime_seconds": 100.0, "mesh": {"count": 10}},
            {"uptime_seconds": 140.0, "mesh": {"count": 110}})

WANT = {"route.slices_ms": 0.1772, "exec.handoff_ms": 0.0757,
        "exec.account_ms": 0.062, "mesh.lock_wait_ms": 0.006,
        "mesh.prepare_ms": 0.2982, "refresh.walk_ms": 4.0,
        "host.cpu_cores": 0.95, "refresh.fragments_per_walk": 1.25}


def ctx(phases_us, vars_pair):
    log = [window.Done(0, i, "count", 0.0, 0.010, True, (),
                       {"total_us": 5800.0, "phases_us": phases_us})
           for i in range(3)]
    return layers.Context(vars_before=vars_pair[0], vars_after=vars_pair[1],
                          prom_before={}, prom_after={}, log=log, trace=None,
                          device_kind="TPU v5 lite", config={})


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_reads_what_the_program_records(name):
    spec = layers.load_metric(name)
    got = layers.evaluate(spec["value"], ctx(PHASES_US, VARS))
    assert got == pytest.approx(WANT[name])
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert entry["better"] == spec["better"] == "lower"
    assert entry["source"] == spec["source"]
    # Contains, not equals: a later cell whose reads take the same path
    # is listed by appending to the end (seg-2b-x4.herd64, PR 29).
    assert entry["workloads"][:2] == ["seg-1b.lone1", "seg-1b.herd64"]


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_file_gives_none_where_the_program_has_nothing(name):
    spec = layers.load_metric(name)
    assert layers.evaluate(spec["value"], ctx(OLD_PHASES_US, OLD_VARS)) is None
    assert layers.read_all([name], ctx(OLD_PHASES_US, OLD_VARS)) == {}


def test_no_walk_in_the_window_is_none_not_zero():
    same = dict(VARS[1])
    spec = layers.load_metric("refresh.walk_ms")
    assert layers.evaluate(spec["value"], ctx(PHASES_US, (same, same))) is None


def test_no_walk_in_the_window_visits_none_not_zero_fragments():
    same = dict(VARS[1])
    spec = layers.load_metric("refresh.fragments_per_walk")
    assert layers.evaluate(spec["value"], ctx(PHASES_US, (same, same))) is None


def _profile_phases(expr):
    if isinstance(expr, dict):
        yield from expr.get("profile", ())
        for v in expr.values():
            for e in (v if isinstance(v, list) else [v]):
                yield from _profile_phases(e)


def test_every_phase_a_metric_file_reads_is_one_the_program_documents():
    for m in BENCHMARK["per_layer"]:
        spec = layers.load_metric(m["name"])
        for ph in _profile_phases(spec["value"]):
            assert ph in profile.PHASES, (m["name"], ph)
    assert set(WANT) <= {m["name"] for m in BENCHMARK["per_layer"]}


# -- the TopN leg, its passes, the front end's own time, who holds _mu ---------------

# ?profile=true of one mesh-served TopN(frame, n=3) after a write, as the
# program gave it (CPU mesh, 16 slices), and of the same TopN on a program
# without the leg's phases: its lock waits and refresh were all it had.
TOPN_US = {"parse": 3.9, "plan": 34.0, "route_slices": 61.4,
           "pool_handoff": 190.5, "mesh_prepare": 214.6,
           "mesh_lock_wait": 9.9, "view_refresh": 1349.6,
           "device_exec": 1345.3, "readback_d2h": 86.9, "rank": 149.3,
           "account": 105.2, "respond": 23.1}
OLD_TOPN_US = {k: TOPN_US[k] for k in ("parse", "plan", "route_slices",
                                       "pool_handoff", "mesh_lock_wait",
                                       "view_refresh", "respond")}
HOLDERS = ({"total": 5000.0, "topn_args": 3000.0, "memo": 2000.0},
           {"total": 41000.0, "topn_args": 27000.0, "memo": 14000.0})
TOPN_VARS = tuple(
    {"executor": {"topn_queries": q, "topn_passes": 2 * q},
     "http": {"queries": n, "read_us": r, "write_us": w},
     "mesh": {"count": 10, "topn": 2 * q, "mu_held_us": held}}
    for q, n, r, w, held in ((100, 120, 6000.0, 9000.0, HOLDERS[0]),
                             (400, 420, 21000.0, 54000.0, HOLDERS[1])))
OLD_TOPN_VARS = ({"mesh": {"count": 10, "topn": 200}},
                 {"mesh": {"count": 10, "topn": 800}})
TOPN_WANT = {"topn.prepare_ms": 1.5741, "topn.wait_ms": 1.4322,
             "topn.rank_ms": 0.1493, "topn.passes_per_query": 2.0,
             "http.server_ms": 0.2, "mesh.lock_held_ms": 0.06}
# What each reads on the parent's pair: it has the lock waits and the
# refresh, and none of the rest.
TOPN_PARENT = dict.fromkeys(TOPN_WANT, None)
TOPN_PARENT["topn.prepare_ms"] = 1.3595


@pytest.mark.parametrize("name", sorted(TOPN_WANT))
def test_topn_and_front_end_metric_files_read_the_program(name):
    spec = layers.load_metric(name)
    got = layers.evaluate(spec["value"], ctx(TOPN_US, TOPN_VARS))
    assert got == pytest.approx(TOPN_WANT[name])
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    assert (entry["better"], entry["source"]) == \
        (spec["better"], spec["source"]) == ("lower", spec["source"])
    assert "startrace-500m.lone1" not in entry["workloads"]
    assert {"topn-1b.lone1", "topn-ingest-1b.lone1"} <= \
        set(entry["workloads"])
    old = layers.evaluate(spec["value"], ctx(OLD_TOPN_US, OLD_TOPN_VARS))
    assert old == pytest.approx(TOPN_PARENT[name]) \
        if TOPN_PARENT[name] is not None else old is None


def test_no_query_in_the_window_is_none_not_zero():
    same = (TOPN_VARS[1], TOPN_VARS[1])
    for name in ("topn.passes_per_query", "http.server_ms",
                 "mesh.lock_held_ms"):
        spec = layers.load_metric(name)
        assert layers.evaluate(spec["value"], ctx(TOPN_US, same)) is None
