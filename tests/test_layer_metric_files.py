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
         "mesh": {"count": 10, "refresh_walks": 3, "refresh_walk_us": 9000}},
        {"uptime_seconds": 140.0, "process_cpu_seconds": 98.5,
         "mesh": {"count": 110, "refresh_walks": 23,
                  "refresh_walk_us": 89000}})
OLD_VARS = ({"uptime_seconds": 100.0, "mesh": {"count": 10}},
            {"uptime_seconds": 140.0, "mesh": {"count": 110}})

WANT = {"route.slices_ms": 0.1772, "exec.handoff_ms": 0.0757,
        "exec.account_ms": 0.062, "mesh.lock_wait_ms": 0.006,
        "mesh.prepare_ms": 0.2982, "refresh.walk_ms": 4.0,
        "host.cpu_cores": 0.95}


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
